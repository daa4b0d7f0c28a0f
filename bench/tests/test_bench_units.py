"""The yardstick's arithmetic: kernel work against hand counts, the
layer-list counts against the formulas they replaced, the scene-motion
generator, the device check and the trace reduction."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import strided_res
from harness import layers as ly, runner, trace as tr
from harness.catalog import Catalog
from generators.scene_motion import Generator, dilate, pingpong
from tiny import REPO, tiny_scene

REF = Catalog(REPO).module("references", "roi_detector")
DETECTOR = {"channels": [8, 16, 16], "tile": 16, "num_anchors": 2}
DIMS = {"tile": 16, "cin": 3, "channels": [8, 16, 16], "heads": 10,
        "layers": REF.layers(DETECTOR)}
STEP = {"n_active": 100, "useful": 10}
MOTION = {"generator": "scene_motion", "span": [0, 4], "patches": 64}
WORK = ("tile_delta_gate", "roi_conv_entry", "roi_conv_stack",
        "sbnet_scatter_changed")


@pytest.mark.parametrize("kernel,flops,nbytes", [
    # gate: 100 windows of 18*18*3 values, frame and reference, 2 ops
    # each; bytes 4 * (2 * 100 * 972 + 8 * 100)
    ("tile_delta_gate", 2 * 100 * 972, 4 * (2 * 100 * 972 + 800)),
    # entry: 10 tiles * 256 px * 2 * 27 * 8 FLOPs; windows in, 8-ch out
    ("roi_conv_entry", 10 * 256 * 2 * 27 * 8,
     4 * (10 * (972 + 256 * 8) + 27 * 8)),
    # stack: 8->16 and 16->16 convs; 8-ch in, 16-ch out per tile
    ("roi_conv_stack", 10 * 256 * 2 * 9 * (8 * 16 + 16 * 16),
     4 * (10 * 256 * (8 + 16) + 9 * (8 * 16 + 16 * 16))),
    # scatter: 10 tiles of 256 px * 10 heads, read and written
    ("sbnet_scatter_changed", 0, 4 * 2 * 10 * 256 * 10),
])
def test_work_hand_counts(kernel, flops, nbytes):
    work = Catalog(REPO).module("work", kernel)
    assert work.work(STEP, DIMS) == (flops, nbytes)


def test_mfu_counts_the_detector_flops_per_pixel():
    mfu = Catalog(REPO).module("metrics", "mfu.step")
    ctx = SimpleNamespace(dims=DIMS, steps=[STEP, STEP], window_s=2.0,
                          chips=1, peak={"bf16_flops_per_s": 1e12})
    # RoIDetector.flops per pixel: 2*9*(3*8 + 8*16 + 16*16) + 2*16*10
    per_px = 2 * 9 * (24 + 128 + 256) + 320
    assert per_px == 7664
    assert mfu.read(ctx) == pytest.approx(100 * 20 * 256 * per_px / 2e12)


# strided_res's layers at 16-px tiles: the stem at stride 1 (256 px a
# tile), the rest at stride 2 (64 px a tile)
STRIDED = {"tile": 16, "layers": strided_res.layers(
    {"widths": [8, 16], "num_anchors": 2})}


@pytest.mark.parametrize("kernel,flops,nbytes", [
    ("tile_delta_gate", 2 * 100 * 972, 4 * (2 * 100 * 972 + 800)),
    # stem: the 18x18 window in, 8 channels out at stride 1
    ("roi_conv_entry", 10 * 256 * 2 * 27 * 8,
     4 * (10 * (972 + 256 * 8) + 27 * 8)),
    # down (3x3) and proj (1x1) at stride 2, their sum adds no FLOPs;
    # the stem's 8 channels in at stride 1, the sum's 16 out at stride 2
    ("roi_conv_stack", 10 * 64 * 2 * (9 * 8 * 16 + 8 * 16),
     4 * (10 * (256 * 8 + 64 * 16) + 9 * 8 * 16 + 8 * 16)),
    # the head's 10 channels at stride 2, read and written
    ("sbnet_scatter_changed", 0, 4 * 2 * 10 * 64 * 10),
])
def test_work_counts_each_layer_at_its_stride(kernel, flops, nbytes):
    work = Catalog(REPO).module("work", kernel)
    assert work.work(STEP, STRIDED) == (flops, nbytes)


def test_mfu_counts_each_layer_at_its_stride():
    mfu = Catalog(REPO).module("metrics", "mfu.step")
    ctx = SimpleNamespace(dims=STRIDED, steps=[STEP], window_s=1.0,
                          chips=1, peak={"bf16_flops_per_s": 1e12})
    per_tile = (256 * 2 * 27 * 8 + 64 * 2 * 9 * 8 * 16 + 64 * 2 * 8 * 16
                + 64 * 2 * 9 * 16 * 10)
    assert mfu.read(ctx) == pytest.approx(100 * 10 * per_tile / 1e12)


@pytest.mark.parametrize("layers,rf,tile,rings", [
    # three 3x3 stride-1 convs and a 1x1 head: 1 px each
    (REF.layers(DETECTOR), 3, 16, 1),
    # stem 1 | 1; down 1 | 2 (pads 0 before, 1 after at stride 2);
    # proj 1 | 0; head 3x3 at stride 2: 1 + 2 | 2 + 2
    (STRIDED["layers"], 4, 16, 1),
    (STRIDED["layers"], 4, 2, 2),
])
def test_receptive_field_and_rings(layers, rf, tile, rings):
    ly.check(layers)
    assert ly.rf_px(layers) == rf
    assert ly.rings(layers, tile) == rings


@pytest.mark.parametrize("edit", [
    lambda ls: ls[1].update(stride_in=2),        # stride_in off its input
    lambda ls: ls[3].update(inputs=["stem", "down"]),  # add across strides
    lambda ls: ls[2].update(inputs=["head"]),    # a later layer as input
    lambda ls: ls.pop(),                         # no head
])
def test_layer_list_check_refuses(edit):
    layers = strided_res.layers({"widths": [8, 16], "num_anchors": 2})
    edit(layers)
    with pytest.raises(ValueError):
        ly.check(layers)


# the counts as they stood before the layer list, for the one detector
# they described: three 3x3 stride-1 convs (3 -> channels) and a 1x1 head
def _old_dilate(m):
    p = np.pad(m, 1)
    out = np.zeros_like(m)
    h, w = m.shape
    for dy in (0, 1, 2):
        for dx in (0, 1, 2):
            out |= p[dy:dy + h, dx:dx + w]
    return out


def _old_work(kernel, step, dims):
    u, n, t, cin, ch, a = step["useful"], step["n_active"], dims["tile"], \
        dims["cin"], list(dims["channels"]), dims["heads"]
    if kernel == "tile_delta_gate":
        win = (t + 2) ** 2 * cin
        return 2 * n * win, 4 * (2 * n * win + 8 * n)
    if kernel == "roi_conv_entry":
        c1 = ch[0]
        return (u * t * t * 2 * 9 * cin * c1,
                4 * (u * ((t + 2) ** 2 * cin + t * t * c1) + 9 * cin * c1))
    if kernel == "roi_conv_stack":
        pairs = list(zip(ch[:-1], ch[1:]))
        return (u * t * t * sum(2 * 9 * a * b for a, b in pairs),
                4 * (u * t * t * (ch[0] + ch[-1])
                     + sum(9 * a * b for a, b in pairs)))
    return 0.0, 4.0 * 2 * u * t * t * a


def _old_mfu(ctx):
    d = ctx.dims
    chans = [d["cin"]] + list(d["channels"])
    per_px = sum(2 * 9 * a * b for a, b in zip(chans[:-1], chans[1:]))
    per_px += 2 * chans[-1] * d["heads"]
    flops = sum(s["useful"] for s in ctx.steps) * d["tile"] ** 2 * per_px
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])


def _walk(name):
    """(generator, dims) of one whole walk period: the tiny scene under
    the tiny cells' traffic, or district4's recorded scenes under
    ``scene_motion``, each at its configuration's detector."""
    cat = Catalog(REPO)
    if name == "tiny":
        scenes, traffic, scale = [tiny_scene()], MOTION, 0.5
        detector = DETECTOR
    else:
        cfg = cat.config(name)
        scenes = [cat.scene(g["scene"]) for g in cfg["groups"]]
        traffic, scale = cat.traffic("scene_motion"), cfg["scale"]
        detector = cfg["detector"]
    layers = REF.layers(detector)
    tile = detector["tile"]
    gen = Generator(scenes, traffic, scale, tile, 11, ly.rings(layers, tile))
    dims = {"tile": tile, "cin": 3, "channels": list(detector["channels"]),
            "heads": detector["num_anchors"] * 5, "layers": layers}
    return gen, dims


@pytest.mark.parametrize("name", ["tiny", "district4"])
def test_layer_counts_equal_the_old_formulas(name):
    gen, dims = _walk(name)
    cat = Catalog(REPO)
    mfu = cat.module("metrics", "mfu.step")
    peak = {"bf16_flops_per_s": 197e12}
    for j in range(gen.period):
        tiles, changed, useful = gen.transition(j)
        old_useful = 0
        for (ys, xs), act in zip(tiles, gen._active):
            ch = np.zeros_like(act)
            ch[ys, xs] = True
            old_useful += int((_old_dilate(ch) & act).sum())
        assert useful == old_useful
        step = {"useful": useful, "n_active": gen.n_active}
        for kernel in WORK:
            assert cat.module("work", kernel).work(step, dims) == \
                _old_work(kernel, step, dims)
        ctx = SimpleNamespace(dims=dims, steps=[step, step], window_s=0.37,
                              chips=1, peak=peak)
        assert mfu.read(ctx) == _old_mfu(ctx)


def _motion(seed, span=(0, 4)):
    return Generator([tiny_scene()], dict(MOTION, span=list(span)), 0.5,
                     16, seed, 1)


def test_scene_motion_is_deterministic_per_seed():
    a, b, c = _motion(5), _motion(5), _motion(6)
    for _ in range(9):
        # the seed moves the pixels, never the sizes of the work
        assert a.advance() == b.advance() == c.advance()
    for fa, fb, fc in zip(a.frames[0], b.frames[0], c.frames[0]):
        assert np.array_equal(fa, fb)
        assert not np.array_equal(fa, fc)


def test_changed_set_is_the_box_tiles_and_the_rest_is_static():
    m = _motion(7, span=(0, 6))
    sc = tiny_scene()
    for _ in range(25):
        before = m.snapshot()[0]
        changed, useful = m.advance()
        pos = pingpong(m.step, 6)
        prev = pingpong(m.step - 1, 6)
        n_changed = n_useful = 0
        for cam, (f0, f1) in enumerate(zip(before, m.frames[0])):
            diff = (f0 != f1).any(axis=2)
            tiles = diff.reshape(diff.shape[0] // 16, 16,
                                 diff.shape[1] // 16, 16).any(axis=(1, 3))
            boxes = np.zeros_like(tiles)
            for f, c, x0, y0, x1, y1 in sc["boxes"]:
                if c == cam and f in (pos, prev):
                    boxes[y0 // 32:(y1 - 1) // 32 + 1,
                          x0 // 32:(x1 - 1) // 32 + 1] = True
            assert np.array_equal(tiles, boxes)
            act = m.grids[0][cam]
            n_changed += int((boxes & act).sum())
            n_useful += int((dilate(boxes, 1) & act).sum())
        assert (changed, useful) == (n_changed, n_useful)


def test_pingpong_never_ends():
    n = 5
    walk = [pingpong(i, n) for i in range(-20, 1000)]
    assert min(walk) == 0 and max(walk) == n - 1
    assert all(abs(a - b) == 1 for a, b in zip(walk, walk[1:]))


def test_every_transition_repeats_each_period():
    m = _motion(3)
    one = [m.transition(m.step + i)[1:] for i in range(m.period)]
    two = [m.transition(m.step + m.period + i)[1:] for i in range(m.period)]
    assert one == two


def _fake_jax(platform, kind, n):
    dev = SimpleNamespace(platform=platform, device_kind=kind)
    return SimpleNamespace(devices=lambda: [dev] * n)


@pytest.mark.parametrize("platform,kind,n,chips", [
    ("cpu", "cpu", 1, 1),
    ("tpu", "TPU v9 ultra", 1, 1),       # no peaks for this kind
    ("tpu", "TPU v5 lite", 1, 4),        # fewer chips than the cell asks
])
def test_device_check_refuses(platform, kind, n, chips):
    peaks = Catalog(REPO).peaks()
    with pytest.raises(runner.Refused):
        runner.chip_devices(_fake_jax(platform, kind, n), chips, peaks)


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "district4.mixed", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "refused" in p.stderr


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_trace_reduction_on_a_chip_trace():
    """A trace of six steps of one intersection (5 cameras,
    ``uniform_s0`` under ``scene_motion``) on one TPU v5e, cut down to
    the device's ``XLA Ops`` line and the benchmark's spans (50 kB of the
    35 MB the profiler wrote): the reduction gives what it gave on the
    chip from the whole trace."""
    with open(os.path.join(DATA, "trace_expect.json")) as f:
        expect = json.load(f)
    spans, ops = tr.read_events(os.path.join(DATA, "intersection.xplane.pb"))
    red = tr.reduce(spans, ops, [0])
    assert red["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    cat = Catalog(REPO)
    for kernel, seconds in expect["kernel_s"].items():
        names = cat.module("work", kernel).TRACE_NAMES
        assert tr.op_seconds(red["per_op_s"], names) == pytest.approx(
            seconds, rel=1e-9)
        assert seconds > 0
    assert {n for n, _ in red["gaps"]} <= set(tr.SPANS) | {"between_spans"}


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6)]
    assert tr._union_length(iv) == 4
    assert tr._gaps(iv, 0, 8) == [(3, 5), (6, 8)]
