"""The yardstick's arithmetic: kernel work against hand counts, the
scene-motion generator, the device check and the trace reduction."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from harness import runner, trace as tr
from harness.catalog import Catalog
from generators.scene_motion import Generator, dilate, pingpong
from tiny import REPO, tiny_scene

DIMS = {"tile": 16, "cin": 3, "channels": [8, 16, 16], "heads": 10}
STEP = {"n_active": 100, "useful": 10}
MOTION = {"generator": "scene_motion", "span": [0, 4], "patches": 64}


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


def _motion(seed, span=(0, 4)):
    return Generator([tiny_scene()], dict(MOTION, span=list(span)), 0.5,
                     16, seed)


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
            n_useful += int((dilate(boxes) & act).sum())
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
