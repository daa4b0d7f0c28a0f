"""A strided residual backbone (Darknet-53's pattern) on the fleet reuse
path: the stage kernel against its oracle, cold heads against the plain
reference the benchmark uses (``bench/references/darknet53.py``), warm
steps at threshold 0 bit-identical to a full recompute when a change
reaches the third tile ring, the rings and FLOPs counted from the
program's layer list, and the district walk's launches kept as they
were."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleet.runtime import fleet_reuse_step
from repro.kernels import ops, ref
from repro.serving.detector import (DetectorConfig, PackedActivationCache,
                                    RoIDetector)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import layers as ly  # noqa: E402
from references import darknet53  # noqa: E402

# Darknet-53's pattern at small widths: stem 8, stages 16/32/64 of one
# block each, 3 anchors of 5 + 2 classes
SMALL = {"stem": 8, "stages": [[16, 1], [32, 1], [64, 1]],
         "num_anchors": 3, "num_classes": 2}


def _published():
    with open(os.path.join(BENCH, "configs",
                           "district4_darknet53.json")) as f:
        return json.load(f)["detector"]


def _cfg(detector, **kw):
    d = dict(detector, **kw)
    return DetectorConfig(**dict(d, stages=tuple(map(tuple, d["stages"]))))


def _det(tile, seed=3, **kw):
    detector = dict(SMALL, tile=tile, **kw)
    params = darknet53.init(jax.random.PRNGKey(seed), detector)
    return RoIDetector(_cfg(detector), params=params), params


def _stage_params(rng, cin, cout, nb):
    def arr(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)
    cm = cout // 2
    down = (arr(3, 3, cin, cout, scale=1 / np.sqrt(9 * cin)),
            arr(cout, scale=0.1))
    blocks = [(arr(1, 1, cout, cm, scale=1 / np.sqrt(cout)),
               arr(cm, scale=0.1),
               arr(3, 3, cm, cout, scale=1 / np.sqrt(9 * cm)),
               arr(cout, scale=0.1)) for _ in range(nb)]
    return down, blocks


# ---------------------------------------------------------------------------
# the stage kernel alone
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t_in,cin,cout,nb,shape,block", [
    (8, 8, 16, 1, (3, 4), 2),       # 4x4 output tiles, ragged blocks
    (4, 16, 32, 2, (3, 4), 3),      # two blocks: both ping-pong buffers
    (2, 8, 16, 1, (4, 4), 4),       # 1-px output tiles
    (32, 8, 16, 1, (2, 3), 2),      # the stem's 32-px tiles
])
def test_stage_kernel_matches_its_oracle(t_in, cin, cout, nb, shape, block):
    """``roi_conv_stage`` == ``ref.roi_conv_stage`` (scatter, SAME convs
    masked to the active tiles at each stride, gather) on a ragged mask
    with inactive neighbors and frame edges on every side."""
    rng = np.random.default_rng(t_in + nb)
    grid = rng.random(shape) < 0.6
    grid[0, 0] = grid[-1, -1] = True
    idx = ops.mask_to_indices(grid)
    nbr = jnp.asarray(ops.neighbor_table(idx, grid.shape))
    x = jnp.asarray(np.maximum(rng.normal(size=(idx.shape[0], t_in, t_in,
                                                cin)), 0), jnp.float32)
    down, blocks = _stage_params(rng, cin, cout, nb)
    got = np.asarray(ops.roi_conv_stage(x, down, blocks, nbr, block=block))
    want = np.asarray(ref.roi_conv_stage(x, jnp.asarray(idx), grid.shape,
                                         down, blocks))
    assert got.shape == (idx.shape[0], t_in // 2, t_in // 2, cout)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


# ---------------------------------------------------------------------------
# the layer list: rings, receptive field, FLOPs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["district4", "darknet53"])
def test_program_rings_equal_the_harness_rings(name):
    """The program's receptive field and rings, from its own layer list,
    equal what the benchmark's harness counts from the reference's:
    ``district4`` 3 px, 1 ring at 16-px tiles; Darknet-53 through
    stride 8 82 px, 3 rings at 32-px tiles."""
    if name == "district4":
        cat = json.load(open(os.path.join(BENCH, "configs",
                                          "district4.json")))["detector"]
        from references import roi_detector
        theirs = roi_detector.layers(cat)
        cfg = DetectorConfig(**dict(cat, channels=tuple(cat["channels"])))
        rf, rings = 3, 1
    else:
        cat = _published()
        theirs = darknet53.layers(cat)
        cfg = _cfg(cat)
        rf, rings = 82, 3
    mine = cfg.layers()
    assert ops.rf_px(mine) == ly.rf_px(theirs) == rf
    assert ops.halo_rings(mine, cfg.tile) == ly.rings(theirs, cfg.tile) \
        == rings
    strip = [{k: v for k, v in layer.items() if k != "role"}
             for layer in theirs]
    assert mine == strip


def test_flops_and_conv_count_come_from_the_layer_list():
    """``flops`` and ``num_conv_layers`` count each layer at its own
    stride: 7664 FLOPs a pixel and 3 convs for the district's net;
    144,056 and 26 for Darknet-53 through stride 8."""
    d4 = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    assert d4.flops(1, 1) == 7664 and d4.num_conv_layers == 3
    cfg = _cfg(_published())
    dn = RoIDetector(cfg, params={"convs": [], "biases": [],
                                  "head": jnp.zeros((256, 255)),
                                  "head_bias": jnp.zeros((255,))})
    assert dn.flops(1, 1) == 144056 and dn.num_conv_layers == 26
    assert dn.flops(544, 960, 0.5) == 544 * 960 * 0.5 * 144056
    assert dn.chain_dispatches == {"roi_conv_entry": 1, "roi_conv_stage": 3}


# ---------------------------------------------------------------------------
# the fleet reuse path
# ---------------------------------------------------------------------------

def test_cold_heads_equal_the_plain_reference():
    """Two cameras of 64x96 px at 32-px tiles through ``fleet_reuse_step``
    (cold): each camera's (8, 12, 21) stride-8 head map equals
    ``references/darknet53.forward`` within 2e-6 of its largest
    magnitude (the kernels sum their taps in another order than XLA's
    convolution)."""
    det, params = _det(32)
    rng = np.random.default_rng(0)
    grids = {0: [np.array([[1, 1, 0], [1, 1, 1]], bool),
                 np.array([[0, 1, 1], [1, 1, 0]], bool)]}
    frames = [rng.standard_normal((64, 96, 3)).astype(np.float32)
              for _ in range(2)]
    cache = PackedActivationCache()
    outs, counts, st = fleet_reuse_step(
        det, {0: [jnp.asarray(f) for f in frames]}, grids, cache)
    assert st.cold and dict(counts) == {"roi_conv_entry": 1,
                                        "roi_conv_stage": 3,
                                        "sbnet_scatter_fleet": 1}
    assert cache.packed.shape == (9, 4, 4, 64)
    assert st.canvas_bytes == 9 * 4 * 4 * 21 * 4
    for cam, f in enumerate(frames):
        mask = darknet53.pixel_mask(grids[0][cam], 32, f.shape)
        want = np.asarray(darknet53.forward(params, f, mask))
        got = np.asarray(outs[0][cam])
        assert got.shape == want.shape == (8, 12, 21)
        assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max()


def _ring_trace(det):
    """Threshold-0 reuse steps of a 16x16-tile camera (8-px tiles, and a
    second camera beside it) in which one 2x2-px patch moves one pixel a
    step inside tile (8, 8), off its edges (so no neighbor's haloed
    window sees it).  Returns per warm step the number of head elements
    differing from a full recompute, the head pixels (one a tile) that
    moved, and the stats."""
    rng = np.random.default_rng(16)
    t = det.cfg.tile
    grids = {0: [np.ones((16, 16), bool), rng.random((4, 5)) < 0.7]}
    base = [rng.normal(size=(g.shape[0] * t, g.shape[1] * t, 3)
                       ).astype(np.float32) for g in grids[0]]
    cache = PackedActivationCache()
    out, prev = [], None
    for step in range(4):
        cur = [f.copy() for f in base]
        y, x = 8 * t + 2, 8 * t + 2 + step
        cur[0][y:y + 2, x:x + 2] += 5.0
        frames = {0: [jnp.asarray(f) for f in cur]}
        outs, _, st = fleet_reuse_step(det, frames, grids, cache)
        full, _ = det.superlaunch_forward_reuse(frames, grids,
                                                PackedActivationCache(), 0.0)
        heads = np.asarray(outs[0][0])
        if step:
            moved = np.nonzero((heads != prev).any(axis=2))
            mism = sum(int((np.asarray(a) != np.asarray(b)).sum())
                       for a, b in zip(outs[0], full[0]))
            out.append((mism, moved, st))
        prev = heads
    return out


@pytest.fixture(scope="module")
def ring_det():
    """Darknet-53's pattern on 8-px tiles: its head reaches 21 px beyond
    the entry, 3 rings of 8-px tiles (a stride-8 map is 1 px a tile)."""
    det, _ = _det(8, vmem_budget_bytes=1 << 20)
    assert ops.halo_rings(det.layers, 8) == 3
    return det


def test_reuse_threshold0_bitwise_when_changes_reach_the_third_ring(
        ring_det):
    """Every warm step's heads equal a full recompute bit for bit, and
    some step's change moves a head pixel three tiles from the changed
    tile: the third ring is needed and served."""
    trace = _ring_trace(ring_det)
    assert [m for m, _, _ in trace] == [0, 0, 0]
    reach = 0
    for _, (ys, xs), st in trace:
        assert 0 < st.changed_out < st.computed < st.total_tiles
        assert st.launched >= st.computed
        reach = max(reach, int(np.max(np.maximum(np.abs(ys - 8),
                                                 np.abs(xs - 8)))))
    assert reach == 3


def test_two_rings_are_too_few(ring_det, monkeypatch):
    """Negative control: one ring fewer than ``halo_rings`` leaves heads
    stale or zero-halo-corrupted, and the trace above sees it."""
    orig = ops.halo_rings
    monkeypatch.setattr(ops, "halo_rings",
                        lambda layers, tile: orig(layers, tile) - 1)
    assert sum(m for m, _, _ in _ring_trace(ring_det)) > 0


def test_district_walk_launches_the_rows_it_did():
    """On a fixed walk of the district's detector (three 3x3 layers,
    16-px tiles, three cameras in two groups) the rows
    ``fleet_forward_reuse`` plans each step are the ones it planned when
    its rings came from the layer count, and it launches them padded to
    their quarter-octave bucket (``ops.launch_rows``): (raw changed,
    changed out, computed, launched) per step."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    grids = {0: [rng.random((6, 9)) < 0.7, rng.random((5, 7)) < 0.8],
             1: [rng.random((6, 8)) < 0.6]}
    frames = {g: [rng.standard_normal((a.shape[0] * 16, a.shape[1] * 16, 3)
                                      ).astype(np.float32) for a in gs]
              for g, gs in grids.items()}
    cache = PackedActivationCache()
    rows = []
    for step in range(8):
        if step:
            y, x = 5 * step, 11 * step
            frames[0][0][y:y + 20, x:x + 20] = rng.standard_normal(
                (20, 20, 3))
            if step % 3 == 0:
                frames[1][0][40:60, 100 - 9 * step:120 - 9 * step] = \
                    rng.standard_normal((20, 20, 3))
        _, _, st = fleet_reuse_step(
            det, {g: [jnp.asarray(f) for f in fs]
                  for g, fs in frames.items()}, grids, cache)
        rows.append((st.raw_changed, st.changed_out, st.computed,
                     st.launched))
    assert rows == [(96, 96, 96, 96), (3, 6, 12, 12), (2, 9, 16, 16),
                    (8, 21, 40, 40), (6, 16, 28, 28), (4, 14, 24, 24),
                    (9, 26, 47, 48), (6, 17, 31, 32)]


def test_a_backbone_refuses_what_it_cannot_run():
    """Frames that are not whole tiles, the paths that run the plain
    chain only and the sharded step are refused for a backbone, not run
    wrong."""
    from repro.fleet.sharded import ShardedSuperlaunch
    from repro.launch.mesh import make_fleet_mesh
    det, _ = _det(32)
    grids = [np.ones((2, 2), bool)]
    with pytest.raises(ValueError):
        det.fleet_forward_reuse([jnp.zeros((60, 64, 3))], grids,
                                PackedActivationCache())
    with pytest.raises(ValueError):
        det.fleet_forward([jnp.zeros((64, 64, 3))], grids)
    for path in (det.dense_forward, det.roi_forward):
        with pytest.raises(ValueError):
            path(jnp.zeros((64, 64, 3)), grids[0])
    with pytest.raises(ValueError):
        ShardedSuperlaunch(det, {0: grids}, make_fleet_mesh(1))
    with pytest.raises(ValueError):
        RoIDetector(_cfg(dict(SMALL, tile=4)), jax.random.PRNGKey(0))
