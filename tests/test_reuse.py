"""Temporal delta-gated inference: the reuse gate kernel, changed-set
dilation, compact super-launches, the persistent packed-activation cache,
and the blocked entry/scatter walks.

The contract everywhere is BIT-identity with full recompute at threshold
0: the reuse path changes which tiles are convolved, never the math of
any tile whose value is used.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleet import fleet_reuse_step, sharded_fleet_step
from repro.fleet.sharded import ShardedSuperlaunch
from repro.kernels import ops, ref
from repro.launch.mesh import make_fleet_mesh
from repro.serving.detector import (DetectorConfig, PackedActivationCache,
                                    RoIDetector)


def _rng(seed=0):
    return np.random.default_rng(seed)


def _fleet_pack(rng, shapes, density=0.5):
    grids = [rng.random(s) < density for s in shapes]
    for g in grids:
        g[min(1, g.shape[0] - 1), min(1, g.shape[1] - 1)] = True
    idx, _ = ops.fleet_indices(grids)
    nbr = ops.fleet_neighbor_table(grids)
    return grids, idx, nbr


# ---------------------------------------------------------------------------
# the gate kernel: bit-exact window + body pricing in one dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qstep", [8.0, 2.0, 16.0])
def test_tile_delta_gate_bit_exact_vs_reference(qstep):
    """The served gate, ``tile_delta_gate_canvas`` with the previous
    frame as its reference canvas, equals ``ref.tile_delta_gate`` bit
    for bit, at one tile and at four tiles a grid step."""
    rng = _rng(1)
    th = tw = 8
    grids, idx, _ = _fleet_pack(rng, [(4, 5), (3, 3)])
    cur = rng.normal(size=(2, 4 * th, 5 * tw, 3)).astype(np.float32)
    prev = cur + (rng.random(cur.shape) < 0.02) * \
        rng.normal(size=cur.shape).astype(np.float32) * 20
    prev = prev.astype(np.float32)
    cur_p = ops.pad_frames(jnp.asarray(cur), tw)
    ref_c = ops.pad_frames(jnp.asarray(prev), tw)
    expect = ref.tile_delta_gate(cur, prev, idx, th, tw, qstep=qstep)
    for block in (1, 4):
        stats = ops.tile_delta_gate_canvas(cur_p, ref_c, jnp.asarray(idx),
                                           th, tw, qstep=qstep, block=block)
        np.testing.assert_array_equal(np.asarray(stats), expect)


def test_tile_delta_gate_body_cols_match_tile_delta():
    """Cols 0..3 of the gate stats equal ``tile_delta`` on the unpadded
    per-camera frame — the rate controller can threshold the shared
    dispatch with unchanged semantics."""
    rng = _rng(2)
    th = tw = 8
    grids, idx, _ = _fleet_pack(rng, [(3, 4), (4, 3)])
    cur = rng.normal(size=(2, 4 * th, 4 * tw, 3)).astype(np.float32)
    prev = (cur + rng.normal(size=cur.shape) * 5).astype(np.float32)
    gate = ref.tile_delta_gate(cur, prev, idx, th, tw)
    for c, g in enumerate(grids):
        ii = ops.mask_to_indices(g)
        body = ref.tile_delta(cur[c], prev[c], ii, th, tw)
        np.testing.assert_array_equal(gate[idx[:, 0] == c][:, :4],
                                      body[:, :4])


def test_tile_delta_gate_sees_inactive_neighbor_halo_change():
    """A pixel flip in an INACTIVE tile adjacent to an active tile must
    register through the active tile's haloed window — the body view
    alone would miss it and the entry conv would serve a stale tile."""
    th = tw = 8
    grid = np.zeros((3, 3), bool)
    grid[1, 1] = True                      # single active tile
    idx, _ = ops.fleet_indices([grid])
    cur = np.zeros((1, 3 * th, 3 * tw, 2), np.float32)
    prev = cur.copy()
    prev[0, th - 1, tw + 3, 0] = 7.0       # inactive N tile, bottom row
    out = ops.tile_delta_gate_canvas(ops.pad_frames(jnp.asarray(cur), tw),
                                     ops.pad_frames(jnp.asarray(prev), tw),
                                     jnp.asarray(idx), th, tw)
    out = np.asarray(out)
    np.testing.assert_array_equal(out,
                                  ref.tile_delta_gate(cur, prev, idx, th, tw))
    assert out[0, ops.GATE_WIN_EXACT] == 1     # window sees it
    assert out[0, 1] == 0                      # body nnz does not


# ---------------------------------------------------------------------------
# changed-set dilation + compaction
# ---------------------------------------------------------------------------

def test_dilate_changed_matches_grid_morphology():
    """Neighbor-table dilation == 3x3 morphological dilation on the tile
    grid, restricted to active tiles (the only tiles that exist)."""
    rng = _rng(3)
    grid = rng.random((9, 11)) < 0.6
    grid[4, 5] = True
    idx = ops.mask_to_indices(grid)
    nbr = ops.neighbor_table(idx, grid.shape)
    raw = rng.random(idx.shape[0]) < 0.1
    got = ops.dilate_changed(raw, nbr)
    g = np.zeros(grid.shape, bool)
    g[idx[raw][:, 0], idx[raw][:, 1]] = True
    gp = np.pad(g, 1)
    dil = np.zeros_like(g)
    for dy in (0, 1, 2):
        for dx in (0, 1, 2):
            dil |= gp[dy:dy + g.shape[0], dx:dx + g.shape[1]]
    np.testing.assert_array_equal(got, dil[idx[:, 0], idx[:, 1]])


@pytest.mark.parametrize("n_layers,tile,rings",
                         [(1, 16, 0), (3, 16, 1), (3, 8, 1), (6, 4, 2)])
def test_reuse_sets_growth_and_nesting(n_layers, tile, rings):
    """changed = raw grown by the receptive field's tile rings, compute =
    changed grown by as many again: a one-layer net needs none (the entry
    reads the frame), a stack whose n_layers - 1 px stay inside a tile
    needs one, a deeper one more.  The rings come from the layer list of
    a chain of ``n_layers`` 3x3 convs."""
    layers = DetectorConfig(tile=tile, channels=(4,) * n_layers).layers()
    assert ops.rf_px(layers) == n_layers
    assert ops.halo_rings(layers, tile) == rings
    rng = _rng(4)
    grid = rng.random((10, 10)) < 0.7
    grid[5, 5] = True
    idx = ops.mask_to_indices(grid)
    nbr = ops.neighbor_table(idx, grid.shape)
    raw = np.zeros(idx.shape[0], bool)
    raw[np.nonzero((idx[:, 0] == 5) & (idx[:, 1] == 5))[0]] = True
    changed, compute = ops.reuse_sets(raw, nbr, rings)
    assert (raw <= changed).all() and (changed <= compute).all()
    d = raw
    for _ in range(rings):
        d = ops.dilate_changed(d, nbr)
    np.testing.assert_array_equal(changed, d)
    for _ in range(rings):
        d = ops.dilate_changed(d, nbr)
    np.testing.assert_array_equal(compute, d)


def test_compact_tables_remap_and_zero_halo():
    rng = _rng(5)
    grids, idx, nbr = _fleet_pack(rng, [(4, 4), (3, 5)])
    n = idx.shape[0]
    keep = rng.random(n) < 0.5
    keep[0] = True
    cidx, cnbr = ops.compact_tables(idx, nbr, keep)
    k = int(keep.sum())
    assert cidx.shape == (k, 3) and cnbr.shape == (k, 8)
    np.testing.assert_array_equal(cidx, idx[keep])
    kept_slots = np.nonzero(keep)[0]
    for r, slot in enumerate(kept_slots):
        for j in range(8):
            src = nbr[slot, j]
            if src < 0 or not keep[src]:
                assert cnbr[r, j] == -1      # dropped donor -> zero halo
            else:
                assert kept_slots[cnbr[r, j]] == src


@pytest.mark.parametrize("n", [1, 100, 3791, 15164])
def test_launch_rows_quarter_octave_buckets(n):
    """``launch_rows(k, n)`` for every k from 1 to 5000: at least k and
    at most max(k, n), under 1.25 k from 8 rows up, non-decreasing, at
    most four buckets an octave, a multiple of 64 from 512 up (or the
    cap n), and the backbone cell's compute sets 2237-2537 all in 2560."""
    ks = np.arange(1, 5001)
    rows = np.array([ops.launch_rows(int(k), n) for k in ks])
    assert (rows >= ks).all() and (rows <= np.maximum(ks, n)).all()
    assert (rows[ks >= 8] < 1.25 * ks[ks >= 8]).all()
    assert (np.diff(rows) >= 0).all()
    under = ks <= n
    octave = np.ceil(np.log2(ks)).astype(int)
    for o in np.unique(octave[under]):
        assert len(set(rows[under & (octave == o)])) <= 4
    big = under & (ks >= 512) & (rows != n)
    assert (rows[big] % 64 == 0).all()
    if n >= 2537:
        assert {ops.launch_rows(k, n) for k in (2237, 2400, 2537)} == {2560}
    assert ops.launch_rows(5, n) == min(8, max(5, n))


@pytest.mark.parametrize("kind", ["chain", "backbone"])
def test_reuse_threshold0_bitwise_at_a_quarter_octave_bucket(kind):
    """A patch moving inside tile (1, 2) of one camera grows to a compute
    set of 20 tiles (the chain: 1 ring, 4x5 tiles against the frame's
    corner) or 72 (a small Darknet-53 pattern on 8-px tiles: 3 rings,
    8x9 tiles): warm steps launch 20 = 5*4 and 80 = 5*16 rows, buckets
    that are not powers of two, and their heads equal a full recompute
    bit for bit."""
    if kind == "chain":
        det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
        shape, computed, launched = (6, 8), 20, 20
    else:
        det = RoIDetector(DetectorConfig(
            tile=8, stem=8, stages=((16, 1), (32, 1), (64, 1)),
            num_anchors=3, num_classes=2, vmem_budget_bytes=1 << 20),
            jax.random.PRNGKey(3))
        shape, computed, launched = (9, 10), 72, 80
    t = det.cfg.tile
    grids = {0: [np.ones(shape, bool)]}
    base = _rng(17).normal(size=(shape[0] * t, shape[1] * t, 3)
                           ).astype(np.float32)
    cache = PackedActivationCache()
    for step in range(3):
        cur = base.copy()
        y, x = t + t // 2 - 1, 2 * t + t // 2 - 1 + (step % 2)
        cur[y:y + 2, x:x + 2] += 5.0
        frames = {0: [jnp.asarray(cur)]}
        outs, _, st = fleet_reuse_step(det, frames, grids, cache)
        if not step:
            continue
        assert (st.raw_changed, st.computed, st.launched) == \
            (1, computed, launched)
        assert launched == ops.launch_rows(computed, st.total_tiles)
        full, _ = det.superlaunch_forward_reuse(
            frames, grids, PackedActivationCache(), 0.0)
        np.testing.assert_array_equal(np.asarray(outs[0][0]),
                                      np.asarray(full[0][0]))


# ---------------------------------------------------------------------------
# choose_block: VMEM-budgeted tile-block sizing
# ---------------------------------------------------------------------------

def test_choose_block_default_budget_and_floors():
    # the 24 MiB default (3/4 of the kernels' scoped-VMEM limit) fits 8
    # lane-padded YOLO-lite tiles at ~2.4 MB each; half the budget, half
    # the block
    assert ops.choose_block(16, 16, 16, 3) == 8
    assert ops.choose_block(16, 16, 16, 3, vmem_bytes=12 << 20) == 4
    assert ops.choose_block(16, 16, 16, 3, vmem_bytes=1024) == 1
    # small tiles hit the window-operand cap
    assert ops.choose_block(8, 8, 6, 2) == ops.MAX_WINDOWS_PER_STEP
    last = 0
    for mb in (1, 2, 4, 8, 16, 32):
        b = ops.choose_block(16, 16, 16, 3, vmem_bytes=mb << 20)
        assert b >= max(last, 1)
        last = b
    # channels occupy whole 128-lane vregs: only past 128 does a wider
    # layer shrink the block
    assert ops.choose_block(16, 16, 64, 3) == ops.choose_block(16, 16, 8, 3)
    assert ops.choose_block(16, 16, 256, 3) < ops.choose_block(16, 16, 8, 3)
    # detector wires it through
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    assert det.block == ops.choose_block(16, 16, 16, 3)
    det_small = RoIDetector(DetectorConfig(vmem_budget_bytes=1 << 20),
                            jax.random.PRNGKey(0))
    assert 1 <= det_small.block < det.block


# ---------------------------------------------------------------------------
# blocked entry: bit-identical to the per-tile walk; scatter padding
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [2, 3, 16, 256])
def test_blocked_entry_bitwise_vs_per_tile(block):
    rng = _rng(6)
    th = tw = 8
    grids, idx, _ = _fleet_pack(rng, [(4, 5), (3, 3)])
    x = jnp.asarray(rng.normal(size=(2, 4 * th, 5 * tw, 3)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 3, 6)) * 0.3, jnp.float32)
    base = ops.roi_conv_entry(x, w, jnp.asarray(idx), th, tw, block=1)
    out = ops.roi_conv_entry(x, w, jnp.asarray(idx), th, tw, block=block)
    assert (np.asarray(out) == np.asarray(base)).all()


@pytest.mark.parametrize("pad", [1, 5, 64])
def test_scatter_repeat_last_padding_is_idempotent(pad):
    """The repeat-last padding contract the reuse path's pow-2 buckets
    rely on: duplicate stores rewrite identical bytes, never corrupt a
    neighbor, and every real tile lands at its (cam, ty, tx)."""
    rng = _rng(7)
    th = tw = 8
    grids, idx, _ = _fleet_pack(rng, [(4, 5), (3, 3)])
    n = idx.shape[0]
    packed = rng.normal(size=(n, th, tw, 6)).astype(np.float32)
    base = rng.normal(size=(2, 4 * th, 5 * tw, 6)).astype(np.float32)
    expect = base.copy()
    for (cam, ty, tx), tile in zip(idx, packed):
        expect[cam, ty * th:(ty + 1) * th, tx * tw:(tx + 1) * tw] = tile
    idx_p = np.concatenate([idx, np.repeat(idx[-1:], pad, axis=0)])
    packed_p = np.concatenate([packed, np.repeat(packed[-1:], pad, axis=0)])
    out = ops.sbnet_scatter_fleet(jnp.asarray(packed_p),
                                  jnp.asarray(idx_p), jnp.asarray(base))
    np.testing.assert_array_equal(np.asarray(out), expect)


# ---------------------------------------------------------------------------
# the delta-gated fleet step: bit-identity, dispatch structure, leaks
# ---------------------------------------------------------------------------

def _mk_fleet(rng, det, group_shapes, density=0.5):
    t = det.cfg.tile
    frames, grids = {}, {}
    for gid, shapes in enumerate(group_shapes):
        grids[gid] = [rng.random(s) < density for s in shapes]
        for g in grids[gid]:
            g[min(1, g.shape[0] - 1), min(1, g.shape[1] - 1)] = True
        frames[gid] = [np.asarray(rng.normal(size=(gy * t, gx * t, 3)),
                                  np.float32) for gy, gx in shapes]
    return frames, grids


def _as_jnp(frames):
    return {g: [jnp.asarray(f) for f in fs] for g, fs in frames.items()}


def test_reuse_threshold0_bitwise_on_ragged_fleet_trace():
    """The acceptance contract: over a trace of sparse changes on a
    ragged multi-group fleet, every step's outputs are bit-identical to
    ``fleet_forward`` full recompute, while convolving only the
    dilated changed sets."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(8)
    frames, grids = _mk_fleet(rng, det,
                              [[(4, 5), (3, 4)], [(2, 3)], [(5, 3),
                                                            (3, 3)]])
    grids[1][0][:] = False
    grids[1][0][0, 0] = True               # single-tile group
    cache = PackedActivationCache()
    cur = frames
    computed = []
    for step in range(5):
        outs, counts, st = fleet_reuse_step(det, _as_jnp(cur), grids,
                                            cache)
        for gid in grids:
            legacy = det.fleet_forward(
                [jnp.asarray(f) for f in cur[gid]], grids[gid])
            for a, b in zip(outs[gid], legacy):
                assert (np.asarray(a) == np.asarray(b)).all(), \
                    f"step {step} group {gid} diverged from full recompute"
        computed.append(st.computed)
        # next frame: flip a couple of pixels in one camera of one group
        cur = {g: [f.copy() for f in fs] for g, fs in cur.items()}
        gid = int(rng.integers(len(grids)))
        cam = int(rng.integers(len(cur[gid])))
        f = cur[gid][cam]
        f[int(rng.integers(f.shape[0])), int(rng.integers(f.shape[1])),
          :] += 9.0
    assert st.total_tiles > 0
    assert computed[0] == st.total_tiles       # cold step = full
    assert all(c < st.total_tiles for c in computed[1:]), computed
    assert cache.compute_fraction < 1.0


def test_all_static_frame_dispatches_gate_only():
    """Zero-copy static step: the persistent canvas is served as-is —
    the gate is the ONLY launch and not one canvas byte is written."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(9)
    frames, grids = _mk_fleet(rng, det, [[(3, 4), (4, 3)]])
    cache = PackedActivationCache()
    fleet_reuse_step(det, _as_jnp(frames), grids, cache)   # cold seed
    outs, counts, st = fleet_reuse_step(det, _as_jnp(frames), grids,
                                        cache)
    assert st.computed == 0 and st.raw_changed == 0
    assert dict(counts) == {"tile_delta_gate": 1}
    assert st.canvas_bytes == 0 and cache.canvas_bytes_last == 0
    # and a third static step stays that way
    outs, counts, st = fleet_reuse_step(det, _as_jnp(frames), grids,
                                        cache)
    assert dict(counts) == {"tile_delta_gate": 1}
    assert st.canvas_bytes == 0


def test_dilation_never_leaks_across_cameras_or_groups():
    """A changed tile on a camera's edge must not pull any other
    camera's tiles into the compute set (the neighbor table has no
    cross-camera slots), and outputs stay bit-exact everywhere."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(1))
    rng = _rng(10)
    t = det.cfg.tile
    # two groups; every tile active so adjacency would leak if it could
    frames, grids = _mk_fleet(rng, det, [[(3, 4), (3, 4)], [(4, 3)]],
                              density=2.0)
    cache = PackedActivationCache()
    fleet_reuse_step(det, _as_jnp(frames), grids, cache)
    # flip a pixel in camera 0's bottom-right corner tile (grid edge)
    cur = {g: [f.copy() for f in fs] for g, fs in frames.items()}
    cur[0][0][3 * t - 1, 4 * t - 1, 0] += 11.0
    outs, counts, st = fleet_reuse_step(det, _as_jnp(cur), grids, cache)
    assert st.computed > 0
    # the compute set stayed inside flat camera 0
    n0 = int(np.count_nonzero(grids[0][0]))
    assert st.computed <= n0, "dilation leaked past the changed camera"
    for gid in grids:
        legacy = det.fleet_forward(
            [jnp.asarray(f) for f in cur[gid]], grids[gid])
        for a, b in zip(outs[gid], legacy):
            assert (np.asarray(a) == np.asarray(b)).all()


def test_reuse_positive_threshold_reuses_more():
    """A lossy threshold can only shrink the compute set; the gate stats
    stay available for the rate controller either way."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(11)
    frames, grids = _mk_fleet(rng, det, [[(4, 5)]])
    small = {0: [frames[0][0] + (rng.random(frames[0][0].shape) < 0.001
                                 ).astype(np.float32) * 0.5]}
    cache0 = PackedActivationCache()
    fleet_reuse_step(det, _as_jnp(frames), grids, cache0)
    _, _, st0 = fleet_reuse_step(det, _as_jnp(small), grids, cache0,
                                 threshold=0.0)
    cache1 = PackedActivationCache()
    fleet_reuse_step(det, _as_jnp(frames), grids, cache1)
    _, _, st1 = fleet_reuse_step(det, _as_jnp(small), grids, cache1,
                                 threshold=10 ** 6)
    assert st1.computed <= st0.computed
    assert st1.computed == 0                   # huge threshold: all reused
    assert st0.gate_stats is not None and st1.gate_stats is not None


def test_gate_stats_shared_with_rate_controller_single_dispatch():
    """The satellite contract: one delta dispatch per step serves both
    the reuse gate and the encoder's static-tile calibration — no
    ``tile_delta`` launch rides along."""
    from repro.net import static_fraction_from_stats, tile_static_fraction
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(12)
    t = det.cfg.tile
    frames, grids = _mk_fleet(rng, det, [[(3, 4), (4, 4)]])
    cache = PackedActivationCache()
    fleet_reuse_step(det, _as_jnp(frames), grids, cache)
    cur = {0: [f.copy() for f in frames[0]]}
    cur[0][0][5, 5, :] += 30.0
    with ops.count_kernels() as c:
        outs, counts, st = fleet_reuse_step(det, _as_jnp(cur), grids,
                                            cache)
        frac = static_fraction_from_stats(st.gate_stats, 3, t)
        # per-camera slices work too (fleet packing is camera-major)
        idx = cache.idx_np
        frac0 = static_fraction_from_stats(st.gate_stats[idx[:, 0] == 0],
                                           3, t)
    assert c["tile_delta_gate"] == 1
    assert c.get("tile_delta", 0) == 0
    assert 0.0 <= frac0 <= 1.0 and frac > 0.5  # mostly-static frame
    # the stats= passthrough of tile_static_fraction skips the kernel
    with ops.count_kernels() as c2:
        f2 = tile_static_fraction(np.asarray(cur[0][0]),
                                  np.asarray(frames[0][0]), grids[0][0],
                                  t, stats=st.gate_stats[idx[:, 0] == 0])
    assert sum(c2.values()) == 0 and f2 == frac0


# ---------------------------------------------------------------------------
# a receptive field wider than a tile: the margin takes two rings
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def deep_det():
    """Six 3x3 layers on 4-px tiles: the five packed layers walk a change
    5 px, past a whole tile, so ``halo_rings`` is 2.  4 px is the
    smallest tile the kernels run bit-exactly in interpret mode (at 3 px
    the last bits already depend on the launch's row count).  The 1-MiB
    VMEM budget holds the block at 2 tiles, so the gate compiles in
    seconds."""
    det = RoIDetector(DetectorConfig(tile=4, channels=(4,) * 6,
                                     vmem_budget_bytes=1 << 20),
                      jax.random.PRNGKey(0))
    assert ops.halo_rings(det.layers, 4) == 2
    return det


def _deep_trace_mismatches(det, path):
    """Threshold-0 reuse steps over a 2x2-px patch that moves one pixel
    diagonally per step across a tile corner of a 12x14-tile camera (a
    second, static camera beside it).  Returns, per step, the number of
    head elements that differ from a full recompute (a cold step
    through a fresh cache), and checks that every warm step convolved a
    margin around its changed set and no more than part of the fleet."""
    rng = _rng(16)
    t = det.cfg.tile
    grids = {0: [np.ones((12, 14), bool), rng.random((4, 5)) < 0.7]}
    base = [rng.normal(size=(g.shape[0] * t, g.shape[1] * t, 3)
                       ).astype(np.float32) for g in grids[0]]
    if path == "sharded":
        rt = ShardedSuperlaunch(det, grids, make_fleet_mesh(1))
        cache = rt.make_cache()
    else:
        cache = PackedActivationCache()
    mismatches = []
    for step in range(4):
        cur = [f.copy() for f in base]
        y, x = 6 * t - 1 + step, 7 * t - 1 + step
        cur[0][y:y + 2, x:x + 2] += 5.0
        frames = {0: cur}
        if path == "sharded":
            got, _, st = sharded_fleet_step(rt, frames, cache, 0.0)
        else:
            outs, _, st = fleet_reuse_step(det, _as_jnp(frames), grids,
                                           cache)
            got = {0: [np.asarray(o) for o in outs[0]]}
        full, _ = det.superlaunch_forward_reuse(
            _as_jnp(frames), grids, PackedActivationCache(), 0.0)
        if step:
            assert 0 < st.changed_out < st.computed < st.total_tiles, st
        mismatches.append(sum(
            int((np.asarray(a) != np.asarray(b)).sum())
            for a, b in zip(got[0], full[0])))
    return mismatches


@pytest.mark.parametrize("path", ["single", "sharded"])
def test_reuse_threshold0_bitwise_when_field_spans_two_rings(deep_det,
                                                             path):
    """With ``halo_rings`` == 2 every warm step's heads equal a full
    recompute bit for bit, on the single-device and the sharded path."""
    assert _deep_trace_mismatches(deep_det, path) == [0, 0, 0, 0]


@pytest.mark.parametrize("path", ["single", "sharded"])
def test_one_ring_short_margin_is_seen(deep_det, monkeypatch, path):
    """Negative control: one ring fewer than ``halo_rings`` leaves
    changed tiles stale or zero-halo-corrupted, and the trace above
    sees it — so the test can tell an under-sized margin."""
    orig = ops.halo_rings
    monkeypatch.setattr(ops, "halo_rings",
                        lambda layers, tile: orig(layers, tile) - 1)
    assert sum(_deep_trace_mismatches(deep_det, path)[1:]) > 0


# ---------------------------------------------------------------------------
# cache lifecycle: ring bound, invalidation, drift re-solve
# ---------------------------------------------------------------------------

def test_cache_invalidate_recomputes_and_reference_advances():
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(13)
    frames, grids = _mk_fleet(rng, det, [[(3, 3)]])
    cache = PackedActivationCache()
    for _ in range(4):
        fleet_reuse_step(det, _as_jnp(frames), grids, cache)
    assert cache.cold_steps == 1 and cache.ref_canvas is not None
    cache.invalidate()
    assert cache.packed is None and cache.invalidations == 1
    assert cache.ref_canvas is None and cache.canvas is None
    _, counts, st = fleet_reuse_step(det, _as_jnp(frames), grids, cache)
    assert st.cold and st.computed == st.total_tiles
    assert counts.get("tile_delta_gate", 0) == 0


def test_lossy_threshold_drift_accumulates_against_reference():
    """Under a lossy threshold the gate's reference only advances at
    refreshed tiles, so sub-threshold per-step drift ACCUMULATES and
    eventually trips the gate — it cannot creep into the cache
    unboundedly one sub-threshold step at a time."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(16)
    frames, grids = _mk_fleet(rng, det, [[(3, 3)]])
    thr = 40.0                                  # bytes, lossy gate
    cache = PackedActivationCache()
    fleet_reuse_step(det, _as_jnp(frames), grids, cache, threshold=thr)
    cur = frames
    tripped = 0
    for step in range(12):
        # one tile drifts a little every step; each single-step delta
        # prices under the threshold, the accumulated delta does not
        cur = {0: [cur[0][0].copy()]}
        cur[0][0][20:24, 20:24, :] += 2.0
        _, _, st = fleet_reuse_step(det, _as_jnp(cur), grids, cache,
                                    threshold=thr)
        tripped += st.raw_changed
    assert tripped >= 1, \
        "accumulated sub-threshold drift never tripped the lossy gate"


def test_mask_change_misses_content_key():
    """A changed grid (what a drift re-solve produces) must force a full
    recompute even without an explicit invalidate call."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(14)
    frames, grids = _mk_fleet(rng, det, [[(3, 4)]])
    cache = PackedActivationCache()
    fleet_reuse_step(det, _as_jnp(frames), grids, cache)
    _, _, st = fleet_reuse_step(det, _as_jnp(frames), grids, cache)
    assert not st.cold
    grown = {0: [grids[0][0].copy()]}
    grown[0][0][0, 3] = not grown[0][0][0, 3]
    _, _, st = fleet_reuse_step(det, _as_jnp(frames), grown, cache)
    assert st.cold and st.computed == st.total_tiles


def test_drift_resolve_invalidates_cache_and_next_step_recomputes():
    """The drift adapter's mask listeners invalidate registered caches on
    every re-solve, so the step after a mask mutation recomputes fully
    (belt and braces on top of the content key, and countable)."""
    from repro.core.pipeline import OfflineConfig, run_offline
    from repro.core.scene import SceneConfig, generate_scene
    from repro.fleet.drift import DriftAdapter
    scene = generate_scene(SceneConfig(duration_s=25, seed=5))
    off = run_offline(scene, OfflineConfig(profile_frames=150,
                                           solver="greedy"))
    adapter = DriftAdapter(scene, off)
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    cache = PackedActivationCache()
    adapter.add_mask_listener(lambda _: cache.invalidate())
    # the cache serves a (small, synthetic) fleet; the adapter maintains
    # the masks — the listener is the coupling under test
    rng = _rng(15)
    frames, grids = _mk_fleet(rng, det, [[(3, 3), (3, 4)]])
    fleet_reuse_step(det, _as_jnp(frames), grids, cache)
    _, _, st = fleet_reuse_step(det, _as_jnp(frames), grids, cache)
    assert not st.cold
    # a warm re-solve (empty residual window here: the mask itself does
    # not grow, but cam_grids are regenerated) must notify the listeners
    adapter._resolve(t=999)
    assert len(adapter.events) == 1
    assert cache.invalidations == 1 and cache.packed is None
    _, _, st = fleet_reuse_step(det, _as_jnp(frames), grids, cache)
    assert st.cold and st.computed == st.total_tiles
