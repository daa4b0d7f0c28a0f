"""One-launch fleet backbone: the fused layer-stack megakernel, coalesced
rim halos, the cross-group super-launch, and the per-grid digest cache.

The contract everywhere is BIT-identity with successive one-layer
launches and per-group ``fleet_forward``: the fused path changes the
dispatch structure, never the math; the detector paths are held to the
plain ``jax.numpy`` reference ``dense_forward``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleet.runtime import fleet_inference_step
from repro.kernels import ops, ref
from repro.serving.detector import DetectorConfig, RoIDetector


def _rng(seed=0):
    return np.random.default_rng(seed)


def _mk_group(rng, shapes, t, ensure=True):
    grids = [rng.random(s) < 0.45 for s in shapes]
    if ensure:
        for g in grids:
            g[min(1, g.shape[0] - 1), min(1, g.shape[1] - 1)] = True
    frames = [jnp.asarray(rng.normal(size=(gy * t, gx * t, 3)),
                          jnp.float32) for gy, gx in shapes]
    return frames, grids


# ---------------------------------------------------------------------------
# the megakernel alone: bitwise vs one launch per layer
# ---------------------------------------------------------------------------

def _layer_by_layer(packed, ws, nbr, block=16):
    """The chain as successive one-layer ``roi_conv_stack`` launches."""
    for w in ws:
        packed = ops.roi_conv_stack(packed, [w], nbr, block=block)
    return packed


@pytest.mark.parametrize("chans", [(3, 4, 6, 6, 5), (3, 8), (3, 5, 7)])
def test_stack_kernel_bitwise_vs_per_layer_chain(chans):
    """One ``roi_conv_stack`` launch == successive one-layer launches,
    bit for bit, including ragged channel widths across layers; the
    entry kernel's blocked walk == its one-tile walk."""
    rng = _rng(1)
    th = tw = 8
    grids = [rng.random((4, 5)) < 0.5, rng.random((3, 3)) < 0.4]
    grids[0][1, 1] = True
    grids[1][:] = False
    grids[1][2, 2] = True                  # isolated single-tile camera
    idx, _ = ops.fleet_indices(grids)
    nbr = jnp.asarray(ops.fleet_neighbor_table(grids))
    idx = jnp.asarray(idx)
    x = jnp.asarray(rng.normal(size=(2, 4 * th, 5 * tw, 3)), jnp.float32)
    ws = [jnp.asarray(rng.normal(size=(3, 3, ci, co)) * 0.3, jnp.float32)
          for ci, co in zip(chans[:-1], chans[1:])]

    p0 = ops.roi_conv_entry(x, ws[0], idx, th, tw, block=1)
    p4 = ops.roi_conv_entry(x, ws[0], idx, th, tw, block=4)
    assert (np.asarray(p4) == np.asarray(p0)).all(), \
        "the entry kernel's blocked walk must equal its one-tile walk"
    if len(ws) == 1:
        return
    fused = ops.roi_conv_stack(p0, ws[1:], nbr)
    legacy = _layer_by_layer(p0, ws[1:], nbr)
    assert (np.asarray(fused) == np.asarray(legacy)).all(), \
        "megakernel must be bit-identical to one launch per layer"


def test_stack_matches_scatter_conv_oracle():
    """Each megakernel layer equals the scatter-into-zeros oracle
    (``ref.roi_conv_packed``): inactive and off-frame neighbors read as a
    zero halo, whatever row the kernel fetched them from."""
    rng = _rng(2)
    th = tw = 8
    grid = rng.random((4, 6)) < 0.5
    grid[2, 2] = True
    idx = ops.mask_to_indices(grid)
    nbr = jnp.asarray(ops.neighbor_table(idx, grid.shape))
    n = idx.shape[0]
    packed = jax.nn.relu(
        jnp.asarray(rng.normal(size=(n, th, tw, 4)), jnp.float32))
    ws = [jnp.asarray(rng.normal(size=(3, 3, 4, 6)) * 0.3, jnp.float32),
          jnp.asarray(rng.normal(size=(3, 3, 6, 5)) * 0.3, jnp.float32)]
    fused = ops.roi_conv_stack(packed, ws, nbr, block=4)
    expect = packed
    for w in ws:
        expect = jax.nn.relu(ref.roi_conv_packed(expect, jnp.asarray(idx),
                                                 grid.shape, w))
    np.testing.assert_allclose(np.asarray(fused), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("block", [1, 3, 16, 256])
def test_stack_block_raggedness_bitwise(block):
    """Any tile-block size (including non-dividing and over-sized ones)
    keeps the megakernel bit-identical to one launch per layer."""
    rng = _rng(3)
    th = tw = 8
    grid = rng.random((5, 7)) < 0.45
    grid[2, 3] = True
    idx = ops.mask_to_indices(grid)
    nbr = jnp.asarray(ops.neighbor_table(idx, grid.shape))
    n = idx.shape[0]
    packed = jax.nn.relu(
        jnp.asarray(rng.normal(size=(n, th, tw, 4)), jnp.float32))
    ws = [jnp.asarray(rng.normal(size=(3, 3, 4, 6)) * 0.2, jnp.float32),
          jnp.asarray(rng.normal(size=(3, 3, 6, 5)) * 0.2, jnp.float32)]
    fused = ops.roi_conv_stack(packed, ws, nbr, block=block)
    legacy = _layer_by_layer(packed, ws, nbr)
    assert (np.asarray(fused) == np.asarray(legacy)).all()


# ---------------------------------------------------------------------------
# detector paths: fused == the plain jax.numpy reference == per-camera
# ---------------------------------------------------------------------------

# float32 tolerance of the fused chain against ``dense_forward``: both
# sum the same products in float32, in different orders
DENSE_TOL = 1e-5


def test_roi_forward_bitwise_vs_per_layer_path():
    """``roi_forward`` equals ``dense_forward(x, grid)`` on every pixel,
    the zero outside the mask included."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(4)
    t = det.cfg.tile
    grid = rng.random((5, 6)) < 0.5
    grid[2, 2] = True
    x = jnp.asarray(rng.normal(size=(5 * t, 6 * t, 3)), jnp.float32)
    fused = det.roi_forward(x, grid)
    dense = det.dense_forward(x, grid)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                               atol=DENSE_TOL, rtol=DENSE_TOL)


def test_roi_forward_empty_mask_no_launches():
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    t = det.cfg.tile
    x = jnp.ones((3 * t, 3 * t, 3), jnp.float32)
    with ops.count_kernels() as c:
        out = det.roi_forward(x, np.zeros((3, 3), bool))
    assert sum(c.values()) == 0
    assert out.shape == (3 * t, 3 * t, det.head.shape[-1])
    assert float(jnp.abs(out).max()) == 0.0


def test_fleet_forward_bitwise_vs_per_layer_fleet():
    """Unequal frame sizes + an empty-mask camera + a single-tile camera:
    every camera's heads equal ``dense_forward`` on that camera alone."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(1))
    rng = _rng(5)
    t = det.cfg.tile
    shapes = [(4, 5), (3, 4), (2, 2), (5, 3)]
    frames, grids = _mk_group(rng, shapes, t)
    grids[2][:] = False                     # empty-mask camera
    grids[3][:] = False
    grids[3][4, 1] = True                   # single-tile camera
    fused = det.fleet_forward(frames, grids)
    for o, x, g in zip(fused, frames, grids):
        np.testing.assert_allclose(np.asarray(o),
                                   np.asarray(det.dense_forward(x, g)),
                                   atol=DENSE_TOL, rtol=DENSE_TOL)
    # the empty-mask camera ships an all-zero head map
    assert float(jnp.abs(fused[2]).max()) == 0.0


# ---------------------------------------------------------------------------
# the cross-group super-launch
# ---------------------------------------------------------------------------

def test_superlaunch_tables_flatten_groups_leak_free():
    rng = _rng(6)
    per_group = [[rng.random((3, 4)) < 0.6 for _ in range(2)],
                 [rng.random((2, 5)) < 0.6 for _ in range(3)],
                 [np.zeros((3, 3), bool)]]
    per_group[2][0][1, 1] = True
    idx, nbr, tile_off, cam_starts = ops.superlaunch_tables(per_group)
    flat = [g for gs in per_group for g in gs]
    np.testing.assert_array_equal(cam_starts, [0, 2, 5, 6])
    assert idx.shape[0] == tile_off[-1] == nbr.shape[0]
    # per flat camera: slots stay inside the camera's own range
    for ci in range(len(flat)):
        sl = nbr[tile_off[ci]:tile_off[ci + 1]]
        ok = (sl == -1) | ((sl >= tile_off[ci]) & (sl < tile_off[ci + 1]))
        assert ok.all(), f"flat camera {ci} halo leaks"
        sub = idx[tile_off[ci]:tile_off[ci + 1]]
        assert (sub[:, 0] == ci).all()
        np.testing.assert_array_equal(sub[:, 1:],
                                      ops.mask_to_indices(flat[ci]))


def test_superlaunch_bitwise_vs_per_group_ragged():
    """Ragged group sizes (1, 2 and 4 cameras), unequal canvases, an
    empty-mask camera and a single-tile group: the one-launch fleet step
    is bit-identical to per-group fleet_forward."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(2))
    rng = _rng(7)
    t = det.cfg.tile
    frames, grids = {}, {}
    frames[0], grids[0] = _mk_group(rng, [(4, 5), (3, 4)], t)
    frames[1], grids[1] = _mk_group(rng, [(2, 3)], t)
    grids[1][0][:] = False
    grids[1][0][0, 0] = True                # single-tile group
    frames[2], grids[2] = _mk_group(rng, [(5, 3), (3, 3), (2, 6), (4, 4)],
                                    t)
    grids[2][1][:] = False                  # empty-mask camera
    outs, counts = fleet_inference_step(det, frames, grids)
    assert sum(counts.values()) <= 3
    assert counts["roi_conv_entry"] == 1
    assert counts["roi_conv_stack"] == 1
    assert counts["sbnet_scatter_fleet"] == 1
    for gid in frames:
        per_group = det.fleet_forward(frames[gid], grids[gid])
        for a, b in zip(outs[gid], per_group):
            assert a.shape == b.shape
            assert (np.asarray(a) == np.asarray(b)).all(), \
                f"group {gid}: super-launch diverged from per-group chain"


def test_superlaunch_dispatches_independent_of_k_and_n():
    """The dispatch count stays ≤3 as K grows and for a deeper stack."""
    rng = _rng(8)
    for n_layers, K in [(1, 2), (2, 3), (4, 5)]:
        det = RoIDetector(DetectorConfig(
            channels=(8,) * n_layers), jax.random.PRNGKey(3))
        t = det.cfg.tile
        frames, grids = {}, {}
        for gid in range(K):
            frames[gid], grids[gid] = _mk_group(rng, [(2, 3), (3, 2)], t)
        outs, counts = fleet_inference_step(det, frames, grids)
        assert sum(counts.values()) <= 3
        assert counts["roi_conv_entry"] == 1
        assert counts["roi_conv_stack"] == (1 if n_layers > 1 else 0)
        assert len(outs) == K


def test_empty_fleet_launches_nothing():
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    t = det.cfg.tile
    frames = {0: [jnp.zeros((2 * t, 2 * t, 3), jnp.float32)]}
    grids = {0: [np.zeros((2, 2), bool)]}
    outs, counts = fleet_inference_step(det, frames, grids)
    assert sum(counts.values()) == 0
    assert float(jnp.abs(outs[0][0]).max()) == 0.0


# ---------------------------------------------------------------------------
# per-grid digest cache (the fleet cache-key cost fix)
# ---------------------------------------------------------------------------

def test_fleet_cache_key_hashes_each_grid_once():
    """Repeated fleet_forward with the same grid objects must not
    re-serialize any grid: the digest memo absorbs the key cost and the
    table cache reports hits."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(9)
    t = det.cfg.tile
    frames, grids = _mk_group(rng, [(3, 4), (4, 3)], t)
    det.fleet_forward(frames, grids)
    assert det.grid_hash_computes == 2
    assert det.fleet_cache_hits == 0
    for _ in range(3):
        det.fleet_forward(frames, grids)
    assert det.grid_hash_computes == 2, \
        "cache hits must not re-serialize grids"
    assert det.fleet_cache_hits == 3
    # equal content in a NEW array object: one fresh digest, but the
    # table cache still hits (content-keyed)
    grids2 = [g.copy() for g in grids]
    det.fleet_forward(frames, grids2)
    assert det.grid_hash_computes == 4
    assert det.fleet_cache_hits == 4


def test_grid_digest_guard_catches_inplace_mutation():
    """Mutating a memoized grid in place (popcount-changing, the normal
    case) must re-hash and produce fresh tables, not stale ones."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(11)
    t = det.cfg.tile
    grid = np.zeros((3, 3), bool)
    grid[1, 1] = True
    x = jnp.asarray(rng.normal(size=(3 * t, 3 * t, 3)), jnp.float32)
    det.roi_forward(x, grid)
    grid[0, 0] = True                      # in-place mask update
    mutated = np.asarray(det.roi_forward(x, grid))
    fresh = np.asarray(det.roi_forward(x, grid.copy()))
    np.testing.assert_array_equal(mutated, fresh)
    assert np.abs(mutated[:t, :t]).max() > 0.0   # new tile is live


def test_digest_memo_capacity_scales_with_fleet():
    """A fleet wider than the default memo must still hit the digest
    memo on the second step (no per-step re-serialization)."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(12)
    t = det.cfg.tile
    n_cams = 80                            # > the 64-entry default cap
    grids = [rng.random((2, 2)) < 0.7 for _ in range(n_cams)]
    for g in grids:
        g[0, 0] = True
    frames = [jnp.zeros((2 * t, 2 * t, 3), jnp.float32)] * n_cams
    det.fleet_forward(frames, grids)
    assert det.grid_hash_computes == n_cams
    det.fleet_forward(frames, grids)
    assert det.grid_hash_computes == n_cams, \
        "second step must not re-serialize any grid"
    assert det.fleet_cache_hits == 1


def test_mask_cache_digest_reuse_single_camera():
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(10)
    t = det.cfg.tile
    grid = rng.random((3, 3)) < 0.6
    grid[1, 1] = True
    x = jnp.asarray(rng.normal(size=(3 * t, 3 * t, 3)), jnp.float32)
    det.roi_forward(x, grid)
    h = det.grid_hash_computes
    det.roi_forward(x, grid)
    det.roi_forward(x, grid)
    assert det.grid_hash_computes == h
    assert det.mask_cache_hits == 2
