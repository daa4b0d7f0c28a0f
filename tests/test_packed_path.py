"""Stay-packed execution path: structural + edge-case contracts.

Covers the packed-resident conv chain (one gather, N packed layers, one
scatter), neighbor-table halo correctness, causal block skipping in the
packed-prefill attention, pack/unpack degenerate keeps, and the batched
group decode in the serving engine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.roi_attention import PAD_POS
from repro.serving.detector import DetectorConfig, RoIDetector


def _rng(seed=0):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# pack / unpack edge cases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S", [16, 31, 32, 48, 65])
def test_pack_unpack_all_false(S):
    x = jnp.asarray(_rng(S).normal(size=(S, 3)), jnp.float32)
    keep = jnp.zeros(S, bool)
    packed, positions, n_kept = ops.pack_tokens(x, keep, block=32)
    assert int(n_kept) == 0
    assert packed.shape[0] % 32 == 0
    assert (np.asarray(positions) == int(PAD_POS)).all()
    restored = ops.unpack_tokens(packed, positions, S)
    np.testing.assert_array_equal(np.asarray(restored), np.zeros((S, 3)))


@pytest.mark.parametrize("S", [16, 31, 32, 48, 65])
def test_pack_unpack_all_true(S):
    x = jnp.asarray(_rng(S + 1).normal(size=(S, 3)), jnp.float32)
    keep = jnp.ones(S, bool)
    packed, positions, n_kept = ops.pack_tokens(x, keep, block=32)
    assert int(n_kept) == S
    np.testing.assert_array_equal(np.asarray(packed[:S]), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(positions[:S]), np.arange(S))
    assert (np.asarray(positions[S:]) == int(PAD_POS)).all()
    restored = ops.unpack_tokens(packed, positions, S)
    np.testing.assert_array_equal(np.asarray(restored), np.asarray(x))


def test_pack_non_multiple_block_positions_monotone():
    S = 45                                     # not a multiple of 32
    rng = _rng(7)
    x = jnp.asarray(rng.normal(size=(S, 2)), jnp.float32)
    keep = jnp.asarray(rng.random(S) < 0.5)
    packed, positions, n_kept = ops.pack_tokens(x, keep, block=32)
    assert packed.shape[0] == 64
    real = np.asarray(positions[:int(n_kept)])
    assert (np.diff(real) > 0).all(), "kept rows must stay in original order"


# ---------------------------------------------------------------------------
# packed-resident conv: one layer of ``roi_conv_stack`` (conv + ReLU)
# ---------------------------------------------------------------------------

def _one_layer(packed, w, nbr):
    return ops.roi_conv_stack(packed, [w], nbr, block=4)


def test_roi_conv_packed_matches_scatter_oracle():
    """A packed layer == relu(scatter-to-zeros -> conv -> gather)
    (``ref.roi_conv_packed``), any mask."""
    rng = _rng(1)
    grid = rng.random((5, 7)) < 0.45
    grid[2, 3] = True
    idx = ops.mask_to_indices(grid)
    nbr = jnp.asarray(ops.neighbor_table(idx, grid.shape))
    th = tw = 8
    packed = jnp.asarray(rng.normal(size=(idx.shape[0], th, tw, 4)),
                         jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 6)) * 0.2, jnp.float32)
    out = _one_layer(packed, w, nbr)
    expect = jax.nn.relu(ref.roi_conv_packed(packed, jnp.asarray(idx),
                                             grid.shape, w))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4)


def test_roi_conv_packed_interior_matches_dense():
    """Interior tile (all 8 neighbors active): packed == dense conv."""
    rng = _rng(2)
    grid = np.zeros((4, 4), bool)
    grid[0:3, 0:3] = True                      # (1,1) is interior
    idx = ops.mask_to_indices(grid)
    nbr = jnp.asarray(ops.neighbor_table(idx, grid.shape))
    th = tw = 8
    packed = jnp.asarray(rng.normal(size=(idx.shape[0], th, tw, 4)),
                         jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 4)) * 0.3, jnp.float32)
    # dense oracle over the scattered frame
    base = jnp.zeros((32, 32, 4), jnp.float32)
    full = ref.sbnet_scatter(packed, jnp.asarray(idx), base, th, tw)
    dense = jax.nn.relu(jax.lax.conv_general_dilated(
        full[None], w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0])
    out = _one_layer(packed, w, nbr)
    slot = {(int(y), int(x)): i for i, (y, x) in enumerate(idx)}
    i11 = slot[(1, 1)]
    np.testing.assert_allclose(np.asarray(out[i11]),
                               np.asarray(dense[8:16, 8:16]), atol=1e-4)


def test_roi_conv_packed_isolated_tile_zero_halo():
    """A tile with NO active neighbors sees an all-zero halo."""
    rng = _rng(3)
    grid = np.zeros((3, 3), bool)
    grid[1, 1] = True
    idx = ops.mask_to_indices(grid)
    nbr_np = ops.neighbor_table(idx, grid.shape)
    assert (nbr_np == -1).all()
    th = tw = 8
    packed = jnp.asarray(rng.normal(size=(1, th, tw, 3)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 3, 5)) * 0.3, jnp.float32)
    out = _one_layer(packed, w, jnp.asarray(nbr_np))
    # oracle: zero-pad the lone tile and convolve
    xp = jnp.pad(packed[0], ((1, 1), (1, 1), (0, 0)))
    expect = jax.nn.relu(jax.lax.conv_general_dilated(
        xp[None], w, (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0])
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(expect),
                               atol=1e-4)


def test_neighbor_table_frame_boundary():
    """Corner tile: off-frame neighbors are -1, in-frame active ones map
    to their packed slots."""
    grid = np.ones((2, 2), bool)
    idx = ops.mask_to_indices(grid)            # row-major: (0,0)(0,1)(1,0)(1,1)
    nbr = ops.neighbor_table(idx, grid.shape)
    # tile (0,0): NW,N,NE,W off-frame; E=(0,1) slot 1, SW off, S=(1,0) slot 2,
    # SE=(1,1) slot 3
    np.testing.assert_array_equal(nbr[0], [-1, -1, -1, -1, 1, -1, 2, 3])
    # tile (1,1): NW=(0,0) slot 0, N=(0,1)... mirrored
    np.testing.assert_array_equal(nbr[3], [0, 1, -1, 2, -1, -1, -1, -1])


# ---------------------------------------------------------------------------
# one gather / one scatter structure of the detector stack
# ---------------------------------------------------------------------------

def test_roi_forward_one_gather_one_scatter():
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    rng = _rng(4)
    gy, gx = 5, 6
    grid = np.zeros((gy, gx), bool)
    grid[1:4, 1:5] = True
    x = jnp.asarray(rng.normal(size=(gy * 16, gx * 16, 3)), jnp.float32)
    ops.KERNEL_COUNTS.clear()
    roi = det.roi_forward(x, grid)
    counts = dict(ops.KERNEL_COUNTS)
    assert counts.get("roi_conv_entry", 0) == 1      # the (fused) gather
    assert counts.get("roi_conv_stack", 0) == 1      # ALL remaining layers
    assert counts.get("sbnet_scatter", 0) == 1       # the scatter
    assert counts.get("sbnet_gather", 0) == 0        # no per-layer re-slice
    assert sum(counts.values()) <= 3                 # constant dispatches
    # packed output matches the dense path on interior tiles to <= 1e-4
    dense = det.dense_forward(x)
    t = det.cfg.tile
    checked = 0
    for ty in range(1, gy - 1):
        for tx in range(1, gx - 1):
            if grid[ty - 1:ty + 2, tx - 1:tx + 2].all():
                a = np.asarray(dense[ty * t:(ty + 1) * t,
                                     tx * t:(tx + 1) * t])
                b = np.asarray(roi[ty * t:(ty + 1) * t, tx * t:(tx + 1) * t])
                assert np.abs(a - b).max() <= 1e-4
                checked += 1
    assert checked >= 2
    # non-RoI regions stay zero
    for ty in range(gy):
        for tx in range(gx):
            if not grid[ty, tx]:
                blk = np.asarray(roi[ty * t:(ty + 1) * t,
                                     tx * t:(tx + 1) * t])
                assert np.abs(blk).max() == 0.0


def test_roi_forward_matches_legacy_scatter_chain():
    """The packed chain must equal the per-layer scatter/gather chain of
    the ``ref.py`` oracles on EVERY tile (inactive-neighbor halos are
    zero in both regimes)."""
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(1))
    rng = _rng(5)
    gy, gx = 4, 5
    grid = rng.random((gy, gx)) < 0.5
    grid[2, 2] = True
    x = jnp.asarray(rng.normal(size=(gy * 16, gx * 16, 3)), jnp.float32)
    roi = det.roi_forward(x, grid)
    # legacy chain: per-layer conv + gather, full-frame scatter
    idx = jnp.asarray(ops.mask_to_indices(grid))
    t = det.cfg.tile
    xl = x
    for w in det.weights:
        packed = jax.nn.relu(ref.roi_conv(xl, w, idx, t, t))
        base = jnp.zeros(x.shape[:2] + (w.shape[-1],), packed.dtype)
        xl = ref.sbnet_scatter(packed, idx, base, t, t)
    legacy = xl @ det.head
    np.testing.assert_allclose(np.asarray(roi), np.asarray(legacy),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# causal block skipping
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("keep_frac", [0.25, 0.6])
def test_block_skip_bitwise_equal(keep_frac):
    rng = _rng(6)
    S, H, D, bq, bk = 256, 2, 32, 32, 32
    q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    n_kept = int(keep_frac * S)
    pos = np.full(S, int(PAD_POS), np.int32)
    pos[:n_kept] = np.sort(rng.choice(4 * S, n_kept, replace=False))
    pos = jnp.asarray(pos)
    out_skip, visited = ops.roi_attention(q, k, v, pos, block_q=bq,
                                          block_k=bk, causal_skip=True,
                                          return_stats=True)
    out_full = ops.roi_attention(q, k, v, pos, block_q=bq, block_k=bk,
                                 causal_skip=False)
    # bitwise equality on real rows
    assert (np.asarray(out_skip[:n_kept])
            == np.asarray(out_full[:n_kept])).all()
    # and against the dense reference
    expect = ref.roi_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out_skip[:n_kept]),
                               np.asarray(expect[:n_kept]), atol=2e-5)
    # visited counts match the host-side bound and skip the dead blocks
    vis = np.asarray(visited)
    bound = ops.attention_visit_bound(np.asarray(pos), bq, bk)
    for h in range(H):
        np.testing.assert_array_equal(vis[h], bound)
    nq, nk = S // bq, S // bk
    visited_frac = vis[0].sum() / (nq * nk)
    exhaustive_frac = 1.0
    assert visited_frac < 0.3 * exhaustive_frac if keep_frac <= 0.25 \
        else visited_frac < 0.75


def test_block_skip_quarter_keep_tracks_lower_triangle():
    """Acceptance: at 25% keep, visited blocks ~ the causal lower-tri
    fraction of the real prefix, not the full quadratic walk."""
    rng = _rng(8)
    S, H, D, bq, bk = 512, 1, 16, 64, 64
    n_kept = S // 4
    pos = np.full(S, int(PAD_POS), np.int32)
    pos[:n_kept] = np.arange(n_kept) * 3          # monotone original order
    q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    out, visited = ops.roi_attention(q, q, q, jnp.asarray(pos), block_q=bq,
                                     block_k=bk, return_stats=True)
    vis = np.asarray(visited)[0]
    nq, nk = S // bq, S // bk
    real_q = -(-n_kept // bq)
    lower_tri = real_q * (real_q + 1) // 2
    assert vis.sum() == lower_tri                 # exact causal prefix
    assert vis.sum() / (nq * nk) <= 0.10          # vs 1.0 exhaustive


def test_block_skip_all_padding_stream():
    """keep = all-False: every k-block is dead; kernel visits nothing."""
    S, H, D = 128, 1, 16
    pos = jnp.full((S,), int(PAD_POS), jnp.int32)
    q = jnp.ones((S, H, D), jnp.float32)
    out, visited = ops.roi_attention(q, q, q, pos, block_q=64, block_k=64,
                                     return_stats=True)
    assert int(np.asarray(visited).sum()) == 0
    assert float(jnp.abs(out).max()) == 0.0


# ---------------------------------------------------------------------------
# cost model flows through
# ---------------------------------------------------------------------------

def test_server_model_amortized_overhead():
    from repro.core.pipeline import ServerModel
    sm = ServerModel()
    assert sm.sbnet_overhead == pytest.approx(sm.io_round_trip
                                              / sm.num_layers)
    assert sm.sbnet_overhead <= 0.30 / sm.num_layers
    # packed regime beats the per-layer regime at every sub-switch density
    legacy = ServerModel(num_layers=1)
    for d in (0.1, 0.3, 0.5):
        assert sm.speedup(d) > legacy.speedup(d)
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    assert det.speedup_estimate(0.2) == pytest.approx(sm.speedup(0.2))
