"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.

All kernels run in interpret mode (CPU container); the contracts are the
ref.py semantics.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.roi_attention import PAD_POS


def _rng(seed=0):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# sbnet gather / scatter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("th,tw,H,W,C", [
    (8, 8, 32, 40, 4),
    (16, 32, 64, 96, 8),
    (32, 32, 128, 128, 16),
])
def test_sbnet_gather_sweep(dtype, th, tw, H, W, C):
    rng = _rng(th * tw)
    x = jnp.asarray(rng.normal(size=(H, W, C)), dtype)
    ty, tx = H // th, W // tw
    all_tiles = [(y, x_) for y in range(ty) for x_ in range(tx)]
    sel = rng.choice(len(all_tiles), size=min(5, len(all_tiles)),
                     replace=False)
    idx = jnp.asarray(np.array([all_tiles[i] for i in sel], np.int32))
    out = ops.sbnet_gather(x, idx, th, tw)
    expect = ref.sbnet_gather(x, idx, th, tw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sbnet_scatter_roundtrip(dtype):
    rng = _rng(3)
    H, W, C, th, tw = 96, 96, 8, 32, 32
    x = jnp.asarray(rng.normal(size=(H, W, C)), dtype)
    idx = jnp.asarray(np.array([[0, 0], [2, 2], [1, 0]], np.int32))
    packed = ops.sbnet_gather(x, idx, th, tw)
    base = jnp.zeros((H, W, C), dtype)
    out = ops.sbnet_scatter(packed, idx, base)
    expect = ref.sbnet_scatter(packed, idx, base, th, tw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32))
    # gathered tiles land back exactly; non-active tiles stay base
    np.testing.assert_allclose(np.asarray(out[:32, :32], np.float32),
                               np.asarray(x[:32, :32], np.float32))
    assert float(jnp.abs(out[:32, 32:64].astype(jnp.float32)).max()) == 0.0


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_sbnet_gather_property(seed):
    """Property: gather output tile i == x at the tile rect, any tile set."""
    rng = _rng(seed)
    th, tw, C = 8, 16, 4
    ty, tx = int(rng.integers(2, 5)), int(rng.integers(2, 5))
    H, W = ty * th, tx * tw
    x = jnp.asarray(rng.normal(size=(H, W, C)), jnp.float32)
    n = int(rng.integers(1, ty * tx + 1))
    flat = rng.choice(ty * tx, size=n, replace=False)
    idx = jnp.asarray(np.stack([flat // tx, flat % tx], 1).astype(np.int32))
    out = ops.sbnet_gather(x, idx, th, tw)
    for i in range(n):
        y0, x0 = int(idx[i, 0]) * th, int(idx[i, 1]) * tw
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(x[y0:y0 + th, x0:x0 + tw]))


# ---------------------------------------------------------------------------
# roi conv: the entry kernel (gather + 3x3 conv + ReLU) vs relu(ref.roi_conv)
# ---------------------------------------------------------------------------

def _entry(x, w, idx, th, tw, block=1):
    """``ops.roi_conv_entry`` on one (H, W, Cin) frame and (n, 2) tile
    coords (camera 0 of a one-camera stack)."""
    idx3 = jnp.concatenate([jnp.zeros_like(idx[:, :1]), idx], axis=1)
    return ops.roi_conv_entry(x[None], w, idx3, th, tw, block=block)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 0.15)])
@pytest.mark.parametrize("th,tw,Cin,Cout", [
    (8, 8, 4, 8),
    (16, 16, 8, 8),
    (32, 32, 3, 16),
])
def test_roi_conv_sweep(dtype, tol, th, tw, Cin, Cout):
    rng = _rng(th + Cin)
    H, W = th * 3, tw * 4
    x = jnp.asarray(rng.normal(size=(H, W, Cin)), dtype)
    w = jnp.asarray(rng.normal(size=(3, 3, Cin, Cout)) * 0.2, dtype)
    idx = jnp.asarray(np.array([[0, 0], [1, 2], [2, 3], [1, 1]], np.int32))
    out = _entry(x, w, idx, th, tw, block=2)
    expect = jax.nn.relu(ref.roi_conv(x, w, idx, th, tw))
    assert out.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_roi_conv_interior_tile_matches_dense():
    """An interior active tile must equal the dense conv exactly (halo
    correctness)."""
    rng = _rng(9)
    th = tw = 16
    x = jnp.asarray(rng.normal(size=(48, 48, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 4)) * 0.3, jnp.float32)
    idx = jnp.asarray(np.array([[1, 1]], np.int32))
    out = _entry(x, w, idx, th, tw)[0]
    dense = jax.nn.relu(jax.lax.conv_general_dilated(
        x[None], w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0])
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense[16:32, 16:32]), atol=2e-4)


def test_roi_conv_batched():
    """A batch of frames with one shared active set is one entry launch
    over the stacked frames, camera by camera."""
    rng = _rng(11)
    x = jnp.asarray(rng.normal(size=(2, 32, 32, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 8)) * 0.2, jnp.float32)
    idx = jnp.asarray(np.array([[0, 0], [1, 1]], np.int32))
    idx3 = jnp.asarray(np.array([[b, 0, 0] for b in range(2)]
                                + [[b, 1, 1] for b in range(2)], np.int32))
    with ops.count_kernels() as c:
        out = ops.roi_conv_entry(x, w, idx3, 16, 16, block=4)
    assert dict(c) == {"roi_conv_entry": 1}
    out = out.reshape(2, 2, 16, 16, 8).transpose(1, 0, 2, 3, 4)
    assert out.shape == (2, 2, 16, 16, 8)
    for b in range(2):
        expect = jax.nn.relu(ref.roi_conv(x[b], w, idx, 16, 16))
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(expect),
                                   atol=1e-4)


# ---------------------------------------------------------------------------
# roi attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 0.05)])
@pytest.mark.parametrize("S,H,D,bq,bk", [
    (128, 2, 32, 64, 64),
    (256, 4, 64, 128, 128),
    (256, 1, 128, 64, 128),
])
def test_roi_attention_sweep(dtype, tol, S, H, D, bq, bk):
    rng = _rng(S + D)
    q = jnp.asarray(rng.normal(size=(S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(S, H, D)), dtype)
    v = jnp.asarray(rng.normal(size=(S, H, D)), dtype)
    n_kept = int(0.8 * S)
    pos = np.full(S, PAD_POS, np.int32)
    pos[:n_kept] = np.sort(rng.choice(4 * S, n_kept, replace=False))
    pos = jnp.asarray(pos)
    out = ops.roi_attention(q, k, v, pos, block_q=bq, block_k=bk)
    expect = ref.roi_attention(q, k, v, pos)
    np.testing.assert_allclose(
        np.asarray(out[:n_kept], np.float32),
        np.asarray(expect[:n_kept], np.float32), atol=tol, rtol=tol)


def test_roi_attention_equals_causal_when_dense():
    """With keep=all and positions=arange, packed attention == plain causal."""
    rng = _rng(21)
    S, H, D = 128, 2, 32
    q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    out = ops.roi_attention(q, k, v, pos, block_q=64, block_k=64)
    # plain causal reference
    logits = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    logits = jnp.where(mask[None], logits, -1e30)
    expect = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(logits, axis=-1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(10, 200))
def test_pack_unpack_roundtrip(seed, S):
    rng = _rng(seed)
    x = jnp.asarray(rng.normal(size=(S, 3)), jnp.float32)
    keep = jnp.asarray(rng.random(S) < 0.6)
    packed, positions, n_kept = ops.pack_tokens(x, keep, block=64)
    assert packed.shape[0] % 64 == 0
    assert int(n_kept) == int(keep.sum())
    # kept rows are a stable-order prefix
    kept_rows = np.asarray(x)[np.asarray(keep)]
    np.testing.assert_array_equal(np.asarray(packed[:int(n_kept)]),
                                  kept_rows)
    restored = ops.unpack_tokens(packed, positions, S)
    expect = np.where(np.asarray(keep)[:, None], np.asarray(x), 0.0)
    np.testing.assert_array_equal(np.asarray(restored), expect)
