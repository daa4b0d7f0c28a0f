"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics contracts: tests sweep shapes/dtypes and assert
the Pallas kernels (run in interpret mode on CPU) match these references.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# sbnet gather / scatter (tile granularity)
# ---------------------------------------------------------------------------

def sbnet_gather(x: jax.Array, idx: jax.Array, th: int, tw: int) -> jax.Array:
    """x: (H, W, C); idx: (n, 2) int32 tile coords (ty, tx).
    Returns packed (n, th, tw, C)."""
    def take(t):
        ty, tx = t[0], t[1]
        return jax.lax.dynamic_slice(
            x, (ty * th, tx * tw, 0), (th, tw, x.shape[-1]))
    return jax.vmap(take)(idx)


def sbnet_scatter(packed: jax.Array, idx: jax.Array, base: jax.Array,
                  th: int, tw: int) -> jax.Array:
    """Write packed tiles back into ``base`` at their tile positions.
    Tiles must be disjoint (guaranteed by mask construction)."""
    def body(i, acc):
        ty, tx = idx[i, 0], idx[i, 1]
        return jax.lax.dynamic_update_slice(
            acc, packed[i], (ty * th, tx * tw, 0))
    return jax.lax.fori_loop(0, idx.shape[0], body, base)


# ---------------------------------------------------------------------------
# roi conv (3x3, stride 1, same padding over the *full* frame, evaluated
# only on active tiles)
# ---------------------------------------------------------------------------

def roi_conv(x: jax.Array, w: jax.Array, idx: jax.Array,
             th: int, tw: int) -> jax.Array:
    """x: (H, W, Cin); w: (3, 3, Cin, Cout); idx: (n, 2) tile coords.
    Returns packed conv outputs (n, th, tw, Cout): identical to running a
    SAME conv over the whole frame then gathering the active tiles."""
    full = jax.lax.conv_general_dilated(
        x[None].astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]
    return sbnet_gather(full.astype(x.dtype), idx, th, tw)


def roi_conv_packed(packed: jax.Array, idx: jax.Array, grid_shape,
                    w: jax.Array) -> jax.Array:
    """Oracle for the packed-resident conv: scatter the packed tiles onto a
    zeroed full frame (inactive tiles = 0, exactly the zero-halo contract),
    run a SAME conv, gather the active tiles back."""
    n, th, tw, C = packed.shape
    H, W = grid_shape[0] * th, grid_shape[1] * tw
    base = jnp.zeros((H, W, C), packed.dtype)
    full = sbnet_scatter(packed, idx, base, th, tw)
    return roi_conv(full, w, idx, th, tw)


def _leaky(x):
    return jnp.maximum(x, 0.1 * x)


def _conv_s(x, w, stride):
    return jax.lax.conv_general_dilated(
        x[None], w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)[0]


def roi_conv_stage(packed: jax.Array, idx: jax.Array, grid_shape, down,
                   blocks) -> jax.Array:
    """Oracle for ``roi_conv_stage``: scatter one camera's packed (n, T,
    T, Cin) tiles (``idx`` (n, 2) tile coords of a ``grid_shape`` grid)
    onto a zeroed full map, run the stage with SAME convolutions — the
    3x3 stride-2 down conv, then per block y + act(conv3(act(conv1(y))))
    — zeroing every layer's output outside the active tiles, and gather
    the active tiles back at the halved size.  Every conv adds its bias
    and applies act, leaky ReLU of slope 0.1."""
    n, T, _, _ = packed.shape
    t = T // 2
    gy, gx = grid_shape
    full = sbnet_scatter(packed, idx, jnp.zeros((gy * T, gx * T,
                                                 packed.shape[-1]),
                                                packed.dtype), T, T)
    tiles = jnp.zeros(grid_shape, jnp.int32).at[idx[:, 0], idx[:, 1]].set(1)
    mask = jnp.kron(tiles, jnp.ones((t, t), jnp.int32))[..., None] > 0
    wd, bd = down
    y = jnp.where(mask, _leaky(_conv_s(full, wd, 2) + bd), 0.0)
    for w1, b1, w2, b2 in blocks:
        h = jnp.where(mask, _leaky(_conv_s(y, w1, 1) + b1), 0.0)
        y = jnp.where(mask, y + _leaky(_conv_s(h, w2, 1) + b2), 0.0)
    return sbnet_gather(y, idx, t, t)


# ---------------------------------------------------------------------------
# roi attention (packed prefill with original-position causal mask)
# ---------------------------------------------------------------------------

def roi_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  positions: jax.Array, scale: float | None = None
                  ) -> jax.Array:
    """q,k,v: (S, H, D) packed (RoI-kept) tokens; positions: (S,) int32
    original positions (padding rows use position INT32_MAX for k-masking).
    Causal over original positions: query i attends key j iff
    positions[i] >= positions[j]."""
    S, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    logits = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = positions[:, None] >= positions[None, :]
    logits = jnp.where(mask[None], logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("hqk,khd->qhd", p / denom, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# tile delta + zero-run byte estimation
# ---------------------------------------------------------------------------

def tile_delta(cur, prev, idx, th: int, tw: int, qstep: float = 8.0,
               coef_bits: int = 6, run_bits: int = 10):
    """Numpy oracle for kernels/tile_delta.py — same integer math, same
    float32 quantization, same row-independent zero-run definition, so the
    Pallas kernel must match it BIT-EXACTLY.  Returns (n, 8) int32 rows of
    ``[byte_estimate, nnz, zero_runs, sum_abs_q, 0, 0, 0, 0]``."""
    import numpy as np
    cur = np.asarray(cur, np.float32)
    prev = np.asarray(prev, np.float32)
    idx = np.asarray(idx)
    out = np.zeros((idx.shape[0], 8), np.int32)
    for i, (ty, tx) in enumerate(idx):
        c = cur[ty * th:(ty + 1) * th, tx * tw:(tx + 1) * tw, :]
        p = prev[ty * th:(ty + 1) * th, tx * tw:(tx + 1) * tw, :]
        q = np.round((c - p) / np.float32(qstep)).astype(np.int32)
        z2 = (q == 0).reshape(th, -1)
        nnz = int((~z2).sum())
        left = np.concatenate([np.zeros((th, 1), bool), z2[:, :-1]], axis=1)
        runs = int((z2 & ~left).sum())
        sabs = int(np.abs(q).sum())
        out[i] = [(nnz * coef_bits + runs * run_bits + 7) // 8,
                  nnz, runs, sabs, 0, 0, 0, 0]
    return out


def tile_delta_gate(cur, prev, idx, th: int, tw: int, qstep: float = 8.0,
                    coef_bits: int = 6, run_bits: int = 10):
    """Numpy oracle for ``kernels/tile_delta.tile_delta_gate_canvas``
    with ``prev`` as the reference canvas: per active tile of a stacked
    fleet, the BODY delta stats (cols 0..3, identical to ``tile_delta``
    on that camera) plus the HALOED-WINDOW stats the temporal reuse gate
    thresholds — col 4 the exact bitwise change count of the (th+2,
    tw+2, C) window, col 5 its quantized byte estimate.

    cur, prev: UNPADDED (C, H, W, Cin) stacked frames (the oracle applies
    the same zero padding the kernel's callers do); idx: (n, 3) int32
    (cam, ty, tx).  Bit-exact contract."""
    import numpy as np
    cur = np.asarray(cur, np.float32)
    prev = np.asarray(prev, np.float32)
    idx = np.asarray(idx)
    pad = ((0, 0), (1, 1), (1, 1), (0, 0))
    cur_p = np.pad(cur, pad)
    prev_p = np.pad(prev, pad)

    def stats(c, p):
        rows = c.shape[0]
        q = np.round((c - p) / np.float32(qstep)).astype(np.int32)
        z2 = (q == 0).reshape(rows, -1)
        nnz = int((~z2).sum())
        left = np.concatenate([np.zeros((rows, 1), bool), z2[:, :-1]],
                              axis=1)
        runs = int((z2 & ~left).sum())
        return ((nnz * coef_bits + runs * run_bits + 7) // 8, nnz, runs,
                int(np.abs(q).sum()))

    out = np.zeros((idx.shape[0], 8), np.int32)
    for i, (cam, ty, tx) in enumerate(idx):
        cw = cur_p[cam, ty * th:ty * th + th + 2,
                   tx * tw:tx * tw + tw + 2, :]
        pw = prev_p[cam, ty * th:ty * th + th + 2,
                    tx * tw:tx * tw + tw + 2, :]
        b = stats(cw[1:1 + th, 1:1 + tw], pw[1:1 + th, 1:1 + tw])
        w = stats(cw, pw)
        out[i] = [b[0], b[1], b[2], b[3], int((cw != pw).sum()), w[0],
                  0, 0]
    return out


def tile_delta_halo(cur, prev, idx, th: int, tw: int, qstep: float = 8.0,
                    coef_bits: int = 6, run_bits: int = 10):
    """Numpy oracle for ``kernels/tile_delta.tile_delta_halo``: delta
    stats of each tile's edge ring as 4 independent scan strips (top row,
    bottom row, left column, right column; corners in both a row and a
    column strip — the duplication is the halo cost).  Bit-exact
    contract, same stats row layout as ``tile_delta``."""
    import numpy as np
    cur = np.asarray(cur, np.float32)
    prev = np.asarray(prev, np.float32)
    idx = np.asarray(idx)
    out = np.zeros((idx.shape[0], 8), np.int32)
    for i, (ty, tx) in enumerate(idx):
        y0, x0 = ty * th, tx * tw
        strips = [(cur[y0, x0:x0 + tw], prev[y0, x0:x0 + tw]),
                  (cur[y0 + th - 1, x0:x0 + tw],
                   prev[y0 + th - 1, x0:x0 + tw]),
                  (cur[y0:y0 + th, x0], prev[y0:y0 + th, x0]),
                  (cur[y0:y0 + th, x0 + tw - 1],
                   prev[y0:y0 + th, x0 + tw - 1])]
        nnz = runs = sabs = 0
        for c, p in strips:
            q = np.round((c - p) / np.float32(qstep)).astype(np.int32)
            z = (q == 0).reshape(1, -1)
            nnz += int((~z).sum())
            left = np.concatenate([np.zeros((1, 1), bool), z[:, :-1]],
                                  axis=1)
            runs += int((z & ~left).sum())
            sabs += int(np.abs(q).sum())
        out[i] = [(nnz * coef_bits + runs * run_bits + 7) // 8,
                  nnz, runs, sabs, 0, 0, 0, 0]
    return out
