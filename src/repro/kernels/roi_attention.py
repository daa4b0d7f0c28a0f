"""RoI-packed prefill attention as a Pallas TPU kernel.

The CrossRoI technique lifted to transformer serving (DESIGN.md §2): the
offline set-cover mask maps to a token keep-list; kept tokens are packed
into a dense prefix and prefilled in one pass.  Causality must follow the
tokens' *original* positions, so the kernel carries a positions vector and
masks with pos_q >= pos_k instead of the block-triangular structure.

Flash-attention structure: grid = (heads, q_blocks); the q block and the
head's K/V live in VMEM via BlockSpec and the kernel walks k-blocks with
dynamic-slice loads, maintaining the online-softmax running max/denominator.
Padding rows carry position INT32_MAX (never attended, never attending).

Causal block skipping: ``pack_tokens`` keeps kept rows in original order,
so positions are monotone over real rows with PAD_POS padding at the tail.
A scalar-prefetched per-k-block minimum-position vector bounds the k-loop
at the *last* k-block whose min position can be <= the q-block's max real
position — the standard flash-attention causal bound, which also skips
all-padding tail blocks (their min is PAD_POS).  Skipped blocks are ones
the exhaustive kernel fully masks, and a fully-masked block is an exact
no-op in the online softmax once any real block has been folded in
(alpha = 1, p = exp(-inf) = 0), so outputs on real rows are bitwise equal
to the exhaustive kernel.  The kernel also emits a per-(head, q-block)
visited-block count so the skip ratio is observable in tests/benchmarks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
PAD_POS = jnp.iinfo(jnp.int32).max


def _roi_attn_kernel(pos_ref, kmin_ref, q_ref, k_ref, v_ref, pq_ref, pk_ref,
                     o_ref, cnt_ref, *, block_k: int, scale: float,
                     causal_skip: bool):
    qi = pl.program_id(1)
    bq, D = q_ref.shape[1], q_ref.shape[2]
    S = k_ref.shape[1]
    q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
    pos_q = pq_ref[...]                               # (bq, 1)

    nk = S // block_k

    if causal_skip:
        # visit k-blocks [0, hi): hi = 1 + last j with min(pos_k_j) <=
        # max(real pos_q).  Correct for any positions vector; for the
        # monotone packed layout it is exactly the causal prefix.  A
        # q-block of pure padding has no real rows -> hi = 0.  Scalar
        # walks over the SMEM copies of the positions.
        def row_max(r, m):
            pr = pos_ref[qi * bq + r]
            return jnp.where(pr != PAD_POS, jnp.maximum(m, pr), m)
        pos_q_max = jax.lax.fori_loop(0, bq, row_max, -1)

        def scan_last(j, h):
            return jnp.where(kmin_ref[j] <= pos_q_max, j + 1, h)
        hi = jax.lax.fori_loop(0, nk, scan_last, 0)
    else:
        hi = nk

    def body(j, carry):
        acc, m, l = carry
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, pl.ds(start, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(start, block_k), :].astype(jnp.float32)
        pos_k = pk_ref[:, pl.ds(start, block_k)]      # (1, bk)
        s = q @ k.T                                   # (bq, bk)
        s = jnp.where(pos_q >= pos_k, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + p @ v
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)
    cnt_ref[...] = jnp.full(cnt_ref.shape, hi, jnp.int32)


def block_min_positions(positions: jax.Array, block_k: int) -> jax.Array:
    """Per-k-block minimum original position, (S // block_k,) int32.

    Computed once per prefill on the host side of the kernel (the packed
    layout makes it positions[::block_k], but the segment-min form stays
    correct for arbitrary position vectors)."""
    S = positions.shape[0]
    return positions.reshape(S // block_k, block_k).min(axis=1)


def roi_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  positions: jax.Array, *, block_q: int = 128,
                  block_k: int = 128, scale: float | None = None,
                  causal_skip: bool = True, return_stats: bool = False,
                  interpret: bool):
    """q,k,v: (S, H, D) packed tokens; positions: (S,) int32 original
    positions (padding = PAD_POS).  S must divide by block_q and block_k
    (ops.roi_attention pads).  Returns (S, H, D), or
    ((S, H, D), visited (H, S // block_q) int32) with ``return_stats``."""
    S, H, D = q.shape
    assert S % block_q == 0 and S % block_k == 0
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    kmin = block_min_positions(positions, block_k)
    kernel = functools.partial(_roi_attn_kernel, block_k=block_k, scale=scale,
                               causal_skip=causal_skip)
    # layout: (H, S, D) so heads are the leading grid axis
    qh = jnp.swapaxes(q, 0, 1)
    kh = jnp.swapaxes(k, 0, 1)
    vh = jnp.swapaxes(v, 0, 1)
    nq = S // block_q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, i, pos, kmin: (h, i, 0)),
            pl.BlockSpec((1, S, D), lambda h, i, pos, kmin: (h, 0, 0)),
            pl.BlockSpec((1, S, D), lambda h, i, pos, kmin: (h, 0, 0)),
            # positions again as vectors for the mask: a query column
            # block and the whole key row
            pl.BlockSpec((block_q, 1), lambda h, i, pos, kmin: (i, 0)),
            pl.BlockSpec((1, S), lambda h, i, pos, kmin: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, D), lambda h, i, pos, kmin: (h, i, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda h, i, pos, kmin: (h, i, 0, 0)),
        ),
    )

    out, visited = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((H, S, D), q.dtype),
                   jax.ShapeDtypeStruct((H, nq, 1, 1), jnp.int32)),
        interpret=interpret,
    )(positions, kmin, qh, kh, vh, positions.reshape(S, 1),
      positions.reshape(1, S))
    out = jnp.swapaxes(out, 0, 1)
    visited = visited.reshape(H, nq)
    if return_stats:
        return out, visited
    return out
