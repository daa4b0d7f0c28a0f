"""Per-tile temporal delta + quantized zero-run byte estimation (Pallas).

The edge rate controller (repro/net/encoder.py) needs to know, per RoI
tile, how many bytes the tile would cost to ship *this* frame — cheap,
static tiles are the ones whose quality can be shed under uplink backlog.
The estimator is the structural core of an inter-frame codec: quantize the
temporal delta, then price it as entropy-coded (nonzero coefficient,
zero-run) tokens:

    q     = round((cur - prev) / qstep)            # int32 coefficients
    nnz   = #(q != 0)
    runs  = #(maximal zero runs)   per (th,) row of the (th, tw*C) layout
    bytes = ceil((nnz * coef_bits + runs * run_bits) / 8)

entirely in integer ops (bit-exact by construction against the numpy
reference in ``kernels/ref.py``).  Row-independent run counting (a zero
run never joins across rows) is the *definition* of the estimate: a
row's runs are its zeros minus its adjacent zero pairs, which the kernel
counts with masked reductions — no sequential carry.

One kernel, ``tile_delta_gate_canvas``, prices every active tile of the
stacked fleet for BOTH consumers per step; ``tile_delta`` (one camera,
body stats only) is the same launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocking import (VMEM_LIMIT_BYTES, balanced_split,
                                    pad_frames, pad_repeat_last,
                                    widen_padded)
from repro.kernels.roi_conv import MAX_WINDOWS_PER_STEP, window_specs

# entropy-coder token prices (bits): a nonzero coefficient token and a
# zero-run token.  Calibration constants, not tunables-per-call — keeping
# them static keeps the byte estimate an integer function of the tile.
COEF_BITS = 6
RUN_BITS = 10

STATS_WIDTH = 8          # output lane padding; cols 0..5 are live

# The gate's stats-row columns.  Cols 0..3 are the BODY stats and
# match ``tile_delta`` / ``ref.tile_delta`` bit for bit (so the rate
# controller can threshold the shared dispatch exactly as before); cols
# 4..5 are the HALOED-WINDOW stats the temporal reuse gate thresholds.
GATE_BODY_BYTES = 0
GATE_BODY_NNZ = 1
GATE_BODY_RUNS = 2
GATE_BODY_SABS = 3
GATE_WIN_EXACT = 4       # exact count of (th+2, tw+2, C) positions that
#                          differ bitwise — the threshold-0 gate signal
GATE_WIN_BYTES = 5       # quantized zero-run byte estimate of the window

# ---------------------------------------------------------------------------
# reuse-gate delta pricing (haloed input windows on the stacked fleet)
# ---------------------------------------------------------------------------
#
# The temporal reuse gate (serving/detector.fleet_forward_reuse) must know
# whether a tile's ENTRY-LAYER INPUT changed — that is the (th+2, tw+2)
# haloed window the fused gather+conv reads, not just the (th, tw) body:
# a pixel flip in an *inactive* neighbor tile changes an active tile's
# conv output through the 1-px halo, and only the window view sees it.
# One kernel prices both views per tile so the rate controller (body
# stats, cols 0..3, bit-compatible with ``tile_delta``) and the reuse
# gate (window stats, cols 4..5) share a single dispatch per fleet step.
# The frames arrive zero-PADDED (C, H+2, W', Cin) so every window fetch
# is a static-size in-bounds block (pad-ring deltas are 0-0; the numpy
# reference ``ref.tile_delta_gate`` mirrors the padding).


def _window_stats(cur, prev, th: int, tw: int, qstep: float,
                  coef_bits: int, run_bits: int) -> jax.Array:
    """(tb, th+2, X >= tw+2, C) window pairs -> (tb, 1, 1, STATS_WIDTH)
    int32 rows [body bytes, nnz, runs, sum|q|, window exact, window
    bytes, 0, 0].  Columns past tw+2 are masked out."""
    tb, rows, cols, c = cur.shape
    i32 = jnp.int32
    q = jnp.round((cur.astype(jnp.float32) - prev.astype(jnp.float32))
                  / qstep).astype(i32)
    lane = jax.lax.broadcasted_iota(i32, q.shape, 3)
    zero = (q == 0).astype(i32)
    # per-pixel channel planes of the zero mask, (tb, rows, cols, 1)
    zc = [jnp.sum(jnp.where(lane == k, zero, 0), axis=3, keepdims=True)
          for k in range(c)]
    zeros_px = functools.reduce(jnp.add, zc)
    # adjacent zero pairs in a scan row's (x, c) order: channel k-1 -> k
    # inside a pixel, and the last channel of x-1 -> channel 0 of x
    within_px = functools.reduce(
        jnp.add, [zc[k - 1] * zc[k] for k in range(1, c)],
        jnp.zeros_like(zeros_px))
    cross = zc[c - 1][:, :, :-1] * zc[0][:, :, 1:]     # ends at x = 1..
    sabs_px = jnp.sum(jnp.abs(q), axis=3, keepdims=True)
    diff_px = jnp.sum((cur != prev).astype(i32), axis=3, keepdims=True)
    ys = jax.lax.broadcasted_iota(i32, zeros_px.shape, 1)
    xs = jax.lax.broadcasted_iota(i32, zeros_px.shape, 2)
    yc = jax.lax.broadcasted_iota(i32, cross.shape, 1)
    xc = jax.lax.broadcasted_iota(i32, cross.shape, 2) + 1

    def total(v, m):
        # one axis at a time: Mosaic aborts on a 3-axis keepdims reduce
        v = jnp.where(m, v, 0)
        for ax in (3, 2, 1):
            v = jnp.sum(v, axis=ax, keepdims=True)
        return v

    def region(y0, y1, x0, x1):
        m = (ys >= y0) & (ys < y1) & (xs >= x0) & (xs < x1)
        mc = (yc >= y0) & (yc < y1) & (xc > x0) & (xc < x1)
        zeros = total(zeros_px, m)
        nnz = total(c - zeros_px, m)
        runs = zeros - total(within_px, m) - total(cross, mc)
        nbytes = (nnz * coef_bits + runs * run_bits + 7) // 8
        return m, nbytes, nnz, runs

    mb, b_bytes, b_nnz, b_runs = region(1, th + 1, 1, tw + 1)
    mw, w_bytes, _, _ = region(0, th + 2, 0, tw + 2)
    vals = {GATE_BODY_BYTES: b_bytes, GATE_BODY_NNZ: b_nnz,
            GATE_BODY_RUNS: b_runs, GATE_BODY_SABS: total(sabs_px, mb),
            GATE_WIN_EXACT: total(diff_px, mw), GATE_WIN_BYTES: w_bytes}
    col = jax.lax.broadcasted_iota(i32, (tb, 1, 1, STATS_WIDTH), 3)
    out = jnp.zeros((tb, 1, 1, STATS_WIDTH), i32)
    for k, v in vals.items():
        out = jnp.where(col == k, v, out)
    return out


def _gate_canvas_kernel(idx_ref, *refs, th: int, tw: int, tb: int,
                        qstep: float, coef_bits: int, run_bits: int):
    o_ref = refs[2 * tb]
    # one tile at a time keeps the stats temporaries to one window's VMEM
    for j in range(tb):
        o_ref[j:j + 1] = _window_stats(refs[j][...], refs[tb + j][...], th,
                                       tw, qstep, coef_bits, run_bits)


def tile_delta_gate_canvas(cur_p: jax.Array, ref_c: jax.Array,
                           idx: jax.Array, th: int, tw: int,
                           qstep: float = 8.0, coef_bits: int = COEF_BITS,
                           run_bits: int = RUN_BITS, *, block: int,
                           interpret: bool) -> jax.Array:
    """The reuse gate's shared delta dispatch with CANVAS-RESIDENT
    references: cur_p and ref_c are zero-padded (C, H+2, W', Cin) frame
    canvases of the same shape (``blocking.pad_frames``; a plain 1-px
    ring is widened here), addressed through the same (n, 3) (cam, ty,
    tx) rows.  Returns (n, STATS_WIDTH) int32 rows — cols 0..3 the BODY
    delta stats (equal to ``tile_delta`` when the references hold the
    previous frame), col 4 the exact bitwise change count of the haloed
    window, col 5 its quantized byte estimate.  Bit-exact vs
    ``ref.tile_delta_gate``.  ``block`` tiles (at most
    ``roi_conv.MAX_WINDOWS_PER_STEP``) share a grid step; per-tile
    refresh epochs are tracked host-side (serving/detector)."""
    n = idx.shape[0]
    if n == 0:
        return jnp.zeros((0, STATS_WIDTH), jnp.int32)
    cur_p = widen_padded(cur_p, tw)
    ref_c = widen_padded(ref_c, tw)
    cin = cur_p.shape[-1]
    _, tb, n_pad = balanced_split(n, min(max(block, 1),
                                         MAX_WINDOWS_PER_STEP))
    idx = pad_repeat_last(idx, n_pad)
    kernel = functools.partial(_gate_canvas_kernel, th=th, tw=tw, tb=tb,
                               qstep=qstep, coef_bits=coef_bits,
                               run_bits=run_bits)
    specs = window_specs(tb, th, tw, cin)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // tb,),
        in_specs=specs + specs,
        out_specs=pl.BlockSpec((tb, 1, 1, STATS_WIDTH),
                               lambda b, idx_ref: (b, 0, 0, 0)),
    )
    stats = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, 1, 1, STATS_WIDTH),
                                       jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(idx.reshape(-1), *([cur_p] * tb), *([ref_c] * tb))
    return stats.reshape(n_pad, STATS_WIDTH)[:n]


def tile_delta(cur: jax.Array, prev: jax.Array, idx: jax.Array, th: int,
               tw: int, qstep: float = 8.0, coef_bits: int = COEF_BITS,
               run_bits: int = RUN_BITS, *, interpret: bool) -> jax.Array:
    """cur, prev: (H, W, C) frames; idx: (n, 2) int32 active-tile coords.
    Returns (n, STATS_WIDTH) int32 per-tile stats rows:
    ``[byte_estimate, nnz, zero_runs, sum_abs_q, 0...]`` — the body
    columns of ``tile_delta_gate_canvas`` on the one camera."""
    idx3 = jnp.concatenate([jnp.zeros_like(idx[:, :1]), idx], axis=1)
    stats = tile_delta_gate_canvas(
        pad_frames(cur[None], tw), pad_frames(prev[None], tw), idx3, th,
        tw, qstep, coef_bits, run_bits, block=1, interpret=interpret)
    body = jnp.arange(STATS_WIDTH) <= GATE_BODY_SABS
    return jnp.where(body, stats, 0)


# ---------------------------------------------------------------------------
# halo-strip delta pricing (the boundary ring, not the tile body)
# ---------------------------------------------------------------------------

def _halo_strip_stats(cur, prev, qstep: float):
    """One strip pair -> (nnz, runs, sum|q|) with the strip as ONE scan
    row (a zero run never joins across strips)."""
    q = jnp.round((cur.astype(jnp.float32) - prev.astype(jnp.float32))
                  / qstep).astype(jnp.int32)
    z = (q == 0).reshape(1, -1)
    nnz = jnp.sum((~z).astype(jnp.int32))
    left = jnp.concatenate([jnp.zeros((1, 1), bool), z[:, :-1]], axis=1)
    runs = jnp.sum((z & ~left).astype(jnp.int32))
    return nnz, runs, jnp.sum(jnp.abs(q))


def _tile_delta_halo_kernel(idx_ref, cur_ref, prev_ref, o_ref, *, th: int,
                            tw: int, qstep: float, coef_bits: int,
                            run_bits: int):
    i = pl.program_id(0)
    y0 = idx_ref[i, 0] * th
    x0 = idx_ref[i, 1] * tw
    # the tile's edge ring as 4 strips: top row, bottom row, left column,
    # right column.  Corners sit in both a row and a column strip — that
    # duplication IS the halo cost of encoding rectangles independently.
    sels = [(pl.ds(y0, 1), pl.ds(x0, tw)),
            (pl.ds(y0 + th - 1, 1), pl.ds(x0, tw)),
            (pl.ds(y0, th), pl.ds(x0, 1)),
            (pl.ds(y0, th), pl.ds(x0 + tw - 1, 1))]
    nnz = runs = sabs = jnp.asarray(0, jnp.int32)
    for sel in sels:
        dn, dr, ds_ = _halo_strip_stats(cur_ref[sel], prev_ref[sel], qstep)
        nnz, runs, sabs = nnz + dn, runs + dr, sabs + ds_
    nbytes = (nnz * coef_bits + runs * run_bits + 7) // 8
    col = jnp.arange(STATS_WIDTH)
    o_ref[0] = jnp.where(col == 0, nbytes, 0) + jnp.where(col == 1, nnz, 0) \
        + jnp.where(col == 2, runs, 0) + jnp.where(col == 3, sabs, 0)


def tile_delta_halo(cur: jax.Array, prev: jax.Array, idx: jax.Array,
                    th: int, tw: int, qstep: float = 8.0,
                    coef_bits: int = COEF_BITS, run_bits: int = RUN_BITS,
                    *, interpret: bool) -> jax.Array:
    """Delta stats of each active tile's HALO RING (top/bottom rows +
    left/right columns, corners counted in both — the duplicated boundary
    pixels behind the codec model's ``k/sqrt(area)`` surcharge).  Same
    stats row layout as ``tile_delta``; bit-exact vs
    ``ref.tile_delta_halo``.  Lets the rate controller shed halo rows
    whose content is temporally static before touching whole tiles.
    One tile per grid step, strips read from the whole frames (an
    edge-encoder path, not part of the served fleet step)."""
    n = idx.shape[0]
    kernel = functools.partial(_tile_delta_halo_kernel, th=th, tw=tw,
                               qstep=qstep, coef_bits=coef_bits,
                               run_bits=run_bits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, STATS_WIDTH),
                               lambda i, idx_ref: (i, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, STATS_WIDTH), jnp.int32),
        interpret=interpret,
    )(idx, cur, prev)
