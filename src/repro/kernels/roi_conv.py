"""RoI-sparse 3x3 convolution as Pallas TPU kernels.

``roi_conv_entry`` — the *entry* layer: convolution evaluated only on
active tiles, reading straight from the stacked frames.  grid =
(tile_block,); each grid step receives ``block`` haloed (th+2, tw+2,
Cin) windows, one element-indexed BlockSpec per window whose index map
reads the scalar-prefetched (cam, ty, tx) rows, so Mosaic's pipeline
DMAs the next block's windows while this one computes.  The 3x3 conv is
9 shifted (block*th*tw, Cin) @ (Cin, Cout) MXU matmuls.  This fuses
SBNet's gather into the first conv.  ``roi_conv_fleet`` and ``roi_conv``
are the same kernel without the fused ReLU (one camera group / one
camera).

``roi_conv_stack`` — every *subsequent* layer in ONE launch: grid =
(layer, tile_block).  A layer's packed (n, th, tw, C) output stays in
HBM; the next layer DMAs each tile's center and the 1-deep edges of its
8 neighbors (an offline (n, 8) neighbor table, scalar-prefetched) into
a VMEM window, so the sparse representation never round-trips through a
full-frame scatter between layers.  Inactive or off-frame neighbors map
to an all-zero row: the zero halo the scatter-into-zeros path produced.

``roi_conv_packed`` is the per-layer form of one stack layer, kept as
the bit-identical baseline the megakernel is tested against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocking import (LANES, VMEM_LIMIT_BYTES, balanced_split,
                                    pad_frames, pad_repeat_last, round_up,
                                    window_width)

# neighbor-table column order: (dy, dx) offsets of the 8 surrounding tiles
NEIGHBOR_OFFSETS = ((-1, -1), (-1, 0), (-1, 1), (0, -1),
                    (0, 1), (1, -1), (1, 0), (1, 1))

# at most this many haloed windows per entry grid step: each window is
# its own pipelined operand
MAX_WINDOWS_PER_STEP = 16


def contraction_width(cin: int) -> int:
    """Channels a packed-layer tap contracts over: cin rounded up to 8,
    the extra window lanes and weight rows being zero.  XLA's CPU dot
    (the interpreter's) sums a 5-wide contraction in an order that
    depends on the row count; at multiples of 8 it does not, so a tile's
    bits stay independent of its block there too."""
    return -(-cin // 8) * 8


def _conv_taps(win: jax.Array, w: jax.Array, th: int, tw: int,
               k: int) -> jax.Array:
    """(tb, th+2, >=tw+2, >=k) haloed windows -> (tb, th, tw, Cout): 9
    shifted (tb*th*tw, k) @ (k, Cout) float32 matmuls over the first k
    lanes (w: (3, 3, k, Cout)).  Output rows are independent dot
    products, so a tile's values do not depend on the block it is
    computed in."""
    tb = win.shape[0]
    cout = w.shape[-1]
    acc = jnp.zeros((tb * th * tw, cout), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            patch = win[:, dy:dy + th, dx:dx + tw, :k].reshape(
                tb * th * tw, k)
            acc += jnp.dot(patch.astype(jnp.float32),
                           w[dy, dx].astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    return acc.reshape(tb, th, tw, cout)


def window_specs(tb: int, th: int, tw: int, cin: int):
    """``tb`` element-indexed BlockSpecs: spec j fetches the haloed
    window of row ``b*tb + j`` of the scalar-prefetched, flattened (n*3,)
    (cam, ty, tx) table from (C, H+2, W', Cin) padded frames.  (Scalar
    memory pads a 2-D table's rows to 128 words, so tables travel flat.)"""
    wx = window_width(tw)

    def spec(j):
        def index_map(b, idx_ref):
            r = 3 * (b * tb + j)
            return (idx_ref[r], idx_ref[r + 1] * th, idx_ref[r + 2] * tw, 0)
        return pl.BlockSpec((pl.Element(1), pl.Element(th + 2),
                             pl.Element(wx), pl.Element(cin)), index_map)
    return [spec(j) for j in range(tb)]


def _entry_kernel(idx_ref, *refs, th: int, tw: int, tb: int,
                  fuse_relu: bool):
    win_refs, w_ref, o_ref = refs[:tb], refs[tb], refs[tb + 1]
    win = jnp.concatenate([r[...] for r in win_refs], axis=0)
    o = _conv_taps(win, w_ref[...], th, tw, win.shape[-1])
    if fuse_relu:
        o = jnp.maximum(o, 0.0)
    o_ref[...] = o.astype(o_ref.dtype)


def _entry_call(x, w, idx, th, tw, *, block, fuse_relu, interpret):
    """Gather + 3x3 conv (+ ReLU) of the (n, 3) (cam, ty, tx) tiles of
    (C, H, W, Cin) stacked frames -> packed (n, th, tw, Cout)."""
    n = idx.shape[0]
    cout = w.shape[-1]
    if n == 0:
        return jnp.zeros((0, th, tw, cout), x.dtype)
    cin = x.shape[-1]
    _, tb, n_pad = balanced_split(n, min(max(block, 1),
                                         MAX_WINDOWS_PER_STEP))
    idx_p = pad_repeat_last(idx, n_pad)
    xp = pad_frames(x, tw)
    kernel = functools.partial(_entry_kernel, th=th, tw=tw, tb=tb,
                               fuse_relu=fuse_relu)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // tb,),
        in_specs=window_specs(tb, th, tw, cin) + [
            pl.BlockSpec((3, 3, cin, cout), lambda b, idx_ref: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, th, tw, cout),
                               lambda b, idx_ref: (b, 0, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, th, tw, cout), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(idx_p.reshape(-1), *([xp] * tb), w)
    return out[:n]


def roi_conv_entry(x: jax.Array, w: jax.Array, idx: jax.Array, th: int,
                   tw: int, *, block: int, interpret: bool) -> jax.Array:
    """The fused backbone's entry layer: gather + 3x3 conv + ReLU in ONE
    launch for any number of cameras (and camera groups — the (n, 3)
    (flat_cam, ty, tx) index space is oblivious to how cameras are
    grouped).  x: (C, H, W, Cin) stacked frames; w: (3, 3, Cin, Cout);
    idx: (n, 3).  Returns relu'd packed (n, th, tw, Cout) — relu is
    idempotent, so callers may re-apply it bit-identically.  The packed
    output feeds ``roi_conv_stack`` for every remaining layer.

    ``block`` tiles (at most ``MAX_WINDOWS_PER_STEP``) share a grid step
    and one (block*th*tw, Cin) GEMM per tap — bit-identical to the
    per-tile walk at ``block=1``.  The index list is padded up with
    repeats of its last row; the duplicate rows' outputs land past ``n``
    and are sliced off.  An EMPTY tile set returns a zero-row packed
    tensor with no pallas_call at all."""
    return _entry_call(x, w, idx, th, tw, block=block, fuse_relu=True,
                       interpret=interpret)


def roi_conv_fleet(x: jax.Array, w: jax.Array, idx: jax.Array, th: int,
                   tw: int, *, interpret: bool) -> jax.Array:
    """Cross-camera fused gather+conv (no ReLU), one launch for a camera
    group: x (C, H, W, Cin) stacked frames, idx (n, 3) (cam, ty, tx).
    Per-camera zero padding reproduces each camera's own SAME-conv frame
    boundary, so the output equals per-camera launches."""
    return _entry_call(x, w, idx, th, tw, block=1, fuse_relu=False,
                       interpret=interpret)


def roi_conv(x: jax.Array, w: jax.Array, idx: jax.Array, th: int, tw: int,
             *, interpret: bool) -> jax.Array:
    """x: (H, W, Cin); w: (3, 3, Cin, Cout); idx: (n, 2) int32 tile coords.
    Returns packed SAME-conv outputs on active tiles: (n, th, tw, Cout)."""
    idx3 = jnp.concatenate([jnp.zeros_like(idx[:, :1]), idx], axis=1)
    return roi_conv_fleet(x[None], w, idx3, th, tw, interpret=interpret)


# ---------------------------------------------------------------------------
# the fused layer-stack megakernel
# ---------------------------------------------------------------------------

def _span(d: int, size: int):
    """(source slice, window slice) along one axis for neighbor offset
    ``d``: the neighbor above/left donates its last row/column, the one
    below/right its first, the tile itself its whole extent."""
    if d < 0:
        return pl.ds(size - 1, 1), pl.ds(0, 1)
    if d > 0:
        return pl.ds(0, 1), pl.ds(size + 1, 1)
    return pl.ds(0, size), pl.ds(1, size)


def _window_fetches(src, nbr_ref, win, sem, base: int, tb: int, th: int,
                    tw: int):
    """DMA the haloed windows of packed rows [base, base+tb) of ``src``
    ((n+1, th, tw, CP), row n all zero) into ``win`` (tb, th+2, >=tw+2,
    CP): the centers in one copy, then each tile's 8 neighbor edges and
    corners from the rows the flattened (n*8,) neighbor table names."""
    halo = []
    for dy, dx in NEIGHBOR_OFFSETS:
        (sy, wy), (sx, wx) = _span(dy, th), _span(dx, tw)
        halo.append(((sy, sx), (wy, wx)))

    def center(start):
        return pltpu.make_async_copy(
            src.at[pl.ds(start, tb)],
            win.at[:, pl.ds(1, th), pl.ds(1, tw)], sem)

    def edge(j, k, row):
        (sy, sx), (wy, wx) = halo[k]
        return pltpu.make_async_copy(src.at[row, sy, sx],
                                     win.at[j, wy, wx], sem)

    def start(j, carry):
        for k in range(8):
            edge(j, k, nbr_ref[8 * (base + j) + k]).start()
        return carry

    def wait(j, carry):
        for k in range(8):
            edge(j, k, 0).wait()
        return carry

    center(base).start()
    jax.lax.fori_loop(0, tb, start, 0)
    center(0).wait()
    jax.lax.fori_loop(0, tb, wait, 0)


def _roi_conv_stack_kernel(nbr_ref, p0_hbm, w_ref, o_ref, act_a, act_b,
                           win, stage, zero, sems, *, th: int, tw: int,
                           chans, tb: int, n_pad: int):
    p = pl.program_id(0)
    b = pl.program_id(1)
    L = len(chans) - 1
    acts = (act_a, act_b)
    base = b * tb

    def emit(o, dst, cout):
        # lanes past cout stay zero (cleared at each layer's first
        # block): the next layer contracts over them
        @pl.when(b == 0)
        def _():
            stage[...] = jnp.zeros(stage.shape, stage.dtype)

        stage[:, :, :, :cout] = o.astype(stage.dtype)
        out = pltpu.make_async_copy(stage, dst.at[pl.ds(base, tb)],
                                    sems.at[1])
        out.start()

        @pl.when(b == 0)
        def _():
            # row n_pad is the all-zero halo donor of inactive neighbors
            zero[...] = jnp.zeros(zero.shape, zero.dtype)
            z = pltpu.make_async_copy(zero, dst.at[pl.ds(n_pad, 1)],
                                      sems.at[1])
            z.start()
            z.wait()

        out.wait()

    # phase l: layer l reads layer l-1's packed output (the entry
    # layer's for l = 0) and writes the other ping-pong buffer, so a
    # block's writes never race a later block's neighbor reads
    for lc in range(L):
        @pl.when(p == lc)
        def _(lc=lc):
            src = p0_hbm if lc == 0 else acts[(lc - 1) % 2]
            _window_fetches(src, nbr_ref, win, sems.at[0], base, tb, th, tw)
            k, cout = contraction_width(chans[lc]), chans[lc + 1]
            w = w_ref[0][:, :, :k, :cout]
            o = jnp.maximum(_conv_taps(win[...], w, th, tw, k), 0.0)
            if lc == L - 1:
                o_ref[...] = o.astype(o_ref.dtype)
            else:
                emit(o, acts[lc % 2], cout)


def roi_conv_stack(packed: jax.Array, ws, nbr: jax.Array, *, block: int,
                   interpret: bool) -> jax.Array:
    """The fused layer-stack megakernel: the ENTIRE packed conv chain
    (3x3 conv + ReLU per layer) in ONE ``pallas_call`` with grid =
    (layer, tile_block), replacing N-1 ``roi_conv_packed`` dispatches.

    packed: (n, th, tw, C0) the entry layer's (relu'd) packed output;
    ws: list of (3, 3, C_l, C_{l+1}) weights; nbr: (n, 8) neighbor table
    (``neighbor_table`` / ``fleet_neighbor_table``, -1 = zero halo).
    Returns the last layer's packed (n, th, tw, C_last), bit-identical to
    the per-layer ``relu(roi_conv_packed(...))`` chain:

    * the layer axis is OUTER, so every tile of layer l completes before
      layer l+1 starts; intermediate layers live in two HBM ping-pong
      buffers (n+1, th, tw, CP) whose last row stays zero, CP being the
      widest layer input rounded up to the 128-lane vector width (the
      HBM tiles hold 128 lanes either way);
    * each grid step DMAs ``block`` centers in one copy plus every
      tile's 8 neighbor edges and corners straight into a VMEM window,
      inactive neighbors reading the zero row;
    * weights are stacked (L, 3, 3, Cmax_in, Cmax_out) and block-indexed
      by layer, so the pipeline prefetches layer l+1's weights while
      layer l computes; ``block`` tiles flatten into the GEMM M
      dimension."""
    n, th, tw, c0 = packed.shape
    chans = (c0,) + tuple(w.shape[-1] for w in ws)
    L = len(ws)
    if n == 0:
        return jnp.zeros((0, th, tw, chans[-1]), packed.dtype)
    _, tb, n_pad = balanced_split(n, block)
    cmax_i = contraction_width(max(chans[:-1]))
    cmax_o = max(chans[1:])
    cp = round_up(cmax_i, LANES)
    dt = packed.dtype
    wstack = jnp.stack([
        jnp.pad(w, ((0, 0), (0, 0), (0, cmax_i - w.shape[2]),
                    (0, cmax_o - w.shape[3]))) for w in ws])
    p0 = jnp.pad(packed, ((0, n_pad + 1 - n), (0, 0), (0, 0),
                          (0, cp - c0)))
    nbr_p = jnp.pad(nbr, ((0, n_pad - n), (0, 0)), constant_values=-1)
    nbr_p = jnp.where(nbr_p >= 0, nbr_p, n_pad).astype(jnp.int32)
    act_rows = n_pad + 1 if L > 1 else 1
    kernel = functools.partial(_roi_conv_stack_kernel, th=th, tw=tw,
                               chans=chans, tb=tb, n_pad=n_pad)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L, n_pad // tb),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 3, 3, cmax_i, cmax_o),
                         lambda p, b, nbr_ref: (p, 0, 0, 0, 0)),
        ],
        out_specs=[
            # only the last layer writes the output; earlier layers keep
            # its block index at 0 so nothing is written back for them
            pl.BlockSpec((tb, th, tw, chans[-1]),
                         lambda p, b, nbr_ref: (
                             jnp.where(p == L - 1, b, 0), 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((tb, th + 2, window_width(tw), cp), dt),
            pltpu.VMEM((tb, th, tw, cp), dt),
            pltpu.VMEM((1, th, tw, cp), dt),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, th, tw, chans[-1]), dt),
            jax.ShapeDtypeStruct((act_rows, th, tw, cp), dt),
            jax.ShapeDtypeStruct((act_rows, th, tw, cp), dt),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(nbr_p.reshape(-1), p0, wstack)
    return out[0][:n]


def roi_conv_packed(packed: jax.Array, w: jax.Array, nbr: jax.Array,
                    *, interpret: bool) -> jax.Array:
    """One packed-resident conv layer (no ReLU): packed (n, th, tw, Cin)
    previous layer's output; w: (3, 3, Cin, Cout); nbr: (n, 8) int32
    neighbor slots (-1 = zero halo, NEIGHBOR_OFFSETS order).  Returns
    packed (n, th, tw, Cout) — the SAME conv each active tile would see
    on the scattered full frame where inactive tiles are zero.  One tile
    per grid step, halo strips read from the neighbor rows; the layers
    of ``roi_conv_stack`` compute exactly this."""
    n, th, tw, cin = packed.shape
    cout = w.shape[-1]
    k = contraction_width(cin)
    w = jnp.pad(w, ((0, 0), (0, 0), (0, k - cin), (0, 0)))

    def kernel(nbr_ref, p_ref, w_ref, o_ref):
        i = pl.program_id(0)

        def strip(k, ys, ny, xs, nx):
            slot = nbr_ref[i, k]
            s = p_ref[pl.ds(jnp.maximum(slot, 0), 1), pl.ds(ys, ny),
                      pl.ds(xs, nx), :][0]
            return jnp.where(slot >= 0, s, jnp.zeros_like(s))

        center = p_ref[pl.ds(i, 1)][0]
        top = jnp.concatenate([strip(0, th - 1, 1, tw - 1, 1),
                               strip(1, th - 1, 1, 0, tw),
                               strip(2, th - 1, 1, 0, 1)], axis=1)
        mid = jnp.concatenate([strip(3, 0, th, tw - 1, 1), center,
                               strip(4, 0, th, 0, 1)], axis=1)
        bot = jnp.concatenate([strip(5, 0, 1, tw - 1, 1),
                               strip(6, 0, 1, 0, tw),
                               strip(7, 0, 1, 0, 1)], axis=1)
        win = jnp.concatenate([top, mid, bot], axis=0)[None]
        win = jnp.pad(win, ((0, 0), (0, 0), (0, 0), (0, k - cin)))
        o_ref[...] = _conv_taps(win, w_ref[...], th, tw, k).astype(
            o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((3, 3, k, cout), lambda i, nbr_ref: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, th, tw, cout),
                               lambda i, nbr_ref: (i, 0, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, th, tw, cout), packed.dtype),
        interpret=interpret,
    )(nbr, packed, w)
