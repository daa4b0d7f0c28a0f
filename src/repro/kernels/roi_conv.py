"""RoI-sparse 3x3 convolution as Pallas TPU kernels.

``roi_conv_entry`` — the *entry* layer: convolution evaluated only on
active tiles, reading straight from the stacked frames.  grid =
(tile_block,); each grid step receives ``block`` haloed (th+2, tw+2,
Cin) windows, one element-indexed BlockSpec per window whose index map
reads the scalar-prefetched (cam, ty, tx) rows, so Mosaic's pipeline
DMAs the next block's windows while this one computes.  The 3x3 conv is
9 shifted (block*th*tw, Cin) @ (Cin, Cout) MXU matmuls.  This fuses
SBNet's gather into the first conv.

``roi_conv_stack`` — every *subsequent* layer in ONE launch: grid =
(layer, tile_block).  A layer's packed (n, th, tw, C) output stays in
HBM; the next layer DMAs each tile's center and the 1-deep edges of its
8 neighbors (an offline (n, 8) neighbor table, scalar-prefetched) into
a VMEM window, so the sparse representation never round-trips through a
full-frame scatter between layers.  Inactive or off-frame neighbors map
to an all-zero row: the zero halo the scatter-into-zeros path produced.

``roi_conv_stage`` — one stage of a strided residual backbone
(Darknet-53's) in ONE launch, over the same compacted rows: a 3x3
stride-2 down conv, then the stage's bottleneck blocks (1x1 conv, 3x3
conv, residual add), each conv with a bias and the activation.  grid =
(phase, tile_block), phase 0 the down conv, phase j the j-th block;
between phases the activations ping-pong through HBM as in the stack,
so every tile at one stride sees its neighbors' rims at the same layer.
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


# the activation after every conv of a backbone's stem and stages:
# Darknet's leaky ReLU
BACKBONE_ACT = "leaky0.1"


def activate(x: jax.Array, act: str) -> jax.Array:
    """A conv output through ``act``: ``"relu"`` or ``"leaky<slope>"``
    (``"leaky0.1"``: Darknet's leaky ReLU)."""
    if act == "relu":
        return jnp.maximum(x, 0.0)
    if act.startswith("leaky"):
        return jnp.maximum(x, float(act[5:]) * x)
    raise ValueError(f"unknown activation {act!r}")


def _dot(a: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(a.astype(jnp.float32), w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _conv_taps(win: jax.Array, w: jax.Array, th: int, tw: int,
               k: int, b=None) -> jax.Array:
    """(tb, th+2, >=tw+2, >=k) haloed windows -> (tb, th, tw, Cout): 9
    shifted (tb*th*tw, k) @ (k, Cout) float32 matmuls over the first k
    lanes (w: (3, 3, k, Cout)), plus the (1, Cout) bias ``b`` if given.
    Output rows are independent dot products, so a tile's values do not
    depend on the block it is computed in."""
    tb = win.shape[0]
    cout = w.shape[-1]
    acc = jnp.zeros((tb * th * tw, cout), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            patch = win[:, dy:dy + th, dx:dx + tw, :k].reshape(
                tb * th * tw, k)
            acc += _dot(patch, w[dy, dx])
    if b is not None:
        acc = acc + b
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


def _entry_kernel(idx_ref, *refs, th: int, tw: int, tb: int, act,
                  has_bias: bool):
    win_refs, w_ref = refs[:tb], refs[tb]
    b = refs[tb + 1][...] if has_bias else None
    o_ref = refs[-1]
    win = jnp.concatenate([r[...] for r in win_refs], axis=0)
    o = activate(_conv_taps(win, w_ref[...], th, tw, win.shape[-1], b),
                 act)
    o_ref[...] = o.astype(o_ref.dtype)


def roi_conv_entry(x: jax.Array, w: jax.Array, idx: jax.Array, th: int,
                   tw: int, *, block: int, interpret: bool, bias=None,
                   act="relu") -> jax.Array:
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
    tensor with no pallas_call at all.

    A backbone's stem gives a (Cout,) ``bias`` (folded batch norm) and
    its activation ``act`` (``"leaky0.1"``); both are static options of
    the kernel."""
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
                               act=act, has_bias=bias is not None)
    extra, extra_specs = (), []
    if bias is not None:
        extra = (bias.reshape(1, cout),)
        extra_specs = [pl.BlockSpec((1, cout), lambda b, idx_ref: (0, 0))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // tb,),
        in_specs=window_specs(tb, th, tw, cin) + [
            pl.BlockSpec((3, 3, cin, cout), lambda b, idx_ref: (0, 0, 0, 0)),
        ] + extra_specs,
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
    )(idx_p.reshape(-1), *([xp] * tb), w, *extra)
    return out[:n]


# ---------------------------------------------------------------------------
# the fused layer-stack megakernel
# ---------------------------------------------------------------------------

def _span(d: int, size: int, origin: int = 1):
    """(source slice, window slice) along one axis for neighbor offset
    ``d``, the tile's own pixels sitting at ``origin`` in the window:
    the neighbor above/left donates its last row/column, the one
    below/right its first, the tile itself its whole extent."""
    if d < 0:
        return pl.ds(size - 1, 1), pl.ds(origin - 1, 1)
    if d > 0:
        return pl.ds(0, 1), pl.ds(origin + size, 1)
    return pl.ds(0, size), pl.ds(origin, size)


# the neighbors whose first row/column a 3x3 stride-2 SAME conv reads
# (it pads one pixel after, none before): right, below, below-right
DOWN_NEIGHBORS = (4, 6, 7)


def _window_fetches(src, nbr_ref, win, sem, base: int, tb: int, th: int,
                    tw: int, ks=tuple(range(8)), origin: int = 1):
    """DMA the haloed windows of packed rows [base, base+tb) of ``src``
    ((n+1, th, tw, CP), row n all zero) into ``win`` (tb, >=th+2,
    >=tw+2, CP): the centers in one copy at (``origin``, ``origin``),
    then each tile's rims from the neighbors of ``ks``
    (``NEIGHBOR_OFFSETS`` columns; all 8 by default), from the rows the
    flattened (n*8,) neighbor table names."""
    halo = []
    for dy, dx in NEIGHBOR_OFFSETS:
        (sy, wy), (sx, wx) = _span(dy, th, origin), _span(dx, tw, origin)
        halo.append(((sy, sx), (wy, wx)))

    def center(start):
        return pltpu.make_async_copy(
            src.at[pl.ds(start, tb)],
            win.at[:, pl.ds(origin, th), pl.ds(origin, tw)], sem)

    def edge(j, k, row):
        (sy, sx), (wy, wx) = halo[k]
        return pltpu.make_async_copy(src.at[row, sy, sx],
                                     win.at[j, wy, wx], sem)

    def start(j, carry):
        for k in ks:
            edge(j, k, nbr_ref[8 * (base + j) + k]).start()
        return carry

    def wait(j, carry):
        for k in ks:
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
    (layer, tile_block).

    packed: (n, th, tw, C0) the entry layer's (relu'd) packed output;
    ws: list of (3, 3, C_l, C_{l+1}) weights; nbr: (n, 8) neighbor table
    (``neighbor_table`` / ``fleet_neighbor_table``, -1 = zero halo).
    Returns the last layer's packed (n, th, tw, C_last), bit-identical to
    N-1 successive one-layer launches (each layer is the SAME conv an
    active tile sees on the scattered full frame, inactive tiles zero —
    ``ref.roi_conv_packed`` — then ReLU):

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


# ---------------------------------------------------------------------------
# one stage of a strided residual backbone
# ---------------------------------------------------------------------------

def _down_taps(win_ref, w: jax.Array, b: jax.Array, th: int, tw: int,
               k: int) -> jax.Array:
    """3x3 stride-2 SAME conv of (tb, 2th+1, >=2tw+1, >=k) windows (the
    tile at the origin, one right/bottom rim) -> (tb, th, tw, Cout) plus
    the (1, Cout) bias: output pixel (i, j) reads input rows 2i..2i+2 and
    columns 2j..2j+2, 9 strided (tb*th*tw, k) @ (k, Cout) matmuls."""
    tb = win_ref.shape[0]
    cout = w.shape[-1]
    acc = jnp.zeros((tb * th * tw, cout), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            patch = win_ref[:, pl.ds(dy, th, stride=2),
                            pl.ds(dx, tw, stride=2), :][..., :k]
            acc += _dot(patch.reshape(tb * th * tw, k), w[dy, dx])
    return (acc + b).reshape(tb, th, tw, cout)


def _pointwise(y: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """1x1 conv plus bias: (tb, th, tw, Cin) @ (Cin, Cout) + (1, Cout)."""
    tb, th, tw, cin = y.shape
    out = _dot(y.reshape(tb * th * tw, cin), w) + b
    return out.reshape(tb, th, tw, w.shape[-1])


def _roi_conv_stage_kernel(nbr_ref, x_hbm, wd_ref, bd_ref, w1_ref, b1_ref,
                           w2_ref, b2_ref, o_ref, y_hbm, h_a, h_b, win_d,
                           win_h, ybuf, hst, zero, sems, *, t_in: int,
                           cin: int, cmid: int, cout: int, nb: int,
                           tb: int, n_pad: int):
    p = pl.program_id(0)
    b = pl.program_id(1)
    t = t_in // 2
    base = b * tb
    hs = (h_a, h_b)

    def emit(y, lc):
        """Phase ``lc``'s block output ``y``: the stage's output after
        the last block, else written to the residual buffer beside the
        next block's 1x1 conv output (its 3x3 conv's input)."""
        if lc == nb:
            o_ref[...] = y.astype(o_ref.dtype)
            return
        dst = hs[lc % 2]

        @pl.when(b == 0)
        def _():
            # lanes past cmid stay zero; row n_pad of ``dst`` is the
            # all-zero halo donor of inactive neighbors
            hst[...] = jnp.zeros(hst.shape, hst.dtype)
            zero[...] = jnp.zeros(zero.shape, zero.dtype)
            z = pltpu.make_async_copy(zero, dst.at[pl.ds(n_pad, 1)],
                                      sems.at[2])
            z.start()
            z.wait()

        ybuf[:, :, :, :cout] = y.astype(ybuf.dtype)
        out_y = pltpu.make_async_copy(ybuf, y_hbm.at[pl.ds(base, tb)],
                                      sems.at[1])
        out_y.start()
        h = activate(_pointwise(y, w1_ref[0], b1_ref[0]), BACKBONE_ACT)
        hst[:, :, :, :cmid] = h.astype(hst.dtype)
        out_h = pltpu.make_async_copy(hst, dst.at[pl.ds(base, tb)],
                                      sems.at[2])
        out_h.start()
        out_y.wait()
        out_h.wait()

    @pl.when(p == 0)
    def _():
        _window_fetches(x_hbm, nbr_ref, win_d, sems.at[0], base, tb, t_in,
                        t_in, ks=DOWN_NEIGHBORS, origin=0)
        y = activate(_down_taps(win_d, wd_ref[...], bd_ref[...], t, t,
                                wd_ref.shape[2]), BACKBONE_ACT)
        emit(y, 0)

    # phase lc runs block lc-1: its 3x3 conv reads the 1x1 output the
    # previous phase wrote (with the neighbors' rims), the residual its
    # own tile of the previous phase's output
    for lc in range(1, nb + 1):
        @pl.when(p == lc)
        def _(lc=lc):
            res = pltpu.make_async_copy(y_hbm.at[pl.ds(base, tb)], ybuf,
                                        sems.at[3])
            res.start()
            _window_fetches(hs[(lc - 1) % 2], nbr_ref, win_h, sems.at[0],
                            base, tb, t, t)
            z = activate(_conv_taps(win_h[...], w2_ref[0], t, t,
                                    w2_ref.shape[3], b2_ref[0]),
                         BACKBONE_ACT)
            res.wait()
            emit(ybuf[:, :, :, :cout] + z, lc)


def roi_conv_stage(packed: jax.Array, down, blocks, nbr: jax.Array, *,
                   block: int, interpret: bool) -> jax.Array:
    """One backbone stage over compacted rows in ONE ``pallas_call``.

    packed: (n, T, T, Cin) the previous stage's (or the stem's) packed
    output at stride s; down: (w (3, 3, Cin, Cout), b (Cout,)), the 3x3
    stride-2 down conv; blocks: ((w1 (1, 1, Cout, Cmid), b1, w2 (3, 3,
    Cmid, Cout), b2), ...), the bottleneck blocks; nbr: (n, 8) neighbor
    table (-1 = zero halo), which serves every stride since a tile keeps
    its neighbors.  Every conv adds its bias and applies act =
    ``BACKBONE_ACT``; a block returns y + act(conv3(act(conv1(y)))).
    Returns the stage's packed (n, T/2, T/2, Cout) at stride 2s.

    * grid = (phase, tile_block), phase outer: phase 0 is the down conv,
      which reads each tile plus the first row/column of its right,
      lower and lower-right neighbors (SAME padding at stride 2 pads one
      pixel after, none before); phase j is block j-1, whose 3x3 conv
      reads the 1-px rims of all 8 neighbors;
    * between phases the activations live in HBM: the residual stream
      (n, T/2, T/2, CP) in place (a tile's residual is read only by its
      own tile), the 1x1 outputs in two ping-pong buffers (n+1, T/2,
      T/2, CP) whose last row stays zero;
    * block weights are stacked and block-indexed by phase, so the
      pipeline prefetches the next block's weights; ``block`` tiles
      flatten into the GEMM M dimension."""
    n, t_in = packed.shape[0], packed.shape[1]
    wd, bd = down
    cin, cout = wd.shape[2], wd.shape[3]
    nb = len(blocks)
    if nb < 1:
        raise ValueError("a stage needs at least one block")
    cmid = blocks[0][0].shape[-1]
    t = t_in // 2
    if n == 0:
        return jnp.zeros((0, t, t, cout), packed.dtype)
    _, tb, n_pad = balanced_split(n, block)
    cp_in, cp_mid, cp_out = (round_up(c, LANES) for c in (cin, cmid, cout))
    dt = packed.dtype
    kin, kmid = contraction_width(cin), contraction_width(cmid)
    wd = jnp.pad(wd, ((0, 0), (0, 0), (0, kin - cin), (0, 0)))
    w1 = jnp.stack([w1.reshape(cout, cmid) for w1, _, _, _ in blocks])
    b1 = jnp.stack([b1.reshape(1, cmid) for _, b1, _, _ in blocks])
    w2 = jnp.stack([jnp.pad(w2, ((0, 0), (0, 0), (0, kmid - cmid), (0, 0)))
                    for _, _, w2, _ in blocks])
    b2 = jnp.stack([b2.reshape(1, cout) for _, _, _, b2 in blocks])
    x0 = jnp.pad(packed, ((0, n_pad + 1 - n), (0, 0), (0, 0),
                          (0, cp_in - cin)))
    nbr_p = jnp.pad(nbr, ((0, n_pad - n), (0, 0)), constant_values=-1)
    nbr_p = jnp.where(nbr_p >= 0, nbr_p, n_pad).astype(jnp.int32)
    kernel = functools.partial(
        _roi_conv_stage_kernel, t_in=t_in, cin=cin, cmid=cmid, cout=cout,
        nb=nb, tb=tb, n_pad=n_pad)

    def w1_index(p, b, nbr_ref):        # block p's 1x1 runs in phase p
        return (jnp.minimum(p, nb - 1), 0, 0)

    def w2_index(p, b, nbr_ref):        # block p-1's 3x3 in phase p
        return (jnp.maximum(p - 1, 0), 0, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb + 1, n_pad // tb),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((3, 3, kin, cout),
                         lambda p, b, nbr_ref: (0, 0, 0, 0)),
            pl.BlockSpec((1, cout), lambda p, b, nbr_ref: (0, 0)),
            pl.BlockSpec((1, cout, cmid), w1_index),
            pl.BlockSpec((1, 1, cmid), w1_index),
            pl.BlockSpec((1, 3, 3, kmid, cout), w2_index),
            pl.BlockSpec((1, 1, cout), lambda p, b, nbr_ref: (
                jnp.maximum(p - 1, 0), 0, 0)),
        ],
        out_specs=[
            # only the last phase writes the output; earlier phases keep
            # its block index at 0 so nothing is written back for them
            pl.BlockSpec((tb, t, t, cout),
                         lambda p, b, nbr_ref: (
                             jnp.where(p == nb, b, 0), 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((tb, t_in + 1, round_up(t_in + 1, 8), cp_in), dt),
            pltpu.VMEM((tb, t + 2, window_width(t), cp_mid), dt),
            pltpu.VMEM((tb, t, t, cp_out), dt),
            pltpu.VMEM((tb, t, t, cp_mid), dt),
            pltpu.VMEM((1, t, t, cp_mid), dt),
            pltpu.SemaphoreType.DMA((4,)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, t, t, cout), dt),
            jax.ShapeDtypeStruct((n_pad, t, t, cp_out), dt),
            jax.ShapeDtypeStruct((n_pad + 1, t, t, cp_mid), dt),
            jax.ShapeDtypeStruct((n_pad + 1, t, t, cp_mid), dt),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(nbr_p.reshape(-1), x0, wd, bd.reshape(1, cout), w1, b1, w2, b2)
    return out[0][:n]
