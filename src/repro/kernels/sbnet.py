"""SBNet gather/scatter as Pallas TPU kernels (paper §4.4, TPU-adapted).

The paper's SBNet is a CUDA kernel: per-thread gather of active tile pixels
into a packed tensor, dense conv, then scatter back.  The TPU-native
formulation (DESIGN.md §2): the active-tile index list is *scalar-prefetched*
into SMEM and drives the BlockSpec index_map, so each grid step DMAs one
whole (th, tw, C) tile HBM->VMEM.  DMA granularity == tile granularity: no
per-element addressing (a VPU anti-pattern), and the packed output feeds the
MXU dense.

Both kernels are grid=(n_active,) with data-dependent block indexing — the
Pallas analogue of SBNet's tile-gather warp loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, x_ref, o_ref):
    # x_ref block = the (th, tw, C) tile selected by idx_ref[i]; copy to
    # packed slot i.  The DMA is issued by the BlockSpec machinery.
    o_ref[0] = x_ref[...]


def sbnet_gather(x: jax.Array, idx: jax.Array, th: int, tw: int,
                 *, interpret: bool) -> jax.Array:
    """x: (H, W, C), idx: (n, 2) int32 tile coords -> packed (n, th, tw, C)."""
    H, W, C = x.shape
    n = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((th, tw, C),
                         lambda i, idx_ref: (idx_ref[i, 0], idx_ref[i, 1], 0)),
        ],
        out_specs=pl.BlockSpec((1, th, tw, C),
                               lambda i, idx_ref: (i, 0, 0, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, th, tw, C), x.dtype),
        interpret=interpret,
    )(idx, x)


def sbnet_scatter(packed: jax.Array, idx: jax.Array, base: jax.Array,
                  *, interpret: bool) -> jax.Array:
    """packed: (n, th, tw, C) -> write tiles into ``base`` (H, W, C) at the
    tile positions in ``idx``; untouched regions keep base values (the
    output aliases ``base``)."""
    n, th, tw, C = packed.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, th, tw, C), lambda i, idx_ref: (i, 0, 0, 0)),
            # the base is only here to seed the aliased output; ANY keeps
            # the pipeline from DMAing the whole frame on every grid step
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((th, tw, C),
                               lambda i, idx_ref: (idx_ref[i, 0],
                                                   idx_ref[i, 1], 0)),
    )

    def kernel(idx_ref, p_ref, b_ref, o_ref):
        o_ref[...] = p_ref[0]

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(base.shape, base.dtype),
        input_output_aliases={2: 0},   # args: (idx, packed, base) -> out
        interpret=interpret,
    )(idx, packed, base)


def sbnet_scatter_fleet(packed: jax.Array, idx: jax.Array, base: jax.Array,
                        *, interpret: bool) -> jax.Array:
    """Cross-camera scatter: ONE launch materializes a whole camera group.

    packed: (n, th, tw, C); idx: (n, 3) int32 (cam, ty, tx); base:
    (num_cams, H, W, C) stacked frames.  Writes tile i into camera
    idx[i, 0]'s plane; untouched regions keep base values.

    One tile per grid step: the output BlockSpec's index map reads the
    scalar-prefetched (cam, ty, tx) row, so the pipeline writes each
    (th, tw, C) tile straight to its place in the aliased canvas.
    Repeated rows (callers pad with repeats of the last tile) rewrite
    identical bytes.

    An EMPTY tile set is a no-op: the base is returned untouched and no
    pallas_call is formed at all."""
    n, th, tw, C = packed.shape
    if n == 0:
        return base
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, th, tw, C), lambda i, idx_ref: (i, 0, 0, 0)),
            # aliased seed only — ANY avoids a whole-canvas DMA per step
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, th, tw, C),
                               lambda i, idx_ref: (idx_ref[3 * i],
                                                   idx_ref[3 * i + 1],
                                                   idx_ref[3 * i + 2], 0)),
    )

    def kernel(idx_ref, p_ref, b_ref, o_ref):
        o_ref[...] = p_ref[...]

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(base.shape, base.dtype),
        input_output_aliases={2: 0},   # args: (idx, packed, base) -> out
        interpret=interpret,
    )(idx.reshape(-1), packed, base)


def sbnet_scatter_changed(packed: jax.Array, idx: jax.Array,
                          base: jax.Array, *, interpret: bool) -> jax.Array:
    """Changed-only scatter into a PERSISTENT canvas: O(changed) bytes.

    Same store machinery as ``sbnet_scatter_fleet`` (scalar-prefetched
    (cam, ty, tx) rows, aliased/donated base), but the contract is
    different: ``base`` is the PREVIOUS step's device-resident
    head-map canvas and ``packed``/``idx`` carry ONLY the tiles whose
    content changed this step.  Unchanged tiles pass through untouched —
    their canvas bytes were written by the step that last computed them —
    so the composite result is bit-identical to re-scattering the whole
    active set while writing ``n_changed`` tiles instead of ``n_active``.
    An empty changed set returns the canvas with zero launches (the
    all-static step writes 0 canvas bytes)."""
    return sbnet_scatter_fleet(packed, idx, base, interpret=interpret)
