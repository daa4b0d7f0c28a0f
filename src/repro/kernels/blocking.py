"""Shared tile-block and window arithmetic for the blocked kernel walks.

Every blocked walk (``roi_conv_entry``, ``roi_conv_stack``,
``tile_delta_gate``) splits its ragged n-tile index space the same way:
as many grid steps as the VMEM cap demands, then equal-size blocks —
minimal padding (vs up to 2x duplicate tiles when n is just past a block
multiple) — with the pad rows repeating the LAST real row so duplicate
work is inert (duplicate outputs are sliced off).  One implementation
keeps the "bit-identical to the per-tile walk" contract from diverging
per kernel.

The haloed (th+2, tw+2) input windows are fetched by Mosaic's pipeline
as element-indexed blocks, whose second-minor extent must be a multiple
of 8: a window is therefore ``window_width(tw)`` columns wide (the
columns past tw+2 are fetched and never read), and the padded frames
carry that many extra zero columns on the right so the last tile's
window stays in bounds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# lane width of a TPU vector register: the minor dimension of every
# array a kernel slices with a manual DMA is padded to a multiple of it
LANES = 128
# Mosaic's scoped-VMEM limit for the served kernels.  ``ops.choose_block``
# sizes tile blocks against 3/4 of it.
VMEM_LIMIT_BYTES = 32 * 2 ** 20


def round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def balanced_split(n: int, block: int) -> "tuple[int, int, int]":
    """(num_blocks, tile_block, padded_n) for an n-tile walk capped at
    ``block`` tiles per grid step.  n == 0 yields (1, 1, 0)."""
    nb = -(-max(n, 1) // max(block, 1))
    tb = -(-max(n, 1) // nb)
    return nb, tb, (nb * tb if n else 0)


def pad_repeat_last(arr: jax.Array, n_pad: int) -> jax.Array:
    """Pad ``arr`` to ``n_pad`` leading rows by repeating its last row."""
    n = arr.shape[0]
    if n_pad <= n:
        return arr
    return jnp.concatenate(
        [arr, jnp.broadcast_to(arr[-1:], (n_pad - n,) + arr.shape[1:])])


def window_width(tw: int) -> int:
    """Columns fetched per haloed window: tw+2 rounded up to 8."""
    return round_up(tw + 2, 8)


def pad_frames(x: jax.Array, tw: int) -> jax.Array:
    """(C, H, W, Cin) stacked frames -> zero-padded (C, H+2, W+2+e, Cin):
    the 1-px SAME-conv ring plus the ``e`` extra right columns a
    ``window_width`` fetch of the last tile column reads."""
    e = window_width(tw) - (tw + 2)
    return jnp.pad(x, ((0, 0), (1, 1), (1, 1 + e), (0, 0)))


def widen_padded(xp: jax.Array, tw: int) -> jax.Array:
    """Give ring-padded (C, H+2, W', Cin) frames the extra right columns
    of ``pad_frames`` (a no-op when they are already there): the window
    of the last tile column that fits in W'-2 must end in bounds, since
    the device reads out of bounds silently."""
    last = (xp.shape[2] - 2) // tw - 1
    need = last * tw + window_width(tw)
    if xp.shape[2] >= need:
        return xp
    return jnp.pad(xp, ((0, 0), (0, 0), (0, need - xp.shape[2]), (0, 0)))
