"""Public jit'd wrappers around the Pallas kernels.

Handles the framework-facing conveniences: mask -> index-list conversion,
neighbor-table construction for the packed-resident conv chain, padding to
hardware-aligned block counts, batching (vmap), and how a kernel runs:
compiled by Mosaic on a TPU, through the Pallas interpreter when JAX's
default backend is the CPU (the test suite).  The choice is made per call
from the backend, so importing this module initialises no backend.

Every public wrapper bumps ``KERNEL_COUNTS[name]`` *outside* the jit
boundary, so tests and benchmarks can assert structural properties of the
hot path — e.g. that an N-layer RoI conv stack performs exactly one gather
and one scatter (see serving/detector.RoIDetector.roi_forward).
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.blocking import (LANES, VMEM_LIMIT_BYTES, pad_frames,
                                    round_up, window_width)
from repro.obs import metrics as obs_metrics
from repro.kernels.roi_attention import (PAD_POS, block_min_positions,
                                         roi_attention as _roi_attn)
from repro.kernels.roi_conv import (MAX_WINDOWS_PER_STEP, NEIGHBOR_OFFSETS,
                                    roi_conv_entry as _roi_conv_entry,
                                    roi_conv_stack as _roi_conv_stack,
                                    roi_conv_stage as _roi_conv_stage)
from repro.kernels.sbnet import sbnet_gather as _gather, \
    sbnet_scatter as _scatter, sbnet_scatter_changed as _scatter_changed, \
    sbnet_scatter_fleet as _scatter_fleet
from repro.kernels.tile_delta import (COEF_BITS, GATE_BODY_BYTES,
                                      GATE_WIN_BYTES, GATE_WIN_EXACT,
                                      RUN_BITS, STATS_WIDTH,
                                      tile_delta as _tile_delta,
                                      tile_delta_gate_canvas as
                                      _tile_delta_gate_canvas,
                                      tile_delta_halo as _tile_delta_halo)


def interpret_mode() -> bool:
    """Run Pallas kernels through the interpreter?  Only when JAX's
    default backend is the CPU; on a TPU every kernel is compiled by
    Mosaic.  Asked per call, never at import."""
    return jax.default_backend() == "cpu"


# kernel-dispatch counter: wrapper name -> number of pallas_call launches
# issued from Python.  Process-lifetime totals; each launch is counted once
# regardless of jit caching.  Reset with KERNEL_COUNTS.clear() around a
# region of interest, or — the concurrency-safe way — open a
# ``count_kernels()`` region: regions live on a contextvar stack, so a
# dispatch issued from another thread or async task can NEVER leak into a
# region it is not lexically inside (the sharded fleet runtime and the
# async dispatch pipeline rely on this; the bare global is kept for the
# single-threaded consumers that predate them).
KERNEL_COUNTS: collections.Counter = collections.Counter()

_COUNT_LOCK = threading.Lock()
# per-context stack of open count_kernels() regions.  contextvars give
# thread- AND task-local isolation: a region opened on the main thread is
# invisible to dispatches made from a pipeline worker thread, and vice
# versa — which is exactly the trust property dispatch-ceiling assertions
# need under concurrent shard/async execution.
_COUNT_STACK: contextvars.ContextVar = contextvars.ContextVar(
    "repro_kernel_count_stack", default=())


def record_dispatch(name: str, n: int = 1) -> None:
    """Count ``n`` kernel launches under ``name``: bumps the process-wide
    ``KERNEL_COUNTS`` and every ``count_kernels()`` region open in THIS
    context.  Every public wrapper below calls this; runtimes that launch
    raw kernels themselves (the shard_map'd fleet step dispatches one SPMD
    program that runs the kernel once on every shard) call it directly so
    dispatch-structure assertions see their launches too.

    ``name`` must come from the canonical ``obs.metrics.KERNEL_NAMES``
    set — a typo'd counter name raises here instead of silently counting
    zero forever.  When observability is enabled the same bump lands on
    the ``obs`` ``kernel_dispatches`` counter family (label
    ``kernel=name``), bit-compatible with this module's counters over
    the same window."""
    if name not in obs_metrics.KERNEL_NAMES:
        raise ValueError(
            f"unknown kernel counter {name!r}: dispatch names must come "
            f"from obs.metrics.KERNEL_NAMES")
    with _COUNT_LOCK:
        KERNEL_COUNTS[name] += n
        for region in _COUNT_STACK.get():
            region[name] += n
    obs_metrics.KERNEL_DISPATCHES.inc(n, kernel=name)


@contextlib.contextmanager
def count_kernels():
    """Isolated dispatch-count region: ``with count_kernels() as c: ...``.

    ``c`` accumulates exactly the dispatches issued from inside the
    region *in this thread/async context* — counts from earlier work, or
    from other threads dispatching concurrently, cannot corrupt it.  The
    global ``KERNEL_COUNTS`` keeps accumulating independently (it is
    never cleared or restored here), and an enclosing region still
    observes every inner dispatch, so nesting composes.  ``c`` is live
    during the region and final at exit."""
    region: collections.Counter = collections.Counter()
    token = _COUNT_STACK.set(_COUNT_STACK.get() + (region,))
    try:
        yield region
    finally:
        _COUNT_STACK.reset(token)


def mask_to_indices(grid: np.ndarray) -> np.ndarray:
    """Bool (ty, tx) RoI grid -> (n, 2) int32 active-tile coords (static:
    computed offline from the RoI mask, exactly like SBNet's reduce_mask)."""
    ys, xs = np.nonzero(grid)
    return np.stack([ys, xs], axis=1).astype(np.int32)


def neighbor_table(idx: np.ndarray, grid_shape) -> np.ndarray:
    """(n, 2) active-tile coords -> (n, 8) int32 packed-slot neighbor table.

    Column j is the packed slot of the neighbor at NEIGHBOR_OFFSETS[j]
    (NW, N, NE, W, E, SW, S, SE), or -1 when that neighbor is inactive or
    off-frame — the packed conv kernel substitutes a zero halo there,
    matching what the scatter-into-zeros path would have produced.  Static:
    computed offline from the RoI mask, once per mask lifetime.
    """
    idx = np.asarray(idx)
    ty_max, tx_max = grid_shape
    slot = {(int(y), int(x)): i for i, (y, x) in enumerate(idx)}
    nbr = np.full((idx.shape[0], 8), -1, np.int32)
    for i, (y, x) in enumerate(idx):
        for j, (dy, dx) in enumerate(NEIGHBOR_OFFSETS):
            ny, nx = int(y) + dy, int(x) + dx
            if 0 <= ny < ty_max and 0 <= nx < tx_max:
                nbr[i, j] = slot.get((ny, nx), -1)
    return nbr


# ---------------------------------------------------------------------------
# fleet (multi-camera group) index plumbing
# ---------------------------------------------------------------------------

def fleet_indices(grids) -> "tuple[np.ndarray, np.ndarray]":
    """Per-camera bool grids -> one packed index space for the whole group.

    grids: sequence of (tiles_y, tiles_x) bool RoI grids, one per camera.
    Returns (idx (n, 3) int32 rows of (cam, ty, tx), offsets (C+1,) int64):
    camera c's tiles occupy packed slots [offsets[c], offsets[c+1]), in the
    same row-major order ``mask_to_indices`` would give per camera — so the
    fleet-packed tensor is the per-camera packed tensors concatenated."""
    rows = []
    offsets = np.zeros(len(grids) + 1, np.int64)
    for c, grid in enumerate(grids):
        ys, xs = np.nonzero(np.asarray(grid, bool))
        offsets[c + 1] = offsets[c] + ys.size
        rows.append(np.stack([np.full(ys.size, c), ys, xs], axis=1))
    idx = (np.concatenate(rows, axis=0) if rows
           else np.zeros((0, 3))).astype(np.int32)
    return idx, offsets


def fleet_neighbor_table(grids) -> np.ndarray:
    """(n, 8) neighbor table for the concatenated fleet packing.

    Each camera's table is built on its OWN grid (off-frame and inactive
    neighbors are -1) and its slots are shifted by the camera's packed
    offset — a tile's halo can therefore only ever reference slots of the
    same camera, so halos never leak across cameras by construction."""
    tables = []
    off = 0
    for grid in grids:
        grid = np.asarray(grid, bool)
        idx = mask_to_indices(grid)
        nbr = neighbor_table(idx, grid.shape)
        nbr[nbr >= 0] += off
        off += idx.shape[0]
        tables.append(nbr)
    if not tables:
        return np.zeros((0, 8), np.int32)
    return np.concatenate(tables, axis=0).astype(np.int32)


def superlaunch_tables(grids_per_group):
    """Fleet-flat index space over ALL groups' cameras — the super-launch.

    grids_per_group: sequence of per-group camera-grid lists.  Flattens
    every camera of every group into ONE (flat_cam, ty, tx) index space:
    returns (idx (n, 3) int32, nbr (n, 8) int32, tile_offsets (F+1,),
    cam_starts (K+1,)) where F is the flat camera count and group g's
    cameras are flat cams [cam_starts[g], cam_starts[g+1]).  Slot offsets
    are per flat camera (``fleet_neighbor_table``), so halos are leak-free
    across cameras AND across groups by construction — group boundaries
    are just camera boundaries in the flat space."""
    flat = [g for gs in grids_per_group for g in gs]
    idx, tile_offsets = fleet_indices(flat)
    nbr = fleet_neighbor_table(flat)
    cam_starts = np.cumsum([0] + [len(gs) for gs in grids_per_group]) \
        .astype(np.int64)
    return idx, nbr, tile_offsets, cam_starts


# ---------------------------------------------------------------------------
# shard planning: group -> device-shard assignment (placement-free)
# ---------------------------------------------------------------------------

class ShardPlan:
    """Placement-free assignment of camera groups to mesh shards.

    ``superlaunch_tables`` stays device-agnostic (flat tables over any
    group subset); the plan is the SEPARATE object that says which groups
    land on which shard.  Balanced by ACTIVE-TILE count, not group count
    — one busy intersection cannot straggle a shard behind the others —
    via longest-processing-time greedy (sort groups by tile count
    descending, place each on the least-loaded shard), which carries the
    classic LPT bound: max shard load <= mean load + max single-group
    load.  Groups keep their offered order WITHIN a shard, so per-shard
    flat tables are ``superlaunch_tables`` of an order-preserving
    subsequence."""

    def __init__(self, assignment: np.ndarray, tile_counts: np.ndarray,
                 n_shards: int):
        self.assignment = np.asarray(assignment, np.int64)   # (K,)
        self.tile_counts = np.asarray(tile_counts, np.int64)  # (K,)
        self.n_shards = int(n_shards)

    @property
    def n_groups(self) -> int:
        return int(self.assignment.shape[0])

    def shard_groups(self, s: int) -> "list[int]":
        """Group positions assigned to shard ``s``, in offered order."""
        return [int(i) for i in np.nonzero(self.assignment == s)[0]]

    @property
    def shard_tiles(self) -> np.ndarray:
        """(S,) active tiles per shard."""
        out = np.zeros(self.n_shards, np.int64)
        np.add.at(out, self.assignment, self.tile_counts)
        return out

    @property
    def imbalance(self) -> float:
        """max/mean shard tile load (1.0 = perfectly balanced)."""
        loads = self.shard_tiles
        mean = float(loads.mean()) if loads.size else 0.0
        return float(loads.max()) / mean if mean > 0 else 1.0


def shard_plan(grids_per_group, n_shards: int) -> ShardPlan:
    """Plan the group -> shard assignment for a sharded super-launch.

    grids_per_group: sequence of per-group camera-grid lists (the same
    argument ``superlaunch_tables`` takes).  Deterministic: ties broken
    by group position."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    tiles = np.array([sum(int(np.count_nonzero(np.asarray(g, bool)))
                          for g in gs) for gs in grids_per_group],
                     np.int64)
    order = np.argsort(-tiles, kind="stable")       # LPT: biggest first
    loads = np.zeros(n_shards, np.int64)
    assignment = np.zeros(tiles.shape[0], np.int64)
    for gi in order:
        s = int(np.argmin(loads))                   # least-loaded shard
        assignment[gi] = s
        loads[s] += tiles[gi]
    return ShardPlan(assignment, tiles, n_shards)


# ---------------------------------------------------------------------------
# temporal reuse: changed-set dilation + compaction (host-side, static)
# ---------------------------------------------------------------------------

def dilate_changed(changed: np.ndarray, nbr: np.ndarray) -> np.ndarray:
    """One morphological dilation of a per-tile bool set through the
    (n, 8) neighbor table: a tile joins the set when any of its in-table
    neighbors is in it.  The table never references another camera's
    slots (``fleet_neighbor_table`` offsets are per camera), so dilation
    respects camera — and therefore group — boundaries by construction."""
    changed = np.asarray(changed, bool)
    if changed.size == 0:
        return changed
    nbr = np.asarray(nbr)
    safe = np.clip(nbr, 0, changed.size - 1)
    return changed | (changed[safe] & (nbr >= 0)).any(axis=1)


def rf_px(layers, start: int = 0) -> int:
    """Receptive-field radius in frame pixels of a detector's layer list
    (``DetectorConfig.layers``): how far beyond its own pixels (a block
    of ``stride_out`` frame pixels) a head pixel's input reaches, the
    larger of the two sides, over every path of the graph.  Layers
    before index ``start`` are taken to reach nothing.

    Under SAME padding a k x k layer of stride s pads ``(k - s) // 2``
    before and ``k - s - (k - s) // 2`` after, so output pixel j reads
    input pixels ``j*s - lo`` to ``j*s - lo + k - 1``: it reaches ``lo``
    input pixels before its own and ``k - s - lo`` after, each
    ``stride_in`` frame pixels wide.  An ``add`` reaches as far as the
    farthest of its inputs."""
    reach = {"frame": (0, 0)}
    out = 0
    for i, layer in enumerate(layers):
        before = max(reach[n][0] for n in layer["inputs"])
        after = max(reach[n][1] for n in layer["inputs"])
        if layer["op"] != "add" and i >= start:
            k, s, s_in = layer["k"], layer["stride"], layer["stride_in"]
            lo = max(k - s, 0) // 2
            before += s_in * lo
            after += s_in * (k - s - lo)
        reach[layer["name"]] = (before, after)
        if layer["op"] == "head":
            out = max(out, before, after)
    return out


def halo_rings(layers, tile: int) -> int:
    """Tile rings a change can cross on its way to the heads, from the
    receptive field of the detector's layer list.

    The entry layer reads haloed frame windows, and the delta gate
    compares those whole windows, so the entry moves nothing across
    tiles.  Every later layer moves a change — and the zero halo that
    compaction puts at the border of the compute set — as far as its
    reach: ``rf_px(layers, start=1)`` frame pixels in all, which crosses
    ``ceil(rf / tile)`` tile rings (0 for a one-layer net; 1 for three
    3x3 stride-1 layers on 16-px tiles; 3 for Darknet-53 through its
    stride-8 stage on 32-px tiles, whose head reaches 81 px beyond the
    entry).  A strided layer reads a rim of at most one map pixel from
    each neighbor, so one 8-neighbor table serves every stride."""
    return -(-rf_px(layers, start=1) // tile)


def reuse_sets(raw_changed: np.ndarray, nbr: np.ndarray,
               rings: int) -> "tuple[np.ndarray, np.ndarray]":
    """The delta gate's receptive-field bookkeeping.  ``raw_changed``
    marks tiles whose ENTRY-LAYER INPUT (the haloed window) changed;
    ``rings`` is ``halo_rings`` of the net.  Returns (changed_out,
    compute) bool masks:

    * ``changed_out`` — tiles whose FINAL-layer output may differ: the
      raw set dilated ``rings`` times (a reused tile is only bit-safe
      if no change reaches it through the packed layers' halos).
    * ``compute`` — the tiles the compact launch must convolve:
      ``changed_out`` dilated ``rings`` more times.  The margin absorbs
      the zero-halo error of compaction: a compact neighbor table
      zero-halos active tiles outside the set, and that error walks one
      pixel inward per packed layer, the same distance a change walks
      outward, so every ``changed_out`` tile (``rings`` tiles from the
      compute boundary by construction) is bit-exact.  Margin tiles are
      computed and DISCARDED (the cache keeps their old, still-valid
      values)."""
    changed = np.asarray(raw_changed, bool)
    for _ in range(rings):
        changed = dilate_changed(changed, nbr)
    compute = changed
    for _ in range(rings):
        compute = dilate_changed(compute, nbr)
    return changed, compute


def compact_tables(idx: np.ndarray, nbr: np.ndarray, keep: np.ndarray
                   ) -> "tuple[np.ndarray, np.ndarray]":
    """Compact the superlaunch tables to the kept tiles: returns
    (idx[keep], remapped (k, 8) neighbor table).  Kept neighbors are
    renumbered to compact slots; dropped or inactive neighbors become -1
    (zero halo) — the compaction the reuse margin is sized for."""
    idx = np.asarray(idx)
    nbr = np.asarray(nbr)
    keep = np.asarray(keep, bool)
    n = idx.shape[0]
    pos = np.full(n, -1, np.int64)
    pos[keep] = np.arange(int(keep.sum()))
    cnbr = np.where(nbr >= 0, pos[np.clip(nbr, 0, max(n - 1, 0))],
                    -1).astype(np.int32)
    return idx[keep].astype(np.int32), cnbr[keep]


def launch_rows(k: int, n: int) -> int:
    """The rows a warm launch of ``k`` compacted tiles is padded to: the
    smallest of four evenly spaced sizes per octave at or above ``k``
    (``p/2 + j*p/8``, j = 1..4, with ``p`` the next power of two >= k;
    below 8 rows, ``p`` itself), at most ``n``, the active tile count,
    so a compute set near the whole fleet reuses the cold step's shapes.
    The padding is under k/4 from 8 rows up, the jit caches key on at
    most four shapes an octave, and from 512 rows up every bucket is a
    multiple of 64 (``MAX_STAGE_BLOCK``), so no kernel pads it again."""
    p = 1 << max(k - 1, 0).bit_length()
    if p > 8:
        step = p // 8
        p = -(-k // step) * step
    return min(p, max(k, n))


def choose_block(th: int, tw: int, c: int, n_layers: int,
                 vmem_bytes: int = VMEM_LIMIT_BYTES * 3 // 4,
                 dtype_bytes: int = 4) -> int:
    """Size the entry/stack/gate ``block`` (tiles per grid step) from a
    VMEM budget.

    Every buffer occupies whole (8, 128) vector tiles, so the channel
    dim counts as ``round_up(c, 128)`` lanes and the column dim is
    rounded up to 8 sublanes.  Per resident tile a kernel holds its
    haloed window ((th+2) x ``window_width(tw)``) plus about 17 tile-
    sized buffers: the nine shifted tap patches, their products, the
    accumulator and the double-buffered output block.  That model
    matches what Mosaic allocates for the entry and stack kernels at
    the default widths (about 2.4 MB per 16x16 tile, compiled for a
    v5e).  The weight plane is (3, 3, C, C) x2 for the pipeline's
    layer-(l+1) prefetch (a 1-layer net has no stack weights).  The
    block is the largest power of two that fits, at most
    ``MAX_WINDOWS_PER_STEP`` (the entry and the gate fetch one pipelined
    window per tile), floored at 1 so degenerate budgets still
    launch."""
    lanes = round_up(max(int(c), 1), LANES)
    window = (th + 2) * window_width(tw) * lanes
    tile = th * round_up(tw, 8) * lanes
    per_tile = (window + 17 * tile) * dtype_bytes
    weights = ((2 if n_layers > 1 else 1) * 9 * round_up(max(int(c), 1), 8)
               * lanes * dtype_bytes)
    return _fit_block(per_tile, weights, vmem_bytes, MAX_WINDOWS_PER_STEP)


def _fit_block(per_tile: int, weights: int, vmem_bytes: int,
               cap: int) -> int:
    """The largest power of two of tiles whose buffers fit beside the
    weights, at most ``cap``, floored at 1."""
    budget = int(vmem_bytes) - weights
    if budget < per_tile:
        return 1
    tb = budget // per_tile
    block = 1
    while block * 2 <= tb and block < cap:
        block *= 2
    return block


# tiles per grid step of ``roi_conv_stage`` at most: its windows arrive
# by manual DMA, so no per-window pipelined operand caps it
MAX_STAGE_BLOCK = 64


def stage_block(t_in: int, cin: int, cmid: int, cout: int,
                vmem_bytes: int = VMEM_LIMIT_BYTES * 3 // 4,
                dtype_bytes: int = 4) -> int:
    """Size one ``roi_conv_stage`` launch's ``block`` (tiles per grid
    step) from its own widths, on ``choose_block``'s model: per resident
    tile its down-conv window ((t_in+1) rows) and its 3x3 window at the
    output side t = t_in/2, plus about 17 output-tile buffers at the
    widest of its layers (taps, products, accumulator, residual and
    staging buffers); the weight planes of the down conv and of one
    block twice over (the pipeline prefetches the next block's)."""
    t = t_in // 2
    lin, lmid, lout = (round_up(c, LANES) for c in (cin, cmid, cout))
    win_d = (t_in + 1) * round_up(t_in + 1, 8) * lin
    win_h = (t + 2) * window_width(t) * lmid
    tile = t * round_up(t, 8) * max(lmid, lout)
    per_tile = (win_d + win_h + 17 * tile) * dtype_bytes
    weights = 2 * (9 * round_up(cin, 8) * lout + 9 * round_up(cmid, 8)
                   * lout + round_up(cout, 8) * lmid) * dtype_bytes
    return _fit_block(per_tile, weights, vmem_bytes, MAX_STAGE_BLOCK)


# ---------------------------------------------------------------------------
# jit'd kernel entry points (private) + counting public wrappers
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("th", "tw", "interpret"))
def _sbnet_gather_jit(x, idx, th, tw, interpret):
    return _gather(x, idx, th, tw, interpret=interpret)


def sbnet_gather(x: jax.Array, idx: jax.Array, th: int,
                 tw: int) -> jax.Array:
    """(H, W, C) + (n, 2) tile coords -> packed (n, th, tw, C)."""
    record_dispatch("sbnet_gather")
    return _sbnet_gather_jit(x, idx, th, tw, interpret_mode())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sbnet_scatter_jit(packed, idx, base, interpret):
    return _scatter(packed, idx, base, interpret=interpret)


def sbnet_scatter(packed: jax.Array, idx: jax.Array,
                  base: jax.Array) -> jax.Array:
    """Packed tiles -> full map, untouched regions keep ``base`` values."""
    record_dispatch("sbnet_scatter")
    return _sbnet_scatter_jit(packed, idx, base, interpret_mode())


@functools.partial(jax.jit, static_argnames=("th", "tw", "block", "act",
                                             "interpret"))
def _roi_conv_entry_jit(x, w, idx, th, tw, block, interpret, bias=None,
                        act="relu"):
    return _roi_conv_entry(x, w, idx, th, tw, block=block,
                           interpret=interpret, bias=bias, act=act)


def roi_conv_entry(x: jax.Array, w: jax.Array, idx: jax.Array, th: int,
                   tw: int, block: int = 1, bias=None,
                   act: str = "relu") -> jax.Array:
    """Fleet-flat fused gather+conv+relu over any number of cameras (and
    groups): (C, H, W, Cin) stacked frames + (n, 3) (flat_cam, ty, tx)
    coords -> relu'd packed (n, th, tw, Cout) — the fused backbone's
    entry layer, feeding ``roi_conv_stack`` (or ``roi_conv_stage``).
    ``block`` tiles share a grid step (``choose_block`` sizes it against
    VMEM), one GEMM per tap per block, bit-identical to the per-tile
    walk.  A backbone's stem adds its (Cout,) ``bias`` and applies its
    ``act`` (``"leaky0.1"``) in place of the ReLU.  An empty compute set
    is NOT a dispatch: zero tiles return an empty packed tensor with no
    launch formed and no counter bump."""
    if idx.shape[0] == 0:
        return jnp.zeros((0, th, tw, w.shape[-1]), x.dtype)
    record_dispatch("roi_conv_entry")
    return _roi_conv_entry_jit(x, w, idx, th, tw, int(block),
                               interpret_mode(), bias=bias, act=act)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _roi_conv_stack_jit(packed, ws, nbr, block, interpret):
    return _roi_conv_stack(packed, ws, nbr, block=block,
                           interpret=interpret)


def roi_conv_stack(packed: jax.Array, ws, nbr: jax.Array,
                   block: int = MAX_WINDOWS_PER_STEP) -> jax.Array:
    """The fused layer-stack megakernel: the whole packed conv chain
    (conv + relu per layer, halos DMA'd from the neighbor rows, weight
    prefetch for layer l+1 during layer l) in ONE dispatch —
    bit-identical to N-1 successive one-layer launches."""
    record_dispatch("roi_conv_stack")
    return _roi_conv_stack_jit(packed, tuple(ws), nbr, int(block),
                               interpret_mode())


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _roi_conv_stage_jit(packed, down, blocks, nbr, block, interpret):
    return _roi_conv_stage(packed, down, blocks, nbr, block=block,
                           interpret=interpret)


def roi_conv_stage(packed: jax.Array, down, blocks, nbr: jax.Array,
                   block: int = 1) -> jax.Array:
    """One strided residual backbone stage in ONE dispatch: the 3x3
    stride-2 down conv of ``down`` = (w, b), then the bottleneck
    ``blocks`` = ((w1, b1, w2, b2), ...), over the packed (n, T, T, Cin)
    rows and the (n, 8) neighbor table of the previous stage -> packed
    (n, T/2, T/2, Cout) (``kernels/roi_conv.roi_conv_stage``).  ``block``
    tiles share a grid step (``stage_block`` sizes it).  An empty row set
    is NOT a dispatch."""
    cout = down[0].shape[-1]
    t = packed.shape[1] // 2
    if packed.shape[0] == 0:
        return jnp.zeros((0, t, t, cout), packed.dtype)
    record_dispatch("roi_conv_stage")
    return _roi_conv_stage_jit(packed, tuple(down),
                               tuple(tuple(b) for b in blocks), nbr,
                               int(block), interpret_mode())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sbnet_scatter_fleet_jit(packed, idx, base, interpret):
    return _scatter_fleet(packed, idx, base, interpret=interpret)


def sbnet_scatter_fleet(packed: jax.Array, idx: jax.Array,
                        base: jax.Array) -> jax.Array:
    """Cross-camera scatter: packed group tiles -> (C, H, W, Cout) stacked
    frames in ONE launch; untouched regions keep ``base`` values.  An
    empty tile set is NOT a dispatch: ``base`` is returned untouched with
    no launch formed and no counter bump."""
    if packed.shape[0] == 0:
        return base
    record_dispatch("sbnet_scatter_fleet")
    return _sbnet_scatter_fleet_jit(packed, idx, base, interpret_mode())


@functools.lru_cache(maxsize=2)
def _sbnet_scatter_changed_jit(donate: bool):
    return jax.jit(_scatter_changed, static_argnames=("interpret",),
                   donate_argnums=(2,) if donate else ())


def sbnet_scatter_changed(packed: jax.Array, idx: jax.Array,
                          base: jax.Array,
                          donate: bool = False) -> jax.Array:
    """Changed-only scatter into the PERSISTENT head-map canvas:
    ``base`` is the previous step's device-resident canvas, ``packed`` /
    ``idx`` carry ONLY this step's changed tiles, unchanged tiles pass
    through untouched — O(changed) canvas bytes per step, bit-identical
    to re-scattering the whole active set (``sbnet_scatter_fleet``)
    composed with the passthrough.  An all-static step (zero changed
    tiles) returns the canvas with NO launch and NO counter bump.
    ``donate=True`` donates the canvas buffer to the launch (in-place
    update, double-buffer-free) — caller must not reuse ``base`` after;
    only ask for it off-CPU (see ``RoIDetector._donate_canvas``)."""
    if packed.shape[0] == 0:
        return base
    record_dispatch("sbnet_scatter_changed")
    return _sbnet_scatter_changed_jit(bool(donate))(
        packed, idx, base, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("th", "tw", "qstep",
                                             "coef_bits", "run_bits",
                                             "interpret"))
def _tile_delta_jit(cur, prev, idx, th, tw, qstep, coef_bits, run_bits,
                    interpret):
    return _tile_delta(cur, prev, idx, th, tw, qstep, coef_bits, run_bits,
                       interpret=interpret)


def tile_delta(cur: jax.Array, prev: jax.Array, idx: jax.Array, th: int,
               tw: int, qstep: float = 8.0, coef_bits: int = COEF_BITS,
               run_bits: int = RUN_BITS) -> jax.Array:
    """Per-tile temporal delta stats for the edge rate controller:
    (H, W, C) frame pair + (n, 2) tile coords -> (n, STATS_WIDTH) int32
    rows of [byte_estimate, nnz, zero_runs, sum|q|, 0...] (bit-exact vs
    ``ref.tile_delta``)."""
    record_dispatch("tile_delta")
    return _tile_delta_jit(cur, prev, idx, th, tw, float(qstep),
                           int(coef_bits), int(run_bits), interpret_mode())


@functools.partial(jax.jit, static_argnames=("th", "tw", "qstep",
                                             "coef_bits", "run_bits",
                                             "block", "interpret"))
def _tile_delta_gate_canvas_jit(cur_p, ref_c, idx, th, tw, qstep,
                                coef_bits, run_bits, block, interpret):
    return _tile_delta_gate_canvas(cur_p, ref_c, idx, th, tw, qstep,
                                   coef_bits, run_bits, block=block,
                                   interpret=interpret)


def tile_delta_gate_canvas(cur_p: jax.Array, ref_c: jax.Array,
                           idx: jax.Array, th: int, tw: int,
                           qstep: float = 8.0, coef_bits: int = COEF_BITS,
                           run_bits: int = RUN_BITS,
                           block: int = 1) -> jax.Array:
    """The reuse gate's shared delta dispatch: (C, H+2, W', Cin)
    zero-padded stacked fleet frames ``cur_p`` against a reference
    canvas ``ref_c`` of the same shape, both addressed through the same
    (n, 3) (cam, ty, tx) rows -> (n, STATS_WIDTH) int32 stats rows.
    Cols 0..3 are the BODY stats (identical to ``tile_delta`` when the
    reference holds the previous frame, feeding the rate controller),
    col GATE_WIN_EXACT the exact bitwise change count of the haloed
    entry window, col GATE_WIN_BYTES its quantized byte estimate
    (bit-exact vs ``ref.tile_delta_gate``).  ONE launch per fleet step
    serves both the reuse gate and the encoder's static-tile
    calibration; ``block`` tiles share a grid step like the blocked
    entry kernel.  Counted as ``tile_delta_gate``."""
    record_dispatch("tile_delta_gate")
    return _tile_delta_gate_canvas_jit(cur_p, ref_c, idx, th, tw,
                                       float(qstep), int(coef_bits),
                                       int(run_bits), int(block),
                                       interpret_mode())


@functools.partial(jax.jit, static_argnames=("th", "tw", "qstep",
                                             "coef_bits", "run_bits",
                                             "interpret"))
def _tile_delta_halo_jit(cur, prev, idx, th, tw, qstep, coef_bits,
                         run_bits, interpret):
    return _tile_delta_halo(cur, prev, idx, th, tw, qstep, coef_bits,
                            run_bits, interpret=interpret)


def tile_delta_halo(cur: jax.Array, prev: jax.Array, idx: jax.Array,
                    th: int, tw: int, qstep: float = 8.0,
                    coef_bits: int = COEF_BITS,
                    run_bits: int = RUN_BITS) -> jax.Array:
    """Per-tile temporal delta stats of the HALO STRIPS (the tile's edge
    ring — the pixels duplicated into neighbors when rectangles encode
    independently): (n, STATS_WIDTH) int32 rows, bit-exact vs
    ``ref.tile_delta_halo``.  Feeds halo-first shedding in the edge rate
    controller."""
    record_dispatch("tile_delta_halo")
    return _tile_delta_halo_jit(cur, prev, idx, th, tw, float(qstep),
                                int(coef_bits), int(run_bits),
                                interpret_mode())


def pack_tokens(x: jax.Array, keep: jax.Array, block: int = 128):
    """Pack kept rows of (S, ...) to a dense prefix padded to ``block``.

    keep: (S,) bool.  Returns (packed, positions, n_kept) where positions
    holds original indices (padding rows = PAD_POS).  Padded length is the
    smallest multiple of ``block`` >= S (static shape, jit-friendly).
    Kept rows stay in original order, so positions are monotone over real
    rows — the invariant the attention kernel's causal block skip uses.
    """
    S = x.shape[0]
    Sp = -(-S // block) * block
    order = jnp.argsort(~keep, stable=True)          # kept rows first
    n_kept = jnp.sum(keep.astype(jnp.int32))
    gathered = x[order]
    positions = jnp.where(jnp.arange(S) < n_kept, order, PAD_POS)
    pad = [(0, Sp - S)] + [(0, 0)] * (x.ndim - 1)
    packed = jnp.pad(gathered, pad)
    positions = jnp.pad(positions, (0, Sp - S), constant_values=PAD_POS)
    return packed, positions.astype(jnp.int32), n_kept


def unpack_tokens(packed: jax.Array, positions: jax.Array, S: int,
                  fill: float = 0.0) -> jax.Array:
    """Inverse of pack_tokens: scatter packed rows back to (S, ...)."""
    out = jnp.full((S,) + packed.shape[1:], fill, packed.dtype)
    # padding rows carry PAD_POS; route them out-of-bounds and drop, so
    # they can never collide with a real write
    pos = jnp.where(positions < S, positions, S)
    return out.at[pos].set(packed, mode="drop")


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "causal_skip",
                                    "return_stats", "interpret"))
def _roi_attention_jit(q, k, v, positions, block_q, block_k, causal_skip,
                       return_stats, interpret):
    return _roi_attn(q, k, v, positions, block_q=block_q, block_k=block_k,
                     causal_skip=causal_skip, return_stats=return_stats,
                     interpret=interpret)


def roi_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  positions: jax.Array, block_q: int = 128,
                  block_k: int = 128, causal_skip: bool = True,
                  return_stats: bool = False):
    """Packed-prefill attention over (S, H, D) with original-position
    causality.  S must already be block-padded (pack_tokens does this).
    ``causal_skip`` bounds the k-block walk at the causal frontier (exact:
    outputs on real rows are unchanged); ``return_stats`` additionally
    returns the (H, S // block_q) visited-k-block counts."""
    record_dispatch("roi_attention")
    return _roi_attention_jit(q, k, v, positions, block_q, block_k,
                              causal_skip, return_stats, interpret_mode())


def attention_visit_bound(positions: np.ndarray, block_q: int = 128,
                          block_k: int = 128) -> np.ndarray:
    """Host-side mirror of the kernel's causal bound: visited k-blocks per
    q-block, (S // block_q,) int.  Useful for structural FLOP accounting
    without launching the kernel."""
    positions = np.asarray(positions)
    S = positions.shape[0]
    kmin = np.asarray(block_min_positions(positions, block_k))
    out = np.zeros(S // block_q, np.int64)
    for qi in range(S // block_q):
        pq = positions[qi * block_q:(qi + 1) * block_q]
        real = pq[pq != int(PAD_POS)]
        if real.size == 0:
            continue
        hits = np.nonzero(kmin <= real.max())[0]
        out[qi] = 0 if hits.size == 0 else int(hits[-1]) + 1
    return out


__all__ = ["mask_to_indices", "neighbor_table", "fleet_indices",
           "fleet_neighbor_table", "superlaunch_tables", "ShardPlan",
           "shard_plan", "record_dispatch", "dilate_changed", "rf_px",
           "halo_rings", "stage_block", "roi_conv_stage",
           "reuse_sets", "compact_tables", "choose_block", "sbnet_gather",
           "sbnet_scatter", "sbnet_scatter_fleet", "sbnet_scatter_changed",
           "roi_conv_entry", "roi_conv_stack",
           "tile_delta", "tile_delta_gate_canvas",
           "pad_frames", "tile_delta_halo",
           "interpret_mode",
           "GATE_BODY_BYTES",
           "GATE_WIN_BYTES", "GATE_WIN_EXACT", "STATS_WIDTH", "pack_tokens",
           "unpack_tokens", "roi_attention", "attention_visit_bound",
           "block_min_positions", "KERNEL_COUNTS", "count_kernels",
           "PAD_POS", "ref"]
