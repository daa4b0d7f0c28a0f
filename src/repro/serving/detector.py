"""The RoI detector: a conv detector running on active tiles only.

Two backbones share every path below: RoI-YOLO-lite, a small chain of
3x3 stride-1 convs (``DetectorConfig.channels``), and a strided residual
backbone (``DetectorConfig.stem``/``stages``: Darknet-53's stem, stride-2
stages and bottleneck blocks, biases and leaky ReLU), whose entry kernel
is followed by one ``roi_conv_stage`` launch per stage and whose head
maps come at the output stride.  The rest of this docstring describes
the chain; a backbone's launches replace its stack megakernel.

The online-phase server model (paper §4.4), with the packed representation
persistent across the whole stack AND the whole launch chain fused to a
constant number of dispatches: layer 0 is the fused gather+conv+relu
entry kernel (``roi_conv_entry`` reads haloed windows straight from the
stacked frames — the *one* gather), layers 1..N-1 run inside ONE
``roi_conv_stack`` megakernel (grid over (layer, tile block), ping-pong
activations in HBM, halos DMA'd from the neighbor rows, per-layer weight
prefetch), and a *single* scatter materializes the full-frame head maps.
Every RoI forward — one camera, one group, or the WHOLE FLEET via
``superlaunch_forward`` — is exactly 3 dispatches (2 for a 1-layer
stack), independent of camera count, group count and layer count.  The
old SBNet formulation paid a full-frame scatter + HBM re-slice per layer.

``fleet_forward_reuse`` adds the TEMPORAL axis: one
``tile_delta_gate_canvas`` pricing dispatch thresholds each active
tile's haloed entry window against its reference content, the changed
set is dilated by the tile rings the receptive field crosses
(``ops.halo_rings``, ``ops.reuse_sets``) and compacted into the launch
tables, and unchanged tiles composite from a persistent
``PackedActivationCache`` — compute proportional to scene motion,
bit-identical at threshold 0.

Dense fallback (the paper loads both models and routes large-RoI frames to
dense YOLO) selected by the density switch.

FLOP/byte accounting drives the speedup model used in the system
benchmarks:
  dense cost      ~ H*W * sum(9*Cin*Cout)
  packed roi cost ~ n_active*th*tw * sum(9*Cin*Cout)
                    + (gather + scatter bytes) / N_layers   (amortized)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the I/O tax constant lives with the system cost model (ServerModel);
# re-exported here because the detector's speedup_estimate is the
# kernel-side mirror of that model
from repro.core.pipeline import IO_ROUND_TRIP_OVERHEAD
from repro.kernels import ops as kops
from repro.kernels.roi_conv import BACKBONE_ACT
from repro.obs import metrics as obs_metrics, trace as obs_trace


@dataclass
class DetectorConfig:
    channels: Tuple[int, ...] = (8, 16, 16)   # conv stack (YOLO-lite)
    tile: int = 16                            # feature-map tile (TPU block)
    num_anchors: int = 2
    switch_density: float = 0.70
    # VMEM budget the entry/stack/gate tile-block is sized against
    # (ops.choose_block): 3/4 of the kernels' 32 MiB scoped-VMEM limit
    vmem_budget_bytes: int = 24 * 2 ** 20
    # a strided residual backbone (Darknet-53's pattern) in place of the
    # ``channels`` chain: a 3x3 stem of ``stem`` channels, then per
    # stage (width, blocks) a 3x3 stride-2 conv to ``width`` and
    # ``blocks`` bottleneck blocks (1x1 conv to width/2, 3x3 conv back,
    # residual add), every conv with a bias (folded batch norm) and
    # ``BACKBONE_ACT``; () = the plain chain (ReLU, no biases)
    stem: int = 0
    stages: Tuple[Tuple[int, int], ...] = ()
    # the head: a linear 1x1 conv to num_anchors * (5 + num_classes)
    # outputs (objectness, 4 box regressors, class scores per anchor) at
    # the backbone's output stride
    num_classes: int = 0

    @property
    def out_stride(self) -> int:
        return 2 ** len(self.stages)

    @property
    def head_width(self) -> int:
        return self.num_anchors * (5 + self.num_classes)

    def layers(self) -> List[dict]:
        """The detector layer by layer, in order: dicts of ``name``,
        ``op`` (conv | add | head), ``k``, ``stride``, ``cin``, ``cout``,
        ``stride_in`` (the cumulative stride at its input, in frame
        pixels) and ``inputs`` (earlier layers' names, or ``"frame"``).
        ``ops.rf_px`` reads the receptive field from it, ``flops`` the
        work."""
        out = []

        def add(name, op, k, stride, cin, cout, s_in, inputs):
            out.append({"name": name, "op": op, "k": k, "stride": stride,
                        "cin": cin, "cout": cout, "stride_in": s_in,
                        "inputs": inputs})
            return name

        prev, cin, s = "frame", 3, 1
        if not self.stages:
            for i, c in enumerate(self.channels):
                prev = add(f"conv{i}", "conv", 3, 1, cin, c, 1, [prev])
                cin = c
        else:
            prev = add("stem", "conv", 3, 1, 3, self.stem, 1, [prev])
            cin = self.stem
            for i, (width, blocks) in enumerate(self.stages):
                prev = add(f"s{i}.down", "conv", 3, 2, cin, width, s,
                           [prev])
                s, cin = 2 * s, width
                for j in range(blocks):
                    a = add(f"s{i}.b{j}.conv1", "conv", 1, 1, width,
                            width // 2, s, [prev])
                    b = add(f"s{i}.b{j}.conv2", "conv", 3, 1, width // 2,
                            width, s, [a])
                    prev = add(f"s{i}.b{j}.add", "add", 1, 1, width, width,
                               s, [prev, b])
        add("head", "head", 1, 1, cin, self.head_width, s, [prev])
        return out


@dataclass
class ReuseStats:
    """Per-step accounting of the delta-gated (temporal reuse) path."""
    total_tiles: int               # active tiles across the fleet
    raw_changed: int               # tiles whose haloed input window changed
    changed_out: int               # ... dilated by ``ops.halo_rings`` (the
    #                                tiles whose final output may differ)
    computed: int                  # compact-set tiles (changed_out + the
    #                                zero-halo margin) — the semantic
    #                                quantity the dilation bound describes;
    #                                0 = all-static, gate-only step
    launched: int                  # tiles the launch ACTUALLY convolved:
    #                                ``computed`` padded to its shape
    #                                bucket (``ops.launch_rows``; inert
    #                                rows are real GEMM work — honest
    #                                perf accounting uses this one)
    cold: bool                     # cache miss: full recompute, no gate
    # the step's shared tile_delta_gate stats rows ((n, STATS_WIDTH)
    # int32 in fleet packing order, None on a cold step) — hand these to
    # net/encoder.static_fraction_from_stats so the rate controller
    # prices static tiles WITHOUT a second delta dispatch.  At threshold
    # 0 the references hold the previous frame, so the body cols are
    # exactly ``tile_delta(cur, prev)``; under a LOSSY threshold they
    # are deltas vs each tile's LAST-REFRESH content instead — the same
    # change measure the reuse decision itself uses (a tile priced
    # static is one whose content still matches what its cached
    # activations were built from; content oscillating back to that
    # reference prices low even if it moved in between)
    gate_stats: Optional[np.ndarray] = None
    # bytes scattered into the persistent head-map canvas this step:
    # n_written_tiles * th * tw * head_ch * itemsize.  0 on an all-static
    # step (no scatter launch at all); the full active set on a cold
    # step.  Padding rewrites of the last real tile are NOT counted —
    # they land on already-written bytes.
    canvas_bytes: int = 0


TILE_CLASS_BODY = 0      # interior tile: full 8-neighbor ring active
TILE_CLASS_HALO = 1      # boundary tile: >= 1 neighbor missing (zero halo)
N_TILE_CLASSES = 2


def tile_class_rows(nbr_np) -> np.ndarray:
    """Static per-tile class vector from the fleet neighbor table:
    TILE_CLASS_HALO for tiles with any missing (inactive or off-frame)
    neighbor — the rows whose entry windows carry synthesized zero halo
    and sit on the RoI boundary — else TILE_CLASS_BODY.  Feeds the
    per-tile-class gate-threshold schedule
    (``net.encoder.gate_threshold_schedule(halo_gain=...)``)."""
    nbr = np.asarray(nbr_np)
    if nbr.size == 0:
        return np.zeros((nbr.shape[0],), np.int64)
    return np.where((nbr < 0).any(axis=1), TILE_CLASS_HALO,
                    TILE_CLASS_BODY).astype(np.int64)


def _per_row_threshold(thr: np.ndarray, cam_of_row,
                       class_of_row) -> np.ndarray:
    """(C,) per-camera or (C, n_classes) per-camera-per-tile-class
    threshold table -> (n,) per-row thresholds."""
    if thr.ndim == 1:
        return thr[np.asarray(cam_of_row)]
    if class_of_row is None:
        raise ValueError(
            "per-tile-class thresholds (2-D) need class_of_row "
            "(see tile_class_rows)")
    return thr[np.asarray(cam_of_row), np.asarray(class_of_row)]


def gate_changed_rows(stats, threshold, cam_of_row,
                      class_of_row=None) -> np.ndarray:
    """Host-side gate thresholding shared by the single-device and the
    sharded reuse paths: (n, STATS_WIDTH) ``tile_delta_gate`` stats rows
    -> (n,) bool raw-changed mask.

    ``threshold`` is a scalar, a PER-CAMERA (C,) array indexed by
    ``cam_of_row`` (the idx table's camera column), or a PER-CAMERA,
    PER-TILE-CLASS (C, n_classes) array additionally indexed by
    ``class_of_row`` (``tile_class_rows``: body vs halo/boundary rows)
    — the rate controller's gate-threshold schedule raises thresholds
    on cameras it is already shedding, and the tile-class axis lets it
    hold boundary tiles (whose zero-halo windows price noisier) to a
    different bar than interiors.  A threshold <= 0 selects the exact
    bitwise change count for those rows (bit-identical reuse); a
    positive threshold gates on the quantized window byte estimate."""
    s = np.asarray(stats)
    thr = np.asarray(threshold, np.float64)
    if thr.ndim == 0:
        if float(thr) <= 0:
            return s[:, kops.GATE_WIN_EXACT] > 0
        return s[:, kops.GATE_WIN_BYTES] > float(thr)
    per_row = _per_row_threshold(thr, cam_of_row, class_of_row)
    return np.where(per_row <= 0, s[:, kops.GATE_WIN_EXACT] > 0,
                    s[:, kops.GATE_WIN_BYTES] > per_row)


def ref_advance_rows(threshold, cam_of_row, changed,
                     class_of_row=None) -> Optional[np.ndarray]:
    """Which reference rows advance to the current content this step:
    ``None`` = every row (the scalar threshold <= 0 fast path — one
    wholesale assignment, previous-frame semantics), else a (n,) bool
    mask — exact-gated rows always advance, lossy-gated rows advance
    only when refreshed so sub-threshold drift accumulates against each
    tile's own reference (see PackedActivationCache).  With a
    (C, n_classes) threshold table the exact/lossy split is per
    (camera, tile-class) row, mirroring ``gate_changed_rows``."""
    thr = np.asarray(threshold, np.float64)
    if thr.ndim == 0:
        return None if float(thr) <= 0 else np.asarray(changed, bool)
    per_row = _per_row_threshold(thr, cam_of_row, class_of_row)
    return (per_row <= 0) | np.asarray(changed, bool)


class PackedActivationCache:
    """Per-fleet persistent packed-activation cache for temporal reuse.

    Holds the final conv layer's packed (n, th, tw, C_last) activations
    for EVERY active tile of the fleet (at the backbone's output stride),
    the persistent HEAD-MAP CANVAS (``canvas``, (C, H/s, W/s, A)
    head-space at the output stride s — tile-split, see
    ``RoIDetector._head_canvas`` — device-resident across steps
    — warm steps scatter only this step's changed tiles into it, an
    all-static step writes 0 canvas bytes with no scatter launch), and
    the delta gate's reference content: a second padded canvas
    (``ref_canvas``, same shape as the padded stacked frames the gate
    reads, ``ops.pad_frames``) holding each tile's window content as of
    its last refresh, plus an (n,) per-tile refresh-EPOCH vector
    advanced by ``ref_advance_rows``.  Reference advancement writes the
    advanced rows' FULL haloed window regions from the current frame,
    so overlap writes between simultaneously-advanced neighbors carry
    identical content; at threshold <= 0 the wholesale assignment is a
    free alias of the current padded frame (previous-frame semantics).

    Under a lossy threshold only refreshed rows advance, so each tile's
    sub-threshold drift ACCUMULATES against its own reference and trips
    the gate once it crosses the threshold instead of creeping into the
    cache unboundedly.  Content-keyed on the fleet's grid digests and
    canvas shape, so any mask change — a drift re-solve, a shrink
    adoption, a different camera set — misses the key and forces a full
    recompute (cold scatter rebuilds the canvas from zeros: stale canvas
    content can never leak across a re-solve); ``invalidate`` is the
    explicit hook ``fleet/drift.DriftAdapter`` mask listeners call for
    the same effect (belt and braces: the digest key alone already
    invalidates)."""

    def __init__(self):
        self.key: Optional[tuple] = None
        self.packed: Optional[jax.Array] = None   # (n, th, tw, C_last)
        self.canvas: Optional[jax.Array] = None   # (C, H, W, A) head maps
        self.ref_canvas: Optional[jax.Array] = None  # (C, H+2, W', 3)
        self.epoch_np: Optional[np.ndarray] = None   # (n,) last refresh
        self.idx_np: Optional[np.ndarray] = None  # (n, 3) static tables
        self.nbr_np: Optional[np.ndarray] = None  # (n, 8)
        self.cls_np: Optional[np.ndarray] = None  # (n,) tile_class_rows
        self.invalidations = 0
        self.steps = 0
        self.cold_steps = 0
        self.launched_tiles = 0
        self.total_tiles = 0
        self.canvas_bytes_last = 0
        self.canvas_bytes_total = 0

    def invalidate(self) -> None:
        """Drop all cached state; the next reuse step recomputes fully."""
        self.key = None
        self.packed = None
        self.canvas = None
        self.ref_canvas = None
        self.epoch_np = None
        self.idx_np = None
        self.nbr_np = None
        self.cls_np = None
        self.invalidations += 1

    @property
    def compute_fraction(self) -> float:
        """Lifetime convolved-tile fraction vs full recompute (padding
        rows included — they are real launched GEMM work)."""
        return self.launched_tiles / max(self.total_tiles, 1)


@jax.jit
def _head_rows(packed: jax.Array, head: jax.Array) -> jax.Array:
    """Apply the 1x1 head to packed tiles PRE-scatter: (n, th, tw, C) @
    (C, A) -> (n, th, tw, A).  The head is a per-pixel dot product, so
    head-then-scatter is bit-identical to scatter-then-head — which is
    what lets the persistent canvas hold HEAD-space values and a warm
    step write only the changed tiles' head rows (pure jnp, not a
    counted kernel dispatch)."""
    n, th, tw, c = packed.shape
    return jnp.dot(packed.reshape(n * th * tw, c), head,
                   precision=jax.lax.Precision.HIGHEST).reshape(
        n, th, tw, head.shape[-1])


# tiles per matmul of a backbone's head
HEAD_CHUNK = 64


@jax.jit
def _head_rows_chunked(packed: jax.Array, head: jax.Array,
                       bias: jax.Array) -> jax.Array:
    """``_head_rows`` plus the head's bias, for a backbone's wide head,
    in matmuls of ``HEAD_CHUNK`` tiles each (the rows zero-padded to a
    whole number of chunks).  A dot's summation order can depend on its
    row count (XLA's CPU dot does at 64 channels and more), so a tile's
    head rows, and with them threshold-0 reuse's bit-identity, must not
    depend on how many tiles the step computed."""
    n, th, tw, c = packed.shape
    pad = -n % HEAD_CHUNK
    rows = jnp.pad(packed, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        -1, HEAD_CHUNK * th * tw, c)
    out = jax.lax.map(lambda r: jnp.dot(
        r, head, precision=jax.lax.Precision.HIGHEST) + bias, rows)
    return out.reshape(n + pad, th, tw, head.shape[-1])[:n]


def _window_region_mask(idx_rows, t: int, shape) -> np.ndarray:
    """(m, 3) advanced (cam, ty, tx) rows -> bool (C, H+2, W', 1) mask
    over their haloed window regions on the padded reference canvas
    (broadcasts over channels).  Host-built from the static tables —
    overlapping window writes are safe because every advanced region is
    filled from the SAME current frame."""
    m = np.zeros(tuple(shape[:3]) + (1,), bool)
    for cam, ty, tx in np.asarray(idx_rows):
        m[cam, ty * t:ty * t + t + 2, tx * t:tx * t + t + 2, 0] = True
    return m


def _advance_refs(cache: PackedActivationCache, xp: jax.Array,
                  adv: Optional[np.ndarray], t: int) -> None:
    """Advance the gate references per ``ref_advance_rows``'s verdict and
    stamp the per-tile refresh epochs.  ``adv is None`` = every row: a
    FREE alias of the current padded frame (the threshold <= 0
    previous-frame fast path); a partial advance writes the advanced
    rows' full window regions via one masked select."""
    step = cache.steps
    with obs_trace.span("ref_advance"):
        if adv is None:
            cache.ref_canvas = xp
            cache.epoch_np[:] = step
        elif adv.any():
            mask = _window_region_mask(cache.idx_np[adv], t,
                                       cache.ref_canvas.shape)
            cache.ref_canvas = jnp.where(jnp.asarray(mask), xp,
                                         cache.ref_canvas)
            cache.epoch_np[adv] = step


def _draw_params(key: jax.Array, layers, bias: bool) -> dict:
    """Weights from ``key``: conv layer i (k, k, Cin, Cout) from
    ``fold_in(key, i)`` and the (C, A) head from ``fold_in(key, 99)``,
    each normal / sqrt(fan-in); with ``bias`` (a backbone) a normal *
    0.1 bias per conv from ``fold_in(key, 1000 + i)`` and the head's
    from ``fold_in(key, 98)``."""
    convs = [layer for layer in layers if layer["op"] == "conv"]
    ws, bs = [], []
    for i, layer in enumerate(convs):
        k, ci, co = layer["k"], layer["cin"], layer["cout"]
        w = jax.random.normal(jax.random.fold_in(key, i), (k, k, ci, co),
                              jnp.float32)
        ws.append(w / np.sqrt(k * k * ci))
        if bias:
            bs.append(0.1 * jax.random.normal(
                jax.random.fold_in(key, 1000 + i), (co,), jnp.float32))
    head = layers[-1]
    hw = jax.random.normal(jax.random.fold_in(key, 99),
                           (head["cin"], head["cout"]), jnp.float32)
    out = {"convs": ws, "head": hw / np.sqrt(head["cin"])}
    if bias:
        out["biases"] = bs
        out["head_bias"] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, 98), (head["cout"],), jnp.float32)
    return out


class RoIDetector:
    """params: the conv layers of ``cfg.layers()`` + the 1x1 head (and,
    for a backbone, their biases); built for (H, W, 3) frames.
    ``params`` ({"convs", "head"}, and for a backbone "biases" and
    "head_bias") replaces the draw from ``key``."""

    def __init__(self, cfg: DetectorConfig, key: Optional[jax.Array] = None,
                 params: Optional[dict] = None):
        self.cfg = cfg
        self.layers = cfg.layers()
        if cfg.stages:
            if cfg.stem <= 0 or cfg.tile % cfg.out_stride or any(
                    w % 2 or b < 1 for w, b in cfg.stages):
                raise ValueError(f"backbone {cfg.stem}, {cfg.stages}: "
                                 f"needs a stem, even widths, a block per "
                                 f"stage and tiles of whole output pixels")
        if params is None:
            params = _draw_params(key, self.layers, bool(cfg.stages))
        self.weights: List[jax.Array] = list(params["convs"])
        # a backbone's biases: one per conv, and the head's
        self.biases: Optional[List[jax.Array]] = None
        self.head_bias: Optional[jax.Array] = None
        if cfg.stages:
            self.biases = list(params["biases"])
            self.head_bias = params["head_bias"]
        self.head = params["head"]
        chans = (3,) + tuple(layer["cout"] for layer in self.layers
                             if layer["op"] == "conv")
        # per-mask static cache: grid digest -> (idx2, idx3, nbr) arrays
        self._mask_cache: Dict[bytes, Tuple[jax.Array, jax.Array,
                                            jax.Array]] = {}
        # per-group static cache: digest tuple -> (idx3, nbr) arrays
        self._fleet_cache: Dict[tuple, Tuple[jax.Array, jax.Array]] = {}
        # per-grid digest memo: id(grid) -> (grid ref, popcount, digest).
        # Grids are packbits-serialized ONCE per array object, not once
        # per call — the fleet cache key on a hit is K dict lookups, not
        # K serializations.  Grids are treated as immutable (offline
        # re-solves produce fresh arrays); the strong ref pins the id and
        # a popcount guard re-hashes if a caller mutates one in place.
        # Capacity scales with the largest fleet offered (_fleet_tables),
        # so big fleets never thrash the memo back to per-call hashing.
        self._grid_digests: Dict[int, Tuple[np.ndarray, int, bytes]] = {}
        self._digest_cap = 64
        self.grid_hash_computes = 0       # digest serializations performed
        self.mask_cache_hits = 0
        self.fleet_cache_hits = 0
        # tiles per grid step of the entry, stack and gate walks, sized
        # against the VMEM budget (the scatter walks one tile per step);
        # a backbone sizes its entry alone and each stage from its own
        # widths
        t = cfg.tile
        if not cfg.stages:
            self.block = kops.choose_block(t, t, max(chans),
                                           len(cfg.channels),
                                           cfg.vmem_budget_bytes)
            self.stage_blocks: List[int] = []
        else:
            self.block = kops.choose_block(t, t, max(3, cfg.stem), 1,
                                           cfg.vmem_budget_bytes)
            cins = (cfg.stem,) + tuple(w for w, _ in cfg.stages[:-1])
            self.stage_blocks = [
                kops.stage_block(t >> i, ci, w // 2, w,
                                 cfg.vmem_budget_bytes)
                for i, (ci, (w, _)) in enumerate(zip(cins, cfg.stages))]
        # output strides of the conv layers: the strides the chain
        # launches its rows at (``conv_rows{stride}``)
        self.strides = sorted({layer["stride_in"] * layer["stride"]
                               for layer in self.layers
                               if layer["op"] == "conv"})

    @staticmethod
    def _donate_canvas() -> bool:
        """Donate the head-canvas buffer to ``sbnet_scatter_changed``?
        Off the CPU, so the warm-step canvas update is in place
        (O(changed) traffic); never on the CPU, which ignores donation.
        Callers keep what a step returned: its per-camera heads are
        slices copied out of the canvas, so a later step's donation never
        deletes them."""
        return jax.default_backend() != "cpu"

    # -- dense path ----------------------------------------------------------
    def dense_forward(self, x: jax.Array,
                      grid: Optional[np.ndarray] = None) -> jax.Array:
        """Full-frame forward in plain ``jax.numpy``.  With an RoI
        ``grid`` it is the reference of the packed path: every layer's
        output (and the head map) is zeroed outside the active tiles, so
        the next layer sees exactly the zero halo the packed chain
        reads; the first layer still reads the whole frame, as the entry
        kernel's windows do.  The plain chain only."""
        self._plain_chain_only()
        mask = None
        if grid is not None:
            t = self.cfg.tile
            px = np.kron(np.asarray(grid, bool), np.ones((t, t), bool))
            full = np.zeros(x.shape[:2], bool)
            h, w = min(px.shape[0], x.shape[0]), min(px.shape[1], x.shape[1])
            full[:h, :w] = px[:h, :w]
            mask = jnp.asarray(full[..., None])
        for w in self.weights:
            x = jax.nn.relu(jax.lax.conv_general_dilated(
                x[None], w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))[0])
            if mask is not None:
                x = jnp.where(mask, x, 0.0)
        out = x @ self.head
        return out if mask is None else jnp.where(mask, out, 0.0)

    # -- static-table caches ---------------------------------------------------
    def _grid_digest(self, grid) -> bytes:
        """Content digest of one RoI grid, serialized at most once per
        array object (cache keys used to packbits every grid on every
        call, cache hit or not).  A popcount guard catches in-place
        mutation of a memoized grid (an exact-swap mutation that keeps
        the popcount would evade it — produce fresh arrays instead)."""
        pop = int(np.count_nonzero(grid))
        hit = self._grid_digests.get(id(grid))
        if hit is not None and hit[0] is grid and hit[1] == pop:
            return hit[2]
        g = np.asarray(grid, bool)
        self.grid_hash_computes += 1
        digest = np.packbits(g).tobytes() + bytes(str(g.shape), "ascii")
        while len(self._grid_digests) >= self._digest_cap:
            self._grid_digests.pop(next(iter(self._grid_digests)))
        self._grid_digests[id(grid)] = (grid, pop, digest)
        return digest

    def _mask_tables(self, grid: np.ndarray):
        key = self._grid_digest(grid)
        hit = self._mask_cache.get(key)
        if hit is None:
            idx_np = kops.mask_to_indices(grid)
            idx3 = np.concatenate([np.zeros((idx_np.shape[0], 1), np.int32),
                                   idx_np], axis=1)
            hit = (jnp.asarray(idx_np), jnp.asarray(idx3),
                   jnp.asarray(kops.neighbor_table(idx_np, grid.shape)))
            # masks change rarely (offline re-solves); a small FIFO keeps
            # a long-lived server from pinning every mask ever seen
            while len(self._mask_cache) >= 8:
                self._mask_cache.pop(next(iter(self._mask_cache)))
            self._mask_cache[key] = hit
        else:
            self.mask_cache_hits += 1
        return hit

    def _fleet_tables(self, grids):
        # never let one fleet-sized key sweep smaller entries out of the
        # digest memo: keep room for two full fleets' worth of grids
        self._digest_cap = max(self._digest_cap, 2 * len(grids))
        key = tuple(self._grid_digest(g) for g in grids)
        hit = self._fleet_cache.get(key)
        if hit is None:
            idx_np, _ = kops.fleet_indices(grids)
            hit = (jnp.asarray(idx_np),
                   jnp.asarray(kops.fleet_neighbor_table(grids)))
            while len(self._fleet_cache) >= 8:
                self._fleet_cache.pop(next(iter(self._fleet_cache)))
            self._fleet_cache[key] = hit
        else:
            self.fleet_cache_hits += 1
        return hit

    # -- RoI path -------------------------------------------------------------
    def _stack_chain(self, x: jax.Array, idx3: jax.Array,
                     nbr: jax.Array) -> jax.Array:
        """The fused launch chain over stacked frames: entry kernel, then
        the layer-stack megakernel.  2 dispatches for any layer count
        > 1, 1 for a single-layer net."""
        t = self.cfg.tile
        if self.cfg.stages:
            return self._stage_chain(x, idx3, nbr)
        packed = kops.roi_conv_entry(x, self.weights[0], idx3, t, t,
                                     block=self.block)
        if len(self.weights) > 1:
            packed = kops.roi_conv_stack(packed, self.weights[1:], nbr,
                                         block=self.block)
        return packed

    def _stage_chain(self, x: jax.Array, idx3: jax.Array,
                     nbr: jax.Array) -> jax.Array:
        """A backbone's chain: the stem in the entry kernel, then one
        ``roi_conv_stage`` launch per stage over the same rows and
        neighbor table -> packed (n, t/s, t/s, C) at the output stride
        s: 1 + len(stages) dispatches."""
        t, act = self.cfg.tile, BACKBONE_ACT
        ws, bs = self.weights, self.biases
        packed = kops.roi_conv_entry(x, ws[0], idx3, t, t, block=self.block,
                                     bias=bs[0], act=act)
        i = 1
        for (_, n_blocks), block in zip(self.cfg.stages, self.stage_blocks):
            blocks = [(ws[j], bs[j], ws[j + 1], bs[j + 1])
                      for j in range(i + 1, i + 1 + 2 * n_blocks, 2)]
            packed = kops.roi_conv_stage(packed, (ws[i], bs[i]), blocks, nbr,
                                         block=block)
            i += 1 + 2 * n_blocks
        return packed

    @property
    def chain_dispatches(self) -> Dict[str, int]:
        """Kernel launches of one pass of the conv chain."""
        if self.cfg.stages:
            return {"roi_conv_entry": 1,
                    "roi_conv_stage": len(self.cfg.stages)}
        return {"roi_conv_entry": 1,
                "roi_conv_stack": 1 if len(self.cfg.channels) > 1 else 0}

    # -- the head canvas at the output stride ----------------------------------
    def _head_canvas(self, cams: int, canvas_h: int,
                     canvas_w: int) -> jax.Array:
        """A zero head-map canvas for ``cams`` stacked cameras of a
        (canvas_h, canvas_w) frame canvas: (C, H/s, W/s, A) at the output
        stride s, or — when a tile's head rows are fewer than 8, which a
        kernel block cannot address there — the same map with its tile
        axes split out, (C, H/t, t/s, W/t, t/s, A) (``sbnet_scatter_fleet``
        writes either)."""
        t, s = self.cfg.tile, self.cfg.out_stride
        a, ht = self.head.shape[-1], t // s
        if ht % 8 == 0:
            shape = (cams, canvas_h // s, canvas_w // s, a)
        else:
            shape = (cams, -(-canvas_h // t), ht, -(-canvas_w // t), ht, a)
        return jnp.zeros(shape, self.head.dtype)

    def _heads_out(self, canvas: jax.Array, frames) -> List[jax.Array]:
        """Per-camera (H/s, W/s, A) head maps, sliced out of the canvas
        on the device."""
        s = self.cfg.out_stride
        if canvas.ndim == 6:
            c, ty, ht, tx, _, a = canvas.shape
            canvas = canvas.reshape(c, ty * ht, tx * ht, a)
        return [canvas[c, :f.shape[0] // s, :f.shape[1] // s]
                for c, f in enumerate(frames)]

    def _head_rows(self, packed: jax.Array) -> jax.Array:
        if not self.cfg.stages:
            return _head_rows(packed, self.head)
        return _head_rows_chunked(packed, self.head, self.head_bias)

    def _count_rows(self, sp, rows: int) -> None:
        """The rows the chain launched, at each output stride: span args
        ``rows_s<stride>`` and the ``conv_rows{stride}`` counter."""
        sp.set(**{f"rows_s{s}": rows for s in self.strides})
        for s in self.strides:
            obs_metrics.CONV_ROWS.inc(rows, stride=s)

    def roi_forward(self, x: jax.Array, grid: np.ndarray) -> jax.Array:
        """x: (H, W, 3); grid: bool tile mask at self.cfg.tile granularity.
        Returns the full-frame head map with non-RoI regions zero.

        Stay-packed, constant-dispatch execution: ONE entry kernel (the
        gather fused into the first conv), ONE layer-stack megakernel for
        every remaining layer, ONE scatter — 3 dispatches total,
        independent of the layer count.  The plain chain only."""
        self._plain_chain_only()
        idx, idx3, nbr = self._mask_tables(grid)
        if idx.shape[0] == 0:             # empty mask: nothing to launch
            return jnp.zeros(x.shape[:2] + (self.head.shape[-1],), x.dtype)
        packed = self._stack_chain(x[None], idx3, nbr)
        base = jnp.zeros(x.shape[:2] + (packed.shape[-1],), packed.dtype)
        full = kops.sbnet_scatter(packed, idx, base)   # the scatter
        return full @ self.head

    def _plain_chain_only(self) -> None:
        if self.cfg.stages:
            raise ValueError("a backbone runs on fleet_forward_reuse "
                             "only; this path runs the plain conv chain")

    # -- fleet (multi-camera group / whole-fleet) path ------------------------
    def _stack_frames(self, frames, grids):
        t = self.cfg.tile
        if self.cfg.stages and any(f.shape[0] % t or f.shape[1] % t
                                   for f in frames):
            raise ValueError(f"a backbone's frames must be whole {t}-px "
                             f"tiles, got {[f.shape[:2] for f in frames]}")
        canvas_h = max(max(f.shape[0] for f in frames),
                       max(g.shape[0] * t for g in grids))
        canvas_w = max(max(f.shape[1] for f in frames),
                       max(g.shape[1] * t for g in grids))
        return jnp.stack([jnp.pad(f, ((0, canvas_h - f.shape[0]),
                                      (0, canvas_w - f.shape[1]), (0, 0)))
                          for f in frames]), canvas_h, canvas_w

    def fleet_forward(self, frames: List[jax.Array],
                      grids: List[np.ndarray]) -> List[jax.Array]:
        """Any number of cameras, ≤3 dispatches total: frames (one
        (H, W, 3) per camera, any sizes) are stacked on a common zero
        canvas and the whole set's active tiles run as ONE fused
        gather+conv entry, ONE layer-stack megakernel (cross-camera
        neighbor table — halos cannot leak between cameras), and ONE
        scatter.  Returns the per-camera full-frame head maps, each
        bit-compatible with ``roi_forward(frame, grid)`` on that camera
        alone.  Cameras with empty masks get all-zero head maps and cost
        no launches of their own.  The plain chain only: a backbone runs
        on ``fleet_forward_reuse``, whose cold step is this forward."""
        self._plain_chain_only()
        idx, nbr = self._fleet_tables(grids)
        if idx.shape[0] == 0:             # whole set empty: no launches
            return [jnp.zeros(f.shape[:2] + (self.head.shape[-1],),
                              f.dtype) for f in frames]
        x, canvas_h, canvas_w = self._stack_frames(frames, grids)
        packed = self._stack_chain(x, idx, nbr)
        base = jnp.zeros((len(frames), canvas_h, canvas_w,
                          packed.shape[-1]), packed.dtype)
        full = kops.sbnet_scatter_fleet(packed, idx, base)
        heads = full @ self.head
        return [heads[c, :f.shape[0], :f.shape[1]]
                for c, f in enumerate(frames)]

    def superlaunch_forward(self, frames: Dict[int, List[jax.Array]],
                            grids: Dict[int, List[np.ndarray]]
                            ) -> Dict[int, List[jax.Array]]:
        """The cross-group super-launch: EVERY camera of EVERY group in
        one fleet-flat launch chain — ≤3 dispatches for the whole fleet,
        independent of group count and layer count.  Group boundaries are
        just camera boundaries in the flat (flat_cam, ty, tx) index
        space, so per-camera slot offsets keep halos leak-free across
        cameras and groups alike (``_fleet_tables`` builds and caches the
        flat tables; ``ops.superlaunch_tables`` is the equivalent
        standalone builder).  Returns {gid: per-camera head maps}, each
        bit-identical to ``fleet_forward(frames[gid], grids[gid])`` on
        that group alone."""
        gids = list(frames)
        flat_frames = [f for g in gids for f in frames[g]]
        flat_grids = [gr for g in gids for gr in grids[g]]
        heads = self.fleet_forward(flat_frames, flat_grids)
        out, pos = {}, 0
        for g in gids:
            out[g] = heads[pos:pos + len(frames[g])]
            pos += len(frames[g])
        return out

    # -- temporal reuse (delta-gated) path ------------------------------------
    def fleet_forward_reuse(self, frames: List[jax.Array],
                            grids: List[np.ndarray],
                            cache: PackedActivationCache,
                            threshold: float = 0.0,
                            qstep: float = 8.0
                            ) -> Tuple[List[jax.Array], ReuseStats]:
        """``fleet_forward`` with compute proportional to CHANGED tiles.

        One shared ``tile_delta_gate_canvas`` dispatch prices every
        active tile's haloed entry window against the reference canvas; a
        tile is *changed* when its window byte estimate exceeds
        ``threshold`` (at threshold <= 0 the exact bitwise change count
        gates instead, making reuse BIT-IDENTICAL to full recompute).
        ``threshold`` may also be a PER-CAMERA array (one entry per
        flattened camera, see ``gate_changed_rows``) — the rate
        controller's gate-threshold schedule raises thresholds only on
        cameras it is already shedding, and cameras left at <= 0 keep
        exact-gated bit-identity.
        The changed set is dilated by the receptive field's tile rings
        (``ops.halo_rings``) into the changed-OUTPUT set, by as many
        again into the compute margin (``ops.reuse_sets``), compacted
        into the superlaunch tables (``ops.compact_tables``) and run
        through the blocked entry + stack chain; unchanged tiles keep
        their bytes in the PERSISTENT head-map canvas (written by the
        step that last computed them), and one ``sbnet_scatter_changed``
        writes ONLY the refreshed tiles' head rows into it — both sides
        of a step are O(changed) bytes.  An all-static frame dispatches
        the gate ALONE: no conv, no scatter, 0 canvas bytes written.  A
        cache miss (first frame, mask re-solve, canvas change)
        recomputes fully and seeds the cache + canvas from zeros.
        ``threshold`` may also be a (C, N_TILE_CLASSES) per-camera-per-
        tile-class table (body vs halo rows, see ``tile_class_rows``).
        A backbone's packed cache holds its output-stride activations and
        its canvas the head maps at the output stride; every stride's
        kernels run the same compact rows under one neighbor table."""
        t = self.cfg.tile
        with obs_trace.span("stage"):
            idx, nbr = self._fleet_tables(grids)
            n = int(idx.shape[0])
            if n == 0:                    # whole fleet empty: no launches
                s = self.cfg.out_stride
                return ([jnp.zeros((f.shape[0] // s, f.shape[1] // s,
                                    self.head.shape[-1]), f.dtype)
                         for f in frames],
                        ReuseStats(0, 0, 0, 0, 0, cold=False))
            x, canvas_h, canvas_w = self._stack_frames(frames, grids)
            xp = kops.pad_frames(x, t)
        key = (tuple(self._grid_digest(g) for g in grids),
               len(frames), canvas_h, canvas_w)
        cache.steps += 1
        cache.total_tiles += n
        ht = t // self.cfg.out_stride
        tile_bytes = (ht * ht * self.head.shape[-1]
                      * jnp.dtype(self.head.dtype).itemsize)
        cold = (cache.key != key or cache.packed is None
                or cache.canvas is None or cache.ref_canvas is None)
        if cold:
            # miss: mask/canvas changed (or first frame) — recompute all
            # tiles through the fused chain, seed the cache tables and
            # rebuild the head canvas from zeros (stale canvas content
            # can never survive a re-solve)
            cache.key = key
            with obs_trace.span("conv_dispatch") as sp:
                self._count_rows(sp, n)
                cache.packed = self._stack_chain(x, idx, nbr)
                cache.ref_canvas = xp          # free alias, full advance
                cache.idx_np = np.asarray(idx)
                cache.nbr_np = np.asarray(nbr)
                cache.cls_np = tile_class_rows(cache.nbr_np)
                cache.epoch_np = np.zeros(n, np.int64)
                base = self._head_canvas(len(frames), canvas_h, canvas_w)
                cache.canvas = kops.sbnet_scatter_fleet(
                    self._head_rows(cache.packed), idx, base)
            cache.cold_steps += 1
            cache.launched_tiles += n
            stats = ReuseStats(n, n, n, n, n, cold=True,
                               canvas_bytes=n * tile_bytes)
        else:
            with obs_trace.span("gate"):
                gate = kops.tile_delta_gate_canvas(
                    xp, cache.ref_canvas, idx, t, t, qstep=qstep,
                    block=self.block)
            with obs_trace.span("gate_readback") as sp:
                s = np.asarray(gate)
                sp.set(bytes=s.nbytes)
            obs_metrics.READBACK_BYTES.inc(s.nbytes, kind="gate")
            with obs_trace.span("reuse_plan") as sp:
                # exact gate (threshold <= 0, possibly per camera /
                # class): quantization rounds small deltas to zero and
                # even an all-zero delta prices its run tokens, so
                # bit-identity keys on the raw bitwise comparison
                raw = gate_changed_rows(s, threshold, cache.idx_np[:, 0],
                                        cache.cls_np)
                changed, compute = kops.reuse_sets(
                    raw, cache.nbr_np, kops.halo_rings(self.layers, t))
                n_changed = int(changed.sum())
                k = k_pad = 0
                if n_changed:
                    cidx, cnbr = kops.compact_tables(cache.idx_np,
                                                     cache.nbr_np, compute)
                    k = cidx.shape[0]
                    # pad the ragged compact set up to its quarter-octave
                    # bucket with inert repeats (idx) / -1 neighbors, so
                    # the jit caches key on four shapes an octave, not
                    # every |E| (waste < 1.25x; the padding rows are real
                    # GEMM work and are accounted as ``launched``)
                    k_pad = kops.launch_rows(k, n)
                    if k_pad > k:
                        cidx = np.concatenate(
                            [cidx, np.broadcast_to(cidx[-1:],
                                                   (k_pad - k, 3))])
                        cnbr = np.concatenate(
                            [cnbr, np.full((k_pad - k, 8), -1, np.int32)])
                sp.set(raw_changed=int(raw.sum()), computed=k,
                       launched=k_pad)
            if n_changed:
                with obs_trace.span("conv_dispatch") as sp:
                    self._count_rows(sp, k_pad)
                    fresh = self._stack_chain(x, jnp.asarray(cidx),
                                              jnp.asarray(cnbr))
                    # only the changed-OUTPUT rows graduate to the cache
                    # — margin rows absorbed the zero-halo error and
                    # their cached values are still exact
                    slots = np.nonzero(compute)[0]
                    upd = changed[slots]
                    fresh_rows = fresh[jnp.asarray(np.nonzero(upd)[0])]
                    cache.packed = cache.packed.at[
                        jnp.asarray(slots[upd])].set(fresh_rows)
                    # ... and only those rows' head tiles hit the canvas:
                    # O(changed) write bytes, power-of-two repeat-last
                    # padding so the scatter jit keys on log-many shapes
                    # (padding stores rewrite the last real tile's bytes
                    # in place)
                    scidx = cache.idx_np[slots[upd]]
                    ph = self._head_rows(fresh_rows)
                    m = scidx.shape[0]
                    m_pad = 1
                    while m_pad < m:
                        m_pad *= 2
                    if m_pad > m:
                        scidx = np.concatenate(
                            [scidx, np.broadcast_to(scidx[-1:],
                                                    (m_pad - m, 3))])
                        ph = jnp.concatenate(
                            [ph, jnp.broadcast_to(
                                ph[-1:], (m_pad - m,) + ph.shape[1:])])
                    cache.canvas = kops.sbnet_scatter_changed(
                        ph, jnp.asarray(scidx), cache.canvas,
                        donate=self._donate_canvas())
                cache.launched_tiles += k_pad
                stats = ReuseStats(n, int(raw.sum()), n_changed, k,
                                   k_pad, cold=False, gate_stats=s,
                                   canvas_bytes=m * tile_bytes)
                # advance the references of the REFRESHED tiles by
                # masked window-region writes from the current frame
                # (threshold 0 advances every row: previous-frame
                # semantics, one free assignment)
                adv = ref_advance_rows(threshold, cache.idx_np[:, 0],
                                       changed, cache.cls_np)
                _advance_refs(cache, xp, adv, t)
            else:
                # ALL-STATIC: the gate dispatch is the whole step — no
                # conv, no scatter, the canvas is served as-is with 0
                # bytes written
                adv = ref_advance_rows(threshold, cache.idx_np[:, 0],
                                       np.zeros(n, bool), cache.cls_np)
                _advance_refs(cache, xp, adv, t)
                stats = ReuseStats(n, int(raw.sum()), 0, 0, 0,
                                   cold=False, gate_stats=s,
                                   canvas_bytes=0)
        cache.canvas_bytes_last = stats.canvas_bytes
        cache.canvas_bytes_total += stats.canvas_bytes
        # the head maps stay on the device: nothing is pulled to the host
        with obs_trace.span("heads_out", bytes=0):
            out = self._heads_out(cache.canvas, frames)
        return out, stats

    def superlaunch_forward_reuse(self, frames: Dict[int, List[jax.Array]],
                                  grids: Dict[int, List[np.ndarray]],
                                  cache: PackedActivationCache,
                                  threshold: float = 0.0,
                                  qstep: float = 8.0):
        """Delta-gated cross-group super-launch: every camera of every
        group in one compact launch chain (see ``superlaunch_forward``
        for the flattening contract).  Returns ({gid: head maps},
        ReuseStats)."""
        gids = list(frames)
        flat_frames = [f for g in gids for f in frames[g]]
        flat_grids = [gr for g in gids for gr in grids[g]]
        heads, stats = self.fleet_forward_reuse(flat_frames, flat_grids,
                                                cache, threshold, qstep)
        out, pos = {}, 0
        for g in gids:
            out[g] = heads[pos:pos + len(frames[g])]
            pos += len(frames[g])
        return out, stats

    def forward(self, x: jax.Array, grid: Optional[np.ndarray]) -> jax.Array:
        if grid is None or grid.mean() >= self.cfg.switch_density:
            return self.dense_forward(x)
        return self.roi_forward(x, grid)

    # -- cost model -------------------------------------------------------------
    @property
    def num_conv_layers(self) -> int:
        return sum(layer["op"] == "conv" for layer in self.layers)

    def flops(self, H: int, W: int, density: float = 1.0) -> float:
        """Matmul FLOPs of the detector over an (H, W) frame at RoI
        ``density``: 2 * k^2 * Cin * Cout per output pixel of every conv
        and the head, each layer at its own stride."""
        per_px = sum(2 * layer["k"] ** 2 * layer["cin"] * layer["cout"]
                     / (layer["stride_in"] * layer["stride"]) ** 2
                     for layer in self.layers if layer["op"] != "add")
        return H * W * density * per_px

    def io_overhead_per_layer(
            self, round_trip: float = IO_ROUND_TRIP_OVERHEAD) -> float:
        """Gather/scatter byte tax amortized over the conv stack: the packed
        chain pays one round-trip for N layers, so the per-layer overhead is
        round_trip / N (the old per-layer regime paid round_trip / 1)."""
        return round_trip / max(self.num_conv_layers, 1)

    def speedup_estimate(self, density: float,
                         round_trip: float = IO_ROUND_TRIP_OVERHEAD) -> float:
        """Structural speedup (FLOP ratio with the amortized gather/scatter
        byte tax): matches the ServerModel constant used by the system
        pipeline."""
        if density >= self.cfg.switch_density:
            return 1.0
        return 1.0 / (self.io_overhead_per_layer(round_trip) + density)
