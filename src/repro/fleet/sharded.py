"""City-scale sharded serving: the super-launch over a device mesh.

``cross_group_leakage == 0`` by construction makes camera groups an
embarrassingly parallel axis: no tile's halo, neighbor table or scatter
target ever crosses a group boundary, so partitioning groups over a 1-D
``jax.sharding.Mesh`` (``launch.mesh.make_fleet_mesh``) needs ZERO
cross-device collectives on the hot path.  ``ShardedSuperlaunch`` is the
fleet runtime's super-launch (``RoIDetector.superlaunch_forward_reuse``)
rebuilt as ONE ``jax.shard_map`` SPMD program over stacked per-shard
state:

* **Placement-free tables + a shard plan.**  ``ops.superlaunch_tables``
  emits flat tables for any group subset; ``ops.shard_plan`` assigns
  groups to shards balanced by ACTIVE-TILE count (LPT greedy — one busy
  intersection cannot straggle a shard).  Per-shard tables are padded to
  a common power-of-two row count with SACRIFICIAL rows: padding rows
  index a zero camera slot appended to every shard's frame stack
  (``idx = (F_max, 0, 0)``, ``nbr = -1``), so ragged shards — including
  entirely empty ones — run the same SPMD program and padding work can
  never corrupt a real output.
* **Per-shard dispatch ceiling.**  Each step is one gate launch plus a
  ≤3-dispatch conv chain (entry, layer-stack megakernel, changed-only
  canvas scatter) — each counted ONCE per step via
  ``ops.record_dispatch`` because SPMD means the single traced program
  IS the per-shard program: one dispatch runs the kernel once on every
  shard.  An ALL-STATIC step is the gate alone: the persistent head
  canvas is served as-is — zero conv/scatter launches, 0 bytes written.
* **Bit-identity.**  Every per-tile quantity (gate stats, entry/stack
  GEMMs, scatter, head matmul) reduces only over its own tile's inputs,
  so re-partitioning tiles across shards cannot change bits: each
  group's head maps are bit-identical to the single-device
  ``superlaunch_forward_reuse`` on the same trace (asserted by
  tests/test_sharded.py and benchmarks/bench_shard.py).
* **Sharded cache + persistent canvas + per-shard invalidation.**  The
  packed activations, the persistent HEAD-MAP CANVAS ((S, F_max + 1, H,
  W, A) — warm steps scatter only changed tiles' head rows into it,
  padding/margin rows land on the sacrificial camera plane) and the
  canvas-resident gate references ((S, F_max + 1, H + 2, W', 3) with
  a host-side (S, n_max) refresh-epoch table) live in a
  ``ShardedActivationCache``, shard axis over the mesh.  A drift
  re-solve invalidates ONLY the owning shard
  (``drift.wire_shard_invalidation``); the next step wipes that shard's
  canvas plane in-program and recomputes its rows while the others keep
  serving warm — cold and warm shards share the one SPMD program (a
  cold shard's rows are simply all marked raw-changed host-side), so
  canvas invalidation is shard-exact.

``AsyncShardedPipeline`` overlaps the host and the device: the gate for
step t is dispatched BEFORE the conv for step t-1, so pulling the gate
stats blocks only on the gate and the host-side thresholding /
``reuse_sets`` dilation (``halo_rings`` rings for the changed set,
as many again for the compute margin) / table compaction for step t
runs WHILE the device executes step t-1's conv chain (double-buffered
table slots keep the in-flight step's tables alive; the cache buffers
are donated into each conv dispatch).  ``jax.block_until_ready``
happens only at the consumer edge (``collect``); the measured
host/device overlap fraction is a first-class output.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.shardings import fleet_state_sharding
from repro.kernels import ops as kops
from repro.kernels.blocking import pad_frames, window_width
from repro.kernels.roi_conv import (roi_conv_entry as _raw_entry,
                                    roi_conv_stack as _raw_stack)
from repro.kernels.sbnet import (sbnet_scatter_changed as
                                 _raw_scatter_changed)
from repro.kernels.tile_delta import (COEF_BITS, RUN_BITS,
                                      tile_delta_gate_canvas as
                                      _raw_gate_canvas)
from repro.launch.mesh import FLEET_AXIS
from repro.obs import metrics as obs_metrics, trace as obs_trace
from repro.serving.detector import (gate_changed_rows, ref_advance_rows,
                                    tile_class_rows)


class ShardedActivationCache:
    """The ``PackedActivationCache`` sharded along the group axis.

    State for ``ShardedSuperlaunch``: the packed final-layer activations,
    the persistent head canvas and the canvas-resident gate references
    live as (S, ...) STACKED arrays, shard axis split over the fleet mesh
    (``distributed.shardings.fleet_state_sharding``), padded rows
    pointing at a sacrificial camera slot so SPMD shapes stay uniform
    across ragged shards.  Validity is PER SHARD: a drift re-solve on
    one group invalidates only the owning shard (``invalidate_group``,
    fan-out wired by ``fleet/drift.wire_shard_invalidation``), and the
    next sharded step recomputes that shard's rows while every other
    shard keeps serving warm — the single-device cache would have gone
    fleet-wide cold on the same event.  Mixed cold/warm shards run in
    the SAME SPMD program: a cold shard's rows are simply all marked
    raw-changed on the host side."""

    def __init__(self, plan: kops.ShardPlan, gids=None):
        self.plan = plan
        self.gids = list(gids) if gids is not None else None
        self.valid = np.zeros(plan.n_shards, bool)
        self.packed = None      # (S, n_max, th, tw, C_last) mesh-sharded
        self.canvas = None      # (S, F_max+1, H, W, A) persistent heads
        self.ref_canvas = None  # (S, F_max+1, H+2, W', 3) references
        self.epoch_np = None    # (S, n_max) per-tile last-refresh step
        self.canvas_bytes_last = 0
        self.canvas_bytes_total = 0
        self.invalidations = 0
        self.shard_invalidations = np.zeros(plan.n_shards, np.int64)
        self.steps = 0
        self.cold_steps = 0          # steps with >= 1 cold shard
        self.launched_tiles = 0
        self.total_tiles = 0

    def owner_shard(self, group) -> int:
        """Shard owning ``group`` (a gid when the cache was built with
        ``gids``, else a plan position)."""
        pos = self.gids.index(group) if self.gids is not None else int(group)
        return int(self.plan.assignment[pos])

    def invalidate_group(self, group) -> None:
        """Mark ONLY the shard owning ``group`` cold; every other
        shard's cached rows stay valid and keep serving."""
        s = self.owner_shard(group)
        self.valid[s] = False
        self.shard_invalidations[s] += 1
        self.invalidations += 1

    def invalidate(self, _adapter=None) -> None:
        """Fleet-wide drop (the PackedActivationCache-compatible hook);
        accepts and ignores a DriftAdapter argument so it can be
        registered as a mask listener directly."""
        self.valid[:] = False
        self.packed = None
        self.canvas = None
        self.ref_canvas = None
        self.epoch_np = None
        self.invalidations += 1

    @property
    def compute_fraction(self) -> float:
        """Lifetime convolved-tile fraction vs full recompute (padding
        rows included — they are real launched GEMM work)."""
        return self.launched_tiles / max(self.total_tiles, 1)


def _pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1) — the shape-bucketing rule of
    each shard dimension (the single-device path pads its conv rows to
    ``ops.launch_rows`` and its scatter rows to this)."""
    p = 1
    while p < n:
        p *= 2
    return p


@dataclass
class ShardedReuseStats:
    """Per-step accounting of one sharded fleet step (fleet-wide sums;
    ``launched`` counts every convolved row on every shard, padding
    included — honest SPMD accounting: all shards convolve ``k_max``
    rows whenever any shard needs one)."""
    total_tiles: int
    raw_changed: int
    changed_out: int
    computed: int                 # real compact-set tiles, summed
    launched: int                 # S * k_max when the conv launched
    k_max: int                    # per-shard convolved rows this step
    cold_shards: int              # shards that ran a forced recompute
    # bytes scattered into the persistent head canvas this step (real
    # changed-out tiles only; sacrificial-plane padding/margin writes
    # are not counted).  0 on an all-static step — no scatter launch.
    canvas_bytes: int = 0
    per_shard_computed: List[int] = field(default_factory=list)
    # per-shard gate stats over REAL rows (None for cold shards, whose
    # reference content was stale) — feed per-camera slices to
    # net.encoder.static_fraction_from_stats, same shared-dispatch
    # contract as the single-device path
    gate_stats: Optional[List[Optional[np.ndarray]]] = None

    @property
    def cold(self) -> bool:
        return self.cold_shards > 0


@dataclass
class _HostPlan:
    """One step's host-side compaction product (the work the async
    pipeline overlaps with the previous step's device compute)."""
    k_max: int                    # 0 = all-static: gate-only step (the
    #                               persistent canvas is served as-is)
    cidx: Optional[np.ndarray]    # (S, k_max, 3) compact tables
    cnbr: Optional[np.ndarray]    # (S, k_max, 8)
    upd: Optional[np.ndarray]     # (S, k_max) cache row targets (n_max=drop)
    sidx: Optional[np.ndarray]    # (S, k_max, 3) canvas scatter targets:
    #                               changed rows keep their (cam, ty, tx),
    #                               margin/padding rows hit the
    #                               sacrificial camera plane (F_max, 0, 0)
    adv: np.ndarray               # (S, n_max) reference-advance mask
    cold_mask: np.ndarray         # (S,) shards whose canvas plane must be
    #                               wiped to zeros before this step's
    #                               scatter (shard-exact invalidation)
    stats: ShardedReuseStats


class ShardedSuperlaunch:
    """Sharded fleet runtime for a fixed group->shard plan.

    frames/grids are keyed by gid exactly like
    ``RoIDetector.superlaunch_forward_reuse``; the plan (built here via
    ``ops.shard_plan`` unless given) stays valid until a mask re-solve
    calls ``rebuild_group``."""

    def __init__(self, det, grids: Dict[int, List[np.ndarray]], mesh,
                 plan: Optional[kops.ShardPlan] = None):
        if det.cfg.stages:
            raise ValueError("the sharded fleet step runs the plain conv "
                             "chain only")
        self.det = det
        self.mesh = mesh
        self.gids = list(grids)
        self.grids = {g: list(gs) for g, gs in grids.items()}
        n_shards = mesh.shape[FLEET_AXIS]
        self.plan = plan or kops.shard_plan(
            [self.grids[g] for g in self.gids], n_shards)
        if self.plan.n_shards != n_shards:
            raise ValueError(
                f"plan has {self.plan.n_shards} shards, mesh {n_shards}")
        self.sharding = fleet_state_sharding(mesh)
        t = det.cfg.tile
        # canvas: global maxima so head shapes agree across shards (the
        # single-device _stack_frames rule, applied fleet-wide)
        self.canvas_h = max(g.shape[0] * t for gs in self.grids.values()
                            for g in gs)
        self.canvas_w = max(g.shape[1] * t for gs in self.grids.values()
                            for g in gs)
        # width of the padded frames the gate reads (``pad_frames``)
        self.padded_w = self.canvas_w + window_width(t) - t
        self._build_tables()
        self._fns: Dict = {}          # jitted shard_map programs

    # -- table construction ------------------------------------------------
    def _build_tables(self) -> None:
        S = self.plan.n_shards
        self._shard_gids = [[self.gids[i] for i in self.plan.shard_groups(s)]
                            for s in range(S)]
        self._idx_np, self._nbr_np, self._n_s, self._F_s = [], [], [], []
        self._group_slot: Dict[int, Tuple[int, int]] = {}
        for s in range(S):
            gs = [self.grids[g] for g in self._shard_gids[s]]
            idx, nbr, _, cam_starts = kops.superlaunch_tables(gs)
            self._idx_np.append(np.asarray(idx))
            self._nbr_np.append(np.asarray(nbr))
            self._n_s.append(int(idx.shape[0]))
            self._F_s.append(int(sum(len(g) for g in gs)))
            for j, gid in enumerate(self._shard_gids[s]):
                self._group_slot[gid] = (s, int(cam_starts[j]))
        self.F_max = max(self._F_s + [1])
        self.n_max = _pow2(max(self._n_s + [1]))
        self.n_total = int(sum(self._n_s))
        # stacked padded tables: padding rows target the SACRIFICIAL zero
        # camera slot F_max (frames carry F_max + 1 slots), neighbors -1
        idx_pad = np.zeros((S, self.n_max, 3), np.int32)
        idx_pad[:, :, 0] = self.F_max
        for s in range(S):
            idx_pad[s, :self._n_s[s]] = self._idx_np[s]
        self._idx_pad_np = idx_pad
        self.idx_pad = jax.device_put(jnp.asarray(idx_pad), self.sharding)
        # per-shard tile classes (body vs halo/boundary rows) for the
        # per-tile-class gate-threshold schedule
        self._cls_np = [tile_class_rows(nbr) for nbr in self._nbr_np]
        self._fns = {}

    def make_cache(self) -> ShardedActivationCache:
        return ShardedActivationCache(self.plan, gids=self.gids)

    def groups_on_shard(self, shard: int) -> List[int]:
        """Group ids placed on ``shard`` — the blast radius of losing
        that shard.  The fault layer walks this to cold-mark every owned
        group (``cache.invalidate_group``); the next step then
        recomputes them from scratch, which IS the restore path (the
        detect -> restore idiom of ``distributed.fault.ElasticMesh``,
        applied to serving state instead of training state)."""
        return list(self._shard_gids[shard])

    def rebuild_group(self, gid: int, new_grids: Sequence[np.ndarray],
                      cache: Optional[ShardedActivationCache] = None
                      ) -> None:
        """Adopt a re-solved mask for one group: rebuild ONLY the owning
        shard's tables (the shard is already cold via
        ``invalidate_group``); other shards' tables, cache rows and
        reference windows survive untouched.  If the new mask overflows
        the shared row bucket, ``n_max`` grows and every shard's stacked
        arrays are re-padded — warm rows are preserved, so growth does
        not cost the other shards a recompute."""
        t = self.det.cfg.tile
        for g in new_grids:
            if g.shape[0] * t > self.canvas_h or \
                    g.shape[1] * t > self.canvas_w:
                raise ValueError("re-solved grid exceeds the built canvas")
        self.grids[gid] = list(new_grids)
        old_n_max, old_f_max = self.n_max, self.F_max
        self._build_tables()
        if cache is None or cache.packed is None:
            return
        if self.F_max != old_f_max:
            # camera-axis shape changed: the stacked canvases cannot be
            # row-preserved — drop them (every shard reseeds next step)
            cache.packed = None
            cache.ref_canvas = None
            cache.canvas = None
            cache.epoch_np = None
            cache.valid[:] = False
            return
        if self.n_max != old_n_max:
            pad = self.n_max - old_n_max

            def repad(a, n_extra_dims):
                a = np.asarray(a)
                if pad > 0:
                    widths = ((0, 0), (0, pad)) + ((0, 0),) * n_extra_dims
                    return np.pad(a, widths)
                return a[:, :self.n_max]

            cache.packed = jax.device_put(
                jnp.asarray(repad(cache.packed, 3)), self.sharding)
            if cache.epoch_np is not None:
                cache.epoch_np = repad(cache.epoch_np, 0)
        # shard-exact canvas invalidation: the owning shard is already
        # cold (invalidate_group); zero its canvas plane host-side too,
        # so tiles the re-solve REMOVED cannot leak stale head bytes
        # (the in-program cold wipe covers the normal case, but a shard
        # rebuilt to an empty mask never reaches the conv dispatch)
        s = cache.owner_shard(gid)
        if cache.canvas is not None:
            cache.canvas = jax.device_put(
                jnp.asarray(cache.canvas).at[s].set(0.0), self.sharding)

    # -- step building blocks ---------------------------------------------
    def _shard_map(self, f, n_in: int, n_out: int, donate=()):
        spec = jax.sharding.PartitionSpec(FLEET_AXIS)
        sm = jax.shard_map(f, mesh=self.mesh, in_specs=(spec,) * n_in,
                           out_specs=(spec,) * n_out if n_out > 1 else spec,
                           check_vma=False)
        return jax.jit(sm, donate_argnums=donate)

    def _ingest(self, frames: Dict[int, List]) -> jax.Array:
        """Stack per-shard frames onto the common canvas: (S, F_max + 1,
        H, W, 3), slot F_max the sacrificial zero camera."""
        S = self.plan.n_shards
        x = np.zeros((S, self.F_max + 1, self.canvas_h, self.canvas_w, 3),
                     np.float32)
        for gid in self.gids:
            s, c0 = self._group_slot[gid]
            for i, f in enumerate(frames[gid]):
                f = np.asarray(f, np.float32)
                if f.shape[0] > self.canvas_h or f.shape[1] > self.canvas_w:
                    raise ValueError(
                        f"frame {f.shape[:2]} exceeds the grid-derived "
                        f"canvas ({self.canvas_h}, {self.canvas_w})")
                x[s, c0 + i, :f.shape[0], :f.shape[1]] = f
        return jax.device_put(jnp.asarray(x), self.sharding)

    def _gate_fn(self):
        key = ("gate",)
        if key not in self._fns:
            det, t = self.det, self.det.cfg.tile

            def local(x, ref, idx):
                xp = pad_frames(x[0], t)
                # canvas-resident references: the comparison side is the
                # shard's padded reference canvas, addressed through the
                # same tile rows — no packed window duplication, stats
                # rows are the only output
                stats = _raw_gate_canvas(
                    xp, ref[0], idx[0], t, t, 8.0, COEF_BITS, RUN_BITS,
                    block=det.block, interpret=kops.interpret_mode())
                return stats[None]

            self._fns[key] = self._shard_map(local, 3, 1)
        return self._fns[key]

    def _conv_fn(self, k_max: int):
        key = ("conv", k_max)
        if key not in self._fns:
            det, t = self.det, self.det.cfg.tile
            w0, ws, head = det.weights[0], det.weights[1:], det.head

            def local(x, cidx, cnbr, upd, sidx, wipe, packed, canvas):
                interpret = kops.interpret_mode()
                p = _raw_entry(x[0], w0, cidx[0], t, t, block=det.block,
                               interpret=interpret)
                if ws:
                    p = _raw_stack(p, tuple(ws), cnbr[0], block=det.block,
                                   interpret=interpret)
                # only changed-OUTPUT rows graduate; margin and padding
                # rows carry target n_max and drop out of bounds
                new_packed = packed[0].at[upd[0]].set(p, mode="drop")
                # head applied PRE-scatter (bit-identical: per-pixel dot
                # products), then ONLY this step's rows hit the
                # persistent canvas — changed rows at their real
                # (cam, ty, tx), margin/padding rows on the sacrificial
                # camera plane.  A cold shard's plane is wiped to zeros
                # first (shard-exact canvas invalidation, in-program)
                k, C = p.shape[0], p.shape[-1]
                ph = jnp.dot(p.reshape(k * t * t, C), head,
                             precision=jax.lax.Precision.HIGHEST).reshape(
                    k, t, t, head.shape[-1])
                base = jnp.where(wipe[0][0], jnp.zeros_like(canvas[0]),
                                 canvas[0])
                new_canvas = _raw_scatter_changed(ph, sidx[0], base,
                                                  interpret=interpret)
                return new_packed[None], new_canvas[None]

            # donate the cache's packed buffer (argument 6): the update
            # writes in place of the old activations.  The canvas
            # (argument 7) is NOT donated here: the async pipeline's
            # collect() reads the previous step's heads — which ARE the
            # previous canvas buffer — after this dispatch is queued
            # (real-TPU canvas donation is a carried ROADMAP item)
            self._fns[key] = self._shard_map(local, 8, 2, donate=(6,))
        return self._fns[key]

    def _refadv_fn(self):
        key = ("refadv",)
        if key not in self._fns:
            t = self.det.cfg.tile

            def local(ref, x, mask):
                return jnp.where(mask[0], pad_frames(x[0], t), ref[0])[None]

            # pure jnp reference advancement (not a counted kernel
            # dispatch): advanced rows' full window regions take the
            # current frame's content (all writes carry the SAME frame,
            # so window overlap between simultaneously-advanced tiles is
            # harmless); donates the old reference canvas
            self._fns[key] = self._shard_map(local, 3, 1, donate=(0,))
        return self._fns[key]

    def _init_cache_arrays(self, cache: ShardedActivationCache) -> None:
        if cache.packed is not None:
            return
        S, t = self.plan.n_shards, self.det.cfg.tile
        c_last = self.det.cfg.channels[-1]
        a = self.det.head.shape[-1]
        cache.packed = jax.device_put(
            jnp.zeros((S, self.n_max, t, t, c_last), jnp.float32),
            self.sharding)
        cache.ref_canvas = jax.device_put(
            jnp.zeros((S, self.F_max + 1, self.canvas_h + 2,
                       self.padded_w, 3), jnp.float32), self.sharding)
        cache.canvas = jax.device_put(
            jnp.zeros((S, self.F_max + 1, self.canvas_h, self.canvas_w,
                       a), jnp.float32), self.sharding)
        cache.epoch_np = np.zeros((S, self.n_max), np.int64)
        cache.valid[:] = False

    def _host_plan(self, stats_np: np.ndarray,
                   cache: ShardedActivationCache,
                   threshold=0.0) -> _HostPlan:
        """Gate thresholding + ``reuse_sets`` dilation + table
        compaction for every shard — all host-side numpy on static
        tables (the phase the async pipeline overlaps with device
        compute).  ``threshold``: scalar, or {gid: per-camera (F_g,) or
        per-camera-per-tile-class (F_g, N_TILE_CLASSES) array} (the rate
        controller's schedule; see ``gate_threshold_schedule``)."""
        with obs_trace.span("reuse_plan") as sp:
            S = self.plan.n_shards
            t = self.det.cfg.tile
            rings = kops.halo_rings(self.det.layers, t)
            per_changed, per_compute = [], []
            raw_total = changed_total = computed_total = 0
            cold_shards = 0
            gate_stats: List[Optional[np.ndarray]] = []
            thr_by_shard = self._shard_thresholds(threshold)
            for s in range(S):
                n_s = self._n_s[s]
                if n_s == 0:
                    per_changed.append(np.zeros(0, bool))
                    per_compute.append(np.zeros(0, bool))
                    gate_stats.append(None)
                    continue
                rows = stats_np[s, :n_s]
                if cache.valid[s]:
                    raw = np.asarray(gate_changed_rows(
                        rows, thr_by_shard[s], self._idx_np[s][:, 0],
                        self._cls_np[s]), bool)
                    gate_stats.append(rows)
                else:
                    # cold shard: reference content is stale — force a full
                    # recompute of its rows inside the same SPMD step
                    raw = np.ones(n_s, bool)
                    gate_stats.append(None)
                    cold_shards += 1
                changed, compute = kops.reuse_sets(raw, self._nbr_np[s],
                                                   rings)
                per_changed.append(changed)
                per_compute.append(compute)
                raw_total += int(raw.sum())
                changed_total += int(changed.sum())
                computed_total += int(compute.sum())
            k_max = _pow2(max([int(c.sum()) for c in per_compute] + [0])) \
                if computed_total else 0
            adv = np.zeros((S, self.n_max), bool)
            for s in range(S):
                n_s = self._n_s[s]
                if n_s == 0:
                    continue
                if not cache.valid[s]:
                    adv[s, :n_s] = True
                    continue
                a = ref_advance_rows(thr_by_shard[s], self._idx_np[s][:, 0],
                                     per_changed[s], self._cls_np[s])
                adv[s, :n_s] = True if a is None else a
            cold_mask = ~np.asarray(cache.valid, bool)
            tile_bytes = t * t * int(self.det.head.shape[-1]) * 4
            stats = ShardedReuseStats(
                total_tiles=self.n_total, raw_changed=raw_total,
                changed_out=changed_total, computed=computed_total,
                launched=S * k_max if k_max else 0, k_max=k_max,
                cold_shards=cold_shards,
                canvas_bytes=changed_total * tile_bytes,
                per_shard_computed=[int(c.sum()) for c in per_compute],
                gate_stats=gate_stats)
            sp.set(raw_changed=raw_total, computed=computed_total,
                   launched=stats.launched)
            if k_max == 0:
                return _HostPlan(0, None, None, None, None, adv, cold_mask,
                                 stats)
            cidx = np.zeros((S, k_max, 3), np.int32)
            cidx[:, :, 0] = self.F_max                 # sacrificial padding
            cnbr = np.full((S, k_max, 8), -1, np.int32)
            upd = np.full((S, k_max), self.n_max, np.int32)   # n_max = drop
            sidx = np.zeros((S, k_max, 3), np.int32)
            sidx[:, :, 0] = self.F_max                 # sacrificial plane
            for s in range(S):
                compute = per_compute[s]
                k = int(compute.sum())
                if k == 0:
                    continue
                ci, cn = kops.compact_tables(self._idx_np[s], self._nbr_np[s],
                                             compute)
                cidx[s, :k] = ci
                cnbr[s, :k] = cn
                slots = np.nonzero(compute)[0]
                ch = per_changed[s][slots]
                upd[s, :k] = np.where(ch, slots, self.n_max).astype(np.int32)
                # canvas targets: only changed-OUTPUT rows write their real
                # tile; margin rows keep the cache's (still-exact) old bytes
                # by writing the sacrificial plane instead
                sidx[s, :k] = np.where(ch[:, None], ci,
                                       np.array([[self.F_max, 0, 0]],
                                                np.int32))
            return _HostPlan(k_max, cidx, cnbr, upd, sidx, adv, cold_mask,
                             stats)

    def _shard_thresholds(self, threshold) -> List:
        """Resolve the scalar / {gid: per-camera or per-camera-per-
        tile-class} threshold into one scalar, (F_s,) or
        (F_s, n_classes) value per shard, flat-camera indexed."""
        if not isinstance(threshold, dict):
            return [threshold] * self.plan.n_shards
        vals = {g: np.asarray(v, np.float64) for g, v in threshold.items()}
        n_cls = max([v.shape[1] for v in vals.values() if v.ndim == 2],
                    default=0)
        out = []
        for s in range(self.plan.n_shards):
            shape = (max(self._F_s[s], 1),) + ((n_cls,) if n_cls else ())
            thr = np.zeros(shape, np.float64)
            for gid in self._shard_gids[s]:
                if gid in vals:
                    _, c0 = self._group_slot[gid]
                    v = vals[gid]
                    if n_cls and v.ndim == 1:
                        v = np.repeat(v[:, None], n_cls, axis=1)
                    thr[c0:c0 + v.shape[0]] = v
            out.append(thr)
        return out

    def _put_tables(self, plan: _HostPlan, parity: int):
        """Stage one step's compact tables into a device slot.  Two
        slots alternate (``parity``): the PREVIOUS step's tables stay
        referenced while its conv chain is still in flight, so staging
        step t+1 can never free buffers step t is reading.  The canvas
        slots ride the same double-buffer discipline: the conv returns a
        fresh canvas buffer each step (no donation — collect() may still
        read the old one), so the in-flight step's heads stay alive."""
        slot = jax.device_put(
            (jnp.asarray(plan.cidx), jnp.asarray(plan.cnbr),
             jnp.asarray(plan.upd), jnp.asarray(plan.sidx),
             jnp.asarray(plan.cold_mask[:, None])), self.sharding)
        if not hasattr(self, "_table_slots"):
            self._table_slots: List = [None, None]
        self._table_slots[parity % 2] = slot
        return slot

    def _adv_canvas_mask(self, adv: np.ndarray) -> np.ndarray:
        """(S, n_max) advance-row mask -> bool (S, F_max + 1, H + 2,
        W', 1) canvas mask over the advanced rows' haloed window
        regions (host-built from the static tables; broadcasts over
        channels)."""
        t = self.det.cfg.tile
        S = self.plan.n_shards
        m = np.zeros((S, self.F_max + 1, self.canvas_h + 2,
                      self.padded_w, 1), bool)
        for s in range(S):
            for cam, ty, tx in self._idx_np[s][adv[s, :self._n_s[s]]]:
                m[s, cam, ty * t:ty * t + t + 2,
                  tx * t:tx * t + t + 2, 0] = True
        return m

    def _advance_refs(self, cache: ShardedActivationCache, x,
                      plan: _HostPlan) -> None:
        """Advance the reference canvas + epoch table per the plan's
        (S, n_max) advance mask."""
        if not plan.adv.any():
            return
        with obs_trace.span("ref_advance"):
            mask = jax.device_put(
                jnp.asarray(self._adv_canvas_mask(plan.adv)),
                self.sharding)
            cache.ref_canvas = self._refadv_fn()(cache.ref_canvas, x, mask)
            cache.epoch_np[plan.adv] = cache.steps

    # -- synchronous steps -------------------------------------------------
    def step_reuse(self, frames: Dict[int, List],
                   cache: ShardedActivationCache, threshold=0.0):
        """One sharded delta-gated fleet step, blocking at the end.

        Dispatch structure (counted once per step — SPMD: one launch
        runs on every shard): 1 gate + the ≤3-dispatch conv chain
        (entry, stack, changed-only canvas scatter) on changed steps;
        the gate ALONE on all-static steps — the persistent canvas is
        served as-is, zero conv/scatter launches, 0 bytes written;
        nothing on an all-empty fleet.  NOTE the sharded path gates on
        cold shards too (SPMD uniformity — the single-device cold step
        skips the gate instead); outputs stay bit-identical.  Returns
        ({gid: per-camera head maps (numpy)}, ShardedReuseStats)."""
        if cache.plan is not self.plan:
            raise ValueError("cache was built for a different shard plan")
        cache.steps += 1
        cache.total_tiles += self.n_total
        if self.n_total == 0:
            return self._zero_heads(frames), ShardedReuseStats(
                0, 0, 0, 0, 0, 0, 0)
        with obs_trace.span("stage"):
            self._init_cache_arrays(cache)
            x = self._ingest(frames)
        with obs_trace.span("gate"):
            kops.record_dispatch("tile_delta_gate")
            stats_f = self._gate_fn()(x, cache.ref_canvas, self.idx_pad)
        with obs_trace.span("gate_readback") as sp:
            stats_np = np.asarray(stats_f)
            sp.set(bytes=stats_np.nbytes)
        obs_metrics.READBACK_BYTES.inc(stats_np.nbytes, kind="gate")
        plan = self._host_plan(stats_np, cache, threshold)
        heads = self._dispatch_conv(x, plan, cache)
        self._advance_refs(cache, x, plan)
        if plan.stats.cold_shards:
            cache.cold_steps += 1
        cache.valid[:] = True
        cache.launched_tiles += plan.stats.launched
        cache.canvas_bytes_last = plan.stats.canvas_bytes
        cache.canvas_bytes_total += plan.stats.canvas_bytes
        with obs_trace.span("heads_out") as sp:
            heads_np = np.asarray(heads)
            out = self._split_heads(heads_np, frames)
            sp.set(bytes=heads_np.nbytes)
        obs_metrics.READBACK_BYTES.inc(heads_np.nbytes, kind="heads")
        return out, plan.stats

    def step_full(self, frames: Dict[int, List]):
        """The non-reuse sharded super-launch (cold path / A-B
        baseline): ≤3 dispatches, bit-identical per group to
        ``superlaunch_forward``.  Returns {gid: head maps (numpy)}."""
        if self.n_total == 0:
            return self._zero_heads(frames)
        x = self._ingest(frames)
        plan = self._full_plan()
        kops.record_dispatch("roi_conv_entry")
        if self.det.num_conv_layers > 1:
            kops.record_dispatch("roi_conv_stack")
        kops.record_dispatch("sbnet_scatter_fleet")
        slot = self._put_tables(plan, 0)
        packed0 = jax.device_put(
            jnp.zeros((self.plan.n_shards, self.n_max, self.det.cfg.tile,
                       self.det.cfg.tile, self.det.cfg.channels[-1]),
                      jnp.float32), self.sharding)
        canvas0 = jax.device_put(
            jnp.zeros((self.plan.n_shards, self.F_max + 1, self.canvas_h,
                       self.canvas_w, self.det.head.shape[-1]),
                      jnp.float32), self.sharding)
        _, heads = self._conv_fn(plan.k_max)(x, *slot, packed0, canvas0)
        return self._split_heads(np.asarray(heads), frames)

    def _full_plan(self) -> _HostPlan:
        """An everything-changed plan: compact tables = full tables."""
        S = self.plan.n_shards
        k_max = _pow2(max(self._n_s + [1]))
        cidx = np.zeros((S, k_max, 3), np.int32)
        cidx[:, :, 0] = self.F_max
        cnbr = np.full((S, k_max, 8), -1, np.int32)
        upd = np.full((S, k_max), self.n_max, np.int32)
        sidx = np.zeros((S, k_max, 3), np.int32)
        sidx[:, :, 0] = self.F_max
        for s in range(S):
            n_s = self._n_s[s]
            cidx[s, :n_s] = self._idx_np[s]
            cnbr[s, :n_s] = self._nbr_np[s]
            upd[s, :n_s] = np.arange(n_s)
            sidx[s, :n_s] = self._idx_np[s]
        t = self.det.cfg.tile
        tile_bytes = t * t * int(self.det.head.shape[-1]) * 4
        stats = ShardedReuseStats(self.n_total, self.n_total, self.n_total,
                                  self.n_total, S * k_max, k_max, S,
                                  canvas_bytes=self.n_total * tile_bytes)
        return _HostPlan(k_max, cidx, cnbr, upd, sidx,
                         np.zeros((S, self.n_max), bool),
                         np.ones(S, bool), stats)

    def _dispatch_conv(self, x, plan: _HostPlan,
                       cache: ShardedActivationCache, parity: int = 0):
        """Dispatch the conv chain for one planned step; returns the
        heads future (= the updated persistent canvas).  Counts one
        launch per kernel — the SPMD program runs each once on every
        shard.  ``k_max == 0`` (all-static) is a ZERO-dispatch path:
        nothing is launched, no canvas byte is written, and the cached
        canvas is served directly."""
        if plan.k_max == 0:
            return cache.canvas
        with obs_trace.span("conv_dispatch"):
            kops.record_dispatch("roi_conv_entry")
            if self.det.num_conv_layers > 1:
                kops.record_dispatch("roi_conv_stack")
            kops.record_dispatch("sbnet_scatter_changed")
            slot = self._put_tables(plan, parity)
            cache.packed, cache.canvas = self._conv_fn(plan.k_max)(
                x, *slot, cache.packed, cache.canvas)
        return cache.canvas

    # -- output plumbing ---------------------------------------------------
    def _split_heads(self, heads_np: np.ndarray, frames: Dict[int, List]
                     ) -> Dict[int, List[np.ndarray]]:
        out: Dict[int, List[np.ndarray]] = {}
        for gid in self.gids:
            s, c0 = self._group_slot[gid]
            outs = []
            for i, f in enumerate(frames[gid]):
                h, w = np.asarray(f).shape[:2]
                outs.append(heads_np[s, c0 + i, :h, :w])
            out[gid] = outs
        return out

    def _zero_heads(self, frames: Dict[int, List]
                    ) -> Dict[int, List[np.ndarray]]:
        a = self.det.head.shape[-1]
        return {gid: [np.zeros(np.asarray(f).shape[:2] + (a,), np.float32)
                      for f in frames[gid]] for gid in self.gids}


class AsyncShardedPipeline:
    """Depth-1 host/device software pipeline over a ShardedSuperlaunch.

    ``submit(frames)`` dispatches step t's GATE first, then step t-1's
    conv chain behind it — so pulling step t's gate stats blocks only on
    the gate, and the host planning for step t (thresholding, dilation,
    compaction, table staging) runs while the device executes step t-1's
    conv.  ``collect()`` is the ONLY place that blocks on head maps (the
    consumer edge).  ``overlap_fraction`` reports how much host planning
    time ran under an in-flight device step."""

    def __init__(self, runtime: ShardedSuperlaunch,
                 cache: ShardedActivationCache, threshold=0.0):
        self.rt = runtime
        self.cache = cache
        self.threshold = threshold
        self._staged = None           # (step, x, plan, frames, t_submit)
        self._ready: deque = deque()  # (step, heads_future, stats,
        #                                frames, t_submit)
        self._step = 0
        self.host_s = 0.0             # total host planning time
        self.overlapped_host_s = 0.0  # ... under an in-flight device step
        self.blocked_s = 0.0          # consumer-edge block time
        self.latencies: List[float] = []

    def submit(self, frames: Dict[int, List]) -> int:
        rt, cache = self.rt, self.cache
        step = self._step
        self._step += 1
        t0 = time.perf_counter()
        cache.steps += 1
        cache.total_tiles += rt.n_total
        if rt.n_total == 0:
            self._ready.append((step, None, ShardedReuseStats(
                0, 0, 0, 0, 0, 0, 0), frames, t0, obs_trace.NULL_SPAN))
            return step
        rt._init_cache_arrays(cache)
        x = rt._ingest(frames)
        # 1. gate for THIS step goes first on the device queue...
        with obs_trace.span("gate", step=step):
            kops.record_dispatch("tile_delta_gate")
            stats_f = rt._gate_fn()(x, cache.ref_canvas, rt.idx_pad)
        # 2. ...then the conv chain of the STAGED previous step, so the
        # stats pull below waits only for the gate while the conv runs on
        h0 = time.perf_counter()
        with obs_trace.span("host_plan", step=step) as hsp:
            self._flush_staged()
            in_flight = bool(self._ready)
            stats_np = np.asarray(stats_f)        # blocks on the gate only
            # 3. host planning for THIS step — overlaps step t-1's conv
            plan = rt._host_plan(stats_np, cache, self.threshold)
            rt._advance_refs(cache, x, plan)
            hsp.set(overlapped=in_flight, k_max=plan.k_max,
                    computed=plan.stats.computed)
        if plan.stats.cold_shards:
            cache.cold_steps += 1
        cache.valid[:] = True
        cache.launched_tiles += plan.stats.launched
        cache.canvas_bytes_last = plan.stats.canvas_bytes
        cache.canvas_bytes_total += plan.stats.canvas_bytes
        host = time.perf_counter() - h0
        self.host_s += host
        if in_flight:
            self.overlapped_host_s += host
        self._staged = (step, x, plan, frames, t0)
        return step

    def _flush_staged(self) -> None:
        if self._staged is None:
            return
        step, x, plan, frames, t0 = self._staged
        self._staged = None
        # the device-compute span opens at dispatch and closes at the
        # collect() fence — in-flight time lands on its own trace track
        # with NO added sync (the fence already exists)
        dspan = obs_trace.begin("device_compute", track="device",
                                step=step, k_max=plan.k_max)
        heads = self.rt._dispatch_conv(x, plan, self.cache,
                                       parity=step % 2)
        self._ready.append((step, heads, plan.stats, frames, t0, dspan))

    def collect(self):
        """Block on the OLDEST completed step (the consumer edge) and
        return (step, {gid: head maps}, stats)."""
        if not self._ready:
            self._flush_staged()
        if not self._ready:
            raise RuntimeError("collect() with no submitted step pending")
        step, heads, stats, frames, t0, dspan = self._ready.popleft()
        b0 = time.perf_counter()
        with obs_trace.span("collect", step=step):
            if heads is None:
                dspan.end()
                out = self.rt._zero_heads(frames)
            else:
                heads = jax.block_until_ready(heads)  # the ONLY fence
                dspan.end()
                out = self.rt._split_heads(np.asarray(heads), frames)
        now = time.perf_counter()
        self.blocked_s += now - b0
        self.latencies.append(now - t0)
        return step, out, stats

    def drain(self) -> List:
        """Collect every outstanding step."""
        out = []
        while self._ready or self._staged is not None:
            out.append(self.collect())
        return out

    @property
    def overlap_fraction(self) -> float:
        """Fraction of host planning time spent while a device step was
        in flight (0 on a fully serial schedule)."""
        return self.overlapped_host_s / self.host_s if self.host_s else 0.0

    @property
    def p99_latency_s(self) -> float:
        return float(np.percentile(self.latencies, 99)) \
            if self.latencies else 0.0
