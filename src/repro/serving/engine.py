"""Batched serving engine with RoI-sparsified prefill and batched decode.

The CrossRoI insight applied to transformer serving: when a request's
prompt is a multi-camera patch stream (VLM) or any multi-stream ingestion
with cross-stream redundancy, the offline set-cover mask gives a keep-list.
The engine packs kept tokens into a dense prefix (kernels/ops.pack_tokens),
prefills ONLY the packed tokens (compute drops ~proportionally to the
mask), and decodes against the packed KV cache — attention stays correct
because positions travel with the tokens (RoPE is applied at original
positions; causality follows original order).

Decode is batched across the request group: prefills stay per-request
(keep-lists are ragged), but every request's caches are allocated at the
group-common ``max_seq``, stacked into one pytree, and each greedy step is
ONE jit'd vmapped dispatch for the whole group instead of a Python loop of
per-request dispatches.  Per-request start positions ride along as a
vmapped scalar, so RoI-packed (start = n_kept) and dense (start = S)
requests share the same batch.

Plain text serving works through the same engine with roi_sparsity=False.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig
from repro.kernels import ops as kops
from repro.models import model as M
from repro.models.dist import DistContext
from repro.obs import metrics as obs_metrics, trace as obs_trace


@dataclass
class Request:
    rid: int
    tokens: Optional[np.ndarray] = None          # (S,) int32 prompt
    patches: Optional[np.ndarray] = None         # (S_img, D) VLM stream
    keep: Optional[np.ndarray] = None            # (S,) bool RoI keep-list
    max_new_tokens: int = 16
    # deadline-batched serving (serve_deadline): which camera group the
    # request belongs to, and when it arrived at the server
    group: Optional[int] = None
    arrival_s: float = 0.0


@dataclass
class ServeReport:
    """Accounting from ``serve_deadline``: how request groups formed."""
    complete_flushes: int = 0        # group reached its expected size
    deadline_flushes: int = 0        # released early by the deadline
    straggler_requests: int = 0      # arrived after their group released
    release_s: Dict[int, float] = field(default_factory=dict)  # rid -> t

    def wait_s(self, req: "Request") -> float:
        """Batching delay this request paid in the group former."""
        return self.release_s[req.rid] - req.arrival_s


@dataclass
class RoIPrefillResult:
    logits: jax.Array
    caches: Any
    n_kept: int
    n_total: int

    @property
    def compute_fraction(self) -> float:
        return self.n_kept / max(self.n_total, 1)


def _round_up(x: int, block: int) -> int:
    return -(-x // block) * block


def ring_donate_argnums(*argnums: int) -> Tuple[int, ...]:
    """The donation idiom of the engine's persistent device-resident
    group-cache ring: donate the named positional args so on hardware
    the update is in-place (O(written) traffic, not O(buffer)), but
    donate NOTHING on CPU — the CPU backend ignores donation and warns,
    and tests read pre-update buffers the donation would have
    poisoned."""
    return () if jax.default_backend() == "cpu" else tuple(argnums)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, scfg: ServeConfig, params: Dict,
                 dist: Optional[DistContext] = None):
        self.cfg = cfg
        self.scfg = scfg
        self.params = params
        self.dist = dist
        self._decode = jax.jit(
            lambda p, t, c, pos: M.decode_step(p, cfg, t, c, pos, dist=dist))
        self._prefill = jax.jit(
            lambda p, b, c, pos, last=None: M.prefill(
                p, cfg, b, c, dist=dist, positions=pos, last_index=last))
        # group decode: vmap over stacked (per-request B=1) caches, tokens,
        # and scalar positions -> one dispatch per step for the whole group
        self._decode_group = jax.jit(
            lambda p, t, c, pos: jax.vmap(
                lambda tb, cb, pb: M.decode_step(p, cfg, tb, cb, pb,
                                                 dist=dist),
                in_axes=(0, 0, 0))(t, c, pos))
        # persistent group cache ring: one stacked (G, ...) cache pytree
        # reused across flushes, so serve() never jnp.stack's per-request
        # caches.  Stale slot contents are harmless: decode only attends
        # cache rows at positions written by THIS request's prefill/decode
        # chain (rows past the current position are masked).  Slot writes
        # go through one jit'd dynamic-update with the ring donated, so on
        # hardware the update is in-place (O(slot) traffic per request,
        # not O(ring)); CPU ignores donation and falls back to a copy.
        donate = ring_donate_argnums(0)
        self._ring_write = jax.jit(
            lambda ring, slot, gi: jax.tree.map(
                lambda full, s: jax.lax.dynamic_update_index_in_dim(
                    full, s.astype(full.dtype), gi, 0), ring, slot),
            donate_argnums=donate)
        self._ring = None
        self._ring_sig: Optional[Tuple[int, int]] = None
        self.ring_rebuilds = 0          # ring (re)allocations — steady
        #                                 state stays flat across flushes
        self.cache_stack_count = 0      # per-flush jnp.stack's (legacy
        #                                 path only; serve() must not bump)

    # -- plain prefill -----------------------------------------------------
    def prefill(self, batch: Dict, max_seq: Optional[int] = None,
                caches=None):
        """``caches`` (optional) supplies a preallocated cache pytree —
        serve() passes a slot of the persistent group ring instead of
        allocating per request."""
        B = next(iter(batch.values())).shape[0]
        max_seq = max_seq or self.scfg.max_seq
        if caches is None:
            caches = M.init_cache(self.cfg, B, max_seq)
        return self._prefill(self.params, batch, caches, None)

    # -- RoI-sparsified prefill ---------------------------------------------
    def roi_prefill(self, tokens: jax.Array, keep: jax.Array,
                    block: int = 128,
                    max_seq: Optional[int] = None,
                    caches=None) -> RoIPrefillResult:
        """tokens: (S,) or (S, D) stream; keep: (S,) bool.  Packs kept
        tokens, prefills the packed prefix with original positions.
        ``max_seq`` sizes the KV cache (>= packed length; decode masks
        slots past the current position, so oversized caches are safe —
        the group driver uses this to give every request the same cache
        shape)."""
        S = tokens.shape[0]
        packed, positions, n_kept = kops.pack_tokens(tokens, keep, block)
        Sp = packed.shape[0]
        # positions carry PAD_POS on padding rows: padded keys are never
        # attended (pos_q >= pos_k fails), padded queries produce garbage
        # rows that are discarded, and decode masks cache slots >= n_kept.
        if packed.ndim == 1:
            batch = {"tokens": packed[None]}
        else:
            # patch stream: embed via the VLM frontend path
            batch = {"tokens": jnp.zeros((1, 0), jnp.int32),
                     "patches": packed[None]}
        if caches is None:
            caches = M.init_cache(self.cfg, 1, max(max_seq or Sp, Sp, 1))
        logits, caches = self._prefill(self.params, batch, caches,
                                       positions[None], n_kept - 1)
        return RoIPrefillResult(logits, caches, int(n_kept), S)

    # -- decode -------------------------------------------------------------
    def decode_tokens(self, caches, first_token: jax.Array, start_pos: int,
                      n_steps: int) -> Tuple[np.ndarray, Any]:
        B = first_token.shape[0]
        out = []
        tok = first_token.reshape(B, 1)
        for i in range(n_steps):
            logits, caches = self._decode(self.params, tok, caches,
                                          start_pos + i)
            tok = jnp.argmax(logits[:, -1], axis=-1).reshape(B, 1)
            out.append(np.asarray(tok))
        return np.concatenate(out, axis=1), caches

    def decode_tokens_group(self, caches_list: List[Any],
                            first_tokens: List[jax.Array],
                            start_pos: List[int],
                            n_steps: int) -> Tuple[np.ndarray, Any]:
        """Greedy-decode G same-cache-shape requests together.

        caches_list: per-request cache pytrees (B=1, identical shapes —
        allocate prefills at a group-common max_seq).  Returns (G, n_steps)
        tokens; one jit'd dispatch per step serves the whole group.

        Legacy entry point: stacks the per-request caches on every call
        (counted in ``cache_stack_count``).  ``serve`` avoids this by
        prefilling straight into the persistent group ring."""
        self.cache_stack_count += 1
        caches = jax.tree.map(lambda *xs: jnp.stack(xs), *caches_list)
        return self._decode_stacked(caches, first_tokens, start_pos,
                                    n_steps)

    def _decode_stacked(self, caches, first_tokens, start_pos,
                        n_steps: int) -> Tuple[np.ndarray, Any]:
        tok = jnp.stack([jnp.asarray(t).reshape(1, 1)
                         for t in first_tokens])            # (G, 1, 1)
        pos0 = jnp.asarray(start_pos, jnp.int32)            # (G,)
        out = []
        for i in range(n_steps):
            logits, caches = self._decode_group(self.params, tok, caches,
                                                pos0 + i)
            tok = jnp.argmax(logits[:, :, -1], axis=-1)[..., None]  # (G,1,1)
            out.append(np.asarray(tok[:, :, 0]))
        return np.concatenate(out, axis=1), caches

    # -- persistent group cache ring ------------------------------------------
    def _ensure_ring(self, G: int, max_seq: int):
        """(Re)allocate the stacked group cache only when the flush needs a
        wider group or a longer sequence than the ring already holds —
        steady-state flushes reuse the same buffers with zero stacking."""
        sig = (G, max_seq)
        if (self._ring is None or self._ring_sig[0] != G
                or self._ring_sig[1] < max_seq):
            slot = M.init_cache(self.cfg, 1, max_seq, abstract=True)
            self._ring = jax.tree.map(
                lambda s: jnp.zeros((G,) + s.shape, s.dtype), slot)
            self._ring_sig = sig
            self.ring_rebuilds += 1
        return self._ring

    # -- batched request driver ----------------------------------------------
    def serve(self, requests: List[Request], greedy_steps: int = 8
              ) -> Dict[int, np.ndarray]:
        """Batched serving: group requests to max_batch, prefill each
        request (RoI-packed when a keep-list is present — keep-lists are
        ragged, so packing stays per-request) INTO a slot of the persistent
        group cache ring, then greedy-decode the whole group in lockstep
        with one vmapped dispatch per step.  The ring survives across
        flushes: no per-flush cache allocation and no per-request
        ``jnp.stack`` — ``cache_stack_count`` stays flat and
        ``ring_rebuilds`` only moves when the group geometry grows.
        Returns {rid: generated tokens}."""
        results: Dict[int, np.ndarray] = {}
        group: List[Request] = []
        with obs_trace.span("serve", requests=len(requests)):
            obs_metrics.SERVE_EVENTS.inc(len(requests), event="request")
            for r in requests:
                group.append(r)
                if len(group) >= self.scfg.max_batch:
                    self._flush_group(group, greedy_steps, results)
                    group = []
            self._flush_group(group, greedy_steps, results)
        return results

    def _flush_group(self, group: List[Request], greedy_steps: int,
                     results: Dict[int, np.ndarray]) -> None:
        """Prefill every request of ``group`` into the persistent ring and
        greedy-decode the batch in lockstep (shared by ``serve`` and the
        deadline former)."""
        if not group:
            return
        pack_block = 128
        steps = [min(r.max_new_tokens, greedy_steps) for r in group]
        gsteps = max(steps)
        # group-common cache length: every request's packed/dense
        # prompt plus the GROUP's decode step count fits (lockstep
        # decode runs gsteps for everyone; a shorter per-request
        # budget must not let KV writes clamp onto the cache end)
        need = []
        for r in group:
            if r.keep is not None and self.scfg.roi_sparsity:
                need.append(_round_up(len(r.tokens), pack_block) + gsteps)
            else:
                need.append(len(r.tokens) + gsteps)
        with obs_trace.span("serve_flush", batch=len(group),
                            decode_steps=gsteps):
            ring = self._ensure_ring(len(group), max(need))

            firsts, starts = [], []
            for gi, r in enumerate(group):   # ragged per-request packing
                slot = jax.tree.map(lambda x: x[gi], ring)
                if r.keep is not None and self.scfg.roi_sparsity:
                    res = self.roi_prefill(jnp.asarray(r.tokens),
                                           jnp.asarray(r.keep),
                                           block=pack_block, caches=slot)
                    new_slot = res.caches
                    firsts.append(jnp.argmax(res.logits[:, -1], -1))
                    starts.append(res.n_kept)
                else:
                    batch = {"tokens": jnp.asarray(r.tokens)[None]}
                    logits, new_slot = self.prefill(batch, caches=slot)
                    firsts.append(jnp.argmax(logits[:, -1], -1))
                    starts.append(len(r.tokens))
                ring = self._ring_write(ring, new_slot, gi)
            toks, ring = self._decode_stacked(ring, firsts, starts, gsteps)
        self._ring = ring                 # keep buffers for next flush
        for gi, (r, ns) in enumerate(zip(group, steps)):
            results[r.rid] = toks[gi, :ns]

    # -- deadline-based group forming ------------------------------------------
    def serve_deadline(self, requests: List[Request],
                       group_sizes: Dict[int, int],
                       deadline_s: float, greedy_steps: int = 8
                       ) -> Tuple[Dict[int, np.ndarray], ServeReport]:
        """Deadline-based group former over a timestamped request stream —
        the ``repro.net.batcher`` release policy at the serving layer.

        Requests carry ``(group, arrival_s)``; a group flushes the moment
        its ``group_sizes[gid]`` members are pending, or when its oldest
        pending member has waited ``deadline_s`` (measured against the
        stream clock, which advances with each arrival).  Members that
        show up after their batch left are stragglers: they ride the
        group's next flush and are counted in the report.  Each flush is
        one lockstep batch through the persistent cache ring, identical
        to ``serve``'s."""
        results: Dict[int, np.ndarray] = {}
        report = ServeReport()
        pending: Dict[int, List[Request]] = {}
        # after a deadline flush releases k of a group's N members, the
        # next (N - k) arrivals of that group are the stragglers of THAT
        # cycle — members beyond them belong to the next batch and are
        # not late.  A complete flush clears the quota.
        late_quota: Dict[int, int] = {}

        def flush(gid: int, now: float, by_deadline: bool) -> None:
            members = pending.pop(gid, [])
            if not members:
                return
            obs_metrics.BACKLOG_DEPTH.observe(len(members))
            obs_metrics.SERVE_EVENTS.inc(
                1, event="deadline_flush" if by_deadline
                else "complete_flush")
            self._flush_group(members, greedy_steps, results)
            for r in members:
                report.release_s[r.rid] = now
            if by_deadline:
                report.deadline_flushes += 1
                late_quota[gid] = (group_sizes.get(gid,
                                                   self.scfg.max_batch)
                                   - len(members))
            else:
                report.complete_flushes += 1
                late_quota[gid] = 0

        with obs_trace.span("serve_deadline", requests=len(requests)):
            obs_metrics.SERVE_EVENTS.inc(len(requests), event="request")
            for r in sorted(requests, key=lambda r: r.arrival_s):
                now = r.arrival_s
                # deadlines that expired while the stream was quiet
                for gid in list(pending):
                    oldest = min(m.arrival_s for m in pending[gid])
                    if now - oldest >= deadline_s:
                        flush(gid, oldest + deadline_s, by_deadline=True)
                gid = r.group if r.group is not None else -1
                if late_quota.get(gid, 0) > 0:
                    report.straggler_requests += 1
                    obs_metrics.SERVE_EVENTS.inc(1,
                                                 event="straggler_request")
                    late_quota[gid] -= 1
                pending.setdefault(gid, []).append(r)
                if len(pending[gid]) >= group_sizes.get(
                        gid, self.scfg.max_batch):
                    flush(gid, now, by_deadline=False)
            for gid in list(pending):
                oldest = min(m.arrival_s for m in pending[gid])
                flush(gid, oldest + deadline_s, by_deadline=True)
        return results, report
