"""Temporal delta-gated inference benchmark: changed-tile compact
super-launch + persistent packed-activation cache vs full recompute.

Five panels:

  1. compute proportionality — over a mostly-static fleet trace (per
     step, a couple of cameras move one tile each; the rest are static)
     the per-step convolved-tile count tracks the DILATED changed set,
     not the active set; the reduction vs full recompute is the
     acceptance number (floor 40%).
  2. correctness — at threshold 0 every step's head maps are
     bit-identical to ``superlaunch_forward`` full recompute, and the
     per-step compute count never exceeds the receptive-field dilation
     bound computed by an INDEPENDENT 2D grid-morphology oracle.
  3. dispatch structure — warm changed steps: gate + entry + stack +
     changed-only canvas scatter (conv ceiling ≤3 preserved); all-static
     steps: the gate ALONE (the persistent canvas is served as-is —
     zero conv/scatter launches, 0 canvas bytes written).
  4. wall clock (interpret mode) — the reuse step on the sparse-motion
     steady state vs the full-recompute super-launch step (interleaved
     min over reps), plus the all-static step wall (the zero-copy
     gate-only step, a history headline the sentinel watches) and the
     VMEM-calibrated ``ops.choose_block`` size the blocked
     entry/stack/scatter walks run at.
  5. persistent-canvas accounting — per-step canvas bytes written are
     exactly ``changed_out * tile_bytes`` (bytes ∝ changed fraction, 0
     on all-static steps).
  6. per-tile-class gate-threshold schedule — shed cameras' body tiles
     stop relaunching tiny deltas under a (C, 2) [body, halo] schedule
     while the head-map accuracy floor vs exact recompute holds.

``quick=True`` is the CI smoke shape.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_json, table
from repro.fleet.runtime import fleet_inference_step, fleet_reuse_step
from repro.net.encoder import gate_threshold_schedule
from repro.serving.detector import (DetectorConfig, PackedActivationCache,
                                    RoIDetector)


def _block(out):
    jax.tree_util.tree_map(
        lambda a: a.block_until_ready() if hasattr(
            a, "block_until_ready") else a, out)


def _time_min_interleaved(fns, reps: int):
    best = [float("inf")] * len(fns)
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            _block(fn())
            best[i] = min(best[i], time.perf_counter() - t0)
    return best


def _dilation_bound(grids, frames_a, frames_b, tile, n_layers):
    """Independent oracle for the per-step compute bound: scatter the
    raw changed tiles (any haloed-window difference) onto each camera's
    tile grid, 3x3-dilate it twice by the tile rings that the packed
    layers' n_layers-1 px of halo cross, with plain numpy morphology
    (NOT the neighbor-table helper under test), and count the active
    survivors."""
    rings = -(-(n_layers - 1) // tile)
    total = 0
    for g, fa, fb in zip(grids, frames_a, frames_b):
        gy, gx = g.shape
        diff = np.zeros((gy, gx), bool)
        d = np.pad(np.any(np.asarray(fa) != np.asarray(fb), axis=-1), 1)
        for ty in range(gy):
            for tx in range(gx):
                win = d[ty * tile:ty * tile + tile + 2,
                        tx * tile:tx * tile + tile + 2]
                diff[ty, tx] = g[ty, tx] and bool(win.any())
        for _ in range(2 * rings):
            dp = np.pad(diff, 1)
            grown = np.zeros_like(diff)
            for dy in (0, 1, 2):
                for dx in (0, 1, 2):
                    grown |= dp[dy:dy + gy, dx:dx + gx]
            diff = grown
        total += int((diff & g).sum())
    return total


def run(verbose: bool = True, quick: bool = False):
    t00 = time.time()
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    t = det.cfg.tile
    n_layers = det.num_conv_layers
    K = 2
    cams = 3
    gshape = (6, 8) if quick else (8, 10)
    steps = 4 if quick else 8
    reps = 3 if quick else 5
    rng = np.random.default_rng(0)
    grids = {gid: [rng.random(gshape) < 0.5 for _ in range(cams)]
             for gid in range(K)}
    for gs in grids.values():
        for g in gs:
            g[1, 1] = True
    flat_grids = [g for gs in grids.values() for g in gs]
    n_active = sum(int(g.sum()) for g in flat_grids)

    def mk_frames():
        return {gid: [np.asarray(
            rng.normal(size=(gshape[0] * t, gshape[1] * t, 3)),
            np.float32) for _ in range(cams)] for gid in range(K)}

    def perturb(frames, n_cams=2):
        """The mostly-static trace's per-step motion: one tile's worth
        of pixels moves on ``n_cams`` cameras; everything else is
        bit-static."""
        out = {g: [f.copy() for f in fs] for g, fs in frames.items()}
        for _ in range(n_cams):
            gid = int(rng.integers(K))
            cam = int(rng.integers(cams))
            ty, tx = (int(rng.integers(gshape[0])),
                      int(rng.integers(gshape[1])))
            f = out[gid][cam]
            f[ty * t:(ty + 1) * t, tx * t:(tx + 1) * t, :] += \
                rng.normal(size=(t, t, 3)).astype(np.float32) * 5
        return out

    def as_jnp(frames):
        return {g: [jnp.asarray(f) for f in fs]
                for g, fs in frames.items()}

    # --- panels 1-3: trace — proportionality, bit-exactness, dispatch ---
    cache = PackedActivationCache()
    frames = mk_frames()
    fleet_reuse_step(det, as_jnp(frames), grids, cache)     # cold seed
    tile_bytes = t * t * int(det.head.shape[-1]) * 4
    computed, launched, changed, bounds = [], [], [], []
    canvas_bytes, changed_out = [], []
    max_diff = 0.0
    static_counts = changed_counts = None
    static_canvas_bytes = -1
    for s in range(steps):
        prev = frames
        frames = perturb(frames) if s % 2 == 0 else frames  # odd = static
        outs, counts, st = fleet_reuse_step(det, as_jnp(frames), grids,
                                            cache)
        assert not st.cold
        full = det.superlaunch_forward(as_jnp(frames), grids)
        for gid in grids:
            for a, b in zip(outs[gid], full[gid]):
                max_diff = max(max_diff, float(jnp.abs(a - b).max()))
        computed.append(st.computed)
        launched.append(st.launched)
        changed.append(st.raw_changed)
        canvas_bytes.append(st.canvas_bytes)
        changed_out.append(st.changed_out)
        flat_prev = [f for fs in prev.values() for f in fs]
        flat_cur = [f for fs in frames.values() for f in fs]
        bounds.append(_dilation_bound(flat_grids, flat_prev, flat_cur, t,
                                      n_layers))
        if st.computed == 0:
            static_counts = dict(counts)
            static_canvas_bytes = st.canvas_bytes
        else:
            changed_counts = dict(counts)
    # canvas-write proportionality: bytes written are EXACTLY the
    # changed-out tile count times the per-tile head footprint — the
    # scatter touches nothing else (all-static steps write 0 bytes)
    canvas_prop_ok = all(cb == co * tile_bytes
                         for cb, co in zip(canvas_bytes, changed_out))
    # honest accounting: the reduction is measured on LAUNCHED tiles
    # (compact set + power-of-two bucket padding), not the semantic
    # compact set alone
    compute_frac = sum(launched) / (steps * n_active)
    changed_frac = sum(changed) / (steps * n_active)
    reduction = 1.0 - compute_frac

    # --- panel 4: wall clock, mostly-static steady state ----------------
    # the timed unit is the TRACE's repeating cell: one sparse-motion
    # step (alternating A/B so the gate always sees the dilated changed
    # set) followed by one all-static step — vs two full-recompute
    # super-launch steps on the same frames.  Both sides issue the same
    # number of launch chains; the reuse side convolves only the changed
    # sets and composites the static step from the cache.
    frames_a = mk_frames()
    frames_b = perturb(frames_a)
    fa, fb = as_jnp(frames_a), as_jnp(frames_b)
    wall_cache = PackedActivationCache()
    fleet_reuse_step(det, fa, grids, wall_cache)            # seed + warm
    fleet_reuse_step(det, fb, grids, wall_cache)
    fleet_reuse_step(det, fb, grids, wall_cache)            # static warm
    fleet_inference_step(det, fa, grids)                    # warm chain
    # the cache now holds fb, so start the flip at fb: the first timed
    # pair flips to fa — a real changed step, not an all-static freebie
    # the min-over-reps would otherwise latch onto
    flip = {"cur": fb}

    def reuse_pair():
        flip["cur"] = fb if flip["cur"] is fa else fa
        r1 = fleet_reuse_step(det, flip["cur"], grids, wall_cache)[0]
        r2 = fleet_reuse_step(det, flip["cur"], grids, wall_cache)[0]
        return (r1, r2)

    def full_pair():
        r1 = fleet_inference_step(det, flip["cur"], grids)[0]
        r2 = fleet_inference_step(det, flip["cur"], grids)[0]
        return (r1, r2)

    reuse_wall, full_wall = _time_min_interleaved(
        [reuse_pair, full_pair], max(reps, 3))

    # all-static step wall: the cache already holds flip["cur"], so each
    # timed call is the gate-only zero-copy step (no conv, no scatter,
    # 0 canvas bytes) — the headline the sentinel's named absolute rule
    # watches for a regression re-enabling full-canvas writes
    def static_step():
        return fleet_reuse_step(det, flip["cur"], grids, wall_cache)[0]

    fleet_reuse_step(det, flip["cur"], grids, wall_cache)   # settle static
    static_wall = _time_min_interleaved([static_step], max(reps, 3))[0]

    # --- panel 5: per-tile-class gate-threshold schedule ----------------
    # every other camera shed; its BODY tiles get a high byte threshold,
    # its HALO (mask-boundary) tiles half that — boundary content stays
    # fresher under the same shedding.  Tiny sub-threshold drift must
    # stop relaunching shed body tiles while the served (stale) heads
    # hold the accuracy floor vs exact recompute.
    flat_cams = K * cams
    quality = np.ones(flat_cams)
    quality[::2] = 0.5
    thr2 = gate_threshold_schedule(quality, t, 3, gain=0.5,
                                   halo_gain=0.25)           # (C, 2)
    assert thr2.shape == (flat_cams, 2)
    tc_cache = PackedActivationCache()
    f0 = mk_frames()
    fleet_reuse_step(det, as_jnp(f0), grids, tc_cache, thr2)  # cold seed
    f1 = {g: [f + np.float32(2e-3) for f in fs] for g, fs in f0.items()}
    got_tc, _, tc_stats = fleet_reuse_step(det, as_jnp(f1), grids,
                                           tc_cache, thr2)
    exact = det.superlaunch_forward(f1, grids)
    close = tot = 0
    tc_worst = 0.0
    for gid in grids:
        for i in range(len(grids[gid])):
            d = np.abs(np.asarray(exact[gid][i])
                       - np.asarray(got_tc[gid][i]))
            close += int((d <= 1e-2).sum())
            tot += d.size
            tc_worst = max(tc_worst, float(d.max()) if d.size else 0.0)
    tileclass_accuracy_floor = close / max(tot, 1)
    tileclass_sheds_suppressed = tc_stats.raw_changed < tc_stats.total_tiles

    payload = {
        "groups": K, "cameras": K * cams, "grid_shape": list(gshape),
        "num_conv_layers": n_layers, "active_tiles": n_active,
        "trace_steps": steps,
        "computed_per_step": computed,
        "launched_per_step": launched,
        "changed_per_step": changed,
        "dilation_bound_per_step": bounds,
        "compute_tile_fraction": compute_frac,
        "changed_tile_fraction": changed_frac,
        "conv_tile_reduction": reduction,
        "reuse_vs_full_max_abs_diff": max_diff,
        "static_step_dispatches": static_counts,
        "changed_step_dispatches": changed_counts,
        "reuse_step_wall_s": reuse_wall,
        "full_step_wall_s": full_wall,
        "static_step_wall_s": static_wall,
        "canvas_bytes_per_step": canvas_bytes,
        "changed_out_per_step": changed_out,
        "tile_canvas_bytes": tile_bytes,
        "canvas_bytes_prop_ok": bool(canvas_prop_ok),
        "static_canvas_bytes": static_canvas_bytes,
        "canvas_bytes_total": cache.canvas_bytes_total,
        "tileclass_accuracy_floor": tileclass_accuracy_floor,
        "tileclass_max_abs_diff": tc_worst,
        "tileclass_sheds_suppressed": bool(tileclass_sheds_suppressed),
        "chosen_block": det.block,
        "vmem_budget_bytes": det.cfg.vmem_budget_bytes,
        "cache_invalidations": cache.invalidations,
        "headline": {
            "canvas_bytes_per_step": float(np.mean(canvas_bytes)),
            "static_step_wall_s": static_wall,
            "static_canvas_bytes": float(static_canvas_bytes),
        },
        "wall_s": time.time() - t00,
    }
    if verbose:
        rows = [
            ["convolved tiles / step",
             f"{np.mean(launched):.1f}", str(n_active)],
            ["compute fraction", f"{compute_frac:.3f}", "1.000"],
            ["trace-cell wall (s)", f"{reuse_wall:.4f}",
             f"{full_wall:.4f}"],
            ["all-static step wall (s)", f"{static_wall:.4f}", "-"],
            ["canvas bytes / step", f"{np.mean(canvas_bytes):.0f}",
             f"{n_active * tile_bytes}"],
        ]
        print(f"== delta-gated reuse: {K} groups x {cams} cams, "
              f"{gshape[0]}x{gshape[1]} grids, {n_active} active tiles, "
              f"block={det.block} ==")
        print(table(rows, ["metric", "reuse", "full recompute"]))
        print(f"conv-tile reduction: {reduction:.1%} "
              f"(changed {changed_frac:.1%} -> dilated "
              f"{compute_frac:.1%}); max |diff| {max_diff:.1e}")
        print(f"static step: {static_counts} "
              f"({static_canvas_bytes} canvas bytes); "
              f"changed step: {changed_counts}")
        print(f"canvas prop ok: {canvas_prop_ok}; tile-class accuracy "
              f"floor {tileclass_accuracy_floor:.4f} (sheds suppressed: "
              f"{tileclass_sheds_suppressed})")
    save_json("bench_reuse.json", payload)
    return payload


if __name__ == "__main__":
    run()
