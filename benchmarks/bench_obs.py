"""Observability benchmark: the telemetry layer must be (near) free.

Four panels:

  1. overhead — the SAME delta-gated fleet trace timed with
     observability disabled vs enabled (interleaved min-of-reps); the
     acceptance number is < 2% added wall on a fleet-reuse step, with
     ZERO added device dispatches (``ops.count_kernels`` Counters are
     equal bit-for-bit between the two runs).
  2. bit-compatibility — over the enabled run, the
     ``kernel_dispatches`` metric family equals the legacy
     ``ops.count_kernels`` region Counter exactly.
  3. async timeline — in an ``AsyncShardedPipeline`` run on mesh=(1,)
     the recorded spans show step t's ``host_plan`` span overlapping
     step t-1's ``device_compute`` span; disabled mode records zero
     spans for the identical workload.
  4. SLO panel — ``FleetSLOReport`` built from the measured step
     reports plus one simulated transport window (p50/p99 response
     delay, deadline hit rate, bytes shed, changed-tile fraction);
     ``run.py --obs`` merges it into ``BENCH_kernels.json``.

``quick=True`` is the CI smoke shape.
"""
from __future__ import annotations

import collections
import time

import jax
import numpy as np

from benchmarks.common import save_json, table
from repro import obs
from repro.fleet.runtime import fleet_reuse_step
from repro.fleet.sharded import AsyncShardedPipeline, ShardedSuperlaunch
from repro.kernels import ops
from repro.launch.mesh import make_fleet_mesh
from repro.net.batcher import simulate_transport
from repro.net.encoder import CameraCoefficients
from repro.obs import metrics as obs_metrics
from repro.obs import slo as obs_slo
from repro.obs import trace as obs_trace
from repro.serving.detector import (DetectorConfig, PackedActivationCache,
                                    RoIDetector)


def _det():
    return RoIDetector(DetectorConfig(tile=8, channels=(6, 8)),
                       jax.random.PRNGKey(0))


def _case(n_groups=2, cams=2, gshape=(5, 6), density=0.55, seed=0):
    rng = np.random.default_rng(seed)
    grids = {}
    for gid in range(n_groups):
        gs = [rng.random(gshape) < density for _ in range(cams)]
        for g in gs:
            g[1, 1] = True                      # never fully empty
        grids[gid] = gs
    return grids


def _trace(grids, tile, steps, seed=1, move_cams=2):
    """Mostly-static trace: per step, ``move_cams`` random cameras get
    one tile's worth of fresh pixels; every other camera is static."""
    rng = np.random.default_rng(seed)
    frames = {g: [np.asarray(rng.normal(size=(gr.shape[0] * tile,
                                              gr.shape[1] * tile, 3)),
                             np.float32) for gr in gs]
              for g, gs in grids.items()}
    out = [frames]
    for _ in range(steps - 1):
        nxt = {g: [f.copy() for f in fs] for g, fs in frames.items()}
        for _ in range(move_cams):
            gid = int(rng.integers(len(grids)))
            cam = int(rng.integers(len(grids[gid])))
            gr = grids[gid][cam]
            ys, xs = np.nonzero(gr)
            j = int(rng.integers(len(ys)))
            y0, x0 = ys[j] * tile, xs[j] * tile
            nxt[gid][cam][y0:y0 + tile, x0:x0 + tile] = \
                rng.normal(size=(tile, tile, 3)).astype(np.float32)
        out.append(nxt)
        frames = nxt
    return out


def _run_reuse(det, frames_list, grids, enabled):
    """One full reuse trace with obs on/off; returns (wall_s, dispatch
    Counter over all steps, per-step StepReports)."""
    obs.configure(enabled=enabled, reset=True)
    cache = PackedActivationCache()
    total = collections.Counter()
    reports = []
    t0 = time.perf_counter()
    with ops.count_kernels() as region:
        for i, frames in enumerate(frames_list):
            s0 = time.perf_counter()
            _, counts, stats = fleet_reuse_step(det, frames, grids, cache)
            total += counts
            reports.append(obs_slo.StepReport.from_reuse(
                i, time.perf_counter() - s0, counts, stats))
    wall = time.perf_counter() - t0
    bitmatch = (obs_metrics.kernel_counts() == dict(region)) if enabled \
        else None
    return wall, total, reports, bitmatch


def _transport_window():
    """One synthetic 4-camera transport window (coefficients passed
    directly, so no scene/offline fixture is needed)."""
    C = 4
    coef = CameraCoefficients(body=np.full(C, 3e4), halo=np.full(C, 4e3),
                              headers=np.full(C, 200.0),
                              has_mask=np.ones(C, bool))
    return simulate_transport([None] * C, None, None,
                              np.full(C, 2.5e5), None,
                              1.0, 10, 6, 8.0, 40.0, 120.0, 2e8,
                              coef=coef)


def _overlap_windows(events):
    """(host_plan, device_compute) step pairs whose spans overlap."""
    hosts = {e.step: (e.t0_ns, e.t0_ns + e.dur_ns)
             for e in events if e.name == "host_plan"}
    devs = {e.step: (e.t0_ns, e.t0_ns + e.dur_ns)
            for e in events if e.name == "device_compute"}
    pairs = []
    for s, (h0, h1) in hosts.items():
        d = devs.get(s - 1)
        if d and max(h0, d[0]) < min(h1, d[1]):
            pairs.append(s)
    return pairs, len(hosts), len(devs)


def run(verbose=True, quick=False):
    det = _det()
    grids = _case()
    steps = 6 if quick else 12
    reps = 7                      # min-of-reps; CI timing noise insurance
    frames_list = _trace(grids, det.cfg.tile, steps)
    # the overhead arms get their OWN longer trace: the per-step obs
    # cost is sub-microsecond python, so each timed arm must be long
    # enough (~hundreds of ms) that one scheduler preemption cannot
    # swing the per-arm minimum by whole percents — 6-step (~35 ms)
    # arms once recorded overhead_frac = -2.2% (enabled "faster")
    tax_steps = 30
    tax_frames = _trace(grids, det.cfg.tile, tax_steps)

    # warm every jit path once (cold + warm shapes) before timing
    _run_reuse(det, frames_list, grids, enabled=False)
    _run_reuse(det, tax_frames, grids, enabled=False)

    # -- panel 1+2: overhead / added dispatches / bit-compatibility ----
    walls_off, walls_on = [], []
    counts_off = counts_on = None
    bitmatch = False

    def _round(n):
        nonlocal counts_off, counts_on, bitmatch
        for rep in range(n):      # interleaved min-of-reps, alternating
            for enabled in ([False, True] if rep % 2 == 0
                            else [True, False]):
                w, counts, _, bm = _run_reuse(
                    det, tax_frames, grids, enabled)
                if enabled:
                    walls_on.append(w)
                    counts_on, bitmatch = counts, bm
                else:
                    walls_off.append(w)
                    counts_off = counts

    # min-of-reps overhead: single-rep deltas swing ±2% with scheduler
    # noise (history once recorded -2.2%: enabled measured FASTER) — the
    # per-arm minima are the stable estimator, and the recorded spread
    # shows how much noise the minima absorbed.  The min is monotone
    # non-increasing in rep count, and the TRUE obs cost is ~0.2% of a
    # 30-step arm (13.7 us/step, measured in isolation), so when a
    # busy machine inflates every rep of one arm we keep adding
    # interleaved rounds: noise washes out, a real >2% regression
    # cannot (its min never drops below the true cost).
    _round(reps)
    for _extra in range(3):
        if (min(walls_on) - min(walls_off)) / min(walls_off) < 0.02:
            break
        _round(4)
    wall_off, wall_on = min(walls_off), min(walls_on)
    reps = len(walls_on)
    # step reports for the SLO panel come from one enabled pass over
    # the (shorter) panel trace, so panel n_steps == steps
    _, _, reports, _ = _run_reuse(det, frames_list, grids, enabled=True)
    overhead = (wall_on - wall_off) / wall_off
    spread_off = (max(walls_off) - min(walls_off)) / wall_off
    spread_on = (max(walls_on) - min(walls_on)) / wall_on
    assert overhead < 0.02, \
        f"obs overhead must stay < 2% on min-of-{reps}-rep walls " \
        f"(got {overhead:+.2%}, rep spread off/on " \
        f"{spread_off:.1%}/{spread_on:.1%})"
    added = sum((counts_on - counts_off).values()) \
        + sum((counts_off - counts_on).values())

    # -- panel 3: async pipeline timeline + disabled-mode zero spans ---
    rt = ShardedSuperlaunch(det, grids, make_fleet_mesh(1))
    pipe = AsyncShardedPipeline(rt, rt.make_cache())
    with obs.enabled():
        obs.configure(reset=True)
        for frames in frames_list:
            pipe.submit(frames)
        pipe.drain()
        enabled_spans = obs_trace.span_count()
        overlapped, n_host, n_dev = _overlap_windows(obs_trace.events())

    obs.configure(enabled=False, reset=True)
    pipe2 = AsyncShardedPipeline(rt, rt.make_cache())
    for frames in frames_list[:2]:
        pipe2.submit(frames)
    pipe2.drain()
    disabled_spans = obs_trace.span_count()

    # -- panel 4: SLO report (steps + one transport window) ------------
    with obs.enabled():
        ts = _transport_window()
    cache = PackedActivationCache()
    for frames in frames_list:
        fleet_reuse_step(det, frames, grids, cache)
    panel = obs_slo.FleetSLOReport.build(
        steps=reports, transport=ts, accuracy_floor=1.0,
        accuracy_mean=1.0, cache=cache, n_windows=6).to_dict()
    obs.configure(enabled=False, reset=True)

    payload = {
        "steps": steps,
        "overhead_steps": tax_steps,
        "wall_disabled_s": wall_off,
        "wall_enabled_s": wall_on,
        # per-step wall is the cross-commit comparable: the total arm
        # wall scales with the arm length, which the de-flake changed
        "wall_enabled_per_step_s": wall_on / max(tax_steps, 1),
        "overhead_frac": overhead,
        "rep_count": reps,
        "spread_disabled_frac": spread_off,
        "spread_enabled_frac": spread_on,
        "added_dispatches": int(added),
        "kernel_counts_bitmatch": bool(bitmatch),
        "dispatches_per_trace": dict(counts_on),
        "enabled_span_count": int(enabled_spans),
        "disabled_span_count": int(disabled_spans),
        "host_plan_spans": int(n_host),
        "device_compute_spans": int(n_dev),
        "overlapped_steps": overlapped,
        "pipeline_overlap_fraction": float(pipe.overlap_fraction),
        "slo_panel": panel,
    }
    if verbose:
        print(table([
            ["fleet wall, obs off", f"{wall_off * 1e3:.1f} ms"],
            ["fleet wall, obs on", f"{wall_on * 1e3:.1f} ms"],
            ["overhead", f"{overhead:+.2%} (min of {reps} reps, "
             f"spread {spread_off:.1%}/{spread_on:.1%})"],
            ["added dispatches", added],
            ["kernel counts bit-match", bitmatch],
            ["spans (enabled run)", enabled_spans],
            ["spans (disabled run)", disabled_spans],
            ["host/device overlapped steps",
             f"{len(overlapped)}/{max(n_host - 1, 1)}"],
            ["pipeline overlap fraction",
             f"{pipe.overlap_fraction:.2f}"],
            ["p50 / p99 delay",
             f"{panel['p50_delay_s']:.3f} / {panel['p99_delay_s']:.3f} s"],
            ["deadline hit rate", f"{panel['deadline_hit_rate']:.2f}"],
            ["changed-tile fraction",
             f"{panel['changed_tile_fraction']:.3f}"],
        ], ["obs", "value"]))
    save_json("bench_obs.json", payload)
    return payload


if __name__ == "__main__":
    run()
