"""Sharded fleet serving benchmark: the shard_map fleet-of-fleets with
the async host/device dispatch pipeline vs the single-device
super-launch.

Four panels:

  1. scaling curve — groups x simulated mesh size (subprocesses force
     ``--xla_force_host_platform_device_count``): per-step fleet wall,
     p99 submit-to-collect step latency, and measured host/device
     overlap fraction of the async pipeline at every mesh size; the
     acceptance number is sharded wall <= single-device wall at >= 2
     shards.
  2. correctness — the mesh=(1,) sharded step is bit-identical to
     ``superlaunch_forward_reuse`` over a ragged mostly-static trace,
     and ``sharded_fleet_step`` asserts the per-shard 1-gate +
     <=3-conv dispatch ceiling every step (SPMD: one counted dispatch
     IS the per-shard launch).
  3. shard plan — LPT balance by active-tile count (imbalance =
     max/mean shard load).
  4. per-camera gate-threshold schedule — the rate controller's
     ``gate_threshold_schedule`` raises thresholds on shed cameras
     only; the head-map accuracy floor vs exact recompute is measured
     (and asserted by ``run.py --shard``).

``quick=True`` is the CI smoke shape.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks.common import save_json, table
from repro.fleet.runtime import sharded_fleet_step
from repro.fleet.sharded import AsyncShardedPipeline, ShardedSuperlaunch
from repro.launch.mesh import make_fleet_mesh
from repro.net.encoder import gate_threshold_schedule
from repro.serving.detector import (DetectorConfig, PackedActivationCache,
                                    RoIDetector)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _det():
    return RoIDetector(DetectorConfig(tile=8, channels=(6, 8)),
                       jax.random.PRNGKey(0))


def _case(n_groups: int, cams: int = 2, gshape=(6, 7), density=0.55,
          seed: int = 0):
    rng = np.random.default_rng(seed)
    grids = {}
    for gid in range(n_groups):
        gs = [rng.random(gshape) < density for _ in range(cams)]
        for g in gs:
            g[1, 1] = True                      # never fully empty
        grids[gid] = gs
    return grids


def _trace(grids, tile: int, steps: int, seed: int = 1, move_cams=3):
    """Mostly-static trace: per step, ``move_cams`` random cameras get
    one tile's worth of fresh pixels; every other camera is
    bit-static."""
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
            gy, gx = grids[gid][cam].shape
            ty, tx = int(rng.integers(gy)), int(rng.integers(gx))
            nxt[gid][cam][ty * tile:(ty + 1) * tile,
                          tx * tile:(tx + 1) * tile, :] += \
                rng.normal(size=(tile, tile, 3)).astype(np.float32) * 5
        frames = nxt
        out.append(frames)
    return out


def child_main(n_shards: int, n_groups: int, steps: int,
               reps: int = 2) -> None:
    """Subprocess body: pipelined sharded serving at a forced device
    count; prints one RESULT json line.

    Two regimes are timed for each path, in one fresh process so both
    start from cold JIT caches:

    * ``*_wall_s`` — FROM-COLD serving wall: the first pass over the
      trace, including cold-shard seeding and every k_max-bucket
      compile.  This is the acceptance regime: compile/dispatch cost of
      the interpret-mode super-launch grows superlinearly with
      per-launch grid size, so halving the per-shard grid at mesh=2
      beats the single-device program even on one host core (on real
      multi-device hardware the steady state parallelizes too).
    * ``*_warm_wall_s`` — min-over-reps replay with every bucket
      compiled, reported for transparency: on a single host core the
      simulated mesh cannot actually parallelize warm execution, so
      the sharded warm wall carries the shard_map/padding overhead.

    The single-device ``superlaunch_forward_reuse`` baseline runs FIRST
    (any process warm-up favors the baseline, which is the conservative
    direction for the sharded-wall acceptance check)."""
    det = _det()
    grids = _case(n_groups)
    trace = _trace(grids, det.cfg.tile, steps)

    base_cache = PackedActivationCache()

    def single_pass():
        for f in trace:
            outs, _ = det.superlaunch_forward_reuse(
                f, grids, base_cache, 0.0)
            for fs in outs.values():
                for h in fs:
                    np.asarray(h)

    t0 = time.perf_counter()
    single_pass()
    single_cold = (time.perf_counter() - t0) / steps
    single_warm = []
    for _ in range(reps):
        t0 = time.perf_counter()
        single_pass()
        single_warm.append((time.perf_counter() - t0) / steps)

    mesh = make_fleet_mesh(n_shards)
    rt = ShardedSuperlaunch(det, grids, mesh)
    pipe = AsyncShardedPipeline(rt, rt.make_cache())

    def sharded_pass():
        for f in trace:
            pipe.submit(f)
            while pipe._ready:                    # steady-state consumer
                pipe.collect()
        pipe.drain()

    t0 = time.perf_counter()
    sharded_pass()
    sharded_cold = (time.perf_counter() - t0) / steps
    # serving-latency metrics come from the warm replays only (the cold
    # pass is compile-dominated); each rep's first step re-converges the
    # cache since trace[0] differs from trace[-1]
    pipe.latencies.clear()
    pipe.host_s = pipe.overlapped_host_s = pipe.blocked_s = 0.0
    sharded_warm = []
    for _ in range(reps):
        t0 = time.perf_counter()
        sharded_pass()
        sharded_warm.append((time.perf_counter() - t0) / steps)

    res = {"mesh": n_shards, "groups": n_groups,
           "fleet_step_wall_s": sharded_cold,
           "fleet_step_warm_wall_s": min(sharded_warm),
           "single_device_wall_s": single_cold,
           "single_device_warm_wall_s": min(single_warm),
           "p99_step_latency_s": pipe.p99_latency_s,
           "overlap_fraction": pipe.overlap_fraction,
           "imbalance": rt.plan.imbalance,
           "total_tiles": rt.n_total}
    print("RESULT " + json.dumps(res))


def _run_child(n_shards: int, n_groups: int, steps: int,
               timeout: int = 560) -> dict:
    env = dict(os.environ)
    # a CPU simulation by design: fake host devices, never the chip the
    # parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n_shards}"
    env["PYTHONPATH"] = f"{REPO}:{os.path.join(REPO, 'src')}"
    code = (f"from benchmarks.bench_shard import child_main; "
            f"child_main({n_shards}, {n_groups}, {steps})")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=REPO)
    if r.returncode != 0:
        raise RuntimeError(f"shard child (S={n_shards}) failed:\n"
                           f"{r.stdout}\n{r.stderr[-3000:]}")
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def run(verbose: bool = True, quick: bool = False):
    t00 = time.time()
    det = _det()
    tile = det.cfg.tile
    n_groups = 4
    meshes = [1, 2] if quick else [1, 2, 4]
    group_sweep = [n_groups] if quick else [n_groups, 2 * n_groups]
    steps = 4 if quick else 6

    # --- panel 2: bit-exactness + dispatch ceiling (in-process, S=1) ---
    grids = _case(n_groups)
    trace = _trace(grids, tile, 2 + steps)
    rt = ShardedSuperlaunch(det, grids, make_fleet_mesh(1))
    cache = rt.make_cache()
    pcache = PackedActivationCache()
    max_diff = 0.0
    dispatches = []
    for f in trace:
        ref, _ = det.superlaunch_forward_reuse(f, grids, pcache, 0.0)
        got, counts, stats = sharded_fleet_step(rt, f, cache, 0.0)
        dispatches.append(dict(counts))
        for gid in grids:
            for i in range(len(grids[gid])):
                d = np.abs(np.asarray(ref[gid][i]) - got[gid][i])
                max_diff = max(max_diff, float(d.max()) if d.size else 0.0)
    bit_exact = max_diff == 0.0
    ceiling_ok = all(
        c.get("tile_delta_gate", 0) <= 1 and
        sum(v for k, v in c.items() if k != "tile_delta_gate") <= 3
        for c in dispatches)

    # --- panel 4: per-camera threshold schedule accuracy floor ---------
    # the rate controller sheds half the cameras; their gate thresholds
    # rise, tiny deltas stop relaunching, and the served (stale) heads
    # are compared against exact recompute
    flat_cams = sum(len(gs) for gs in grids.values())
    quality = np.ones(flat_cams)
    quality[::2] = 0.5                       # every other camera shed
    thr_sched = gate_threshold_schedule(quality, tile, 3, gain=0.5)
    thr = {}
    pos = 0
    for gid in sorted(grids):
        k = len(grids[gid])
        thr[gid] = thr_sched[pos:pos + k]
        pos += k
    rt2 = ShardedSuperlaunch(det, grids, make_fleet_mesh(1))
    cache2 = rt2.make_cache()
    f0 = trace[0]
    rt2.step_reuse(f0, cache2, thr)          # cold seed
    f1 = {g: [f + np.float32(2e-3) for f in fs] for g, fs in f0.items()}
    got, sstats = rt2.step_reuse(f1, cache2, thr)
    exact = det.superlaunch_forward(f1, grids)
    close = tot = 0
    worst = 0.0
    for gid in grids:
        for i in range(len(grids[gid])):
            d = np.abs(np.asarray(exact[gid][i]) - got[gid][i])
            close += int((d <= 1e-2).sum())
            tot += d.size
            worst = max(worst, float(d.max()) if d.size else 0.0)
    accuracy_floor = close / max(tot, 1)
    sheds_suppressed = sstats.raw_changed < sstats.total_tiles

    # --- panel 1: scaling curve over simulated mesh sizes --------------
    curve = []
    for g in group_sweep:
        for s in meshes:
            if quick and g != n_groups:
                continue
            res = _run_child(s, g, steps)
            curve.append(res)
            if verbose:
                print(f"  mesh={s} groups={g}: "
                      f"wall {res['fleet_step_wall_s'] * 1e3:.0f} ms  "
                      f"p99 {res['p99_step_latency_s'] * 1e3:.0f} ms  "
                      f"overlap {res['overlap_fraction']:.2f}")
    by_mesh = {c["mesh"]: c for c in curve if c["groups"] == n_groups}
    # compare the 2-shard wall against the baseline measured in the SAME
    # child process (baseline first), so load noise hits both alike
    single_wall = by_mesh[2]["single_device_wall_s"]
    speedup_2shard = single_wall / by_mesh[2]["fleet_step_wall_s"]

    payload = {
        "groups": n_groups,
        "mesh_sizes": meshes,
        "scaling_curve": curve,
        "single_device_wall_s": single_wall,
        "sharded_wall_2shard_s": by_mesh[2]["fleet_step_wall_s"],
        "speedup_2shard": speedup_2shard,
        "single_device_warm_wall_s": by_mesh[2]["single_device_warm_wall_s"],
        "sharded_warm_wall_2shard_s": by_mesh[2]["fleet_step_warm_wall_s"],
        "overlap_fraction": by_mesh[1]["overlap_fraction"],
        "overlap_fraction_2shard": by_mesh[2]["overlap_fraction"],
        "p99_step_latency_2shard_s": by_mesh[2]["p99_step_latency_s"],
        "bit_exact": bit_exact,
        "sharded_vs_single_max_abs_diff": max_diff,
        "dispatch_ceiling_ok": ceiling_ok,
        "per_step_dispatches": dispatches,
        "shard_plan_imbalance_2shard": by_mesh[2]["imbalance"],
        "threshold_accuracy_floor": accuracy_floor,
        "threshold_max_abs_diff": worst,
        "threshold_sheds_suppressed": bool(sheds_suppressed),
        "total_tiles": rt.n_total,
        "wall_s": time.time() - t00,
    }
    if verbose:
        rows = [["from-cold step wall (ms)",
                 f"{single_wall * 1e3:.0f}",
                 f"{by_mesh[2]['fleet_step_wall_s'] * 1e3:.0f}"],
                ["warm step wall (ms)",
                 f"{by_mesh[2]['single_device_warm_wall_s'] * 1e3:.0f}",
                 f"{by_mesh[2]['fleet_step_warm_wall_s'] * 1e3:.0f}"],
                ["p99 step latency (ms)",
                 f"{by_mesh[1]['p99_step_latency_s'] * 1e3:.0f}",
                 f"{by_mesh[2]['p99_step_latency_s'] * 1e3:.0f}"],
                ["host/device overlap",
                 f"{by_mesh[1]['overlap_fraction']:.2f}",
                 f"{by_mesh[2]['overlap_fraction']:.2f}"]]
        print(f"== sharded serving: {n_groups} groups, "
              f"{rt.n_total} active tiles, meshes {meshes} ==")
        print(table(rows, ["metric", "single/1-shard", "2-shard"]))
        print(f"2-shard speedup {speedup_2shard:.2f}x; bit-exact "
              f"{bit_exact}; ceiling ok {ceiling_ok}; threshold "
              f"accuracy floor {accuracy_floor:.4f}")
    save_json("bench_shard.json", payload)
    return payload


if __name__ == "__main__":
    run()
