"""Chaos harness: fault injection, detection, failover, recovery bounds.

Five legs, each an acceptance criterion of the fault-tolerance layer:

1. **fault-free bit-identity** — with the schedule off, ``drive_chaos``
   / ``drive_chaos_sharded`` produce BIT-identical outputs and the
   IDENTICAL dispatch Counter as the plain loadgen drivers: the fault
   layer costs nothing in production (the ``obs`` discipline).
2. **freeze detection on the kernel path** — a scripted frozen camera
   in an otherwise always-moving fleet is confirmed dead by the
   ``LivenessMonitor`` (fed only by the step's OWN gate stats — zero
   added dispatches) within the configured window, while a genuinely
   static camera is NEVER flagged; degraded-window accuracy is measured
   against the exact forward on the TRUE frames.
3. **camera blackout -> failover on the paper scene** — transport
   heartbeat detects the blackout, ONE warm re-solve
   (``failover_resolve``) reassigns the dead camera's coverage to the
   surviving overlapping cameras (>= 95% of pre-fault coverage
   restored, mask listeners fired exactly once), and the
   coverage-dip depth/duration + MTTR in steps are measured.  A second
   scenario kills every camera except one: the hole is REPORTED as a
   positive ``uncovered_fraction``, never silently zero.
4. **shard loss** — losing a shard's activation state mid-run
   cold-marks exactly its groups; the next SPMD step restores them
   (detect -> restore) with outputs bit-identical to a never-faulted
   run and the per-shard dispatch ceiling intact.
5. **zero-bandwidth uplink outage** — a congestion episode at factor
   0.0 yields FINITE transport p50/p99 (backlog carries across the
   outage and drains at the restored rate).

The flat ``headline`` block (mttr_steps, detect_latency_steps,
uncovered_frac_p99, ...) is lifted into BENCH_history.jsonl as the
``chaos`` record block, where ``obs.sentinel``'s absolute rules hold
the recovery bounds across commits.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List

import numpy as np

from benchmarks.common import offline_crossroi, paper_scene, save_json

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fleet_fixture():
    import jax

    from repro.serving.detector import DetectorConfig, RoIDetector

    return RoIDetector(DetectorConfig(tile=8, channels=(6, 8)),
                       jax.random.PRNGKey(0))


def _outputs_equal(a: List[Dict], b: List[Dict]) -> bool:
    if len(a) != len(b):
        return False
    for oa, ob in zip(a, b):
        if set(oa) != set(ob):
            return False
        for gid in oa:
            for ha, hb in zip(oa[gid], ob[gid]):
                if not np.array_equal(np.asarray(ha), np.asarray(hb)):
                    return False
    return True


# ---------------------------------------------------------------------------
# leg 1: fault-free bit-identity (fleet + sharded), zero added dispatches
# ---------------------------------------------------------------------------

def _leg_bit_identity(det, verbose: bool) -> Dict:
    from repro.fleet.faults import FaultSchedule, drive_chaos, \
        drive_chaos_sharded
    from repro.fleet.sharded import ShardedSuperlaunch
    from repro.launch.mesh import make_fleet_mesh
    from repro.obs.loadgen import (LoadgenConfig, drive_fleet,
                                   drive_sharded, make_frame_trace,
                                   make_grids)
    from repro.serving.detector import PackedActivationCache

    cfg = LoadgenConfig(steps=5, grid_shape=(4, 4))
    grids = make_grids(cfg, 2, 2)
    frames = make_frame_trace(cfg, grids, static_fraction=0.5)

    _, plain_out, plain_counts = drive_fleet(
        det, frames, grids, PackedActivationCache(), keep_outputs=True)
    _, chaos_out, chaos_counts, _ = drive_chaos(
        det, frames, grids, PackedActivationCache(), schedule=None,
        keep_outputs=True)
    fleet_identical = _outputs_equal(plain_out, chaos_out)
    fleet_added = sum(chaos_counts.values()) - sum(plain_counts.values())
    assert dict(plain_counts) == dict(chaos_counts), \
        (dict(plain_counts), dict(chaos_counts))

    # disabled-but-constructed schedule must behave the same as None
    off_sched = FaultSchedule((), enabled=False)
    _, off_out, off_counts, _ = drive_chaos(
        det, frames, grids, PackedActivationCache(), schedule=off_sched,
        keep_outputs=True)
    assert _outputs_equal(plain_out, off_out)

    rt = ShardedSuperlaunch(det, grids, make_fleet_mesh(1))
    _, sp_out, sp_counts = drive_sharded(rt, frames, rt.make_cache(),
                                         keep_outputs=True)
    _, sc_out, sc_counts, _ = drive_chaos_sharded(
        rt, frames, rt.make_cache(), schedule=None, keep_outputs=True)
    sharded_identical = _outputs_equal(sp_out, sc_out)
    sharded_added = sum(sc_counts.values()) - sum(sp_counts.values())
    assert dict(sp_counts) == dict(sc_counts)

    if verbose:
        print(f"  fault-free: fleet bit-identical={fleet_identical} "
              f"(+{fleet_added} dispatches), sharded "
              f"bit-identical={sharded_identical} (+{sharded_added})")
    return {"fleet_bit_identical": fleet_identical,
            "fleet_added_dispatches": int(fleet_added),
            "sharded_bit_identical": sharded_identical,
            "sharded_added_dispatches": int(sharded_added)}


# ---------------------------------------------------------------------------
# leg 2: freeze detection from gate stats (frozen vs genuinely static)
# ---------------------------------------------------------------------------

def _leg_freeze_detection(det, verbose: bool) -> Dict:
    from repro.fleet.faults import (FaultEvent, FaultSchedule,
                                    LivenessConfig, LivenessMonitor,
                                    drive_chaos, flat_cam_index)
    from repro.obs.loadgen import (LoadgenConfig, accuracy_vs_exact,
                                   make_grids)
    from repro.serving.detector import PackedActivationCache

    cfg = LoadgenConfig(steps=12, grid_shape=(4, 4))
    grids = make_grids(cfg, 2, 2)
    flat = flat_cam_index(grids)
    tile = cfg.tile
    static_cam = (1, 1)        # genuinely static: NEVER moves
    frozen_cam = (0, 1)        # moves, then freezes mid-run
    fault_t0 = 6

    # every camera except static_cam refreshes one tile every step
    rng = np.random.default_rng(3)
    frames = {g: [np.asarray(rng.normal(size=(gr.shape[0] * tile,
                                              gr.shape[1] * tile, 3)),
                             np.float32) for gr in gs]
              for g, gs in grids.items()}
    frames_list = [frames]
    for _ in range(cfg.steps - 1):
        nxt = {g: [f.copy() for f in fs] for g, fs in frames.items()}
        for (g, c), _f in flat.items():
            if (g, c) == static_cam:
                continue
            ys, xs = np.nonzero(grids[g][c])
            j = int(rng.integers(len(ys)))
            nxt[g][c][ys[j] * tile:(ys[j] + 1) * tile,
                      xs[j] * tile:(xs[j] + 1) * tile] = \
                rng.normal(size=(tile, tile, 3)).astype(np.float32)
        frames_list.append(nxt)
        frames = nxt

    sched = FaultSchedule((FaultEvent("freeze", fault_t0, cfg.steps,
                                      gid=frozen_cam[0],
                                      cam=frozen_cam[1]),))
    lcfg = LivenessConfig(freeze_window=3, min_expected_rate=0.5)
    monitor = LivenessMonitor(len(flat), lcfg)
    cache = PackedActivationCache()
    _, outs, _, detected = drive_chaos(
        det, frames_list, grids, cache, schedule=sched, monitor=monitor,
        keep_outputs=True)

    frozen_flat = flat[frozen_cam]
    static_flat = flat[static_cam]
    latency = monitor.detect_latency_steps(frozen_flat, fault_t0)
    # degraded-window accuracy: faulted outputs vs exact on TRUE frames
    acc_floor, acc_mean = accuracy_vs_exact(
        det, frames_list[fault_t0:], grids, outs[fault_t0:])

    if verbose:
        print(f"  freeze: cam {frozen_cam} confirmed dead "
              f"{latency} step(s) after onset (window "
              f"{lcfg.freeze_window}); static cam flagged: "
              f"{static_flat in monitor.confirmed}; degraded-window "
              f"accuracy mean {acc_mean:.4f}")
    return {"frozen_cam_confirmed": frozen_flat in monitor.confirmed,
            "freeze_detect_latency_steps": int(latency),
            "freeze_window": lcfg.freeze_window,
            "static_cam_flagged": static_flat in monitor.confirmed,
            "degraded_accuracy_floor": float(acc_floor),
            "degraded_accuracy_mean": float(acc_mean)}


# ---------------------------------------------------------------------------
# leg 3: blackout -> heartbeat -> ONE warm failover re-solve (paper scene)
# ---------------------------------------------------------------------------

def _leg_failover(verbose: bool) -> Dict:
    from repro.fleet.drift import DriftAdapter, DriftConfig
    from repro.fleet.faults import degraded_coverage, failover_resolve
    from repro.net.batcher import HeartbeatConfig, HeartbeatMonitor

    scene = paper_scene()
    off = offline_crossroi()
    # drift disabled (confirm_frames huge): failover is the ONLY
    # mutation path, so "ONE warm re-solve" is exactly measurable
    adapter = DriftAdapter(scene, off,
                          DriftConfig(confirm_frames=10 ** 9))
    notifications = []
    adapter.add_mask_listener(lambda a: notifications.append(1))

    t_warm0, t_fault, t_end = 600, 660, 720
    cam_ids = [c.cam_id for c in scene.cameras]
    # kill the camera with the most EXCLUSIVE coverage — appearances no
    # other camera's mask covers.  CrossRoI removed exactly that
    # redundancy, so this is the worst case the failover must handle.
    exclusive = np.zeros(len(cam_ids), np.int64)
    for t in range(t_warm0, t_fault, 5):
        by_obj: Dict[int, List] = {}
        for d in scene.detections[t]:
            by_obj.setdefault(d.obj, []).append(d)
        for ds in by_obj.values():
            covering = {d.cam for d in ds if adapter._covered(d)}
            if len(covering) == 1:
                exclusive[covering.pop()] += 1
    if exclusive.any():
        dead_cam = int(exclusive.argmax())
    else:                           # fully redundant mask: fall back to
        owners = np.searchsorted(   # the biggest mask owner
            adapter.universe.offsets, np.asarray(sorted(adapter.mask)),
            side="right") - 1
        dead_cam = int(np.bincount(owners, minlength=len(cam_ids)).argmax())

    hb = HeartbeatMonitor(cam_ids, HeartbeatConfig(interval_s=1.0,
                                                   timeout_beats=3.0),
                          t0=float(t_warm0 - 1))
    cov_t: List[int] = []
    raw_cov, svc_cov, hole = [], [], []
    detected_at = None
    failover_ev = None
    pre_cov: List[float] = []
    for t in range(t_warm0, t_end):
        dets = scene.detections[t]
        dead = [dead_cam] if t >= t_fault else []
        covered, coverable, total = degraded_coverage(adapter, dets, dead)
        cov_t.append(t)
        # raw: over every object; service: over what surviving cameras
        # CAN cover (failover's responsibility); hole: what they can't
        raw_cov.append(covered / max(total, 1))
        svc_cov.append(covered / max(coverable, 1))
        hole.append((total - coverable) / max(total, 1))
        if t < t_fault:
            pre_cov.append(covered / max(total, 1))
        adapter.observe(t, dets)
        # transport heartbeat: every camera beats except the dead one
        for c in cam_ids:
            if c != dead_cam or t < t_fault:
                hb.beat(float(t), c)
        newly = hb.poll(float(t))
        if newly and detected_at is None:
            assert newly == [dead_cam], newly
            detected_at = t
            failover_ev = failover_resolve(adapter, [dead_cam], t)

    pre_mean = float(np.mean(pre_cov))
    cov_t_a = np.asarray(cov_t)
    raw_a, svc_a = np.asarray(raw_cov), np.asarray(svc_cov)
    fault_sel = cov_t_a >= t_fault
    dip_depth = float(pre_mean - raw_a[fault_sel].min())
    # recovery is judged on SERVICE coverage (reassignable appearances);
    # the genuine hole is reported separately, never folded in
    below = fault_sel & (svc_a < 0.95 * pre_mean)
    dip_duration = int(np.count_nonzero(below))
    recovered = np.nonzero(below)[0]
    mttr = int(cov_t_a[recovered.max()] - t_fault + 1) if recovered.size \
        else int(detected_at - t_fault + 1)
    post_sel = cov_t_a > (detected_at if detected_at is not None
                          else t_fault)
    restored_ratio = float(np.mean(svc_a[post_sel]) / pre_mean)
    detect_latency = int(detected_at - t_fault)
    # post-failover service-coverage deficit (the headline the sentinel
    # holds: growth past its band means failover stopped restoring)
    uncovered_post = 1.0 - svc_a[post_sel]
    genuine_hole_frac = float(np.mean(np.asarray(hole)[post_sel]))

    # --- uncoverable scenario: kill everything but the thinnest camera
    adapter2 = DriftAdapter(scene, off,
                            DriftConfig(confirm_frames=10 ** 9))
    for t in range(t_warm0, t_fault):
        adapter2.observe(t, scene.detections[t])
    occ = adapter2.occupancy_by_camera()
    keep = min(occ, key=occ.get)
    dead_all = [c for c in cam_ids if c != keep]
    ev2 = failover_resolve(adapter2, dead_all, t_fault)
    unc_cov, _, unc_tot = degraded_coverage(
        adapter2, scene.detections[t_fault], dead_all)
    lone_uncovered = 1.0 - unc_cov / max(unc_tot, 1)

    if verbose:
        print(f"  blackout cam {dead_cam}: heartbeat detected after "
              f"{detect_latency} step(s); failover re-solve dropped "
              f"{failover_ev.tiles_dropped} dead tiles, added "
              f"{failover_ev.tiles_added} surviving tiles in "
              f"{failover_ev.wall_s * 1e3:.1f} ms")
        print(f"  coverage: pre {pre_mean:.4f}, dip depth "
              f"{dip_depth:.4f} for {dip_duration} step(s), service "
              f"coverage restored {restored_ratio:.3f}x pre, MTTR "
              f"{mttr} step(s); genuine hole (sole-observer objects) "
              f"{genuine_hole_frac:.3f} reported as "
              f"uncovered_fraction {failover_ev.uncovered_fraction:.3f}")
        print(f"  uncoverable scenario (only cam {keep} alive): "
              f"re-solve reports uncovered_fraction "
              f"{ev2.uncovered_fraction:.3f}, live hole "
              f"{lone_uncovered:.3f}")
    return {"dead_cam": dead_cam,
            "heartbeat_detect_latency_steps": detect_latency,
            "mask_listener_calls": len(notifications),
            "failover_tiles_dropped": failover_ev.tiles_dropped,
            "failover_tiles_added": failover_ev.tiles_added,
            "failover_wall_s": failover_ev.wall_s,
            "failover_uncovered_fraction": failover_ev.uncovered_fraction,
            "pre_fault_coverage": pre_mean,
            "coverage_dip_depth": dip_depth,
            "coverage_dip_duration_steps": dip_duration,
            "mttr_steps": mttr,
            "coverage_restored_ratio": restored_ratio,
            "genuine_hole_frac": genuine_hole_frac,
            "uncovered_frac_p99_post": float(
                np.percentile(uncovered_post, 99)),
            "uncoverable_reported_fraction": ev2.uncovered_fraction,
            "uncoverable_live_fraction": float(lone_uncovered)}


# ---------------------------------------------------------------------------
# leg 4: shard loss -> cold-mark -> next-step restore (bit-identical)
# ---------------------------------------------------------------------------

def chaos_shard_child(n_shards: int = 2, steps: int = 6) -> None:
    """Subprocess entry (bench_shard's simulated-mesh idiom: the forced
    host device count must be set before jax initializes)."""
    from repro.fleet.faults import FaultEvent, FaultSchedule, \
        drive_chaos_sharded
    from repro.fleet.sharded import ShardedSuperlaunch
    from repro.launch.mesh import make_fleet_mesh
    from repro.obs.loadgen import (LoadgenConfig, drive_sharded,
                                   make_frame_trace, make_grids)

    det = _fleet_fixture()
    cfg = LoadgenConfig(steps=steps, grid_shape=(4, 4))
    grids = make_grids(cfg, 2 * n_shards, 2)
    frames = make_frame_trace(cfg, grids, static_fraction=0.5)
    rt = ShardedSuperlaunch(det, grids, make_fleet_mesh(n_shards))

    _, ref_out, _ = drive_sharded(rt, frames, rt.make_cache(),
                                  keep_outputs=True)
    lost_shard, lose_at = 0, steps // 2
    sched = FaultSchedule((FaultEvent("shard", lose_at, lose_at + 1,
                                      shard=lost_shard),))
    cache = rt.make_cache()
    _, out, _, lost = drive_chaos_sharded(rt, frames, cache,
                                          schedule=sched,
                                          keep_outputs=True)
    affected = lost.get(lose_at, [])
    expected_gids = rt.groups_on_shard(lost_shard)
    res = {"n_shards": n_shards, "n_groups": len(grids),
           "lost_shard": lost_shard, "lost_at_step": lose_at,
           "affected_groups": sorted(map(int, affected)),
           "expected_groups": sorted(map(int, expected_gids)),
           "restore_bit_identical": _outputs_equal(ref_out, out),
           "shard_invalidations": int(np.asarray(
               cache.shard_invalidations).sum()),
           "shard_mttr_steps": 1}
    print("RESULT " + json.dumps(res))


def _leg_shard_loss(verbose: bool) -> Dict:
    n_shards = 2
    env = dict(os.environ)
    # a CPU simulation by design: fake host devices, never the chip the
    # parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n_shards}"
    env["PYTHONPATH"] = f"{REPO}:{os.path.join(REPO, 'src')}"
    code = (f"from benchmarks.bench_chaos import chaos_shard_child; "
            f"chaos_shard_child({n_shards}, 6)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560, env=env, cwd=REPO)
    if r.returncode != 0:
        raise RuntimeError(f"chaos shard child (S={n_shards}) failed:\n"
                           f"{r.stdout}\n{r.stderr[-3000:]}")
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    if verbose:
        print(f"  shard {res['lost_shard']}/{res['n_shards']} lost at "
              f"step {res['lost_at_step']}: groups "
              f"{res['affected_groups']} (of {res['n_groups']}) "
              f"cold-marked, restored next step (bit-identical to "
              f"fault-free: {res['restore_bit_identical']}; "
              f"{res['shard_invalidations']} shard invalidation(s))")
    return res


# ---------------------------------------------------------------------------
# leg 5: zero-bandwidth outage -> finite transport latencies
# ---------------------------------------------------------------------------

def _leg_outage_transport(verbose: bool) -> Dict:
    from repro.obs.loadgen import LoadgenConfig, transport_window

    cfg = LoadgenConfig()
    out = {}
    for rc_on, tag in ((False, "fifo"), (True, "rate_controlled")):
        cfg_l = LoadgenConfig(rate_control=rc_on)
        ts = transport_window(cfg_l, 6, "episode:0.0", 0.9)
        finite = bool(np.isfinite(ts.latency_s).all()
                      and np.isfinite(ts.p50_s)
                      and np.isfinite(ts.p99_s))
        out[tag] = {"finite": finite, "p50_s": float(ts.p50_s),
                    "p99_s": float(ts.p99_s),
                    "frames": int(ts.latency_s.size)}
        if verbose:
            print(f"  outage ({tag}): finite={finite} "
                  f"p50={ts.p50_s:.3f}s p99={ts.p99_s:.3f}s")
    baseline = transport_window(cfg, 6, "none", 0.9)
    out["outage_slower_than_clear"] = \
        out["fifo"]["p99_s"] > float(baseline.p99_s)
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run(verbose: bool = False, quick: bool = False) -> Dict:
    t0 = time.time()
    det = _fleet_fixture()

    if verbose:
        print("chaos leg 1: fault-free bit-identity")
    bit = _leg_bit_identity(det, verbose)
    if verbose:
        print("chaos leg 2: freeze detection (frozen vs static)")
    freeze = _leg_freeze_detection(det, verbose)
    if verbose:
        print("chaos leg 3: blackout -> failover (paper scene)")
    failover = _leg_failover(verbose)
    if verbose:
        print("chaos leg 4: shard loss -> restore (2-shard mesh)")
    shard = _leg_shard_loss(verbose)
    if verbose:
        print("chaos leg 5: zero-bandwidth outage transport")
    outage = _leg_outage_transport(verbose)

    payload = {
        "bit_identity": bit,
        "freeze": freeze,
        "failover": failover,
        "shard_loss": shard,
        "outage": outage,
        # flat headline: lifted into BENCH_history.jsonl as the "chaos"
        # block; obs.sentinel holds the recovery bounds absolutely
        "headline": {
            "mttr_steps": float(failover["mttr_steps"]),
            "detect_latency_steps": float(
                failover["heartbeat_detect_latency_steps"]),
            "freeze_detect_latency_steps": float(
                freeze["freeze_detect_latency_steps"]),
            "uncovered_frac_p99": float(
                failover["uncovered_frac_p99_post"]),
            "coverage_restored_ratio": float(
                failover["coverage_restored_ratio"]),
            "degraded_accuracy_floor": float(
                freeze["degraded_accuracy_floor"]),
        },
        "wall_s": time.time() - t0,
    }
    save_json("bench_chaos.json", payload)
    if verbose:
        print(f"chaos harness done in {payload['wall_s']:.1f}s")
    return payload


if __name__ == "__main__":
    run(verbose=True)
