"""Benchmark driver: one benchmark per paper table/figure + roofline.

  PYTHONPATH=src python -m benchmarks.run [--only reid,ablations,...]
  PYTHONPATH=src python -m benchmarks.run --quick

``--quick`` is the CI smoke mode: it runs bench_kernels on reduced shapes,
asserts the structural invariants of the stay-packed hot path (FLOP ratio,
one-gather/one-scatter dispatch structure, exact block-skip attention),
and writes ``BENCH_kernels.json`` at the repo root so the perf trajectory
accumulates across commits.
"""
from __future__ import annotations

import argparse
import json
import os
import time

BENCHES = ["reid", "compression", "ablations", "sensitivity", "reducto",
           "kernels", "fleet", "net", "stack", "reuse", "shard", "obs",
           "slo", "chaos", "roofline"]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# headline wall-clock keys lifted from BENCH_kernels.json panels into
# each BENCH_history.jsonl record (panel, key)
_HEADLINE_WALLS = [
    ("reuse", "reuse_step_wall_s"), ("reuse", "full_step_wall_s"),
    ("reuse", "static_step_wall_s"),
    ("shard", "sharded_wall_2shard_s"), ("shard", "single_device_wall_s"),
    # per-step, not total: the 30-step de-flake arms made the total
    # wall incomparable with pre-de-flake history under the same name
    ("obs", "wall_enabled_per_step_s"), ("obs", "overhead_frac"),
]


def _git_sha() -> str:
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except OSError:
        return "unknown"


def append_history(mode: str) -> None:
    """One timestamped summary line per driver run appended to
    ``BENCH_history.jsonl``: git SHA, which panels BENCH_kernels.json
    holds, the headline walls, and — when an SLO frontier panel exists —
    its flat ``headline`` block as ``frontier`` (likewise the chaos
    panel's headline as ``chaos`` and the reuse panel's
    persistent-canvas headline as ``canvas``).  Records are stamped
    with ``HISTORY_SCHEMA_VERSION`` and validated before the append; a
    malformed record is REFUSED (the sentinel depends on this stream
    staying parseable)."""
    from benchmarks.common import (HISTORY_SCHEMA_VERSION,
                                   validate_history_record)

    bench_path = os.path.join(REPO_ROOT, "BENCH_kernels.json")
    panels = {}
    if os.path.exists(bench_path):
        try:
            with open(bench_path) as f:
                panels = json.load(f)
        except (OSError, ValueError):
            panels = {}
    walls = {}
    for panel, key in _HEADLINE_WALLS:
        src = panels.get(panel, panels if panel == "kernels" else {})
        if isinstance(src, dict) and key in src:
            walls[f"{panel}.{key}"] = float(src[key])
    record = {
        "schema": HISTORY_SCHEMA_VERSION,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "git_sha": _git_sha(),
        "mode": mode,
        "panels": sorted(k for k, v in panels.items()
                         if isinstance(v, dict)),
        "headline_walls": walls,
    }
    for panel, block in (("slo", "frontier"), ("chaos", "chaos"),
                         ("reuse", "canvas")):
        headline = panels.get(panel, {}).get("headline")
        if isinstance(headline, dict):
            record[block] = {k: float(v) for k, v in headline.items()
                             if isinstance(v, (int, float))
                             and not isinstance(v, bool)}
    problems = validate_history_record(record)
    if problems:
        raise ValueError("refusing to append malformed history record: "
                         + "; ".join(problems))
    path = os.path.join(REPO_ROOT, "BENCH_history.jsonl")
    with open(path, "a") as f:
        f.write(json.dumps(record, default=float) + "\n")
    print(f"history record ({record['git_sha']}) -> {path}")


def quick():
    from benchmarks import bench_kernels
    t0 = time.time()
    payload = bench_kernels.run(verbose=True, quick=True)

    # structural invariants of the stay-packed execution model
    density = payload["mask_density_540p"]
    assert abs(payload["flop_ratio"] - density) < 1e-9, \
        "RoI FLOP ratio must equal mask density"
    assert payload["flop_ratio"] < 0.7, \
        f"RoI mask should cut conv FLOPs (got ratio {payload['flop_ratio']})"
    n_layers = payload["num_conv_layers"]
    counts = payload["kernel_dispatches"]
    # amortization check derived from the OBSERVED dispatch structure: a
    # regression to per-layer scatter/gather shows up as extra round-trips
    round_trips = (counts.get("roi_conv_entry", 0)
                   + counts.get("sbnet_gather", 0)
                   + counts.get("sbnet_scatter", 0)) / 2
    observed = payload["io_round_trip_overhead"] * round_trips / n_layers
    assert observed <= 0.30 / n_layers + 1e-9, \
        f"gather/scatter tax must amortize to <= 0.30/N per layer " \
        f"(observed {round_trips} round-trips over {n_layers} layers)"
    # one-launch backbone: entry + layer-stack megakernel + scatter,
    # ≤3 dispatches regardless of layer count
    assert counts.get("roi_conv_entry", 0) == 1, counts
    assert counts.get("roi_conv_stack", 0) == 1, counts
    assert counts.get("sbnet_scatter", 0) == 1, counts
    assert counts.get("sbnet_gather", 0) == 0, counts
    assert sum(counts.values()) <= 3, counts
    assert payload["roi_conv_interior_err"] <= 1e-4, payload
    assert payload["attn_skip_err"] == 0.0, \
        "block-skip attention must be bitwise-equal on real rows"
    assert payload["attn_visited_block_frac"] <= \
        payload["attn_keep_frac"] ** 2 + 0.05, \
        "visited k-blocks should track the causal lower-tri fraction"

    out = os.path.join(REPO_ROOT, "BENCH_kernels.json")
    payload = _merge_bench_json(out, payload)
    with open(out, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    print(f"\nquick smoke OK in {time.time() - t0:.1f}s -> {out}")


def _merge_bench_json(path: str, update: dict) -> dict:
    """BENCH_kernels.json accumulates panels (--quick writes the kernel
    keys, --fleet the "fleet" key); merge so neither run clobbers the
    other's section."""
    merged = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            merged = {}
    merged.update(update)
    return merged


def fleet_quick():
    """CI smoke for the fleet subsystem: 2 groups x 5 cams (~10 s).

    Asserts the fleet structural invariants — one packed conv launch per
    group per step (not per camera), zero cross-group leakage, per-group
    accuracy no worse than the single-group baseline, and the drift
    adapter recovering >= 95% coverage with one warm re-solve — then
    writes throughput + drift-resolve counts into BENCH_kernels.json
    under the "fleet" key."""
    from benchmarks import bench_fleet
    t0 = time.time()
    payload = bench_fleet.run(verbose=True, quick=True)

    assert payload["cross_group_leakage"] == 0
    launches = payload["launches_per_step"]
    assert launches.get("roi_conv_entry", 0) == 1, launches
    assert launches.get("roi_conv_stack", 0) == 1, launches
    assert launches.get("sbnet_scatter_fleet", 0) == 1, launches
    assert sum(launches.values()) <= 3, launches
    for acc, base in zip(payload["per_group_accuracy"],
                         payload["per_group_baseline_accuracy"]):
        assert acc >= base, "fleet runtime must not lose accuracy"
    assert payload["drift_resolves"] == 1, payload["drift_resolves"]
    assert payload["drift_coverage_after"] >= 0.95, \
        payload["drift_coverage_after"]
    assert payload["fleet_server_hz"] > 0

    out = os.path.join(REPO_ROOT, "BENCH_kernels.json")
    merged = _merge_bench_json(out, {"fleet": payload})
    with open(out, "w") as f:
        json.dump(merged, f, indent=1, default=float)
    print(f"\nfleet smoke OK in {time.time() - t0:.1f}s -> {out}")


def net_quick():
    """CI smoke for the streaming runtime: analytic<->simulated
    equivalence at 1e-6, the paper-style >= 20% p50 delay reduction for
    CrossRoI masks under the default congestion trace, bit-exact
    tile_delta dispatches, and live rate-control/deadline accounting —
    then merges a "net" panel into BENCH_kernels.json."""
    from benchmarks import bench_net
    t0 = time.time()
    payload = bench_net.run(verbose=True, quick=True)

    assert payload["equiv_latency_rel_err"] < 1e-6, payload
    assert payload["equiv_bytes_rel_err"] < 1e-6, payload
    assert payload["p50_reduction"] >= 0.20, \
        f"RoI masks must cut p50 response delay >= 20% under the " \
        f"default congestion trace (got {payload['p50_reduction']:.1%})"
    assert payload["p99_reduction"] > 0.0, payload
    assert payload["tile_delta_bit_exact"], \
        "tile_delta kernel must match the numpy reference bit-exactly"
    assert payload["tile_delta_dispatches"] == 2, payload
    assert payload["rc_shed_mb"] > 0 and payload["rc_quality_min"] < 1.0
    assert payload["rc_p50_s"] < payload["full_p50_s"]
    assert payload["deadline_hits"] > 0 and payload["straggler_frac"] > 0

    out = os.path.join(REPO_ROOT, "BENCH_kernels.json")
    merged = _merge_bench_json(out, {"net": payload})
    with open(out, "w") as f:
        json.dump(merged, f, indent=1, default=float)
    print(f"\nnet smoke OK in {time.time() - t0:.1f}s -> {out}")


def stack_quick():
    """CI smoke for the one-launch fleet backbone: ≤3 dispatches per
    fleet step regardless of group/layer count, each group's heads
    bit-identical to that group alone, the megakernel's halo fetch
    structure (8 edge DMAs per tile, centers per block), and the
    straggler fold reclaiming launch chains — merges a "stack" panel
    into BENCH_kernels.json."""
    from benchmarks import bench_stack
    t0 = time.time()
    payload = bench_stack.run(verbose=True, quick=True)

    assert payload["superlaunch_dispatches"] <= 3, payload["launch_counts"]
    launches = payload["launch_counts"]
    assert launches.get("roi_conv_entry", 0) == 1, launches
    assert launches.get("roi_conv_stack", 0) == 1, launches
    assert launches.get("sbnet_scatter_fleet", 0) == 1, launches
    assert payload["superlaunch_vs_per_group_max_abs_diff"] == 0.0, \
        "super-launch must be bit-identical to each group alone"
    # fetch structure counted from the kernel source (bench_stack): 8
    # halo fetches per tile, centers coalesced per block
    assert payload["stack_halo_dmas_per_tile"] == 8
    assert payload["center_dmas_fused"] <= payload["halo_dmas_fused"] / 8
    assert payload["fold_reclaimed_launches"] >= 1
    assert payload["fold_folded_frames"] >= 1

    out = os.path.join(REPO_ROOT, "BENCH_kernels.json")
    merged = _merge_bench_json(out, {"stack": payload})
    with open(out, "w") as f:
        json.dump(merged, f, indent=1, default=float)
    print(f"\nstack smoke OK in {time.time() - t0:.1f}s -> {out}")


def reuse_quick():
    """CI smoke for temporal delta-gated inference: per-step convolved
    tiles bounded by the dilated changed set (checked against an
    independent grid-morphology oracle), ≥40% conv-tile reduction on the
    default mostly-static trace with BIT-identical outputs at threshold
    0, all-static steps dispatching the gate ALONE (zero conv/scatter
    launches, 0 canvas bytes), canvas bytes written exactly proportional
    to the changed-out tile count, the per-tile-class threshold
    schedule holding the accuracy floor, the conv chain keeping its
    ≤3-dispatch ceiling, the reuse step's wall clock at or below full
    recompute, and the VMEM-calibrated block recorded — merges a
    "reuse" panel into BENCH_kernels.json."""
    from benchmarks import bench_reuse
    t0 = time.time()
    payload = bench_reuse.run(verbose=True, quick=True)

    # compute-tile fraction ≤ changed fraction + dilation bound: per
    # step the compact set must never exceed the receptive-field
    # dilation of the changed set (oracle-computed), and the LAUNCHED
    # count (compact set + power-of-two bucket padding) stays within the
    # bucket factor of it
    for got, launched, bound in zip(payload["computed_per_step"],
                                    payload["launched_per_step"],
                                    payload["dilation_bound_per_step"]):
        assert got <= bound, \
            f"computed {got} tiles > dilation bound {bound}"
        assert launched <= max(2 * bound, 1), \
            f"launched {launched} tiles > 2x dilation bound {bound}"
    assert payload["compute_tile_fraction"] <= \
        payload["changed_tile_fraction"] + \
        2 * max(payload["dilation_bound_per_step"]) / max(
            payload["active_tiles"], 1)
    # the acceptance number: ≥40% fewer convolved tiles on the default
    # mostly-static trace, with bit-identical detector outputs
    assert payload["conv_tile_reduction"] >= 0.40, \
        f"reuse must cut convolved tiles >= 40% " \
        f"(got {payload['conv_tile_reduction']:.1%})"
    assert payload["reuse_vs_full_max_abs_diff"] == 0.0, \
        "threshold-0 reuse must be bit-identical to full recompute"
    # dispatch structure: all-static = the gate ALONE (zero-copy step —
    # the persistent canvas is served as-is); changed steps keep the
    # ≤3-dispatch conv ceiling next to the one shared gate dispatch
    assert payload["static_step_dispatches"] == {
        "tile_delta_gate": 1}, payload
    ch = payload["changed_step_dispatches"]
    assert ch["tile_delta_gate"] == 1 and ch["roi_conv_entry"] == 1
    assert ch["sbnet_scatter_changed"] == 1, ch
    assert sum(v for k, v in ch.items() if k != "tile_delta_gate") <= 3
    # persistent canvas: bytes written ∝ changed fraction (exactly
    # changed_out * tile_bytes per step), 0 bytes on all-static steps
    assert payload["canvas_bytes_prop_ok"], \
        "canvas bytes written must equal changed_out * tile_bytes"
    assert payload["static_canvas_bytes"] == 0, \
        f"all-static step wrote {payload['static_canvas_bytes']} canvas " \
        f"bytes (must be 0)"
    # per-tile-class threshold schedule: shed cameras stop relaunching
    # tiny deltas, yet ≥99% of head entries stay within 1e-2 of exact
    assert payload["tileclass_sheds_suppressed"], \
        "per-tile-class thresholds must suppress shed-camera relaunches"
    assert payload["tileclass_accuracy_floor"] >= 0.99, \
        f"per-tile-class schedule broke the accuracy floor " \
        f"(got {payload['tileclass_accuracy_floor']:.4f})"
    # 15% slack absorbs scheduler noise on shared CI runners (same
    # policy as the stack smoke) without hiding a real regression
    assert payload["reuse_step_wall_s"] <= \
        1.15 * payload["full_step_wall_s"], \
        f"reuse path must not be slower than full recompute " \
        f"({payload['reuse_step_wall_s']:.3f}s vs " \
        f"{payload['full_step_wall_s']:.3f}s)"
    assert payload["chosen_block"] >= 1
    assert payload["cache_invalidations"] == 0

    out = os.path.join(REPO_ROOT, "BENCH_kernels.json")
    merged = _merge_bench_json(out, {"reuse": payload})
    with open(out, "w") as f:
        json.dump(merged, f, indent=1, default=float)
    print(f"\nreuse smoke OK in {time.time() - t0:.1f}s -> {out}")


def shard_quick():
    """CI smoke for city-scale sharded serving: the mesh=(1,) sharded
    step bit-identical to the single-device super-launch with the
    per-shard 1-gate + ≤3-conv dispatch ceiling, the async pipeline
    overlapping host planning with device compute, the 2-shard
    simulated-mesh wall at or below the single-device wall, an LPT
    shard plan within the greedy balance bound, and the per-camera
    gate-threshold schedule holding the head-map accuracy floor —
    merges a "shard" panel (with the groups x mesh scaling curve) into
    BENCH_kernels.json."""
    from benchmarks import bench_shard
    t0 = time.time()
    payload = bench_shard.run(verbose=True, quick=True)

    # bit-exactness: the shard axis must be pure partitioning — no
    # numeric difference vs the single-device reuse path, ever
    assert payload["bit_exact"], \
        f"sharded step diverged from single-device " \
        f"(max |diff| {payload['sharded_vs_single_max_abs_diff']})"
    # per-shard dispatch ceiling (SPMD: one counted dispatch is the
    # per-shard launch): 1 gate + ≤3 conv dispatches every step
    assert payload["dispatch_ceiling_ok"], payload["per_step_dispatches"]
    for c in payload["per_step_dispatches"]:
        assert c.get("tile_delta_gate", 0) == 1, c
        assert sum(v for k, v in c.items() if k != "tile_delta_gate") <= 3
    # the async pipeline must actually hide host planning time
    assert payload["overlap_fraction"] > 0, payload["overlap_fraction"]
    assert payload["overlap_fraction_2shard"] > 0, payload
    # acceptance number: sharded wall ≤ single-device wall at 2 shards
    assert payload["sharded_wall_2shard_s"] <= \
        payload["single_device_wall_s"], \
        f"2-shard wall must not exceed single-device " \
        f"({payload['sharded_wall_2shard_s']:.3f}s vs " \
        f"{payload['single_device_wall_s']:.3f}s, " \
        f"speedup {payload['speedup_2shard']:.2f}x)"
    # LPT plan balance: max shard load within 2x of the mean on this
    # many-small-groups case (greedy bound is mean + max-group)
    assert payload["shard_plan_imbalance_2shard"] <= 2.0, payload
    # per-camera gate-threshold schedule: shed cameras stop relaunching
    # tiny deltas, yet ≥99% of head entries stay within 1e-2 of exact
    assert payload["threshold_sheds_suppressed"], \
        "scheduled thresholds must suppress shed-camera relaunches"
    assert payload["threshold_accuracy_floor"] >= 0.99, \
        f"gate-threshold schedule broke the accuracy floor " \
        f"(got {payload['threshold_accuracy_floor']:.4f})"

    out = os.path.join(REPO_ROOT, "BENCH_kernels.json")
    merged = _merge_bench_json(out, {"shard": payload})
    with open(out, "w") as f:
        json.dump(merged, f, indent=1, default=float)
    print(f"\nshard smoke OK in {time.time() - t0:.1f}s -> {out}")


def obs_quick():
    """CI smoke for the observability layer: < 2% wall overhead on the
    delta-gated fleet trace with ZERO added device dispatches, the
    ``kernel_dispatches`` metric family bit-matching the legacy
    ``ops.count_kernels`` Counter, async-pipeline spans whose
    host-plan spans overlap the prior step's device-compute span,
    disabled mode recording zero spans, and a well-formed SLO panel —
    merged into BENCH_kernels.json under "obs"."""
    from benchmarks import bench_obs
    t0 = time.time()
    payload = bench_obs.run(verbose=True, quick=True)

    # the telemetry layer must be (near) free: < 2% wall overhead and
    # not a single extra kernel launch with tracing+metrics enabled
    assert payload["overhead_frac"] < 0.02, \
        f"obs overhead must stay < 2% " \
        f"(got {payload['overhead_frac']:+.2%})"
    # the overhead number is a min over interleaved reps; the recorded
    # rep count + spread prove the noise treatment actually ran
    assert payload["rep_count"] >= 3, payload["rep_count"]
    assert payload["spread_disabled_frac"] >= 0.0 \
        and payload["spread_enabled_frac"] >= 0.0, payload
    assert payload["added_dispatches"] == 0, payload["dispatches_per_trace"]
    assert payload["kernel_counts_bitmatch"], \
        "kernel_dispatches metric family must bit-match ops.KERNEL_COUNTS"
    # disabled mode is the tier-1 default: literally nothing recorded
    assert payload["disabled_span_count"] == 0, payload
    assert payload["enabled_span_count"] > 0, payload
    # the async host/device overlap must be VISIBLE in the trace: every
    # steady-state step's host_plan overlaps the prior device_compute
    assert payload["host_plan_spans"] == payload["steps"]
    assert payload["device_compute_spans"] == payload["steps"]
    assert len(payload["overlapped_steps"]) >= payload["steps"] - 1, \
        f"host_plan/device_compute spans must overlap " \
        f"(got {payload['overlapped_steps']})"
    assert payload["pipeline_overlap_fraction"] > 0
    # SLO panel shape: response delay + deadline + bytes + compute keys
    panel = payload["slo_panel"]
    assert panel["p50_delay_s"] > 0 and \
        panel["p99_delay_s"] >= panel["p50_delay_s"]
    assert 0.0 <= panel["deadline_hit_rate"] <= 1.0
    assert panel["bytes_total"] > 0
    assert 0.0 < panel["changed_tile_fraction"] < 1.0
    assert panel["n_steps"] == payload["steps"]
    assert panel["cache"]["steps"] == payload["steps"]

    out = os.path.join(REPO_ROOT, "BENCH_kernels.json")
    merged = _merge_bench_json(out, {"obs": payload})
    with open(out, "w") as f:
        json.dump(merged, f, indent=1, default=float)
    print(f"\nobs smoke OK in {time.time() - t0:.1f}s -> {out}")


def slo_quick():
    """CI smoke for the SLO frontier harness: a small fixed sweep grid
    (scale x congestion x static fraction, plus the real-LTE-trace and
    serve-rate legs) with the frontier sanity properties asserted —
    p99 delay non-decreasing in scripted congestion severity at fixed
    scale/profile, accuracy floor >= 99%, the loadgen harness adding
    zero kernel dispatches and < 2% wall vs driving the runtime inline,
    constant-trace parity with the analytic formula < 1e-6, and CrossRoI
    masks beating full-frame p50 under the real uplink trace — merged
    into BENCH_kernels.json under "slo" (its flat ``headline`` block
    becomes the history record's ``frontier``)."""
    from benchmarks import bench_slo
    t0 = time.time()
    payload = bench_slo.run(verbose=True, quick=True)

    # >= 3 swept axes, every grid point a full FleetSLOReport
    axes = payload["axes"]
    assert len(axes["scale"]) >= 2 and len(axes["congestion"]) >= 3 \
        and len(axes["static_fraction"]) >= 2, axes
    for r in payload["grid"]:
        slo = r["slo"]
        assert slo["p99_delay_s"] >= slo["p50_delay_s"] > 0, r["point"]
        assert slo["n_steps"] > 0 and slo["bytes_total"] > 0, r["point"]
        assert 0.0 <= slo["deadline_hit_rate"] <= 1.0, r["point"]
    # frontier sanity: more congestion can't mean faster responses
    assert payload["monotonic_p99_ok"], \
        "p99 delay must be non-decreasing in congestion severity"
    assert payload["accuracy_floor_min"] >= 0.99, \
        f"frontier accuracy floor broke 99% " \
        f"(got {payload['accuracy_floor_min']:.4f})"
    # the harness itself must be free
    tax = payload["loadgen"]
    assert tax["added_dispatches"] == 0, tax
    assert tax["overhead_frac"] < 0.02, \
        f"loadgen harness overhead must stay < 2% " \
        f"(got {tax['overhead_frac']:+.2%} over {tax['rep_count']} reps)"
    # real-trace replay: analytic parity + the paper claim on real bw
    tr = payload["trace_replay"]
    assert tr["const_trace_parity_rel_err"] < 1e-6, tr
    assert tr["p50_reduction"] >= 0.20, \
        f"RoI masks must cut p50 delay >= 20% under the real LTE " \
        f"uplink trace (got {tr['p50_reduction']:.1%})"
    assert tr["p99_reduction"] > 0.0, tr
    assert all(s["served"] == s["n_requests"] for s in payload["serve"])

    out = os.path.join(REPO_ROOT, "BENCH_kernels.json")
    merged = _merge_bench_json(out, {"slo": payload})
    with open(out, "w") as f:
        json.dump(merged, f, indent=1, default=float)
    print(f"\nslo smoke OK in {time.time() - t0:.1f}s -> {out}")


def chaos_quick():
    """CI smoke for the fault-tolerance layer: fault-free chaos drives
    BIT-identical to production with ZERO added dispatches, a scripted
    frozen camera confirmed within the liveness window while a genuinely
    static camera is never flagged, camera blackout -> heartbeat
    detection -> ONE warm failover re-solve restoring >= 95% of
    pre-fault coverage (and a positive ``uncovered_fraction`` reported
    when no surviving camera can cover the hole), shard loss restored
    bit-identically on the next SPMD step, and zero-bandwidth uplink
    outages pricing FINITE transport percentiles — merged into
    BENCH_kernels.json under "chaos" (its flat ``headline`` block
    becomes the history record's ``chaos``)."""
    from benchmarks import bench_chaos
    t0 = time.time()
    payload = bench_chaos.run(verbose=True, quick=True)

    # the fault layer must be free in production: bit-identical outputs,
    # not one extra dispatch, on both the fleet and sharded paths
    bit = payload["bit_identity"]
    assert bit["fleet_bit_identical"] and bit["sharded_bit_identical"], bit
    assert bit["fleet_added_dispatches"] == 0, bit
    assert bit["sharded_added_dispatches"] == 0, bit
    # frozen-vs-static: the scripted freeze is confirmed within the
    # liveness window (from the step's OWN gate stats); the camera that
    # never moved is never declared dead
    fr = payload["freeze"]
    assert fr["frozen_cam_confirmed"], fr
    assert 0 <= fr["freeze_detect_latency_steps"] <= \
        fr["freeze_window"] + 1, fr
    assert not fr["static_cam_flagged"], \
        "a genuinely static camera must never be confirmed dead"
    # blackout -> heartbeat -> ONE warm re-solve -> coverage restored
    fo = payload["failover"]
    assert fo["mask_listener_calls"] == 1, \
        f"failover must fan out through the mask listeners exactly " \
        f"once (got {fo['mask_listener_calls']})"
    assert fo["failover_tiles_dropped"] > 0, fo
    assert fo["coverage_restored_ratio"] >= 0.95, \
        f"failover must restore >= 95% of pre-fault coverage " \
        f"(got {fo['coverage_restored_ratio']:.3f}x)"
    assert fo["mttr_steps"] <= fo["heartbeat_detect_latency_steps"] + 3, fo
    # degraded mode is explicit, never silent: any genuine hole
    # (sole-observer appearances) must surface as a reported positive
    # uncovered fraction, and killing all overlap certainly must
    assert fo["genuine_hole_frac"] <= 0.01 \
        or fo["failover_uncovered_fraction"] > 0, fo
    assert fo["uncoverable_reported_fraction"] > 0, fo
    assert fo["uncoverable_live_fraction"] > 0, fo
    # shard loss: exactly the owning groups cold-marked, next step
    # restores, outputs bit-identical to a never-faulted run
    sh = payload["shard_loss"]
    assert sh["restore_bit_identical"], sh
    assert sorted(sh["affected_groups"]) == sorted(sh["expected_groups"])
    assert 0 < len(sh["affected_groups"]) < sh["n_groups"], \
        "shard loss must cold-mark exactly the owning shard's groups"
    assert sh["shard_invalidations"] >= 1, sh
    # zero-bandwidth outages must price finite (backlog carries over)
    out_leg = payload["outage"]
    assert out_leg["fifo"]["finite"], out_leg
    assert out_leg["rate_controlled"]["finite"], out_leg
    assert out_leg["outage_slower_than_clear"], out_leg

    out = os.path.join(REPO_ROOT, "BENCH_kernels.json")
    merged = _merge_bench_json(out, {"chaos": payload})
    with open(out, "w") as f:
        json.dump(merged, f, indent=1, default=float)
    print(f"\nchaos smoke OK in {time.time() - t0:.1f}s -> {out}")


def sentinel_gate(window: int = 5) -> None:
    """CI gate over BENCH_history.jsonl: first the sentinel's self-test
    (a temp history with an injected 2x wall slowdown MUST be flagged
    while the clean and ±2%-noise copies pass), then the real analysis —
    exits non-zero with a delta table naming the metric on a confirmed
    regression."""
    import sys

    from repro.obs import sentinel

    path = os.path.join(REPO_ROOT, "BENCH_history.jsonl")
    self_res = sentinel.self_test(path, window=window)
    print(f"sentinel self-test OK: 2x slowdown flagged on "
          f"{self_res['flagged_metrics']}, clean + noise-band pass")
    report = sentinel.analyze_path(path, window=window)
    print(report.render())
    if report.has_regression:
        sys.exit(1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma list of: {','.join(BENCHES)}")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: bench_kernels invariants + "
                         "BENCH_kernels.json")
    ap.add_argument("--fleet", action="store_true",
                    help="CI smoke: fleet invariants (2 groups x 5 cams) "
                         "merged into BENCH_kernels.json")
    ap.add_argument("--net", action="store_true",
                    help="CI smoke: streaming-runtime invariants "
                         "(equivalence, congestion p50 reduction, "
                         "tile_delta exactness) merged into "
                         "BENCH_kernels.json")
    ap.add_argument("--stack", action="store_true",
                    help="CI smoke: one-launch backbone invariants "
                         "(≤3 dispatches per fleet step, megakernel "
                         "bit-exact + wall-clock vs per-layer chain, "
                         "rim-DMA structure, straggler fold) merged "
                         "into BENCH_kernels.json")
    ap.add_argument("--reuse", action="store_true",
                    help="CI smoke: temporal delta-gated inference "
                         "(convolved tiles ≤ dilated changed set, ≥40% "
                         "reduction on the mostly-static trace, bit-"
                         "exact at threshold 0, gate-only zero-copy "
                         "static steps, canvas bytes ∝ changed "
                         "fraction, ≤1.0x reference storage) merged "
                         "into BENCH_kernels.json")
    ap.add_argument("--shard", action="store_true",
                    help="CI smoke: sharded fleet serving (mesh=(1,) "
                         "bit-exact, per-shard dispatch ceiling, async "
                         "pipeline overlap > 0, 2-shard wall ≤ single-"
                         "device, threshold-schedule accuracy floor) "
                         "merged into BENCH_kernels.json")
    ap.add_argument("--obs", action="store_true",
                    help="CI smoke: observability layer (< 2% overhead, "
                         "zero added dispatches, kernel-counter bit-"
                         "match, overlapping async host/device trace "
                         "spans, disabled-mode zero spans, SLO panel) "
                         "merged into BENCH_kernels.json")
    ap.add_argument("--slo", action="store_true",
                    help="CI smoke: SLO frontier sweep (scale x "
                         "congestion x static fraction + real-LTE-trace "
                         "and serve-rate legs; p99 monotone in "
                         "severity, accuracy floor >= 99%%, zero-"
                         "dispatch < 2%% loadgen tax, const-trace "
                         "analytic parity) merged into "
                         "BENCH_kernels.json")
    ap.add_argument("--chaos", action="store_true",
                    help="CI smoke: fault-tolerance layer (fault-free "
                         "bit-identity with zero added dispatches, "
                         "freeze detection within the liveness window, "
                         "blackout failover restoring >= 95%% coverage "
                         "with one warm re-solve, explicit uncovered-"
                         "fraction reporting, shard-loss restore, "
                         "finite zero-bandwidth transport) merged into "
                         "BENCH_kernels.json")
    ap.add_argument("--sentinel", action="store_true",
                    help="CI gate: self-test the regression sentinel "
                         "(injected 2x slowdown must be flagged), then "
                         "compare the latest BENCH_history.jsonl SHA "
                         "against the median-of-window baseline; exits "
                         "non-zero on a confirmed regression")
    args = ap.parse_args()
    smokes = [("quick", args.quick, quick), ("fleet", args.fleet,
              fleet_quick), ("net", args.net, net_quick),
              ("stack", args.stack, stack_quick),
              ("reuse", args.reuse, reuse_quick),
              ("shard", args.shard, shard_quick),
              ("obs", args.obs, obs_quick),
              ("slo", args.slo, slo_quick),
              ("chaos", args.chaos, chaos_quick)]
    ran = [name for name, on, fn in smokes if on and (fn() or True)]
    if ran:
        append_history("+".join(ran))
        if args.sentinel:
            sentinel_gate()
        return
    if args.sentinel:
        sentinel_gate()       # gate-only invocation: no panel, no append
        return
    selected = args.only.split(",") if args.only else BENCHES

    import importlib
    t00 = time.time()
    for name in selected:
        mod = importlib.import_module(f"benchmarks.bench_{name}")
        print(f"\n{'=' * 72}\n== bench_{name}\n{'=' * 72}")
        t0 = time.time()
        mod.run()
        print(f"[bench_{name}: {time.time() - t0:.1f}s]")
    print(f"\nall benchmarks done in {time.time() - t00:.1f}s")
    append_history("full" if args.only is None else args.only)


if __name__ == "__main__":
    main()
