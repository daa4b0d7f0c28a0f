"""Observability subsystem: default-off no-ops, the fleet step's spans
(parent and step id in memory, nesting on the profiler's host plane,
overlapping async host/device spans), the typed metrics registry,
canonical kernel-counter-name enforcement, SLO panels, and the transport
empty-distribution guards."""
import functools
import glob
import json
import os
import re

import numpy as np
import pytest

import jax

from repro import obs
from repro.fleet import fleet_reuse_step
from repro.fleet.runtime import sharded_fleet_step
from repro.fleet.sharded import AsyncShardedPipeline, ShardedSuperlaunch
from repro.kernels import ops
from repro.launch.mesh import make_fleet_mesh
from repro.net.batcher import (TransportStats, empty_transport,
                               merge_transport, simulate_transport)
from repro.obs import metrics, slo, trace
from repro.serving.detector import (DetectorConfig, PackedActivationCache,
                                    RoIDetector)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test leaves observability off and empty (tier-1 default)."""
    obs.configure(enabled=False, reset=True)
    yield
    obs.configure(enabled=False, reset=True)


@pytest.fixture(scope="module")
def small_det():
    return RoIDetector(DetectorConfig(tile=8, channels=(4, 6)),
                       jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# default-off: zero spans, zero metric values, zero device dispatches
# ---------------------------------------------------------------------------

def test_disabled_by_default_records_nothing():
    assert not obs.is_enabled()
    n0 = trace.span_count()
    with trace.span("x", a=1):
        with trace.span("y"):
            pass
    trace.begin("dev").end()
    assert trace.span_count() == n0
    c = metrics.counter("t_disabled_counter")
    c.inc(5)
    g = metrics.gauge("t_disabled_gauge")
    g.set(3.0)
    h = metrics.histogram("t_disabled_hist")
    h.observe(1.0)
    assert c.total() == 0 and g.value() == 0.0 and h.count() == 0


def test_enabled_context_is_scoped():
    with obs.enabled():
        assert obs.is_enabled()
        with trace.span("scoped"):
            pass
    assert not obs.is_enabled()
    assert any(e[0] == "scoped" for e in trace.events())


# ---------------------------------------------------------------------------
# typed registry semantics
# ---------------------------------------------------------------------------

def test_registry_type_and_label_safety():
    c = metrics.counter("t_typed", labels=("camera", "group"))
    with pytest.raises(ValueError):          # same name, different type
        metrics.gauge("t_typed", labels=("camera", "group"))
    with pytest.raises(ValueError):          # same name, different labels
        metrics.counter("t_typed", labels=("camera",))
    assert metrics.counter("t_typed", labels=("camera", "group")) is c
    with obs.enabled():
        c.inc(2, camera="c0", group="g1")
        with pytest.raises(ValueError):      # undeclared label set
            c.inc(1, camera="c0")
    assert c.value(camera="c0", group="g1") == 2


def test_snapshot_shape_and_reset():
    with obs.enabled():
        metrics.counter("t_snap_c", labels=("k",)).inc(3, k="a")
        metrics.histogram("t_snap_h").observe(1.0)
        metrics.histogram("t_snap_h").observe(3.0)
    snap = metrics.REGISTRY.snapshot()
    assert snap["t_snap_c"]["type"] == "counter"
    assert snap["t_snap_c"]["values"] == [
        {"labels": {"k": "a"}, "value": 3}]
    hv = snap["t_snap_h"]["values"][0]["value"]
    assert hv["count"] == 2 and hv["sum"] == 4.0 and hv["p50"] == 2.0
    json.dumps(snap)                         # serializable as-is
    metrics.REGISTRY.reset()
    assert metrics.REGISTRY.get("t_snap_c").total() == 0


# ---------------------------------------------------------------------------
# canonical kernel-counter names (satellite: typo'd names fail loudly)
# ---------------------------------------------------------------------------

def test_record_dispatch_rejects_unknown_names():
    # typo'd names built by concatenation so the literal scan below
    # doesn't flag this test's own fixtures
    typo = "sbnet_gather" + "r"
    with pytest.raises(ValueError, match=typo):
        ops.record_dispatch(typo)
    before = ops.KERNEL_COUNTS["sbnet_gather"]
    with pytest.raises(ValueError):
        ops.record_dispatch("tile_" + "delta_gte")
    assert ops.KERNEL_COUNTS["sbnet_gather"] == before


def test_kernel_dispatch_mirror_bitmatches_legacy_counter():
    with obs.enabled():
        obs.configure(reset=True)
        with ops.count_kernels() as region:
            ops.record_dispatch("roi_conv_entry")
            ops.record_dispatch("roi_conv_stack")
            ops.record_dispatch("sbnet_scatter_fleet", 2)
        assert metrics.kernel_counts() == dict(region)


# string literals that match the kernel-name grammar but are benchmark
# panel keys or exported API names, not dispatch counters — anything
# else outside KERNEL_NAMES is a typo and fails the scan below
PANEL_KEYS = frozenset({
    "tile_delta_dispatches", "tile_delta_bit_exact",
    "tile_delta_static_frac", "roi_conv_interior_err",
    "roi_conv_checked_tiles",
    # ops.__all__ export: the canvas-reference gate variant dispatches
    # under the ONE "tile_delta_gate" counter (structurally the same
    # gate), so its function name is not itself a counter
    "tile_delta_gate_canvas",
})

_KNAME = re.compile(
    r"[\"'](sbnet_[a-z_]+|tile_delta[a-z_]*|roi_conv[a-z_]*"
    r"|roi_attention[a-z_]*)[\"']")


def _scan_literals(*dirnames):
    found = set()
    for d in dirnames:
        for root, _, files in os.walk(os.path.join(REPO, d)):
            for fn in files:
                if fn.endswith(".py"):
                    with open(os.path.join(root, fn)) as f:
                        found |= set(_KNAME.findall(f.read()))
    return found


def test_counter_names_in_tests_and_benchmarks_are_canonical():
    """Every kernel-counter-shaped string asserted anywhere in tests/
    benchmarks/src comes from the ONE canonical frozenset (or the known
    panel-key allowlist) — a typo'd counter name fails here instead of
    silently counting zero."""
    found = _scan_literals("tests", "benchmarks", "src")
    assert found >= {"tile_delta_gate", "roi_conv_entry"}  # scan sanity
    stray = found - metrics.KERNEL_NAMES - PANEL_KEYS
    assert not stray, f"non-canonical kernel counter names: {stray}"


def test_every_canonical_name_has_a_dispatch_site():
    pat = re.compile(r"record_dispatch\(\s*[\"']([a-z_]+)[\"']")
    found = set()
    for root, _, files in os.walk(os.path.join(REPO, "src")):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn)) as f:
                    found |= set(pat.findall(f.read()))
    assert found == metrics.KERNEL_NAMES


# ---------------------------------------------------------------------------
# the step's spans: one API, two sinks (memory and the profiler's clock)
# ---------------------------------------------------------------------------

def _reuse_case():
    rng = np.random.default_rng(1)
    grids = {0: [rng.random((3, 3)) < 0.8], 1: [rng.random((2, 3)) < 0.9]}
    f0 = {g: [rng.random((a.shape[0] * 8, a.shape[1] * 8, 3)
                         ).astype(np.float32) for a in gs]
          for g, gs in grids.items()}
    f1 = {g: [f.copy() for f in fs] for g, fs in f0.items()}
    f1[0][0][:8, :8] += 1.0               # one tile of one camera moves
    return grids, f0, f1


WARM_SPANS = ("stage", "gate", "gate_readback", "reuse_plan",
              "conv_dispatch", "ref_advance", "heads_out")


def _self_ns(evs):
    """{span_id: duration minus what its child spans cover}."""
    out = {e.span_id: e.dur_ns for e in evs}
    for e in evs:
        if e.parent in out:
            out[e.parent] -= e.dur_ns
    return out


def test_disabled_step_stores_no_event_and_makes_no_annotation(
        small_det, monkeypatch):
    made = []
    real = trace.TraceAnnotation
    monkeypatch.setattr(trace, "TraceAnnotation",
                        lambda name: made.append(name) or real(name))
    grids, f0, f1 = _reuse_case()
    cache = PackedActivationCache()
    fleet_reuse_step(small_det, f0, grids, cache)
    fleet_reuse_step(small_det, f1, grids, cache)
    assert trace.span_count() == 0 and made == []
    with obs.enabled():                   # the same step, observed
        fleet_reuse_step(small_det, f0, grids, cache)
    assert made == [e.name for e in sorted(trace.events(),
                                           key=lambda e: e.t0_ns)]
    assert set(WARM_SPANS) < set(made)


@pytest.mark.parametrize("path", ["single", "sharded"])
def test_step_spans_carry_parent_and_step_and_self_times_add_up(
        small_det, path):
    grids, f0, f1 = _reuse_case()
    if path == "single":
        cache = PackedActivationCache()
        run = functools.partial(fleet_reuse_step, small_det, grids=grids,
                                cache=cache)
        root = "fleet_reuse_step"
    else:
        rt = ShardedSuperlaunch(small_det, grids, make_fleet_mesh(1))
        cache = rt.make_cache()
        run = functools.partial(sharded_fleet_step, rt, cache=cache)
        root = "sharded_fleet_step"
    run(frames=f0)
    with obs.enabled():
        obs.configure(reset=True)
        step = cache.steps
        _, _, stats = run(frames=f1)
        evs = trace.events()
    assert all(e.step == step for e in evs)
    assert {e.name for e in evs} <= set(trace.STEP_SPANS)
    (top,) = [e for e in evs if e.parent == 0]
    assert top.name == root
    children = [e for e in evs if e.parent == top.span_id]
    assert [e.name for e in sorted(children, key=lambda e: e.t0_ns)] == \
        list(WARM_SPANS)
    # siblings follow one another inside the step span
    for e in children:
        assert top.t0_ns <= e.t0_ns <= e.t0_ns + e.dur_ns \
            <= top.t0_ns + top.dur_ns
    self_ns = _self_ns(evs)
    assert all(v >= 0 for v in self_ns.values())
    assert sum(self_ns.values()) == top.dur_ns
    args = {e.name: e.args for e in evs}
    assert args["reuse_plan"] == {"raw_changed": stats.raw_changed,
                                  "computed": stats.computed,
                                  "launched": stats.launched}
    readback = {k[0]: v for k, v in metrics.READBACK_BYTES.items()}
    assert readback.get("gate") == args["gate_readback"]["bytes"] > 0
    # single device: the heads stay on the device; sharded: the whole
    # canvas comes back to the host
    assert readback.get("heads", 0) == args["heads_out"]["bytes"]
    assert (args["heads_out"]["bytes"] > 0) == (path == "sharded")


def test_program_spans_nest_inside_the_callers_span_on_the_profiler_clock(
        small_det, tmp_path):
    """Under ``jax.profiler`` the program's spans land on the host plane
    of the trace, nested inside the caller's own annotation (the
    benchmark's ``fleet_step``), on the device ops' clock."""
    from jax.profiler import ProfileData
    grids, f0, f1 = _reuse_case()
    cache = PackedActivationCache()
    fleet_reuse_step(small_det, f0, grids, cache)
    with obs.enabled():
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("fleet_step"):
                fleet_reuse_step(small_det, f1, grids, cache)
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                             "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in trace.STEP_SPANS + ("fleet_step",):
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    (outer,) = found.pop("fleet_step")
    assert set(found) == {"fleet_reuse_step"} | set(WARM_SPANS)
    (step,) = found.pop("fleet_reuse_step")
    assert outer[0] <= step[0] <= step[1] <= outer[1]
    for name, ivs in found.items():
        for s, e in ivs:
            assert step[0] <= s <= e <= step[1], name


def test_async_pipeline_spans_nest_under_host_plan_and_overlap(small_det):
    """The pipeline's ``host_plan`` span takes the shared helpers' spans
    as children, and step t's planning overlaps step t-1's in-flight
    ``device_compute`` span."""
    rng = np.random.default_rng(0)
    grids = {0: [rng.random((3, 4)) < 0.6], 1: [rng.random((2, 3)) < 0.7]}
    frames = [{g: [rng.random((a.shape[0] * 8, a.shape[1] * 8, 3)
                              ).astype(np.float32) for a in gs]
               for g, gs in grids.items()} for _ in range(2)]
    rt = ShardedSuperlaunch(small_det, grids, make_fleet_mesh(1))
    pipe = AsyncShardedPipeline(rt, rt.make_cache())
    with obs.enabled():
        for f in frames:
            pipe.submit(f)
        pipe.drain()
        evs = trace.events()
    hosts = {e.step: e for e in evs if e.name == "host_plan"}
    devs = {e.step: e for e in evs if e.name == "device_compute"}
    assert set(hosts) == {0, 1} and set(devs) == {0, 1}
    by_id = {e.span_id: e for e in evs}
    for e in evs:
        if e.name in ("reuse_plan", "ref_advance"):
            assert by_id[e.parent].name == "host_plan"
            assert e.step == by_id[e.parent].step
    # step 0's conv chain is dispatched inside step 1's planning, the
    # last one by the collect() that drains the pipeline
    convs = [e for e in evs if e.name == "conv_dispatch"]
    assert len(convs) == 2
    assert by_id[convs[0].parent] is hosts[1] and convs[1].parent == 0
    h, d = hosts[1], devs[0]
    assert max(h.t0_ns, d.t0_ns) < min(h.t0_ns + h.dur_ns,
                                       d.t0_ns + d.dur_ns)
    assert devs[0].tid >= trace.TRACK_TID_BASE > hosts[0].tid


# ---------------------------------------------------------------------------
# fleet-step metrics capture (quantities previously dropped on the floor)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("net", ["chain", "backbone"])
def test_conv_rows_and_dispatch_args_equal_launched(small_det, net):
    """Each step's ``conv_dispatch`` span carries the rows launched at
    each output stride of the net (``rows_s<stride>``), and the
    ``conv_rows{stride}`` counter adds them up: both equal
    ``ReuseStats.launched``, on a cold step and a warm one."""
    if net == "chain":
        det, strides, t = small_det, [1], 8
    else:
        det = RoIDetector(DetectorConfig(
            tile=8, stem=4, stages=((8, 1), (8, 1)), num_classes=1,
            vmem_budget_bytes=1 << 20),
            jax.random.PRNGKey(0))
        strides, t = [1, 2, 4], 8
    rng = np.random.default_rng(1)
    grids = {0: [rng.random((3, 4)) < 0.8]}
    f0 = {0: [rng.random((24, 32, 3)).astype(np.float32)]}
    f1 = {0: [f0[0][0].copy()]}
    f1[0][0][:t, :t] += 1.0
    cache = PackedActivationCache()
    launched = []
    with obs.enabled():
        obs.configure(reset=True)
        for f in (f0, f1):
            launched.append(fleet_reuse_step(det, f, grids, cache)[2]
                            .launched)
        evs = [e for e in trace.events() if e.name == "conv_dispatch"]
        rows = {k[0]: v for k, v in metrics.CONV_ROWS.items()}
    assert det.strides == strides
    assert [e.args for e in evs] == [
        {f"rows_s{s}": n for s in strides} for n in launched]
    assert rows == {s: sum(launched) for s in strides}


def test_fleet_reuse_step_records_tiles_cache_and_span(small_det):
    det = small_det
    rng = np.random.default_rng(1)
    grids = {0: [rng.random((3, 3)) < 0.8]}
    f0 = {0: [rng.random((24, 24, 3)).astype(np.float32)]}
    cache = PackedActivationCache()
    with obs.enabled():
        obs.configure(reset=True)
        _, c0, s0 = fleet_reuse_step(det, f0, grids, cache)   # cold
        _, c1, s1 = fleet_reuse_step(det, f0, grids, cache)   # all-static
    tiles = {k[0]: v for k, v in metrics.TILES.items()}
    assert tiles["total"] == s0.total_tiles + s1.total_tiles
    assert tiles["computed"] == s0.computed + s1.computed
    ev = {k[0]: v for k, v in metrics.CACHE_EVENTS.items()}
    assert ev["step"] == 2 and ev["cold_step"] == 1
    # the warm step served every non-recomputed tile from the cache
    assert ev["hit"] == s1.total_tiles - s1.computed
    assert metrics.CHANGED_FRACTION.value() == 0.0   # latest step static
    names = [e[0] for e in trace.events()]
    assert names.count("fleet_reuse_step") == 2
    # dispatch mirror stayed bit-compatible across both steps
    assert metrics.kernel_counts() == dict(c0 + c1)


# ---------------------------------------------------------------------------
# transport empty-distribution guards (satellite: zero-frame == 0.0)
# ---------------------------------------------------------------------------

def test_zero_frame_transport_stats_are_zero_not_nan():
    ts = empty_transport(3)
    assert ts.p50_s == 0.0 and ts.p99_s == 0.0 and ts.mean_s == 0.0
    assert ts.straggler_frac == 0.0 and ts.shed_bytes == 0.0
    for k in ts.parts:
        assert ts.part_p99(k) == 0.0
    assert ts.parts_mean() == {k: 0.0 for k in ts.parts}
    assert ts.frames_sent.shape == (3,)


def test_simulate_transport_degenerate_shapes_return_zero_stats():
    class _Cam:                       # never touched on the guard path
        cam_id = 0
    # no cameras at all (the (0, S) max-reduction used to raise)
    ts = simulate_transport([], [], None, np.zeros(0), None,
                            1.0, 10, 5, 10.0, 40.0, 100.0, 1e7)
    assert ts.latency_s.size == 0 and ts.p50_s == 0.0 and ts.p99_s == 0.0
    # cameras but a zero-segment window
    ts2 = simulate_transport([_Cam()], [0], None, np.zeros(1), None,
                             1.0, 10, 0, 10.0, 40.0, 100.0, 1e7)
    assert ts2.p50_s == 0.0 and ts2.part_p99("wait") == 0.0
    assert ts2.frames_sent.shape == (1,)


def test_merge_transport_empty_and_roundtrip():
    assert merge_transport([]).p99_s == 0.0
    m = merge_transport([empty_transport(1), empty_transport(2)])
    assert m.p50_s == 0.0 and m.frames_sent.shape == (3,)


# ---------------------------------------------------------------------------
# SLO panels
# ---------------------------------------------------------------------------

def _fake_transport():
    lat = np.linspace(0.1, 1.0, 100)
    parts = {k: lat / 5 for k in ("wait", "encode", "network",
                                  "batching", "inference")}
    return TransportStats(latency_s=lat, parts=parts,
                          frame_cam=np.zeros(100, np.int64),
                          bytes_total=6e6, bytes_base=1e7,
                          frames_sent=np.full(4, 25, np.int64),
                          straggler_frames=5, deadline_hits=3,
                          quality_min=0.8, shed_halo_bytes=3e6,
                          shed_body_bytes=1e6)


def test_fleet_slo_report_aggregates_and_serializes():
    steps = [slo.StepReport(step=i, wall_s=0.1 + 0.01 * i,
                            total_tiles=100, changed_tiles=20 + i,
                            computed_tiles=30 + i, launched_tiles=32,
                            cold=(i == 0), dispatches={"roi_conv_entry": 1})
             for i in range(4)]
    ts = _fake_transport()
    rep = slo.FleetSLOReport.build(steps=steps, transport=ts,
                                   accuracy_floor=0.97,
                                   accuracy_mean=0.99, n_windows=30)
    assert rep.p50_delay_s == pytest.approx(ts.p50_s)
    assert rep.p99_delay_s == pytest.approx(ts.p99_s)
    assert rep.deadline_hit_rate == pytest.approx(3 / 30)
    assert rep.shed_bytes == pytest.approx(4e6)
    assert rep.changed_tile_fraction == pytest.approx(
        sum(20 + i for i in range(4)) / 400)
    assert rep.steps[0].compute_fraction == pytest.approx(0.30)
    d = rep.to_dict()
    json.dumps(d)
    assert d["n_steps"] == 4 and len(d["steps"]) == 4
    assert d["part_p99_s"].keys() == ts.parts.keys()
    assert d["accuracy_floor"] == 0.97


def test_step_report_from_reuse_duck_types_sharded_stats():
    class _S:                          # ShardedReuseStats-shaped
        total_tiles, raw_changed, computed, launched = 10, 4, 6, 8
        cold_shards = 1
    r = slo.StepReport.from_reuse(2, 0.5, {"tile_delta_gate": 1}, _S())
    assert r.cold and r.changed_fraction == 0.4
