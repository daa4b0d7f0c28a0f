"""The spans window and the readers of the program's own spans: self
times, the window's length and the run state it leaves, a program
without step spans, and traced runs of both tiny cells."""
import json
from collections import namedtuple
from types import SimpleNamespace

import jax
import pytest

from harness import runner, spans
from harness.catalog import Catalog
from tiny import REPO, make_root

SPAN_METRICS = {"gate_wait_ms.step", "plan_ms.step", "dispatch_ms.step",
                "readback_ms.step", "readback_mb.step"}


def test_self_times_add_up_to_the_step_span():
    E = namedtuple("E", "name span_id parent step dur_ns")
    evs = [E("fleet_reuse_step", 1, 0, 7, 100), E("gate", 2, 1, 7, 10),
           E("gate_readback", 3, 1, 7, 30), E("conv_dispatch", 4, 1, 7, 25),
           E("ref_advance", 5, 4, 7, 5),       # nested one level deeper
           E("fleet_reuse_step", 6, 0, 8, 50), E("gate", 7, 6, 8, 50),
           E("collect", 8, 0, None, 9)]        # outside any step
    names = ("fleet_reuse_step", "gate", "gate_readback", "conv_dispatch",
             "ref_advance")
    steps = spans.self_times(evs, names)
    assert steps == [
        pytest.approx({"fleet_reuse_step": 35e-9, "gate": 10e-9,
                       "gate_readback": 30e-9, "conv_dispatch": 20e-9,
                       "ref_advance": 5e-9}),
        pytest.approx({"fleet_reuse_step": 0.0, "gate": 50e-9})]
    assert sum(steps[0].values()) == pytest.approx(100e-9)
    assert spans.root_names(evs, names) == {"fleet_reuse_step"}
    assert spans.mean_ms(steps, ("gate",)) == pytest.approx(30e-6)
    assert spans.mean_ms(steps, ("conv_dispatch",)) == pytest.approx(10e-6)
    assert spans.mean_ms(steps, ("heads_out",)) is None


class FakeRun:
    """A run whose window opens the program's spans the way a fleet step
    does: one step span per step, a gate readback and a plan inside."""

    def __init__(self, measured_steps, window_s):
        self.steps = [{"step_s": window_s / measured_steps, "host_s": 0.0,
                       "walk": i % 3} for i in range(measured_steps)]
        self.snaps = ["the check's samples"]
        self.window_s = window_s
        self.lowerings_in_window = 0
        self.setup_s = 12.5
        self.asked = []

    def window(self, max_steps=None, sample=True, **kw):
        from repro.obs import metrics, trace
        self.asked.append((max_steps, sample))
        recs = []
        for i in range(max_steps):
            with trace.span("fleet_reuse_step", step=i):
                with trace.span("gate_readback"):
                    pass
                metrics.READBACK_BYTES.inc(1000, kind="gate")
                with trace.span("reuse_plan"):
                    pass
            recs.append({"step_s": 0.01, "host_s": 0.01, "walk": i % 3})
        self.steps, self.snaps, self.window_s = recs, [], 99.0
        self.setup_s = -1.0
        return recs


@pytest.mark.parametrize("measured,window_s,want", [
    (400, 40.0, 40),        # 10 steps a second: TRACE_SECONDS' worth
    (11, 40.0, spans.MIN_STEPS),   # slow steps: never fewer than 10
    (50, 0.5, 50),          # a window shorter than TRACE_SECONDS
])
def test_spans_window_length_and_the_state_it_leaves(measured, window_s,
                                                     want):
    from repro import obs
    run = FakeRun(measured, window_s)
    before = (list(run.steps), list(run.snaps), run.window_s,
              run.setup_s)
    assert not obs.is_enabled()
    ctx = SimpleNamespace(run=run)
    w = spans.window(ctx)
    assert spans.window(ctx) is w and len(run.asked) == 1
    assert run.asked == [(want, False)]
    assert len(w.steps) == len(w.spans) == want
    assert w.roots == {"fleet_reuse_step"}
    assert set(w.spans[0]) == {"fleet_reuse_step", "gate_readback",
                               "reuse_plan"}
    assert w.readback_bytes == 1000 * want
    # the measured window's records and samples, and obs, as they were
    assert (run.steps, run.snaps, run.window_s, run.setup_s) == before
    assert not obs.is_enabled()
    from repro.obs import trace
    assert trace.span_count() == 0


def test_a_program_without_step_spans_has_no_spans_window(monkeypatch):
    from repro.obs import trace
    monkeypatch.delattr(trace, "STEP_SPANS")
    run = FakeRun(10, 1.0)
    ctx = SimpleNamespace(run=run)
    assert spans.window(ctx) is None and run.asked == []
    cat = Catalog(REPO)
    for name in SPAN_METRICS:
        assert cat.module("metrics", name).read(ctx) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload", ["tiny.motion", "tiny2.motion"])
def test_traced_run_reports_span_metrics(root, capsys, monkeypatch,
                                         workload):
    # the run must not leave JAX's persistent cache pointing into a
    # temporary directory for the rest of the process
    monkeypatch.setattr(runner, "compile_cache", lambda jax, cat: "off")
    jax.clear_caches()
    rc = runner.main(["--workload", workload, "--seed", "3000000019",
                      "--seconds", "0.5", "--trace", "1"], root, 0.0,
                     require_chip=False)
    out, err = capsys.readouterr()
    assert rc == 0, err
    r = json.loads(out.strip().splitlines()[-1])
    assert r["correct"] is True
    names = {m["name"] for m in Catalog(root).metrics("per_layer",
                                                      workload)}
    assert SPAN_METRICS <= set(r["metrics"]) <= names
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert all(m[k] > 0 for k in SPAN_METRICS - {"readback_mb.step"})
    # the program's spans lie inside the benchmark's fleet-step span
    assert m["gate_wait_ms.step"] + m["plan_ms.step"] \
        + m["dispatch_ms.step"] + m["readback_ms.step"] \
        < 2 * m["host_ms.step"]
    # gate stats per step (8 int32 words per active tile, 60 tiles); the
    # sharded entry also pulls its whole head canvas back
    gate_mb = 60 * 8 * 4 / 1e6
    if workload == "tiny.motion":
        assert m["readback_mb.step"] == pytest.approx(gate_mb)
    else:
        assert m["readback_mb.step"] > 10 * gate_mb
    assert "spans window: " in err and "uncovered by the program's spans" \
        in err
    # the measured window's numbers are the ones the metrics read
    assert m["compiles_in_window"] == 0
