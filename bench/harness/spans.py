"""The program's own spans, read from its in-memory recorder.

The program (``repro.obs.trace``) records a span per role inside a
fleet step, each with its parent span and the step it belongs to, and
exports the names in ``STEP_SPANS``.  A program that exports no such
tuple has nothing here to read: ``window`` then gives None and every
reader returns None.

The readers of a ``--trace 1`` run read a **spans window**: after the
traced window, the first reader that asks runs the cell on, closed loop
as in the measured window, with the program's spans on and no profiler
(the profiler slows the host several-fold, so host milliseconds from
the traced window would be inflated).  It lasts as many steps as the
measured window ran in ``TRACE_SECONDS`` (or its own length, if
shorter), and at least ``MIN_STEPS``.  The measured window's records
and samples stay the run's own, and observability is left as it was.
Its log line on stderr sets the spans window's step against the
measured window's at the same walk positions (what tracing costs when
on) and says how much of the step span the program's spans leave
uncovered.

A span's self time is its duration less what its child spans cover;
over one step the self times of all its spans add up to the step
span's duration.
"""
import math
import statistics

from harness import runner

MIN_STEPS = 10


def program_span_names():
    """The span names the program opens inside a fleet step."""
    from repro.obs import trace
    return tuple(getattr(trace, "STEP_SPANS", ()))


def self_times(events, names):
    """Per step, in step order: {span name: self seconds, summed over
    that step's spans of the name}, over the events named in
    ``names``."""
    evs = [e for e in events if e.name in names and e.step is not None]
    own = {e.span_id: e.dur_ns for e in evs}
    for e in evs:
        if e.parent in own:
            own[e.parent] -= e.dur_ns
    steps = {}
    for e in evs:
        per = steps.setdefault(e.step, {})
        per[e.name] = per.get(e.name, 0.0) + own[e.span_id] * 1e-9
    return [steps[k] for k in sorted(steps)]


def root_names(events, names):
    """The names, among ``names``, of the spans that no other span of
    ``names`` encloses: the step spans."""
    evs = [e for e in events if e.name in names and e.step is not None]
    ids = {e.span_id for e in evs}
    return {e.name for e in evs if e.parent not in ids}


def mean_ms(steps, names):
    """Mean per step of the self times of the spans ``names``, in ms;
    None where no step holds any of them."""
    if not any(n in s for s in steps for n in names):
        return None
    return 1e3 * statistics.fmean(sum(s.get(n, 0.0) for n in names)
                                  for s in steps)


class SpansWindow:
    """What the spans window recorded: its step records (``steps``), the
    self time of each program span per step (``spans``), the step spans'
    names (``roots``) and the bytes the program read back to the host
    (``readback_bytes``, None where it counts none)."""

    def __init__(self, steps, spans, roots, readback_bytes):
        self.steps = steps
        self.spans = spans
        self.roots = roots
        self.readback_bytes = readback_bytes

    def mean_ms(self, names):
        return mean_ms(self.spans, names)


def window(ctx):
    """The spans window of the run behind ``ctx``: run by the first
    caller, kept on ``ctx`` for the others.  None for a program without
    step spans."""
    if not hasattr(ctx, "_spans_window"):
        ctx._spans_window = run_window(ctx.run)
    return ctx._spans_window


def window_steps(run):
    """Steps the spans window runs: as many as the measured window ran in
    ``TRACE_SECONDS`` (or in its own length, if shorter), at least
    ``MIN_STEPS``."""
    seconds = min(run.window_s, runner.TRACE_SECONDS)
    rate = len(run.steps) / run.window_s if run.window_s > 0 else 0.0
    return max(MIN_STEPS, math.ceil(seconds * rate))


def run_window(run):
    names = program_span_names()
    if not names:                     # a program without step spans
        runner.log("spans window: the program records no step spans")
        return None
    from repro import obs
    from repro.obs import metrics, trace
    saved = (run.steps, run.snaps, run.window_s, run.lowerings_in_window,
             run.setup_s)
    was_on = obs.is_enabled()
    obs.configure(enabled=True, reset=True)
    try:
        steps = run.window(max_steps=window_steps(run), sample=False)
        events = trace.events()
        try:
            readback = metrics.REGISTRY.get("readback_bytes").total()
        except KeyError:
            readback = None
    finally:
        obs.configure(enabled=was_on, reset=True)
        (run.steps, run.snaps, run.window_s, run.lowerings_in_window,
         run.setup_s) = saved
    got = SpansWindow(steps, self_times(events, names),
                      root_names(events, names), readback)
    log_window(run, got)
    return got


def same_walk_seconds(measured, steps):
    """Per step of ``steps``, the mean step time of the ``measured`` steps
    at the same walk position, summed; None where ``measured`` missed a
    position ``steps`` met."""
    walls = {}
    for s in measured:
        walls.setdefault(s["walk"], []).append(s["step_s"])
    if any(s["walk"] not in walls for s in steps):
        return None
    return sum(statistics.fmean(walls[s["walk"]]) for s in steps)


def log_window(run, w):
    """Log what the spans window cost against the measured window, and
    how much of the step span the program's spans leave uncovered."""
    n = len(w.steps)
    on = sum(s["step_s"] for s in w.steps)
    off = same_walk_seconds(run.steps, w.steps)
    cost = (f"{1e3 * off / n:.3f} ms in the measured window at the same "
            f"walk positions ({100 * (on / off - 1):+.2f}%)"
            if off else "no measured step at the same walk positions")
    runner.log(f"spans window: {n} steps, step {1e3 * on / n:.3f} ms "
               f"against {cost}")
    root = w.mean_ms(tuple(w.roots))
    if root is None:
        return
    names = {k for s in w.spans for k in s}
    total = w.mean_ms(tuple(names))
    inner = w.mean_ms(tuple(names - w.roots)) or 0.0
    host_ms = 1e3 * statistics.fmean(s["host_s"] for s in w.steps)
    runner.log(
        f"spans: step span {total:.3f} ms, uncovered by the program's "
        f"spans {root:.3f} ms ({100 * root / total:.2f}%); spans inside "
        f"it {inner:.3f} ms against {host_ms:.3f} ms of host time in the "
        f"fleet-step call; per span " + ", ".join(
            f"{k} {w.mean_ms((k,)):.3f}" for k in sorted(names)))
