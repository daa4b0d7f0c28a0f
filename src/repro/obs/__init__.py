"""Fleet observability: tracing, typed metrics, SLO panels.

Three pieces, all default-OFF and zero-dispatch by construction (host
timestamps around already-existing sync points only — no
``block_until_ready`` is ever added to a hot path):

* ``obs.trace`` — the span API (``with obs.trace.span("gate"): ...``):
  each span is kept in memory with its parent and step id and written
  through ``jax.profiler.TraceAnnotation``, so a profiler session puts
  it on the device trace's clock; async begin/end handles put in-flight
  device work on its own track.
* ``obs.metrics`` — typed counters/gauges/histograms with labels.
  ``kernel_dispatches`` mirrors ``ops.KERNEL_COUNTS`` bit-for-bit;
  the canonical ``KERNEL_NAMES`` frozenset makes typo'd counter names
  fail loudly.
* ``obs.slo`` — ``StepReport``/``FleetSLOReport`` panels
  (p50/p99 delay, deadline hit rate, bytes shed, accuracy floor,
  changed-tile fraction) that ``benchmarks/run.py`` merges into
  ``BENCH_kernels.json``.

On top of the panels sit the heavy-traffic harness (``obs.loadgen`` —
SLO frontier sweeps over fleet scale x congestion x traffic profile x
serve rate, driving the production runtimes with zero added dispatches)
and the CI gate that watches the resulting history stream
(``obs.sentinel`` — git-SHA-aware regression detection with
noise-robust min-of-reps / median-of-window baselines).

Switch it on with ``obs.configure(enabled=True)`` (or scoped:
``with obs.enabled(): ...``); ``configure(reset=True)`` clears the
recorded spans and metric values.
"""
from __future__ import annotations

import contextlib

from repro.obs import (loadgen, metrics, sentinel, slo,  # noqa: F401
                       state, trace)


def configure(enabled=None, reset: bool = False) -> bool:
    """Set the global observability switch and/or reset recorded data.

    ``configure(enabled=True)`` turns span recording and metric updates
    on (default off — tier-1 tests and production paths pay one boolean
    check per call site).  ``configure(reset=True)`` clears the span
    buffer and zeroes every registered metric (registrations survive).
    Returns the resulting enabled state."""
    if enabled is not None:
        state.enabled = bool(enabled)
    if reset:
        trace.clear()
        metrics.REGISTRY.reset()
    return state.enabled


def is_enabled() -> bool:
    return state.enabled


@contextlib.contextmanager
def enabled(flag: bool = True):
    """Scoped enable/disable: ``with obs.enabled(): run_step()``."""
    prev = state.enabled
    state.enabled = bool(flag)
    try:
        yield
    finally:
        state.enabled = prev
