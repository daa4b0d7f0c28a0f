"""Reduce a profiler trace of the measured window to device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<run>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``; on each, the line ``XLA Ops`` holds one event per
operation the device ran, on the same clock as the host planes, where
the benchmark's own spans (``jax.profiler.TraceAnnotation``) sit on the
thread that made them.

From that the reduction takes, for the traced window (from the start of
the first host span of a step to the end of the last):

* ``busy_s`` per device: the union of the device-op intervals inside the
  window, and ``idle_share`` = 1 - busy / window, averaged over devices;
* the device seconds of every op name, summed over devices;
* the idle gaps on each device, each named by the innermost benchmark
  span that covers its middle.
"""
import glob
import os
import re

import numpy as np

SPANS = ("generate", "upload", "fleet_step", "wait")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, "
                           f"found {len(files)}")
    return files[0]


def _union_length(iv):
    """Total length of the union of (start, end) intervals."""
    if not iv:
        return 0.0
    iv = sorted(iv)
    total, cur_s, cur_e = 0.0, iv[0][0], iv[0][1]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + cur_e - cur_s


def _gaps(iv, lo, hi):
    """Idle (start, end) gaps between the union of ``iv`` within
    [lo, hi]."""
    out, t = [], lo
    for s, e in sorted(iv):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def read_events(path):
    """(host spans [(name, start_ns, end_ns)], device ops
    {device: [(name, start_ns, end_ns)]}) of one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, ops = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops[int(m.group(1))] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
            elif plane.name.startswith("/host:"):
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in SPANS)
    return spans, ops


def reduce(spans, ops, devices):
    """Window, busy time, per-op device seconds and named idle gaps.

    ``devices``: the device ids the cell ran on."""
    if not spans:
        raise RuntimeError("the trace holds none of the benchmark's spans")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    window_s = (hi - lo) * 1e-9
    busy, per_op, gaps = [], {}, []
    ordered = sorted(spans, key=lambda s: s[1])
    starts = np.array([s for _, s, _ in ordered], np.float64)
    for d in devices:
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in ops.get(d, [])
               if e > lo and s < hi]
        iv = [(s, e) for _, s, e in evs]
        busy.append(_union_length(iv) * 1e-9)
        for n, s, e in evs:
            per_op[n] = per_op.get(n, 0.0) + (e - s) * 1e-9
        for s, e in _gaps(iv, lo, hi):
            # the spans follow one another, so only the latest one to
            # start before the middle of the gap can cover it
            mid = 0.5 * (s + e)
            j = int(np.searchsorted(starts, mid)) - 1
            name = ordered[j][0] if j >= 0 and ordered[j][2] >= mid \
                else "between_spans"
            gaps.append((name, (e - s) * 1e-9))
    busy_s = float(np.mean(busy)) if busy else 0.0
    # no device plane at all (a CPU run): nothing to read an idle share of
    seen = any(ops.get(d) for d in devices)
    return {"window_s": window_s, "busy_s": busy_s,
            "busy_per_device_s": busy, "per_op_s": per_op,
            "idle_share": (1.0 - busy_s / window_s
                           if seen and window_s > 0 else None),
            "gaps": sorted(gaps, key=lambda g: -g[1])}


def op_seconds(per_op, patterns):
    """Device seconds of the ops whose names match any of ``patterns``."""
    rx = [re.compile(p) for p in patterns]
    return sum(t for n, t in per_op.items() if any(r.search(n) for r in rx))
