"""One run of one cell: set-up, warm-up, measured window, check, result.

The window is closed loop: each fleet step starts when the previous one
has finished.  Between steps the generator advances every camera by one
scene frame, editing the host frames in place; a step is timed from the
moment the frames are handed over until every head map the step
returned is ready.  Nothing compiles in the window: the warm-up walks
one whole period of the traffic, so every transition the window can meet
has been run once.  The traffic mix names its generator, a module under
``bench/generators/``.

What a configuration brings, all found by name: its reference
(``bench/references/``), which declares the detector layer by layer
(``layers``), draws the weights (``init``) and computes the plain head
map (``forward``, ``pixel_mask``); its entry (``bench/entries/``), which
builds the program's detector from the ``detector`` dict and those
weights and runs a fleet step; and a work file (``bench/work/``) for
each kernel role its layers name that no configuration has named
before.  The FLOPs of ``mfu.step``, each role's work and the tile rings
of the generator's useful tiles are counted from the layer list
(``harness/layers.py``); nothing here knows the architecture.

``correct`` compares head maps the timed steps returned against the
plain reference on the same frames: a sample of steps drawn from the
seed, the step with the most changed tiles among the first
``SAMPLE_RANGE``, and the last step of the window.  Their heads and
frames are copied to the host right after the step, with the window's
clock paused.  The reference runs once the window has closed, the
memory peak has been read and the program's state is freed.
"""
import argparse
import contextlib
import gc
import json
import math
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

from harness import layers as ly
from harness import trace as tr
from harness.catalog import Catalog

SAMPLE_RANGE = 64     # the sampled steps lie among the window's first 64
SAMPLE_DRAWS = 2
TRACE_SECONDS = 4     # length of the profiled window of a --trace 1 run
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class Refused(Exception):
    """The run cannot measure what the cell asks for."""


class CompileCounter:
    """Counts lowerings (every jit cache miss, whether the persistent
    cache then hits or the backend compiles) and backend compiles, from
    JAX's own monitoring events."""

    def __init__(self, jax):
        self.lowerings = 0
        self.compiles = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **kwargs):
        if event == LOWERING_EVENT:
            self.lowerings += 1
        elif event == COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration_secs


def compile_cache(jax, cat):
    """JAX's persistent compilation cache, at a fixed directory in the
    checkout (``bench/.cache/jax``), caching every program however short
    its compile, so that only a checkout's first run compiles."""
    path = cat.path(".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def chip_devices(jax, chips, peaks):
    """The cell's devices, or ``Refused``: a TPU whose kind has peaks,
    with as many chips as the cell asks for, running compiled kernels."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's devices are {devs[0].platform}")
    if devs[0].device_kind not in peaks:
        raise Refused(f"device kind {devs[0].device_kind!r} has no entry in "
                      f"bench/peaks.json")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    from repro.kernels import ops
    if ops.interpret_mode():
        raise Refused("Pallas kernels would run in the interpreter")
    return devs[:chips]


def _null_span(name):
    return contextlib.nullcontext()


def _host_heads(outs):
    return {g: [np.asarray(h) for h in hs] for g, hs in outs.items()}


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


class Run:
    """One cell, one seed.  ``devices`` are the devices to run on (found
    by ``chip_devices`` in a benchmark run)."""

    def __init__(self, cat, workload, seed, devices, t_start=None):
        import jax
        self.jax = jax
        self.cat = cat
        self.wl = cat.workload(workload)
        self.cfg = cat.config(self.wl["config"])
        self.traffic = cat.traffic(self.wl["traffic"])
        self.seed = int(seed)
        self.devices = devices
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.counter = CompileCounter(jax)

    # -- set-up ------------------------------------------------------------
    def setup(self):
        cfg = self.cfg
        detector = cfg["detector"]
        tile = detector["tile"]
        self.ref = self.cat.module("references", cfg["reference"])
        layers = self.ref.layers(detector)
        ly.check(layers)
        scenes = [self.cat.scene(g["scene"]) for g in cfg["groups"]]
        gen = self.cat.module("generators", self.traffic["generator"])
        self.motion = gen.Generator(scenes, self.traffic, cfg["scale"],
                                    tile, self.seed, ly.rings(layers, tile))
        self.params = self.ref.init(self.jax.random.PRNGKey(self.seed),
                                    detector)
        self.dims = {"tile": tile, "cin": layers[0]["cin"],
                     "channels": [layer["cout"] for layer in layers
                                  if layer["op"] == "conv"],
                     "heads": sum(h["cout"] for h in ly.heads(layers)),
                     "n_active": self.motion.n_active,
                     "layers": layers, "rf_px": ly.rf_px(layers)}
        entry = self.cat.module("entries", cfg["entry"]).Entry
        self.entry = entry(detector, self.params, self.motion.grids,
                           self.devices, cfg["gate_threshold"])

    def warmup(self, steps=None):
        """The cold step, then ``steps`` warm steps (default: one whole
        period of the walk, every transition once).  Counts the
        lowerings: the programs the cold step and the walk need, whether
        the persistent cache holds them or not."""
        lowerings0 = self.counter.lowerings
        self.entry.step(self.motion.frames, _null_span)
        n = self.motion.period if steps is None else steps
        t0 = last = time.perf_counter()
        for i in range(n):
            self.motion.advance()
            self.entry.step(self.motion.frames, _null_span)
            now = time.perf_counter()
            if now - last > 30:
                last = now
                log(f"warm-up: {i + 1}/{n} steps in {now - t0:.1f} s, "
                    f"{self.counter.compiles} backend compiles "
                    f"({self.counter.seconds:.1f} s)")
        self.warmup_lowerings = self.counter.lowerings - lowerings0

    # -- the measured window ----------------------------------------------
    def sample_plan(self):
        rng = np.random.default_rng([self.seed, 1])
        picks = set(int(i) for i in rng.choice(SAMPLE_RANGE, SAMPLE_DRAWS,
                                               replace=False))
        first = self.motion.step + 1
        changed = [self.motion.transition(first + i)[1]
                   for i in range(SAMPLE_RANGE)]
        picks.add(int(np.argmax(changed)))
        return picks

    def window(self, seconds=None, max_steps=None, span=_null_span,
               sample=True):
        """Closed-loop steps until ``seconds`` of window time or
        ``max_steps`` steps, keeping the check's samples unless
        ``sample`` is false.  Returns the step records."""
        motion, entry = self.motion, self.entry
        picks = self.sample_plan() if sample else set()
        recs, snaps, paused = [], [], 0.0
        outs = None
        lowerings0 = self.counter.lowerings
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_start
        prev = t0
        while True:
            if max_steps is not None and len(recs) >= max_steps:
                break
            if seconds is not None and \
                    time.perf_counter() - t0 - paused >= seconds:
                break
            with span("generate"):
                changed, useful = motion.advance()
            g = time.perf_counter()
            outs, stats, host_s = entry.step(motion.frames, span)
            e = time.perf_counter()
            recs.append({"step_s": e - g, "host_s": host_s,
                         "generate_s": g - prev,
                         "walk": motion.step % motion.period,
                         "launched": int(stats.launched),
                         "computed": int(stats.computed),
                         "changed": changed, "useful": useful,
                         "n_active": motion.n_active})
            if len(recs) - 1 in picks:
                p = time.perf_counter()
                snaps.append((len(recs) - 1, motion.snapshot(),
                              _host_heads(outs)))
                paused += time.perf_counter() - p
            prev = time.perf_counter()
        self.window_s = prev - t0 - paused
        self.lowerings_in_window = self.counter.lowerings - lowerings0
        if sample and recs and len(recs) - 1 not in picks:
            snaps.append((len(recs) - 1, motion.snapshot(),
                          _host_heads(outs)))
        self.steps, self.snaps = recs, snaps
        return recs

    # -- after the window --------------------------------------------------
    def memory_peak(self):
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks) if peaks else 0

    def release(self):
        """Drop the program's state before the reference runs."""
        self.entry = None
        gc.collect()

    def head_gaps(self, passes="highest", against=None):
        """Per checked step, the widest gap between heads and the
        reference at ``passes``, as a share of the reference's largest
        magnitude.  ``against="reference"`` compares the reference at
        ``passes`` with the reference at ``highest`` instead of the
        program (the control)."""
        ref, tile = self.ref, self.dims["tile"]
        gaps = []
        for i, frames, heads in self.snaps:
            worst = 0.0
            for g, fs in frames.items():
                for c, f in enumerate(fs):
                    mask = ref.pixel_mask(self.motion.grids[g][c], tile,
                                          f.shape)
                    r = np.asarray(ref.forward(self.params, f, mask))
                    got = heads[g][c] if against is None else np.asarray(
                        ref.forward(self.params, f, mask, passes=passes))
                    worst = max(worst, _gap(got, r))
            gaps.append((i, worst))
        return gaps


def _gap(got, ref):
    got = np.asarray(got)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return math.inf
    scale = float(np.max(np.abs(ref)))
    return float(np.max(np.abs(got - ref))) / max(scale, 1e-30)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def end_to_end(run):
    cams = run.motion.cameras
    ms = [r["step_s"] * 1e3 for r in run.steps]
    return {"setup_s": run.setup_s,
            "frames_per_s": cams * len(run.steps) / run.window_s,
            "step_ms.p50": percentile(ms, 50),
            "step_ms.p95": percentile(ms, 95)}


class Context:
    """What a per-layer metric reader may read: the measured window's
    step records, wall length and lowerings; the warm-up's lowerings;
    the traced window's step records and trace reduction; the chip's
    peaks and the kernels' work functions; the cell's ``dims``: tile,
    the reference's layer list and its receptive field ``rf_px``."""

    def __init__(self, run, traced_steps, trace, peak):
        self.run = run
        self.steps = run.steps
        self.window_s = run.window_s
        self.chips = len(run.devices)
        self.lowerings_in_window = run.lowerings_in_window
        self.warmup_lowerings = run.warmup_lowerings
        self.traced_steps = traced_steps
        self.trace = trace
        self.peak = peak
        self.dims = run.dims

    def work(self, kernel):
        """(FLOPs, bytes) the kernel's role needs over the traced
        window."""
        mod = self.run.cat.module("work", kernel)
        flops = nbytes = 0.0
        for s in self.traced_steps:
            f, b = mod.work(s, self.dims)
            flops += f
            nbytes += b
        return flops, nbytes

    def untraced_seconds(self):
        """What the traced window's steps took in the measured window:
        per traced step, the mean wall (generation and step) of the
        measured steps at the same walk position, summed; None where the
        measured window missed a position the traced one met."""
        walls = {}
        for s in self.steps:
            walls.setdefault(s["walk"], []).append(s["generate_s"]
                                                   + s["step_s"])
        if any(s["walk"] not in walls for s in self.traced_steps):
            return None
        return sum(statistics.fmean(walls[s["walk"]])
                   for s in self.traced_steps)

    def kernel_seconds(self, kernel):
        mod = self.run.cat.module("work", kernel)
        return tr.op_seconds(self.trace["per_op_s"], mod.TRACE_NAMES)


def per_layer(run, ctx, names):
    out = {}
    for m in names:
        v = run.cat.module("metrics", m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def breakdown(trace, n_devices):
    """The ten ops with the most device seconds (per device; an op's
    name is its HLO text, cut to 160 characters) and the ten
    longest idle gaps, named by the span that covers them."""
    ops = sorted(trace["per_op_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n[:160], t / n_devices] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in trace["gaps"][:10]]}


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------

def traced_window(jax, run, seconds):
    """A second, profiled window after the measured one: its step
    records and the reduction of its trace.  The profiler slows the
    host several-fold, so the measured window's numbers stay its own;
    the check keeps the measured window's samples."""
    clean = (run.steps, run.snaps, run.window_s, run.lowerings_in_window,
             run.setup_s)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    # the device ops and the benchmark's own spans: no Python tracer,
    # and host events of level 1 only
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        traced = run.window(seconds=seconds, sample=False,
                            span=jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    try:
        spans, ops = tr.read_events(tr.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    (run.steps, run.snaps, run.window_s, run.lowerings_in_window,
     run.setup_s) = clean
    return traced, tr.reduce(spans, ops, [d.id for d in run.devices])


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, root, t_start, require_chip=True):
    """Run one cell; print its result line.  Returns the exit code."""
    args = parse(argv)
    cat = Catalog(root)
    wl = cat.workload(args.workload)
    import jax
    try:
        cache_dir = compile_cache(jax, cat)
        if require_chip:
            devices = chip_devices(jax, wl["chips"], cat.peaks())
        else:
            devices = jax.devices()[:wl["chips"]]
    except Refused as e:
        log(f"refused: {e}")
        return 2
    dev = devices[0]
    log(f"cell {args.workload} seed {args.seed}: {len(devices)} x "
        f"{dev.device_kind}; compile cache {cache_dir}")
    run = Run(cat, args.workload, args.seed, devices, t_start)
    run.setup()
    run.warmup()
    log(f"set-up: {run.motion.cameras} cameras, {run.motion.n_active} active "
        f"tiles, warm-up {run.motion.period + 1} steps, "
        f"{run.counter.compiles} backend compiles "
        f"({run.counter.seconds:.1f} s)")
    run.window(seconds=args.seconds)
    gen = [r["generate_s"] * 1e3 for r in run.steps]
    slow = sorted(range(len(run.steps)), key=lambda i: -run.steps[i]["step_s"])
    log(f"window: {len(run.steps)} steps in {run.window_s:.3f} s; "
        f"generator {statistics.fmean(gen):.3f} ms per step "
        f"(max {max(gen):.3f}); {run.lowerings_in_window} lowerings; "
        f"slowest steps " + ", ".join(
            f"#{i} {run.steps[i]['step_s'] * 1e3:.1f} ms" for i in slow[:5]))
    if len(run.steps) < 200:
        log(f"warning: {len(run.steps)} steps leave fewer than 10 beyond "
            f"the 95th percentile")
    memory = run.memory_peak()
    names = cat.metrics("per_layer" if args.trace else "end_to_end",
                        args.workload)
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": memory}}
    if args.trace:
        traced, trace = traced_window(jax, run, min(args.seconds,
                                                    TRACE_SECONDS))
        peak = cat.peaks().get(dev.device_kind)
        metrics = per_layer(run, Context(run, traced, trace, peak), names)
        result["device"].update(busy_s=trace["busy_s"],
                                window_s=trace["window_s"])
        result["breakdown"] = breakdown(trace, len(devices))
    else:
        e2e = end_to_end(run)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in names}
    run.release()
    limit = float(run.cfg["check"]["head_gap_limit"])
    gaps = run.head_gaps()
    worst = max(g for _, g in gaps)
    failed = sum(g > limit for _, g in gaps)
    log(f"checked steps {[i for i, _ in gaps]} of the window")
    out = {"correct": failed == 0, "attempted": len(run.steps),
           "failed": failed, "metrics": metrics}
    out.update(result)
    out["check"] = {"head_gap": {"value": worst, "limit": limit}}
    print(json.dumps(out), flush=True)
    log(f"check head_gap {worst!r} limit {limit!r}")
    return 0
