"""Smoke run of the served fleet step on a TPU.

    python chip_smoke.py              # one chip: the intersection
    python chip_smoke.py --chips 4    # four chips: the sharded district

One chip: the paper's intersection (5 overlapping cameras, scene and
offline RoI masks generated from seed 0) served at half resolution —
the 540p class dense inference runs at — through
``fleet.runtime.fleet_reuse_step`` with the default ``DetectorConfig``.
Each 64-px offline tile maps to 2x2 detector tiles of 16 px, so the four
1920x1080 cameras infer on 544x960 frames and the 1280x960 one on
480x640.  Four steps: cold, warm with a few tiles changed in two
cameras, all-static (the gate alone) and all-changed.  Every step's heads
are checked against ``RoIDetector.dense_forward`` with the tile mask, a
plain float32 ``jax.numpy`` reference at ``highest`` matmul precision;
the warm and all-changed steps must equal a full recompute bit for bit
(threshold-0 reuse); after the last step every earlier step's heads are
read again, which fails if a donated buffer was handed out.

Four chips: a district of 4 such intersections on ``make_fleet_mesh(4)``
through ``ShardedSuperlaunch`` (two ``sharded_fleet_step``s and one
``AsyncShardedPipeline`` submit/collect), each group's heads compared
with the single-device ``superlaunch_forward_reuse`` on the same frames.

The script refuses to run anywhere but a TPU, with the Pallas kernels
compiled by Mosaic.  Its last stdout line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when set, else
to ``.jax_cache/`` beside this script.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the largest head error allowed, relative to the reference's max |value|
REL_BOUND = 1e-3


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums JAX's own compile-phase durations (trace, lower, backend
    compile or persistent-cache fetch) and counts cache hits."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kwargs):
        if event in self.EVENTS:
            self.seconds += duration_secs
            self.compiles += event == self.EVENTS[-1]

    def _event(self, event, **kwargs):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"


def setup_compile_cache(jax) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:            # JAX reads the variable itself when it is set
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def require_tpu(jax, chips: int):
    devices = jax.devices()
    log(f"jax.devices(): {devices}")
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's default devices are {dev.platform}")
    if len(devices) < chips:
        fail(f"--chips {chips} needs {chips} devices, found {len(devices)}")
    from repro.kernels import ops
    if ops.interpret_mode():
        fail("Pallas kernels would run in the interpreter")
    return dev, len(devices)


def check_compiled(jax, jnp):
    """The served entry kernel lowers to a Mosaic custom call."""
    from repro.kernels import ops
    text = ops._roi_conv_entry_jit.lower(
        jnp.zeros((1, 32, 32, 3)), jnp.zeros((3, 3, 3, 8)),
        jnp.zeros((1, 3), jnp.int32), 16, 16, 1, False).as_text()
    if "tpu_custom_call" not in text:
        fail("the entry kernel did not lower to a Mosaic kernel")


def intersection_grids(np, seed: int):
    """Offline RoI masks of one seeded intersection, upsampled to 16-px
    detector tiles at half resolution (each 64-px tile -> 2x2)."""
    from repro.core.pipeline import run_offline
    from repro.core.scene import SceneConfig, generate_scene
    scene = generate_scene(SceneConfig(seed=seed))
    off = run_offline(scene)
    return [np.kron(off.cam_grids[c.cam_id], np.ones((2, 2), bool))
            for c in scene.cameras]


def make_frames(np, rng, grids, t):
    return [rng.normal(size=(g.shape[0] * t, g.shape[1] * t, 3))
            .astype(np.float32) for g in grids]


def change_tiles(np, rng, frames, grids, t, cams, per_cam=3):
    """Copy of ``frames`` with ``per_cam`` active tiles redrawn in each
    camera of ``cams``."""
    out = [f.copy() for f in frames]
    for c in cams:
        ys, xs = np.nonzero(grids[c])
        for i in rng.choice(ys.size, size=per_cam, replace=False):
            y, x = ys[i] * t, xs[i] * t
            out[c][y:y + t, x:x + t] = rng.normal(size=(t, t, 3))
    return out


def head_error(np, got, ref):
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(ref))))
    scale = float(np.max(np.abs(np.asarray(ref))))
    return err, scale


def run_intersection(jax, jnp, np, seed: int):
    from repro.fleet.runtime import fleet_reuse_step
    from repro.serving.detector import (DetectorConfig, PackedActivationCache,
                                        RoIDetector)
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(seed))
    t = det.cfg.tile
    grids = intersection_grids(np, seed)
    n_tiles = int(sum(g.sum() for g in grids))
    log(f"intersection: {len(grids)} cameras, frames "
        f"{[(g.shape[0] * t, g.shape[1] * t) for g in grids]}, "
        f"{n_tiles} active {t}-px tiles, block {det.block}")
    rng = np.random.default_rng(seed)
    f0 = make_frames(np, rng, grids, t)
    f1 = change_tiles(np, rng, f0, grids, t, cams=(0, 3))
    f3 = make_frames(np, rng, grids, t)
    steps = [("cold", f0), ("warm", f1), ("static", f1), ("all-changed", f3)]
    expected = {
        "cold": {"roi_conv_entry": 1, "roi_conv_stack": 1,
                 "sbnet_scatter_fleet": 1},
        "warm": {"tile_delta_gate": 1, "roi_conv_entry": 1,
                 "roi_conv_stack": 1, "sbnet_scatter_changed": 1},
        "static": {"tile_delta_gate": 1},
        "all-changed": {"tile_delta_gate": 1, "roi_conv_entry": 1,
                        "roi_conv_stack": 1, "sbnet_scatter_changed": 1},
    }
    cache = PackedActivationCache()
    kept = []
    for name, frames in steps:
        fj = {0: [jnp.asarray(f) for f in frames]}
        outs, counts, stats = fleet_reuse_step(det, fj, {0: grids}, cache)
        heads = [np.asarray(h) for h in outs[0]]
        if dict(counts) != expected[name]:
            fail(f"{name} step dispatched {dict(counts)}, expected "
                 f"{expected[name]}")
        worst = 0.0
        with jax.default_matmul_precision("highest"):
            for c, (f, g) in enumerate(zip(frames, grids)):
                ref = det.dense_forward(jnp.asarray(f), g)
                err, scale = head_error(np, heads[c], ref)
                worst = max(worst, err / max(scale, 1e-30))
                if err > REL_BOUND * scale:
                    fail(f"{name} step camera {c}: max abs error {err:.3e} "
                         f"> {REL_BOUND} x max|ref| {scale:.3e}")
        log(f"step {name}: dispatches {dict(counts)}, computed "
            f"{stats.computed}/{stats.total_tiles} tiles, max abs error / "
            f"max|ref| = {worst:.3e}")
        kept.append((name, outs[0], heads))
        if name in ("warm", "all-changed"):
            full, _, _ = fleet_reuse_step(det, fj, {0: grids},
                                          PackedActivationCache())
            same = all(np.array_equal(np.asarray(a), b)
                       for a, b in zip(full[0], heads))
            log(f"step {name}: threshold-0 reuse bit-identical to a full "
                f"recompute: {same}")
            if not same:
                fail(f"{name} step differs from a full recompute")
    for name, dev_heads, host in kept:
        if not all(np.array_equal(np.asarray(a), b)
                   for a, b in zip(dev_heads, host)):
            fail(f"{name} step heads changed after later steps")
    log("re-read every step's heads after the last step: unchanged")


def run_district(jax, jnp, np, seed: int):
    from repro.fleet.runtime import (run_fleet_offline, sharded_fleet_step)
    from repro.fleet.sharded import AsyncShardedPipeline, ShardedSuperlaunch
    from repro.fleet.topology import FleetConfig, GroupSpec, build_fleet
    from repro.launch.mesh import make_fleet_mesh
    from repro.serving.detector import (DetectorConfig, PackedActivationCache,
                                        RoIDetector)
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(seed))
    t = det.cfg.tile
    fleet = build_fleet(FleetConfig(groups=[
        GroupSpec(p, seed=seed + i) for i, p in
        enumerate(("uniform", "rush_hour", "sparse", "bursty"))]))
    offs = run_fleet_offline(fleet)
    grids = {g.gid: [np.kron(offs.per_group[g.gid].cam_grids[c.cam_id],
                             np.ones((2, 2), bool))
                     for c in g.scene.cameras] for g in fleet.groups}
    log(f"district: {len(grids)} groups x {fleet.cams_per_group} cameras, "
        f"{int(sum(g.sum() for gs in grids.values() for g in gs))} active "
        f"tiles")
    rng = np.random.default_rng(seed)
    fa = {gid: make_frames(np, rng, gs, t) for gid, gs in grids.items()}
    fb = {gid: change_tiles(np, rng, fa[gid], gs, t, cams=(1,))
          for gid, gs in grids.items()}
    mesh = make_fleet_mesh(4)
    rt = ShardedSuperlaunch(det, grids, mesh)
    log(f"shard plan: groups per shard "
        f"{[rt.groups_on_shard(s) for s in range(4)]}")

    def compare(label, outs, frames):
        worst = 0.0
        for gid, gs in grids.items():
            ref, _ = det.superlaunch_forward_reuse(
                {gid: [jnp.asarray(f) for f in frames[gid]]}, {gid: gs},
                PackedActivationCache())
            for c, (got, r) in enumerate(zip(outs[gid], ref[gid])):
                err, scale = head_error(np, got, r)
                worst = max(worst, err / max(scale, 1e-30))
                if err > REL_BOUND * scale:
                    fail(f"{label} group {gid} camera {c}: max abs error "
                         f"{err:.3e} > {REL_BOUND} x max|ref| {scale:.3e}")
        log(f"{label}: per-group heads vs single-device superlaunch, max abs "
            f"error / max|ref| = {worst:.3e}")

    cache = rt.make_cache()
    for label, frames in (("sharded step 1", fa), ("sharded step 2", fb)):
        outs, counts, stats = sharded_fleet_step(rt, frames, cache)
        log(f"{label}: dispatches {dict(counts)}, computed "
            f"{stats.computed}/{stats.total_tiles} tiles")
        compare(label, outs, frames)
    span = cache.canvas.sharding.device_set
    log(f"stacked head canvas spans {len(span)} devices")
    if len(span) != 4:
        fail(f"the canvas spans {len(span)} devices, not 4")
    pipe = AsyncShardedPipeline(rt, rt.make_cache())
    pipe.submit(fb)
    _, outs, _ = pipe.collect()
    compare("async pipeline submit/collect", outs, fb)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    cache_dir = setup_compile_cache(jax)
    clock = CompileClock(jax)
    dev, count = require_tpu(jax, args.chips)
    log(f"device kind: {dev.device_kind}; compile cache: {cache_dir}")
    check_compiled(jax, jnp)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_district(jax, jnp, np, args.seed)
    else:
        run_intersection(jax, jnp, np, args.seed)
    log(f"compile set-up: {clock.seconds:.1f} s over {clock.compiles} "
        f"compiles, {clock.cache_hits} persistent-cache hits "
        f"(whole run {time.perf_counter() - t0:.1f} s)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
