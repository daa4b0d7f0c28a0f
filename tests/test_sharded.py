"""Sharded fleet serving: shard-plan balance, mesh=(1,) bit-identity to
the single-device super-launch, per-shard dispatch ceilings, async
pipeline parity, per-shard drift invalidation, and per-context kernel
counters under threads.

Multi-device cases run in subprocesses (XLA locks the host platform
device count at first init); everything else uses an in-process
1-device fleet mesh — bit-identity there is the base case the
multi-shard subprocess extends."""
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

import jax

from hypothesis import given, settings, strategies as st

from repro.fleet import sharded_fleet_step, wire_shard_invalidation
from repro.fleet.sharded import (AsyncShardedPipeline,
                                 ShardedActivationCache, ShardedSuperlaunch)
from repro.kernels import ops
from repro.launch.mesh import make_fleet_mesh
from repro.net.batcher import DeadlineGroupFormer
from repro.net.encoder import gate_threshold_schedule
from repro.serving.detector import (DetectorConfig, PackedActivationCache,
                                    RoIDetector)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 2, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, \
        f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# shard planning (host-only: no mesh, no kernels)
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(0, 40), min_size=1, max_size=24),
       st.integers(1, 8))
@settings(max_examples=60, deadline=None)
def test_shard_plan_balance_property(tile_counts, n_shards):
    """LPT bound: max shard load <= mean + the largest single group —
    and the plan is a partition (every group exactly once)."""
    grids = [[np.ones((1, t), bool)] if t else [np.zeros((1, 1), bool)]
             for t in tile_counts]
    plan = ops.shard_plan(grids, n_shards)
    assert plan.n_groups == len(tile_counts)
    assert sorted(sum((plan.shard_groups(s) for s in range(n_shards)), [])
                  ) == list(range(len(tile_counts)))
    loads = plan.shard_tiles
    assert int(loads.sum()) == sum(tile_counts)
    if sum(tile_counts):
        assert loads.max() <= loads.sum() / n_shards + max(tile_counts)
        assert plan.imbalance >= 1.0


def test_shard_plan_rejects_zero_shards():
    with pytest.raises(ValueError):
        ops.shard_plan([[np.ones((1, 1), bool)]], 0)


# ---------------------------------------------------------------------------
# mesh=(1,) sharded path == single-device super-launch, bit for bit
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_det():
    return RoIDetector(DetectorConfig(tile=8, channels=(4, 6)),
                       jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def ragged_grids():
    rng = np.random.default_rng(0)
    return {0: [rng.random((3, 4)) < 0.6, rng.random((2, 2)) < 0.9],
            1: [rng.random((4, 3)) < 0.5],
            2: [np.zeros((2, 3), bool)],          # empty group
            3: [rng.random((3, 3)) < 0.7, np.ones((1, 4), bool)]}


def _trace(grids, steps, seed=7):
    """Frames with per-camera static repeats sprinkled in."""
    rng = np.random.default_rng(seed)
    out, prev = [], None
    for s in range(steps):
        f = {}
        for gid, gs in grids.items():
            f[gid] = [prev[gid][i] if (s > 0 and (s + gid + i) % 3 == 0)
                      else rng.random((g.shape[0] * 8, g.shape[1] * 8, 3)
                                      ).astype(np.float32)
                      for i, g in enumerate(gs)]
        prev = f
        out.append(f)
    return out


def test_mesh1_bit_identical_with_dispatch_ceiling(small_det, ragged_grids):
    """The sharded step on a 1-device mesh reproduces
    ``superlaunch_forward_reuse`` bit for bit over a ragged trace (cold
    start, warm deltas, static repeats, an empty group) while
    ``sharded_fleet_step`` asserts the 1-gate + ≤3-conv per-shard
    dispatch structure every step."""
    det, grids = small_det, ragged_grids
    rt = ShardedSuperlaunch(det, grids, make_fleet_mesh(1))
    scache = rt.make_cache()
    pcache = PackedActivationCache()
    for f in _trace(grids, 5):
        ref, _ = det.superlaunch_forward_reuse(f, grids, pcache, 0.0)
        got, counts, stats = sharded_fleet_step(rt, f, scache, 0.0)
        assert counts["tile_delta_gate"] == 1
        assert sum(v for k, v in counts.items()
                   if k != "tile_delta_gate") <= 3
        for gid in grids:
            for i in range(len(grids[gid])):
                np.testing.assert_array_equal(np.asarray(ref[gid][i]),
                                              got[gid][i])
    assert scache.steps == 5 and scache.cold_steps == 1
    assert 0 < scache.compute_fraction


def test_mesh1_all_static_step_is_gate_only(small_det, ragged_grids):
    det, grids = small_det, ragged_grids
    rt = ShardedSuperlaunch(det, grids, make_fleet_mesh(1))
    cache = rt.make_cache()
    f = _trace(grids, 1)[0]
    sharded_fleet_step(rt, f, cache, 0.0)
    _, counts, stats = sharded_fleet_step(rt, f, cache, 0.0)  # same frames
    assert stats.computed == 0 and stats.k_max == 0
    assert dict(counts) == {"tile_delta_gate": 1}
    assert stats.canvas_bytes == 0 and cache.canvas_bytes_last == 0


def test_mesh1_step_full_matches_superlaunch(small_det, ragged_grids):
    det, grids = small_det, ragged_grids
    rt = ShardedSuperlaunch(det, grids, make_fleet_mesh(1))
    f = _trace(grids, 1)[0]
    ref = det.superlaunch_forward(f, grids)
    got = rt.step_full(f)
    for gid in grids:
        for i in range(len(grids[gid])):
            np.testing.assert_array_equal(np.asarray(ref[gid][i]),
                                          got[gid][i])


def test_empty_fleet_launches_nothing(small_det):
    grids = {0: [np.zeros((2, 2), bool)], 1: [np.zeros((1, 3), bool)]}
    rt = ShardedSuperlaunch(small_det, grids, make_fleet_mesh(1))
    cache = rt.make_cache()
    f = {0: [np.zeros((16, 16, 3), np.float32)],
         1: [np.zeros((8, 24, 3), np.float32)]}
    got, counts, stats = sharded_fleet_step(rt, f, cache, 0.0)
    assert dict(counts) == {} and stats.total_tiles == 0
    assert got[0][0].shape == (16, 16, small_det.head.shape[-1])
    assert not got[0][0].any()


def test_async_pipeline_bit_identical_and_overlapped(small_det,
                                                     ragged_grids):
    """Pipelined submits return the same bits as the synchronous path
    and actually overlap host planning with in-flight device steps."""
    det, grids = small_det, ragged_grids
    rt = ShardedSuperlaunch(det, grids, make_fleet_mesh(1))
    pipe = AsyncShardedPipeline(rt, rt.make_cache())
    pcache = PackedActivationCache()
    trace = _trace(grids, 6)
    for f in trace:
        pipe.submit(f)
    outs = pipe.drain()
    assert [s for s, _, _ in outs] == list(range(6))
    for (sid, got, _), f in zip(outs, trace):
        ref, _ = det.superlaunch_forward_reuse(f, grids, pcache, 0.0)
        for gid in grids:
            for i in range(len(grids[gid])):
                np.testing.assert_array_equal(np.asarray(ref[gid][i]),
                                              got[gid][i])
    # every submit after the first plans while a step is in flight
    assert pipe.overlap_fraction > 0.5
    assert len(pipe.latencies) == 6 and pipe.p99_latency_s > 0


# ---------------------------------------------------------------------------
# per-shard drift invalidation
# ---------------------------------------------------------------------------

class _FakeCam:
    def __init__(self, cam_id):
        self.cam_id = cam_id


class _FakeAdapter:
    """The DriftAdapter listener surface (add_mask_listener + cam_grids
    + cameras), minus the drift monitor."""

    def __init__(self, grids):
        self.cameras = [_FakeCam(i) for i in range(len(grids))]
        self.cam_grids = {i: g.copy() for i, g in enumerate(grids)}
        self._fns = []

    def add_mask_listener(self, fn):
        self._fns.append(fn)

    def resolve(self):                     # a mask mutation lands
        for fn in self._fns:
            fn(self)


def test_invalidation_targets_exactly_the_owning_shard():
    grids = [[np.ones((1, 3), bool)], [np.ones((1, 5), bool)],
             [np.ones((1, 4), bool)]]
    plan = ops.shard_plan(grids, 2)
    cache = ShardedActivationCache(plan, gids=[10, 11, 12])
    cache.valid[:] = True
    adapters = {11: _FakeAdapter(grids[1])}
    wire_shard_invalidation(adapters, cache)
    adapters[11].resolve()
    owner = cache.owner_shard(11)
    assert not cache.valid[owner]
    assert cache.valid[1 - owner]
    assert cache.shard_invalidations[owner] == 1
    assert cache.shard_invalidations[1 - owner] == 0
    cache.invalidate()                      # fleet-wide listener form
    assert not cache.valid.any()


def test_rebuild_group_keeps_bits_and_recomputes_cold(small_det,
                                                      ragged_grids):
    """A re-solve that grows one group's mask rebuilds the sharded
    tables, forces exactly one cold recompute, and the next step is
    bit-identical to the plain super-launch on the NEW grids."""
    det = small_det
    grids = {g: [a.copy() for a in gs] for g, gs in ragged_grids.items()}
    rt = ShardedSuperlaunch(det, grids, make_fleet_mesh(1))
    cache = rt.make_cache()
    trace = _trace(grids, 3)
    for f in trace[:2]:
        sharded_fleet_step(rt, f, cache, 0.0)
    ad = _FakeAdapter(grids[1])
    wire_shard_invalidation({1: ad}, cache, runtime=rt)
    ad.cam_grids[0][:] = True               # the re-solved (grown) mask
    ad.resolve()
    assert not cache.valid[cache.owner_shard(1)]
    assert rt.grids[1][0].all()
    new_grids = {**grids, 1: [ad.cam_grids[0]]}
    got, counts, stats = sharded_fleet_step(rt, trace[2], cache, 0.0)
    assert stats.cold_shards == 1
    ref = det.superlaunch_forward(trace[2], new_grids)
    gid = 1
    for i in range(len(new_grids[gid])):
        np.testing.assert_array_equal(np.asarray(ref[gid][i]),
                                      got[gid][i])


# ---------------------------------------------------------------------------
# per-context kernel counters under concurrency (satellite: ops counters)
# ---------------------------------------------------------------------------

def test_count_kernels_regions_are_thread_isolated():
    """Concurrent count_kernels regions never see each other's
    dispatches (the contextvar stack is per-thread), a main-thread
    region never absorbs worker bumps, and the global counter sees
    everything — the invariants the async sharded pipeline and
    subprocess-free concurrent benches rely on."""
    ops.KERNEL_COUNTS.clear()
    errs, done = [], []
    gate = threading.Barrier(4)
    # distinct CANONICAL names (record_dispatch validates against
    # obs.metrics.KERNEL_NAMES), one per worker thread
    names = ["sbnet_gather", "roi_conv_stage", "tile_delta", "roi_attention"]

    def worker(name, n):
        try:
            with ops.count_kernels() as region:
                gate.wait(timeout=30)     # all regions live at once
                for _ in range(n):
                    ops.record_dispatch(name)
            assert dict(region) == {name: n}, region
            done.append(name)
        except Exception as e:            # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(names[i], 50 + i))
          for i in range(4)]
    with ops.count_kernels() as outer:
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    assert not errs and len(done) == 4
    # worker regions are invisible to the main thread's region...
    assert dict(outer) == {}
    # ...but the global counter accumulated every thread's dispatches
    for i in range(4):
        assert ops.KERNEL_COUNTS[names[i]] == 50 + i


# ---------------------------------------------------------------------------
# per-camera gate thresholds (satellite: rate-controller schedule)
# ---------------------------------------------------------------------------

def test_per_camera_thresholds_gate_only_shedded_cameras(small_det):
    """A raised per-camera threshold suppresses relaunches for small
    deltas on THAT camera only; threshold-0 cameras keep the exact
    gate."""
    det = small_det
    grids = [np.ones((2, 2), bool), np.ones((2, 2), bool)]
    rng = np.random.default_rng(3)
    f0 = [rng.random((16, 16, 3)).astype(np.float32) for _ in range(2)]
    cache = PackedActivationCache()
    det.fleet_forward_reuse(f0, grids, cache, 0.0)
    # tiny per-pixel nudge on both cameras; cam 1 gets a huge threshold
    f1 = [f + np.float32(1e-3) for f in f0]
    thr = np.array([0.0, 1e9])
    _, stats = det.fleet_forward_reuse(f1, grids, cache, thr)
    assert stats.raw_changed == 4          # only cam 0's tiles relaunch
    # schedule shape: quality 1.0 keeps the exact gate, shedding raises
    q = np.array([[1.0, 1.0], [0.5, 0.9]])
    sched = gate_threshold_schedule(q, tile=8, n_channels=3)
    assert sched[0] == 0.0 and sched[1] > 0.0


# ---------------------------------------------------------------------------
# straggler fold gating (satellite: capture-segment references)
# ---------------------------------------------------------------------------

def test_straggler_fold_capture_gating_launches_fewer_tiles(small_det):
    """Folded late segments gated against their CAPTURE-segment
    reference (capture-order waves) launch no more tiles than gating
    them against the already-advanced current reference."""
    det = small_det
    grids = [np.ones((2, 2), bool) for _ in range(3)]
    rng = np.random.default_rng(2)
    base = [rng.random((16, 16, 3)).astype(np.float32) for _ in range(3)]

    def frame(cam, t):
        f = base[cam].copy()
        f[(t % 3) * 4:(t % 3) * 4 + 4] += 0.5      # small moving stripe
        return f

    def run(fold_gate):
        gf = DeadlineGroupFormer(det, [0, 1, 2], deadline_s=0.5,
                                 reuse_cache=PackedActivationCache(),
                                 fold_gate=fold_gate)
        t, rels = 0.0, []
        for step in range(6):
            if step % 2 == 1:         # cam 2 catches up with TWO segments
                for tt in (step - 1, step):
                    r = gf.offer(t, 2, frame(2, tt), grids[2])
                    t += 0.01
                    if r:
                        rels.append(r)
            for cam in (0, 1):
                r = gf.offer(t, cam, frame(cam, step), grids[cam])
                t += 0.01
                if r:
                    rels.append(r)
            if step % 2 == 0:
                r = gf.poll(t + 1.0)  # deadline fires without cam 2
                if r:
                    rels.append(r)
        return gf, sum(r.folded_frames for r in rels)

    gf_cap, folded_cap = run("capture")
    gf_cur, folded_cur = run("current")
    assert folded_cap == folded_cur > 0
    assert gf_cap.reclaimed_launches > 0
    assert gf_cap.reuse_launched_tiles < gf_cur.reuse_launched_tiles
    with pytest.raises(ValueError):
        DeadlineGroupFormer(det, [0], 0.1, fold_gate="bogus")


# ---------------------------------------------------------------------------
# multi-shard subprocess: bit-exactness + warm-shard survival (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_two_shard_bit_exact_and_warm_survival():
    _run("""
        import numpy as np, jax
        from repro.serving.detector import (RoIDetector, DetectorConfig,
                                            PackedActivationCache)
        from repro.fleet import sharded_fleet_step
        from repro.fleet.sharded import ShardedSuperlaunch
        from repro.launch.mesh import make_fleet_mesh

        assert len(jax.devices()) == 2
        rng = np.random.default_rng(0)
        det = RoIDetector(DetectorConfig(tile=8, channels=(4, 6)),
                          jax.random.PRNGKey(0))
        grids = {0: [rng.random((3, 4)) < 0.6, rng.random((2, 2)) < 0.9],
                 1: [rng.random((4, 3)) < 0.5],
                 2: [np.zeros((2, 3), bool)],
                 3: [rng.random((3, 3)) < 0.7, np.ones((1, 4), bool)]}
        mesh = make_fleet_mesh(2)
        rt = ShardedSuperlaunch(det, grids, mesh)
        assert len(set(rt.plan.assignment)) == 2
        cache = rt.make_cache()
        pc = PackedActivationCache()
        prev = None
        for step in range(4):
            f = {}
            for gid, gs in grids.items():
                f[gid] = [prev[gid][i]
                          if (step > 0 and (step + gid + i) % 3 == 0)
                          else rng.random((g.shape[0] * 8,
                                           g.shape[1] * 8, 3)
                                          ).astype(np.float32)
                          for i, g in enumerate(gs)]
            prev = f
            ref, _ = det.superlaunch_forward_reuse(f, grids, pc, 0.0)
            got, counts, stats = sharded_fleet_step(rt, f, cache, 0.0)
            assert counts["tile_delta_gate"] == 1
            assert sum(v for k, v in counts.items()
                       if k != "tile_delta_gate") <= 3
            for gid in grids:
                for i in range(len(grids[gid])):
                    assert np.array_equal(np.asarray(ref[gid][i]),
                                          got[gid][i]), (step, gid, i)
        # invalidate one group: only its shard goes cold next step
        gid = 1
        cache.invalidate_group(gid)
        f = prev
        _, _, stats = sharded_fleet_step(rt, f, cache, 0.0)
        assert stats.cold_shards == 1
        print("2-shard OK")
        """)
