"""Persistent output canvas: changed-only scatter == composite scatter
(property, incl. empty/full extremes and a drift re-solve shrinking the
active set mid-sequence), canvas-resident references held to a model of
private per-tile reference windows at every threshold, zero-copy
all-static steps, per-tile epoch tracking, and per-tile-class gate
thresholds."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.fleet import sharded_fleet_step
from repro.fleet.runtime import fleet_reuse_step
from repro.fleet.sharded import ShardedSuperlaunch
from repro.kernels import ops, ref
from repro.launch.mesh import make_fleet_mesh
from repro.serving.detector import (DetectorConfig, N_TILE_CLASSES,
                                    PackedActivationCache, RoIDetector,
                                    TILE_CLASS_BODY, TILE_CLASS_HALO,
                                    gate_changed_rows, ref_advance_rows,
                                    tile_class_rows)


def _rng(seed):
    return np.random.default_rng(seed)


def _ragged_fleet_idx(rng, t):
    """A ragged multi-camera fleet: per-camera grid shapes differ, the
    shared canvas is sized at the maxima.  Returns (idx (n, 3) int32,
    canvas shape (C, H, W))."""
    n_cams = int(rng.integers(2, 5))
    shapes = [(int(rng.integers(1, 4)), int(rng.integers(1, 4)))
              for _ in range(n_cams)]
    rows = []
    for cam, (gy, gx) in enumerate(shapes):
        g = rng.random((gy, gx)) < 0.7
        g[0, 0] = True                          # never an empty camera
        for ty in range(gy):
            for tx in range(gx):
                if g[ty, tx]:
                    rows.append((cam, ty, tx))
    H = max(gy for gy, _ in shapes) * t
    W = max(gx for _, gx in shapes) * t
    return np.asarray(rows, np.int32), (n_cams, H, W)


# ---------------------------------------------------------------------------
# property: changed-only scatter == full composite scatter, bit for bit
# ---------------------------------------------------------------------------

@given(st.integers(0, 10 ** 6))
@settings(max_examples=25, deadline=None)
def test_changed_scatter_matches_composite_property(seed):
    """For any ragged fleet and any changed subset (empty and full
    included), scattering ONLY the changed rows onto the previous
    canvas is bit-identical to composite-scattering the full updated
    tile set into zeros — the unchanged-tile passthrough contract."""
    rng = _rng(seed)
    t, A = 4, 3
    idx, (C, H, W) = _ragged_fleet_idx(rng, t)
    n = idx.shape[0]
    zeros = jnp.zeros((C, H, W, A), jnp.float32)
    heads_old = rng.normal(size=(n, t, t, A)).astype(np.float32)
    canvas_old = ops.sbnet_scatter_fleet(jnp.asarray(heads_old),
                                         jnp.asarray(idx), zeros)
    # changed subset: forced empty / full on some seeds, random otherwise
    if seed % 5 == 0:
        changed = np.zeros(n, bool)
    elif seed % 5 == 1:
        changed = np.ones(n, bool)
    else:
        changed = rng.random(n) < rng.uniform(0.1, 0.9)
    heads_new = heads_old.copy()
    heads_new[changed] = rng.normal(
        size=(int(changed.sum()), t, t, A)).astype(np.float32)
    with ops.count_kernels() as c:
        inc = ops.sbnet_scatter_changed(jnp.asarray(heads_new[changed]),
                                        jnp.asarray(idx[changed]),
                                        canvas_old)
    full = ops.sbnet_scatter_fleet(jnp.asarray(heads_new),
                                   jnp.asarray(idx), zeros)
    np.testing.assert_array_equal(np.asarray(inc), np.asarray(full))
    if not changed.any():
        # empty compute set: ZERO dispatches, the canvas passes through
        assert sum(c.values()) == 0, dict(c)
        assert inc is canvas_old
    else:
        assert c["sbnet_scatter_changed"] == 1, dict(c)


def test_empty_compute_set_short_circuits_to_zero_dispatches():
    """``sbnet_scatter_changed``/``sbnet_scatter_fleet``/
    ``roi_conv_entry`` with an empty row set launch NOTHING — no
    dispatch recorded, no kernel built."""
    t, A = 4, 3
    base = jnp.ones((2, 2 * t, 2 * t, A), jnp.float32)
    x = jnp.zeros((2, 2 * t, 2 * t, 3), jnp.float32)
    w = jnp.zeros((3, 3, 3, A), jnp.float32)
    empty_rows = jnp.zeros((0, t, t, A), jnp.float32)
    empty_idx = jnp.zeros((0, 3), jnp.int32)
    with ops.count_kernels() as c:
        out_ch = ops.sbnet_scatter_changed(empty_rows, empty_idx, base)
        out_fl = ops.sbnet_scatter_fleet(empty_rows, empty_idx, base)
        out_cv = ops.roi_conv_entry(x, w, empty_idx, t, t)
    assert sum(c.values()) == 0, dict(c)
    assert out_ch is base and out_fl is base
    assert out_cv.shape == (0, t, t, A)


# ---------------------------------------------------------------------------
# canvas-resident references == private per-tile windows, every threshold
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def det():
    return RoIDetector(DetectorConfig(tile=8, channels=(4, 6)),
                       jax.random.PRNGKey(0))


def _mk_fleet(rng, t, spec):
    """spec: {gid: [grid shapes]} -> (frames, grids)."""
    grids, frames = {}, {}
    for gid, shapes in spec.items():
        gs, fs = [], []
        for gy, gx in shapes:
            g = rng.random((gy, gx)) < 0.7
            g[0, 0] = True
            gs.append(g)
            fs.append(rng.normal(size=(gy * t, gx * t, 3)
                                 ).astype(np.float32))
        grids[gid], frames[gid] = gs, fs
    return frames, grids


def _as_jnp(frames):
    return {g: [jnp.asarray(f) for f in fs] for g, fs in frames.items()}


class _PrivateWindowGate:
    """The gate's reference semantics in numpy: every active tile keeps
    a PRIVATE copy of its haloed (t+2, t+2, 3) entry window as of its
    last refresh, priced with ``ref.tile_delta_gate`` (column 4, the
    exact change count, at threshold <= 0; column 5, the window byte
    estimate, above), and advanced when the tile refreshes: every tile
    at threshold <= 0, else the changed-output tiles.  The changed set
    grows by the receptive field's rings over each camera's tile grid,
    and as many again for the compute margin."""

    def __init__(self, grids, t, rings):
        self.grids, self.t, self.rings = grids, t, rings
        self.idx, _ = ops.fleet_indices(grids)
        self.pos = {tuple(r): i for i, r in enumerate(self.idx.tolist())}
        self.win = None

    def _stack(self, frames):
        t = self.t
        h = max(max(f.shape[0] for f in frames),
                max(g.shape[0] * t for g in self.grids))
        w = max(max(f.shape[1] for f in frames),
                max(g.shape[1] * t for g in self.grids))
        x = np.zeros((len(frames), h, w, 3), np.float32)
        for c, f in enumerate(frames):
            x[c, :f.shape[0], :f.shape[1]] = f
        return x

    def _windows(self, x):
        t = self.t
        xp = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
        return np.stack([xp[c, ty * t:ty * t + t + 2, tx * t:tx * t + t + 2]
                         for c, ty, tx in self.idx])

    def _grow(self, rows, times):
        out = rows.copy()
        for _ in range(times):
            grown = out.copy()
            for i, (c, ty, tx) in enumerate(self.idx.tolist()):
                if out[i]:
                    for dy in (-1, 0, 1):
                        for dx in (-1, 0, 1):
                            j = self.pos.get((c, ty + dy, tx + dx))
                            if j is not None:
                                grown[j] = True
            out = grown
        return out

    def step(self, frames, threshold):
        """frames: the flat camera list -> (raw_changed, changed_out,
        computed) of a warm step, or None on the cold (first) step."""
        x = self._stack(frames)
        cur = self._windows(x)
        if self.win is None:
            self.win = cur
            return None
        t = self.t
        stats = np.zeros((len(self.idx), 8), np.int64)
        for i, (c, ty, tx) in enumerate(self.idx):
            # the private window as a one-tile "previous frame": the
            # current frame with this tile's window region replaced
            prev = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
            prev[c, ty * t:ty * t + t + 2, tx * t:tx * t + t + 2] = \
                self.win[i]
            stats[i] = ref.tile_delta_gate(x, prev[:, 1:-1, 1:-1],
                                           self.idx[i:i + 1], t, t)[0]
        raw = (stats[:, 4] > 0 if threshold <= 0
               else stats[:, 5] > threshold)
        changed = self._grow(raw, self.rings)
        compute = self._grow(changed, self.rings)
        adv = np.ones_like(raw) if threshold <= 0 else changed
        self.win[adv] = cur[adv]
        return int(raw.sum()), int(changed.sum()), int(compute.sum())


@pytest.mark.parametrize("threshold", [0.0, 40.0, 1e9])
def test_ref_modes_bit_equal_at_every_threshold(det, threshold):
    """The canvas-resident references + epoch tracking gate exactly as
    private per-tile reference windows would (``_PrivateWindowGate``) at
    exact (0), lossy (40 bytes) and everything-reused (1e9) thresholds,
    over a trace whose motion stays in tile interiors (the regime where
    a shared canvas and private windows are defined to agree) plus
    all-static repeats.  At 0 the heads equal a full recompute; at 1e9
    they stay the cold step's."""
    t = det.cfg.tile
    rng = _rng(3)
    frames, grids = _mk_fleet(rng, t, {0: [(3, 4), (2, 2)], 1: [(4, 3)]})
    cache = PackedActivationCache()
    model = _PrivateWindowGate([g for gs in grids.values() for g in gs], t,
                               ops.halo_rings(det.layers, t))
    cur = frames
    cold_heads = None
    for step in range(8):
        if step % 3 == 2:
            pass                                # all-static repeat
        else:
            cur = {g: [f.copy() for f in fs] for g, fs in cur.items()}
            gid = int(rng.integers(2))
            f = cur[gid][0]
            # an active tile of a few, so that bumps land on tiles again
            # and sub-threshold drift accumulates against a reference
            act = np.argwhere(grids[gid][0])[:4]
            ty, tx = act[int(rng.integers(len(act)))]
            # interior bump: the tile's rim pixels stay bit-static; its
            # window byte estimate lands on both sides of 40 bytes
            f[ty * t + 2:ty * t + t - 2,
              tx * t + 2:tx * t + t - 2, :] += 4 * \
                rng.normal(size=(t - 4, t - 4, 3)).astype(np.float32)
        got, _, st = fleet_reuse_step(det, _as_jnp(cur), grids, cache,
                                      threshold)
        expect = model.step([f for fs in cur.values() for f in fs],
                            threshold)
        if expect is None:
            assert st.cold
            cold_heads = {g: [np.asarray(h) for h in hs]
                          for g, hs in got.items()}
            continue
        assert (st.raw_changed, st.changed_out, st.computed) == expect
        if threshold == 0.0:
            # threshold 0 == full recompute, bit for bit
            for gid in grids:
                full = det.fleet_forward(
                    [jnp.asarray(f) for f in cur[gid]], grids[gid])
                for a, b in zip(got[gid], full):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
        if threshold == 1e9:
            assert st.computed == 0
            for gid in grids:
                for a, b in zip(got[gid], cold_heads[gid]):
                    np.testing.assert_array_equal(np.asarray(a), b)


def test_epoch_tracking_advances_only_refreshed_tiles(det):
    t = det.cfg.tile
    rng = _rng(4)
    frames, grids = _mk_fleet(rng, t, {0: [(3, 3)]})
    cache = PackedActivationCache()
    fleet_reuse_step(det, _as_jnp(frames), grids, cache)  # cold seed
    assert (cache.epoch_np == 0).all()
    # scalar threshold 0: every reference advances every step
    fleet_reuse_step(det, _as_jnp(frames), grids, cache, 0.0)
    full_epoch = cache.steps
    assert (cache.epoch_np == full_epoch).all()
    # lossy threshold, one changed tile: ONLY its epoch moves
    thr = 40.0
    cur = {0: [frames[0][0].copy()]}
    cur[0][0][2:t - 2, 2:t - 2, :] += 50.0                # tile (0, 0)
    _, _, st = fleet_reuse_step(det, _as_jnp(cur), grids, cache, thr)
    moved = cache.epoch_np == cache.steps
    kept = cache.epoch_np == full_epoch
    assert 1 <= st.raw_changed <= st.changed_out < st.total_tiles
    # refreshed rows == the dilated changed-OUTPUT set, nothing more
    assert moved.sum() == st.changed_out and kept.sum() == \
        cache.epoch_np.size - st.changed_out
    # all-static step under the lossy gate: no epoch moves, 0 bytes
    _, counts, st2 = fleet_reuse_step(det, _as_jnp(cur), grids, cache,
                                      thr)
    assert st2.computed == 0 and st2.canvas_bytes == 0
    assert not (cache.epoch_np == cache.steps).any()


def test_canvas_bytes_proportional_to_changed(det):
    t = det.cfg.tile
    rng = _rng(5)
    frames, grids = _mk_fleet(rng, t, {0: [(4, 4)]})
    cache = PackedActivationCache()
    _, _, st0 = fleet_reuse_step(det, _as_jnp(frames), grids, cache)
    tile_bytes = t * t * int(det.head.shape[-1]) * 4
    assert st0.canvas_bytes == st0.total_tiles * tile_bytes  # cold seed
    cur = {0: [frames[0][0].copy()]}
    cur[0][0][1, 1, :] += 9.0
    _, _, st1 = fleet_reuse_step(det, _as_jnp(cur), grids, cache)
    assert 0 < st1.canvas_bytes == st1.changed_out * tile_bytes
    assert st1.canvas_bytes < st0.canvas_bytes
    assert cache.canvas_bytes_total == st0.canvas_bytes + st1.canvas_bytes


# ---------------------------------------------------------------------------
# per-tile-class gate thresholds
# ---------------------------------------------------------------------------

def test_tile_class_rows_body_vs_halo():
    g = np.ones((3, 3), bool)
    _, nbr, _, _ = ops.superlaunch_tables([[g]])
    cls = tile_class_rows(np.asarray(nbr))
    assert cls.shape == (9,)
    assert set(np.unique(cls)) <= {TILE_CLASS_BODY, TILE_CLASS_HALO}
    assert (cls == TILE_CLASS_BODY).sum() == 1      # only the center
    assert (cls == TILE_CLASS_HALO).sum() == 8      # the boundary ring


def test_per_tile_class_thresholds_route_by_class():
    """A (C, 2) [body, halo] threshold table gates body and halo rows
    against different bars, and ``ref_advance_rows`` follows the same
    split; 2-D thresholds without a class vector are rejected."""
    stats = np.zeros((4, 8), np.int64)
    stats[:, 5] = 100                       # GATE_WIN_BYTES estimate
    cam = np.zeros(4, np.int64)
    cls = np.array([TILE_CLASS_BODY, TILE_CLASS_BODY,
                    TILE_CLASS_HALO, TILE_CLASS_HALO])
    thr = np.array([[1e6, 10.0]])           # body never, halo always
    changed = gate_changed_rows(stats, thr, cam, cls)
    np.testing.assert_array_equal(changed,
                                  [False, False, True, True])
    adv = ref_advance_rows(thr, cam, changed, cls)
    np.testing.assert_array_equal(adv, changed)
    # exact gate for one class: its rows advance regardless of change
    thr0 = np.array([[0.0, 1e6]])
    adv0 = ref_advance_rows(thr0, cam, np.zeros(4, bool), cls)
    np.testing.assert_array_equal(adv0, [True, True, False, False])
    with pytest.raises(ValueError):
        gate_changed_rows(stats, thr, cam, None)
    assert thr.shape[1] == N_TILE_CLASSES


# ---------------------------------------------------------------------------
# drift re-solve shrinking the active set mid-sequence
# ---------------------------------------------------------------------------

def test_drift_shrink_does_not_leak_stale_canvas(det):
    """Mid-sequence a re-solve SHRINKS one group's mask.  The removed
    tiles' canvas bytes must not leak into served heads on either path:
    the single-device cache reseeds on the key change; the sharded
    runtime wipes the owning shard's canvas plane."""
    t = det.cfg.tile
    rng = _rng(6)
    frames, grids = _mk_fleet(rng, t, {0: [(3, 4)], 1: [(3, 3)]})
    # single-device: key change -> cold reseed on the new grids
    cache = PackedActivationCache()
    fleet_reuse_step(det, _as_jnp(frames), grids, cache)
    fleet_reuse_step(det, _as_jnp(frames), grids, cache)
    small = {0: [grids[0][0].copy()], 1: [g.copy() for g in grids[1]]}
    small[0][0][1:, :] = False                  # drop most of group 0
    small[0][0][0, 0] = True
    got, _, st = fleet_reuse_step(det, _as_jnp(frames), small, cache)
    assert st.cold
    legacy = det.fleet_forward(
        [jnp.asarray(f) for f in frames[0]], small[0])
    np.testing.assert_array_equal(np.asarray(got[0][0]),
                                  np.asarray(legacy[0]))
    # a removed tile's head region is exactly zero (no stale bytes)
    assert (np.asarray(got[0][0])[2 * t:3 * t, :t] == 0).all()

    # sharded: rebuild_group + shard-exact canvas invalidation, then the
    # changed-only scatter keeps matching the full-recompute reference
    rt = ShardedSuperlaunch(det, grids, make_fleet_mesh(1))
    scache = rt.make_cache()
    sharded_fleet_step(rt, frames, scache, 0.0)
    sharded_fleet_step(rt, frames, scache, 0.0)
    scache.invalidate_group(0)
    rt.rebuild_group(0, small[0], scache)
    new_grids = {0: small[0], 1: grids[1]}
    got_s, _, stats = sharded_fleet_step(rt, frames, scache, 0.0)
    ref = det.superlaunch_forward(frames, new_grids)
    for gid in new_grids:
        for i in range(len(new_grids[gid])):
            np.testing.assert_array_equal(np.asarray(ref[gid][i]),
                                          got_s[gid][i])
    assert (got_s[0][0][2 * t:3 * t, :t] == 0).all()
    # ...and warm steps after the shrink stay bit-exact too
    cur = {g: [f.copy() for f in fs] for g, fs in frames.items()}
    cur[1][0][2:t - 2, 2:t - 2, :] += 7.0
    got_s2, counts, _ = sharded_fleet_step(rt, cur, scache, 0.0)
    ref2 = det.superlaunch_forward(cur, new_grids)
    for gid in new_grids:
        for i in range(len(new_grids[gid])):
            np.testing.assert_array_equal(np.asarray(ref2[gid][i]),
                                          got_s2[gid][i])
    assert counts.get("sbnet_scatter_changed", 0) == 1, dict(counts)
