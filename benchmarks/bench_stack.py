"""One-launch fleet backbone benchmark: fused megakernel vs per-layer
chain, cross-group super-launch dispatch ceiling, halo fetch structure,
and straggler fold-in.

Four panels:

  1. dispatch structure — one fleet step over K groups runs in ≤3 Pallas
     dispatches (entry + layer-stack megakernel + scatter) vs the
     per-group per-layer chain's K×(N+1); outputs bit-identical.
  2. wall clock (interpret mode) — the fused ``roi_conv_stack`` launch vs
     the N-1 ``roi_conv_packed`` dispatches it replaces, and the whole
     super-launch step vs the per-group chain loop (min over reps,
     post-warmup).
  3. halo fetch structure — per tile per layer both paths fetch 8 halo
     edges; the fused path DMAs a block's centers in one copy.
  4. straggler fold — a scripted deadline former: late segments ride the
     next release's packed launch; reclaimed launch chains counted.

``quick=True`` is the CI smoke shape (2 groups).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_json, table
from repro.fleet.runtime import fleet_inference_step
from repro.kernels import ops
from repro.net.batcher import DeadlineGroupFormer
from repro.serving.detector import DetectorConfig, RoIDetector


def _block(out):
    jax.tree_util.tree_map(
        lambda a: a.block_until_ready() if hasattr(
            a, "block_until_ready") else a, out)


def _time_min_interleaved(fns, reps: int):
    """min-over-reps wall time per fn, A/B-interleaved so scheduler
    drift on a shared runner hits both sides equally."""
    best = [float("inf")] * len(fns)
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            _block(fn())
            best[i] = min(best[i], time.perf_counter() - t0)
    return best


def run(verbose: bool = True, quick: bool = False):
    t00 = time.time()
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    t = det.cfg.tile
    n_layers = det.num_conv_layers
    K = 2 if quick else 4
    cams = 5
    reps = 3 if quick else 5
    rng = np.random.default_rng(0)
    grids = {gid: [rng.random((3, 4)) < 0.5 for _ in range(cams)]
             for gid in range(K)}
    for gs in grids.values():
        for g in gs:
            g[1, 1] = True
    frames = {gid: [jnp.asarray(rng.normal(size=(3 * t, 4 * t, 3)),
                                jnp.float32) for _ in range(cams)]
              for gid in range(K)}

    # --- panel 1: dispatch structure + bit-exactness -----------------------
    outs, counts = fleet_inference_step(det, frames, grids)
    superlaunch_dispatches = int(sum(counts.values()))
    chain_dispatches = K * (n_layers + 1)          # per-group per-layer
    max_diff = 0.0
    for gid in range(K):
        legacy = det.fleet_forward_layers(frames[gid], grids[gid])
        for a, b in zip(outs[gid], legacy):
            max_diff = max(max_diff,
                           float(jnp.abs(a - b).max()))

    # --- panel 2: wall clock (interpret mode) ------------------------------
    flat_frames = [f for gid in range(K) for f in frames[gid]]
    flat_grids = [g for gid in range(K) for g in grids[gid]]
    idx, nbr = det._fleet_tables(flat_grids)
    x, _, _ = det._stack_frames(flat_frames, flat_grids)
    ws = det.weights[1:]

    # the asserted kernel-for-kernel comparison runs on a LARGE tile set
    # so the ~20% fused margin dwarfs scheduler noise on shared runners
    big_grid = rng.random((20, 24)) < 0.5
    big_grid[1, 1] = True
    big_idx = ops.mask_to_indices(big_grid)
    big_nbr = jnp.asarray(ops.neighbor_table(big_idx, big_grid.shape))
    packed_big = jax.nn.relu(jnp.asarray(
        rng.normal(size=(big_idx.shape[0], t, t, det.cfg.channels[0])),
        jnp.float32))

    def fused_stack():
        return ops.roi_conv_stack(packed_big, ws, big_nbr)

    def chain_stack():
        p = packed_big
        for w in ws:
            p = jax.nn.relu(ops.roi_conv_packed(p, w, big_nbr))
        return p

    a, b = fused_stack(), chain_stack()            # warm both jits
    assert (np.asarray(a) == np.asarray(b)).all()
    stack_wall, chain_wall = _time_min_interleaved(
        [fused_stack, chain_stack], max(reps, 5))

    def superlaunch_step():
        return det.superlaunch_forward(frames, grids)

    def per_group_chain():
        return {gid: det.fleet_forward_layers(frames[gid], grids[gid])
                for gid in range(K)}

    superlaunch_step(), per_group_chain()          # warm
    # informational: the per-group loop touches K small buffers where the
    # super-launch touches one big one, which flatters the loop under the
    # interpreter's copy-per-ref-access semantics; the asserted comparison
    # is the megakernel vs the per-layer dispatches it replaces, on
    # identical inputs
    step_wall, per_group_wall = _time_min_interleaved(
        [superlaunch_step, per_group_chain], reps)

    # --- panel 3: halo fetch structure -------------------------------------
    # per packed layer the chain loads its tile plus 8 masked halo strips
    # per TILE; the megakernel DMAs each block's centers in ONE copy and
    # each tile's 8 neighbor edges from the zero-row-padded activations.
    # Counted from the kernel sources so a change of the fetch structure
    # changes the panel instead of silently reporting stale constants.
    import inspect
    from repro.kernels import roi_conv as roi_conv_mod
    fetch_src = inspect.getsource(roi_conv_mod._window_fetches)
    assert "for k in range(8)" in fetch_src
    stack_halo = len(roi_conv_mod.NEIGHBOR_OFFSETS)
    chain_src = inspect.getsource(roi_conv_mod.roi_conv_packed)
    chain_halo = chain_src.count("strip(") - 1     # minus the def
    n_tiles = int(idx.shape[0])
    tb = max(1, min(det.block, n_tiles))   # the detector's stack block
    layers = max(n_layers - 1, 0)
    halo_dmas_fused = stack_halo * n_tiles * layers
    halo_dmas_chain = chain_halo * n_tiles * layers
    center_dmas_fused = -(-n_tiles // tb) * layers
    center_loads_chain = n_tiles * layers

    # --- panel 4: straggler fold-in ----------------------------------------
    former = DeadlineGroupFormer(det, expected_cams=list(range(3)),
                                 deadline_s=0.5)
    g3 = [rng.random((3, 4)) < 0.5 for _ in range(3)]
    for g in g3:
        g[1, 1] = True
    mk = lambda: jnp.asarray(rng.normal(size=(3 * t, 4 * t, 3)),
                             jnp.float32)
    with ops.count_kernels() as fold_counts:
        former.offer(0.00, 0, mk(), g3[0])
        former.offer(0.05, 1, mk(), g3[1])
        former.poll(0.60)                  # deadline leaves cam 2 behind
        former.offer(0.70, 2, mk(), g3[2])     # straggler, stays queued
        former.offer(1.00, 2, mk(), g3[2])     # next segment: FOLDS
        former.offer(1.05, 0, mk(), g3[0])
        former.offer(1.10, 1, mk(), g3[1])     # completes -> one launch
    fold_launches = fold_counts["roi_conv_entry"]
    folded_frames = sum(r.folded_frames for r in former.releases)

    payload = {
        "groups": K, "cameras": K * cams, "num_conv_layers": n_layers,
        "active_tiles": n_tiles,
        "superlaunch_dispatches": superlaunch_dispatches,
        "chain_dispatches": chain_dispatches,
        "launch_counts": {k: int(v) for k, v in counts.items()},
        "fused_vs_chain_max_abs_diff": max_diff,
        "stack_kernel_wall_s": stack_wall,
        "chain_kernel_wall_s": chain_wall,
        "superlaunch_step_wall_s": step_wall,
        "per_group_chain_wall_s": per_group_wall,
        "stack_halo_dmas_per_tile": stack_halo,
        "chain_halo_loads_per_tile": chain_halo,
        "halo_dmas_fused": halo_dmas_fused,
        "halo_dmas_chain": halo_dmas_chain,
        "center_dmas_fused": center_dmas_fused,
        "center_loads_chain": center_loads_chain,
        "fold_reclaimed_launches": former.reclaimed_launches,
        "fold_folded_frames": folded_frames,
        "fold_total_launches": int(fold_launches),
        "wall_s": time.time() - t00,
    }
    if verbose:
        rows = [
            ["dispatches / fleet step", str(superlaunch_dispatches),
             str(chain_dispatches)],
            ["conv-stack wall (s)", f"{stack_wall:.4f}",
             f"{chain_wall:.4f}"],
            ["full step wall (s)", f"{step_wall:.4f}",
             f"{per_group_wall:.4f}"],
            ["halo fetches per tile", str(stack_halo), str(chain_halo)],
            ["center fetches", str(center_dmas_fused),
             str(center_loads_chain)],
        ]
        print(f"== one-launch fleet backbone: {K} groups x {cams} cams, "
              f"{n_layers} conv layers, {n_tiles} tiles ==")
        print(table(rows, ["metric", "fused", "per-layer chain"]))
        print(f"fused vs chain max |diff|: {max_diff:.1e} (bit-identical)")
        print(f"straggler fold: {former.reclaimed_launches} launch "
              f"chain(s) reclaimed, {folded_frames} folded frame(s), "
              f"{fold_launches} total launches in the scripted window")
    save_json("bench_stack.json", payload)
    return payload


if __name__ == "__main__":
    run()
