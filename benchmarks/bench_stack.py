"""One-launch fleet backbone benchmark: cross-group super-launch
dispatch ceiling, halo fetch structure, and straggler fold-in.

Three panels:

  1. dispatch structure — one fleet step over K groups runs in ≤3 Pallas
     dispatches (entry + layer-stack megakernel + scatter); each group's
     heads bit-identical to ``fleet_forward`` on that group alone.
  2. halo fetch structure — per tile per layer the megakernel fetches 8
     halo edges and DMAs a block's centers in one copy.
  3. straggler fold — a scripted deadline former: late segments ride the
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


def run(verbose: bool = True, quick: bool = False):
    t00 = time.time()
    det = RoIDetector(DetectorConfig(), jax.random.PRNGKey(0))
    t = det.cfg.tile
    n_layers = det.num_conv_layers
    K = 2 if quick else 4
    cams = 5
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
    max_diff = 0.0
    for gid in range(K):
        alone = det.fleet_forward(frames[gid], grids[gid])
        for a, b in zip(outs[gid], alone):
            max_diff = max(max_diff,
                           float(jnp.abs(a - b).max()))

    # --- panel 2: halo fetch structure -------------------------------------
    # the megakernel DMAs each block's centers in ONE copy and each
    # tile's 8 neighbor edges from the zero-row-padded activations.
    # Counted from the kernel source so a change of the fetch structure
    # changes the panel instead of silently reporting stale constants.
    import inspect
    from repro.kernels import roi_conv as roi_conv_mod
    fetch_src = inspect.getsource(roi_conv_mod._window_fetches)
    assert "ks=tuple(range(8))" in fetch_src
    stack_halo = len(roi_conv_mod.NEIGHBOR_OFFSETS)
    flat_grids = [g for gid in range(K) for g in grids[gid]]
    n_tiles = int(det._fleet_tables(flat_grids)[0].shape[0])
    tb = max(1, min(det.block, n_tiles))   # the detector's stack block
    layers = max(n_layers - 1, 0)
    halo_dmas_fused = stack_halo * n_tiles * layers
    center_dmas_fused = -(-n_tiles // tb) * layers

    # --- panel 3: straggler fold-in ----------------------------------------
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
        "launch_counts": {k: int(v) for k, v in counts.items()},
        "superlaunch_vs_per_group_max_abs_diff": max_diff,
        "stack_halo_dmas_per_tile": stack_halo,
        "halo_dmas_fused": halo_dmas_fused,
        "center_dmas_fused": center_dmas_fused,
        "fold_reclaimed_launches": former.reclaimed_launches,
        "fold_folded_frames": folded_frames,
        "fold_total_launches": int(fold_launches),
        "wall_s": time.time() - t00,
    }
    if verbose:
        rows = [
            ["dispatches / fleet step", str(superlaunch_dispatches)],
            ["halo fetches per tile", str(stack_halo)],
            ["halo fetches", str(halo_dmas_fused)],
            ["center fetches", str(center_dmas_fused)],
        ]
        print(f"== one-launch fleet backbone: {K} groups x {cams} cams, "
              f"{n_layers} conv layers, {n_tiles} tiles ==")
        print(table(rows, ["metric", "super-launch"]))
        print(f"super-launch vs per-group max |diff|: {max_diff:.1e} "
              f"(bit-identical)")
        print(f"straggler fold: {former.reclaimed_launches} launch "
              f"chain(s) reclaimed, {folded_frames} folded frame(s), "
              f"{fold_launches} total launches in the scripted window")
    save_json("bench_stack.json", payload)
    return payload


if __name__ == "__main__":
    run()
