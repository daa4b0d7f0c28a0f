"""Record the scenes the benchmark's configurations serve.

    PYTHONPATH=src python bench/make_scenes.py

Each scene is one intersection of the CrossRoI evaluation setting (5
overlapping cameras: 4 x 1920x1080 and 1 x 1280x960, 10 fps, 180 s)
generated by ``repro.core.scene.generate_scene`` with a fixed traffic
profile and scene seed, and its offline RoI masks solved by
``repro.core.pipeline.run_offline`` over the 60 s profiling window.  The
recording keeps what serving needs and nothing of the program:

* ``cam_size``  (C, 2) int32   full-resolution width, height per camera
* ``offline_tile``  int        px per offline mask tile (64)
* ``grids``  (C, TY, TX) bool  offline masks, zero-padded to the largest
* ``span``  (2,) int32         recorded frames [f0, f1): the online phase
* ``boxes``  (n, 6) int32      frame, camera, x0, y0, x1, y1 of every
                               vehicle box in the span (full-res px,
                               x1/y1 exclusive)
* ``spec``  str                JSON of the scene parameters

The benchmark reads the recordings and never runs the scene generator or
the set cover itself, so a later change to either leaves the yardstick
where it is.  Rerunning this script reproduces the same arrays.
"""
import json
import math
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "scenes")

# (file name, traffic profile, scene seed)
SCENES = (("uniform_s0", "uniform", 0), ("rush_hour_s1", "rush_hour", 1),
          ("sparse_s2", "sparse", 2), ("bursty_s3", "bursty", 3))
DURATION_S = 180
PROFILE_FRAMES = 600


def record(profile: str, seed: int) -> dict:
    from repro.core.pipeline import run_offline
    from repro.core.scene import SceneConfig, generate_scene
    cfg = SceneConfig(seed=seed, spawn_profile=profile,
                      duration_s=DURATION_S)
    scene = generate_scene(cfg)
    off = run_offline(scene)
    cams = scene.cameras
    tile = cams[0].tile
    ty = max(c.tiles_y for c in cams)
    tx = max(c.tiles_x for c in cams)
    grids = np.zeros((len(cams), ty, tx), bool)
    for c in cams:
        g = np.asarray(off.cam_grids[c.cam_id], bool)
        grids[c.cam_id, :g.shape[0], :g.shape[1]] = g
    f0, f1 = PROFILE_FRAMES, cfg.num_frames
    rows = []
    for f in range(f0, f1):
        for d in scene.detections[f]:
            b = d.bbox
            rows.append((f, d.cam, math.floor(b.left), math.floor(b.top),
                         math.ceil(b.right), math.ceil(b.bottom)))
    spec = {"generator": "repro.core.scene.generate_scene",
            "masks": "repro.core.pipeline.run_offline",
            "spawn_profile": profile, "seed": seed,
            "duration_s": DURATION_S, "fps": cfg.fps,
            "spawn_rate": cfg.spawn_rate,
            "profile_frames": PROFILE_FRAMES,
            "min_box_area_px": 24 * 24}
    return {"cam_size": np.array([(c.width, c.height) for c in cams],
                                 np.int32),
            "offline_tile": np.int32(tile),
            "grids": grids,
            "span": np.array([f0, f1], np.int32),
            "boxes": np.array(rows, np.int32).reshape(-1, 6),
            "spec": np.array(json.dumps(spec, sort_keys=True))}


def main():
    os.makedirs(OUT, exist_ok=True)
    for name, profile, seed in SCENES:
        rec = record(profile, seed)
        np.savez_compressed(os.path.join(OUT, name + ".npz"), **rec)
        print(f"{name}: {rec['grids'].sum()} active offline tiles, "
              f"{rec['boxes'].shape[0]} boxes in frames "
              f"{rec['span'].tolist()}", flush=True)


if __name__ == "__main__":
    main()
