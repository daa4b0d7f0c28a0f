"""A tiny copy of the benchmark for CPU tests: the real harness files and
a small scene, configurations and cells written beside them, as a later
change would add its own."""
import json
import os
import shutil

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

# two cameras: 256x192 and 192x128 px at full resolution, 64-px offline
# tiles; at scale 0.5 and 16-px tiles that is 8x6 and 6x4 detector tiles
CAM_SIZE = [(256, 192), (192, 128)]
N_FRAMES = 6


def tiny_scene():
    grids = np.zeros((2, 3, 4), bool)
    grids[0] = [[1, 1, 1, 0], [1, 1, 1, 1], [0, 1, 1, 1]]
    grids[1, :2, :3] = [[1, 1, 0], [1, 1, 1]]
    boxes = []
    for f in range(N_FRAMES):
        # one vehicle crossing camera 0, another one camera 1
        boxes.append((f, 0, 20 + 30 * f, 40, 60 + 30 * f, 90))
        boxes.append((f, 1, 150 - 20 * f, 10, 180 - 20 * f, 50))
    return {"cam_size": np.array(CAM_SIZE, np.int32),
            "offline_tile": np.int32(64), "grids": grids,
            "span": np.array([0, N_FRAMES], np.int32),
            "boxes": np.array(boxes, np.int32),
            "spec": np.array(json.dumps({"tiny": True}))}


def tiny_config(name, entry, groups, limit=1e-5):
    return {"name": name, "source": "test", "entry": entry,
            "reference": "roi_detector",
            "groups": [{"scene": s} for s in groups], "scale": 0.5,
            "detector": {"channels": [8, 16, 16], "tile": 16,
                         "num_anchors": 2},
            "precision": "float32, matmul precision highest",
            "gate_threshold": 0.0, "check": {"head_gap_limit": limit},
            "reduced": [], "assumed": []}


def make_root(tmp, limit=1e-5):
    """A benchmark root in ``tmp``: the repository's harness and
    BENCHMARK.json, plus tiny cells ``tiny.motion`` (one device) and
    ``tiny2.motion`` (the sharded entry over one device)."""
    root = os.path.join(str(tmp), "root")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    np.savez(os.path.join(root, "bench", "scenes", "tiny.npz"),
             **tiny_scene())
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for name, entry, groups in (("tiny", "fleet_reuse", ["tiny"]),
                                ("tiny2", "sharded_fleet", ["tiny", "tiny"])):
        path = os.path.join("bench", "configs", name + ".json")
        with open(os.path.join(root, path), "w") as f:
            json.dump(tiny_config(name, entry, groups, limit), f)
        manifest["configs"].append({"name": name, "source": "test",
                                    "file": path, "reduced": [],
                                    "why": "CPU test"})
        manifest["workloads"].append({"name": name + ".motion",
                                      "config": name,
                                      "traffic": "tiny_motion", "chips": 1,
                                      "why": "CPU test"})
    with open(os.path.join(root, "bench", "traffic", "tiny_motion.json"),
              "w") as f:
        json.dump({"generator": "scene_motion", "span": [0, 4],
                   "patches": 64}, f)
    # the CPU has no entry among the chips' peaks; give the copy one so
    # the per-layer readers run (their numbers mean nothing here)
    peaks_path = os.path.join(root, "bench", "peaks.json")
    with open(peaks_path) as f:
        peaks = json.load(f)
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "hbm_bytes": 1e10, "source": "test"}
    with open(peaks_path, "w") as f:
        json.dump(peaks, f)
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        if "workloads" in m:
            m["workloads"] += ["tiny.motion", "tiny2.motion"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
