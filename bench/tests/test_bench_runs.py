"""Whole runs of tiny cells on the CPU, past the look for a chip: the
result line, a cell added by files alone, and the control that the
correctness limit has to reject."""
import json
import os

import jax
import pytest

import control
from harness import runner
from harness.catalog import Catalog
from tiny import REPO, make_root

E2E = {"setup_s", "frames_per_s", "step_ms.p50", "step_ms.p95"}

# a generator written beside the benchmark's own, as a later change adds
# one: the scene-motion walk with every tile of every camera redrawn
ALL_CHANGED = """\
import numpy as np
from generators import scene_motion


class Generator(scene_motion.Generator):
    def _transition(self, j):
        tiles, changed, useful = [], 0, 0
        for act in self._active:
            tiles.append(np.nonzero(np.ones_like(act)))
            changed += int(act.sum())
            useful += int(act.sum())
        return tiles, changed, useful
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    # the runs must not leave JAX's persistent cache pointing into a
    # temporary directory for the rest of the process
    monkeypatch.setattr(runner, "compile_cache", lambda jax, cat: "off")


def run_cell(root, capsys, workload, trace=0, seed=20241016123):
    rc = runner.main(["--workload", workload, "--seed", str(seed),
                      "--seconds", "0.5", "--trace", str(trace)], root,
                     0.0, require_chip=False)
    out, err = capsys.readouterr()
    assert rc == 0, err
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result)[-1] == "check"
    assert err.strip().splitlines()[-1].startswith("check head_gap ")
    return result


@pytest.mark.parametrize("workload", ["tiny.motion", "tiny2.motion"])
def test_run_is_correct(root, capsys, workload):
    r = run_cell(root, capsys, workload)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] > 0
    assert set(r["metrics"]) == E2E
    assert r["device"]["count"] >= 1
    gap = r["check"]["head_gap"]
    assert 0 <= gap["value"] <= gap["limit"]


def test_traced_run_reports_per_layer_metrics(root, capsys):
    # a fresh process has no program in memory: neither has this one
    # after clearing JAX's caches, whatever ran before in it
    jax.clear_caches()
    r = run_cell(root, capsys, "tiny.motion", trace=1)
    assert r["correct"] is True
    names = {m["name"] for m in Catalog(root).metrics("per_layer",
                                                      "tiny.motion")}
    # no device plane on the CPU: the trace readers find nothing to read
    # and leave their metrics out; the counters and spans report
    assert {"host_ms.step", "launch_waste", "compiles_in_window",
            "warmup_programs", "mfu.step"} <= set(r["metrics"]) <= names
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    # the cold step and the walk lower programs, persistent cache or not
    assert r["metrics"]["warmup_programs"]["value"] > 0
    assert r["metrics"]["launch_waste"]["value"] >= 1
    assert r["device"]["window_s"] > 0
    assert len(r["breakdown"]["idle_gaps"]) <= 10


def test_a_cell_is_added_by_files_alone(tmp_path, capsys):
    """A new generator and traffic mix, per-layer metric and kernel work
    function, and a cell using them, from a temporary directory: no
    harness edit.  The generator redraws every tile at every step."""
    root = make_root(tmp_path)
    with open(os.path.join(root, "bench", "generators", "all_changed.py"),
              "w") as f:
        f.write(ALL_CHANGED)
    with open(os.path.join(root, "bench", "traffic", "tiny_slow.json"),
              "w") as f:
        json.dump({"generator": "all_changed", "span": [1, 3],
                   "patches": 32}, f)
    with open(os.path.join(root, "bench", "metrics", "useful_per_step.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    return sum(s['useful'] for s in ctx.steps)"
                " / len(ctx.steps)\n")
    with open(os.path.join(root, "bench", "work", "tiny_kernel.py"),
              "w") as f:
        f.write("TRACE_NAMES = ('tiny_kernel',)\n\n\n"
                "def work(step, dims):\n"
                "    return step['useful'], 0.0\n")
    with open(os.path.join(root, "bench", "metrics",
                           "tiny_kernel_roofline.py"), "w") as f:
        f.write("from harness.roofline import share\n\n\n"
                "def read(ctx):\n"
                "    return share(ctx, 'tiny_kernel')\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["workloads"].append({"name": "tiny.slow", "config": "tiny",
                                  "traffic": "tiny_slow", "chips": 1,
                                  "why": "test"})
    for name, layer in (("useful_per_step", "reuse planning"),
                        ("tiny_kernel_roofline", "kernels")):
        manifest["per_layer"].append({
            "name": name, "unit": "tiles", "better": "higher",
            "source": "program_counter", "layer": layer,
            "moves": "frames_per_s", "workloads": ["tiny.slow"]})
    with open(path, "w") as f:
        json.dump(manifest, f)
    r = run_cell(root, capsys, "tiny.slow", trace=1)
    assert r["correct"] is True
    # every active tile of the tiny scene is useful at every step
    assert r["metrics"]["useful_per_step"]["value"] == 40 + 20
    # no kernel of that name in the trace: the reader returns nothing
    assert "tiny_kernel_roofline" not in r["metrics"]


def test_control_fails_the_limit(root, capsys):
    """The reference at three bfloat16 passes in place of the program
    reads above the limit the configurations hold; the program reads
    below it (at a size a CPU test can hold)."""
    limit = Catalog(REPO).config("district4")["check"]["head_gap_limit"]
    rows = control.main(["--workload", "tiny.motion", "--seeds", "1,2,3",
                         "--steps", "8"], root, require_chip=False)
    capsys.readouterr()
    for row in rows:
        assert row["program"] < limit < row["control"], row
