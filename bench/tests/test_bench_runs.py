"""Whole runs of tiny cells on the CPU, past the look for a chip: the
result line, a cell and a strided residual detector added by files
alone, and the control that the correctness limit has to reject."""
import json
import os
import shutil

import jax
import numpy as np
import pytest

import control
from generators.scene_motion import pingpong
from harness import runner
from harness.catalog import Catalog
from tiny import REPO, make_root, tiny_config, tiny_scene

HERE = os.path.dirname(os.path.abspath(__file__))

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


def _add_metric(root, manifest, name, body, workload):
    with open(os.path.join(root, "bench", "metrics", name + ".py"),
              "w") as f:
        f.write("def read(ctx):\n    return " + body + "\n")
    manifest["per_layer"].append({
        "name": name, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "reuse planning",
        "moves": "frames_per_s", "workloads": [workload]})


def _box_tiles(cam, frames, shape):
    """The tiny scene's box tiles of camera ``cam`` at ``frames``, at
    2-px detector tiles of the half-resolution frame (4 px of the
    scene's)."""
    out = np.zeros(shape, bool)
    for f, c, x0, y0, x1, y1 in tiny_scene()["boxes"]:
        if c == cam and f in frames:
            out[y0 // 4:(y1 - 1) // 4 + 1, x0 // 4:(x1 - 1) // 4 + 1] = True
    return out


def _grow(m, rings):
    out = np.zeros_like(m)
    for y, x in zip(*np.nonzero(m)):
        out[max(y - rings, 0):y + rings + 1,
            max(x - rings, 0):x + rings + 1] = True
    return out


def test_a_strided_residual_detector_is_added_by_files_alone(tmp_path,
                                                             capsys):
    """A configuration whose reference declares a strided residual
    detector (3x3 stem, 3x3 stride-2 conv beside a 1x1 stride-2
    projection, their sum, a 3x3 head at stride 2) and whose entry runs
    it in plain ``jax.numpy``, at 2-px tiles, through the unedited
    harness: correct, ``mfu.step`` from the layer list, and useful tiles
    grown by the receptive field's rings (4 px: 2 rings)."""
    root = make_root(tmp_path)
    shutil.copy(os.path.join(HERE, "strided_res.py"),
                os.path.join(root, "bench", "references"))
    shutil.copy(os.path.join(HERE, "plain_entry.py"),
                os.path.join(root, "bench", "entries"))
    cfg = tiny_config("strided", "plain_entry", ["tiny"])
    cfg["reference"] = "strided_res"
    cfg["detector"] = {"widths": [8, 16], "tile": 2, "num_anchors": 2}
    path = os.path.join("bench", "configs", "strided.json")
    with open(os.path.join(root, path), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "strided", "source": "test",
                                "file": path, "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "strided.motion",
                                  "config": "strided",
                                  "traffic": "tiny_motion", "chips": 1,
                                  "why": "test"})
    _add_metric(root, manifest, "useful_tiles",
                "sum(s['useful'] for s in ctx.steps)", "strided.motion")
    _add_metric(root, manifest, "window_s", "ctx.window_s",
                "strided.motion")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    r = run_cell(root, capsys, "strided.motion", trace=1)
    assert r["correct"] is True and r["failed"] == 0
    m = {k: v["value"] for k, v in r["metrics"].items()}
    # FLOPs a 2-px tile: stem 2*27*8 at 4 px, down 2*9*8*16 and proj
    # 2*8*16 at 1 px, head 2*9*16*10 at 1 px
    per_tile = 4 * 2 * 27 * 8 + 2 * 9 * 8 * 16 + 2 * 8 * 16 + 2 * 9 * 16 * 10
    assert per_tile == 7168
    assert m["mfu.step"] == pytest.approx(
        100 * m["useful_tiles"] * per_tile / (m["window_s"] * 1e12),
        rel=1e-12)

    run = runner.Run(Catalog(root), "strided.motion", 5, jax.devices()[:1])
    run.setup()
    assert run.dims["rf_px"] == 4
    gen, n = run.motion, 4                       # the traffic's span
    for j in range(gen.period):
        frames = {pingpong(j - 1, n), pingpong(j, n)}
        want = 0
        for cam, act in enumerate(gen.grids[0]):
            want += int((_grow(_box_tiles(cam, frames, act.shape), 2)
                         & act).sum())
        assert gen.transition(j)[2] == want, j
    # and no head outside the useful tiles changes from step to step
    ref, grids = run.ref, gen.grids[0]
    masks = [ref.pixel_mask(g, 2, f.shape)
             for g, f in zip(grids, gen.frames[0])]

    def heads():
        return [np.asarray(ref.forward(run.params, f, mk))
                for f, mk in zip(gen.frames[0], masks)]

    before = heads()
    for _ in range(gen.period):
        gen.advance()
        after = heads()
        j = gen.step
        for cam, (h0, h1) in enumerate(zip(before, after)):
            moved = (h0 != h1).any(axis=2)
            useful = _grow(_box_tiles(cam, {pingpong(j - 1, n),
                                            pingpong(j, n)},
                                      grids[cam].shape), 2)
            assert not (moved & ~useful).any(), (j, cam)
        before = after


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
