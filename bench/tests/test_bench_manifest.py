"""BENCHMARK.json: every name resolves to its file, names and units keep
to their characters, and every cell reports what its metrics move."""
import json
import os
import re

import pytest

from harness.catalog import Catalog
from tiny import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def cat():
    return Catalog(REPO)


def test_keys_and_command(cat):
    m = cat.manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["bench"]
    assert m["command"][1] == "bench/run.py"
    assert 1 <= m["run_seconds"] <= 51
    with open(os.path.join(REPO, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024


def test_names_and_units(cat):
    m = cat.manifest
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[section]]
        assert len(names) == len(set(names)), section
        for n in names:
            assert NAME.match(n), n
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for w in m["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


def test_every_name_resolves(cat):
    m = cat.manifest
    used = set()
    for w in m["workloads"]:
        cfg = cat.config(w["config"])
        used.add(w["config"])
        gen = cat.module("generators", cat.traffic(w["traffic"])["generator"])
        assert callable(gen.Generator)
        assert os.path.isfile(cat.path("entries", cfg["entry"] + ".py"))
        assert os.path.isfile(cat.path("references",
                                       cfg["reference"] + ".py"))
        for g in cfg["groups"]:
            assert os.path.isfile(cat.path("scenes", g["scene"] + ".npz"))
    assert used == {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(REPO, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for p in m["per_layer"]:
        assert hasattr(cat.module("metrics", p["name"]), "read")
        if p["name"].endswith("_roofline"):
            work = cat.module("work", p["name"][:-len("_roofline")])
            assert work.TRACE_NAMES and callable(work.work)


def test_each_cell_reports_what_its_metrics_move(cat):
    m = cat.manifest
    e2e = {e["name"] for e in m["end_to_end"]}
    for w in m["workloads"]:
        mine = {e["name"] for e in cat.metrics("end_to_end", w["name"])}
        assert "setup_s" in mine and len(mine) >= 2
        layer = cat.metrics("per_layer", w["name"])
        assert layer
        for p in layer:
            assert p["moves"] in e2e and p["moves"] in mine
    layers = {}
    for p in m["per_layer"]:
        layers.setdefault(p["layer"], []).append(p["name"])
    assert all(1 <= len(layer) <= 200 for layer in layers)
