"""Find the benchmark's files by name.

``root`` is the directory holding ``BENCHMARK.json``; everything else is
under ``root/bench``:

* ``configs/<config>.json`` via the configuration's ``file`` entry,
* ``traffic/<traffic>.json``, whose ``generator`` names
  ``generators/<generator>.py``,
* ``scenes/<scene>.npz``,
* ``references/<reference>.py``: the configuration's plain reference,
  which declares its detector layer by layer (``layers``), draws its
  weights (``init``) and computes its head maps (``forward``,
  ``pixel_mask``),
* ``entries/<entry>.py``: builds the program's detector from the
  configuration's ``detector`` dict and the reference's weights, and
  runs one fleet step,
* ``metrics/<metric>.py`` (one reader per per-layer metric),
* ``work/<kernel>.py`` (one work function per kernel role that a layer
  list names),
* ``peaks.json``.
"""
import importlib.util
import json
import os
import re

import numpy as np

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class Catalog:
    def __init__(self, root):
        self.root = os.path.abspath(root)
        self.dir = os.path.join(self.root, "bench")
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.manifest = json.load(f)
        self._modules = {}

    def path(self, *parts):
        return os.path.join(self.dir, *parts)

    def _named(self, kind, name, ext):
        if not NAME.match(name):
            raise ValueError(f"bad {kind} name {name!r}")
        p = self.path(kind, name + ext)
        if not os.path.isfile(p):
            raise FileNotFoundError(f"no {kind} file {p}")
        return p

    def workload(self, name):
        for w in self.manifest["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name):
        for c in self.manifest["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name):
        with open(self._named("traffic", name, ".json")) as f:
            return json.load(f)

    def scene(self, name):
        with np.load(self._named("scenes", name, ".npz")) as z:
            return {k: z[k] for k in z.files}

    def peaks(self):
        with open(self.path("peaks.json")) as f:
            return json.load(f)

    def module(self, kind, name):
        key = (kind, name)
        if key not in self._modules:
            p = self._named(kind, name, ".py")
            mod_name = f"bench_{kind}_" + re.sub(r"\W", "_", name)
            spec = importlib.util.spec_from_file_location(mod_name, p)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def metrics(self, section, workload):
        """The metrics of ``section`` (``end_to_end`` or ``per_layer``)
        that ``workload`` reports."""
        return [m for m in self.manifest[section]
                if workload in m.get("workloads", [workload])]
