"""The check catches a broken timed path: whole runs of tiny cells on the
CPU with a fault planted in the program underneath, each of which must
read ``correct: false``.

* ``state_unchanged``: the step returns its head canvas as it was;
* ``half_batch``: half of the tiles whose output changed keep their
  old heads;
* ``answer_altered``: the head rows are altered by one part in 10^4
  where they are produced.

The fleet paths hold no exchange between chips (each shard runs its own
groups), so there is no such fault to plant.
"""
import json

import pytest

import repro.fleet.sharded as sharded
import repro.kernels.ops as ops
from harness import runner
from tiny import make_root


def _state_unchanged(monkeypatch):
    def unchanged(packed, idx, base, donate=False):
        ops.record_dispatch("sbnet_scatter_changed")
        return base

    monkeypatch.setattr(ops, "sbnet_scatter_changed", unchanged)
    monkeypatch.setattr(sharded, "_raw_scatter_changed",
                        lambda ph, sidx, base, interpret: base)


def _half_batch(monkeypatch):
    orig = ops.reuse_sets

    def half(raw, nbr, n_layers):
        changed, compute = orig(raw, nbr, n_layers)
        changed = changed.copy()
        changed[changed.nonzero()[0][::2]] = False
        return changed, compute

    monkeypatch.setattr(ops, "reuse_sets", half)


def _answer_altered(monkeypatch):
    orig_single, orig_sharded = ops.sbnet_scatter_changed, \
        sharded._raw_scatter_changed
    monkeypatch.setattr(
        ops, "sbnet_scatter_changed",
        lambda packed, idx, base, donate=False: orig_single(
            packed * (1 + 1e-4), idx, base, donate))
    monkeypatch.setattr(
        sharded, "_raw_scatter_changed",
        lambda ph, sidx, base, interpret: orig_sharded(
            ph * (1 + 1e-4), sidx, base, interpret=interpret))


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload", ["tiny.motion", "tiny2.motion"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_reads_incorrect(root, capsys, monkeypatch, fault, workload):
    monkeypatch.setattr(runner, "compile_cache", lambda jax, cat: "off")
    FAULTS[fault](monkeypatch)
    rc = runner.main(["--workload", workload, "--seed", "777",
                      "--seconds", "0.3", "--trace", "0"], root, 0.0,
                     require_chip=False)
    out, _ = capsys.readouterr()
    assert rc == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is False
    assert result["failed"] >= 1
    gap = result["check"]["head_gap"]
    assert gap["value"] > gap["limit"]
