"""The `antiaff-5k.backlog` cell at a toy size through main() on the CPU:
its shape (benchmark/shapes/antiaff.py) and entries as committed, the
configuration cut as every cell's is (conftest.make_root), and its sets
cut to fit the cut cluster."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import byname
from benchmark.tests.conftest import make_root, run_cell

CELL = "antiaff-5k.backlog"
SET_PODS = 60      # sets that fit the cut cluster's 300 nodes


@pytest.fixture
def antiaff_root(tmp_path):
    root = make_root(str(tmp_path / "root"))
    path = os.path.join(root, "benchmark", "configs", "sp5k-antiaff.json")
    cfg = json.load(open(path))
    cfg["set_pods"] = SET_PODS
    json.dump(cfg, open(path, "w"))
    return root


def _entries(root, key, cell=CELL):
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    return bench, [m for m in bench[key] if cell in m.get("workloads", [])]


def test_cell_runs_correct_with_its_checks(antiaff_root, on_cpu, capsys):
    out, err = run_cell(on_cpu, antiaff_root, capsys, CELL)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"pods_per_s", "setup_s"}
    assert list(out["checks"]) == ["placement_mismatches", "failed_pods",
                                   "degraded_cycles", "overcommitted_nodes",
                                   "antiaffinity_violations"]
    assert all(v["value"] == 0 for v in out["checks"].values())
    assert '"compiles_in_window": 0' in err
    # the stream reaches both of the reference's rules
    assert '"batch-start"' in err and '"sequential"' in err


def test_traced_run_reads_the_affinity_layers(antiaff_root, on_cpu, capsys):
    out, _ = run_cell(on_cpu, antiaff_root, capsys, CELL, trace=1)
    assert out["correct"] is True
    _, per_layer = _entries(antiaff_root, "per_layer")
    assert {m["name"] for m in per_layer} == {
        "affinity_encode_ms_per_kpod.antiaff",
        "redo_fence_ms_per_kpod.antiaff"}
    assert set(out["metrics"]) == {m["name"] for m in per_layer}
    assert out["metrics"]["affinity_encode_ms_per_kpod.antiaff"]["value"] > 0


def test_control_in_bfloat16_is_not_correct(antiaff_root, on_cpu, capsys):
    out, _ = run_cell(on_cpu, antiaff_root, capsys, CELL, control=1)
    assert out["correct"] is False
    assert out["checks"]["placement_mismatches"]["value"] > 0


def test_reference_without_the_anti_affinity_mask_fails(antiaff_root, on_cpu,
                                                        capsys, monkeypatch):
    """Plant the fault: the shape's reference admits every node for every
    set; the program's placements then disagree with it."""
    load = byname.load

    def faulty(root, group, name):
        mod = load(root, group, name)
        if group == "shapes":
            mod.Reference.forbidden = (
                lambda self, st, t: np.zeros(self.w.n, bool))
            mod.Reference.collides = lambda self, sets, hosts: False
        return mod

    monkeypatch.setattr(byname, "load", faulty)
    out, _ = run_cell(on_cpu, antiaff_root, capsys, CELL)
    assert out["correct"] is False
    assert out["checks"]["placement_mismatches"]["value"] > 0
