"""The seam between the harness and a configuration's shape and traffic
kind.  The plain shape gives what the harness hard-wired before the seam
existed: digests frozen from that harness (golden.py,
data/plain_golden.json).  A shape and a kind that come as new files make
a cell that runs, is checked, and fails its check when the shape's
reference drops what tells its templates apart."""

import filecmp
import json
import os

import numpy as np
import pytest

from benchmark.harness import byname
from benchmark.harness import check as chk
from benchmark.tests import golden
from benchmark.tests.conftest import (
    TEST_CELL,
    add_entries,
    make_root,
    new_cell_files,
    run_cell,
)

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "plain_golden.json")


@pytest.fixture(scope="module")
def plain_digests(tmp_path_factory):
    """The digests of what the seam gives for sp5k-plain, cut to the
    mini root (300 nodes, batch 64)."""
    root = make_root(str(tmp_path_factory.mktemp("golden") / "root"))
    cfg = byname.load_config(root, "sp5k-plain")
    assert "shape" not in cfg          # the plain shape by default
    shape = byname.load(root, "shapes", cfg.get("shape", "plain"))
    parts = golden.plain_parts(
        cfg, int(cfg["scheduler"]["batchSize"]),
        build_world=shape.build_world, capacity=shape.capacity,
        warm_sizes=shape.warm_sizes, stream=shape.stream,
        pod_object=shape.pod_object, build_encoder=shape.build_encoder,
        place=lambda w, b, t, dt: chk.control_placements(shape, w, b, t, dt),
        compare=lambda w, b, t, n: chk.compare(shape, w, b, t, n),
        guarantees=shape.guarantees)
    return golden.digests(parts)


def _frozen():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("part", sorted(_frozen()["digests"]))
def test_plain_shape_gives_the_frozen_part(part, plain_digests):
    assert plain_digests["digests"][part] == _frozen()["digests"][part]


def test_frozen_stream_reaches_both_rules(plain_digests):
    frozen = _frozen()
    rules = plain_digests["compare"]["rules"]
    assert rules == frozen["compare"]["rules"]
    assert rules["batch-start"] > 0 and rules["sequential"] > 0
    assert plain_digests["compare"]["mismatches"] == 0
    assert plain_digests["warm_sizes"] == frozen["warm_sizes"]
    # the bfloat16 control places the same stream otherwise
    assert (plain_digests["digests"]["control_placements"]
            != plain_digests["digests"]["placements"])


def _files(root):
    out = set()
    for d, _, fs in os.walk(root):
        out.update(os.path.relpath(os.path.join(d, f), root) for f in fs)
    return out


def test_a_new_shape_and_kind_need_only_new_files(tmp_path):
    """The test-only cell is new files and appended BENCHMARK.json
    entries: every file of the root without it is there unchanged."""
    before = make_root(str(tmp_path / "before"))
    after = make_root(str(tmp_path / "after"), add=new_cell_files())
    add_entries(after)
    old, new = _files(before), _files(after)
    assert new - old == set(new_cell_files())
    assert old <= new
    for rel in sorted(old - {"BENCHMARK.json"}):
        assert filecmp.cmp(os.path.join(before, rel),
                           os.path.join(after, rel), shallow=False), rel
    b0 = json.load(open(os.path.join(before, "BENCHMARK.json")))
    b1 = json.load(open(os.path.join(after, "BENCHMARK.json")))
    for key, val in b0.items():
        if isinstance(val, list):
            assert b1[key][:len(val)] == val, key
        else:
            assert b1[key] == val, key


def _ignore_selector(monkeypatch):
    """Plant the fault: the shape's reference admits every node for
    every template."""
    load = byname.load

    def faulty(root, group, name):
        mod = load(root, group, name)
        if group == "shapes":
            mod.Reference.eligible = lambda self, c: np.ones(self.w.n, bool)
        return mod

    monkeypatch.setattr(byname, "load", faulty)


@pytest.mark.parametrize("fault", [None, "reference_ignores_selector"])
def test_a_cell_of_new_files_runs_and_is_checked(fault, mini_root, on_cpu,
                                                 capsys, monkeypatch):
    if fault:
        _ignore_selector(monkeypatch)
    out, err = run_cell(on_cpu, mini_root, capsys, TEST_CELL)
    assert out["attempted"] == 200
    assert set(out["metrics"]) == {"setup_s", "fixed_pods_per_s"}
    checks = out["checks"]
    assert list(checks) == ["placement_mismatches", "failed_pods",
                            "degraded_cycles", "overcommitted_nodes",
                            "selector_violations"]
    assert checks["selector_violations"]["value"] == 0
    if fault:
        assert out["correct"] is False
        assert checks["placement_mismatches"]["value"] > 0
    else:
        assert out["correct"] is True and out["failed"] == 0
        assert '"rules": {"batch-start"' in err
