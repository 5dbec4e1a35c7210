"""`pair_encode_ms_per_kpod.antiaff` in the `antiaff-5k.backlog` cell at a
toy size through main() on the CPU: a traced run reads it, with the
CPU's dense pair leaves and with the chip's factored ones, and a program
without the `encode_pairs` counter reports nothing for it."""

import os

from benchmark.harness import byname
from benchmark.tests.conftest import ROOT, run_cell
from benchmark.tests.test_antiaff_cell import CELL, antiaff_root  # noqa: F401

METRIC = "pair_encode_ms_per_kpod.antiaff"


def test_traced_run_reads_the_pair_leaf_layer(antiaff_root, on_cpu,  # noqa: F811
                                              capsys):
    out, _ = run_cell(on_cpu, antiaff_root, capsys, CELL, trace=1)
    assert out["correct"] is True
    # the CPU packs unfactored, so the live cycle copies each class row
    assert out["metrics"][METRIC]["value"] > 0
    assert out["metrics"][METRIC]["unit"] == "ms/kpod"


def test_traced_run_with_the_chips_factored_pair_leaves(
        antiaff_root, on_cpu, capsys, monkeypatch):  # noqa: F811
    from kubernetes_tpu.codec import transfer

    monkeypatch.setattr(transfer, "factors_by_default", lambda: True)
    out, err = run_cell(on_cpu, antiaff_root, capsys, CELL, trace=1)
    assert out["correct"] is True
    assert all(v["value"] == 0 for v in out["checks"].values())
    assert out["metrics"][METRIC]["value"] >= 0
    assert '"compiles_in_window": 0' in err


def test_a_program_without_the_counter_reports_nothing():
    read = byname.load(ROOT, "metrics", METRIC).read
    assert read({"pods": 2048, "phase_s": {"encode": 0.2}}) is None
    assert read({"pods": 0, "phase_s": {"encode_pairs": 0.1}}) is None
    assert read({"pods": 2000, "phase_s": {"encode_pairs": 0.1}}) == 50.0
    assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                       METRIC + ".py"))
