"""The harness end to end on the CPU, at small sizes."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import tracereduce
from benchmark.kinds.poisson import poisson_offsets
from benchmark.tests.conftest import ROOT, TEST_CELL, run_cell

# the committed cells, and a cell that a test root adds as new files
CELLS = ["plain-5k.backlog", "plain-5k.bursts", TEST_CELL]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_prints_a_correct_contract_line(cell, mini_root, on_cpu,
                                             capsys):
    out, err = run_cell(on_cpu, mini_root, capsys, cell)
    assert KEYS <= set(out) and list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    bench = json.load(open(os.path.join(mini_root, "BENCHMARK.json")))
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert '"compiles_in_window": 0' in err
    # every number compared is printed with its limit, last on stderr
    tail = err.strip().splitlines()[-len(out["checks"]):]
    assert all(ln.startswith("check ") and " limit " in ln for ln in tail)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_the_per_layer_metrics(cell, mini_root, on_cpu,
                                                  capsys):
    out, _ = run_cell(on_cpu, mini_root, capsys, cell, trace=1)
    assert out["correct"] is True
    bench = json.load(open(os.path.join(mini_root, "BENCHMARK.json")))
    host_read = {m["name"] for m in bench["per_layer"]
                 if cell in m["workloads"] and m["source"] != "device_trace"}
    # the CPU has no device plane: device metrics are left out, not 0
    assert host_read <= set(out["metrics"])
    assert not any(n.startswith("device_") for n in out["metrics"])


def test_seeds_change_the_order_of_arrivals_not_the_work():
    mix = {"kind": "poisson", "rate_per_s": 500}
    a = poisson_offsets(mix, 4.0, 1)
    b = poisson_offsets(mix, 4.0, 2 ** 31 + 7)
    assert len(a) == len(b) == 2000
    # every seed draws its gaps from one set: all but the first gap,
    # which the start of the window absorbs, are shared
    da, db = np.round(np.diff(a), 9), np.round(np.diff(b), 9)
    assert np.isin(da, db).sum() >= len(da) - 1
    assert not np.allclose(a, b)
    assert (a == poisson_offsets(mix, 4.0, 1)).all()
    assert a[0] == 0 and a[-1] <= 4.0 and (np.diff(a) > 0).all()


def test_a_new_cell_is_found_by_name(mini_root, on_cpu, capsys):
    """A configuration file, a traffic file and a BENCHMARK.json entry
    are all a new cell needs: here a 400-node deployment under an
    open-loop Poisson mix."""
    cdir = os.path.join(mini_root, "benchmark", "configs")
    cfg = json.load(open(os.path.join(cdir, "sp5k-plain.json")))
    cfg.update(name="sp-new", nodes=400, existing_pods=40)
    json.dump(cfg, open(os.path.join(cdir, "sp-new.json"), "w"))
    json.dump({"kind": "poisson", "rate_per_s": 40, "follow_s": 20},
              open(os.path.join(mini_root, "benchmark", "traffic",
                                "poisson-new.json"), "w"))
    path = os.path.join(mini_root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "sp-new", "source": "x",
                             "file": "benchmark/configs/sp-new.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new.poisson", "config": "sp-new",
                               "traffic": "poisson-new", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"]:
        if "plain-5k.bursts" in m.get("workloads", []):
            m["workloads"].append("new.poisson")
    json.dump(bench, open(path, "w"))
    out, err = run_cell(on_cpu, mini_root, capsys, "new.poisson")
    assert out["correct"] is True and out["attempted"] == 80
    assert {"e2e_p50_ms", "e2e_p99_ms", "setup_s"} == set(out["metrics"])


def test_refuses_to_run_without_an_accelerator(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "plain-5k.backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert "needs an accelerator" in p.stderr
    assert p.stdout.strip() == ""


def test_trace_reduction_on_a_synthetic_trace():
    dev, ops = "/device:TPU:0", "XLA Ops"
    ev = [
        ["/host:CPU", "main", "bench.window", 0.0, 100e6],
        [dev, ops, "a", 10e6, 20e6],        # busy 10..30
        [dev, ops, "b", 25e6, 10e6],        # overlaps: busy 10..35
        [dev, ops, "a", 60e6, 10e6],        # busy 60..70
        [dev, ops, "c", 120e6, 5e6],        # outside the window
        ["/host:CPU", "main", "bench.run_once", 0.0, 100e6],
        ["/host:CPU", "main", "ktpu.fetch", 35e6, 20e6],
    ]
    red = tracereduce.reduce(ev)
    assert red["busy_s"] == pytest.approx(0.035)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["device_ops"][0] == ["a", pytest.approx(0.03)]
    assert red["idle_gaps"] == [
        ["bench.run_once", pytest.approx(0.03)],     # 70..100
        ["ktpu.fetch", pytest.approx(0.025)],        # 35..60
        ["bench.run_once", pytest.approx(0.01)],     # 0..10
    ]


def test_trace_reduction_on_a_recorded_chip_trace():
    """An excerpt of a real TPU v5 lite trace: 100 ms of a
    plain-5k.bursts window (PR 22, bench.py's world) around its
    longest idle gap, device operations, programs and host spans.  Busy
    time is checked against a 1-microsecond raster of the same
    operations."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_excerpt.json")
    ev = json.load(open(path))
    red = tracereduce.reduce(ev)
    win = [e for e in ev if e[2] == tracereduce.WINDOW_EVENT][0]
    t0, n = win[3], int(win[4] // 1000)
    raster = np.zeros(n, bool)
    ops = [e for e in ev if tracereduce.is_device_plane(e[0])
           and e[1] in tracereduce.OP_LINES]
    for _, _, _, s, d in ops:
        a = max(0, int((s - t0) // 1000))
        b = min(n, int(np.ceil((s + d - t0) / 1000)))
        raster[a:b] = True
    assert red["window_s"] == pytest.approx(win[4] / 1e9)
    assert red["busy_s"] == pytest.approx(raster.sum() * 1e-6,
                                          abs=2e-6 * len(ops))
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["device_ops"][0][0].startswith("jit_")
    longest = red["idle_gaps"][0]
    assert longest[0].startswith(tracereduce.HOST_PREFIXES)
    assert longest[1] == max(g for _, g in red["idle_gaps"])
