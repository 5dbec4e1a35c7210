"""The benchmark's tests run on the CPU with only the device check
stubbed: every cell at a small size through the same harness, the trace
reduction on a recorded excerpt, the control and the planted faults.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

# small sizes for the CPU: batches small enough to compile quickly, a
# node count that is not the program's arena width (512 rows here)
MINI_NODES = 300
MINI_EXISTING = 100
MINI_BATCH = 64


# what the cut sets in a traffic mix, by parameter, whatever its kind
TRAFFIC_CUT = {"pods_per_s_max": 3000, "burst": 16, "interval_s": 0.1,
               "follow_s": 20}


def make_root(dst, nodes=MINI_NODES, batch=MINI_BATCH, add=None):
    """A checkout-shaped copy of BENCHMARK.json and benchmark/ data with
    the configurations and mixes cut to CPU sizes.  `add` maps a path
    under the root to a file's text, written before the cut: a new
    configuration of any shape is cut through the keys every shape
    keeps (nodes, existing_pods, scheduler.batchSize)."""
    os.makedirs(dst, exist_ok=True)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for rel, text in (add or {}).items():
        with open(os.path.join(dst, rel), "w") as f:
            f.write(text)
    cdir = os.path.join(dst, "benchmark", "configs")
    for f in os.listdir(cdir):
        p = os.path.join(cdir, f)
        c = json.load(open(p))
        c["nodes"] = nodes
        c["existing_pods"] = MINI_EXISTING
        c["scheduler"]["batchSize"] = batch
        json.dump(c, open(p, "w"))
    tdir = os.path.join(dst, "benchmark", "traffic")
    for f in os.listdir(tdir):
        p = os.path.join(tdir, f)
        t = json.load(open(p))
        t.update((k, v) for k, v in TRAFFIC_CUT.items() if k in t)
        json.dump(t, open(p, "w"))
    return dst


# a cell that only adds files: a configuration of a test-only shape
# (`zone`: half the nodes labelled, the test pods select the label) under
# a mix of a test-only kind (`fixed`: a fixed count of pods at once),
# laid out as under a root, and its BENCHMARK.json entries
NEW_CELL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "new_cell")
TEST_CELL = "zone.fixed"


def new_cell_files() -> dict:
    """The test-only cell's files: path under a root -> text."""
    out = {}
    for group in ("configs", "shapes", "kinds", "traffic"):
        for f in sorted(os.listdir(os.path.join(NEW_CELL, group))):
            with open(os.path.join(NEW_CELL, group, f)) as fh:
                out[os.path.join("benchmark", group, f)] = fh.read()
    return out


def add_entries(root) -> None:
    """Append the test-only cell's entries to the root's BENCHMARK.json."""
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    for key, entries in json.load(
            open(os.path.join(NEW_CELL, "entries.json"))).items():
        bench[key].extend(entries)
    json.dump(bench, open(path, "w"))


@pytest.fixture
def mini_root(tmp_path):
    """The cut root, with the test-only cell added as new files."""
    root = make_root(str(tmp_path / "root"), add=new_cell_files())
    add_entries(root)
    return root


@pytest.fixture
def on_cpu(monkeypatch):
    """Stub the harness's look for a chip, and take the chip's branch of
    the speculative engine (the packed program) on the CPU."""
    from benchmark.harness import main as hm
    from kubernetes_tpu.models import speculative

    devs = jax.devices()
    monkeypatch.setattr(hm, "require_device", lambda chips: {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)})
    monkeypatch.setattr(speculative, "FORCE_PACKED_PATH", True)
    return hm


def run_cell(hm, root, capsys, workload, seed=12345678901, seconds=2.0,
             trace=0, control=0):
    """One run through main(); returns (result line, stderr)."""
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--control", str(control)]
    assert hm.main(argv, root, time.perf_counter()) == 0
    cap = capsys.readouterr()
    return json.loads(cap.out.strip().splitlines()[-1]), cap.err
