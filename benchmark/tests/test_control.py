"""The check has to fail what it exists to catch: the control (the
reference in bfloat16 in the program's place) and faults planted in the
timed path underneath a whole run.  On one chip there is no exchange
between chips to leave out."""

import numpy as np
import pytest

from benchmark.tests.conftest import TEST_CELL, run_cell

# the committed cells, and a cell that a test root adds as new files
CELLS = ["plain-5k.backlog", "plain-5k.bursts", TEST_CELL]


def _check(err, name):
    line = [ln for ln in err.splitlines() if ln.startswith(f"check {name} ")]
    return int(line[-1].split()[2])


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_is_not_correct(cell, mini_root, on_cpu, capsys):
    out, err = run_cell(on_cpu, mini_root, capsys, cell, control=1)
    assert out["correct"] is False
    assert out["checks"]["placement_mismatches"]["value"] > 0
    assert _check(err, "placement_mismatches") > 0


def _patch_hosts(monkeypatch, fn):
    from kubernetes_tpu.runtime.scheduler import Scheduler

    orig = Scheduler._validate_hosts

    def patched(self, hosts, n):
        return fn(np.array(orig(self, hosts, n)))

    monkeypatch.setattr(Scheduler, "_validate_hosts", patched)


def _swap_first_two(h):
    if len(h) > 1 and h[0] >= 0 and h[1] >= 0 and h[0] != h[1]:
        h[0], h[1] = h[1], h[0]
    return h


def _drop_second_half(h):
    h[len(h) // 2:] = -1
    return h


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_turns_correct_false(fault, cell, mini_root, on_cpu,
                                           capsys, monkeypatch):
    fn = {"answer_altered": _swap_first_two,
          "half_batch_left_out": _drop_second_half}[fault]
    _patch_hosts(monkeypatch, fn)
    out, err = run_cell(on_cpu, mini_root, capsys, cell)
    assert out["correct"] is False
    assert _check(err, "placement_mismatches") > 0


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged_turns_correct_false(cell, mini_root, on_cpu,
                                                  capsys, monkeypatch):
    """The device snapshot never takes a cycle's commits: every batch is
    placed against the empty cluster."""
    from kubernetes_tpu.codec import transfer

    orig = transfer.DeviceSnapshotCache.update
    seen = {}

    def stale(self, cluster, dirty_rows=None):
        if id(self) in seen:
            return seen[id(self)]
        seen[id(self)] = out = orig(self, cluster, dirty_rows)
        return out

    monkeypatch.setattr(transfer.DeviceSnapshotCache, "update", stale)
    out, err = run_cell(on_cpu, mini_root, capsys, cell)
    assert out["correct"] is False
