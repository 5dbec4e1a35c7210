"""Hot-path performance observatory (ISSUE 11).

Pins the tentpole contracts: the per-cycle host/device split reconciles
with the scheduler's phase_seconds wall time on the live path, the
phase x width EWMA matrix fills, the transfer accounting is byte-EXACT
on the incremental dirty-row path, /debug/perf + /debug/profile +
/debug/ serve on both servers (inflight-exempt on the apiserver), the
profiler capture state machine (throttle / in-progress / graceful
unsupported no-op), the heartbeat satellite fields, and the
bench.py --baseline perf-regression gate (self-compare exits 0, a
synthetic regression exits non-zero).
"""

import dataclasses
import json
import logging
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from kubernetes_tpu.codec.transfer import (
    AsyncFetch,
    DeviceSnapshotCache,
    host_fetch,
    transfer_delta,
    transfer_totals,
)
from kubernetes_tpu.runtime import perfobs
from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.cluster import LocalCluster
from kubernetes_tpu.runtime.health import start_health_server
from kubernetes_tpu.runtime.queue import PodBackoff, PriorityQueue
from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig

from fixtures import make_node, make_pod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _live_scheduler(nodes=4, **cfg_kw):
    cache = SchedulerCache()
    queue = PriorityQueue(backoff=PodBackoff(initial=0.01, max_duration=0.05))
    cfg = SchedulerConfig(
        disable_preemption=True, batch_size=64, batch_window_s=0.0, **cfg_kw
    )
    sched = Scheduler(
        cache=cache, queue=queue, binder=lambda p, n: True, config=cfg
    )
    for i in range(nodes):
        cache.add_node(make_node(f"n{i}", cpu="16", mem="32Gi"))
    return sched, queue


def _drain(sched, queue, budget_s=60.0):
    deadline = time.monotonic() + budget_s
    while queue.has_schedulable() and time.monotonic() < deadline:
        sched.run_once(timeout=0.0)
    sched.flush_pipeline()


# ------------------------------------------------------- cost-model split


def test_cycle_split_reconciles_with_phase_seconds():
    """Acceptance pin: on the live (synchronous) path the host split
    (enqueue + stall + commit) accounts for ~all of each cycle's wall
    clock, and the observatory's totals reconcile with the scheduler's
    own phase_seconds counters — two independent stamp sets that must
    tell one story."""
    sched, queue = _live_scheduler()
    for i in range(300):
        queue.add(make_pod(f"p{i}", cpu="50m", mem="64Mi"))
    _drain(sched, queue)
    po = sched.perfobs
    summary = po.summary()
    assert summary["cycles"] >= 2
    ph = sched.phase_seconds
    tot = summary["totals_s"]
    # same stamps, independent accumulation points: enqueue == the
    # encode+dispatch phases, stall == host_stall (tight tolerance)
    enq_ref = ph["encode"] + ph["dispatch"]
    assert abs(tot["host_enqueue"] - enq_ref) <= 0.02 + 0.05 * enq_ref
    assert (
        abs(tot["host_stall"] - ph["host_stall"])
        <= 0.02 + 0.05 * max(ph["host_stall"], 1e-9)
    )
    # the commit measure covers the WHOLE tail (ledger/telemetry/perf
    # included) so it bounds the phase counter from above
    assert tot["host_commit"] >= ph["commit"] * 0.9 - 0.02
    # the reconciliation: host split sum ~= cycle wall on the sync path
    host = summary["host_s"]
    wall = summary["wall_s"]
    assert wall > 0
    assert host <= wall + 0.02
    assert summary["unaccounted_s"] <= 0.15 * wall + 0.1, summary
    # per-sample invariant: the payload's arithmetic is self-consistent
    for s in po.debug_payload()["samples"]:
        split_host = sum(
            s["split_s"][p] for p in perfobs.HOST_PHASES
        )
        assert s["cycle_wall_s"] + 1e-6 >= split_host
        assert abs(
            s["cycle_wall_s"] - split_host - s["unaccounted_s"]
        ) < 1e-3


def test_ewma_matrix_covers_every_phase_and_width():
    sched, queue = _live_scheduler()
    for i in range(150):
        queue.add(make_pod(f"p{i}", cpu="50m", mem="64Mi"))
    _drain(sched, queue)
    matrix = sched.perfobs.ewma_matrix()
    assert set(matrix) == set(perfobs.PHASES)
    for phase, row in matrix.items():
        assert row, f"phase {phase} has no width entries"
        for width, v in row.items():
            assert int(width) > 0 and v >= 0.0
    # the batch width the engine actually compiled (pow2 pad of 64)
    assert "64" in matrix["host_enqueue"]


def test_degraded_cycle_attributes_to_host():
    """A breaker-open cycle is served by the CPU engine: the sample is
    tagged degraded and carries no device-side seconds."""
    from kubernetes_tpu.runtime.chaos import Disruptions

    sched, queue = _live_scheduler(
        device_retry_max=0, breaker_failure_threshold=1,
        breaker_open_s=10.0, cpu_fallback=True,
    )
    dis = Disruptions(LocalCluster())
    dis.device_lost()
    try:
        queue.add(make_pod("deg", cpu="50m"))
        sched.run_once(timeout=0.2)
        sched.flush_pipeline()
    finally:
        dis.clear_device_faults()
    samples = sched.perfobs.debug_payload()["samples"]
    deg = [s for s in samples if s["degraded"]]
    assert deg, "no degraded sample recorded"
    assert deg[-1]["split_s"]["device_execute"] == 0.0
    assert deg[-1]["split_s"]["d2h_materialize"] == 0.0
    assert sched.perfobs.summary()["degraded_cycles"] >= 1


# --------------------------------------------------- transfer accounting


def test_dirty_row_scatter_byte_accounting_is_exact():
    """Satellite pin: the counter delta equals the nbytes of the arrays
    that ACTUALLY crossed the wire, on the incremental dirty-row path —
    the pow2-padded row-index vector plus the padded row values."""
    from kubernetes_tpu.codec.schema import _pow2

    @dataclasses.dataclass
    class Snap:
        a: np.ndarray
        b: np.ndarray

    cache = DeviceSnapshotCache()
    a = np.zeros((16, 4), np.float32)
    b = np.arange(16, dtype=np.float32)
    before = transfer_totals()
    cache.update(Snap(a=a, b=b))
    d = transfer_delta(before)
    assert d["h2d/snapshot_upload"]["bytes"] == a.nbytes + b.nbytes
    assert d["h2d/snapshot_upload"]["calls"] == 1

    # touch exactly rows 2 and 3 of one field; the other is
    # identity-reused, so ONLY the scatter moves bytes
    a2 = a.copy()
    a2[2] = 1.0
    a2[3] = 2.0
    rows = np.asarray([2, 3], np.int64)
    before = transfer_totals()
    cache.update(Snap(a=a2, b=b), dirty_rows=rows)
    d = transfer_delta(before)
    k = _pow2(len(rows))  # the shape-bucket pad the wire actually pays
    expected = k * np.dtype(np.int32).itemsize + k * a2[0].nbytes
    assert d == {
        "h2d/dirty_scatter": {"bytes": expected, "calls": 1}
    }, d


def test_fetch_accounting_matches_materialized_nbytes():
    import jax.numpy as jnp

    x = jnp.arange(64, dtype=jnp.float32)
    before = transfer_totals()
    out = host_fetch(x)
    d = transfer_delta(before)
    assert d["d2h/fetch"] == {"bytes": out.nbytes, "calls": 1}

    before = transfer_totals()
    f = AsyncFetch(jnp.arange(32, dtype=jnp.int32))
    out = f.result()
    # the worker sets the split AFTER result() may return: wait for the
    # accounting to land
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and not transfer_delta(before):
        time.sleep(0.01)
    d = transfer_delta(before)
    assert d["d2h/fetch"] == {"bytes": out.nbytes, "calls": 1}
    # the host/device attribution split the observatory consumes
    assert f.execute_seconds >= 0.0 and f.materialize_seconds >= 0.0
    assert f.execute_seconds + f.materialize_seconds <= f.seconds + 0.05


def test_live_cycle_span_annotated_with_transfer_bytes():
    from kubernetes_tpu.runtime.flightrecorder import FlightRecorder

    rec = FlightRecorder()
    cache = SchedulerCache()
    queue = PriorityQueue()
    sched = Scheduler(
        cache=cache, queue=queue, binder=lambda p, n: True,
        config=SchedulerConfig(disable_preemption=True),
        flight_recorder=rec,
    )
    cache.add_node(make_node("m1", cpu="8", mem="16Gi"))
    queue.add(make_pod("p", cpu="100m"))
    sched.run_once(timeout=0.2)
    sched.flush_pipeline()
    spans = rec.spans()
    assert spans
    attrs = spans[-1].attrs
    assert attrs.get("transfer_bytes", 0) > 0, attrs
    assert "/" in attrs.get("transfer_top_seam", ""), attrs


def test_pipelined_cycle_transfer_deltas_do_not_double_count():
    """Under pipeline_commit a cycle's tail runs AFTER the next cycle's
    dispatch.  The per-cycle delta is taken at the commit FENCE (before
    that next dispatch), so summing every cycle's delta must equal the
    global counters' movement — a tail-time delta would count each
    dispatch's uploads twice."""
    before = transfer_totals()
    sched, queue = _live_scheduler(pipeline_commit=True)
    for i in range(300):
        queue.add(make_pod(f"p{i}", cpu="50m", mem="64Mi"))
    _drain(sched, queue)
    global_delta = transfer_delta(before)
    summed: dict = {}
    for s in sched.perfobs.debug_payload()["samples"]:
        for k, v in s["transfers"].items():
            cell = summed.setdefault(k, {"bytes": 0, "calls": 0})
            cell["bytes"] += v["bytes"]
            cell["calls"] += v["calls"]
    assert summed, "no per-cycle transfer deltas recorded"
    assert summed == global_delta


# ----------------------------------------------------- debug endpoints


def test_debug_perf_and_index_on_health_server():
    sched, queue = _live_scheduler()
    for i in range(100):
        queue.add(make_pod(f"p{i}", cpu="50m"))
    _drain(sched, queue)
    srv = start_health_server()
    try:
        h, p = srv.address
        with urllib.request.urlopen(
            f"http://{h}:{p}/debug/perf", timeout=10
        ) as r:
            assert "application/json" in r.headers.get("Content-Type", "")
            body = json.loads(r.read())
        assert {"summary", "ewma_s", "profiler", "samples"} <= set(body)
        assert body["summary"]["cycles"] >= 1
        assert body["summary"]["transfers"]
        with urllib.request.urlopen(
            f"http://{h}:{p}/debug/perf?limit=1", timeout=10
        ) as r:
            limited = json.loads(r.read())
        assert len(limited["samples"]) == 1
        with urllib.request.urlopen(
            f"http://{h}:{p}/debug/", timeout=10
        ) as r:
            idx = json.loads(r.read())
        eps = idx["endpoints"]
        assert {
            "/debug/traces", "/debug/decisions", "/debug/cluster",
            "/debug/perf", "/debug/profile",
        } <= set(eps)
        for desc in eps.values():
            assert isinstance(desc, str) and desc
    finally:
        srv.stop()


def test_debug_perf_and_index_on_apiserver_inflight_exempt():
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.apiserver.fairness import FlowControlConfig

    sched, queue = _live_scheduler(nodes=1)
    queue.add(make_pod("p", cpu="100m"))
    sched.run_once(timeout=0.2)
    sched.flush_pipeline()
    # a starved limiter rejects every non-exempt request: the debug
    # surface must still answer (diagnosing an overload needs it)
    srv = APIServer(
        cluster=LocalCluster(),
        flow_control=FlowControlConfig(
            max_inflight_readonly=1, max_inflight_mutating=1,
            queue_length_per_flow=0, queue_wait_timeout_s=0.01,
        ),
    ).start()
    try:
        with urllib.request.urlopen(
            f"{srv.url}/debug/perf?limit=2", timeout=10
        ) as r:
            body = json.loads(r.read())
        assert "summary" in body and len(body["samples"]) <= 2
        with urllib.request.urlopen(
            f"{srv.url}/debug/", timeout=10
        ) as r:
            idx = json.loads(r.read())
        assert "/debug/perf" in idx["endpoints"]
    finally:
        srv.stop()


def test_debug_perf_body_respects_response_cap():
    from kubernetes_tpu.runtime.ledger import debug_body

    po = perfobs.PerfObservatory(ring_capacity=256)
    for c in range(200):
        po.on_cycle(
            width=64, tier="bulk", degraded=False,
            enqueue_s=0.001, execute_s=0.0005, materialize_s=0.0001,
            stall_s=0.0002, commit_s=0.002, wall_s=0.004,
            transfers={"h2d/snapshot_upload": {"bytes": 100, "calls": 1}},
            trace_id=f"{c:032x}",
        )
    full = json.loads(debug_body(po.debug_payload, ""))
    assert len(full["samples"]) == 200
    capped = json.loads(debug_body(po.debug_payload, "", cap=8192))
    assert 0 < len(capped["samples"]) < 200


# ----------------------------------------------------- profiler capture


class _FakeProfiler:
    def __init__(self, fail_start=False):
        self.fail_start = fail_start
        self.started = []
        self.stopped = 0

    def start_trace(self, d):
        if self.fail_start:
            raise RuntimeError("profiler unsupported on this backend")
        self.started.append(d)

    def stop_trace(self):
        self.stopped += 1


def _patched_capture(monkeypatch, tmp_path, fake, **kw):
    import jax

    monkeypatch.setattr(jax, "profiler", fake)
    return perfobs.ProfilerCapture(profile_dir=str(tmp_path), **kw)


def test_profiler_capture_lifecycle_and_throttle(monkeypatch, tmp_path):
    fake = _FakeProfiler()
    clock = [100.0]
    cap = _patched_capture(
        monkeypatch, tmp_path, fake,
        min_interval_s=30.0, clock=lambda: clock[0],
    )
    out = cap.start(0.05)
    assert out["started"] and out["seconds"] == 0.05
    assert out["dir"].startswith(str(tmp_path))
    # a second start while active reports in-progress, never a
    # concurrent double capture
    again = cap.start(0.05)
    assert not again["started"]
    assert again["reason"] == "capture already in progress"
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and cap.status()["active"]:
        time.sleep(0.01)
    assert fake.stopped == 1 and cap.captures_total == 1
    # throttled until min_interval elapses on the capture's clock
    throttled = cap.start(0.05)
    assert not throttled["started"] and throttled["reason"] == "throttled"
    assert throttled["retry_after_s"] > 0
    clock[0] += 31.0
    assert cap.start(0.05)["started"]
    _wait_inactive(cap)


def _wait_inactive(cap, budget_s=5.0):
    """Let a pending capture timer fire inside THIS test's monkeypatch
    window — a timer outliving the test would stop the next test's
    fake profiler."""
    deadline = time.monotonic() + budget_s
    while time.monotonic() < deadline and cap.status()["active"]:
        time.sleep(0.01)
    assert not cap.status()["active"]


def test_profiler_capture_unsupported_is_graceful_noop(
    monkeypatch, tmp_path
):
    cap = _patched_capture(
        monkeypatch, tmp_path, _FakeProfiler(fail_start=True)
    )
    out = cap.start(1.0)
    assert out == {
        "started": False, "supported": False,
        "error": "profiler unsupported on this backend",
    }
    # the failed start released the slot: a later start may try again
    assert not cap.status()["active"]


def test_profiler_capture_clamps_seconds(monkeypatch, tmp_path):
    fake = _FakeProfiler()
    cap = _patched_capture(
        monkeypatch, tmp_path, fake, max_seconds=0.2, min_interval_s=0.0
    )
    out = cap.start(9999.0)
    assert out["started"] and out["seconds"] == 0.2
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and cap.status()["active"]:
        time.sleep(0.01)
    assert fake.stopped == 1


def test_profile_request_parses_query(monkeypatch, tmp_path):
    fake = _FakeProfiler()
    cap = _patched_capture(
        monkeypatch, tmp_path, fake, max_seconds=600.0, min_interval_s=0.0
    )
    po = perfobs.PerfObservatory()
    po.profiler = cap
    old = perfobs.get_default()
    perfobs.set_default(po)
    try:
        out = perfobs.profile_request("seconds=0.07")
        assert out["started"] and out["seconds"] == 0.07
        _wait_inactive(cap)
        # malformed seconds falls back to the 2s default
        out = perfobs.profile_request("seconds=bogus")
        assert out["started"] and out["seconds"] == 2.0
        _wait_inactive(cap)
    finally:
        perfobs.set_default(old)


# ----------------------------------------------------------- heartbeat


def test_heartbeat_line_carries_observatory_fields():
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    logger = logging.getLogger("kubernetes_tpu")
    handler = _Capture(level=logging.INFO)
    logger.addHandler(handler)
    old_level = logger.level
    logger.setLevel(logging.INFO)
    try:
        sched, queue = _live_scheduler(heartbeat_s=0.01)
        for i in range(40):
            queue.add(make_pod(f"p{i}", cpu="50m"))
        _drain(sched, queue)
        time.sleep(0.02)
        sched.run_once(timeout=0.0)  # idle poll fires the heartbeat
        beats = [r for r in records if r.startswith("heartbeat:")]
        assert beats, "no heartbeat line"
        line = beats[-1]
        for field in ("host_ms=", "dev_ms=", "xfer_top="):
            assert field in line, f"heartbeat missing {field}: {line}"
        # scheduling work happened since the window opened: host time
        # and a top transfer seam must both be visible
        assert "xfer_top=none" not in line or "host_ms=0 " not in line
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)


def test_heartbeat_window_is_a_delta():
    po = perfobs.PerfObservatory()
    po.on_cycle(
        width=8, tier="bulk", degraded=False, enqueue_s=0.5,
        execute_s=0.2, materialize_s=0.0, stall_s=0.1, commit_s=0.4,
        wall_s=1.0,
    )
    host_ms, dev_ms, _ = po.heartbeat_window()
    assert host_ms == pytest.approx(1000.0, abs=1.0)
    assert dev_ms == pytest.approx(200.0, abs=1.0)
    # nothing new since: the window resets
    host_ms, dev_ms, top = po.heartbeat_window()
    assert host_ms == pytest.approx(0.0, abs=1e-6)
    assert dev_ms == pytest.approx(0.0, abs=1e-6)
    assert top == "none"


# -------------------------------------------------- --baseline gate


def _write_artifact(path, **overrides):
    art = {
        "metric": "pods_scheduled_per_sec_5k_nodes",
        "value": 1000.0,
        "unit": "pods/s",
        "p99_schedule_latency_ms": 100.0,
        "cold_start_seconds": 1.0,
        "live_path_pods_per_s": 500.0,
        "detail": {"phases": {"encode": 1.0, "commit": 2.0}},
    }
    art.update(overrides)
    with open(path, "w") as f:
        json.dump(art, f)
    return art


def _run_gate(baseline, current, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--baseline", str(baseline), "--compare-to", str(current),
         *extra],
        capture_output=True, text=True, timeout=120,
    )


def test_baseline_self_compare_exits_zero(tmp_path):
    """Acceptance pin: an artifact compared against itself is clean."""
    art = tmp_path / "a.json"
    _write_artifact(art)
    out = _run_gate(art, art, "--perf-delta-out",
                    str(tmp_path / "delta.json"))
    assert out.returncode == 0, out.stderr
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["metric"] == "perf_delta" and line["value"] == 1.0
    delta = json.loads((tmp_path / "delta.json").read_text())
    assert not delta["detail"]["regressions"]
    assert {c["name"] for c in delta["detail"]["checks"]} >= {
        "pods_per_s", "p99_ms", "cold_start_seconds",
        "live_path_pods_per_s",
    }


def test_baseline_synthetic_regression_exits_nonzero(tmp_path):
    """Acceptance pin: an injected regression trips the gate."""
    base = tmp_path / "base.json"
    bad = tmp_path / "bad.json"
    _write_artifact(base)
    _write_artifact(bad, value=400.0,
                    p99_schedule_latency_ms=500.0)
    out = _run_gate(base, bad, "--perf-delta-out",
                    str(tmp_path / "delta.json"))
    assert out.returncode == 1, out.stderr
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["value"] == 0.0
    regs = set(line["detail"]["regressions"])
    assert {"pods_per_s", "p99_ms"} <= regs, regs


def test_baseline_accepts_driver_wrapper_format(tmp_path):
    """BENCH_rNN.json files are the driver's {parsed: artifact}
    wrapper; the gate unwraps them."""
    inner = _write_artifact(tmp_path / "inner.json")
    wrapped = tmp_path / "wrapped.json"
    with open(wrapped, "w") as f:
        json.dump({"n": 1, "rc": 0, "tail": "…", "parsed": inner}, f)
    out = _run_gate(wrapped, tmp_path / "inner.json")
    assert out.returncode == 0, out.stderr


def test_compare_artifacts_units():
    import bench

    base = {"value": 100.0, "p99_schedule_latency_ms": 10.0,
            "detail": {"phases": {"encode": 0.1}}}
    # a faster run never regresses; metrics missing on either side skip
    cur = {"value": 200.0}
    d = bench.compare_artifacts(base, cur, tolerance=0.2)
    assert not d["regressions"]
    assert [c["name"] for c in d["checks"]] == ["pods_per_s"]
    # direction matters: throughput down 50% trips, p99 down never does
    d = bench.compare_artifacts(
        base, {"value": 50.0, "p99_schedule_latency_ms": 1.0},
        tolerance=0.2,
    )
    assert d["regressions"] == ["pods_per_s"]
    # phases: relative growth alone is not enough below the absolute
    # floor (0.1s -> 0.3s is 3x but only +0.2s)
    d = bench.compare_artifacts(
        base,
        {"value": 100.0, "detail": {"phases": {"encode": 0.3}}},
        tolerance=0.2,
    )
    assert not d["regressions"]
    d = bench.compare_artifacts(
        {"value": 100.0, "detail": {"phases": {"encode": 1.0}}},
        {"value": 100.0, "detail": {"phases": {"encode": 2.0}}},
        tolerance=0.2,
    )
    assert d["regressions"] == ["phase:encode"]
