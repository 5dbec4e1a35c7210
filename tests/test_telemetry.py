"""Cluster + device telemetry (ISSUE 8).

Pins the tentpole contracts: the jitted analytics kernel is BIT-EXACT
against the numpy reference on randomized snapshots (including recycled
rows and an empty cluster), the multi-window SLO burn-rate math on
synthetic histories, the slo_burn postmortem trigger + throttle (an
induced deadline-overrun storm fires exactly ONE), the /debug/cluster
endpoint's limit/cap behavior, the memory_stats CPU fallback, and the
heartbeat satellite.
"""

import dataclasses
import json
import logging
import time
import urllib.request

import numpy as np
import pytest

from kubernetes_tpu.ops.analytics import (
    OCC_BINS,
    RESOURCE_NAMES,
    STAT_NAMES,
    analytics_to_dict,
    cluster_analytics,
    cluster_analytics_np,
)
from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.chaos import Disruptions
from kubernetes_tpu.runtime.cluster import LocalCluster
from kubernetes_tpu.runtime.flightrecorder import FlightRecorder
from kubernetes_tpu.runtime.health import start_health_server
from kubernetes_tpu.runtime.queue import PodBackoff, PriorityQueue
from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.runtime.telemetry import (
    SLOEvaluator,
    SLOObjective,
    TelemetryHub,
    device_memory_stats,
)
from kubernetes_tpu.utils import metrics as m

from fixtures import make_node, make_pod


def _mini_scheduler(recorder=None, nodes=1, **cfg_kw):
    cache = SchedulerCache()
    queue = PriorityQueue(backoff=PodBackoff(initial=0.01, max_duration=0.05))
    cfg = SchedulerConfig(disable_preemption=True, **cfg_kw)
    sched = Scheduler(
        cache=cache, queue=queue, binder=lambda p, n: True, config=cfg,
        flight_recorder=recorder,
    )
    for i in range(nodes):
        cache.add_node(make_node(f"n{i}", cpu="4", mem="8Gi"))
    return sched, queue


def _assert_bit_exact(alloc, req, valid):
    dev = cluster_analytics(alloc, req, valid)
    ref = cluster_analytics_np(alloc, req, valid)
    for f in dataclasses.fields(dev):
        a, b = np.asarray(getattr(dev, f.name)), np.asarray(
            getattr(ref, f.name)
        )
        assert np.array_equal(a, b), (
            f"{f.name} differs: kernel={a!r} reference={b!r}"
        )
    return ref


# ------------------------------------------------------- analytics kernel


def test_analytics_bit_exact_on_randomized_snapshots(rng):
    """Tentpole acceptance: the jitted kernel and the numpy reference
    agree to the BIT on randomized snapshots — overcommitted nodes,
    zero-capacity columns, invalid (recycled/padding) rows."""
    for trial in range(8):
        N = int(rng.choice([8, 16, 64, 257, 512, 1000]))
        R = 8
        alloc = (
            rng.uniform(0, 1e4, (N, R)) * rng.integers(0, 2, (N, R))
        ).astype(np.float32)
        req = (alloc * rng.uniform(0, 1.5, (N, R))).astype(np.float32)
        valid = rng.random(N) < 0.8
        _assert_bit_exact(alloc, req, valid)


def test_analytics_bit_exact_on_empty_cluster():
    N, R = 16, 8
    zeros = np.zeros((N, R), np.float32)
    ref = _assert_bit_exact(zeros, zeros, np.zeros(N, bool))
    d = analytics_to_dict(ref)
    assert d["nodes"] == 0
    assert d["fragmentation"] == 0.0
    assert d["utilization"]["cpu"]["p99"] == 0.0
    assert sum(d["occupancy"]) == 0


def test_analytics_bit_exact_on_encoder_snapshot_with_recycled_rows():
    """The real input shape: an encoder-built snapshot after node adds,
    pod commits, and a node REMOVAL (a recycled row the valid mask must
    exclude from every statistic)."""
    from kubernetes_tpu.codec import SnapshotEncoder

    enc = SnapshotEncoder()
    enc.add_nodes([
        make_node(f"n-{i}", cpu="8", mem="16Gi", pods=10) for i in range(6)
    ])
    for i in range(8):
        enc.add_pod(make_pod(f"p-{i}", cpu="1", mem="1Gi",
                             node_name=f"n-{i % 6}"))
    enc.remove_node("n-3")
    snap = enc.snapshot()
    ref = _assert_bit_exact(snap.allocatable, snap.requested, snap.valid)
    d = analytics_to_dict(ref)
    assert d["nodes"] == 5
    assert d["pods_running"] == pytest.approx(7.0)  # n-3's pod went too
    assert 0.0 < d["utilization"]["cpu"]["mean"] <= 1.0
    assert sum(d["occupancy"]) == 5


def test_analytics_semantics_known_cluster():
    """Hand-checked values on a 3-node cluster: utilization stats,
    stranded capacity, fragmentation, occupancy deciles."""
    R = 8
    alloc = np.zeros((4, R), np.float32)
    req = np.zeros((4, R), np.float32)
    valid = np.array([True, True, True, False])
    # node0: half cpu, memory EXHAUSTED -> its free cpu is stranded
    alloc[0, :4] = (4000, 8.0, 10.0, 10)
    req[0, :4] = (2000, 8.0, 0.0, 5)
    # node1: cpu exhausted, half memory -> its free memory is stranded
    alloc[1, :4] = (4000, 8.0, 10.0, 10)
    req[1, :4] = (4000, 4.0, 0.0, 9)
    # node2: idle
    alloc[2, :4] = (2000, 4.0, 10.0, 10)
    # node3 is INVALID and fully loaded — must not count anywhere
    alloc[3, :4] = (1000, 1.0, 1.0, 1)
    req[3, :4] = (1000, 1.0, 1.0, 1)
    d = analytics_to_dict(_assert_bit_exact(alloc, req, valid))
    assert d["nodes"] == 3
    assert d["utilization"]["cpu"]["mean"] == pytest.approx((0.5 + 1.0) / 3)
    assert d["utilization"]["cpu"]["max"] == 1.0
    assert d["utilization"]["memory"]["p50"] == pytest.approx(0.5)
    assert d["stranded"]["cpu"] == pytest.approx(2000.0)   # node0's free cpu
    assert d["stranded"]["memory"] == pytest.approx(4.0)   # node1's free mem
    # free cpu total = 2000 + 0 + 2000; free mem total = 0 + 4 + 4
    assert d["fragmentation"] == pytest.approx(
        0.5 * (2000.0 / 4000.0) + 0.5 * (4.0 / 8.0)
    )
    assert d["largest_free"]["cpu"] == pytest.approx(2000.0)
    # occupancy: 50% -> decile 5, 90% -> decile 9, 0% -> decile 0
    occ = d["occupancy"]
    assert occ[0] == 1 and occ[5] == 1 and occ[9] == 1
    assert sum(occ) == 3
    assert d["pods_running"] == pytest.approx(14.0)
    assert d["imbalance"] > 0.0


def test_analytics_dict_shape():
    N, R = 8, 8
    d = analytics_to_dict(cluster_analytics_np(
        np.ones((N, R), np.float32), np.zeros((N, R), np.float32),
        np.ones(N, bool),
    ))
    assert set(d["utilization"]) == set(RESOURCE_NAMES)
    for res in RESOURCE_NAMES:
        assert set(d["utilization"][res]) == set(STAT_NAMES)
    assert len(d["occupancy"]) == OCC_BINS
    json.dumps(d)  # the /debug/cluster body must serialize


# ------------------------------------------------------------ SLO windows


def test_slo_burn_window_math_synthetic_history():
    """Window math on a synthetic clock: burn = bad fraction within the
    window / error budget, per window."""
    clk = [100.0]
    ev = SLOEvaluator(
        (SLOObjective("o", objective=0.9, fast_window_s=10.0,
                      slow_window_s=100.0),),
        clock=lambda: clk[0],
    )
    # t=100: 8 good, 2 bad -> bad frac 0.2, budget 0.1 -> burn 2.0
    ev.observe("o", good=8, bad=2)
    fast, slow = ev.burn_rates("o")
    assert fast == pytest.approx(2.0) and slow == pytest.approx(2.0)
    # 20s later the events left the fast window but not the slow one
    clk[0] = 120.0
    ev.observe("o", good=10, bad=0)
    fast, slow = ev.burn_rates("o")
    assert fast == pytest.approx(0.0)
    assert slow == pytest.approx((2 / 20) / 0.1)
    # past the slow window everything ages out
    clk[0] = 250.0
    ev.observe("o", good=1, bad=0)
    fast, slow = ev.burn_rates("o")
    assert fast == 0.0 and slow == 0.0
    # unknown objectives are ignored, not an error
    ev.observe("nope", bad=1)


def test_slo_alert_needs_both_windows_and_rearms():
    clk = [0.0]
    ev = SLOEvaluator(
        (SLOObjective("o", objective=0.9, fast_window_s=10.0,
                      slow_window_s=1000.0, burn_threshold=1.0),),
        clock=lambda: clk[0],
    )
    # slow window poisoned by old badness, fast window clean -> no alert
    ev.observe("o", bad=5)
    clk[0] = 500.0
    ev.observe("o", good=50)
    assert ev.evaluate() == []
    # now the fast window burns too -> exactly one alert...
    ev.observe("o", bad=50)
    fired = ev.evaluate()
    assert [f[0] for f in fired] == ["o"]
    # ...and a still-burning followup does NOT re-fire (hysteresis)
    ev.observe("o", bad=5)
    assert ev.evaluate() == []
    # fast recovery re-arms; a fresh burn fires again
    clk[0] = 600.0
    ev.observe("o", good=100)
    assert ev.evaluate() == []
    ev.observe("o", bad=1000)
    assert [f[0] for f in ev.evaluate()] == ["o"]
    assert ev.alerts_total == 2
    assert m.SLO_BURN_RATE.value(objective="o", window="fast") > 1.0


@pytest.mark.chaos
def test_deadline_overrun_storm_fires_one_throttled_slo_burn_postmortem():
    """Acceptance: an induced deadline-overrun storm fires exactly ONE
    throttled slo_burn postmortem, and the /metrics burn-rate gauge for
    the cycle_deadline objective crosses 1.0."""
    fr = FlightRecorder(postmortem_min_interval_s=60.0)
    sched, queue = _mini_scheduler(
        recorder=fr,
        cycle_deadline_s=1e-9,  # every non-empty cycle overruns
        adaptive_batch=True, batch_size_min=1, batch_size=4,
    )
    for i in range(12):
        queue.add(make_pod(f"storm-{i}", cpu="10m"))
    deadline = time.monotonic() + 30
    while queue.has_schedulable() and time.monotonic() < deadline:
        sched.run_once(timeout=0.0)
    assert sched.telemetry is not None
    pms = fr.postmortems(trigger="slo_burn")
    assert len(pms) == 1, (
        f"expected exactly one throttled slo_burn postmortem, got "
        f"{[p['detail'] for p in pms]}"
    )
    assert "cycle_deadline" in pms[0]["detail"]
    fast = m.SLO_BURN_RATE.value(objective="cycle_deadline", window="fast")
    slow = m.SLO_BURN_RATE.value(objective="cycle_deadline", window="slow")
    assert fast >= 1.0 and slow >= 1.0
    assert m.SLO_ALERTS.value(objective="cycle_deadline") >= 1


# ----------------------------------------------------------- the live hub


def test_scheduler_telemetry_samples_and_gauges():
    sched, queue = _mini_scheduler(nodes=2)
    for i in range(4):
        queue.add(make_pod(f"p{i}", cpu="500m"))
    sched.run_once(timeout=0.2)
    queue.add(make_pod("late", cpu="500m"))
    sched.run_once(timeout=0.2)
    hub = sched.telemetry
    s = hub.summary()
    assert s["samples"] >= 1 and s["cycles"] >= 2
    a = s["analytics"]
    assert a["nodes"] == 2
    # the sample reflects the SNAPSHOT its cycle dispatched against
    # (one-cycle lag): by cycle 2 the first batch's pods are visible
    assert a["utilization"]["cpu"]["mean"] > 0.0
    assert 0.0 <= a["fragmentation"] <= 1.0
    assert m.CLUSTER_NODES.value == 2.0
    assert m.CLUSTER_UTILIZATION.value(resource="cpu", stat="mean") > 0.0
    assert m.PENDING_PRESSURE.value(tier="bulk") == 0.0
    # the sample source is the device-resident path on a healthy engine
    assert hub.debug_payload()["samples"][-1]["source"] == "device"
    # launch EWMA recorded for the dispatched width
    assert hub._launch_ewma, "no launch EWMA recorded"


def test_telemetry_interval_cycles_amortizes_sampling():
    sched, queue = _mini_scheduler(telemetry_interval_cycles=3)
    for i in range(6):
        queue.add(make_pod(f"p{i}", cpu="10m"))
        sched.run_once(timeout=0.2)
    hub = sched.telemetry
    hub.summary()
    # 6 cycles at interval 3 -> 2 dispatches, ~1-2 materialized samples
    assert hub.cycles_total >= 6
    assert 1 <= hub.samples_total <= 2


@pytest.mark.chaos
def test_degraded_cycle_falls_back_to_host_analytics():
    """Breaker open -> resident device buffers are invalidated; the
    telemetry stream must continue through the numpy reference."""
    sched, queue = _mini_scheduler(
        device_retry_max=0, breaker_failure_threshold=1,
        breaker_open_s=10.0, cpu_fallback=True,
    )
    dis = Disruptions(LocalCluster())
    dis.device_lost()
    try:
        queue.add(make_pod("degraded", cpu="100m"))
        sched.run_once(timeout=0.2)
        queue.add(make_pod("degraded-2", cpu="100m"))
        sched.run_once(timeout=0.2)
    finally:
        dis.clear_device_faults()
    assert sched.device_health.state == "open"
    payload = sched.telemetry.debug_payload()
    assert payload["samples"], "telemetry stream died with the device"
    assert payload["samples"][-1]["source"] == "host"


def test_samples_are_built_on_the_telemetry_worker():
    """on_cycle dispatches and returns: the fetch, the gauges and the
    ring sample are the worker's, in dispatch order, and summary() waits
    for the newest of them."""
    import threading

    hub = TelemetryHub()
    built = []
    sample = hub._sample

    def record(cycle, *rest):
        built.append((cycle, threading.current_thread().name))
        sample(cycle, *rest)

    hub._sample = record
    N, R = 4, 8
    alloc = np.ones((N, R), np.float32)
    req = np.zeros((N, R), np.float32)
    valid = np.ones(N, bool)
    for c in range(5):
        hub.on_cycle(cycle=c, tier="bulk", cycle_s=0.01, placed=1,
                     unschedulable=0, host_snapshot=(alloc, req, valid))
    s = hub.summary()
    assert built == [(c, "ktpu-telemetry") for c in range(5)]
    assert s["samples"] == 5 and s["cycle"] == 4
    assert [x["cycle"] for x in hub.debug_payload()["samples"]] == [
        0, 1, 2, 3, 4,
    ]


def test_telemetry_off_removes_the_hook():
    sched, queue = _mini_scheduler(telemetry=False)
    assert sched.telemetry is None
    queue.add(make_pod("p", cpu="100m"))
    sched.run_once(timeout=0.2)  # must not crash without the hub


# --------------------------------------------------- device runtime facts


def test_memory_stats_fallback_on_cpu():
    """XLA:CPU devices return None from memory_stats(): the helper must
    yield {} without raising, and set no HBM gauges."""
    import jax

    out = device_memory_stats()
    if jax.default_backend() == "cpu":
        assert out == {}
    # whatever the backend, the gauge family must still expose cleanly
    assert "ktpu_device_hbm_bytes" in m.DEVICE_HBM.expose()


def test_launch_ewma_and_prune():
    hub = TelemetryHub()
    hub.note_launch(256, 0.010)
    first = hub._launch_ewma[256]
    assert first == pytest.approx(0.010)
    hub.note_launch(256, 0.020)
    assert 0.010 < hub._launch_ewma[256] < 0.020
    hub.note_launch(512, 0.030)
    assert m.LAUNCH_EWMA.value(width="512") == pytest.approx(0.030)
    hub.prune_widths({256})
    assert 512 not in hub._launch_ewma
    assert m.LAUNCH_EWMA.value(width="512") == 0.0
    assert m.LAUNCH_EWMA.value(width="256") > 0.0


# ------------------------------------------------------- /debug/cluster


def test_debug_cluster_endpoint_on_health_server_with_limit():
    sched, queue = _mini_scheduler()
    for i in range(3):
        queue.add(make_pod(f"p{i}", cpu="100m"))
        sched.run_once(timeout=0.2)
    sched.telemetry.summary()  # drain the in-flight sample
    srv = start_health_server()
    try:
        h, p = srv.address
        with urllib.request.urlopen(
            f"http://{h}:{p}/debug/cluster", timeout=5
        ) as r:
            assert r.headers["Content-Type"] == "application/json"
            body = json.loads(r.read())
        with urllib.request.urlopen(
            f"http://{h}:{p}/debug/cluster?limit=1", timeout=5
        ) as r:
            limited = json.loads(r.read())
    finally:
        srv.stop()
    assert body["samples"] and body["summary"]["analytics"]["nodes"] == 1
    assert len(limited["samples"]) == 1
    assert limited["samples"][0] == body["samples"][-1]  # newest kept


def test_debug_cluster_endpoint_on_apiserver_inflight_exempt():
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.apiserver.fairness import FlowControlConfig

    sched, queue = _mini_scheduler()
    queue.add(make_pod("p", cpu="100m"))
    sched.run_once(timeout=0.2)
    sched.telemetry.summary()
    # a zero-inflight limiter rejects EVERY non-exempt request: the
    # debug endpoint must still answer (diagnosing an overload needs it)
    srv = APIServer(
        cluster=LocalCluster(),
        flow_control=FlowControlConfig(
            max_inflight_readonly=1, max_inflight_mutating=1,
            queue_length_per_flow=0, queue_wait_timeout_s=0.01,
        ),
    ).start()
    try:
        with urllib.request.urlopen(
            f"{srv.url}/debug/cluster?limit=2", timeout=5
        ) as r:
            body = json.loads(r.read())
    finally:
        srv.stop()
    assert "summary" in body and "samples" in body


def test_debug_cluster_body_respects_response_cap():
    """The shared bounded_json halving: a tiny cap forces the sample
    list down (well-formed JSON either way)."""
    from kubernetes_tpu.runtime.ledger import debug_body

    hub = TelemetryHub(ring_capacity=64)
    N, R = 8, 8
    alloc = np.ones((N, R), np.float32)
    req = np.zeros((N, R), np.float32)
    valid = np.ones(N, bool)
    for c in range(40):
        hub.on_cycle(cycle=c, tier="bulk", cycle_s=0.01, placed=1,
                     unschedulable=0, host_snapshot=(alloc, req, valid))
    hub.summary()
    full = json.loads(debug_body(hub.debug_payload, ""))
    assert len(full["samples"]) >= 30
    capped = json.loads(debug_body(hub.debug_payload, "", cap=8192))
    assert len(capped["samples"]) < len(full["samples"])


# ------------------------------------------------------------- heartbeat


def test_heartbeat_line_emitted_and_off_when_zero():
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    logger = logging.getLogger("kubernetes_tpu")
    handler = _Capture(level=logging.INFO)
    old_level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        sched, queue = _mini_scheduler(heartbeat_s=0.01)
        queue.add(make_pod("hb", cpu="100m"))
        sched.run_once(timeout=0.2)
        time.sleep(0.02)
        sched.run_once(timeout=0.0)  # idle poll must still heartbeat
        beats = [r for r in records if r.startswith("heartbeat:")]
        assert beats, "no heartbeat line on a quiet loop"
        line = beats[-1]
        for field in ("cycles=", "placed=", "unschedulable=", "active=",
                      "express=", "breaker=", "batch=", "hbm_bytes="):
            assert field in line, f"heartbeat line missing {field}: {line}"
        assert "placed=1" in line
        assert "breaker=closed" in line

        # off when 0 (the default): no heartbeat however long we wait
        records.clear()
        sched2, queue2 = _mini_scheduler()
        queue2.add(make_pod("quiet", cpu="100m"))
        sched2.run_once(timeout=0.2)
        time.sleep(0.02)
        sched2.run_once(timeout=0.0)
        assert not [r for r in records if r.startswith("heartbeat:")]
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)


# -------------------------------------------------------------- /metrics


def test_telemetry_families_survive_strict_metrics_parser():
    from test_metrics_format import check_histograms, parse_exposition

    sched, queue = _mini_scheduler()
    queue.add(make_pod("p", cpu="100m"))
    sched.run_once(timeout=0.2)
    sched.telemetry.summary()
    families = parse_exposition(m.REGISTRY.expose())
    check_histograms(families)
    for fam in (
        "scheduler_cluster_utilization_ratio",
        "scheduler_cluster_fragmentation_index",
        "scheduler_cluster_dominant_share_stddev",
        "scheduler_cluster_pods_per_node_occupancy_nodes",
        "scheduler_pending_pressure_pods",
        "scheduler_launch_duration_ewma_seconds",
        "scheduler_slo_burn_rate",
        "scheduler_telemetry_seconds_total",
        "ktpu_device_hbm_bytes",
        "ktpu_compile_cache_events_total",
        "ktpu_backend_compile_seconds_total",
    ):
        assert fam in families, f"{fam} missing from /metrics"
    util = [
        (lbl, v) for _, lbl, v in
        families["scheduler_cluster_utilization_ratio"]["samples"]
    ]
    assert len(util) == 20  # 4 resources x 5 stats
    for lbl, v in util:
        assert 0.0 <= v <= 1.0 or lbl["resource"] == "ephemeral"
