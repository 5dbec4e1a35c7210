"""Prometheus text-format validity + histogram quantile estimator.

ISSUE 5 satellites: a STRICT parser over the full `REGISTRY.expose()`
output (HELP/TYPE pairing, sample-name discipline, label escaping,
monotone cumulative histogram buckets, +Inf == _count) run against a
LIVE scheduler after a mixed success / unschedulable / degraded
workload, plus unit tests pinning the linearly-interpolated
`Histogram.quantile` on a known distribution (it used to return the
bucket upper bound, inflating p50 by up to 2x on pow2 buckets).
"""

import re
import urllib.request

import pytest

from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.chaos import Disruptions
from kubernetes_tpu.runtime.cluster import LocalCluster
from kubernetes_tpu.runtime.health import start_health_server
from kubernetes_tpu.runtime.queue import PodBackoff, PriorityQueue
from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.utils.metrics import Histogram

from fixtures import make_node, make_pod

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_SAMPLE_RE = re.compile(
    rf"^({_NAME})(?:\{{(.*)\}})? (-?(?:[0-9]+(?:\.[0-9]+)?"
    r"(?:[eE][+-]?[0-9]+)?|Inf|inf)|NaN)$"
)
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\\n]|\\["\\n])*)"')


def parse_exposition(text: str) -> dict:
    """Strict parser for the Prometheus text format (version 0.0.4).

    Returns {family: {"type": ..., "samples": [(name, {labels}, value)]}}
    and raises AssertionError on any violation: a sample without a
    preceding HELP+TYPE pair, TYPE before HELP, a sample name that
    doesn't belong to the current family (histograms may only append
    _bucket/_sum/_count), malformed label syntax, or an unparseable
    value."""
    families: dict = {}
    helped: set = set()
    current = None  # family name of the preceding TYPE line
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            parts = line.split(" ", 3)
            assert len(parts) >= 3, f"line {lineno}: malformed HELP"
            name = parts[2]
            assert name not in families, (
                f"line {lineno}: duplicate HELP for {name}"
            )
            helped.add(name)
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            assert len(parts) == 4, f"line {lineno}: malformed TYPE"
            _, _, name, mtype = parts
            assert mtype in ("counter", "gauge", "histogram", "summary",
                            "untyped"), f"line {lineno}: bad type {mtype}"
            assert name in helped, (
                f"line {lineno}: TYPE {name} without preceding HELP"
            )
            assert name not in families, (
                f"line {lineno}: duplicate TYPE for {name}"
            )
            families[name] = {"type": mtype, "samples": []}
            current = name
            continue
        assert not line.startswith("#"), f"line {lineno}: stray comment"
        m = _SAMPLE_RE.match(line)
        assert m, f"line {lineno}: unparseable sample {line!r}"
        name, labels_raw, value = m.group(1), m.group(2), m.group(3)
        assert current is not None, (
            f"line {lineno}: sample before any HELP/TYPE"
        )
        allowed = {current}
        if families[current]["type"] == "histogram":
            allowed |= {current + s for s in ("_bucket", "_sum", "_count")}
        assert name in allowed, (
            f"line {lineno}: sample {name} outside family {current}"
        )
        labels = {}
        if labels_raw:
            consumed = 0
            for lm in _LABEL_RE.finditer(labels_raw):
                labels[lm.group(1)] = lm.group(2)
                consumed = lm.end()
            rest = labels_raw[consumed:].strip().strip(",")
            assert not rest, (
                f"line {lineno}: malformed labels {labels_raw!r}"
            )
        float(value.replace("Inf", "inf"))  # parseable
        families[current]["samples"].append((name, labels, float(
            value.replace("Inf", "inf"))))
    return families


def check_histograms(families: dict) -> int:
    """Monotone cumulative buckets, ascending le, +Inf == _count,
    non-negative _sum for every histogram SERIES — a labeled histogram
    family (HistogramVec, e.g. the tier-labeled e2e histogram) exposes
    one child per label set, each with its own bucket ladder, grouped
    here by the label set minus `le`.  Returns how many histogram
    families were checked."""
    checked = 0
    for fam, data in families.items():
        if data["type"] != "histogram":
            continue
        series: dict = {}
        for n, lbl, v in data["samples"]:
            key = frozenset(
                (k, val) for k, val in lbl.items() if k != "le"
            )
            s = series.setdefault(
                key, {"buckets": [], "count": None, "sum": None}
            )
            if n == fam + "_bucket":
                s["buckets"].append((lbl["le"], v))
            elif n == fam + "_count":
                s["count"] = v
            elif n == fam + "_sum":
                s["sum"] = v
        assert series, f"{fam}: no samples"
        for key, s in series.items():
            where = f"{fam}{dict(key) if key else ''}"
            buckets = s["buckets"]
            assert buckets, f"{where}: no buckets"
            assert buckets[-1][0] == "+Inf", (
                f"{where}: last bucket must be +Inf"
            )
            les = [float(le.replace("+Inf", "inf")) for le, _ in buckets]
            assert les == sorted(les), (
                f"{where}: le boundaries not ascending"
            )
            counts = [v for _, v in buckets]
            assert counts == sorted(counts), (
                f"{where}: cumulative bucket counts not monotone: {counts}"
            )
            assert s["count"] is not None, f"{where}: missing _count"
            assert counts[-1] == s["count"], f"{where}: +Inf bucket != _count"
            assert s["sum"] is not None and s["sum"] >= 0.0, (
                f"{where}: missing or negative _sum"
            )
        checked += 1
    return checked


def test_metrics_exposition_valid_after_mixed_live_workload():
    """The full registry text, after a live scheduler ran success +
    unschedulable + DEGRADED (device-lost -> CPU fallback) cycles, must
    survive the strict parser — fetched over HTTP like a real scraper."""
    cache = SchedulerCache()
    queue = PriorityQueue(backoff=PodBackoff(initial=0.01, max_duration=0.05))
    sched = Scheduler(
        cache=cache, queue=queue, binder=lambda p, n: True,
        config=SchedulerConfig(
            disable_preemption=True,
            device_retry_max=0, breaker_failure_threshold=1,
            breaker_open_s=10.0, cpu_fallback=True,
            # ISSUE 7 satellites: the attribution + ledger families must
            # survive the strict parser with live values
            attribution=True, decision_ledger=True,
            # ISSUE 15: the capacity families must carry live values —
            # interval 1 so the 'never' pod's parked backlog solves and
            # materializes within the two cycles below; the 64-core
            # shape makes the overflow fit SOME catalog entry, so the
            # labeled recommended-nodes gauge gets a child
            capacity_planner=True, capacity_interval_cycles=1,
            node_shape_catalog=[
                {"name": "metrics-big", "cpu": "128", "memory": "512Gi"},
            ],
        ),
    )
    # TWO nodes: the placed pod then has a runner-up, so the quality
    # margin family (a labeled histogram — empty families fail the
    # strict checker by design) records a sample on the device cycle
    cache.add_node(make_node("m1", cpu="4", mem="8Gi"))
    cache.add_node(make_node("m2", cpu="8", mem="16Gi"))
    # success + unschedulable in one cycle
    queue.add(make_pod("fits", cpu="100m"))
    queue.add(make_pod("never", cpu="64"))
    sched.run_once(timeout=0.3)
    # degraded cycle: persistent fault trips the breaker, CPU serves it
    dis = Disruptions(LocalCluster())
    dis.device_lost()
    try:
        queue.add(make_pod("degraded", cpu="100m"))
        sched.run_once(timeout=0.3)
    finally:
        dis.clear_device_faults()
    assert sched.device_health.state == "open"
    # materialize the in-flight capacity solve (dispatched on the last
    # cycle with 'never' parked unschedulable) so the gauges below
    # carry the live backlog/overflow/recommendation values
    sched.capacity.finalize()

    srv = start_health_server()
    try:
        h, p = srv.address
        with urllib.request.urlopen(
            f"http://{h}:{p}/metrics", timeout=5
        ) as r:
            assert "text/plain" in r.headers.get("Content-Type", "")
            body = r.read().decode()
    finally:
        srv.stop()

    families = parse_exposition(body)
    assert check_histograms(families) >= 5
    # the workload actually moved the counters the parser just validated
    attempts = families["scheduler_schedule_attempts_total"]["samples"]
    results = {lbl["result"] for _, lbl, v in attempts if v > 0}
    assert {"scheduled", "unschedulable"} <= results
    assert families["scheduler_degraded_cycles_total"]["samples"][0][2] > 0
    # satellite: the per-cycle phase family is exposed and accumulated
    phases = {
        lbl["phase"]: v
        for _, lbl, v in
        families["scheduler_cycle_phase_seconds_total"]["samples"]
    }
    for phase in ("pop", "encode", "dispatch", "commit", "commit_state",
                  "commit_tail"):
        assert phase in phases, f"phase {phase} missing from /metrics"
    assert phases["encode"] > 0.0
    # the commit-path family: both series are exposed, and the placed
    # pods (plain, so each has a pod class) took the class path
    assert families["scheduler_commit_pods_total"]["type"] == "counter"
    commit_paths = {
        lbl["path"]: v
        for _, lbl, v in
        families["scheduler_commit_pods_total"]["samples"]
    }
    assert set(commit_paths) == {"class", "per_pod"}, commit_paths
    assert commit_paths["class"] >= 2, commit_paths
    # ISSUE 7 satellites: the unschedulable pod fed the per-plugin
    # reasons family through the attribution path, and the ledger
    # accounted its cycles (ring-only here — bytes/dropped expose as
    # zero-valued counters, still strict-parser-visible)
    reasons = {
        lbl["plugin"]: v
        for _, lbl, v in
        families["scheduler_unschedulable_reasons_total"]["samples"]
        if v > 0
    }
    assert "PodFitsResources" in reasons, reasons
    ledger_cycles = families["scheduler_ledger_cycles_total"]["samples"]
    assert ledger_cycles and ledger_cycles[0][2] > 0
    for fam in ("scheduler_ledger_bytes_total",
                "scheduler_ledger_dropped_total"):
        assert families[fam]["type"] == "counter"
    # ISSUE 11 satellites: the transfer and observatory families are
    # strict-parser-valid AND carry live values from the workload just
    # run — the snapshot upload + winners fetch both moved bytes, every
    # byte family has a matching calls series, and the phase x width
    # EWMA matrix filled for every cost-model phase
    xfer = {
        (lbl["direction"], lbl["seam"]): v
        for _, lbl, v in families["ktpu_transfer_bytes_total"]["samples"]
    }
    assert xfer[("h2d", "snapshot_upload")] > 0, xfer
    assert xfer[("d2h", "fetch")] > 0, xfer
    calls = {
        (lbl["direction"], lbl["seam"]): v
        for _, lbl, v in families["ktpu_transfer_calls_total"]["samples"]
    }
    for key, nbytes in xfer.items():
        assert calls.get(key, 0) > 0, (key, nbytes, calls)
    ewma_phases = {
        lbl["phase"]
        for _, lbl, v in
        families["scheduler_perf_phase_ewma_seconds"]["samples"]
    }
    from kubernetes_tpu.runtime.perfobs import PHASES

    assert ewma_phases == set(PHASES), ewma_phases
    assert families["scheduler_perfobs_seconds_total"]["type"] == "counter"
    assert families["scheduler_perfobs_seconds_total"]["samples"][0][2] > 0
    # ISSUE 13 satellites: the quality families survive the strict
    # parser WITH live values — the device cycle placed a pod with a
    # runner-up (margin sample in the bulk tier child), counted its
    # feasible candidates, and stamped the hook's own cost counter;
    # the drift/regret families expose as their declared types
    margin = families["scheduler_placement_margin"]
    assert margin["type"] == "histogram"
    m_counts = {
        lbl.get("tier"): v for n, lbl, v in margin["samples"]
        if n.endswith("_count")
    }
    assert m_counts.get("bulk", 0) > 0, m_counts
    feas = families["scheduler_feasible_nodes"]
    assert feas["type"] == "histogram"
    feas_count = [v for n, _, v in feas["samples"] if n.endswith("_count")]
    assert feas_count and feas_count[0] > 0
    assert families["scheduler_placement_regret"]["type"] == "gauge"
    assert (
        families["scheduler_quality_drift_alerts_total"]["type"]
        == "counter"
    )
    assert families["scheduler_quality_seconds_total"]["samples"][0][2] > 0
    # ISSUE 15 satellites: the capacity families survive the strict
    # parser WITH live values — the hook stamped its cost counter, the
    # 'never' pod's parked backlog drove a materialized solve (backlog/
    # overflow gauges non-zero), and the 128-core catalog shape fit the
    # overflow so the labeled recommendation gauge carries a child
    assert (
        families["scheduler_capacity_seconds_total"]["type"] == "counter"
    )
    assert (
        families["scheduler_capacity_seconds_total"]["samples"][0][2] > 0
    )
    assert families["scheduler_capacity_solves_total"]["samples"][0][2] > 0
    backlog = {
        lbl["kind"]: v
        for _, lbl, v in
        families["scheduler_capacity_backlog"]["samples"]
    }
    assert backlog.get("pods", 0) >= 1, backlog
    assert backlog.get("classes", 0) >= 1, backlog
    assert (
        families["scheduler_capacity_overflow_pods"]["samples"][0][2] >= 1
    )
    reco = {
        lbl["shape"]: v
        for _, lbl, v in
        families["scheduler_capacity_recommended_nodes"]["samples"]
    }
    assert reco.get("metrics-big", 0) >= 1, reco
    for fam in ("scheduler_capacity_absorbed_pods",
                "scheduler_capacity_drainable_nodes"):
        assert families[fam]["type"] == "gauge"
    # ISSUE 20 satellites: the timeline families survive the strict
    # parser WITH live values — the commit tail sampled at least once
    # (samples + cost counters, series gauge), and the degraded cycle /
    # breaker trip pushed typed event annotations through the seams
    assert (
        families["scheduler_timeline_samples_total"]["samples"][0][2] > 0
    )
    assert (
        families["scheduler_timeline_seconds_total"]["type"] == "counter"
    )
    assert (
        families["scheduler_timeline_seconds_total"]["samples"][0][2] > 0
    )
    assert families["scheduler_timeline_series"]["samples"][0][2] > 0
    assert families["scheduler_timeline_lag_seconds"]["type"] == "gauge"
    ev_kinds = {
        lbl["kind"]: v
        for _, lbl, v in
        families["scheduler_timeline_events_total"]["samples"]
        if v > 0
    }
    assert "postmortem" in ev_kinds, ev_kinds
    assert "breaker" in ev_kinds, ev_kinds
    assert (
        families["scheduler_timeline_anomalies_total"]["type"]
        == "counter"
    )


def test_quality_family_cardinality_bounded():
    """ISSUE 13 satellite: every labeled quality family declares a
    bounded max_children (the guard that keeps a tier/k/series label
    from leaking series without bound), well under the default."""
    from kubernetes_tpu.utils.metrics import (
        PLACEMENT_MARGIN,
        QUALITY_DRIFT_ALERTS,
    )

    assert PLACEMENT_MARGIN.max_children <= 8
    assert QUALITY_DRIFT_ALERTS.max_children <= 16
    # the label sets in live use stay far inside the bound
    assert PLACEMENT_MARGIN.child_count() <= PLACEMENT_MARGIN.max_children
    assert (
        QUALITY_DRIFT_ALERTS.child_count()
        <= QUALITY_DRIFT_ALERTS.max_children
    )


def test_labeled_families_remove_and_restart():
    """ISSUE 8 satellite: label-set children are removable — the series
    disappears from the exposition and restarts from zero if it comes
    back (the per-width launch-EWMA family depends on this to stay
    bounded)."""
    from kubernetes_tpu.utils.metrics import (
        LabeledCounter,
        LabeledGauge,
        LabeledHistogram,
    )

    c = LabeledCounter("t_rm_counter", label_names=("w",))
    c.inc(3, w="a")
    c.inc(1, w="b")
    assert c.remove(w="a") is True
    assert c.remove(w="a") is False  # already gone
    assert 'w="a"' not in c.expose() and 'w="b"' in c.expose()
    assert c.value(w="a") == 0.0
    c.inc(w="a")
    assert c.value(w="a") == 1.0  # restarted from zero
    assert c.child_count() == 2

    g = LabeledGauge("t_rm_gauge", label_names=("w",))
    g.set(5, w="x")
    assert g.remove(w="x") and 'w="x"' not in g.expose()

    h = LabeledHistogram("t_rm_hist", label_names=("tier",))
    h.observe(0.5, tier="bulk")
    h.observe(0.5, tier="express")
    assert h.child_count() == 2
    assert h.remove(tier="express") is True
    assert h.remove(tier="express") is False
    assert 'tier="express"' not in h.expose()
    assert h.labels(tier="express").total == 0  # fresh ladder


def test_labeled_family_cardinality_guard_warns_once():
    """Past max_children the family logs ONE warning (per family) and
    keeps recording — a leak is made visible without log spam or data
    loss."""
    import logging

    from kubernetes_tpu.utils.metrics import (
        LabeledCounter,
        LabeledHistogram,
    )

    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    logger = logging.getLogger("kubernetes_tpu")
    handler = _Capture(level=logging.WARNING)
    logger.addHandler(handler)
    old_level = logger.level
    logger.setLevel(logging.WARNING)
    try:
        c = LabeledCounter("t_guard", label_names=("k",), max_children=3)
        for i in range(10):
            c.inc(k=f"v{i}")
        warns = [r for r in records if "t_guard" in r]
        assert len(warns) == 1, warns
        assert "3 label sets" in warns[0]
        assert c.child_count() == 10  # recording never dropped
        # an existing key never triggers the guard
        records.clear()
        c.inc(k="v0")
        assert not [r for r in records if "t_guard" in r]

        h = LabeledHistogram("t_guard_h", label_names=("k",),
                             max_children=2)
        for i in range(5):
            h.observe(0.1, k=f"v{i}")
        assert len([r for r in records if "t_guard_h" in r]) == 1
        assert h.child_count() == 5
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)


def test_quantile_interpolates_within_bucket():
    """Known distribution: 1000 evenly spaced samples in [0, 1) over
    quarter buckets — p50/p99 must land ~where the true percentiles
    are, not snap to bucket upper bounds (the old behavior returned
    0.5 for ANY p in (25%, 50%])."""
    h = Histogram("t_interp", buckets=[0.25, 0.5, 0.75, 1.0])
    h.observe_batch([i / 1000 for i in range(1000)])
    # bucket counts: 251 / 250 / 250 / 249 (bisect_left boundary rule)
    p50 = h.quantile(0.5)
    assert p50 == pytest.approx(0.25 + 0.25 * (500 - 251) / 250, abs=1e-9)
    assert abs(p50 - 0.4995) < 0.002  # ~the true median
    p99 = h.quantile(0.99)
    assert p99 == pytest.approx(0.75 + 0.25 * (990 - 751) / 249, abs=1e-9)
    assert abs(p99 - 0.9895) < 0.002


def test_quantile_edges():
    h = Histogram("t_edges", buckets=[1.0, 2.0, 4.0])
    assert h.quantile(0.5) == 0.0  # empty
    h.observe_n(0.5, 10)   # bucket [0, 1]
    h.observe_n(1.5, 10)   # bucket (1, 2]
    assert h.quantile(0.5) == pytest.approx(1.0)    # rank 10 tops bucket 0
    assert h.quantile(0.75) == pytest.approx(1.5)   # halfway into bucket 1
    # overflow bucket reports the highest finite boundary (the
    # histogram_quantile convention)
    h2 = Histogram("t_over", buckets=[1.0, 2.0])
    h2.observe(5.0)
    assert h2.quantile(0.99) == 2.0
    # first bucket interpolates from 0
    h3 = Histogram("t_first", buckets=[1.0, 2.0])
    h3.observe_n(0.5, 4)
    assert h3.quantile(0.5) == pytest.approx(0.5)
