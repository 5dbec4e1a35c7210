"""Placement-quality observatory (ISSUE 13).

Pins the tentpole contracts: the engines' `quality_topk` static flag is
OUTPUT-ONLY — placements bit-identical flag-on/off for both engines
and the live Scheduler (single-chip and the 8-virtual-device sharded
mesh) with winner == top-1 everywhere; the
observatory's margin/feasible/regret records off a live run; the
dual-window drift detector's fire-once/re-arm hysteresis and its
postmortem seam; the FFD counterfactual's per-bin-capacity binpack; and
the ledger's top-k blocks replaying into offline quality figures.
"""

import time

import numpy as np
import pytest

from kubernetes_tpu.api.factory import make_node, make_pod
from kubernetes_tpu.codec import SnapshotEncoder
from kubernetes_tpu.models.batched import (
    encode_batch_ports,
    make_sequential_scheduler,
)
from kubernetes_tpu.models.speculative import make_speculative_scheduler
from kubernetes_tpu.ops.select import select_host, select_topk
from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.queue import PriorityQueue
from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig

ZONE = "failure-domain.beta.kubernetes.io/zone"


# --------------------------------------------------------------- helpers


def _skewed_nodes(n=16):
    """Heterogeneous capacities + labels: scores differ across nodes, so
    margins are non-degenerate (an all-identical fleet ties everything
    to margin 0 — also a valid signal, but not the one under test)."""
    out = []
    for i in range(n):
        out.append(make_node(
            f"n{i}", cpu=str(2 + (i % 5) * 2), mem=f"{4 + (i % 3) * 4}Gi",
            labels={ZONE: f"z-{i % 3}", "tier": "a" if i % 3 else "b"},
        ))
    return out


def _pods(n, prefix="p"):
    return [
        make_pod(
            f"{prefix}{i}", cpu="300m", mem="256Mi",
            labels={"app": f"d{i % 4}"},
            node_selector={"tier": "a"} if i % 5 == 0 else None,
        )
        for i in range(n)
    ]


def _encode(enc, pods):
    batch = enc.encode_pods(pods)
    ports = encode_batch_ports(enc, pods)
    return batch, ports


def _engine_kw(enc):
    return dict(
        unsched_taint_key=enc.interner.intern(
            "node.kubernetes.io/unschedulable"
        ),
        zone_key_id=enc.getzone_key,
    )


# ------------------------------------------------------- select_topk unit


def test_select_topk_winner_pinned_and_sorted(rng):
    import jax.numpy as jnp

    for trial in range(20):
        n = int(rng.integers(3, 24))
        scores = jnp.asarray(
            rng.integers(0, 4, size=n).astype(np.float32)
        )  # coarse scores force ties
        mask = jnp.asarray(rng.random(n) > 0.3)
        li = int(rng.integers(0, 7))
        host, feasible = select_host(scores, mask, jnp.int32(li))
        k = min(3, n)
        q = select_topk(scores, mask, host, feasible, k)
        tn = np.asarray(q.top_nodes)
        ts = np.asarray(q.top_scores)
        feas = int(np.asarray(q.feasible))
        assert feas == int(np.asarray(mask).sum())
        if bool(np.asarray(feasible)):
            # winner pinned at column 0 even when tie rotation picked a
            # non-first-occurrence argmax
            assert tn[0] == int(np.asarray(host))
            # runner-ups descending, none better than the winner's score
            valid = ts[tn >= 0]
            assert (valid[0] >= valid[1:] - 1e-6).all()
            if len(valid) > 2:
                assert (np.diff(valid[1:]) <= 1e-6).all()
            # -1 fill exactly where fewer than k feasible
            assert (tn >= 0).sum() == min(k, feas)
        else:
            assert (tn == -1).all()


def test_select_topk_k1():
    import jax.numpy as jnp

    scores = jnp.asarray(np.asarray([1.0, 3.0, 2.0], np.float32))
    mask = jnp.asarray(np.asarray([True, True, True]))
    host, feasible = select_host(scores, mask, jnp.int32(0))
    q = select_topk(scores, mask, host, feasible, 1)
    assert np.asarray(q.top_nodes).tolist() == [1]
    assert float(np.asarray(q.top_scores)[0]) == 3.0


# ------------------------------------------------- engine identity pins


def test_sequential_quality_identity_and_winner_pinning():
    enc = SnapshotEncoder()
    enc.add_nodes(_skewed_nodes())
    pods = _pods(12)
    batch, ports = _encode(enc, pods)
    cluster = enc.snapshot()
    kw = _engine_kw(enc)
    plain = make_sequential_scheduler(**kw)
    qual = make_sequential_scheduler(**kw, quality_topk=3)
    h0 = np.asarray(plain(cluster, batch, ports, np.int32(5))[0])
    out = qual(cluster, batch, ports, np.int32(5))
    hq, q = np.asarray(out[0]), out[2]
    assert np.array_equal(h0, hq)
    tn = np.asarray(q.top_nodes)[: len(pods)]
    ts = np.asarray(q.top_scores)[: len(pods)]
    feas = np.asarray(q.feasible)[: len(pods)]
    placed = hq[: len(pods)] >= 0
    assert np.array_equal(tn[placed, 0], hq[: len(pods)][placed])
    assert (feas[placed] >= 1).all()
    # runner-up scores never exceed the winner's
    two = placed & (tn[:, 1] >= 0)
    assert two.any()
    assert (ts[two, 0] >= ts[two, 1] - 1e-5).all()


def test_sequential_quality_nonzero_margin_on_unique_best():
    """A deterministic non-tie: one clean node vs one PreferNoSchedule-
    tainted node — TaintToleration makes the winner strictly better, so
    the reported margin must be positive (ties elsewhere report 0, also
    a valid signal, but this pins the gap math itself)."""
    enc = SnapshotEncoder()
    enc.add_node(make_node("clean", cpu="8", mem="16Gi"))
    enc.add_node(make_node(
        "tainted", cpu="8", mem="16Gi",
        taints=[{"key": "soft", "value": "x", "effect": "PreferNoSchedule"}],
    ))
    pods = [make_pod("one", cpu="100m", mem="64Mi")]
    batch, ports = _encode(enc, pods)
    cluster = enc.snapshot()
    fn = make_sequential_scheduler(**_engine_kw(enc), quality_topk=2)
    out = fn(cluster, batch, ports, np.int32(0))
    hq, q = np.asarray(out[0]), out[2]
    tn = np.asarray(q.top_nodes)[0]
    ts = np.asarray(q.top_scores)[0]
    assert hq[0] == tn[0] == 0          # the clean node wins
    assert tn[1] == 1                   # the tainted one is runner-up
    assert ts[0] - ts[1] > 0.5, (ts[0], ts[1])


def test_sequential_quality_rides_attribution():
    """Both static flags on one launch: output order is
    (hosts, cluster, Attribution, TopKQuality), winners unchanged."""
    enc = SnapshotEncoder()
    enc.add_nodes(_skewed_nodes())
    pods = _pods(8)
    batch, ports = _encode(enc, pods)
    cluster = enc.snapshot()
    kw = _engine_kw(enc)
    plain = make_sequential_scheduler(**kw)
    both = make_sequential_scheduler(
        **kw, attribution=True, quality_topk=3
    )
    h0 = np.asarray(plain(cluster, batch, ports, np.int32(0))[0])
    out = both(cluster, batch, ports, np.int32(0))
    assert len(out) == 4
    assert np.array_equal(h0, np.asarray(out[0]))
    attrib, q = out[2], out[3]
    assert np.asarray(attrib.reason_counts).shape[0] == batch.n_pods
    placed = h0[: len(pods)] >= 0
    assert np.array_equal(
        np.asarray(q.top_nodes)[: len(pods)][placed, 0],
        h0[: len(pods)][placed],
    )


@pytest.mark.parametrize("packed", [False, True])
def test_speculative_quality_identity(packed):
    from kubernetes_tpu.models import speculative

    enc = SnapshotEncoder()
    enc.add_nodes(_skewed_nodes())
    pods = _pods(12, prefix=f"sp{int(packed)}-")
    batch, ports = _encode(enc, pods)
    cluster = enc.snapshot()
    kw = _engine_kw(enc)
    old = speculative.FORCE_PACKED_PATH
    speculative.FORCE_PACKED_PATH = packed
    try:
        plain = make_speculative_scheduler(**kw)
        qual = make_speculative_scheduler(**kw, quality_topk=3)
        h0 = np.asarray(plain(cluster, batch, ports, np.int32(0))[0])
        out = qual(cluster, batch, ports, np.int32(0))
        hq, q = np.asarray(out[0]), out[2]
    finally:
        speculative.FORCE_PACKED_PATH = old
    assert np.array_equal(h0, hq)
    placed = hq[: len(pods)] >= 0
    assert np.array_equal(
        np.asarray(q.top_nodes)[: len(pods)][placed, 0],
        hq[: len(pods)][placed],
    )
    assert (np.asarray(q.feasible)[: len(pods)][placed] >= 1).all()


@pytest.mark.parametrize("packed", [False, True])
def test_speculative_quality_identity_under_hybrid_redo(packed):
    """A contended batch (capacity pressure -> real bounces + an
    unscheduled pod) trips the exactness redo on BOTH paths; quality
    must then describe the sequential scan's placements."""
    from kubernetes_tpu.models import speculative

    enc = SnapshotEncoder()
    enc.add_nodes([make_node(f"m{i}", cpu="1", mem="1Gi")
                   for i in range(2)])
    pods = [make_pod(f"t{int(packed)}-{i}", cpu="600m", mem="256Mi")
            for i in range(4)]
    batch, ports = _encode(enc, pods)
    cluster = enc.snapshot()
    kw = _engine_kw(enc)
    old = speculative.FORCE_PACKED_PATH
    speculative.FORCE_PACKED_PATH = packed
    try:
        plain = make_speculative_scheduler(**kw)
        qual = make_speculative_scheduler(**kw, quality_topk=3)
        h0 = np.asarray(plain(cluster, batch, ports, np.int32(0))[0])
        out = qual(cluster, batch, ports, np.int32(0))
        hq, q = np.asarray(out[0]), out[2]
    finally:
        speculative.FORCE_PACKED_PATH = old
    assert np.array_equal(h0, hq)
    n = len(pods)
    assert (hq[:n] < 0).any()  # the contention actually bit
    placed = hq[:n] >= 0
    assert np.array_equal(
        np.asarray(q.top_nodes)[:n][placed, 0], hq[:n][placed]
    )
    # unschedulable pods carry all -1 rows
    assert (np.asarray(q.top_nodes)[:n][~placed] == -1).all()


# --------------------------------------------------- live scheduler pins


def _live(quality_k, shard=0, interval=1, nodes=None, **cfg_kw):
    cache = SchedulerCache(SnapshotEncoder())
    for n in (nodes or _skewed_nodes()):
        cache.add_node(n)
    kw = dict(
        batch_size=8, batch_window_s=0.0, disable_preemption=True,
        batched_commit=True, pipeline_commit=True,
        quality_top_k=quality_k, quality_interval_cycles=interval,
        shard_devices=shard,
    )
    kw.update(cfg_kw)
    return Scheduler(
        cache=cache, queue=PriorityQueue(), binder=lambda p, n: True,
        config=SchedulerConfig(**kw),
    )


def _drain(s, budget_s=120.0):
    deadline = time.monotonic() + budget_s
    while time.monotonic() < deadline:
        got = s.run_once(timeout=0.0)
        if got == 0 and not s.pipeline_pending:
            if not s.queue.has_schedulable():
                break
            time.sleep(0.002)
    s.flush_pipeline()


def _placements(s):
    return {
        (r.pod.namespace, r.pod.name): r.node for r in s.results
    }


def test_live_scheduler_identity_quality_on_off():
    """The whole live path (pop -> dispatch -> fence -> commit) places
    identically with the quality seam on and off."""
    runs = {}
    for k in (0, 3):
        s = _live(k)
        for p in _pods(40, prefix=f"lq{k}-"):
            # same pod NAMES across runs so the placement maps compare
            p.metadata.name = p.name.replace(f"lq{k}-", "lq-")
            s.queue.add(p)
        _drain(s)
        runs[k] = _placements(s)
        assert s.quality is None if k == 0 else s.quality is not None
    assert runs[0] and runs[0] == runs[3]


@pytest.mark.sharded
def test_sharded_live_quality_identity():
    """8-virtual-device node-sharded mesh: quality on/off placement
    identity AND sharded-vs-single-chip identity with quality on — the
    cross-shard top-k reduce cannot perturb the argmax."""
    maps = {}
    for tag, (shard, k) in {
        "single_q": (0, 3), "mesh_q": (8, 3), "mesh_plain": (8, 0),
    }.items():
        s = _live(k, shard=shard)
        for p in _pods(32, prefix=f"sh{tag}-"):
            p.metadata.name = p.name.replace(f"sh{tag}-", "sh-")
            s.queue.add(p)
        _drain(s)
        maps[tag] = _placements(s)
        if k:
            assert s.quality is not None
            assert s.quality.decisions_total >= 32
    assert maps["single_q"] == maps["mesh_q"] == maps["mesh_plain"]


def test_quality_records_margin_feasible_regret():
    s = _live(3, interval=1)
    for p in _pods(48, prefix="qr-"):
        s.queue.add(p)
    _drain(s)
    s.quality.finalize()
    summ = s.quality.summary()
    assert summ["decisions"] >= 48
    assert summ["margin"]["count"] > 0
    # skewed fleet: the sliding window has non-tied margins
    assert summ["margin"]["p50"] >= 0.0
    assert summ["feasible"]["p50"] >= 1
    assert summ["regret"] is not None and summ["regret"]["ratio"] >= 1.0
    assert summ["regret"]["ffd_nodes"] >= 1
    # payload shape + limit contract
    pay = s.quality.debug_payload(limit=2)
    assert len(pay["samples"]) <= 2
    assert pay["summary"]["top_k"] == 3
    sample = pay["samples"][-1]
    assert {"cycle", "tier", "pods", "placed"} <= set(sample)


def test_quality_examples_carry_attribution_components():
    """With the sequential attribution seam active the ring examples
    name per-plugin score components for winner vs runner-up."""
    s = _live(3, interval=4, attribution=True)
    for p in _pods(16, prefix="qa-"):
        s.queue.add(p)
    _drain(s)
    samples = s.quality.debug_payload()["samples"]
    examples = [e for smp in samples for e in smp.get("examples", [])]
    assert examples, "no per-decision examples recorded"
    with_comp = [e for e in examples if "winner_components" in e]
    assert with_comp, "attribution components missing from examples"
    ex = with_comp[0]
    assert ex["winner"] >= 0 and isinstance(ex["winner_components"], dict)
    assert ex["winner_components"], ex


def test_quality_absent_when_disabled():
    s = _live(0)
    assert s.quality is None
    for p in _pods(8, prefix="qd-"):
        s.queue.add(p)
    _drain(s)  # no quality hook, no crash


def test_heartbeat_line_carries_quality_fields():
    import logging

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
        s = _live(3, interval=1, heartbeat_s=0.01)
        for p in _pods(24, prefix="hb-"):
            s.queue.add(p)
        _drain(s)
        time.sleep(0.02)
        s.run_once(timeout=0.0)  # idle poll fires the heartbeat
        beats = [r for r in records if r.startswith("heartbeat:")]
        assert beats, "no heartbeat line"
        line = beats[-1]
        for field in ("margin=", "regret="):
            assert field in line, f"heartbeat missing {field}: {line}"
        # at least one regret sample materialized at interval 1, so the
        # figure on the line is live, not the 0.0 placeholder
        assert "regret=0.00" not in line, line
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)


# -------------------------------------------------------- drift detector


def test_step_detector_fires_once_and_rearms():
    from kubernetes_tpu.runtime.quality import StepDetector

    det = StepDetector("margin", threshold=0.25, min_samples=8)
    fired = [det.update(1.0) for _ in range(20)]
    assert not any(fired)
    # a step down: fast window leaves the slow baseline
    fired = [det.update(0.1) for _ in range(10)]
    assert sum(fired) == 1, "step must fire exactly once"
    assert det.active
    # staying at the new level: slow converges, detector re-arms
    for _ in range(400):
        det.update(0.1)
    assert not det.active
    # a second step fires again
    assert any(det.update(1.0) for _ in range(10))
    assert det.alerts == 2


def test_drift_alert_fires_metric_and_postmortem():
    from kubernetes_tpu.runtime.quality import QualityObservatory
    from kubernetes_tpu.ops.select import TopKQuality
    from kubernetes_tpu.utils import metrics as m

    calls = []
    obs = QualityObservatory(
        top_k=2, interval_cycles=10_000,
        postmortem=lambda trig, det: calls.append((trig, det)),
        drift_threshold=0.25, drift_min_samples=4,
    )
    before = m.QUALITY_DRIFT_ALERTS.value(series="margin")

    def cycle(i, margin):
        q = TopKQuality(
            top_nodes=np.asarray([[0, 1]], np.int32),
            top_scores=np.asarray([[10.0, 10.0 - margin * 10.0]],
                                  np.float32),
            feasible=np.asarray([2], np.int32),
        )
        obs.on_cycle(cycle=i, tier="bulk", degraded=False,
                     hosts=np.asarray([0], np.int32), n_pods=1, quality=q)

    for i in range(12):
        cycle(i, 0.8)
    for i in range(12, 24):
        cycle(i, 0.01)  # margin collapse
    assert obs.drift_alerts_total >= 1
    assert m.QUALITY_DRIFT_ALERTS.value(series="margin") > before
    assert calls and calls[0][0] == "quality_drift"
    assert "margin" in calls[0][1]


def test_on_cycle_rejects_unpinned_winner():
    """The observatory enforces the winner == top-1 contract — a future
    engine regression surfaces as a loud failure, not silent garbage."""
    from kubernetes_tpu.runtime.quality import QualityObservatory
    from kubernetes_tpu.ops.select import TopKQuality

    obs = QualityObservatory(top_k=2)
    q = TopKQuality(
        top_nodes=np.asarray([[1, 0]], np.int32),
        top_scores=np.asarray([[5.0, 4.0]], np.float32),
        feasible=np.asarray([2], np.int32),
    )
    with pytest.raises(AssertionError):
        obs.on_cycle(cycle=0, tier="bulk", degraded=False,
                     hosts=np.asarray([0], np.int32), n_pods=1, quality=q)


# --------------------------------------------------- FFD counterfactual


def test_binpack_ffd_per_bin_capacities():
    from kubernetes_tpu.models.binpack import binpack_ffd

    caps = np.asarray([[0.0, 0.0], [4.0, 4.0], [2.0, 2.0]], np.float32)
    reqs = np.asarray(
        [[2.0, 2.0], [2.0, 2.0], [2.0, 2.0], [2.0, 2.0]], np.float32
    )
    used, loads, placed = binpack_ffd(reqs, caps, max_bins=3)
    assert int(used) == 2               # the zero bin is never used
    assert bool(np.asarray(placed)[:3].all())
    assert not bool(np.asarray(placed)[3])  # 3 fit (2+1), 4th overflows
    assert np.asarray(loads)[0].sum() == 0.0


def test_regret_counterfactual_kernel():
    from kubernetes_tpu.runtime.quality import _ffd_counterfactual
    import jax

    alloc = np.asarray([[4.0, 4.0]] * 4, np.float32)
    used = np.asarray([[0.0, 0.0]] * 4, np.float32)
    valid = np.asarray([True, True, True, False])
    reqs = np.asarray([[1.0, 1.0]] * 6 + [[0.0, 0.0]] * 2, np.float32)
    nodes, placed, real = jax.jit(_ffd_counterfactual)(
        alloc, used, valid, reqs
    )
    assert int(real) == 6
    assert int(placed) == 6
    assert int(nodes) == 2  # 6 unit pods into 4-cap bins -> 2 bins


# ------------------------------------------------- ledger + replay seam


def test_ledger_quality_roundtrip_and_offline_replay(tmp_path):
    from kubernetes_tpu.runtime.ledger import (
        DecisionLedger,
        read_ledger,
        replay,
    )

    path = str(tmp_path / "quality.ledger")
    cache = SchedulerCache(SnapshotEncoder())
    for n in _skewed_nodes():
        cache.add_node(n)
    ledger = DecisionLedger(path=path)
    s = Scheduler(
        cache=cache, queue=PriorityQueue(), binder=lambda p, n: True,
        config=SchedulerConfig(
            batch_size=8, batch_window_s=0.0, disable_preemption=True,
        ),
        ledger=ledger,
    )
    for p in _pods(24, prefix="lg-"):
        s.queue.add(p)
    _drain(s)
    assert ledger.flush(30)
    _, recs = read_ledger(path)
    assert recs
    for rec in recs:
        q = rec["quality"]
        assert q is not None, "record lost its top-k block"
        n = int(rec["n_pods"])
        w = np.asarray(rec["winners"])[:n]
        tn = np.asarray(q["top_nodes"])[:n]
        placed = w >= 0
        assert np.array_equal(tn[placed, 0], w[placed])
        assert np.asarray(q["feasible"]).shape[0] >= n
    out = replay(path)
    assert out["bit_identical"], out
    q = out["quality"]
    assert q["cycles_with_topk"] == out["cycles"]
    assert q["margins"] > 0
    assert q["feasible_p50"] >= 1
