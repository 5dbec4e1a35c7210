"""Queue-sharded scheduler replicas + optimistic conflict reconciler
(ISSUE 14).

Covers: stable hash-shard pops (add/delete/readd stability, guards
spanning shards), the reconciler edge matrix (zero-conflict fast path
allocation-free, all-N-conflict admitting exactly the sequenced winner,
DRF tiebreak ordering, quota vetoes, conflict against a DEGRADED
replica's CPU-adapter cycle), the per-scheduler observability installs
with the explicit process aggregate (two-replica pin), the new metric
families under the strict /metrics parser, GET /debug/replicas on both
servers, heartbeat fields, ledger replica+seq replay, and the
invariant-checker-clean N-replica overload storm (chaos marker).
"""

from __future__ import annotations

import json
import time
import urllib.request

import pytest

from kubernetes_tpu.codec.encoder import SnapshotEncoder
from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.queue import PriorityQueue
from kubernetes_tpu.runtime.replicas import SchedulerReplicaSet
from kubernetes_tpu.runtime.scheduler import SchedulerConfig
from kubernetes_tpu.utils import metrics as m

from fixtures import TEST_DIMS, make_node, make_pod

pytestmark = pytest.mark.replicas


def _config(**kw) -> SchedulerConfig:
    base = dict(
        batch_size=8,
        batch_window_s=0.0,
        engine="sequential",
        disable_preemption=True,
        telemetry=True,
        quality_top_k=0,   # keep the tiny test launches lean
    )
    base.update(kw)
    return SchedulerConfig(**base)


def _replica_set(n=2, nodes=4, cpu="8", **cfg_kw) -> SchedulerReplicaSet:
    rs = SchedulerReplicaSet(
        replicas=n,
        cache=SchedulerCache(SnapshotEncoder(TEST_DIMS)),
        config=_config(**cfg_kw),
    )
    for i in range(nodes):
        rs.cache.add_node(make_node(f"n{i}", cpu=cpu, mem="32Gi"))
    return rs


def _drive(rs: SchedulerReplicaSet, rounds=40) -> None:
    """Deterministic synchronous drive: round-robin run_once."""
    for _ in range(rounds):
        for s in rs.schedulers:
            s.run_once(timeout=0.0)
        if not rs.queue.has_schedulable() and not any(
            s.pipeline_pending for s in rs.schedulers
        ):
            break
    for s in rs.schedulers:
        s.flush_pipeline()


# ------------------------------------------------------- queue sharding


def test_shard_of_is_stable_and_deterministic():
    pods = [make_pod(f"p{i}", namespace=f"ns{i % 3}") for i in range(64)]
    for of in (1, 2, 4, 8):
        first = [PriorityQueue.shard_of(p, of) for p in pods]
        again = [PriorityQueue.shard_of(p, of) for p in pods]
        assert first == again
        assert all(0 <= s < of for s in first)
    # key-tuple form agrees with the pod form
    for p in pods:
        assert PriorityQueue.shard_of((p.namespace, p.name), 4) == (
            PriorityQueue.shard_of(p, 4)
        )


def test_shard_pops_disjoint_exhaustive_and_stable_under_readd():
    q = PriorityQueue()
    pods = [make_pod(f"p{i}", priority=i % 3) for i in range(40)]
    for p in pods:
        q.add(p)
    by_shard = {
        i: q.pop_batch(100, 0.0, 0.0, shard=i, of=4) for i in range(4)
    }
    got = sorted(p.name for b in by_shard.values() for p in b)
    assert got == sorted(p.name for p in pods)
    for i, batch in by_shard.items():
        for p in batch:
            assert PriorityQueue.shard_of(p, 4) == i
    # readd returns to the OWNER shard; other shards never see it
    victim = by_shard[2][0]
    q.readd(victim)
    for i in (0, 1, 3):
        assert q.pop_batch(10, 0.0, 0.0, shard=i) == []
    back = q.pop_batch(10, 0.0, 0.0, shard=2)
    assert [p.name for p in back] == [victim.name]
    # delete + re-add keeps the shard too
    q.add(victim)
    q.delete(victim)
    q.add(victim)
    assert [p.name for p in q.pop_batch(10, 0.0, 0.0, shard=2)] == [
        victim.name
    ]


def test_global_pop_order_unchanged_by_sharding():
    """pop() without a shard arg pops the GLOBAL priority-FIFO best
    across shard heaps — identical order to an unsharded queue."""
    ref, sharded = PriorityQueue(), PriorityQueue(shards=4)
    pods = [make_pod(f"p{i}", priority=(i * 7) % 5) for i in range(30)]
    for p in pods:
        ref.add(p)
        sharded.add(p)
    ref_order = [ref.pop(0.0).name for _ in range(30)]
    sharded_order = [sharded.pop(0.0).name for _ in range(30)]
    assert ref_order == sharded_order


def test_shed_guard_spans_shards():
    """The at-capacity shed candidate scan sees EVERY shard's entries:
    a high-priority arrival on shard A may evict the lowest-priority
    pod even when it lives on shard B."""
    q = PriorityQueue(capacity=4, shards=4)
    low = [make_pod(f"low{i}", priority=0) for i in range(4)]
    for p in low:
        q.add(p)
    shed = []
    q.on_shed = lambda pod, reason: shed.append((pod.name, reason))
    vip = make_pod("vip", priority=100)
    q.add(vip)
    assert len(q) == 4
    assert shed and shed[0][0].startswith("low")
    # the vip is poppable from its own shard
    s = PriorityQueue.shard_of(vip, 4)
    assert any(
        p.name == "vip" for p in q.pop_batch(10, 0.0, 0.0, shard=s)
    )


# ------------------------------------------------- reconciler edge matrix


def test_zero_conflict_fast_path_is_allocation_free():
    rs = _replica_set(n=2)
    r0 = rs.schedulers[0]
    for i in range(4):
        rs.queue.add(make_pod(f"p{i}", cpu="100m"))
    # no sibling interleaves: every commit must ride the generation
    # fence — neither the jitted kernel nor the numpy twin may run
    def _boom(*a, **kw):
        raise AssertionError("fast path must not reach the scan")

    rs.reconciler._kernel = _boom
    rs.reconciler._admit_np = _boom
    _drive(rs)
    assert rs.placed_total == 4
    stats = rs.reconciler.stats()
    assert stats["kernel_calls"] == 0
    assert stats["scans_total"] == 0
    assert stats["fast_path_total"] >= 1
    assert r0.conflicts_total == 0


def test_all_n_conflict_admits_exactly_the_sequenced_winner():
    rs = _replica_set(n=3, nodes=1, cpu="4")
    r0, r1, r2 = rs.schedulers
    # node headroom fits exactly ONE 3-cpu pod; all three replicas
    # dispatch against the SAME snapshot generation
    pods = [
        make_pod(f"c{i}", cpu="3", namespace=f"t{i}") for i in range(3)
    ]
    infs = [
        s._encode_and_dispatch([p]) for s, p in zip(rs.schedulers, pods)
    ]
    assert len({inf.generation for inf in infs}) == 1
    staged = [
        s._commit_state(inf) for s, inf in zip(rs.schedulers, infs)
    ]
    assert [len(st.winners) for st in staged] == [1, 0, 0]
    assert [len(st.race_lost) for st in staged] == [0, 1, 1]
    for s, st in zip(rs.schedulers, staged):
        s._commit_tail(st)
    # losers went back to their OWNER shards, shed-exempt
    assert len(rs.queue) == 2
    assert rs.reconciler.conflicts_total == 2
    # commit sequence stamped in dispatch order of the commits
    assert [inf.commit_seq for inf in infs] == [1, 2, 3]
    assert rs.invariant_violations_total() == 0


def test_drf_tiebreak_prefers_smaller_dominant_share():
    rs = _replica_set(n=2, nodes=1, cpu="8")
    r0, r1 = rs.schedulers
    # tenant "hog" already holds committed capacity; tenant "tiny" none
    seed = make_pod("seed", cpu="2", namespace="hog", node_name="n0")
    rs.cache.add_pod(seed)
    # the ENGINE sees headroom 6 and approves BOTH 3-cpu contenders;
    # a sibling commit then shrinks live headroom to 4.5 — room for
    # one.  Batch order puts hog FIRST, so only the DRF order can make
    # tiny win the sequenced admission.
    contenders = [
        make_pod("hog-pod", cpu="3", namespace="hog"),
        make_pod("tiny-pod", cpu="3", namespace="tiny"),
    ]
    inf = r0._encode_and_dispatch(contenders)
    bump = make_pod("bump", cpu="1500m", namespace="zz", node_name="n0")
    rs.cache.add_pod(bump)
    st = r0._commit_state(inf)
    winners = [w[1].name for w in st.winners]
    losers = [p.name for _, p in st.race_lost]
    assert winners == ["tiny-pod"], (winners, losers)
    assert losers == ["hog-pod"]
    r0._commit_tail(st)
    assert rs.reconciler.stats()["scans_total"] == 1


def test_quota_veto_parks_unschedulable():
    rs = _replica_set(
        n=2, nodes=2, cpu="8",
        namespace_quotas={"capped": {"cpu": "1"}},
    )
    r0 = rs.schedulers[0]
    pods = [
        make_pod("q1", cpu="900m", namespace="capped"),
        make_pod("q2", cpu="900m", namespace="capped"),
        make_pod("free", cpu="900m", namespace="open"),
    ]
    inf = r0._encode_and_dispatch(pods)
    st = r0._commit_state(inf)
    names = sorted(w[1].name for w in st.winners)
    assert names == ["free", "q1"], names
    assert [p.name for _, p in st.quota_lost] == ["q2"]
    assert st.race_lost == []
    r0._commit_tail(st)
    # the quota loser PARKED (unschedulable w/ backoff), not active
    assert len(rs.queue) == 1
    assert rs.queue.active_depth() == 0
    assert rs.reconciler.quota_vetoes_total == 1
    evs = [
        e for e in r0.recorder.events() if e.reason == "QuotaExceeded"
    ]
    assert evs and evs[0].name == "q2"


def test_stale_fence_requeues_port_carrying_winner():
    """A winner carrying a constraint the scan cannot re-validate
    (host ports here) must NOT commit optimistically across a stale
    generation fence: it requeues to its owner shard and places on the
    next, fresh dispatch.  Lean pods in the same cycle still admit."""
    rs = _replica_set(n=2, nodes=2, cpu="8")
    r0 = rs.schedulers[0]
    porty = make_pod("porty", cpu="100m", ports=[{"containerPort": 80,
                                                  "hostPort": 8080}])
    lean = make_pod("lean", cpu="100m", namespace="t2")
    inf = r0._encode_and_dispatch([porty, lean])
    # a sibling commit bumps the generation -> stale fence
    bump = make_pod("bump", cpu="100m", namespace="zz", node_name="n0")
    rs.cache.add_pod(bump)
    st = r0._commit_state(inf)
    assert [w[1].name for w in st.winners] == ["lean"]
    assert [p.name for _, p in st.race_lost] == ["porty"]
    r0._commit_tail(st)
    assert rs.reconciler.strict_requeues_total == 1
    # the requeued pod is ACTIVE on its owner shard and places cleanly
    # on a fresh cycle (no interleave this time -> fast path)
    shard = PriorityQueue.shard_of(porty, 2)
    repl = rs.schedulers[shard]
    got = rs.queue.pop_batch(4, 0.0, 0.0, shard=shard, of=2)
    assert [p.name for p in got] == ["porty"]
    inf2 = repl._encode_and_dispatch(got)
    st2 = repl._commit_state(inf2)
    assert [w[1].name for w in st2.winners] == ["porty"]
    repl._commit_tail(st2)
    assert rs.invariant_violations_total() == 0


@pytest.mark.chaos
def test_conflict_against_degraded_replica_cpu_adapter_cycle():
    """A replica whose breaker is open serves its cycle from the CPU
    adapter; the reconciler still sequences its commit — via the numpy
    twin — and requeues the race loser."""
    rs = _replica_set(n=2, nodes=1, cpu="4")
    r0, r1 = rs.schedulers
    # trip replica 1's breaker: its cycles degrade to the CPU engine
    from kubernetes_tpu.codec.faults import FAULT_PERSISTENT

    r1.device_health.record_failure(FAULT_PERSISTENT)
    assert not r1.device_health.device_available
    pa = make_pod("dev-pod", cpu="3", namespace="ta")
    pb = make_pod("cpu-pod", cpu="3", namespace="tb")
    inf0 = r0._encode_and_dispatch([pa])
    inf1 = r1._encode_and_dispatch([pb])
    assert inf1.degraded
    kernel_calls0 = rs.reconciler.kernel_calls
    st0 = r0._commit_state(inf0)
    st1 = r1._commit_state(inf1)
    assert len(st0.winners) == 1
    assert [p.name for _, p in st1.race_lost] == ["cpu-pod"]
    # the degraded commit used the numpy twin, not a device launch
    assert rs.reconciler.kernel_calls == kernel_calls0
    r0._commit_tail(st0)
    r1._commit_tail(st1)
    assert rs.invariant_violations_total() == 0


@pytest.mark.chaos
def test_replica_overload_storm_invariants_clean():
    """N replicas + a bounded shedding queue + a multi-tenant burst
    over capacity: conservation holds by construction — offered ==
    placed + shed + still-queued, zero invariant violations, and no
    popped pod is lost at drain."""
    rs = SchedulerReplicaSet(
        replicas=3,
        cache=SchedulerCache(SnapshotEncoder(TEST_DIMS)),
        queue=PriorityQueue(capacity=64, shards=3),
        config=_config(batch_size=16, queue_capacity=64),
    )
    for i in range(8):
        rs.cache.add_node(make_node(f"n{i}", cpu="16", mem="32Gi"))
    offered = 160
    for i in range(offered):
        rs.queue.add(
            make_pod(f"s{i}", cpu="50m", namespace=f"tenant{i % 4}",
                     priority=i % 3)
        )
    shed_on_admit = rs.queue.shed_total
    _drive(rs, rounds=120)
    placed = rs.placed_total
    shed = rs.queue.shed_total
    left = len(rs.queue)
    assert placed + shed + left >= offered - 0  # nothing vanished
    assert placed > 0
    assert rs.invariant_violations_total() == 0
    assert rs.assert_drained()
    # every tenant that offered pods got SOME placements (DRF ordering
    # + hash shards cannot starve a namespace wholesale)
    per_tenant = {f"tenant{t}": 0 for t in range(4)}
    for s in rs.schedulers:
        for r in s.results:
            if r.node is not None:
                per_tenant[r.pod.namespace] += 1
    assert all(v > 0 for v in per_tenant.values()), per_tenant
    del shed_on_admit


# ------------------------------------ singleton installs + aggregate


def test_two_replica_installs_keep_primary_default_and_aggregate():
    from kubernetes_tpu.runtime import perfobs as perfobs_mod
    from kubernetes_tpu.runtime import quality as quality_mod
    from kubernetes_tpu.runtime import telemetry as telemetry_mod

    rs = _replica_set(n=2, quality_top_k=3)
    r0, r1 = rs.schedulers
    # the process DEFAULT is replica 0's instance (not last-writer r1)
    assert telemetry_mod.get_default() is r0.telemetry
    assert perfobs_mod.get_default() is r0.perfobs
    assert quality_mod.get_default() is r0.quality
    # ...and the explicit aggregate holds BOTH replicas' instances
    assert telemetry_mod.replica_instances()[0] is r0.telemetry
    assert telemetry_mod.replica_instances()[1] is r1.telemetry
    assert r0.telemetry is not r1.telemetry
    assert perfobs_mod.replica_instances()[1] is r1.perfobs
    assert quality_mod.replica_instances()[1] is r1.quality
    # both replicas retire spans into the ONE process flight recorder,
    # tagged with their replica id
    for i in range(8):
        rs.queue.add(make_pod(f"p{i}", cpu="100m"))
    _drive(rs)
    assert rs.placed_total == 8
    # the ring is the PROCESS recorder (shared across the suite), so
    # other tests' replicas may appear too — this set's replica 0 must
    replicas_seen = {
        sp.attrs.get("replica")
        for sp in r0.flight_recorder.spans()
        if sp.attrs.get("replica") is not None
    }
    assert 0 in replicas_seen
    # per-replica cycles land in each replica's OWN observatory — no
    # misattribution to the surviving default
    assert r0.perfobs.summary()["cycles"] >= 1
    if r1._outcome_totals["placed"] or r1._outcome_totals["unschedulable"]:
        assert r1.perfobs.summary()["cycles"] >= 1


def test_debug_replicas_payload_and_metric_families():
    from test_metrics_format import parse_exposition

    rs = _replica_set(n=2, nodes=1, cpu="4")
    # manufacture one conflict so the families have samples
    pa = make_pod("ma", cpu="3", namespace="ta")
    pb = make_pod("mb", cpu="3", namespace="tb")
    inf0 = rs.schedulers[0]._encode_and_dispatch([pa])
    inf1 = rs.schedulers[1]._encode_and_dispatch([pb])
    for s, inf in zip(rs.schedulers, (inf0, inf1)):
        s._commit_tail(s._commit_state(inf))
    from kubernetes_tpu.runtime import reconciler as rmod

    payload = rmod.debug_payload()
    assert payload["replicas"] >= 2
    assert payload["reconciler"]["conflicts_total"] >= 1
    assert "ta" in payload["tenants"] or "tb" in payload["tenants"]
    per = payload["per_replica"]
    assert per["0"]["placed"] >= 1
    assert per["1"]["conflicts"] >= 1
    json.dumps(payload)  # JSON-serializable end to end
    # strict exposition: the three new families parse with the right
    # types and labels
    fams = parse_exposition(m.REGISTRY.expose())
    assert fams["scheduler_replicas"]["type"] == "gauge"
    assert fams["scheduler_replicas"]["samples"][0][2] >= 2
    conf = fams["scheduler_replica_conflicts_total"]
    assert conf["type"] == "counter"
    assert any(
        s[1].get("replica") == "1" and s[2] >= 1 for s in conf["samples"]
    )
    req = fams["scheduler_replica_requeued_pods_total"]
    assert req["type"] == "counter"
    assert req["samples"][0][2] >= 1


def test_debug_replicas_served_on_both_servers():
    from kubernetes_tpu.runtime.health import HealthServer
    from kubernetes_tpu.apiserver.server import APIServer
    from kubernetes_tpu.runtime.ledger import DEBUG_ENDPOINTS

    assert "/debug/replicas" in DEBUG_ENDPOINTS
    rs = _replica_set(n=2)
    del rs  # registered as a side effect; the endpoint reads the registry
    hs = HealthServer(host="127.0.0.1", port=0).start()
    try:
        host, port = hs.address
        body = urllib.request.urlopen(
            f"http://{host}:{port}/debug/replicas?limit=2", timeout=5
        ).read()
        payload = json.loads(body)
        assert "per_replica" in payload and "reconciler" in payload
        idx = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/debug/", timeout=5
        ).read())
        assert "/debug/replicas" in idx["endpoints"]
    finally:
        hs.stop()
    api = APIServer(host="127.0.0.1", port=0).start()
    try:
        host, port = api.address
        payload = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/debug/replicas", timeout=5
        ).read())
        assert "per_replica" in payload
    finally:
        api.stop()


def test_heartbeat_line_carries_replica_fields():
    from kubernetes_tpu.utils import klog

    rs = _replica_set(n=2, heartbeat_s=0.01)
    for i in range(4):
        rs.queue.add(make_pod(f"h{i}", cpu="100m"))
    records = []
    orig = klog.infof
    try:
        klog.infof = lambda fmt, *a: records.append(fmt % a if a else fmt)
        time.sleep(0.02)
        _drive(rs)
        time.sleep(0.02)
        rs.schedulers[0].run_once(timeout=0.0)
    finally:
        klog.infof = orig
    beats = [r for r in records if r.startswith("heartbeat:")]
    assert beats, "no heartbeat line"
    assert "replicas=2" in beats[-1]
    assert "conflicts=" in beats[-1]


# ------------------------------------------------------ ledger replay


def test_ledger_records_replica_seq_and_replays_bit_identical(tmp_path):
    from kubernetes_tpu.runtime import ledger as ledger_mod

    path = str(tmp_path / "replicas.ledger")
    rs = SchedulerReplicaSet(
        replicas=2,
        cache=SchedulerCache(SnapshotEncoder(TEST_DIMS)),
        config=_config(decision_ledger=True),
        ledger=ledger_mod.DecisionLedger(path=path),
    )
    for i in range(2):
        rs.cache.add_node(make_node(f"n{i}", cpu="8", mem="32Gi"))
    for i in range(24):
        rs.queue.add(make_pod(f"L{i}", cpu="100m", namespace=f"t{i % 2}"))
    _drive(rs)
    assert rs.placed_total == 24
    rs.primary.ledger.flush(30.0)
    header, records = ledger_mod.read_ledger(path)
    assert records, "no recorded cycles"
    replicas_seen = {rec.get("replica") for rec in records}
    assert replicas_seen <= {0, 1} and replicas_seen
    seqs = [rec.get("seq") for rec in records if rec.get("seq")]
    assert len(seqs) == len(set(seqs)), "commit sequence must be unique"
    # every replica's every cycle replays to bit-identical winners
    out = ledger_mod.replay(path, cluster_stats=False)
    assert out["bit_identical"], out
    assert out["cycles"] == len(records)
    # the /debug/decisions ring carries the replica tag too
    entries = rs.primary.ledger.decisions()
    assert any(e.get("replica") is not None for e in entries)


# --------------------------------------------------- threaded smoke


def test_threaded_replicas_drain_and_config_plumbing():
    from kubernetes_tpu.config.types import KubeSchedulerConfiguration

    cc = KubeSchedulerConfiguration.from_dict({
        "replicas": 2,
        "namespaceQuotas": {"capped": {"cpu": "1"}},
    })
    assert cc.replicas == 2
    cfg = SchedulerConfig.from_component_config(cc)
    assert cfg.replicas == 2
    assert cfg.namespace_quotas == {"capped": {"cpu": "1"}}
    rs = SchedulerReplicaSet(
        replicas=2,
        cache=SchedulerCache(SnapshotEncoder(TEST_DIMS)),
        config=_config(batch_size=16),
    )
    for i in range(4):
        rs.cache.add_node(make_node(f"n{i}", cpu="16", mem="32Gi"))
    for i in range(64):
        rs.queue.add(make_pod(f"T{i}", cpu="50m"))
    placed = rs.run_until_drained(budget_s=60)
    rs.stop()
    assert rs.placed_total == 64, rs.summary()
    assert rs.assert_drained()
    assert placed >= 0
    # guards: replicas exclude mesh sharding + per-pod commit
    with pytest.raises(ValueError):
        SchedulerReplicaSet(replicas=2, config=_config(shard_devices=2))
    with pytest.raises(ValueError):
        SchedulerReplicaSet(
            replicas=2, config=_config(batched_commit=False)
        )
