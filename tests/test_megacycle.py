"""Device-resident megacycle (ISSUE 12).

Pins the tentpole contracts: a megacycle of K batches places
bit-identically to K chained single-cycle launches (raw engines) AND to
K separate live cycles with host commits in between (both engines,
single-chip and on the 8-virtual-device mesh), ineligible pods fall
back to single cycles with identical placements, the resilience stack
treats a megacycle as one retryable unit (transient relaunch, CPU-
adapter sequential replay) with the invariant checker staying clean
across a fault-interrupted megacycle, chained-state donation is sound
across back-to-back megacycles, prewarm covers the K x width ladder,
the host_stall phase and its fence split reconcile with /debug/perf on
the megacycle path, and the ledger records a megacycle as K replayable
blocks.
"""

import dataclasses
import time

import numpy as np
import pytest

import jax

from kubernetes_tpu.codec import SnapshotEncoder
from kubernetes_tpu.codec.faults import (
    FAULT_PERSISTENT,
    FAULT_TRANSIENT,
    FaultInjector,
    install_injector,
)
from kubernetes_tpu.models.batched import (
    encode_batch_ports,
    make_sequential_scheduler,
)
from kubernetes_tpu.models.megacycle import (
    make_megacycle_scheduler,
    stack_windows,
)
from kubernetes_tpu.ops.priorities import pod_group_onehot
from kubernetes_tpu.runtime import perfobs
from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.queue import PodBackoff, PriorityQueue
from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig

from fixtures import make_node, make_pod

pytestmark = pytest.mark.megacycle

_ZONE = "failure-domain.beta.kubernetes.io/zone"


def _encoder(n_nodes=12, n_groups=4):
    enc = SnapshotEncoder()
    for i in range(n_nodes):
        enc.add_node(make_node(
            f"n{i}", cpu="16", mem="32Gi",
            labels={_ZONE: f"z{i % 3}"},
        ))
    for d in range(n_groups):
        enc.add_spread_selector("default", {"app": f"dep-{d}"})
    return enc


def _windows(K=4, W=8, prefix="p", n_groups=4):
    return [
        [
            make_pod(
                f"{prefix}{k}-{i}", cpu="300m", mem="128Mi",
                labels={"app": f"dep-{(k + i) % n_groups}"},
            )
            for i in range(W)
        ]
        for k in range(K)
    ]


def _encode_all(enc, windows):
    # two passes: a later window can grow a sticky pad dim; the second
    # pass encodes every window at the (now stable) max shapes — the
    # scheduler's _dispatch_megacycle does the same
    batches = [enc.encode_pods(w) for w in windows]
    batches = [enc.encode_pods(w) for w in windows]
    ports = [encode_batch_ports(enc, w) for w in windows]
    return batches, ports


def _host_gc_commit(gc, hosts, batch):
    """Host reference of the megacycle's group-count chaining."""
    gc = np.asarray(gc).copy()
    oh = np.asarray(pod_group_onehot(batch, gc.shape[1]))
    for b, h in enumerate(np.asarray(hosts)):
        if h >= 0:
            gc[h] += oh[b]
    return gc


def _chained_reference(fn, cluster, batches, ports, li0):
    """K single-cycle launches chained by hand: resources through the
    engine's returned cluster, spread counts through the host recount —
    exactly what K live cycles with host commits produce."""
    cl = cluster
    out = []
    for k, (b, p) in enumerate(zip(batches, ports)):
        hosts, cl2 = fn(cl, b, p, np.int32(li0[k]))
        hosts = np.asarray(hosts)
        out.append(hosts)
        cl = dataclasses.replace(
            cl2, group_counts=_host_gc_commit(cl.group_counts, hosts, b)
        )
    return np.stack(out), cl


@pytest.mark.parametrize("engine", ["sequential", "speculative"])
def test_raw_megacycle_identical_to_chained_single_cycles(engine):
    enc = _encoder()
    windows = _windows(K=4, W=8)
    batches, ports = _encode_all(enc, windows)
    cluster = enc.snapshot()
    li0 = np.cumsum([0] + [len(w) for w in windows[:-1]]).astype(np.int32)
    mega = make_megacycle_scheduler(
        engine=engine, zone_key_id=enc.getzone_key
    )
    hosts_k, final = mega(
        cluster, stack_windows(batches), stack_windows(ports), li0
    )
    hosts_k = np.asarray(hosts_k)
    if engine == "sequential":
        fn = make_sequential_scheduler(zone_key_id=enc.getzone_key)
    else:
        # the reference must run the same device program family the
        # megacycle scans (the packed while_loop + in-program redo)
        import kubernetes_tpu.models.speculative as spec_mod

        prev = spec_mod.FORCE_PACKED_PATH
        spec_mod.FORCE_PACKED_PATH = True
        try:
            fn = spec_mod.make_speculative_scheduler(
                zone_key_id=enc.getzone_key
            )
            ref, ref_cl = _chained_reference(fn, cluster, batches, ports, li0)
        finally:
            spec_mod.FORCE_PACKED_PATH = prev
        assert np.array_equal(hosts_k, ref)
        assert np.array_equal(
            np.asarray(final.requested), np.asarray(ref_cl.requested)
        )
        assert np.array_equal(
            np.asarray(final.group_counts), np.asarray(ref_cl.group_counts)
        )
        return
    ref, ref_cl = _chained_reference(fn, cluster, batches, ports, li0)
    assert np.array_equal(hosts_k, ref)
    assert np.array_equal(
        np.asarray(final.requested), np.asarray(ref_cl.requested)
    )
    assert np.array_equal(
        np.asarray(final.nonzero_req), np.asarray(ref_cl.nonzero_req)
    )
    assert np.array_equal(
        np.asarray(final.group_counts), np.asarray(ref_cl.group_counts)
    )
    assert (hosts_k >= 0).sum() > 0


# ------------------------------------------------------------ live path


def _live(K, engine="speculative", nodes=8, pipeline=True, shard=0,
          **cfg_kw):
    cache = SchedulerCache()
    queue = PriorityQueue(
        backoff=PodBackoff(initial=0.01, max_duration=0.05)
    )
    cfg = SchedulerConfig(
        batch_size=32, batch_window_s=0.0, engine=engine,
        disable_preemption=True, batched_commit=True,
        pipeline_commit=pipeline, megacycle_batches=K,
        shard_devices=shard,
        device_backoff_base_s=0.001, device_backoff_max_s=0.005,
        breaker_open_s=0.02,
        **cfg_kw,
    )
    sched = Scheduler(
        cache=cache, queue=queue, binder=lambda p, n: True, config=cfg
    )
    for i in range(nodes):
        cache.add_node(make_node(
            f"n{i}", cpu="64", mem="128Gi", labels={_ZONE: f"z{i % 4}"},
        ))
    for d in range(4):
        cache.encoder.add_spread_selector("default", {"app": f"dep-{d}"})
    return sched, queue


def _drain(sched, queue, budget_s=120.0):
    deadline = time.monotonic() + budget_s
    while time.monotonic() < deadline:
        got = sched.run_once(timeout=0.0)
        if got == 0 and not sched.pipeline_pending:
            if not queue.has_schedulable():
                break
            time.sleep(0.002)
    sched.flush_pipeline()


def _feed(queue, n, prefix="p"):
    for i in range(n):
        queue.add(make_pod(
            f"{prefix}{i}", cpu="100m", mem="64Mi",
            labels={"app": f"dep-{i % 4}"},
        ))


def _placements(sched):
    return {
        (r.pod.namespace, r.pod.name): r.node
        for r in sched.results if r.node is not None
    }


@pytest.mark.parametrize("engine", ["sequential", "speculative"])
def test_live_megacycle_identical_to_single_cycles(engine):
    """The acceptance pin: the SAME pod stream through megacycleBatches=4
    and =1 binds every pod to the same node — the on-device chain
    (resources + spread counts) reproduces the host commits exactly."""
    s1, q1 = _live(1, engine)
    _feed(q1, 200)
    _drain(s1, q1)
    s4, q4 = _live(4, engine)
    _feed(q4, 200)
    _drain(s4, q4)
    assert s4.megacycles_total > 0, "no megacycle formed"
    assert _placements(s1) == _placements(s4)
    assert len(_placements(s4)) == 200
    for s in (s1, s4):
        assert s.invariants is not None
        assert s.invariants.violations_total() == 0
        assert s.invariants.assert_drained()


@pytest.mark.sharded
def test_live_megacycle_sharded_identity():
    """Megacycles over the 8-virtual-device mesh place identically to
    the single-chip megacycle run AND to single cycles."""
    s_chip, q_chip = _live(4, "speculative", shard=0)
    _feed(q_chip, 160)
    _drain(s_chip, q_chip)
    s_mesh, q_mesh = _live(4, "speculative", shard=8)
    _feed(q_mesh, 160)
    _drain(s_mesh, q_mesh)
    assert s_mesh.megacycles_total > 0
    assert _placements(s_chip) == _placements(s_mesh)
    s_one, q_one = _live(1, "speculative", shard=8)
    _feed(q_one, 160)
    _drain(s_one, q_one)
    assert _placements(s_one) == _placements(s_mesh)


def test_ineligible_pods_fall_back_to_single_cycles():
    """Pods the chain cannot carry (host ports here) must ride the
    single-cycle path — same placements as megacycleBatches=1, zero
    megacycle launches."""
    def feed_ports(queue, n):
        for i in range(n):
            queue.add(make_pod(
                f"hp{i}", cpu="50m", mem="32Mi",
                ports=[{"hostPort": 8000 + i}],
            ))

    s1, q1 = _live(1, "speculative")
    feed_ports(q1, 60)
    _drain(s1, q1)
    s4, q4 = _live(4, "speculative")
    feed_ports(q4, 60)
    _drain(s4, q4)
    assert s4.megacycles_total == 0
    assert _placements(s1) == _placements(s4)
    assert len(_placements(s4)) == 60


def test_megacycle_safe_gate_matrix():
    sched, _ = _live(4)
    plain = make_pod("ok", cpu="50m", labels={"app": "dep-0"})
    assert sched._megacycle_safe([plain])
    gang = make_pod("g", cpu="50m",
                    labels={Scheduler.POD_GROUP_LABEL: "grp"})
    assert not sched._megacycle_safe([gang])
    porty = make_pod("p", cpu="50m", ports=[{"hostPort": 80}])
    assert not sched._megacycle_safe([porty])
    aff = make_pod(
        "a", cpu="50m",
        affinity={"podAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "labelSelector": {"matchLabels": {"app": "dep-0"}},
                "topologyKey": _ZONE,
            }]}},
    )
    assert not sched._megacycle_safe([aff])
    # two spread groups match this pod: the non-lean shape
    sched.cache.encoder.add_spread_selector("default", {"tier": "x"})
    multi = make_pod("m", cpu="50m",
                     labels={"app": "dep-0", "tier": "x"})
    assert not sched._megacycle_safe([multi])
    # ... and scheduler-level gates
    assert sched._megacycle_ready()
    sched.queue.update_nominated_pod(make_pod("nom", cpu="1m"), "n0")
    assert not sched._megacycle_ready()


def test_express_lane_preempts_between_megacycles():
    """The express preemption point survives megacycle mode: express
    pods arriving under a megacycle bulk backlog are served between
    megacycles (one express cycle per run_once iteration), every pod
    places, and the conservation checker stays clean."""
    s, q = _live(
        4, "speculative",
        express_lane=True, express_batch_size=8,
        express_priority_threshold=1000,
    )
    _feed(q, 160)
    for i in range(12):
        p = make_pod(f"x{i}", cpu="10m", mem="16Mi",
                     labels={"app": "dep-0"})
        p.spec.priority = 2000
        q.add(p)
    _drain(s, q)
    assert s.megacycles_total > 0
    placed = _placements(s)
    assert len(placed) == 172
    assert all(("default", f"x{i}") in placed for i in range(12))
    assert s.invariants.violations_total() == 0
    assert s.invariants.assert_drained()


# ---------------------------------------------------------- resilience


@pytest.fixture
def injector():
    inj = FaultInjector(seed=11)
    remove = install_injector(inj)
    yield inj
    remove()


@pytest.mark.chaos
def test_megacycle_transient_fault_retries_whole_unit(injector):
    """A transient fence fault mid-megacycle relaunches the WHOLE K-deep
    launch: placements match the unfaulted run, every popped pod
    resolves exactly once (the invariant checker stays clean)."""
    s_ref, q_ref = _live(4, "sequential")
    _feed(q_ref, 120)
    _drain(s_ref, q_ref)

    s, q = _live(4, "sequential")
    _feed(q, 120)
    injector.arm("fence", kind=FAULT_TRANSIENT, count=1)
    _drain(s, q)
    assert s.megacycles_total > 0
    assert _placements(s) == _placements(s_ref)
    assert s.invariants.violations_total() == 0
    assert s.invariants.assert_drained()
    from kubernetes_tpu.runtime.health import BREAKER_CLOSED

    assert s.device_health.state == BREAKER_CLOSED


@pytest.mark.chaos
def test_megacycle_persistent_fault_degrades_to_sequential_replay(injector):
    """A persistent fault mid-megacycle serves the K batches
    sequentially from the CPU adapter, bit-identically (sequential
    engine: the adapter carries the scan's tie-rotation), with zero
    pods lost."""
    s_ref, q_ref = _live(4, "sequential")
    _feed(q_ref, 120)
    _drain(s_ref, q_ref)

    s, q = _live(4, "sequential")
    _feed(q, 120)
    injector.arm("fence", kind=FAULT_PERSISTENT)
    _drain(s, q)
    injector.disarm()
    assert _placements(s) == _placements(s_ref)
    assert len(_placements(s)) == 120
    assert s.invariants.violations_total() == 0
    assert s.invariants.assert_drained()


@pytest.mark.chaos
def test_megacycle_relaunch_fault_degrades_instead_of_escaping(injector):
    """A classified fault raised by the RELAUNCH dispatch itself (after
    a transient fence fault approved a retry) must feed the same
    retry/degrade policy as the original fault — the CPU adapter serves
    the K batches and no pod is lost or stranded."""
    s_ref, q_ref = _live(4, "sequential")
    _feed(q_ref, 120)
    _drain(s_ref, q_ref)

    s, q = _live(4, "sequential")
    _feed(q, 120)
    injector.arm("fence", kind=FAULT_TRANSIENT, count=1)
    injector.arm("dispatch", kind=FAULT_PERSISTENT)
    _drain(s, q)
    injector.disarm()
    assert _placements(s) == _placements(s_ref)
    assert len(_placements(s)) == 120
    assert s.invariants.violations_total() == 0
    assert s.invariants.assert_drained()


# ----------------------------------------------- chained-state donation


def test_chained_donation_soundness():
    """Two megacycles back-to-back through the donated chained-state
    path: the second consumes the first's returned cluster, results
    match the undonated path, and on accelerator backends the donated
    input buffers are actually dead after the launch (the classic
    use-after-donate footgun this pins against)."""
    enc = _encoder()
    windows = _windows(K=2, W=8, prefix="d1-")
    windows2 = _windows(K=2, W=8, prefix="d2-")
    b1, p1 = _encode_all(enc, windows + windows2)
    b2, p2 = b1[2:], p1[2:]
    b1, p1 = b1[:2], p1[:2]
    cluster = enc.snapshot()
    li0a = np.asarray([0, 8], np.int32)
    li0b = np.asarray([16, 24], np.int32)

    plain = make_megacycle_scheduler(
        engine="sequential", zone_key_id=enc.getzone_key
    )
    ha, mid_ref = plain(cluster, stack_windows(b1), stack_windows(p1), li0a)
    hb, _ = plain(mid_ref, stack_windows(b2), stack_windows(p2), li0b)

    donated = make_megacycle_scheduler(
        engine="sequential", zone_key_id=enc.getzone_key,
        donate_cluster=True,
    )
    dev0 = jax.device_put(cluster)
    ha2, mid = donated(dev0, stack_windows(b1), stack_windows(p1), li0a)
    if jax.default_backend() != "cpu":
        # the donated input's dynamic buffers must be consumed
        assert dev0.requested.is_deleted()
    ha2 = np.asarray(ha2)
    hb2, final = donated(mid, stack_windows(b2), stack_windows(p2), li0b)
    assert np.array_equal(np.asarray(ha), ha2)
    assert np.array_equal(np.asarray(hb), np.asarray(hb2))
    assert mid is not dev0 and final is not mid


def test_live_back_to_back_megacycles_keep_resident_snapshot_coherent():
    """Two megacycles through the live scheduler: the second's dirty-row
    refresh of the resident device snapshot must reflect the first's
    host commits exactly (placements == one long single-cycle run)."""
    s, q = _live(2, "speculative")
    _feed(q, 128, prefix="a")
    _drain(s, q)
    first = s.megacycles_total
    _feed(q, 128, prefix="b")
    _drain(s, q)
    assert s.megacycles_total > first >= 1
    s1, q1 = _live(1, "speculative")
    _feed(q1, 128, prefix="a")
    _drain(s1, q1)
    _feed(q1, 128, prefix="b")
    _drain(s1, q1)
    assert _placements(s) == _placements(s1)


# ------------------------------------------------------------- prewarm


def test_prewarm_covers_megacycle_ladder():
    s, q = _live(4, "speculative", pipeline=False)
    timings = s.prewarm(widths=[8])
    assert 8 in timings
    assert "mega2x8" in timings and "mega4x8" in timings
    # prewarm must not perturb the runtime: rotation untouched, nothing
    # committed, and the next real stream places like a cold scheduler
    assert s._last_index == 0
    assert not s.results
    _feed(q, 64)
    _drain(s, q)
    s_cold, q_cold = _live(4, "speculative", pipeline=False)
    _feed(q_cold, 64)
    _drain(s_cold, q_cold)
    assert _placements(s) == _placements(s_cold)


# ------------------------------------- phase alias + perfobs + ledger


def test_host_stall_alias_reconciles_with_perfobs_on_megacycle_path():
    """ISSUE 12 satellite: the fence wait is recorded ONCE under the
    perfobs vocabulary; phase_seconds splits it by what the fetch worker
    was doing (fence_queue + fence_device + fence_copy == host_stall),
    and /debug/perf's host_stall total reconciles with it on a
    megacycle-serving scheduler."""
    s, q = _live(4, "speculative")
    _feed(q, 200)
    _drain(s, q)
    assert s.megacycles_total > 0
    ph = s.phase_seconds
    split = ph["fence_queue"] + ph["fence_device"] + ph["fence_copy"]
    assert ph["host_stall"] == pytest.approx(split, abs=1e-9)
    tot = s.perfobs.summary()["totals_s"]
    assert abs(tot["host_stall"] - ph["host_stall"]) <= (
        0.02 + 0.05 * max(ph["host_stall"], 1e-9)
    )
    samples = s.perfobs.debug_payload()["samples"]
    megas = [smp for smp in samples if "mega" in smp]
    assert megas, "no megacycle samples reached the observatory"
    ks = {tuple(smp["mega"]) for smp in megas}
    assert any(k[1] > 1 for k in ks)
    for smp in samples:
        split_host = sum(smp["split_s"][p] for p in perfobs.HOST_PHASES)
        assert smp["cycle_wall_s"] + 1e-6 >= split_host


def test_ledger_records_megacycle_as_replayable_blocks(tmp_path):
    """The ledger records a K-deep megacycle as K blocks, each
    replaying bit-identically through the single-batch engine against
    the host snapshot its predecessors' commits produced."""
    from kubernetes_tpu.runtime.ledger import DecisionLedger, replay

    path = str(tmp_path / "mega.ledger")
    ledger = DecisionLedger(path=path)
    cache = SchedulerCache()
    queue = PriorityQueue(
        backoff=PodBackoff(initial=0.01, max_duration=0.05)
    )
    s = Scheduler(
        cache=cache, queue=queue, binder=lambda p, n: True,
        config=SchedulerConfig(
            batch_size=16, batch_window_s=0.0, engine="speculative",
            disable_preemption=True, pipeline_commit=True,
            megacycle_batches=4,
        ),
        ledger=ledger,
    )
    for i in range(8):
        cache.add_node(make_node(f"n{i}", cpu="64", mem="128Gi",
                                 labels={_ZONE: f"z{i % 4}"}))
    for d in range(4):
        cache.encoder.add_spread_selector("default", {"app": f"dep-{d}"})
    _feed(queue, 128)
    _drain(s, queue)
    assert s.megacycles_total > 0
    ledger.flush(30.0)
    out = replay(path)
    assert out["bit_identical"], out
    assert out["cycles"] >= 4
    # the /debug/decisions ring marks megacycle sub-batches
    ring = ledger.decisions()
    megas = [e for e in ring if e.get("mega")]
    assert megas and any(e["mega"][1] > 1 for e in megas)


# ------------------------------------------------------ config plumbing


def test_megacycle_config_plumbing():
    from kubernetes_tpu.config.types import KubeSchedulerConfiguration

    cc = KubeSchedulerConfiguration.from_dict({"megacycleBatches": 8})
    assert cc.megacycle_batches == 8
    cfg = SchedulerConfig.from_component_config(cc)
    assert cfg.megacycle_batches == 8
    assert SchedulerConfig().megacycle_batches == 1


def test_adaptive_megacycle_depth_sizing():
    """AIMD sizes K: depth grows only at saturated width under backlog,
    halves on a deadline overrun, decays when the backlog drains."""
    s, q = _live(
        8, "speculative", pipeline=False,
        adaptive_batch=True, batch_size_min=8, cycle_deadline_s=10.0,
    )
    assert s._cur_mega == 1
    s._cur_batch = s.config.batch_size
    for i in range(600):
        q.add(make_pod(f"d{i}", cpu="1m", labels={"app": "dep-0"}))
    s._adapt_batch(0.001)
    assert s._cur_mega == 2
    s._adapt_batch(0.001)
    assert s._cur_mega == 4
    # deadline overrun: multiplicative decrease on depth too
    s._adapt_batch(99.0)
    assert s._cur_mega == 2
    # backlog gone: decay back toward single cycles
    while q.pop_batch(64, 0.0):
        pass
    s._adapt_batch(0.001)
    assert s._cur_mega == 1
