"""Elastic degradation ladder (ISSUE 10).

The ladder `full mesh -> shrunken mesh -> single chip -> CPU adapter`,
with per-shard fault attribution, automatic climb-back, and the online
invariant checker — driven over the conftest 8-virtual-device CPU mesh:

* a shard-attributed persistent fault rebuilds the mesh onto the widest
  pow2 of survivors (8 -> 4) with placements BIT-IDENTICAL to the
  single-chip reference and only the gap cycle served by the CPU engine;
* the half-open canary probes the LOST device and restores the original
  mesh when the fault clears;
* a shard-loss-mid-overload-storm soak keeps the invariant checker clean
  (every popped pod ends bound/requeued/shed, no double-bind, committed
  usage <= allocatable, nothing lost at drain);
* a whole-mesh fault on top of a shrink lands on the CPU adapter with
  zero pods lost; progressive losses walk the ladder down to a 1-device
  mesh and climb all the way back.

Everything seeded/deterministic, sleeps <= ~0.1s, runs in tier-1 via the
chaos marker.
"""

import dataclasses
import logging
import time

import numpy as np
import pytest

from kubernetes_tpu.codec import SnapshotEncoder
from kubernetes_tpu.codec.faults import (
    FAULT_PERSISTENT,
    FAULT_TRANSIENT,
    SITE_DISPATCH,
    SITE_FENCE,
    SITE_SCATTER,
    FaultInjector,
    PersistentDeviceError,
    TransientDeviceError,
    fault_device_index,
    install_injector,
)
from kubernetes_tpu.parallel.mesh import (
    make_mesh,
    mesh_device_ids,
    rebuild_without,
)
from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.health import (
    BREAKER_CLOSED,
    BREAKER_HALF_OPEN,
    BREAKER_OPEN,
    DeviceHealth,
    ShardHealth,
)
from kubernetes_tpu.runtime.invariants import InvariantChecker
from kubernetes_tpu.runtime.queue import PriorityQueue
from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.utils import metrics as m

from fixtures import TEST_DIMS, make_node, make_pod

pytestmark = pytest.mark.chaos

N_DEV = 8


# --------------------------------------------------------------- helpers


def _world(cache, n_nodes=32):
    for i in range(n_nodes):
        cache.add_node(make_node(
            f"n{i}", cpu="8", mem="16Gi",
            labels={"disk": "ssd" if i % 2 else "hdd"},
        ))


def _sched(shard=0, n_nodes=32, **cfg_kw):
    cache = SchedulerCache(SnapshotEncoder(TEST_DIMS))
    _world(cache, n_nodes)
    kw = dict(
        batch_size=8, batch_window_s=0.0, disable_preemption=True,
        batched_commit=True, pipeline_commit=True,
        device_backoff_base_s=0.001, device_backoff_max_s=0.005,
        breaker_open_s=0.02, shard_devices=shard,
    )
    kw.update(cfg_kw)
    return Scheduler(
        cache=cache, queue=PriorityQueue(), config=SchedulerConfig(**kw)
    )


def _pods(n, prefix="p"):
    return [
        make_pod(
            f"{prefix}{i}", cpu="200m", mem="256Mi",
            labels={"app": f"d{i % 3}"},
            node_selector={"disk": "ssd"} if i % 4 == 0 else None,
        )
        for i in range(n)
    ]


def _drain(s, budget_s=30.0):
    deadline = time.monotonic() + budget_s
    while (
        (s.queue.has_schedulable() or s.pipeline_pending)
        and time.monotonic() < deadline
    ):
        s.run_once(timeout=0.0)
    s.flush_pipeline()


def _placements(s):
    return [(r.pod.name, r.node) for r in s.results]


def _feed(s, pods):
    for p in pods:
        s.queue.add(p)
    _drain(s)


@pytest.fixture
def injector():
    inj = FaultInjector(seed=13)
    remove = install_injector(inj)
    yield inj
    remove()


def _lose(injector, device, count=None):
    """Arm a shard-lost outage for `device` at the three shard-aware
    seams, ACCUMULATING with previously lost devices (the chaos
    primitive's merge semantics via FaultInjector.arm_devices, inlined
    so these tests do not need a LocalCluster)."""
    for site in (SITE_DISPATCH, SITE_FENCE, SITE_SCATTER):
        injector.arm_devices(site, {device}, kind=FAULT_PERSISTENT,
                             count=count)


def _assert_clean(s):
    """The pass/fail contract the invariant checker gives a chaos soak."""
    assert s.invariants is not None
    assert s.invariants.assert_drained(), dict(s.invariants.counts)
    assert s.invariants.violations_total() == 0, list(s.invariants.violations)


# --------------------------------------------------- rebuild_without unit


def test_rebuild_without_widest_pow2_submesh():
    full = make_mesh(N_DEV)
    ids = sorted(mesh_device_ids(full))
    assert len(ids) == N_DEV

    mesh4, axis = rebuild_without(full, {ids[3]})
    assert mesh4.size == 4 and axis == "nodes"
    surv = sorted(mesh_device_ids(mesh4))
    assert ids[3] not in surv
    # survivors keep flat order: first 4 of the 7 survivors
    assert surv == [i for i in ids if i != ids[3]][:4]

    mesh2, _ = rebuild_without(full, set(ids[:5]))
    assert mesh2.size == 2
    mesh1, _ = rebuild_without(full, set(ids[:7]))
    assert mesh1.size == 1
    none_mesh, none_axis = rebuild_without(full, set(ids))
    assert none_mesh is None and none_axis is None

    # repeated shrinks are deterministic (same lost set -> same mesh)
    again, _ = rebuild_without(full, {ids[3]})
    assert mesh_device_ids(again) == mesh_device_ids(mesh4)


# ------------------------------------------------------- ShardHealth unit


def test_shard_health_lifecycle_and_probe():
    clock = [0.0]
    trans = []
    sh = ShardHealth(
        device_ids=range(4), failure_threshold=2, open_duration_s=1.0,
        clock=lambda: clock[0],
        on_transition=lambda d, f, t: trans.append((d, f, t)),
    )
    # persistent loses the shard immediately — and only ONCE reports
    # "newly opened" (the ladder's shrink trigger must not loop)
    assert sh.record_failure(2, FAULT_PERSISTENT) is True
    assert sh.state(2) == BREAKER_OPEN
    assert sh.lost() == {2}
    assert sh.record_failure(2, FAULT_PERSISTENT) is False
    # transients accumulate to the threshold
    assert sh.record_failure(1, FAULT_TRANSIENT) is False
    assert sh.state(1) == BREAKER_CLOSED
    assert sh.record_failure(1, FAULT_TRANSIENT) is True
    assert sh.lost() == {1, 2}
    # a success on a closed shard resets its streak
    sh.record_failure(0, FAULT_TRANSIENT)
    sh.record_success(0)
    assert sh.record_failure(0, FAULT_TRANSIENT) is False
    # probe gating: not due before the cool-down, half_open after
    assert sh.probe_due(2) is False
    clock[0] = 1.5
    assert sh.probe_due(2) is True
    assert sh.state(2) == BREAKER_HALF_OPEN
    # a failed half-open probe re-opens regardless of class
    assert sh.record_failure(2, FAULT_TRANSIENT) is True
    assert sh.state(2) == BREAKER_OPEN
    clock[0] = 3.0
    assert sh.probe_due(2) is True
    sh.record_success(2)
    assert sh.state(2) == BREAKER_CLOSED and sh.lost() == {1}
    assert (2, BREAKER_CLOSED, BREAKER_OPEN) in trans
    assert (2, BREAKER_HALF_OPEN, BREAKER_CLOSED) in trans
    assert sh.fault_counts[2][FAULT_PERSISTENT] == 2


def test_breaker_transition_audits_are_bounded():
    h = DeviceHealth(transitions_maxlen=16)
    for _ in range(100):
        h.trip()
        h.record_success()
    assert len(h.transitions) == 16  # the deque window
    sh = ShardHealth(device_ids=[0], transitions_maxlen=8)
    for _ in range(50):
        sh.record_failure(0, FAULT_PERSISTENT)
        sh.record_success(0)
    assert len(sh.transitions) == 8


# -------------------------------------------------- fault attribution unit


def test_fault_device_index_attribute_and_message():
    e = PersistentDeviceError("injected device-lost at dispatch")
    assert fault_device_index(e) is None
    e.device_index = 5
    assert fault_device_index(e) == 5
    assert fault_device_index(RuntimeError("INTERNAL: device 3 halted")) == 3
    assert fault_device_index(RuntimeError("DATA_LOSS on TPU_6 core")) == 6
    assert fault_device_index(RuntimeError("device lost")) is None
    assert fault_device_index(ValueError("device 9")) is None


def test_targeted_arm_fires_only_for_its_device(injector):
    injector.arm(SITE_DISPATCH, kind=FAULT_PERSISTENT, device_index=3)
    injector.fire(SITE_DISPATCH, devices=(0, 1, 2))   # no overlap
    injector.fire(SITE_DISPATCH, devices=None)        # unknown devices
    assert injector.log == []
    with pytest.raises(PersistentDeviceError) as ei:
        injector.fire(SITE_DISPATCH, devices=(2, 3))
    assert ei.value.device_index == 3


# ------------------------------------------------- InvariantChecker unit


def test_invariant_checker_clean_lifecycle():
    inv = InvariantChecker()
    pods = _pods(4, prefix="ok")
    inv.note_popped(pods, cycle=1)
    inv.note_bound(pods[0], "n0")
    inv.note_bound(pods[1], "n1")
    inv.note_requeued(pods[2])
    # pods[3] is requeued, then shed FROM THE QUEUE (the only place the
    # bounded queue can shed from)
    inv.note_requeued(pods[3])
    inv.note_shed(pods[3])
    assert inv.assert_drained()
    assert inv.violations_total() == 0
    # a requeued pod legitimately re-pops and binds later
    inv.note_popped([pods[2]], cycle=2)
    inv.note_bound(pods[2], "n2")
    assert inv.assert_drained() and inv.violations_total() == 0


def test_invariant_checker_catches_violations():
    inv = InvariantChecker()
    a, b = _pods(2, prefix="bad")
    inv.note_popped([a], cycle=1)
    inv.note_bound(a, "n0")
    inv.note_requeued(a)  # resolved twice
    assert inv.counts.get("conservation") == 1
    # double bind without an intervening requeue/pop
    inv.note_bound(b, "n1")
    inv.note_bound(b, "n2")
    assert inv.counts.get("double_bind") == 1
    # lost pod: popped, never resolved
    inv.note_popped([_pods(1, prefix="lost")[0]], cycle=2)
    assert not inv.assert_drained()
    assert inv.counts.get("lost_pod") == 1
    before = m.INVARIANT_VIOLATIONS.value(rule="lost_pod")
    assert before >= 1


def test_invariant_checker_capacity_rule():
    inv = InvariantChecker()
    alloc = np.array([[4.0, 8.0], [4.0, 8.0]], np.float32)
    ok = np.array([[4.0, 7.9], [0.0, 0.0]], np.float32)
    inv.check_capacity([0, 1], ok, alloc)
    assert inv.violations_total() == 0
    bad = np.array([[4.2, 1.0], [0.0, 0.0]], np.float32)
    inv.check_capacity([0], bad, alloc, row_name=lambda r: f"n{r}")
    assert inv.counts.get("capacity") == 1
    assert "n0" in inv.violations[-1][1]


# ------------------------------------------------ the ladder, end to end


def test_shard_loss_shrinks_8_to_4_bit_identical(injector):
    """One persistent shard fault mid-stream: the mesh rebuilds onto 4
    devices, the gap batch rides the CPU adapter bit-identically, the
    GLOBAL breaker never opens, and every placement matches the
    single-chip reference."""
    ref, s = _sched(0), _sched(N_DEV)
    ids = sorted(mesh_device_ids(s.mesh))
    lost = ids[3]

    _feed(s, _pods(8, prefix="a"))
    _feed(ref, _pods(8, prefix="a"))
    assert s.mesh.size == N_DEV and s.ladder_rung == "full_mesh"

    _lose(injector, lost)
    _feed(s, _pods(8, prefix="b"))
    _feed(ref, _pods(8, prefix="b"))
    assert s.mesh.size == 4, "mesh did not shrink to the next pow2"
    assert lost not in mesh_device_ids(s.mesh)
    assert s.ladder_rung == "shrunken_mesh"
    assert s.shard_health.lost() == {lost}
    # the ladder absorbed the fault: the whole-mesh breaker stayed closed
    assert s.device_health.state == BREAKER_CLOSED
    assert list(s.device_health.transitions) == []

    # cycles keep serving SHARDED from the shrunken mesh
    _feed(s, _pods(8, prefix="c"))
    _feed(ref, _pods(8, prefix="c"))
    res = s._dev_snapshot.resident(("allocatable", "requested", "valid"))
    assert res is not None
    assert all(len(b.addressable_shards) == 4 for b in res)

    assert _placements(s) == _placements(ref)
    assert all(r.node is not None for r in s.results)
    _assert_clean(s)
    assert m.MESH_REBUILDS.value(direction="shrink") >= 1


def test_climb_back_restores_original_mesh(injector):
    """Clearing the fault lets the half-open canary (which probes the
    LOST device, not the surviving mesh) restore the full mesh, and the
    restored path serves sharded over all 8 devices again."""
    ref, s = _sched(0), _sched(N_DEV)
    lost = sorted(mesh_device_ids(s.mesh))[2]

    _lose(injector, lost)
    _feed(s, _pods(8, prefix="a"))
    _feed(ref, _pods(8, prefix="a"))
    assert s.mesh.size == 4

    # while the outage lasts, probes keep failing and the mesh stays
    # shrunken (the probe targets exactly the lost device)
    time.sleep(s.config.breaker_open_s * 2)
    s.run_once(timeout=0.0)
    assert s.mesh.size == 4 and s.shard_health.lost() == {lost}

    injector.disarm()
    time.sleep(s.config.breaker_open_s * 2)
    s.run_once(timeout=0.0)  # idle poll runs the probe
    assert s.mesh.size == N_DEV, "recovered shard did not restore the mesh"
    assert s.ladder_rung == "full_mesh"
    assert s.shard_health.lost() == frozenset()

    _feed(s, _pods(8, prefix="b"))
    _feed(ref, _pods(8, prefix="b"))
    res = s._dev_snapshot.resident(("allocatable", "requested", "valid"))
    assert all(len(b.addressable_shards) == N_DEV for b in res)
    assert _placements(s) == _placements(ref)
    _assert_clean(s)
    assert m.MESH_REBUILDS.value(direction="restore") >= 1


def test_double_fault_lands_on_cpu_adapter_zero_loss(injector):
    """Shard loss (shrink) + a whole-mesh persistent fault on top: the
    global breaker opens, the CPU adapter serves — zero pods lost — and
    clearing everything climbs all the way back to the full mesh."""
    ref, s = _sched(0), _sched(N_DEV)
    lost = sorted(mesh_device_ids(s.mesh))[1]

    _lose(injector, lost)
    _feed(s, _pods(8, prefix="a"))
    _feed(ref, _pods(8, prefix="a"))
    assert s.mesh.size == 4

    # an UNATTRIBUTED persistent fault: whole-mesh policy, breaker opens
    injector.arm(SITE_FENCE, kind=FAULT_PERSISTENT, count=1)
    _feed(s, _pods(8, prefix="b"))
    _feed(ref, _pods(8, prefix="b"))
    assert s.device_health.state in (BREAKER_OPEN, BREAKER_CLOSED)
    assert ("closed", "open") in s.device_health.transitions

    # everything clears: canary restores the device path, probe restores
    # the full mesh
    injector.disarm()
    time.sleep(s.config.breaker_open_s * 2)
    _feed(s, _pods(8, prefix="c"))
    _feed(ref, _pods(8, prefix="c"))
    assert s.device_health.state == BREAKER_CLOSED
    assert s.mesh.size == N_DEV and s.ladder_rung == "full_mesh"

    assert _placements(s) == _placements(ref)
    assert all(r.node is not None for r in s.results)
    _assert_clean(s)


def test_progressive_losses_walk_ladder_to_single_chip(injector):
    """Losing devices one by one walks the ladder down (8 -> 4 -> ... ->
    a 1-device mesh = the single-chip rung), placements stay
    bit-identical throughout, and clearing the outage restores the full
    mesh from the bottom rung."""
    ref, s = _sched(0), _sched(N_DEV)
    ids = sorted(mesh_device_ids(s.mesh))

    expected_width = {0: 4, 1: 4, 2: 4, 3: 4, 4: 2, 5: 2, 6: 1}
    for k, d in enumerate(ids[:7]):
        _lose(injector, d)
        _feed(s, _pods(4, prefix=f"w{k}"))
        _feed(ref, _pods(4, prefix=f"w{k}"))
        assert s.mesh is not None and s.mesh.size == expected_width[k], (
            f"after losing {k + 1} devices: width {s.mesh.size}"
        )
    assert s.ladder_rung == "single_chip"
    assert s.device_health.state == BREAKER_CLOSED

    injector.disarm()
    time.sleep(s.config.breaker_open_s * 2)
    s.run_once(timeout=0.0)
    assert s.mesh.size == N_DEV and s.ladder_rung == "full_mesh"
    _feed(s, _pods(4, prefix="back"))
    _feed(ref, _pods(4, prefix="back"))
    assert _placements(s) == _placements(ref)
    _assert_clean(s)


def test_scatter_fault_attributes_and_shrinks(injector):
    """The scatter seam (satellite): a shard-targeted fault on the
    dirty-row scatter — previously unclassified — is attributed and
    shrinks the mesh like any other shard fault."""
    ref, s = _sched(0), _sched(N_DEV)
    lost = sorted(mesh_device_ids(s.mesh))[5]

    _feed(s, _pods(8, prefix="a"))  # first wave: full upload, resident
    _feed(ref, _pods(8, prefix="a"))
    injector.arm(SITE_SCATTER, kind=FAULT_PERSISTENT, device_index=lost)
    _feed(s, _pods(4, prefix="b"))  # dirty-row wave: scatter fires
    _feed(ref, _pods(4, prefix="b"))
    assert ("scatter", FAULT_PERSISTENT) in injector.log
    assert s.mesh.size == 4 and lost not in mesh_device_ids(s.mesh)
    assert s.device_health.state == BREAKER_CLOSED
    assert _placements(s) == _placements(ref)
    _assert_clean(s)


def test_shard_loss_mid_overload_storm_soak(injector):
    """The acceptance soak: a sustained arrival storm, one of 8 devices
    lost mid-storm, cleared before the end — the scheduler shrinks,
    keeps serving, climbs back, and the invariant checker ends CLEAN:
    every offered pod is bound or still accounted, none lost, no
    double-bind, zero violations."""
    s = _sched(N_DEV, n_nodes=64, adaptive_batch=True, batch_size=32,
               batch_size_min=8)
    lost = sorted(mesh_device_ids(s.mesh))[4]
    offered = 0
    for wave in range(6):
        pods = _pods(24, prefix=f"storm{wave}")
        offered += len(pods)
        for p in pods:
            s.queue.add(p)
        if wave == 1:
            _lose(injector, lost)
        if wave == 4:
            injector.disarm()
            time.sleep(s.config.breaker_open_s * 2)
        deadline = time.monotonic() + 10.0
        while s.queue.has_schedulable() and time.monotonic() < deadline:
            s.run_once(timeout=0.0)
    _drain(s)
    # idle polls with the pipeline drained run the lost-shard probe
    time.sleep(s.config.breaker_open_s * 2)
    s.run_once(timeout=0.0)

    placed = s._outcome_totals["placed"]
    parked = len(s.queue)
    assert placed + parked == offered, (placed, parked, offered)
    assert placed > 0
    assert s.mesh.size == N_DEV, "mesh did not climb back after the storm"
    _assert_clean(s)
    # the checker watched real traffic, not nothing
    assert s.invariants.events_total > offered


# ---------------------------------------------- telemetry + debug surface


def test_telemetry_repins_shardings_after_rebuild(injector):
    """The stale-sharding satellite: after a shrink the analytics
    side-launch must reduce over the NEW mesh's resident buffers (fresh
    in_shardings), stay bit-exact vs numpy, and /debug/cluster must
    report the live width/rung — not the startup topology."""
    from kubernetes_tpu.ops.analytics import (
        cluster_analytics_auto,
        cluster_analytics_np,
    )

    s = _sched(N_DEV, telemetry=True, telemetry_interval_cycles=1)
    lost = sorted(mesh_device_ids(s.mesh))[0]
    _feed(s, _pods(8, prefix="a"))
    _lose(injector, lost)
    _feed(s, _pods(8, prefix="b"))
    _feed(s, _pods(8, prefix="c"))
    assert s.mesh.size == 4

    res = s._dev_snapshot.resident(("allocatable", "requested", "valid"))
    assert res is not None
    assert all(len(b.addressable_shards) == 4 for b in res)
    a = cluster_analytics_auto(*res)
    host = s._dev_snapshot._host
    b = cluster_analytics_np(
        host["allocatable"], host["requested"], host["valid"]
    )
    for f in dataclasses.fields(a):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f.name)), np.asarray(getattr(b, f.name)),
            err_msg=f.name,
        )

    summary = s.telemetry.summary()
    mesh_info = summary["mesh"]
    assert mesh_info["width"] == 4 and mesh_info["full_width"] == N_DEV
    assert mesh_info["rung"] == "shrunken_mesh"
    assert mesh_info["shards"][str(lost)] == "open"
    assert mesh_info["invariants"]["violations_total"] == 0
    payload = s.telemetry.debug_payload(limit=4)
    assert payload["samples"][-1]["mesh"]["width"] == 4


def test_heartbeat_reports_mesh_and_rung(injector):
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
        s = _sched(N_DEV, heartbeat_s=0.01)
        lost = sorted(mesh_device_ids(s.mesh))[3]
        _lose(injector, lost)
        _feed(s, _pods(8, prefix="hb"))
        time.sleep(0.02)
        s.run_once(timeout=0.0)
        beats = [r for r in records if r.startswith("heartbeat:")]
        assert beats
        line = beats[-1]
        assert "mesh=4" in line
        assert "rung=shrunken_mesh" in line
        assert "shards_lost=1" in line
        assert "invariant_violations=0" in line
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)


# -------------------------------------------------------- config plumbing


def test_component_config_plumbs_ladder_knobs():
    from kubernetes_tpu.config.types import KubeSchedulerConfiguration

    cc = KubeSchedulerConfiguration.from_dict({
        "shardDevices": 8,
        "meshShrinkEnabled": False,
        "shardBreakerFailureThreshold": 5,
        "invariantChecks": False,
    })
    sc = SchedulerConfig.from_component_config(cc)
    assert sc.mesh_shrink is False
    assert sc.shard_breaker_failure_threshold == 5
    assert sc.invariant_checks is False
    dflt = SchedulerConfig.from_component_config(
        KubeSchedulerConfiguration.from_dict({})
    )
    assert dflt.mesh_shrink is True
    assert dflt.shard_breaker_failure_threshold == 2
    assert dflt.invariant_checks is True


def test_mesh_shrink_disabled_keeps_whole_mesh_policy(injector):
    """meshShrinkEnabled=false restores the PR 3 behavior: a shard fault
    trips the GLOBAL breaker and the CPU adapter serves — no rebuild."""
    s = _sched(N_DEV, mesh_shrink=False)
    lost = sorted(mesh_device_ids(s.mesh))[2]
    _lose(injector, lost)
    _feed(s, _pods(8, prefix="a"))
    assert s.mesh.size == N_DEV  # never rebuilt
    assert s.device_health.state != BREAKER_CLOSED or (
        ("closed", "open") in s.device_health.transitions
    )
    assert all(r.node is not None for r in s.results)
    _assert_clean(s)


# ----------------------------------------------------- review regressions


def test_violation_callback_fires_outside_lock():
    """The on_violation callback may re-enter the checker (the
    scheduler's postmortem state dump calls summary()): it must be
    delivered OUTSIDE the checker's non-reentrant lock, or the first
    real violation deadlocks the scheduling thread."""
    fired = []
    inv = InvariantChecker(
        on_violation=lambda rule, detail: fired.append(
            (rule, inv.summary()["violations_total"])
        )
    )
    pod = make_pod("dead", cpu="1m", mem="1Mi")
    inv.note_popped([pod])
    inv.note_bound(pod, "n1")
    inv.note_bound(pod, "n2")  # double-bind: must not hang
    # a re-bind violates two rules (double_bind + resolved-twice); both
    # callbacks delivered, each AFTER the recording lock released (the
    # summary() the callback makes already sees every recorded count)
    assert [r for r, _ in fired] == ["double_bind", "conservation"]
    assert all(total == 2 for _, total in fired)
    assert inv.violations_total() == 2


def test_successful_cycles_heal_shard_streaks():
    """'Consecutive' means consecutive: clean round-trips between two
    isolated transients reset the per-shard streak (the analog of
    DeviceHealth.record_success), so unrelated faults weeks apart can
    never accumulate into a mesh shrink."""
    sh = ShardHealth(range(4), failure_threshold=2)
    assert sh.record_failure(1, FAULT_TRANSIENT) is False
    sh.heal({0, 1, 2, 3})  # a clean cycle over the whole mesh
    assert sh.record_failure(1, FAULT_TRANSIENT) is False
    assert sh.state(1) == BREAKER_CLOSED and sh.lost() == frozenset()
    # back-to-back (no heal between) still opens at the threshold
    assert sh.record_failure(1, FAULT_TRANSIENT) is True
    assert sh.lost() == {1}
    # healing never touches a non-closed shard: its streak belongs to
    # the half-open probe
    sh.heal({1})
    assert sh.state(1) == BREAKER_OPEN


def test_shard_fault_with_retries_does_not_shrink_on_old_streaks(injector):
    """Live version of the heal contract: a single transient shard fault
    (retried same-batch), many clean cycles, then another single
    transient — the mesh must still be whole."""
    s = _sched(N_DEV)
    target = sorted(mesh_device_ids(s.mesh))[1]
    injector.arm(SITE_FENCE, kind=FAULT_TRANSIENT, count=1,
                 device_index=target)
    _feed(s, _pods(8, prefix="a"))
    for wave in range(2):  # clean cycles heal the streak
        _feed(s, _pods(8, prefix=f"mid{wave}"))
    injector.arm(SITE_FENCE, kind=FAULT_TRANSIENT, count=1,
                 device_index=target)
    _feed(s, _pods(8, prefix="b"))
    assert s.mesh.size == N_DEV, "isolated transients accumulated"
    assert s.shard_health.lost() == frozenset()
    assert all(r.node is not None for r in s.results)
    _assert_clean(s)


def test_mesh_rebuild_keeps_compile_cache_dir(injector, tmp_path):
    """A mesh shrink and climb-back never re-point the persistent compile
    cache: one directory serves every mesh width (jax's own cache key
    separates the executables), and a process that configured no cache
    gets none from a rebuild."""
    import jax

    prior = getattr(jax.config, "jax_compilation_cache_dir", None)
    try:
        for base in (None, str(tmp_path / "cache")):
            jax.config.update("jax_compilation_cache_dir", base)
            s = _sched(N_DEV)
            lost = sorted(mesh_device_ids(s.mesh))[0]
            _lose(injector, lost)
            _feed(s, _pods(8, prefix=f"a{base is None}"))
            assert s.mesh.size == 4
            assert jax.config.jax_compilation_cache_dir == base
            injector.disarm()
            time.sleep(s.config.breaker_open_s * 2)
            s.run_once(timeout=0.0)  # probe restores the mesh
            assert s.mesh.size == N_DEV
            assert jax.config.jax_compilation_cache_dir == base
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)


def test_shard_lost_accumulation_preserves_fired_budget(injector):
    """Accumulating a second lost device must not refresh the first
    arm's count= budget: arm_devices merges targets while keeping the
    consumed `fired` count, and clear_devices removes targets without
    touching untargeted arms."""
    injector.arm_devices(SITE_FENCE, {3}, kind=FAULT_PERSISTENT, count=2)
    with pytest.raises(PersistentDeviceError):
        injector.fire(SITE_FENCE, devices={3})
    injector.arm_devices(SITE_FENCE, {0}, kind=FAULT_PERSISTENT)
    with pytest.raises(PersistentDeviceError):
        injector.fire(SITE_FENCE, devices={0, 3})
    # the 2-fire budget is spent: accumulation did not refresh it
    injector.fire(SITE_FENCE, devices={0})
    injector.fire(SITE_FENCE, devices={3})
    injector.clear_devices(SITE_FENCE, {3})
    assert injector.is_armed(SITE_FENCE)  # device 0 still targeted
    injector.clear_devices(SITE_FENCE)
    assert not injector.is_armed(SITE_FENCE)


def test_disruptions_shard_lost_primitive_drives_ladder():
    """The chaos wrapper end-to-end: Disruptions.shard_lost darkens one
    mesh device (the scheduler shrinks, not demotes), a second call
    accumulates, and clear_shard_lost lets the probe climb back."""
    from kubernetes_tpu.runtime.chaos import Disruptions
    from kubernetes_tpu.runtime.cluster import LocalCluster

    s = _sched(N_DEV)
    ids = sorted(mesh_device_ids(s.mesh))
    dis = Disruptions(LocalCluster())
    try:
        dis.shard_lost(ids[2])
        _feed(s, _pods(8, prefix="a"))
        assert s.mesh.size == 4 and s.shard_health.lost() == {ids[2]}
        assert s.device_health.state == BREAKER_CLOSED
        dis.shard_lost(ids[0])  # accumulates: both devices dark
        _feed(s, _pods(8, prefix="b"))
        assert s.shard_health.lost() == {ids[0], ids[2]}
        dis.clear_shard_lost(ids[0])  # partial clear: ids[2] still dark
        time.sleep(s.config.breaker_open_s * 2)
        s.run_once(timeout=0.0)
        assert s.shard_health.lost() == {ids[2]}
        dis.clear_shard_lost()
        time.sleep(s.config.breaker_open_s * 2)
        s.run_once(timeout=0.0)
        assert s.mesh.size == N_DEV and s.shard_health.lost() == frozenset()
        assert all(r.node is not None for r in s.results)
        _assert_clean(s)
    finally:
        dis.clear_device_faults()
