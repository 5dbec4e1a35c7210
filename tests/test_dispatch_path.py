"""The scheduling cycle's one dispatch path.

`Scheduler.run_once` serves a plain batch through pop -> encode ->
dispatch -> fence -> commit -> observe, either pipelined (the commit
tail of batch k overlaps the dispatch of batch k+1) or synchronously.
These pins cover the seams of that path no other file reaches:

* the relaunch discipline of the resilient fence: a classified fault
  raised by the re-dispatch itself is retried and then degrades to the
  CPU engine, never escaping the cycle;
* a transient fault in a live cycle's dirty-row scatter is retried, and
  the device-resident snapshot then equals a fresh full upload (one
  device and the 8-virtual-device mesh);
* `Scheduler.prewarm` leaves no engine program for later cycles to
  load;
* the pipelined loop's batch-loss guard: when the new dispatch or the
  in-flight state commit raises, the popped batch is requeued and the
  in-flight winners' tail still runs;
* which branch `run_once` takes, with identical placements either way.
"""

import dataclasses
import logging

import numpy as np
import pytest

from kubernetes_tpu.codec import SnapshotEncoder, transfer
from kubernetes_tpu.codec.faults import (
    FAULT_PERSISTENT,
    FAULT_TRANSIENT,
    FaultInjector,
    PersistentDeviceError,
    install_injector,
)
from kubernetes_tpu.framework.v1alpha1 import Framework
from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.queue import (
    TIER_EXPRESS,
    PodBackoff,
    PriorityQueue,
)
from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.utils import metrics as m

from fixtures import TEST_DIMS, make_node, make_pod

ENGINES = ["sequential", "speculative"]


@pytest.fixture
def injector():
    inj = FaultInjector(seed=30)
    remove = install_injector(inj)
    yield inj
    remove()


def _sched(n_nodes=8, framework=None, **cfg_kw):
    cache = SchedulerCache(SnapshotEncoder(TEST_DIMS))
    for i in range(n_nodes):
        cache.add_node(make_node(
            f"n{i}", cpu="8", mem="16Gi",
            labels={"disk": "ssd" if i % 2 else "hdd"},
        ))
    kw = dict(
        batch_size=8, batch_window_s=0.0, disable_preemption=True,
        device_backoff_base_s=0.001, device_backoff_max_s=0.005,
        breaker_open_s=0.02,
    )
    kw.update(cfg_kw)
    return Scheduler(
        cache=cache,
        queue=PriorityQueue(
            backoff=PodBackoff(initial=0.001, max_duration=0.005)
        ),
        binder=lambda pod, node: True,
        config=SchedulerConfig(**kw),
        framework=framework,
    )


def _pods(n, prefix="p"):
    return [
        make_pod(f"{prefix}{i}", cpu=f"{100 + 50 * (i % 5)}m",
                 mem=f"{64 * (1 + i % 3)}Mi")
        for i in range(n)
    ]


def _feed(s, pods):
    for p in pods:
        s.queue.add(p)


def _drain(s, rounds=400):
    for _ in range(rounds):
        if not len(s.queue) and not s.pipeline_pending:
            break
        s.run_once(timeout=0.0)
    s.flush_pipeline()


def _placements(s):
    return {r.pod.name: r.node for r in s.results if r.node is not None}


def _assert_conserved(s, n):
    assert len(_placements(s)) == n
    assert s.invariants.violations_total() == 0
    assert s.invariants.assert_drained()


# ------------------------------------------------- the relaunch discipline


@pytest.mark.chaos
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("kind", [FAULT_TRANSIENT, FAULT_PERSISTENT])
def test_relaunch_fault_is_retried_then_degrades(injector, engine, kind):
    """A transient fence fault approves a same-batch re-dispatch; that
    re-dispatch faults too.  The fault feeds the same retry policy
    (a transient one is retried once more, a persistent one is not),
    then the batch degrades to the CPU engine: nothing escapes, every
    pod is placed as the unfaulted run places it."""
    ref = _sched(engine=engine, pipeline_commit=True)
    _feed(ref, _pods(8))
    _drain(ref)

    s = _sched(engine=engine, pipeline_commit=True)
    _feed(s, _pods(8))
    s.run_once(timeout=0.0)
    assert s.pipeline_pending
    injector.arm("fence", kind=FAULT_TRANSIENT, count=1)
    injector.arm("dispatch", kind=kind)
    degraded0 = m.DEGRADED_CYCLES.value
    assert s.flush_pipeline() == 8
    injector.disarm()
    assert injector.log[0] == ("fence", FAULT_TRANSIENT)
    relaunches = [e for e in injector.log if e[0] == "dispatch"]
    assert relaunches == [("dispatch", kind)] * (
        2 if kind == FAULT_TRANSIENT else 1
    )
    assert m.DEGRADED_CYCLES.value == degraded0 + 1
    assert _placements(s) == _placements(ref)
    _assert_conserved(s, 8)


# ------------------------------------- resident snapshot across live cycles


@pytest.mark.chaos
@pytest.mark.parametrize(
    "shard", [0, pytest.param(8, marks=pytest.mark.sharded)]
)
@pytest.mark.parametrize("engine", ENGINES)
def test_scatter_fault_retry_keeps_resident_snapshot_coherent(
    injector, engine, shard, monkeypatch
):
    """Back-to-back live cycles refresh the resident device snapshot by
    dirty-row scatters.  A transient fault in one of those scatters is
    retried with the same dirty rows; afterwards the resident snapshot
    must equal a fresh full upload of the host snapshot, field by
    field — a field whose scatter faulted is re-sent, never skipped."""
    scatters = []
    for name in ("_scatter_rows", "_scatter_rows_sharded"):
        orig = getattr(transfer, name)

        def spy(*a, _orig=orig, **kw):
            scatters.append(1)
            return _orig(*a, **kw)

        monkeypatch.setattr(transfer, name, spy)
    s = _sched(n_nodes=64, engine=engine, pipeline_commit=True,
               shard_devices=shard)
    _feed(s, _pods(16, prefix="w0-"))
    _drain(s)
    injector.arm("scatter", kind=FAULT_TRANSIENT, count=1)
    for wave in (1, 2):
        _feed(s, _pods(16, prefix=f"w{wave}-"))
        _drain(s)
    assert injector.log == [("scatter", FAULT_TRANSIENT)]
    assert len(scatters) > 1, "no cycle refreshed the snapshot by scatter"
    _assert_conserved(s, 48)
    cluster, _ = s.cache.snapshot()
    resident = s._dev_snapshot.update(
        cluster, dirty_rows=s.cache.encoder.take_dirty_rows()
    )
    fresh = transfer.DeviceSnapshotCache(mesh=s.mesh).update(cluster)
    for f in dataclasses.fields(cluster):
        np.testing.assert_array_equal(
            np.asarray(getattr(resident, f.name)),
            np.asarray(getattr(fresh, f.name)),
            err_msg=f.name,
        )


# ------------------------------------------------------------- prewarm


@pytest.fixture
def compile_log(caplog):
    """Names of the programs jax compiles, in order (`take()` drains)."""
    import jax

    prev = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    caplog.set_level(logging.WARNING, logger="jax._src.interpreters.pxla")

    class Log:
        def take(self):
            got = [
                r.getMessage().split(" ", 2)[1] for r in caplog.records
                if r.getMessage().startswith("Compiling ")
            ]
            caplog.clear()
            return got

    try:
        yield Log()
    finally:
        jax.config.update("jax_log_compiles", prev)


@pytest.mark.parametrize(
    "shard", [0, pytest.param(8, marks=pytest.mark.sharded)]
)
@pytest.mark.parametrize("engine", ENGINES)
def test_prewarm_leaves_no_engine_program_to_load(engine, shard,
                                                  compile_log):
    """After prewarm, a cycle at every AIMD pow2 width and at the
    express width compiles no engine program, on one device and on the
    mesh: what live cycles still
    load are only the side kernels whose shapes prewarm does not own
    (the dirty-row scatter, the telemetry reduction, the regret
    counterfactual)."""
    import jax

    from kubernetes_tpu.ops import analytics
    from kubernetes_tpu.runtime import quality

    side = {
        f"jit({fn.__name__})" for fn in (
            transfer._scatter_impl, analytics._analytics_jax,
            quality._ffd_counterfactual,
        )
    }
    s = _sched(n_nodes=16, engine=engine, adaptive_batch=True,
               batch_size=16, batch_size_min=4, express_lane=True,
               express_batch_size=2, express_priority_threshold=1000,
               shard_devices=shard)
    jax.clear_caches()
    compile_log.take()
    timings = s.prewarm()
    assert sorted(timings) == [2, 4, 8, 16]
    warm = compile_log.take()
    assert warm and not set(warm) & side, warm
    s.schedule_cycle(_pods(2, prefix="express-"), tier=TIER_EXPRESS)
    for w in (4, 8, 16):
        s.schedule_cycle(_pods(w, prefix=f"bulk{w}-"))
    live = compile_log.take()
    assert set(live) <= side, (warm, live)
    assert len(_placements(s)) == 2 + 4 + 8 + 16


# ------------------------------------------- the pipelined batch-loss guard


class _HostBug(RuntimeError):
    pass


def _break(s, where, classified, injector, monkeypatch):
    """Make the next pipelined step fail at `where`: the new batch's
    dispatch, or the in-flight batch's state commit (its fence)."""
    if classified:
        injector.arm("dispatch" if where == "dispatch" else "fence",
                     kind=FAULT_PERSISTENT)
        return PersistentDeviceError
    if where == "dispatch":
        def boom(*a, **kw):
            raise _HostBug("encode bug")

        monkeypatch.setattr(s, "_encode_and_dispatch", boom)
    else:
        class Boom:
            seconds = 0.0

            def result(self):
                raise _HostBug("stale winners buffer")

        s._in_flight.fetch = Boom()
    return _HostBug


@pytest.mark.chaos
@pytest.mark.parametrize("classified", [True, False],
                         ids=["classified", "unclassified"])
@pytest.mark.parametrize("where", ["dispatch", "state_commit"])
def test_pipelined_guard_requeues_popped_batch(
    where, classified, injector, monkeypatch
):
    """Batch A is in flight when batch B is popped.  If B's dispatch
    raises, A's state commit has landed and its tail still runs (A's
    winners bind) while B is requeued; if A's state commit raises, both
    are requeued.  No pod is lost or resolved twice, and once the fault
    clears every pod binds."""
    s = _sched(pipeline_commit=True, cpu_fallback=False)
    wave_a, wave_b = _pods(8, prefix="a-"), _pods(8, prefix="b-")
    _feed(s, wave_a)
    s.run_once(timeout=0.0)
    assert s.pipeline_pending
    _feed(s, wave_b)
    err = _break(s, where, classified, injector, monkeypatch)
    with pytest.raises(err):
        s.run_once(timeout=0.0)
    assert not s.pipeline_pending
    bound = set(_placements(s))
    if where == "dispatch":
        assert bound == {p.name for p in wave_a}
    else:
        assert not bound
    assert len(s.queue) == 16 - len(bound)
    injector.disarm()
    monkeypatch.undo()
    s.queue.move_all_to_active()
    _drain(s)
    _assert_conserved(s, 16)


# ------------------------------------------------ run_once's two branches


@pytest.mark.parametrize("mode", [
    "pipelined", "synchronous", "per_pod_commit", "framework",
])
def test_run_once_branch_and_placements(mode):
    """run_once pipelines a plain batch only when pipeline_commit is on,
    the commit is batched and no framework is configured; every other
    combination commits synchronously.  Either way the placements are
    the synchronous ones."""
    ref = _sched(pipeline_commit=False)
    _feed(ref, _pods(24))
    _drain(ref)

    kw = {
        "pipelined": dict(pipeline_commit=True),
        "synchronous": dict(pipeline_commit=False),
        "per_pod_commit": dict(pipeline_commit=True, batched_commit=False),
        "framework": dict(pipeline_commit=True),
    }[mode]
    s = _sched(framework=Framework() if mode == "framework" else None,
               **kw)
    _feed(s, _pods(24))
    s.run_once(timeout=0.0)
    assert s.pipeline_pending == (mode == "pipelined")
    _drain(s)
    assert _placements(s) == _placements(ref)
    _assert_conserved(s, 24)
