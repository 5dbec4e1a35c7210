"""Live multi-chip sharded control plane (ISSUE 9).

The real `Scheduler` — not the dry-run harness of tests/test_mesh.py —
running with config.shard_devices/mesh_shape: the snapshot's node axis
shards across the 8-virtual-device CPU mesh (conftest provisions
XLA_FLAGS=--xla_force_host_platform_device_count=8), every engine launch
and the incremental dirty-row upload run sharded, and placements must be
BIT-IDENTICAL to the single-chip path across chained batches, both
engines, through the express/bulk lanes, and across the full resilience
stack (breaker trip -> CPU degrade -> half-open restore).
"""

import dataclasses
import time

import jax
import numpy as np
import pytest

from kubernetes_tpu.codec import SnapshotEncoder
from kubernetes_tpu.codec import transfer
from kubernetes_tpu.codec.faults import (
    FAULT_PERSISTENT,
    FaultInjector,
    install_injector,
)
from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.queue import PriorityQueue
from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig

from fixtures import TEST_DIMS, make_node, make_pod

pytestmark = pytest.mark.sharded

N_DEV = 8


# --------------------------------------------------------------- helpers


def _world(cache, n_nodes=16):
    for i in range(n_nodes):
        cache.add_node(make_node(
            f"n{i}", cpu="8", mem="16Gi",
            labels={"disk": "ssd" if i % 2 else "hdd",
                    "tier": "a" if i % 3 else "b"},
        ))


def _sched(shard=0, mesh_shape=None, n_nodes=16, **cfg_kw):
    cache = SchedulerCache(SnapshotEncoder(TEST_DIMS))
    _world(cache, n_nodes)
    kw = dict(
        batch_size=8, batch_window_s=0.0, disable_preemption=True,
        batched_commit=True, pipeline_commit=True,
        device_backoff_base_s=0.001, device_backoff_max_s=0.005,
        breaker_open_s=0.02,
        shard_devices=shard, mesh_shape=mesh_shape,
    )
    kw.update(cfg_kw)
    return Scheduler(
        cache=cache, queue=PriorityQueue(), config=SchedulerConfig(**kw)
    )


def _pods(n, prefix="p"):
    out = []
    for i in range(n):
        out.append(make_pod(
            f"{prefix}{i}", cpu="200m", mem="256Mi",
            labels={"app": f"d{i % 3}"},
            node_selector={"disk": "ssd"} if i % 4 == 0 else None,
            priority=10 if i % 5 == 0 else 0,
        ))
    return out


def _drain(s):
    while s.queue.has_schedulable() or s.pipeline_pending:
        s.run_once(timeout=0.0)
    s.flush_pipeline()


def _placements(s):
    return [(r.pod.name, r.node) for r in s.results]


def _assert_resident_sharded(s, n_shards=N_DEV):
    res = s._dev_snapshot.resident(("allocatable", "requested", "valid"))
    assert res is not None, "no resident device snapshot after live cycles"
    for buf in res:
        assert len(buf.addressable_shards) == n_shards, buf.sharding
    # genuinely distributed, not replicated: distinct shard index ranges
    idx = {str(sh.index) for sh in res[0].addressable_shards}
    assert len(idx) == n_shards


# ------------------------------------------------- placement bit-identity


def test_sharding_off_by_default():
    s = _sched()
    assert SchedulerConfig().shard_devices == 0
    assert s.mesh is None
    assert s._dev_snapshot.mesh is None


@pytest.mark.parametrize("engine", ["speculative", "sequential"])
def test_live_chained_batches_sharded_match_single_chip(engine):
    """schedule_cycle through the real Scheduler, sharded over 8 devices,
    across CHAINED batches (committed state feeds the next snapshot):
    placements bit-identical to the single-chip path, both engines."""
    single, sharded = _sched(0, engine=engine), _sched(N_DEV, engine=engine)
    assert sharded.mesh is not None and sharded.mesh.size == N_DEV
    for s in (single, sharded):
        for p in _pods(24):
            s.queue.add(p)
        _drain(s)
    assert _placements(single) == _placements(sharded)
    assert any(r.node is not None for r in sharded.results)
    _assert_resident_sharded(sharded)


def test_two_level_dcn_ici_mesh_matches_single_chip():
    single, sharded = _sched(0), _sched(0, mesh_shape="2x4")
    assert sharded.mesh is not None
    assert tuple(sharded.mesh.axis_names) == ("dcn", "ici")
    for s in (single, sharded):
        for p in _pods(16):
            s.queue.add(p)
        _drain(s)
    assert _placements(single) == _placements(sharded)
    _assert_resident_sharded(sharded)


def test_express_bulk_interleaved_sharded_identity():
    """Interleaved express/bulk lanes on the mesh: the same pop order
    through the sharded scheduler places exactly as single-chip, and the
    express cycles really run at the express width on sharded state."""
    kw = dict(express_lane=True, express_batch_size=4,
              express_priority_threshold=1000)
    single, sharded = _sched(0, **kw), _sched(N_DEV, **kw)
    for s in (single, sharded):
        for i, p in enumerate(_pods(18, prefix="b")):
            s.queue.add(p)
        for i in range(5):
            p = make_pod(f"e{i}", cpu="100m", mem="128Mi", priority=2000)
            s.queue.add(p)
        _drain(s)
    assert _placements(single) == _placements(sharded)
    express = [r for r in sharded.results if r.pod.name.startswith("e")]
    assert len(express) == 5 and all(r.node is not None for r in express)
    _assert_resident_sharded(sharded)


# ---------------------------------------------- dirty-row shard scatter


def test_dirty_row_scatter_routes_to_owning_shard(monkeypatch):
    """The incremental upload stays O(dirty) on the mesh: a changed
    row-indexed field goes through the SHARDED scatter (not a whole-tensor
    re-upload), and afterwards every shard's block matches the host
    snapshot's rows it owns."""
    sched = _sched(N_DEV)
    cache, enc = sched.cache, sched.cache.encoder
    dsc = sched._dev_snapshot
    cluster, _ = cache.snapshot()
    enc.take_dirty_rows()  # drain the ingest-time dirty stream
    dsc.update(cluster)    # full upload: resident baseline

    scattered = []
    orig = transfer._scatter_rows_sharded

    def spy(dev, rows, vals, sharding):
        scattered.append((rows.copy(), sharding))
        return orig(dev, rows, vals, sharding)

    monkeypatch.setattr(transfer, "_scatter_rows_sharded", spy)

    # commit two pods on rows owned by DIFFERENT shards (rows 1 and 9 of
    # the 16-row axis: shards 0 and 4 on the 8-device mesh)
    cache.assume_pods([
        make_pod("d0", cpu="1", mem="1Gi", node_name="n1"),
        make_pod("d1", cpu="2", mem="2Gi", node_name="n9"),
    ])
    cluster2, _ = cache.snapshot()
    rows = enc.take_dirty_rows()
    assert len(rows) > 0
    dev2 = dsc.update(cluster2, dirty_rows=rows)

    assert scattered, "changed row fields must scatter, not re-upload"
    for rows_p, sharding in scattered:
        assert set(np.asarray(rows_p)) <= set(np.asarray(rows))
        assert not sharding.is_fully_replicated
    # the scatter path, not the whole-tensor path: the host record for
    # requested is the new snapshot array (committed by the scatter arm)
    assert dsc._host["requested"] is np.asarray(cluster2.requested)
    # per-shard content: each device's block equals the host rows it owns
    for name in ("requested", "nonzero_req", "allocatable"):
        host = np.asarray(getattr(cluster2, name))
        dev = getattr(dev2, name)
        np.testing.assert_array_equal(np.asarray(dev), host)
        assert len(dev.addressable_shards) == N_DEV
        for sh in dev.addressable_shards:
            np.testing.assert_array_equal(
                np.asarray(sh.data), host[sh.index[0]]
            )


def test_sharded_cache_rejects_indivisible_axis():
    from kubernetes_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(N_DEV)
    dsc = transfer.DeviceSnapshotCache(mesh=mesh)

    @dataclasses.dataclass
    class Tiny:
        allocatable: object

    with pytest.raises(ValueError, match="does not divide"):
        dsc.update(Tiny(allocatable=np.zeros((12, 4), np.float32)))


# --------------------------------------------------- resilience on mesh


@pytest.fixture
def injector():
    inj = FaultInjector(seed=11)
    remove = install_injector(inj)
    yield inj
    remove()


def test_breaker_trip_degrade_restore_on_mesh(injector):
    """The full resilience arc on the sharded engine: a persistent fault
    trips the breaker mid-cycle, the batch completes bit-identically via
    the CPU adapter, and after the cool-down the half-open canary
    restores the SHARDED fast path — with placements matching a healthy
    single-chip reference throughout."""
    ref = _sched(0)
    s = _sched(N_DEV)
    batch1, batch2 = _pods(8, prefix="a"), _pods(8, prefix="b")

    injector.arm("dispatch", kind=FAULT_PERSISTENT, count=1)
    res1 = s.schedule_cycle(list(batch1))
    assert all(r.node is not None for r in res1)
    assert s.device_health.state == "open"

    injector.disarm()
    time.sleep(s.config.breaker_open_s * 2)
    res2 = s.schedule_cycle(list(batch2))
    assert all(r.node is not None for r in res2)
    assert s.device_health.state == "closed"
    assert ("open", "half_open") in s.device_health.transitions
    assert ("half_open", "closed") in s.device_health.transitions

    # reference: the same two batches through a healthy single-chip path
    ref1 = ref.schedule_cycle(list(_pods(8, prefix="a")))
    ref2 = ref.schedule_cycle(list(_pods(8, prefix="b")))
    assert [r.node for r in res1] == [r.node for r in ref1]
    assert [r.node for r in res2] == [r.node for r in ref2]
    # the restore cycle re-uploaded the invalidated snapshot SHARDED
    _assert_resident_sharded(s)


def test_transient_fault_retries_same_batch_on_mesh(injector):
    injector.arm("fence", kind="transient", count=1)
    s = _sched(N_DEV)
    res = s.schedule_cycle(_pods(6))
    assert all(r.node is not None for r in res)
    assert s.device_health.state == "closed"
    _assert_resident_sharded(s)


# ------------------------------------------------ ledger across meshes


def test_ledger_record_replay_across_mesh_sizes(tmp_path):
    """Cycles recorded by the SHARDED live scheduler replay bit-identically
    (a) offline through a freshly built single-chip engine (the classic
    replay gate) and (b) through a DIFFERENTLY-SIZED mesh (4 devices) with
    the record's snapshot sharded over it — the sharded==unsharded
    identity makes the ledger mesh-portable."""
    from kubernetes_tpu.parallel.mesh import make_mesh, shard_cluster
    from kubernetes_tpu.runtime.ledger import (
        DecisionLedger,
        read_ledger,
        replay,
    )

    path = str(tmp_path / "sharded.ledger")
    s = _sched(N_DEV)
    # wire a file-backed ledger explicitly (attaching post-construction
    # mirrors what Scheduler(ledger=...) does)
    led = DecisionLedger(path=path)
    led.ensure_meta(s._engine_meta())
    s.ledger = led
    for p in _pods(16):
        s.queue.add(p)
    _drain(s)
    led.flush(10.0)
    assert led.cycles_total >= 2

    # (a) offline replay in "a fresh single-chip process"
    out = replay(path)
    assert out["bit_identical"], out

    # (b) replay through a 4-device mesh (records came from an 8-device
    # one): shard each reconstructed snapshot over the smaller mesh
    mesh4 = make_mesh(4)
    replayer = _sched(4)
    _header, records = read_ledger(path)
    assert records
    for rec in records:
        rec = dict(rec)
        rec["cluster"] = shard_cluster(rec["cluster"], mesh4)
        got = replayer.replay_cycle(rec)  # raises on any mismatch
        assert got.shape[0] == rec["n_pods"]


# ------------------------------------------------- analytics + telemetry


def test_sharded_analytics_bit_exact_vs_numpy():
    from kubernetes_tpu.ops.analytics import (
        cluster_analytics_auto,
        cluster_analytics_np,
    )

    s = _sched(N_DEV)
    for p in _pods(12):
        s.queue.add(p)
    _drain(s)
    res = s._dev_snapshot.resident(("allocatable", "requested", "valid"))
    _assert_resident_sharded(s)
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


def test_telemetry_hub_samples_sharded_resident_buffers():
    s = _sched(N_DEV, telemetry=True, telemetry_interval_cycles=1)
    for p in _pods(10):
        s.queue.add(p)
    _drain(s)
    summary = s.telemetry.summary()
    assert summary["analytics"] is not None
    assert summary["analytics"]["nodes"] == 16
    assert summary["analytics"]["utilization"]["cpu"]["mean"] > 0.0


# ------------------------------------------------- prewarm + mesh config


def test_prewarm_compiles_sharded_executables():
    single, sharded = _sched(0), _sched(N_DEV)
    timings = sharded.prewarm(widths=[8])
    assert set(timings) == {8} and timings[8] > 0
    for s in (single, sharded):
        for p in _pods(8):
            s.queue.add(p)
        _drain(s)
    assert _placements(single) == _placements(sharded)
    _assert_resident_sharded(sharded)


def test_build_mesh_validation():
    from kubernetes_tpu.parallel.mesh import build_mesh, mesh_total

    with pytest.raises(ValueError, match="power of two"):
        build_mesh(6)
    with pytest.raises(ValueError, match="<= 512"):
        build_mesh(1024)  # node arenas grow in 512-multiples above 2048
    with pytest.raises(ValueError, match="devices"):
        build_mesh(512)  # pow2 and under the cap, but not provisioned
    with pytest.raises(ValueError, match="total"):
        build_mesh(8, "2x2")
    with pytest.raises(ValueError, match="total"):
        build_mesh(4, "8")  # a conflicting 1D shape is an error too
    with pytest.raises(ValueError, match="not 'N' or 'OxI'"):
        build_mesh(None, "abc")
    with pytest.raises(ValueError, match="not 'N' or 'OxI'"):
        mesh_total("2xx4")
    with pytest.raises(ValueError, match="too many dimensions"):
        mesh_total("2x2x2")  # the preflight rejects what build_mesh would
    with pytest.raises(ValueError, match="non-positive"):
        mesh_total("-2x-4")  # multiplies to a plausible total (8)
    with pytest.raises(ValueError, match="non-positive"):
        build_mesh(None, "0x8")
    mesh, axis = build_mesh(None, "8")
    assert mesh.size == 8 and axis == "nodes"
    mesh2, axis2 = build_mesh(None, "2x4")
    assert mesh2.size == 8 and axis2 == ("dcn", "ici")
    assert mesh_total("2x4") == 8
    assert mesh_total(None, 8) == 8


def test_encoder_node_capacity_floor():
    # a sharded Scheduler floors the arena at mesh.size at startup so the
    # divisibility check can never fire mid-run from a small fleet; every
    # later width on the growth schedule keeps dividing over the mesh
    from kubernetes_tpu.codec.encoder import SnapshotEncoder

    enc = SnapshotEncoder()
    assert enc._cap_n < 128
    enc.ensure_node_capacity(128)
    assert enc._cap_n >= 128 and enc._cap_n % 128 == 0
    for _ in range(8):
        enc._grow_nodes()
        assert enc._cap_n % 128 == 0


def test_component_config_plumbs_shard_knobs():
    from kubernetes_tpu.config.types import KubeSchedulerConfiguration

    cc = KubeSchedulerConfiguration.from_dict(
        {"shardDevices": 8, "meshShape": "2x4"}
    )
    assert cc.shard_devices == 8 and cc.mesh_shape == "2x4"
    sc = SchedulerConfig.from_component_config(cc)
    assert sc.shard_devices == 8 and sc.mesh_shape == "2x4"
    assert KubeSchedulerConfiguration.from_dict({}).shard_devices == 0


def test_compile_cache_one_dir_for_every_topology(tmp_path, monkeypatch):
    """Single-chip and sharded processes share one cache directory: jax's
    own key hashes the device assignment, so their executables never
    serve each other, and the directory stays where it was placed."""
    from kubernetes_tpu.utils import compilecache as cc

    base = str(tmp_path / "cache")
    monkeypatch.delenv(cc.JAX_CACHE_ENV, raising=False)
    assert cc.resolve_cache_dir(base) == base
    monkeypatch.setenv(cc.JAX_CACHE_ENV, base)
    assert cc.resolve_cache_dir(None) == base
