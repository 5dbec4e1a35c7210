"""Latency-tiered express lane (ISSUE 6).

Pins the tentpole's contracts:
  * tier CLASSIFICATION at queue admission: annotation opt-in/out beats
    the priority threshold, default is bulk, gang members never ride
    express;
  * queue ROUTING: express pods surface only through pop_express_batch,
    bulk pops yield to an express arrival, depth/shed/delete accounting
    spans both lanes;
  * STARVATION guards both ways: bulk drains under sustained express
    load, express pods schedule promptly under a saturating bulk
    backlog;
  * placement BIT-IDENTITY: the interleaved two-lane run places every
    pod exactly where a single-lane scheduler replaying the same pop
    order does (both engines);
  * observability: tier label on the e2e histogram + phase counters,
    tier annotation on the schedule_cycle span, tier in postmortem
    state;
  * the raw-speed satellites: Scheduler.prewarm compiles the shared
    AIMD pow2 ladder (codec.schema.aimd_pow2_widths — the same list
    bench warmup sweeps) without perturbing placements, and
    utils/compilecache.py resolves/enables the persistent cache knob.
"""

import os

import pytest

from kubernetes_tpu.codec import SnapshotEncoder
from kubernetes_tpu.codec.schema import aimd_pow2_widths
from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.queue import (
    LATENCY_TIER_ANNOTATION,
    TIER_BULK,
    TIER_EXPRESS,
    PriorityQueue,
    classify_tier,
)
from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.utils import metrics as m

from fixtures import make_node, make_pod

ZONE = "failure-domain.beta.kubernetes.io/zone"


def _cluster(n_nodes=6, cpu="16", mem="32Gi"):
    enc = SnapshotEncoder()
    enc.add_nodes([
        make_node(f"n{i}", cpu=cpu, mem=mem,
                  labels={ZONE: f"z{i % 3}", "tier": "a" if i % 2 else "b"})
        for i in range(n_nodes)
    ])
    enc.add_spread_selector("default", {"app": "web"})
    return SchedulerCache(enc)


def _sched(cache=None, queue=None, binder=None, **cfg):
    cfg.setdefault("disable_preemption", True)
    cfg.setdefault("batch_size", 32)
    cfg.setdefault("batch_window_s", 0.0)
    return Scheduler(
        cache=cache if cache is not None else _cluster(),
        queue=queue,
        binder=binder or (lambda p, n: True),
        config=SchedulerConfig(**cfg),
    )


# ------------------------------------------------------------ classification


def test_classify_priority_threshold():
    hi = make_pod("hi", cpu="1", priority=2000)
    lo = make_pod("lo", cpu="1", priority=10)
    assert classify_tier(hi, 1000) == TIER_EXPRESS
    assert classify_tier(lo, 1000) == TIER_BULK
    # boundary is inclusive
    assert classify_tier(make_pod("edge", cpu="1", priority=1000), 1000) \
        == TIER_EXPRESS
    # no threshold -> priority alone never promotes
    assert classify_tier(hi, None) == TIER_BULK


def test_classify_annotation_wins_both_directions():
    opt_in = make_pod(
        "in", cpu="1",
        annotations={LATENCY_TIER_ANNOTATION: "express"},
    )
    opt_out = make_pod(
        "out", cpu="1", priority=5000,
        annotations={LATENCY_TIER_ANNOTATION: "bulk"},
    )
    junk = make_pod(
        "junk", cpu="1",
        annotations={LATENCY_TIER_ANNOTATION: "turbo"},
    )
    assert classify_tier(opt_in, None) == TIER_EXPRESS
    # explicit bulk opt-out beats a qualifying priority
    assert classify_tier(opt_out, 1000) == TIER_BULK
    # unknown annotation value falls through to the default
    assert classify_tier(junk, None) == TIER_BULK


def test_classify_default_is_bulk():
    assert classify_tier(make_pod("p", cpu="1"), None) == TIER_BULK


def test_gang_members_never_express():
    s = _sched(express_lane=True, express_priority_threshold=100)
    gang = make_pod("g0", cpu="1", priority=500,
                    labels={Scheduler.POD_GROUP_LABEL: "grp"})
    assert s._tier_of(gang) == TIER_BULK
    plain = make_pod("p0", cpu="1", priority=500)
    assert s._tier_of(plain) == TIER_EXPRESS


# ------------------------------------------------------------- queue routing


def test_express_routes_to_express_heap():
    q = PriorityQueue(tier_of=lambda p: classify_tier(p, 1000))
    q.add(make_pod("bulk1", cpu="1"))
    q.add(make_pod("exp1", cpu="1", priority=2000))
    q.add(make_pod("exp2", cpu="1",
                   annotations={LATENCY_TIER_ANNOTATION: "express"}))
    assert len(q) == 3
    assert q.express_depth() == 2
    # bulk pop never surfaces express pods
    assert q.pop(timeout=0.0).name == "bulk1"
    assert q.pop(timeout=0.0) is None
    got = [p.name for p in q.pop_express_batch(8)]
    assert got == ["exp1", "exp2"]  # priority order within the lane
    assert len(q) == 0


def test_bulk_pop_yields_to_express_arrival():
    import threading
    import time

    q = PriorityQueue(tier_of=lambda p: classify_tier(p, 1000))

    def _arrive():
        time.sleep(0.05)
        q.add(make_pod("exp", cpu="1", priority=2000))

    threading.Thread(target=_arrive, daemon=True).start()
    t0 = time.monotonic()
    # the bulk pop must NOT sit out its 5s timeout: the express arrival
    # interrupts it (returns None) so the run loop can serve the lane
    assert q.pop(timeout=5.0, yield_to_express=True) is None
    assert time.monotonic() - t0 < 2.0
    assert q.express_depth() == 1


def test_delete_and_requeue_respect_lanes():
    q = PriorityQueue(tier_of=lambda p: classify_tier(p, 1000))
    exp = make_pod("exp", cpu="1", priority=2000)
    q.add(exp)
    q.delete(exp)
    assert q.pop_express_batch(8) == []
    # an unschedulable requeue + move_all re-classifies back to express
    q.add(exp)
    [got] = q.pop_express_batch(8)
    q.add_unschedulable(got, q.scheduling_cycle)
    q.move_all_to_active()
    import time
    deadline = time.monotonic() + 5.0
    popped = []
    while not popped and time.monotonic() < deadline:
        popped = q.pop_express_batch(8)  # backoff expiry promotes it
        time.sleep(0.05)
    assert [p.name for p in popped] == ["exp"]


# ------------------------------------------------------- scheduler interleave


def test_express_pods_schedule_with_tier_metrics():
    cache = _cluster()
    q = PriorityQueue()
    s = _sched(cache=cache, queue=q, express_lane=True,
               express_priority_threshold=1000, express_batch_size=8)
    exp_before = m.E2E_LATENCY.labels(tier=TIER_EXPRESS).total
    bulk_before = m.E2E_LATENCY.labels(tier=TIER_BULK).total
    phase_before = m.CYCLE_PHASE_SECONDS.value(
        phase="encode", tier=TIER_EXPRESS
    )
    for i in range(5):
        q.add(make_pod(f"b{i}", cpu="100m"))
    for i in range(3):
        q.add(make_pod(f"e{i}", cpu="100m", priority=2000))
    placed = s.run_once(timeout=0.2)
    assert placed == 8
    assert m.E2E_LATENCY.labels(tier=TIER_EXPRESS).total == exp_before + 3
    assert m.E2E_LATENCY.labels(tier=TIER_BULK).total == bulk_before + 5
    assert m.CYCLE_PHASE_SECONDS.value(
        phase="encode", tier=TIER_EXPRESS
    ) > phase_before
    # the express cycle's span carries the tier annotation, and the
    # postmortem state records the last-dispatched tier
    spans = s.flight_recorder.spans()
    tiers = {sp.attrs.get("tier") for sp in spans}
    assert TIER_EXPRESS in tiers and TIER_BULK in tiers
    assert s._postmortem_state()["tier"] in (TIER_EXPRESS, TIER_BULK)


def test_bulk_drains_under_sustained_express_load():
    cache = _cluster(n_nodes=8, cpu="64")
    q = PriorityQueue()
    s = _sched(cache=cache, queue=q, express_lane=True,
               express_priority_threshold=1000, express_batch_size=4,
               batch_size=8)
    for i in range(16):
        q.add(make_pod(f"b{i}", cpu="10m"))
    seq = 0
    bulk_placed = 0
    # every iteration ADDS a full express batch — sustained express
    # pressure; the interleave must still hand the bulk lane one cycle
    # per iteration
    for _ in range(12):
        for _ in range(4):
            q.add(make_pod(f"e{seq}", cpu="10m", priority=2000))
            seq += 1
        s.run_once(timeout=0.05)
        bulk_placed = sum(
            1 for r in s.results
            if r.node is not None and r.pod.name.startswith("b")
        )
        if bulk_placed == 16:
            break
    assert bulk_placed == 16, f"bulk starved: {bulk_placed}/16 placed"


def test_express_served_promptly_under_bulk_saturation():
    cache = _cluster(n_nodes=8, cpu="64")
    q = PriorityQueue()
    s = _sched(cache=cache, queue=q, express_lane=True,
               express_priority_threshold=1000, express_batch_size=8,
               batch_size=16)
    # saturating bulk backlog: many more pods than one cycle drains
    for i in range(200):
        q.add(make_pod(f"b{i}", cpu="10m"))
    s.run_once(timeout=0.05)  # bulk lane mid-drain
    q.add(make_pod("urgent", cpu="10m", priority=2000))
    # the very next iteration must place the express pod, with ~all of
    # the bulk backlog still pending
    s.run_once(timeout=0.05)
    urgent = [r for r in s.results if r.pod.name == "urgent"]
    assert urgent and urgent[0].node is not None
    assert len(q) > 100  # bulk still deep: express did not wait it out


def test_bulk_batch_requeued_when_express_cycle_raises():
    """The bulk batch popped just before the express interleave is held
    only in run_once's frame: an express-cycle failure must requeue it
    (popped pods are never lost), not strand it Pending forever."""
    from kubernetes_tpu.runtime.queue import PodBackoff

    cache = _cluster()
    q = PriorityQueue(backoff=PodBackoff(initial=0.01, max_duration=0.02))
    s = _sched(cache=cache, queue=q, express_lane=True,
               express_priority_threshold=1000, batch_size=8)
    for i in range(5):
        q.add(make_pod(f"b{i}", cpu="10m"))
    q.add(make_pod("e0", cpu="10m", priority=2000))

    def boom():
        raise RuntimeError("express blew up")

    s._run_express = boom
    with pytest.raises(RuntimeError):
        s.run_once(timeout=0.05)
    # every popped bulk pod is back in the queue (parked unschedulable)
    assert len(q) >= 5
    del s._run_express
    q.move_all_to_active()  # the cluster-event revival path
    while len(q):
        s.run_once(timeout=0.1)
    placed = {r.pod.name for r in s.results if r.node is not None}
    assert {f"b{i}" for i in range(5)} <= placed


# --------------------------------------------------------------- bit-identity


@pytest.mark.parametrize("engine", ["sequential", "speculative"])
def test_interleaved_placements_bit_identical_to_single_lane(engine):
    """The tiered run's placements must equal a single-lane scheduler
    replaying the SAME pop order (express batch as its own cycle, then
    the bulk batch): the express lane changes WHEN pods schedule, never
    WHERE."""
    def pods():
        bulk = [
            make_pod(f"b{i}", cpu="500m", mem="1Gi",
                     labels={"app": "web"},
                     node_selector={"tier": "a"} if i % 3 == 0 else None)
            for i in range(12)
        ]
        exp = [
            make_pod(f"e{i}", cpu="500m", mem="1Gi",
                     labels={"app": "web"}, priority=2000 + (i % 2))
            for i in range(5)
        ]
        return bulk, exp

    # tiered run: queue admission classifies, run_once interleaves
    cache_a = _cluster()
    qa = PriorityQueue()
    sa = _sched(cache=cache_a, queue=qa, engine=engine, express_lane=True,
                express_priority_threshold=1000, express_batch_size=8,
                batch_size=16)
    bulk, exp = pods()
    for p in bulk:
        qa.add(p)
    for p in exp:
        qa.add(p)
    sa.run_once(timeout=0.2)
    placed_a = {r.pod.name: r.node for r in sa.results}

    # single-lane replay of the same pop order: express pods first (the
    # lane's priority-FIFO order), then the bulk batch
    cache_b = _cluster()
    sb = _sched(cache=cache_b, engine=engine, batch_size=16)
    bulk_b, exp_b = pods()
    exp_order = sorted(exp_b, key=lambda p: -p.spec.priority)
    for r in sb.schedule_cycle(exp_order):
        pass
    sb.schedule_cycle(bulk_b)
    placed_b = {r.pod.name: r.node for r in sb.results}

    assert placed_a == placed_b, (
        f"tiered vs single-lane diverged: "
        f"{ {k: (placed_a.get(k), placed_b.get(k)) for k in placed_a if placed_a.get(k) != placed_b.get(k)} }"
    )
    assert all(v is not None for v in placed_a.values())


# ------------------------------------------------- prewarm + width helper


def test_aimd_pow2_widths():
    assert aimd_pow2_widths(16, 256) == [16, 32, 64, 128, 256]
    assert aimd_pow2_widths(16, 16) == [16]
    # non-pow2 ends round UP to the encode pad widths actually compiled
    assert aimd_pow2_widths(12, 100) == [16, 32, 64, 128]
    assert aimd_pow2_widths(1, 4) == [1, 2, 4]
    # floor above the cap clamps to the cap — never an empty ladder
    assert aimd_pow2_widths(16, 8) == [8]


def test_prewarm_compiles_without_perturbing_state():
    cache = _cluster()
    q = PriorityQueue()
    s = _sched(cache=cache, queue=q, express_lane=True,
               express_priority_threshold=1000, express_batch_size=8,
               batch_size=16, adaptive_batch=True, batch_size_min=4)
    timings = s.prewarm()
    # the AIMD ladder (4..16) plus the express width (8, already inside)
    assert sorted(timings) == [4, 8, 16]
    assert all(t >= 0 for t in timings.values())
    assert s._last_index == 0  # rotation untouched
    assert len(s.results) == 0
    # placements after prewarm match a never-prewarmed scheduler (the
    # adaptive pop width is 4, so replay single-lane cycles of 4)
    for i in range(8):
        q.add(make_pod(f"p{i}", cpu="100m", labels={"app": "web"}))
    while len(q):
        s.run_once(timeout=0.05)
    placed = {r.pod.name: r.node for r in s.results}

    s2 = _sched(cache=_cluster(), batch_size=16)
    replay = [
        make_pod(f"p{i}", cpu="100m", labels={"app": "web"})
        for i in range(8)
    ]
    s2.schedule_cycle(replay[:4])
    s2.schedule_cycle(replay[4:])
    placed2 = {r.pod.name: r.node for r in s2.results}
    assert placed == placed2


def test_express_width_does_not_grow_sticky_dims():
    cache = _cluster()
    enc = cache.encoder
    q = PriorityQueue()
    s = _sched(cache=cache, queue=q, express_lane=True,
               express_priority_threshold=1000, express_batch_size=4,
               batch_size=64)
    # a bulk cycle grows the sticky pad width...
    for i in range(20):
        q.add(make_pod(f"b{i}", cpu="10m"))
    s.run_once(timeout=0.1)
    bulk_b = enc.dims.B
    assert bulk_b >= 20
    # ...but an express cycle encodes at ITS width without growing dims.B
    q.add(make_pod("e0", cpu="10m", priority=2000))
    s.run_once(timeout=0.1)
    assert enc.dims.B == bulk_b
    assert [r.node for r in s.results if r.pod.name == "e0"] != [None]
    with enc.batch_width(4):
        assert enc.batch_pad(1) == 4
        assert enc.batch_pad(9) == 16  # overflow still pads correctly
    assert enc.batch_pad(1) == bulk_b  # override restored


# ------------------------------------------------------------- compile cache


def test_compile_cache_knob(tmp_path, monkeypatch):
    from kubernetes_tpu.utils import compilecache as cc

    # precedence: JAX_COMPILATION_CACHE_DIR > explicit arg > the fixed
    # default inside the checkout; "off" disables; no subdirectory
    monkeypatch.delenv(cc.JAX_CACHE_ENV, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert cc.resolve_cache_dir(None) == cc.DEFAULT_CACHE_DIR
    assert cc.resolve_cache_dir(str(tmp_path / "arg")) == str(
        tmp_path / "arg"
    )
    assert cc.resolve_cache_dir("off") is None
    monkeypatch.setenv(cc.JAX_CACHE_ENV, str(tmp_path / "env"))
    assert cc.resolve_cache_dir(None) == str(tmp_path / "env")
    assert cc.resolve_cache_dir(str(tmp_path / "arg")) == str(
        tmp_path / "env"
    )
    assert cc.resolve_cache_dir("off") == str(tmp_path / "env")
    monkeypatch.delenv(cc.JAX_CACHE_ENV, raising=False)

    import jax

    prev = jax.config.jax_compilation_cache_dir
    try:
        d = cc.enable_compile_cache(str(tmp_path / "cache"))
        assert d == str(tmp_path / "cache")
        assert os.path.isdir(d)
        assert jax.config.jax_compilation_cache_dir == d
        assert cc.enable_compile_cache("off") is None
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_jax_env_dir_used_exactly(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, the cache is that exact
    directory: whatever the explicit knob says, and with nothing added."""
    import jax

    from kubernetes_tpu.utils import compilecache as cc

    want = str(tmp_path / "placed")
    monkeypatch.setenv(cc.JAX_CACHE_ENV, want)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert cc.enable_compile_cache(str(tmp_path / "knob")) == want
        assert jax.config.jax_compilation_cache_dir == want
        assert os.listdir(tmp_path) == ["placed"]
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
