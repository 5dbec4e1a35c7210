"""End-to-end tracing + flight recorder (ISSUE 5).

The span tree (utils/trace.py), the always-on cycle ring + anomaly
postmortems (runtime/flightrecorder.py), cross-component traceparent
propagation (scheduler -> apiserver bind / extender server / Scheduled
event), the /debug/traces Chrome-trace endpoints, and the <2% overhead
bound on the live path.
"""

import json
import time
import urllib.request

import pytest

from kubernetes_tpu.api.types import Node
from kubernetes_tpu.extender.client import ExtenderConfig, HTTPExtender
from kubernetes_tpu.extender.server import ExtenderServer
from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.chaos import Disruptions
from kubernetes_tpu.runtime.cluster import (
    LocalCluster,
    make_cluster_binder,
    wire_scheduler,
)
from kubernetes_tpu.runtime.flightrecorder import RECORDER, FlightRecorder
from kubernetes_tpu.runtime.health import start_health_server
from kubernetes_tpu.runtime.queue import PodBackoff, PriorityQueue
from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.utils.trace import (
    Span,
    chrome_trace,
    current_traceparent,
    format_traceparent,
    parse_traceparent,
    trace_id_of,
    use_traceparent,
)

from fixtures import make_node, make_pod


# ------------------------------------------------------------- span basics


def test_span_tree_children_and_attrs():
    root = Span("cycle", pods=3)
    a = root.child("encode")
    a.finish()
    b = root.child("dispatch", engine="speculative")
    b.finish()
    root.add_child("fetch", a.start, b.start, overlapped=True)
    root.annotate(placed=2)
    root.finish()
    assert root.finished and root.duration >= 0
    assert [c.name for c in root.children] == ["encode", "dispatch", "fetch"]
    # every child shares the root's trace id; parent ids chain
    for c in root.children:
        assert c.trace_id == root.trace_id
        assert c.parent_id == root.span_id
    assert root.attrs["pods"] == 3 and root.attrs["placed"] == 2
    assert root.find("dispatch").attrs["engine"] == "speculative"


def test_span_finish_idempotent_and_closes_children():
    root = Span("cycle")
    child = root.child("encode")  # left open on purpose
    root.finish()
    first_end = root.end
    time.sleep(0.002)
    root.finish()  # idempotent: the FIRST end time sticks
    assert root.end == first_end
    assert child.finished and child.end == first_end


def test_traceparent_roundtrip_and_rejects_malformed():
    sp = Span("cycle")
    parsed = parse_traceparent(sp.traceparent())
    assert parsed == (sp.trace_id, sp.span_id)
    assert trace_id_of(sp.traceparent()) == sp.trace_id
    for bad in ("", "junk", "00-short-ids-01", "00-" + "g" * 32 + "-" + "0" * 16 + "-01"):
        assert parse_traceparent(bad) is None
    # well-formed synthetic header
    assert parse_traceparent(format_traceparent("ab" * 16, "cd" * 8)) is not None


def test_use_traceparent_thread_local_restores():
    assert current_traceparent() == ""
    sp = Span("outer")
    with use_traceparent(sp):
        assert trace_id_of(current_traceparent()) == sp.trace_id
        with use_traceparent("00-" + "1" * 32 + "-" + "2" * 16 + "-01"):
            assert current_traceparent().startswith("00-1111")
        assert trace_id_of(current_traceparent()) == sp.trace_id
    assert current_traceparent() == ""


def test_chrome_trace_structure():
    root = Span("cycle", pods=1)
    root.child("encode").finish()
    root.finish()
    out = chrome_trace([root])
    assert set(out) == {"traceEvents", "displayTimeUnit"}
    evs = out["traceEvents"]
    assert len(evs) == 2
    for e in evs:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid", "args"} <= set(e)
        assert e["ph"] == "X" and e["dur"] >= 1
        assert e["args"]["trace_id"] == root.trace_id
    # the whole thing must be JSON-serializable (the /debug/traces body)
    json.dumps(out)


# ------------------------------------------------------ flight recorder


def test_flight_recorder_ring_bounded_and_postmortem_throttled():
    fr = FlightRecorder(capacity=4, postmortem_min_interval_s=60.0)
    for i in range(10):
        fr.record(Span(f"cycle-{i}").finish())
    assert len(fr.spans()) == 4
    assert fr.recorded_total == 10
    assert [s.name for s in fr.spans()] == [f"cycle-{i}" for i in range(6, 10)]
    snap = fr.postmortem("breaker_open", "test", state={"queue_depth": 7},
                         metrics_text=lambda: "m 1")
    assert snap is not None
    assert snap["state"]["queue_depth"] == 7 and snap["metrics"] == "m 1"
    assert len(snap["cycles"]) == 4
    # second firing inside the window is throttled; a DIFFERENT trigger
    # still fires
    assert fr.postmortem("breaker_open") is None
    assert fr.postmortem("shed_burst") is not None
    assert [p["trigger"] for p in fr.postmortems()] == [
        "breaker_open", "shed_burst"]


def test_flight_recorder_in_flight_span_not_duplicated():
    fr = FlightRecorder(postmortem_min_interval_s=0.0)
    retired = Span("done").finish()
    fr.record(retired)
    live = Span("failing")
    snap = fr.postmortem("unclassified_error", in_flight=[live, retired])
    assert [c["name"] for c in snap["cycles"]] == ["done"]
    assert [c["name"] for c in snap["in_flight"]] == ["failing"]


def test_flight_recorder_chrome_trace_includes_postmortem_instants():
    fr = FlightRecorder(postmortem_min_interval_s=0.0)
    fr.record(Span("cycle").finish())
    fr.postmortem("cycle_deadline", "0.5s > 0.25s")
    out = fr.chrome_trace()
    phases = {e["ph"] for e in out["traceEvents"]}
    assert phases == {"X", "i"}
    inst = [e for e in out["traceEvents"] if e["ph"] == "i"]
    assert inst[0]["name"] == "postmortem:cycle_deadline"


# ------------------------------------------------- scheduler integration


def _mini_scheduler(recorder=None, **cfg_kw):
    cache = SchedulerCache()
    queue = PriorityQueue(backoff=PodBackoff(initial=0.01, max_duration=0.05))
    cfg = SchedulerConfig(disable_preemption=True, **cfg_kw)
    sched = Scheduler(
        cache=cache, queue=queue, binder=lambda p, n: True, config=cfg,
        flight_recorder=recorder,
    )
    cache.add_node(make_node("n1", cpu="4", mem="8Gi"))
    return sched, queue


def test_cycle_spans_recorded_with_phase_children():
    fr = FlightRecorder()
    sched, queue = _mini_scheduler(recorder=fr)
    queue.add(make_pod("traced", cpu="100m"))
    queue.add(make_pod("too-big", cpu="64"))
    sched.run_once(timeout=0.3)
    spans = fr.spans()
    assert len(spans) == 1
    root = spans[0]
    assert root.name == "schedule_cycle" and root.finished
    names = [c.name for c in root.children]
    for phase in ("encode", "dispatch", "fence", "device_wait", "d2h",
                  "commit", "bind-tail", "observe"):
        assert phase in names, f"missing phase span {phase}: {names}"
    assert root.attrs["batch"] == 2
    assert root.attrs["breaker"] == "closed"
    assert root.attrs["degraded"] is False
    assert root.attrs["placed"] == 1 and root.attrs["unschedulable"] == 1
    # children stay inside the root window
    for c in root.children:
        assert c.start >= root.start - 1e-6
        assert c.end <= root.end + 1e-6


def test_scheduled_event_carries_cycle_trace_id():
    fr = FlightRecorder()
    cluster = LocalCluster()
    cache = SchedulerCache()
    queue = PriorityQueue(backoff=PodBackoff(initial=0.01, max_duration=0.05))
    sched = Scheduler(
        cache=cache, queue=queue, binder=make_cluster_binder(cluster),
        config=SchedulerConfig(disable_preemption=True), flight_recorder=fr,
    )
    wire_scheduler(cluster, sched)
    cluster.add_node(make_node("n1", cpu="2", mem="4Gi"))
    cluster.add_pod(make_pod("joined", cpu="100m"))
    sched.run_once(timeout=0.3)
    root = fr.spans()[-1]
    evs = cluster.events.events(reason="Scheduled", name="joined")
    assert evs and evs[0].trace_id == root.trace_id
    # the in-process binder stamps the same id onto the bound pod
    bound = cluster.get("pods", "default", "joined")
    assert bound.metadata.annotations["kubernetes-tpu.io/trace-id"] == \
        root.trace_id


def test_trace_joins_scheduler_apiserver_extender_end_to_end():
    """THE acceptance path: one pod's scheduling decision produces ONE
    trace id visible in (1) the cycle span tree, (2) the extender
    server's received headers, (3) the apiserver-bound pod's annotation
    stamped from the Binding request's traceparent, and (4) the
    Scheduled event."""
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client.reflector import RemoteBinder

    cluster = LocalCluster()
    srv = APIServer(cluster=cluster).start()
    ext_srv = ExtenderServer()
    ext_srv.start()
    try:
        node = make_node("n1", cpu="2", mem="4Gi")
        ext_srv.cache.add_node(node)
        host, port = ext_srv.address
        ext = HTTPExtender(ExtenderConfig(
            url_prefix=f"http://{host}:{port}", filter_verb="filter",
            node_cache_capable=True,
        ))
        fr = FlightRecorder()
        cache = SchedulerCache()
        queue = PriorityQueue(
            backoff=PodBackoff(initial=0.01, max_duration=0.05))
        sched = Scheduler(
            cache=cache, queue=queue,
            binder=RemoteBinder(srv.url),
            config=SchedulerConfig(disable_preemption=True),
            extenders=[ext], flight_recorder=fr,
        )
        cache.add_node(node)
        pod = make_pod("one-decision", cpu="100m")
        cluster.add_pod(pod)          # the apiserver's store holds the pod
        queue.add(pod)
        sched.run_once(timeout=0.3)

        root = fr.spans()[-1]
        tid = root.trace_id
        assert root.find("extenders") is not None
        # (2) the extender round-trip carried the cycle's traceparent
        assert tid in list(ext_srv.seen_trace_ids)
        # (3) the REST bind stamped the id onto the stored pod
        bound = cluster.get("pods", "default", "one-decision")
        assert bound.spec.node_name == "n1"
        assert bound.metadata.annotations["kubernetes-tpu.io/trace-id"] == tid
        # (4) the Scheduled event joins the same trace
        evs = sched.recorder.events(reason="Scheduled", name="one-decision")
        assert evs and evs[0].trace_id == tid
    finally:
        ext_srv.stop()
        srv.stop()


def test_debug_traces_endpoints_serve_chrome_json():
    # the default-recorder path: a default-constructed Scheduler records
    # into RECORDER, and both servers serve it
    sched, queue = _mini_scheduler()
    queue.add(make_pod("served", cpu="100m"))
    sched.run_once(timeout=0.3)
    assert any(
        s.name == "schedule_cycle" for s in RECORDER.spans()
    ), "default scheduler must record into the process-wide ring"

    hs = start_health_server()
    try:
        h, p = hs.address
        with urllib.request.urlopen(
            f"http://{h}:{p}/debug/traces", timeout=5
        ) as r:
            assert r.headers.get("Content-Type") == "application/json"
            body = json.loads(r.read())
    finally:
        hs.stop()
    assert body["traceEvents"], "health server served an empty trace"
    assert any(e["name"] == "schedule_cycle" for e in body["traceEvents"])

    from kubernetes_tpu.apiserver import APIServer

    srv = APIServer(cluster=LocalCluster()).start()
    try:
        with urllib.request.urlopen(
            f"{srv.url}/debug/traces", timeout=5
        ) as r:
            body2 = json.loads(r.read())
    finally:
        srv.stop()
    assert any(e["name"] == "schedule_cycle" for e in body2["traceEvents"])


def test_slow_cycle_logs_span_breakdown():
    import logging

    # a handler directly on the package logger: klog.setup() sets
    # propagate=False in some test orderings, so caplog's root handler
    # cannot be relied on here
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
        fr = FlightRecorder()
        sched, queue = _mini_scheduler(recorder=fr, trace_threshold_s=0.0001)
        queue.add(make_pod("slowpoke", cpu="100m"))
        sched.run_once(timeout=0.3)
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)
    text = "\n".join(records)
    assert "schedule_cycle" in text and "trace=" in text
    assert "encode" in text and "bind-tail" in text


def test_slow_cycle_log_fires_after_full_tail():
    """ISSUE 8 satellite regression: the slow-cycle log must be stamped
    AFTER the commit tail completes — by the time it fires, the cycle's
    span has retired into the flight recorder and the telemetry hook has
    run, so the logged total is exactly the duration the span tree at
    /debug/traces reports (it used to fire mid-tail, reporting a number
    the rest of the tail then outgrew on pipelined cycles)."""
    import logging

    fr = FlightRecorder()
    sched, queue = _mini_scheduler(
        recorder=fr, trace_threshold_s=0.0001, pipeline_commit=True,
    )
    seen = []

    class _Capture(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if msg.startswith('"schedule_cycle"'):
                # snapshot what had ALREADY happened when the log fired
                seen.append((
                    msg,
                    {s.trace_id for s in fr.spans()},
                    sched.telemetry.cycles_total
                    if sched.telemetry is not None else -1,
                ))

    logger = logging.getLogger("kubernetes_tpu")
    handler = _Capture(level=logging.INFO)
    old_level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        for i in range(3):
            queue.add(make_pod(f"cycle-{i}", cpu="100m"))
            sched.run_once(timeout=0.3)
        sched.flush_pipeline()
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)
    assert seen, "threshold 0.1ms must log every cycle"
    spans = {s.trace_id: s for s in fr.spans()}
    for i, (msg, ring_ids, tel_cycles) in enumerate(seen):
        tid = msg.split("trace=")[1].split()[0]
        # the span had already retired into the ring when the log fired
        assert tid in ring_ids, (
            "slow-cycle log fired before the cycle retired into the "
            "flight recorder"
        )
        # ... and the telemetry hook had already run for this cycle
        assert tel_cycles >= i + 1, (
            "slow-cycle log fired before the tail's telemetry hook"
        )
        # the logged total equals the recorded span's duration (the
        # number /debug/traces reports), not a mid-tail reading
        total_ms = float(msg.split("(total ")[1].split("ms")[0])
        assert total_ms == pytest.approx(
            spans[tid].duration * 1000, abs=0.05
        )


# ----------------------------------------------------- anomaly postmortems


@pytest.mark.chaos
def test_breaker_trip_produces_postmortem_with_failing_cycle():
    """Acceptance: a device-lost storm trips the breaker and the flight
    recorder holds a postmortem whose spans include the FAILING cycle."""
    fr = FlightRecorder(postmortem_min_interval_s=0.0)
    sched, queue = _mini_scheduler(
        recorder=fr,
        device_retry_max=1, breaker_failure_threshold=2,
        device_backoff_base_s=0.001, device_backoff_max_s=0.002,
        breaker_open_s=10.0, cpu_fallback=True,
    )
    # a healthy cycle first, so the ring has lead-up context
    queue.add(make_pod("healthy", cpu="100m"))
    sched.run_once(timeout=0.3)
    dis = Disruptions(LocalCluster())
    dis.device_lost()  # persistent fault at the fence until cleared
    try:
        queue.add(make_pod("doomed", cpu="100m"))
        sched.run_once(timeout=0.3)
    finally:
        dis.clear_device_faults()
    assert sched.device_health.state == "open"
    pms = fr.postmortems(trigger="breaker_open")
    assert pms, "breaker trip must dump a postmortem"
    pm = pms[0]
    # lead-up cycles from the ring + the failing cycle's span
    all_spans = pm["cycles"] + pm["in_flight"]
    assert any(s["name"] == "schedule_cycle" for s in all_spans)
    failing = [
        s for s in all_spans
        if s["attrs"].get("fault_class") or not s["end"]
    ]
    assert failing, "postmortem must contain the failing cycle's spans"
    assert pm["state"]["breaker"] == "open"
    assert "scheduler_device_breaker_state" in pm["metrics"]
    # the degraded CPU cycle that served the batch also left a postmortem
    assert fr.postmortems(trigger="degraded_cycle")
    # and the batch was still served (CPU fallback) — pods never lost
    assert any(
        r.pod.name == "doomed" and r.node is not None for r in sched.results
    )


def test_cycle_deadline_postmortem():
    fr = FlightRecorder(postmortem_min_interval_s=0.0)
    sched, queue = _mini_scheduler(
        recorder=fr, adaptive_batch=True, batch_size_min=1,
        cycle_deadline_s=1e-9,  # every non-empty cycle overruns
    )
    queue.add(make_pod("overrun", cpu="100m"))
    sched.run_once(timeout=0.3)
    assert fr.postmortems(trigger="cycle_deadline")


def test_shed_burst_postmortem():
    fr = FlightRecorder(postmortem_min_interval_s=0.0)
    cache = SchedulerCache()
    queue = PriorityQueue(
        capacity=2, backoff=PodBackoff(initial=0.01, max_duration=0.05))
    sched = Scheduler(
        cache=cache, queue=queue, binder=lambda p, n: True,
        config=SchedulerConfig(disable_preemption=True), flight_recorder=fr,
    )
    assert sched is not None
    for i in range(5):  # over capacity: arrivals shed
        queue.add(make_pod(f"flood-{i}", cpu="100m"))
    assert queue.shed_total > 0
    assert fr.postmortems(trigger="shed_burst")


# ------------------------------------------------------- overhead bound


@pytest.mark.perf_smoke
def test_tracing_overhead_micro_bound():
    """<2% overhead acceptance, pinned two ways: the live-path floor in
    test_perf_smoke runs WITH tracing always-on, and this micro-bound
    keeps one cycle's whole span workload (root + 10 children + annotate
    + finish + ring append) under 500us — against the >=25ms a 256-pod
    CPU cycle costs, that is <2% even at the smoke tier's widths."""
    fr = FlightRecorder()
    n = 1000
    t0 = time.perf_counter()
    for i in range(n):
        root = Span("schedule_cycle", pods=256, cycle=i)
        for name in ("encode", "extenders", "dispatch"):
            root.child(name).finish()
        t = time.monotonic()
        root.add_child("fence", t - 0.001, t)
        root.add_child("device_wait", t - 0.002, t - 0.001, lane="w")
        root.add_child("d2h", t - 0.001, t, lane="w")
        root.add_child("commit", t, t, winners=256)
        root.child("bind-tail").finish()
        root.child("preempt", failed=0).finish()
        root.child("observe").finish()
        root.annotate(batch=256, breaker="closed", degraded=False,
                      placed=256, unschedulable=0)
        root.finish()
        fr.record(root)
    per_cycle = (time.perf_counter() - t0) / n
    assert per_cycle < 500e-6, (
        f"span workload costs {per_cycle * 1e6:.0f}us/cycle"
    )


# --------------------------------------------- phases partition the cycle


@pytest.mark.parametrize("pipeline", [False, True],
                         ids=["sync", "pipelined"])
def test_partition_phases_account_for_run_once_wall(pipeline):
    """Every second of run_once lands in one partition phase: over
    several cycles the partition keys add up to the summed run_once
    wall time, within 2% plus 1 ms a cycle (the loop's own glue)."""
    from kubernetes_tpu.runtime.scheduler import PARTITION_PHASES

    sched, queue = _mini_scheduler(pipeline_commit=pipeline, batch_size=8)
    # one warm cycle: the first launch compiles, which is real time in
    # dispatch too, but keeps the measured cycles comparable
    queue.add(make_pod("warm", cpu="10m"))
    sched.run_once(timeout=0.0)
    sched.flush_pipeline()
    ph0 = dict(sched.phase_seconds)
    wall = 0.0
    cycles = 0
    for i in range(6):
        for j in range(5):
            queue.add(make_pod(f"p{i}-{j}", cpu="10m"))
        t0 = time.monotonic()
        sched.run_once(timeout=0.0)
        wall += time.monotonic() - t0
        cycles += 1
    t0 = time.monotonic()
    sched.run_once(timeout=0.0)      # idle poll: drains the pipeline
    wall += time.monotonic() - t0
    cycles += 1
    assert not sched.pipeline_pending
    part = sum(sched.phase_seconds[k] - ph0[k] for k in PARTITION_PHASES)
    assert part <= wall + 1e-6
    assert wall - part <= 0.02 * wall + 0.001 * cycles, (
        wall, part, {k: sched.phase_seconds[k] - ph0[k]
                     for k in PARTITION_PHASES})
    assert sched.phase_seconds["observe"] > ph0["observe"]


def test_fence_split_adds_up_to_host_stall():
    """fence_queue + fence_device + fence_copy == host_stall, each >= 0,
    on the live path and on synthetic worker stamps."""
    from types import SimpleNamespace

    from kubernetes_tpu.codec.transfer import fence_split

    sched, queue = _mini_scheduler(batch_size=8)
    for i in range(4):
        for j in range(3):
            queue.add(make_pod(f"f{i}-{j}", cpu="10m"))
        sched.run_once(timeout=0.0)
    ph = sched.phase_seconds
    parts = [ph["fence_queue"], ph["fence_device"], ph["fence_copy"]]
    assert all(p >= 0.0 for p in parts)
    assert ph["host_stall"] > 0.0
    assert sum(parts) == pytest.approx(ph["host_stall"], abs=1e-9)
    # every ordering of the fence against the worker's stamps
    f = SimpleNamespace(t_start=2.0, t_ready=5.0)
    for t0, t1, want in [
        (1.0, 1.5, (0.5, 0.0, 0.0)),     # worker not started yet
        (1.0, 3.0, (1.0, 1.0, 0.0)),     # queued, then computing
        (3.0, 6.0, (0.0, 2.0, 1.0)),     # computing, then copying
        (5.5, 6.0, (0.0, 0.0, 0.5)),     # only the copy left
        (1.0, 7.0, (1.0, 3.0, 2.0)),     # all three
    ]:
        q, d, c = fence_split(f, t0, t1)
        assert (q, d, c) == pytest.approx(want)
        assert min(q, d, c) >= 0.0 and q + d + c == pytest.approx(t1 - t0)
    # a host-computed result has no worker stamps: all copy
    assert fence_split(object(), 1.0, 2.0) == (0.0, 0.0, 1.0)


def test_program_load_counts_the_cycle_that_compiles():
    """program_load grows on the cycle that compiles a new batch width
    and stays 0 on a warm repeat; the cycle span counts the loads."""
    import jax

    fr = FlightRecorder()
    sched, queue = _mini_scheduler(recorder=fr, batch_size=8)
    jax.clear_caches()            # whatever this process compiled before

    def cycle(tag, n):
        before = sched.phase_seconds["program_load"]
        for j in range(n):
            queue.add(make_pod(f"{tag}-{j}", cpu="10m"))
        sched.run_once(timeout=0.0)
        return (sched.phase_seconds["program_load"] - before,
                fr.spans()[-1].attrs["program_loads"])

    secs, loads = cycle("a", 3)           # width 4: compiles
    assert secs > 0.0 and loads > 0
    cycle("b", 3)                         # the dirty-row scatter warms
    assert cycle("c", 3) == (0.0, 0)      # warm repeat
    secs, loads = cycle("d", 5)           # width 8: compiles again
    assert secs > 0.0 and loads > 0
    assert cycle("e", 5) == (0.0, 0)


# ------------------------------------------- the profiler's timeline


@pytest.fixture(scope="module")
def profiled_cycles(tmp_path_factory):
    """Three cycles of a small Scheduler inside a jax.profiler capture
    on the CPU, read back with ProfileData: (events by thread line,
    profile start in ns, the flight recorder's Chrome export)."""
    import glob
    import os
    import warnings

    import jax
    from jax.profiler import ProfileData

    fr = FlightRecorder()
    sched, queue = _mini_scheduler(recorder=fr, batch_size=8)
    queue.add(make_pod("warm", cpu="10m"))
    sched.run_once(timeout=0.0)         # compile outside the capture
    fr.clear()
    out = str(tmp_path_factory.mktemp("prof"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        for i in range(3):
            for j in range(3):
                queue.add(make_pod(f"prof{i}-{j}", cpu="10m"))
            with jax.profiler.TraceAnnotation("test.cycle"):
                sched.run_once(timeout=0.0)
    finally:
        jax.profiler.stop_trace()
    chrome = fr.chrome_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                     recursive=True)[0]
    lines = []
    start_ns = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            with warnings.catch_warnings():   # the binding's stats type
                warnings.simplefilter("ignore", DeprecationWarning)
                start_ns = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            lines.append([(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events])
    return lines, start_ns, chrome


def test_ktpu_phase_annotations_on_the_scheduling_thread(profiled_cycles):
    lines, _, _ = profiled_cycles
    sched_lines = [ln for ln in lines
                   if any(n == "test.cycle" for n, _, _ in ln)]
    assert len(sched_lines) == 1
    main = sched_lines[0]
    # no ktpu. range anywhere but the scheduling thread, and the fetch
    # worker's own range is there, on another thread
    for ln in lines:
        if ln is not main:
            assert not any(n.startswith("ktpu.") for n, _, _ in ln)
    assert any(n.startswith("fetchworker.")
               for ln in lines if ln is not main for n, _, _ in ln)
    cycles = sorted((s, e) for n, s, e in main if n == "test.cycle")
    assert len(cycles) == 3
    for c0, c1 in cycles:
        evs = sorted(((s, -e, n) for n, s, e in main
                      if n.startswith("ktpu.") and c0 <= s and e <= c1))
        # properly nested: each range ends inside the one it starts in
        stack, top = [], []
        for s, neg_e, n in evs:
            while stack and stack[-1] <= s:
                stack.pop()
            assert not stack or -neg_e <= stack[-1], n
            if not stack:
                top.append(n)
            stack.append(-neg_e)
        # heartbeat, pop, encode, dispatch, fence, state commit, bind
        # tail, hooks (and the slow-cycle log's own observe, when on)
        assert top[:8] == ["ktpu.observe", "ktpu.pop", "ktpu.encode",
                           "ktpu.dispatch", "ktpu.host_stall",
                           "ktpu.commit", "ktpu.commit",
                           "ktpu.observe"], top
        assert set(top[8:]) <= {"ktpu.observe"}, top


def test_debug_traces_share_the_profiler_clock(profiled_cycles):
    """A span's start in the /debug/traces export and its ktpu.*
    annotation's start on the capture's clock agree within 1 ms."""
    lines, start_ns, chrome = profiled_cycles
    ann = sorted(s for ln in lines for n, s, _ in ln if n == "ktpu.encode")
    spans = sorted(e["ts"] for e in chrome["traceEvents"]
                   if e["name"] == "encode")
    assert len(ann) == len(spans) == 3
    for a_ns, ts_us in zip(ann, spans):
        assert abs((start_ns + a_ns) / 1e3 - ts_us) < 1000.0
