"""Spans and counters of the affinity path: the `encode_affinity` part of
encode, the `fence_redo` part of host_stall, the engine's
batches-by-path and rounds counters, and the fence span's attributes."""

import glob
import os
import time
import warnings

from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.flightrecorder import FlightRecorder
from kubernetes_tpu.runtime.queue import PodBackoff, PriorityQueue
from kubernetes_tpu.runtime.scheduler import (
    PARTITION_PHASES,
    Scheduler,
    SchedulerConfig,
)
from kubernetes_tpu.utils import metrics as m

from fixtures import make_node, make_pod
from test_metrics_format import parse_exposition

HOSTNAME = "kubernetes.io/hostname"


def _anti(app):
    return {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [
            {"labelSelector": {"matchLabels": {"app": app}},
             "topologyKey": HOSTNAME}]}}


def _sched(recorder=None):
    """Four 1-cpu nodes; 100m pods score by how full their node is."""
    cache = SchedulerCache()
    queue = PriorityQueue(backoff=PodBackoff(initial=0.01, max_duration=0.05))
    sched = Scheduler(
        cache=cache, queue=queue, binder=lambda p, n: True,
        config=SchedulerConfig(disable_preemption=True, batch_size=8),
        flight_recorder=recorder)
    for i in range(4):
        cache.add_node(make_node(f"n{i}", cpu="1", mem="8Gi"))
    return sched, queue


def _cycle(sched, queue, pods):
    for p in pods:
        queue.add(p)
    t0 = time.monotonic()
    sched.run_once(timeout=0.0)
    return time.monotonic() - t0


def _plain(tag, n):
    return [make_pod(f"{tag}-{j}", cpu="100m") for j in range(n)]


def _anti_pods(tag, n):
    return [make_pod(f"{tag}-{j}", cpu="100m", labels={"app": tag},
                     affinity=_anti(tag)) for j in range(n)]


def test_encode_affinity_counts_affinity_batches_only():
    fr = FlightRecorder()
    sched, queue = _sched(fr)
    _cycle(sched, queue, _plain("plain", 3))
    assert sched.phase_seconds["encode_affinity"] == 0.0
    assert "encode_affinity" not in [c.name for c in fr.spans()[-1].children]
    _cycle(sched, queue, _anti_pods("web", 3))
    ph = sched.phase_seconds
    assert 0.0 < ph["encode_affinity"] < ph["encode"]
    root = fr.spans()[-1]
    kids = [c for c in root.children if c.name == "encode_affinity"]
    encode = [c for c in root.children if c.name == "encode"][0]
    assert kids and all(encode.start <= c.start and c.end <= encode.end
                        for c in kids)


def test_fence_redo_is_the_redo_batches_share_of_host_stall():
    fr = FlightRecorder()
    sched, queue = _sched(fr)
    batches0 = {p: m.ENGINE_BATCHES.value(path=p)
                for p in ("speculative", "redo")}
    rounds0 = m.ENGINE_ROUNDS.value
    # three pods fill three nodes, then two of one set both prefer the
    # empty node: round 1 collides, the exact scan answers
    _cycle(sched, queue, _plain("fill", 3))
    assert sched.phase_seconds["fence_redo"] == 0.0
    fence = [c for c in fr.spans()[-1].children if c.name == "fence"][0]
    assert fence.attrs["exact_redo"] is False
    assert fence.attrs["engine_rounds"] >= 1
    _cycle(sched, queue, _anti_pods("set", 2))
    fence = [c for c in fr.spans()[-1].children if c.name == "fence"][0]
    assert fence.attrs["exact_redo"] is True
    ph = sched.phase_seconds
    assert 0.0 < ph["fence_redo"] <= ph["host_stall"]
    assert m.ENGINE_BATCHES.value(path="redo") == batches0["redo"] + 1
    assert (m.ENGINE_BATCHES.value(path="speculative")
            == batches0["speculative"] + 1)
    assert m.ENGINE_ROUNDS.value >= rounds0 + 2


def test_partition_phases_account_for_the_wall_with_affinity_batches():
    sched, queue = _sched()
    _cycle(sched, queue, _anti_pods("warm", 2))
    ph0 = dict(sched.phase_seconds)
    wall = 0.0
    for i in range(5):
        wall += _cycle(sched, queue,
                       _anti_pods(f"a{i}", 3) + _plain(f"p{i}", 2))
    part = sum(sched.phase_seconds[k] - ph0[k] for k in PARTITION_PHASES)
    assert part <= wall + 1e-6
    assert wall - part <= 0.02 * wall + 0.001 * 5
    assert sched.phase_seconds["encode_affinity"] > ph0["encode_affinity"]


def test_engine_families_in_the_exposition():
    sched, queue = _sched()
    _cycle(sched, queue, _plain("fill", 3))
    _cycle(sched, queue, _anti_pods("set", 2))
    families = parse_exposition(m.REGISTRY.expose())
    fam = families["scheduler_engine_batches_total"]
    assert fam["type"] == "counter"
    paths = {lbl["path"]: v for _, lbl, v in fam["samples"]}
    assert paths["speculative"] >= 1 and paths["redo"] >= 1
    rounds = families["scheduler_engine_rounds_total"]
    assert rounds["type"] == "counter" and rounds["samples"][0][2] >= 2
    phases = {lbl["phase"] for _, lbl, v in
              families["scheduler_cycle_phase_seconds_total"]["samples"]}
    assert {"encode_affinity", "fence_redo"} <= phases


def test_encode_affinity_range_inside_the_encode_range(tmp_path):
    import jax
    from jax.profiler import ProfileData

    sched, queue = _sched()
    _cycle(sched, queue, _anti_pods("warm", 3))       # compile first
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _cycle(sched, queue, _anti_pods("prof", 3))
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    ranges = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                ranges += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events]
    enc = [(s, e) for n, s, e in ranges if n == "ktpu.encode"]
    aff = [(s, e) for n, s, e in ranges if n == "ktpu.encode_affinity"]
    assert len(enc) == 1 and aff
    assert all(enc[0][0] <= s and e <= enc[0][1] for s, e in aff)
