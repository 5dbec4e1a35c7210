"""Chip smoke: the live scheduling path end to end on one TPU.

What a user runs — nodes and pods through SchedulerCache + PriorityQueue +
Scheduler + a binder, drained with run_once — at the north-star size
(BASELINE.json: 5,000 nodes, 10,000 pending pods, batch 2048), on the
bench world (bench.py: 8 zones, a `tier` label, a NoSchedule taint on
every 50th node, 20 spread selectors).  One process holds the chip for
the whole run; nothing here starts a child.

  phase A  plain stream, speculative engine (the default), 10,000 pods
  phase B  the same world with the sequential engine, and the
           pod-anti-affinity workload under the speculative engine

Every phase warms up one full-width batch, then drains its stream and
flushes the pipeline.  A phase passes only if every pod is bound (or, for
anti-affinity, exactly the reference's count is not), no node exceeds
its allocatable or pod cap, the invariant checker is clean, the device
breaker stayed closed with no degraded cycle, and the first pods of the
stream land exactly where the pure-Python reference (cpuref) puts them.
`cpu_fallback` is off: a device fault fails the run instead of being
served quietly from the CPU engine.

`--chips 4` runs only the sharded path: the plain stream through a
Scheduler sharded over 4 chips and through one chip, in this process,
asserting identical placements and a really split snapshot.

Pods/s printed here are one smoke run, not a benchmark.  Any failed check
or raised error exits non-zero; the last stdout line, only on success, is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
# a drain that has not finished by then has failed (phase A drains in
# about a second on the chip)
DRAIN_BUDGET_S = 600.0


def require_tpu(chips: int) -> dict:
    """The device JAX reports, or exit: this script never runs on a CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r} "
            "(tests and development run on the CPU through pytest; the "
            "chip is reached through the chip tool)"
        )
    if len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} devices, "
            f"JAX found {len(devs)}"
        )
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check(ok, what) -> None:
    """A failed check fails the run (asserts would vanish under -O)."""
    if not ok:
        raise AssertionError(what)


def say(tag: str, payload: dict) -> None:
    print(f"chip_smoke {tag}: {json.dumps(payload, default=str)}",
          flush=True)


def _world(args, workload: str):
    import bench

    ns = argparse.Namespace(nodes=args.nodes, workload=workload)
    nodes = bench._bench_nodes(ns)
    return ns, nodes, bench._build_encoder(ns, nodes)


def _stream(args, ns, n_pods: int):
    """Warm-up batch (pods 0..batch-1) then the drained stream: one
    sequence, so every phase of a workload starts from the same batch."""
    import bench

    return [bench._pending_pod(ns, i) for i in range(args.batch + n_pods)]


def reference_prefix(args, workload: str, engine: str, n: int) -> dict:
    """pod name -> node name (None = unschedulable) for the first `n` pods
    of the stream on a fresh world, from the pure-Python reference
    (cpuref: no JAX).  The first batch dispatches with rotation base 0,
    so pod i's tie-break rotation is i, and:

      sequential   pod i sees pods 0..i-1 of its batch placed — one
                   reference call over the whole prefix;
      speculative  every pod of a round is scored against the state at
                   the batch's start (in-batch spread counts refresh only
                   between rounds) — pod i alone, with rotation i.  On
                   these workloads no pod is bounced out of round 1, so
                   that is the whole batch's semantics.

    A pod's placement depends only on the pods before it, so the
    device's first `n` placements must match exactly."""
    from kubernetes_tpu.cpuref.adapter import CpuEngineAdapter
    from kubernetes_tpu.runtime.cache import SchedulerCache
    from kubernetes_tpu.runtime.scheduler import SchedulerConfig

    ns, _, enc = _world(args, workload)
    pods = _stream(args, ns, 0)[:n]
    ref = CpuEngineAdapter(SchedulerCache(enc), SchedulerConfig())
    t0 = time.monotonic()
    if engine == "sequential":
        rows = ref.schedule_batch(pods, 0)
    else:
        rows = [ref.schedule_batch([p], i)[0] for i, p in enumerate(pods)]
    name_of = {row: name for name, row in enc.node_rows.items()}
    say("reference", {"workload": workload, "engine": engine, "pods": n,
                      "seconds": time.monotonic() - t0})
    return {p.name: (name_of[int(r)] if r >= 0 else None)
            for p, r in zip(pods, rows)}


def expected_unschedulable(nodes, pods) -> int:
    """Plain count of what the stream cannot place: no pod tolerates the
    NoSchedule taint, and a required hostname anti-affinity group fits at
    most one pod per untainted node.  Resources and the pod cap do not
    bind at the smoke's sizes (check_capacity asserts that separately)."""
    open_nodes = sum(
        1 for n in nodes
        if not any(t.effect == "NoSchedule" for t in n.spec.taints)
    )
    groups: dict = {}
    for p in pods:
        anti = p.spec.affinity and p.spec.affinity.pod_anti_affinity
        key = p.labels.get("app") if anti else None
        groups[key] = groups.get(key, 0) + 1
    return sum(
        max(0, c - open_nodes) for k, c in groups.items() if k is not None
    )


def check_capacity(nodes, pods, bound: dict, anti: bool) -> None:
    """Independent of the scheduler's own checker: per-node sums of the
    bound pods' requests against allocatable and the pod cap, no pod on
    a tainted node, and (anti-affinity) one pod per group per node."""
    from kubernetes_tpu.cpuref.reference import (
        node_allocatable,
        pod_requests,
    )

    by_name = {n.name: n for n in nodes}
    used: dict = {}
    seen = set()
    for p in pods:
        node = bound.get(p.name)
        if node is None:
            continue
        u = used.setdefault(node, {"pods": 0.0})
        u["pods"] += 1
        for r, v in pod_requests(p).items():
            u[r] = u.get(r, 0.0) + v
        if anti:
            key = (node, p.labels["app"])
            check(key not in seen, f"anti-affinity broken on {key}")
            seen.add(key)
    for name, u in used.items():
        node = by_name[name]
        check(not any(t.effect == "NoSchedule" for t in node.spec.taints),
              f"pod bound to tainted node {name}")
        alloc = node_allocatable(node)
        for r, v in u.items():
            check(v <= alloc.get(r, 0.0),
                  f"node {name} over {r}: {v} > {alloc.get(r, 0.0)}")


def live_phase(args, engine: str, workload: str, n_pods: int,
               ref: dict, shard_devices: int = 0) -> dict:
    """Warm up one batch, drain `n_pods` through run_once, check, and
    return the phase record (placements under "bound_nodes", the
    Scheduler under "scheduler")."""
    import jax

    from kubernetes_tpu.runtime.cache import SchedulerCache
    from kubernetes_tpu.runtime.queue import PriorityQueue
    from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig
    from kubernetes_tpu.utils import metrics as m

    t_setup = time.monotonic()
    ns, nodes, enc = _world(args, workload)
    stream = _stream(args, ns, n_pods)
    bound: dict = {}

    def binder(pod, node):
        bound[pod.name] = node
        return True

    queue = PriorityQueue()
    sched = Scheduler(
        cache=SchedulerCache(enc), queue=queue, binder=binder,
        config=SchedulerConfig(
            batch_size=args.batch, batch_window_s=0.0, engine=engine,
            disable_preemption=True, batched_commit=True,
            pipeline_commit=True, cpu_fallback=False,
            shard_devices=shard_devices,
        ),
    )
    check(sched._engine_kind == engine, sched._engine_kind)
    degraded0 = m.DEGRADED_CYCLES.value
    setup_s = time.monotonic() - t_setup

    def drain() -> int:
        deadline = time.monotonic() + DRAIN_BUDGET_S
        placed = 0
        while time.monotonic() < deadline:
            got = sched.run_once(timeout=0.0)
            placed += got
            if got == 0 and not sched.pipeline_pending:
                if not queue.has_schedulable():
                    break
                time.sleep(0.002)
        return placed + sched.flush_pipeline()

    t0 = time.monotonic()
    for p in stream[:args.batch]:
        queue.add(p)
    drain()
    warmup_s = time.monotonic() - t0
    for k in sched.phase_seconds:
        sched.phase_seconds[k] = 0.0
    t0 = time.monotonic()
    for p in stream[args.batch:]:
        queue.add(p)
    drain()
    drain_s = time.monotonic() - t0

    want_unsched = expected_unschedulable(nodes, stream)
    timed = stream[args.batch:]
    placed = sum(1 for p in timed if p.name in bound)
    mismatches = [
        (name, bound.get(name), node) for name, node in ref.items()
        if bound.get(name) != node
    ]
    rec = {
        "engine": engine, "workload": workload, "nodes": args.nodes,
        "batch": args.batch, "shard_devices": shard_devices,
        "pods": n_pods, "bound": placed,
        "warmup_bound": sum(1 for p in stream[:args.batch]
                            if p.name in bound),
        "unschedulable": len(stream) - len(bound),
        "expected_unschedulable": want_unsched,
        "pods_per_s": placed / drain_s if drain_s > 0 else 0.0,
        "drain_seconds": drain_s, "warmup_seconds": warmup_s,
        "setup_seconds": setup_s,
        "phase_seconds": dict(sched.phase_seconds),
        "reference_prefix": {"pods": len(ref),
                             "mismatches": len(mismatches)},
        "breaker": sched.device_health.state,
        "ladder_rung": sched.ladder_rung,
        "degraded_cycles": m.DEGRADED_CYCLES.value - degraded0,
        "invariant_violations": sched.invariants.violations_total(),
        "platform": jax.devices()[0].platform,
        "note": "one smoke run, not a benchmark",
    }
    say(f"phase {engine}/{workload}", rec)
    check(sched.invariants.assert_drained(), "popped pods unresolved")
    check(rec["invariant_violations"] == 0, sched.invariants.summary())
    check(rec["unschedulable"] == want_unsched,
          f"{rec['unschedulable']} unschedulable, reference says "
          f"{want_unsched}")
    check_capacity(nodes, stream, bound, workload == "pod-anti-affinity")
    check(rec["breaker"] == "closed", rec["breaker"])
    check(rec["degraded_cycles"] == 0, rec["degraded_cycles"])
    check(not mismatches, f"cpuref prefix differs: {mismatches[:8]}")
    rec["bound_nodes"] = bound
    rec["scheduler"] = sched
    return rec


def sharded_phase(args) -> None:
    """The plain stream on one chip and sharded over 4, same process:
    identical placements, and the resident snapshot split over 4 chips
    (no field left whole on device 0)."""
    single = live_phase(args, "speculative", "plain", args.pods, {})
    sharded = live_phase(args, "speculative", "plain", args.pods, {},
                         shard_devices=4)
    check(sharded["bound_nodes"] == single["bound_nodes"],
          "sharded placements differ from one chip")
    snap = sharded["scheduler"]._dev_snapshot
    fields = snap._dev
    spread = {n: len(a.sharding.device_set) for n, a in fields.items()}
    req = fields["requested"]
    shards = {str(s.index) for s in req.addressable_shards}
    say("sharded", {
        "identical": True, "pods": len(single["bound_nodes"]),
        "requested_shards": len(shards),
        "fields_on_fewer_than_4_devices":
            sorted(n for n, k in spread.items() if k < 4),
    })
    check(len(shards) == 4, shards)
    check(all(k == 4 for k in spread.values()), spread)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the sharded-vs-one-chip identity path")
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--pods", type=int, default=10000,
                    help="phase A stream (after the warm-up batch)")
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--seq-pods", type=int, default=4096,
                    help="phase B sequential-engine stream")
    ap.add_argument("--anti-pods", type=int, default=4096,
                    help="phase B pod-anti-affinity stream")
    ap.add_argument("--ref-pods", type=int, default=128,
                    help="stream prefix checked against cpuref (plain; "
                    "the reference takes ~0.3s/pod at 5k nodes)")
    ap.add_argument("--anti-ref-pods", type=int, default=16,
                    help="stream prefix checked against cpuref "
                    "(anti-affinity; the reference is ~1.5s/pod there)")
    args = ap.parse_args(argv)

    device = require_tpu(args.chips)
    sys.path.insert(0, REPO)
    from kubernetes_tpu.utils.compilecache import (
        compile_stats,
        enable_compile_cache,
    )

    say("device", {**device, "compile_cache_dir": enable_compile_cache(),
                   "sizes": {k: getattr(args, k) for k in (
                       "nodes", "pods", "batch", "seq_pods", "anti_pods",
                       "ref_pods", "anti_ref_pods")}})
    if args.chips == 4:
        sharded_phase(args)
    else:
        for engine, workload, n_pods, n_ref in (
            ("speculative", "plain", args.pods, args.ref_pods),
            ("sequential", "plain", args.seq_pods, args.ref_pods),
            ("speculative", "pod-anti-affinity", args.anti_pods,
             args.anti_ref_pods),
        ):
            ref = reference_prefix(args, workload, engine, n_ref)
            live_phase(args, engine, workload, n_pods, ref)
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    say("compile", compile_stats())
    say("memory", {"peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
