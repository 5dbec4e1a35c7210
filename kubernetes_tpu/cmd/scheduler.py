"""kube-scheduler binary analog.

Mirrors cmd/kube-scheduler/app/server.go Run (:159-268): build the scheduler
from component config (provider or Policy), serve healthz+metrics, wire
informers (LocalCluster watch), and schedule — directly or behind leader
election.  `--simulate-nodes/--simulate-pods` stands in for a populated
apiserver: hollow nodes register and pending pods arrive, so the binary is
drivable end-to-end on one machine (the scheduler_perf density shape).

    python -m kubernetes_tpu.cmd.scheduler --platform cpu \
        --simulate-nodes 100 --simulate-pods 300 --one-shot
"""

from __future__ import annotations

import argparse
import sys
import time

from kubernetes_tpu.cmd.base import (
    add_common_flags,
    apply_platform,
    load_component_config,
    parse_hostport,
    wait_for_term,
)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="kubernetes-tpu-scheduler",
        description="TPU-native scheduler (kube-scheduler analog)",
    )
    add_common_flags(p)
    p.add_argument("--algorithm-provider", default=None,
                   help="override the config's algorithm provider")
    p.add_argument("--policy-config-file", default=None,
                   help="legacy Policy JSON file (wins over provider)")
    p.add_argument("--healthz-bind-address", default=None,
                   help="host:port for /healthz and /metrics "
                   "(default from config, 0 disables)")
    p.add_argument("--server", default=None,
                   help="remote apiserver URL: reflect its state into a "
                   "local mirror and POST bindings back (the real "
                   "multi-process scheduler deployment)")
    p.add_argument("--token", default="",
                   help="bearer token for --server (RBAC planes)")
    p.add_argument("--kubeconfig", default="",
                   help="kubeadm admin.conf JSON; supplies --server/--token")
    p.add_argument("--leader-elect", action="store_true",
                   help="run behind a LocalCluster lease")
    p.add_argument("--leader-elect-identity", default="scheduler-0")
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--trace-threshold-seconds", type=float, default=None,
                   help="log any scheduling cycle whose root span exceeds "
                   "this many seconds (config traceThresholdSeconds; "
                   "default 0.1, <=0 disables the slow-cycle log; the "
                   "flight recorder at /debug/traces stays always-on)")
    p.add_argument("--express-lane", action="store_true", default=None,
                   help="enable the latency-tiered express lane (config "
                   "expressLane): pods opted in via the "
                   "kubernetes-tpu.io/latency-tier=express annotation or "
                   "at/above --express-priority-threshold schedule through "
                   "a small pre-compiled batch interleaved with the bulk "
                   "AIMD lane")
    p.add_argument("--express-batch-size", type=int, default=None,
                   help="express-lane encode width / per-cycle pop cap "
                   "(config expressBatchSize; default 64)")
    p.add_argument("--express-priority-threshold", type=int, default=None,
                   help="pods with spec.priority >= this classify express "
                   "without the annotation (config "
                   "expressPriorityThreshold; default: annotation only)")
    p.add_argument("--compile-cache-dir", default=None,
                   help="persistent XLA compile cache directory (config "
                   "compileCacheDir; $JAX_COMPILATION_CACHE_DIR wins when "
                   "set; default <checkout>/.jax_cache; 'off' disables) "
                   "— restarts pay zero recompiles")
    p.add_argument("--prewarm", action="store_true", default=None,
                   help="pre-pay engine compiles for every AIMD pow2 "
                   "width (+ the express width) at startup (config "
                   "prewarmWidths) instead of stalling the first cycle "
                   "at each new width mid-traffic")
    p.add_argument("--attribution", action="store_true", default=None,
                   help="per-plugin decision attribution (config "
                   "attribution): unschedulable events and the "
                   "kubernetes-tpu.io/unschedulable-reason annotation "
                   "name the dominant failing predicate with per-reason "
                   "node counts; forces the sequential engine "
                   "(bit-identical placements)")
    p.add_argument("--decision-ledger", action="store_true", default=None,
                   help="record every scheduling cycle to the decision "
                   "ledger (config decisionLedger): /debug/decisions + "
                   "replayable via bench.py --replay when --ledger-dir "
                   "is set")
    p.add_argument("--ledger-dir", default=None,
                   help="directory for the append-only decisions.ledger "
                   "file (config ledgerDir; implies --decision-ledger; "
                   "unset = in-memory /debug/decisions ring only)")
    p.add_argument("--ledger-max-cycles", type=int, default=None,
                   help="stop recording to the ledger file after this "
                   "many cycles (config ledgerMaxCycles; default 4096)")
    p.add_argument("--telemetry", dest="telemetry", action="store_true",
                   default=None,
                   help="cluster + device telemetry (config telemetry; "
                   "DEFAULT ON): device-resident fleet analytics on "
                   "/metrics + /debug/cluster, HBM/compile-cache/launch "
                   "facts, SLO burn-rate alerting")
    p.add_argument("--no-telemetry", dest="telemetry",
                   action="store_false",
                   help="disable the telemetry hub entirely")
    p.add_argument("--telemetry-interval-cycles", type=int, default=None,
                   help="dispatch the cluster-analytics side-launch "
                   "every N committed cycles (config "
                   "telemetryIntervalCycles; default 1)")
    p.add_argument("--slo-objectives", default=None,
                   help="JSON list of SLO objectives for the burn-rate "
                   "evaluator (config sloObjectives), e.g. "
                   '\'[{"name":"cycle_deadline","objective":0.99,'
                   '"fastWindowSeconds":60,"slowWindowSeconds":300,'
                   '"burnThreshold":1.0}]\'; default: cycle_deadline + '
                   "goodput + degraded")
    p.add_argument("--heartbeat-seconds", type=float, default=None,
                   help="one-line liveness heartbeat to the log every "
                   "N seconds (config heartbeatSeconds; 0 disables — "
                   "the default)")
    p.add_argument("--shard-devices", type=int, default=None,
                   help="shard the cluster snapshot's node axis across "
                   "this many devices (config shardDevices; pow2; 0 = "
                   "single-chip, the default): every scheduling launch, "
                   "the incremental dirty-row upload, and the telemetry "
                   "analytics then run sharded with placements "
                   "bit-identical to single-chip")
    p.add_argument("--mesh-shape", default=None,
                   help="mesh topology for --shard-devices (config "
                   "meshShape): 'N' = 1D node mesh, 'OxI' (e.g. '2x4') "
                   "= two-level dcn x ici mesh (hosts x chips) — "
                   "cross-shard reductions then lower hierarchically "
                   "(intra-host ICI, per-host DCN).  Implies sharding")
    p.add_argument("--shard-breaker-failure-threshold", type=int,
                   default=None,
                   help="consecutive classified faults attributed to ONE "
                   "mesh shard that lose that shard (config "
                   "shardBreakerFailureThreshold; default 2; a "
                   "persistent shard fault loses it immediately)")
    p.add_argument("--no-mesh-shrink", action="store_true",
                   help="disable the elastic degradation ladder (config "
                   "meshShrinkEnabled=false): any persistent device "
                   "fault demotes the whole mesh to the CPU adapter, "
                   "the pre-ladder behavior")
    p.add_argument("--no-invariant-checks", action="store_true",
                   help="disable the online invariant checker (config "
                   "invariantChecks=false): conservation/double-bind/"
                   "capacity violations are no longer detected live")
    p.add_argument("--profile-dir", default=None,
                   help="directory for on-demand jax.profiler captures "
                   "(config profileDir; default $KTPU_PROFILE_DIR or "
                   "/tmp/ktpu_profile) — GET /debug/profile?seconds=N "
                   "records a bounded device+host trace there; a "
                   "graceful no-op where the backend lacks profiler "
                   "support")
    p.add_argument("--replicas", type=int, default=None,
                   help="run N queue-sharded scheduler replicas "
                   "(threads) over one cache/queue, committing through "
                   "the sequenced optimistic conflict reconciler "
                   "(config replicas; default 1 = the classic single "
                   "loop).  Not combinable with --shard-devices (one "
                   "scale-out axis per process)")
    p.add_argument("--namespace-quotas", default=None,
                   help="JSON {namespace: {resource: quantity}} "
                   "placement quotas enforced by the reconciler at "
                   "commit (config namespaceQuotas)")
    p.add_argument("--capacity-planner", action="store_true",
                   default=None,
                   help="enable the device-resident capacity planner "
                   "(config capacityPlanner): class-compressed what-if "
                   "binpack of the pending backlog over the node-shape "
                   "catalog, served at /debug/capacity")
    p.add_argument("--capacity-interval-cycles", type=int, default=None,
                   help="committed cycles between capacity solves "
                   "(config capacityIntervalCycles; default 256)")
    p.add_argument("--node-shape-catalog", default=None,
                   help="candidate node shapes for the capacity "
                   "planner: inline JSON list or a path to a JSON file "
                   "([{name, cpu, memory, ephemeral-storage?, pods?, "
                   "...}]; config nodeShapeCatalog).  Implies "
                   "--capacity-planner")
    p.add_argument("--autoscaler", action="store_true", default=None,
                   help="enact the capacity plan against the live store "
                   "(config autoscaler; implies --capacity-planner): "
                   "scale-up registers nodes from the winning catalog "
                   "shape, scale-down cordons + drains through the PDB "
                   "path and deletes; hysteresis + cooldown bound "
                   "flapping, stuck drains and mid-batch failures roll "
                   "back.  Local mode only — against --server the "
                   "mirror is read-only for nodes")
    p.add_argument("--autoscaler-interval-s", type=float, default=None,
                   help="seconds between actuation rounds (config "
                   "autoscalerIntervalSeconds; default 1.0)")
    p.add_argument("--autoscaler-dry-run", action="store_true",
                   default=None,
                   help="decide + record but never actuate (config "
                   "autoscalerDryRun)")
    p.add_argument("--autoscaler-cooldown-s", type=float, default=None,
                   help="direction-change window: at most "
                   "autoscalerMaxDirectionChanges changes inside it "
                   "(config autoscalerCooldownSeconds; default 30)")
    p.add_argument("--autoscaler-max-nodes-per-round", type=int,
                   default=None,
                   help="batch cap per actuation round (config "
                   "autoscalerMaxNodesPerRound; default 4)")
    p.add_argument("--autoscaler-drain-deadline-s", type=float,
                   default=None,
                   help="scale-down drain budget before rollback "
                   "(config autoscalerDrainDeadlineSeconds; default 30)")
    p.add_argument("--autoscaler-min-nodes", type=int, default=None,
                   help="fleet floor (config autoscalerMinNodes)")
    p.add_argument("--autoscaler-max-nodes", type=int, default=None,
                   help="fleet ceiling (config autoscalerMaxNodes)")
    p.add_argument("--autoscaler-ledger-path", default=None,
                   help="JSONL actuation ledger for offline replay "
                   "(config autoscalerLedgerPath; bench.py --replay "
                   "re-verifies every recorded decision)")
    p.add_argument("--timeline", dest="timeline", action="store_true",
                   default=None,
                   help="metrics timeline store (config timeline; "
                   "DEFAULT ON): every registered metric family sampled "
                   "per interval into a bounded ring + typed event "
                   "annotations + online anomaly detection, served at "
                   "/debug/timeline")
    p.add_argument("--no-timeline", dest="timeline",
                   action="store_false",
                   help="disable the timeline store entirely")
    p.add_argument("--timeline-interval-seconds", type=float,
                   default=None,
                   help="seconds between timeline samples (config "
                   "timelineIntervalSeconds; default 1.0)")
    p.add_argument("--timeline-retention", type=int, default=None,
                   help="points retained per series (config "
                   "timelineRetention; default 512)")
    p.add_argument("--timeline-rules", default=None,
                   help="JSON list of anomaly rules (config "
                   "timelineRules), e.g. "
                   '\'[{"rule":"threshold","series":'
                   '"scheduler_pending_pods","op":">","value":500}]\'; '
                   "default: degraded-cycle/invariant thresholds + "
                   "pending-depth zscore")
    p.add_argument("--simulate-nodes", type=int, default=0,
                   help="register N hollow nodes")
    p.add_argument("--simulate-pods", type=int, default=0,
                   help="submit M pending pods (500m cpu / 512Mi)")
    p.add_argument("--one-shot", action="store_true",
                   help="drain the queue once, print stats, exit "
                   "(simulation/CI mode; default runs until SIGTERM)")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    apply_platform(args.platform, args.verbosity)

    import json

    from kubernetes_tpu.cmd.base import build_wired_scheduler
    from kubernetes_tpu.runtime.cluster import LocalCluster
    from kubernetes_tpu.runtime.health import HealthServer
    from kubernetes_tpu.runtime.kubemark import HollowFleet

    cc = load_component_config(args.config)
    if args.policy_config_file:
        with open(args.policy_config_file) as f:
            cc.policy = json.load(f)
    if args.algorithm_provider:
        cc.algorithm_provider = args.algorithm_provider
    if args.batch_size:
        cc.batch_size = args.batch_size
    if args.trace_threshold_seconds is not None:
        cc.trace_threshold_s = args.trace_threshold_seconds
    if args.express_lane is not None:
        cc.express_lane = args.express_lane
    if args.express_batch_size is not None:
        cc.express_batch_size = args.express_batch_size
    if args.express_priority_threshold is not None:
        cc.express_priority_threshold = args.express_priority_threshold
        cc.express_lane = True  # a threshold implies the lane
    if args.compile_cache_dir is not None:
        cc.compile_cache_dir = args.compile_cache_dir
    if args.prewarm is not None:
        cc.prewarm_widths = args.prewarm
    if args.attribution is not None:
        cc.attribution = args.attribution
    if args.decision_ledger is not None:
        cc.decision_ledger = args.decision_ledger
    if args.ledger_dir is not None:
        cc.ledger_dir = args.ledger_dir
        cc.decision_ledger = True  # a ledger dir implies recording
    if args.ledger_max_cycles is not None:
        cc.ledger_max_cycles = args.ledger_max_cycles
    if args.telemetry is not None:
        cc.telemetry = args.telemetry
    if args.telemetry_interval_cycles is not None:
        cc.telemetry_interval_cycles = args.telemetry_interval_cycles
    if args.slo_objectives is not None:
        cc.slo_objectives = json.loads(args.slo_objectives)
    if args.heartbeat_seconds is not None:
        cc.heartbeat_s = args.heartbeat_seconds
    if args.shard_devices is not None:
        cc.shard_devices = args.shard_devices
    if args.mesh_shape is not None:
        cc.mesh_shape = args.mesh_shape
    if args.shard_breaker_failure_threshold is not None:
        cc.shard_breaker_failure_threshold = (
            args.shard_breaker_failure_threshold
        )
    if args.no_mesh_shrink:
        cc.mesh_shrink = False
    if args.no_invariant_checks:
        cc.invariant_checks = False
    if args.profile_dir is not None:
        cc.profile_dir = args.profile_dir
    if args.replicas is not None:
        cc.replicas = args.replicas
    if args.namespace_quotas is not None:
        cc.namespace_quotas = json.loads(args.namespace_quotas)
    if args.capacity_planner is not None:
        cc.capacity_planner = args.capacity_planner
    if args.capacity_interval_cycles is not None:
        cc.capacity_interval_cycles = args.capacity_interval_cycles
    if args.node_shape_catalog is not None:
        raw = args.node_shape_catalog
        if raw.lstrip().startswith("["):
            cc.node_shape_catalog = json.loads(raw)
        else:
            with open(raw) as f:
                cc.node_shape_catalog = json.load(f)
        cc.capacity_planner = True  # a catalog implies the planner
    if args.autoscaler is not None:
        cc.autoscaler = args.autoscaler
    if args.autoscaler_interval_s is not None:
        cc.autoscaler_interval_s = args.autoscaler_interval_s
    if args.autoscaler_dry_run is not None:
        cc.autoscaler_dry_run = args.autoscaler_dry_run
    if args.autoscaler_cooldown_s is not None:
        cc.autoscaler_cooldown_s = args.autoscaler_cooldown_s
    if args.autoscaler_max_nodes_per_round is not None:
        cc.autoscaler_max_nodes_per_round = args.autoscaler_max_nodes_per_round
    if args.autoscaler_drain_deadline_s is not None:
        cc.autoscaler_drain_deadline_s = args.autoscaler_drain_deadline_s
    if args.autoscaler_min_nodes is not None:
        cc.autoscaler_min_nodes = args.autoscaler_min_nodes
    if args.autoscaler_max_nodes is not None:
        cc.autoscaler_max_nodes = args.autoscaler_max_nodes
    if args.autoscaler_ledger_path is not None:
        cc.autoscaler_ledger_path = args.autoscaler_ledger_path
    if cc.autoscaler:
        cc.capacity_planner = True  # actuation needs the plan
    if args.timeline is not None:
        cc.timeline = args.timeline
    if args.timeline_interval_seconds is not None:
        cc.timeline_interval_s = args.timeline_interval_seconds
    if args.timeline_retention is not None:
        cc.timeline_retention = args.timeline_retention
    if args.timeline_rules is not None:
        cc.timeline_rules = json.loads(args.timeline_rules)

    # persistent compile cache BEFORE any jit compile (engine build,
    # prewarm, first cycle) so every executable of this process is served
    # from / saved to disk (utils/compilecache.py: JAX_COMPILATION_CACHE_DIR,
    # else compileCacheDir, else the checkout's .jax_cache)
    from kubernetes_tpu.utils.compilecache import enable_compile_cache

    enable_compile_cache(cc.compile_cache_dir)

    if args.kubeconfig:
        with open(args.kubeconfig) as f:
            conf = json.load(f)
        args.server = args.server or conf.get("server")
        args.token = args.token or conf.get("token", "")

    reflector = None
    if args.server:
        # remote mode: informer mirror in, every WRITE back to the remote
        # apiserver — bind (Binding subresource), preemption victim delete,
        # gang unbind (cmd/kube-scheduler against a real apiserver; SURVEY
        # section 3.2 informer start + WaitForCacheSync)
        from kubernetes_tpu.client import (
            Reflector,
            RemoteBinder,
            remote_unbinder,
            remote_victim_deleter,
        )

        if args.leader_elect:
            # leases would live in each process's private mirror: every
            # instance would elect itself; refuse instead of double-running
            print("error: --leader-elect requires a shared store and is "
                  "not supported with --server", file=sys.stderr)
            return 2
        if args.simulate_nodes or args.simulate_pods:
            print("error: --simulate-* inject into the local mirror only "
                  "(the next resync would destroy them); create the "
                  "workload on the remote server instead", file=sys.stderr)
            return 2
        if cc.autoscaler:
            print("error: --autoscaler registers/deletes nodes on the "
                  "local store; against --server the informer mirror is "
                  "resync-owned (the next relist would destroy them)",
                  file=sys.stderr)
            return 2
        reflector = Reflector(args.server, token=args.token).start()
        if not reflector.wait_for_sync(timeout=30.0):
            print(f"error: cache sync against {args.server} timed out",
                  file=sys.stderr)
            return 1
        cluster = reflector.mirror
        # the real client pipeline: remote watch -> mirror -> shared
        # informers -> scheduler cache/queue (server.go:224-229 informer
        # start + WaitForCacheSync)
        sched = build_wired_scheduler(cluster, cc, use_informers=True)
        sched.binder = RemoteBinder(args.server, token=args.token)
        sched.victim_deleter = remote_victim_deleter(
            args.server, token=args.token)
        sched.unbinder = remote_unbinder(args.server, token=args.token)
    else:
        cluster = LocalCluster()
        sched = build_wired_scheduler(cluster, cc)

    # queue-sharded replicas (ISSUE 14): wrap the wired scheduler as
    # replica 0 of an N-way set — siblings share its cache/queue/
    # binder/engines and commit through the sequenced reconciler
    replica_set = None
    if cc.replicas > 1:
        from kubernetes_tpu.runtime.replicas import SchedulerReplicaSet

        if args.leader_elect:
            print("error: --leader-elect drives one scheduler loop; "
                  "combine it with --replicas is not supported",
                  file=sys.stderr)
            return 2
        replica_set = SchedulerReplicaSet.from_primary(sched, cc.replicas)
        print(f"running {cc.replicas} queue-sharded scheduler replicas "
              "(optimistic conflict reconciler)", file=sys.stderr)

    health = None
    addr = args.healthz_bind_address or cc.healthz_bind_address
    if addr and addr != "0":
        host, port = parse_hostport(addr, 10251)
        health = HealthServer(host=host, port=port).start()
        print(f"healthz/metrics on {health.address[0]}:{health.address[1]}",
              file=sys.stderr)

    fleet = None
    if args.simulate_nodes:
        fleet = HollowFleet(cluster, _sim_nodes(args.simulate_nodes))
    if args.simulate_pods:
        for p in _sim_pods(args.simulate_pods):
            cluster.add_pod(p)

    if cc.prewarm_widths:
        # after node registration (compiles are keyed on the snapshot
        # shape), before serving: with a warm compile cache this is
        # seconds of disk reads instead of minutes of XLA
        t_warm = time.monotonic()
        # replica mode warms through the set: the primary's engine
        # ladder PLUS the reconciler's admission kernels (a
        # first-conflict compile inside the commit critical section
        # would stall every sibling replica behind the cache lock)
        warmed = (
            replica_set.prewarm() if replica_set is not None
            else sched.prewarm()
        )
        print(
            f"prewarmed {len(warmed)} batch widths in "
            f"{time.monotonic() - t_warm:.1f}s: "
            + ", ".join(
                f"{w}:{s:.2f}s"
                for w, s in sorted(warmed.items(), key=lambda kv: str(kv[0]))
            ),
            file=sys.stderr,
        )

    autoscaler = None
    if cc.autoscaler:
        from kubernetes_tpu.runtime import autoscaler as autoscaler_mod

        autoscaler = autoscaler_mod.AutoscalerController(
            cluster,
            planner=getattr(sched, "capacity", None),
            invariants=sched.invariants,
            config=autoscaler_mod.AutoscalerConfig(
                interval_s=cc.autoscaler_interval_s,
                dry_run=cc.autoscaler_dry_run,
                cooldown_s=cc.autoscaler_cooldown_s,
                max_nodes_per_round=cc.autoscaler_max_nodes_per_round,
                drain_deadline_s=cc.autoscaler_drain_deadline_s,
                min_nodes=cc.autoscaler_min_nodes,
                max_nodes=cc.autoscaler_max_nodes,
            ),
            ledger=sched.ledger,
            ledger_path=cc.autoscaler_ledger_path,
        )
        autoscaler_mod.set_default(autoscaler)
        autoscaler.start()
        print("autoscaler actuation loop on "
              f"{cc.autoscaler_interval_s}s interval"
              + (" (dry-run)" if cc.autoscaler_dry_run else ""),
              file=sys.stderr)

    try:
        if args.one_shot:
            t0 = time.monotonic()
            snapshot_keys = None
            if args.server:
                # remote mode: the workload is the PRE-DRAIN snapshot of
                # pending pods — pods arriving mid-drain must corrupt
                # neither the loop bound nor the exit status
                snapshot_keys = {
                    (p.namespace, p.name)
                    for p in cluster.list("pods")
                    if not p.spec.node_name
                    and p.status.phase not in ("Succeeded", "Failed")
                }
                target = len(snapshot_keys)
            else:
                target = args.simulate_pods
            # drain until every pod has a verdict (scheduled OR failed once)
            # — unschedulable pods park+retry forever, so len(queue) alone
            # would spin; no-progress across a cycle also terminates
            seen: set = set()
            loops = (
                replica_set.schedulers if replica_set is not None
                else [sched]
            )
            while len(seen) < target:
                before = sum(len(s.results) for s in loops)
                for s in loops:
                    s.run_once(timeout=0.5 / len(loops))
                for s in loops:
                    for r in s.results:
                        key = (r.pod.namespace, r.pod.name)
                        if snapshot_keys is None or key in snapshot_keys:
                            seen.add(key)
                if sum(len(s.results) for s in loops) == before:
                    break
            for s in loops:
                s.flush_pipeline()
            dt = time.monotonic() - t0
            done = len({
                (r.pod.namespace, r.pod.name)
                for s in loops
                for r in s.results
                if r.node is not None and (
                    snapshot_keys is None
                    or (r.pod.namespace, r.pod.name) in snapshot_keys
                )
            })
            print(json.dumps({
                "pods_scheduled": done,
                "target": target,
                "seconds": round(dt, 3),
                "pods_per_sec": round(done / dt, 1) if dt > 0 else 0.0,
                "running_on_hollow_nodes": fleet.total_running if fleet else 0,
            }))
            return 0 if done == target else 1
        if args.leader_elect:
            from kubernetes_tpu.runtime.leaderelection import (
                run_scheduler_elected,
            )

            elector = run_scheduler_elected(
                cluster, sched, identity=args.leader_elect_identity,
                config=cc.leader_election,
            )
            wait_for_term()
            elector.stop()
        elif replica_set is not None:
            replica_set.start()
            wait_for_term()
            replica_set.stop()
        else:
            import threading

            t = threading.Thread(target=sched.run, daemon=True)
            t.start()
            wait_for_term()
            sched.stop()
        return 0
    finally:
        if autoscaler is not None:
            autoscaler.stop()
        if health is not None:
            health.stop()


def _sim_nodes(n: int):
    from kubernetes_tpu.api.types import Node

    return [
        Node.from_dict({
            "metadata": {
                "name": f"hollow-{i}",
                "labels": {
                    "kubernetes.io/hostname": f"hollow-{i}",
                    "failure-domain.beta.kubernetes.io/zone": f"z{i % 4}",
                },
            },
            "status": {
                "allocatable": {"cpu": "8", "memory": "16Gi", "pods": 110},
                "conditions": [{"type": "Ready", "status": "True"}],
            },
        })
        for i in range(n)
    ]


def _sim_pods(m: int):
    from kubernetes_tpu.api.types import Pod

    return [
        Pod.from_dict({
            "metadata": {"name": f"sim-{j}", "namespace": "default",
                         "labels": {"app": "sim"}},
            "spec": {"containers": [{
                "name": "c0",
                "resources": {"requests": {"cpu": "500m",
                                           "memory": "512Mi"}},
            }]},
        })
        for j in range(m)
    ]


if __name__ == "__main__":
    sys.exit(main())
