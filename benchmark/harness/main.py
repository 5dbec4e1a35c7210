"""One run of one cell: set-up, warm-up, the window, the check, the line.

Set-up (everything before the window opens, all counted in setup_s):
world and pods, the Scheduler through the deployment path
(KubeSchedulerConfiguration.from_dict -> SchedulerConfig.from_component_
config), the configuration's existing pods, and warm-up batches at every
width the window can meet.  The window is the traffic kind's.  After it:
the device's peak memory, then the check (not counted anywhere), then
the result line.

What is particular to a configuration comes from its shape
(benchmark/shapes/<shape>.py: world, stream, pods, encoder, capacity,
warm-up widths, reference and guarantees), and what is particular to a
mix from its kind (benchmark/kinds/<kind>.py: window size, window,
end-to-end numbers); both are found by name (byname.py).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from typing import Dict, List

from benchmark.harness import byname
from benchmark.harness import check as chk
from benchmark.harness import drive, tracereduce

# after the shape's warm-up batches, single pods until this many cycles
# in a row load no program, at most WARM_SINGLES of them
WARM_QUIET = 40
WARM_SINGLES = 400
# the traced span of a --trace 1 run
TRACE_SPAN_S = 3.0


def log(tag: str, payload) -> None:
    print(f"bench {tag}: {json.dumps(payload, default=float)}",
          file=sys.stderr, flush=True)


class NoDevice(SystemExit):
    pass


def require_device(chips: int) -> dict:
    """The accelerator JAX reports, or exit non-zero with no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoDevice(f"benchmark: needs an accelerator, JAX found "
                       f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"benchmark: the cell needs {chips} chips, JAX "
                       f"found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileCounter:
    """Backend compiles and persistent-cache hits, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.hits = 0
        self.compile_s = 0.0

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += float(duration)

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)
        # the names of what compiles, for the stderr log: jax logs each
        # compile once jax_log_compiles is on
        import logging

        import jax

        self.names: List[str] = []
        names = self.names

        class _Grab(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    names.append(msg[10:200])

        jax.config.update("jax_log_compiles", True)
        grab = _Grab()
        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
            lg = logging.getLogger(name)
            lg.addHandler(grab)
            lg.propagate = False

    def snap(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.hits,
                "compile_s": self.compile_s, "named": len(self.names)}


def enable_cache(root: str) -> str:
    import jax

    d = os.path.join(root, ".jax_cache")
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: an evicting cache reads an access-time file beside each
    # entry and fails every write where one is missing (entries that came
    # without them), which left every run compiling everything
    jax.config.update("jax_compilation_cache_max_size", -1)
    return d


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics this cell reports: its end-to-end ones with --trace 0,
    its per-layer ones with --trace 1."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def parse(argv):
    ap = argparse.ArgumentParser(description="one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the control (the reference in bfloat16) in the "
                    "program's place for the check; not in the driver's runs")
    return ap.parse_args(argv)


def main(argv, root: str, t_start: float) -> int:
    args = parse(argv)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"benchmark: no workload {args.workload!r}")
    cell = cells[args.workload]
    cfg = byname.load_config(root, cell["config"])
    traffic = byname.load_traffic(root, cell["traffic"])
    shape = byname.load(root, "shapes", cfg.get("shape", "plain"))
    kind = byname.load(root, "kinds", traffic["kind"])
    device = require_device(int(cell["chips"]))
    cache_dir = enable_cache(root)
    compiles = CompileCounter()
    from kubernetes_tpu.codec.transfer import transfer_totals
    from kubernetes_tpu.config.types import KubeSchedulerConfiguration
    from kubernetes_tpu.runtime.cache import SchedulerCache
    from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig
    from kubernetes_tpu.utils import metrics as m

    t_import = drive.clock() - t_start
    seed = args.seed
    world = shape.build_world(cfg)
    kcfg = KubeSchedulerConfiguration.from_dict(cfg["scheduler"])
    batch = int(kcfg.batch_size)

    # ---- the stream: the configuration's existing pods (scheduled first,
    # as upstream does), then the warm-up's test pods, then the window's.
    # The warm-up serves every batch width the window can meet: the
    # shape's warm-up batches, then single pods until WARM_QUIET cycles
    # in a row load no program, so that side launches made every so many
    # cycles are warm too.
    warm_sizes = shape.warm_sizes(world, batch)
    n_warm = sum(warm_sizes) + WARM_SINGLES
    cap = shape.capacity(world)
    n_win, plan = kind.window_pods(traffic, args.seconds, seed,
                                   cap - n_warm)
    n_total = n_warm + n_win
    if n_total > cap:
        raise SystemExit("benchmark: the stream outgrows the cluster")
    ctl_of = shape.stream(world, n_total)
    t0 = drive.clock()
    pods = [shape.pod_object(world, cfg, i, ctl_of[i])
            for i in range(n_total)]
    t_pods = drive.clock() - t0

    t0 = drive.clock()
    enc = shape.build_encoder(world, cfg)
    queue = drive.make_queue()
    seams = drive.Seams()
    sched = Scheduler(
        cache=SchedulerCache(enc), queue=queue, binder=seams.binder,
        config=SchedulerConfig.from_component_config(kcfg),
    )
    t_sched = drive.clock() - t0

    # ---- warm-up: every width the window uses, through the same path
    t0 = drive.clock()
    warm = pods[:n_warm]
    k = 0
    for size in warm_sizes:
        for p in warm[k:k + size]:
            queue.add(p)
        k += size
        while queue.has_schedulable() and drive.clock() - t0 < 600:
            drive.run_cycle(sched, queue, seams, m)
    quiet = 0
    while quiet < WARM_QUIET and k < n_warm:
        loads = compiles.compiles + compiles.hits
        queue.add(warm[k])
        k += 1
        drive.run_cycle(sched, queue, seams, m)
        quiet = quiet + 1 if compiles.compiles + compiles.hits == loads else 0
    t_warm = drive.clock() - t0
    warm_unbound = k - sum(1 for p in warm[:k] if p.name in seams.bound)

    seams.n_pop0 = len(queue.popped)
    n_cyc0 = len(seams.cycles)
    win_pods = pods[n_warm:]
    trace_dir = os.path.join(root, ".bench_trace")
    if args.trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)

    def snap():
        return {"phase": dict(sched.phase_seconds),
                "xfer": transfer_totals(), "bound": len(seams.bound),
                "cycles": len(seams.cycles), "t": drive.clock()}

    tracer = drive.Tracer(bool(args.trace), trace_dir,
                          min(TRACE_SPAN_S, args.seconds))
    if args.trace:
        drive.annotate_phases(sched)
    # the set-up's objects (pods, world, caches) leave the collector's
    # reach, so a full collection over them cannot land in the window
    gc.collect()
    gc.freeze()
    comp0 = compiles.snap()
    snap0 = snap()
    setup_s = drive.clock() - t_start
    log("setup", {"setup_s": setup_s, "import_s": t_import,
                  "pods_s": t_pods, "scheduler_s": t_sched,
                  "warmup_s": t_warm, "warm_pods": k,
                  "warm_unbound": warm_unbound, "window_pods": n_win,
                  "compile": comp0, "cache_dir": cache_dir})

    # ---- the window
    w = kind.window(sched, queue, seams, win_pods, plan, m, traffic,
                    args.seconds, batch, tracer, snap)
    comp1 = compiles.snap()
    gc.unfreeze()
    snap1 = snap()
    window_compiles = comp1["compiles"] - comp0["compiles"]
    window_hits = comp1["cache_hits"] - comp0["cache_hits"]

    # ---- peak memory before anything else touches the device
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    # ---- end-to-end numbers at the seams
    index = {p.name: i for i, p in enumerate(pods)}
    win_names = [p.name for p in win_pods[:w["added"]]]
    popped_win = [n for b in queue.popped[seams.n_pop0:] for n in b]
    wcycles = seams.cycles[n_cyc0:]
    failed_names = set(seams.failed)
    e2e: Dict[str, float] = {"setup_s": setup_s}
    kind_e2e, attempted_names = kind.end_to_end(w, seams, win_names,
                                                popped_win, args.seconds, log)
    e2e.update(kind_e2e)
    unbound = [n for n in attempted_names if n not in seams.bound]
    failed = len(set(unbound) | (failed_names & set(attempted_names)))
    degraded = sum(1 for c in seams.cycles if c["degraded"])
    log("window", {"seconds": w["t_close"] - w["t_open"],
                   "cycles": len(wcycles), "attempted": len(attempted_names),
                   "bound": sum(1 for n in attempted_names
                                if n in seams.bound),
                   "compiles_in_window": window_compiles,
                   "cache_hits_in_window": window_hits,
                   "compiled_in_window":
                       compiles.names[comp0["named"]:comp1["named"]][:20],
                   **{k: v for k, v in e2e.items()}})

    # ---- per-layer numbers over the traced span
    metrics_out: Dict[str, dict] = {}
    breakdown = None
    if args.trace:
        red = tracereduce.reduce(tracereduce.events_from_xplane(trace_dir))
        a, b = tracer.snap0, tracer.snap
        cyc = seams.cycles[a["cycles"]:b["cycles"]]
        run = {
            "pods": b["bound"] - a["bound"],
            "phase_s": {k: b["phase"].get(k, 0.0) - a["phase"].get(k, 0.0)
                        for k in b["phase"]},
            "h2d_bytes": sum(
                v["bytes"] - a["xfer"].get(k, {"bytes": 0})["bytes"]
                for k, v in b["xfer"].items() if k.startswith("h2d/")),
            "cycles": cyc,
            "span_s": b["t"] - a["t"],
            "trace": red,
        }
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
        for mdef in cell_metrics(bench, args.workload, True):
            val = byname.load(root, "metrics", mdef["name"]).read(run)
            if val is not None:
                metrics_out[mdef["name"]] = {"value": float(val),
                                             "unit": mdef["unit"]}
        log("traced", {"span_s": run["span_s"], "pods": run["pods"],
                       "phase_s": run["phase_s"], "trace": red})
    else:
        for mdef in cell_metrics(bench, args.workload, False):
            metrics_out[mdef["name"]] = {"value": float(e2e[mdef["name"]]),
                                         "unit": mdef["unit"]}

    # ---- the check, once the program's state is freed
    batches_names = list(queue.popped)
    del sched
    bound = dict(seams.bound)
    node_id = {name: i for i, name in enumerate(world.names)}
    del enc, pods, win_pods, warm
    gc.collect()
    t0 = drive.clock()
    batches = [[index[n] for n in b] for b in batches_names]
    node_of = {index[n]: node_id[v] for n, v in bound.items()}
    if args.control:
        import ml_dtypes

        node_of = chk.control_placements(shape, world, batches, ctl_of,
                                         ml_dtypes.bfloat16)
    cmp = chk.compare(shape, world, batches, ctl_of, node_of)
    numbers = {
        "placement_mismatches": cmp["mismatches"],
        "failed_pods": failed,
        "degraded_cycles": degraded,
        **shape.guarantees(world, node_of),
    }
    ref_s = drive.clock() - t0
    log("reference", {"seconds": ref_s, "pods": cmp["pods"],
                      "batches": cmp["batches"], "rules": cmp["rules"],
                      "control": "bfloat16" if args.control else None})
    limits = chk.limits(shape)
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in numbers.items()}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    out = {
        "correct": bool(correct),
        "attempted": len(attempted_names),
        "failed": int(failed),
        "metrics": metrics_out,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for k, v in checks.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
