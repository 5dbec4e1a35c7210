"""The plain shape: scheduler_perf's BenchmarkScheduling deployment.

A configuration of this shape is a scheduler_perf deployment as
upstream writes it: a node template repeated `nodes` times, one pod
template, `existing_pods` pods of the setup controller scheduled before
anything is timed, and then the test controller's pods, which the
traffic mix offers.  Each controller is a ReplicationController whose
selector (`name=<ctl>`) is the SelectorSpread selector of its pods.

Two views of the same data: plain arrays (World) that the reference
reads, and the program's API objects (Node, Pod) that the system under
test reads.  The arrays never come from the objects.  Nothing here
depends on the seed: upstream's workload has no random part, and the
traffic mix draws its arrival order from the seed.

Reference: the plain reference of the scheduler's placement semantics,
in numpy.  It imports nothing of the program and reads only the world's
arrays, the pods' controllers and the placements already committed (the
history a batch was scheduled against, like a served model's earlier
tokens).  It states upstream's semantics (kube-scheduler 1.16,
generic_scheduler.go and the default provider) for the shapes the
configurations use:

  filter   PodFitsResources: cpu, memory and the pod cap;
  score    the default provider's weighted sum, each priority
           int-truncated as upstream does: SelectorSpread over the pod's
           controller selector (no zone labels, so node counts alone),
           LeastRequested, BalancedResourceAllocation,
           NodePreferAvoidPods (10 at weight 10000), TaintToleration (10),
           and the priorities that are 0 here (InterPodAffinity,
           NodeAffinity, ImageLocality), in the precision the program
           states (float32);
  select   every node is scored (percentageOfNodesToScore 100, so no
           sample); the best score, ties in node order, the k-th tie
           where k is the pod's index in the stream of pods popped
           (selectHost's lastNodeIndex round robin);
  batch    the batch engine's stated contract: every pod of a batch is
           placed against the state the batch started from; where that
           answer overfills a node or leaves a pod unplaced, the batch
           is placed one pod at a time, each against the pods before it
           (upstream's sequential semantics).

`dtype` is the precision of the score arithmetic: float32 as the program
states; the control runs it in bfloat16.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np

_SUFFIX = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40,
           "k": 10 ** 3, "M": 10 ** 6, "G": 10 ** 9}

# full batches of the test controller's pods in the warm-up
WARM_FULL = 3

# the guarantees' numbers, each with its limit (check.limits)
LIMITS = {"overcommitted_nodes": 0}


def milli_cpu(q: str) -> int:
    """'4' -> 4000, '100m' -> 100."""
    q = str(q)
    return int(q[:-1]) if q.endswith("m") else int(float(q) * 1000)


def mem_bytes(q: str) -> int:
    """'32Gi' -> 34359738368."""
    q = str(q)
    for suf in sorted(_SUFFIX, key=len, reverse=True):
        if q.endswith(suf):
            return int(q[: -len(suf)]) * _SUFFIX[suf]
    return int(q)


@dataclasses.dataclass
class World:
    """The cluster as plain arrays, node i = the i-th node registered."""

    names: List[str]
    cpu: np.ndarray          # i64[N] allocatable milli-cpu
    mem: np.ndarray          # i64[N] allocatable bytes
    pods: np.ndarray         # i64[N] pod cap
    pod_cpu: int
    pod_mem: int
    controllers: List[str]   # [setup controller, test controller]
    existing: int            # pods of controllers[0], scheduled first

    @property
    def n(self) -> int:
        return len(self.names)

    def capacity(self) -> int:
        """Pods the cluster holds at the binding one of cpu, memory and
        the pod cap."""
        per = np.minimum(np.minimum(self.cpu // self.pod_cpu,
                                    self.mem // self.pod_mem), self.pods)
        return int(per.sum())


def build_world(cfg: dict) -> World:
    n = int(cfg["nodes"])
    node, pod = cfg["node"], cfg["pod"]
    return World(
        names=[f"node-{k}" for k in range(n)],
        cpu=np.full(n, milli_cpu(node["cpu"]), np.int64),
        mem=np.full(n, mem_bytes(node["memory"]), np.int64),
        pods=np.full(n, int(node["pods"]), np.int64),
        pod_cpu=milli_cpu(pod["cpu"]),
        pod_mem=mem_bytes(pod["memory"]),
        controllers=[cfg["setup_controller"], cfg["test_controller"]],
        existing=int(cfg["existing_pods"]),
    )


def capacity(world: World) -> int:
    return world.capacity()


def warm_sizes(world: World, batch: int) -> List[int]:
    """The warm-up's batches ahead of its single pods: the existing pods,
    WARM_FULL full batches, then a batch of one pod and of 2**k + 1 pods
    for each power of two below batchSize, so that every power-of-two
    bucket up to batchSize is reached, and one more single pod (a
    batch's rows upload at the next cycle)."""
    return ([world.existing] + [batch] * WARM_FULL + [1]
            + [(1 << k) + 1 for k in range(batch.bit_length() - 1)]
            + [1])


def stream(world: World, n_pods: int) -> np.ndarray:
    """i32[n]: the controller of pod i of the stream (0 = setup, the
    first `existing` pods; 1 = test, every later one)."""
    return (np.arange(n_pods) >= world.existing).astype(np.int32)


def build_encoder(world: World, cfg: dict):
    """The nodes, bulk-ingested, and each controller's selector as a
    SelectorSpread selector."""
    from kubernetes_tpu.api.factory import make_node
    from kubernetes_tpu.codec import SnapshotEncoder

    node = cfg["node"]
    enc = SnapshotEncoder()
    enc.add_nodes([make_node(name, cpu=str(node["cpu"]),
                             mem=str(node["memory"]), pods=int(node["pods"]))
                   for name in world.names])
    for ctl in world.controllers:
        enc.add_spread_selector(cfg["namespace"], {"name": ctl},
                                kind="ReplicationController")
    return enc


def pod_object(world: World, cfg: dict, i: int, ctl: int):
    """Pod i of the stream, from the pod template of its controller."""
    from kubernetes_tpu.api.factory import make_pod

    pod = cfg["pod"]
    name = world.controllers[int(ctl)]
    return make_pod(f"{name}-pod-{i}", namespace=cfg["namespace"],
                    cpu=str(pod["cpu"]), mem=str(pod["memory"]),
                    limits={"cpu": str(pod["cpu"]),
                            "memory": str(pod["memory"])},
                    labels={"name": name})


def guarantees(world: World, node_of: Dict[int, int]) -> dict:
    """The configuration's guarantees over every bound pod, from the
    world's arrays alone."""
    nodes = np.fromiter(node_of.values(), np.int64, len(node_of))
    cnt = np.bincount(nodes, minlength=world.n)
    over = ((cnt * world.pod_cpu > world.cpu)
            | (cnt * world.pod_mem > world.mem)
            | (cnt > world.pods))
    return {"overcommitted_nodes": int(over.sum())}


MAX = 10.0
W_PREFER_AVOID = 10000.0


class State:
    """Committed per-node usage and per-(controller, node) pod counts."""

    def __init__(self, world):
        n = world.n
        self.cpu = np.zeros(n, np.int64)
        self.mem = np.zeros(n, np.int64)
        self.pods = np.zeros(n, np.int64)
        self.count = np.zeros((len(world.controllers), n), np.int64)

    def copy(self) -> "State":
        out = State.__new__(State)
        out.cpu, out.mem = self.cpu.copy(), self.mem.copy()
        out.pods, out.count = self.pods.copy(), self.count.copy()
        return out

    def add(self, world, ctls: np.ndarray, nodes: np.ndarray) -> None:
        ok = nodes >= 0
        c, h = ctls[ok], nodes[ok]
        cnt = np.bincount(h, minlength=world.n)
        self.cpu += cnt * world.pod_cpu
        self.mem += cnt * world.pod_mem
        self.pods += cnt
        np.add.at(self.count, (c, h), 1)


class Reference:
    def __init__(self, world, dtype=np.float32):
        self.w = world
        self.dt = np.dtype(dtype)
        self.state = State(world)
        self.li = 0          # pods popped before the next batch

    def commit(self, ctls: Sequence[int], nodes: Sequence[int]) -> None:
        """Apply one popped batch as it was committed."""
        ctls = np.asarray(ctls, np.int64)
        self.state.add(self.w, ctls, np.asarray(nodes, np.int64))
        self.li += len(ctls)

    def fits(self, st: State, k: int = 1) -> np.ndarray:
        """bool[N]: k more pods of the template fit on the node."""
        w = self.w
        return ((st.cpu + k * w.pod_cpu <= w.cpu)
                & (st.mem + k * w.pod_mem <= w.mem)
                & (st.pods + k <= w.pods))

    def score(self, st: State, c: int) -> np.ndarray:
        """Total score per node in self.dt (priorities/*.go)."""
        dt, w = self.dt.type, self.w
        ten = dt(MAX)

        def least(req, cap):
            per = np.floor(((cap - req) * ten) / cap)
            return np.where(req > cap, dt(0), per)

        rc = (st.cpu + w.pod_cpu).astype(dt)
        rm = (st.mem + w.pod_mem).astype(dt)
        cc, cm = w.cpu.astype(dt), w.mem.astype(dt)
        lr = np.floor((least(rc, cc) + least(rm, cm)) / dt(2))
        fc, fm = rc / cc, rm / cm
        over = (fc >= dt(1)) | (fm >= dt(1))
        bal = np.where(over, dt(0),
                       np.floor((dt(1) - np.abs(fc - fm)) * ten))
        counts = st.count[c].astype(dt)
        mx = counts.max()
        spread = np.floor((ten * (mx - counts)) / max(mx, dt(1))
                          if mx > 0 else np.full_like(counts, ten))
        # the weighted sum in the provider's order: spread, inter-pod
        # affinity (0), least, balanced, prefer-avoid, node affinity (0),
        # taint toleration, image locality (0)
        total = np.zeros(w.n, dt)
        total = total + spread
        total = total + lr
        total = total + bal
        total = total + dt(W_PREFER_AVOID) * ten
        total = total + ten
        return total

    @staticmethod
    def ties(mask: np.ndarray, score: np.ndarray) -> np.ndarray:
        f = np.flatnonzero(mask)
        if len(f) == 0:
            return f
        sc = score[f]
        return f[sc == sc.max()]

    def sequential(self, ctls: Sequence[int], st: State) -> np.ndarray:
        """One pod at a time, each against the pods before it."""
        st = st.copy()
        out = np.full(len(ctls), -1, np.int64)
        for b, c in enumerate(ctls):
            t = self.ties(self.fits(st), self.score(st, int(c)))
            if len(t):
                out[b] = t[(self.li + b) % len(t)]
                st.add(self.w, np.array([c]), out[b:b + 1])
        return out

    def place(self, ctls: Sequence[int]) -> Tuple[np.ndarray, str]:
        """The reference's answer for the next popped batch, against the
        committed state (nothing is committed here).  Returns (node per
        pod, -1 = unschedulable; which rule decided it)."""
        ctls = np.asarray(ctls, np.int64)
        st = self.state
        mask = self.fits(st)
        hosts = np.full(len(ctls), -1, np.int64)
        for c in np.unique(ctls):
            t = self.ties(mask, self.score(st, int(c)))
            if len(t):
                idx = np.flatnonzero(ctls == c)
                hosts[idx] = t[(self.li + idx) % len(t)]
        if (hosts >= 0).all():
            after = st.copy()
            after.add(self.w, ctls, hosts)
            if (after.cpu <= self.w.cpu).all() and (
                    after.mem <= self.w.mem).all() and (
                    after.pods <= self.w.pods).all():
                return hosts, "batch-start"
        return self.sequential(ctls, st), "sequential"
