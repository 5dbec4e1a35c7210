"""The antiaff shape: scheduler_perf's BenchmarkSchedulingPodAntiAffinity
deployment.

The plain deployment's nodes, pod requests and setup controller, with
the measured pods of makeBasePodWithPodAntiAffinity: labels {name: test,
color: green} and one required anti-affinity term, matchLabels {color:
green} over kubernetes.io/hostname, naming no namespaces (so it applies
in the pod's own).  Upstream measures one set of `set_pods` such pods;
the stream repeats that set, set k in its own namespace (`set_namespace`
with <set> = k), so no two pods of a set share a node and sets do not
constrain each other.  Set 0 is the setup controller's pods, with no
term.  Nothing depends on the seed.

Reference: the plain reference (benchmark/shapes/plain.py: filter,
scores, selectHost) with kube-scheduler 1.16's MatchInterPodAffinity for
required anti-affinity, checked both ways (predicates.go:1196-1509):

  the pod's own term against the pods already on a node
  (satisfiesPodsAffinityAntiAffinity), and the terms of the pods on a
  node against the pod (satisfiesExistingPodsAntiAffinity); a term
  matches a pod when the pod is in the term's namespaces (the owner's
  own where it names none) and carries every matchLabels pair; the
  topology key is the hostname, so a domain is one node.

InterPodAffinityPriority is 0 on every node here: 1.16 scores preferred
terms and, for existing pods' required *affinity* terms, the hard
weight (priorities/interpod_affinity.go processTerms / processExistingPod),
and these pods carry only a required anti-affinity term; with every raw
count 0 the priority's max equals its min and each node scores 0.  So
the reference adds nothing for it.  SelectorSpread counts the setup
controller's pods for the setup pods; no controller selects name=test,
so it is constant (10) for the measured pods.

Batch rule: every pod of a batch against the state the batch started
from; where that answer overfills a node, leaves a pod unplaced, or puts
two pods on one node where either's term matches the other, one pod at a
time, each against the pods before it.

The reference imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark.shapes import plain

HOSTNAME = "kubernetes.io/hostname"
LIMITS = {**plain.LIMITS, "antiaffinity_violations": 0}


@dataclasses.dataclass
class World(plain.World):
    """plain's arrays; controllers = [setup controller, the measured
    pods' `name` label, which no controller selects]."""

    set_pods: int = 0
    setup_ns: str = ""
    set_ns: str = ""              # with <set> = k: set k's namespace
    labels: Tuple[dict, dict] = ({}, {})   # setup pods', measured pods'
    term_labels: dict = None      # the measured term's matchLabels
    term_namespaces: tuple = ()   # its namespaces; () = the owner's own
    generate_name: str = ""

    def namespace(self, s: int) -> str:
        if s == 0:
            return self.setup_ns
        return self.set_ns.replace("<set>", str(s))

    def pod_labels(self, s: int) -> dict:
        return self.labels[min(s, 1)]

    def term_matches(self, owner: int, s: int) -> bool:
        """Set `owner`'s required anti-affinity term matches a pod of set
        s (set 0 carries no term)."""
        if owner == 0:
            return False
        nss = self.term_namespaces or (self.namespace(owner),)
        lab = self.pod_labels(s)
        return (self.namespace(s) in nss
                and all(lab.get(k) == v for k, v in self.term_labels.items()))


def build_world(cfg: dict) -> World:
    n = int(cfg["nodes"])
    node, pod, mp = cfg["node"], cfg["pod"], cfg["measured_pod"]
    (term,) = mp["affinity"]["podAntiAffinity"][
        "requiredDuringSchedulingIgnoredDuringExecution"]
    sel = term["labelSelector"]
    if term["topologyKey"] != HOSTNAME or set(sel) != {"matchLabels"}:
        raise ValueError("antiaff: one matchLabels term over the hostname")
    return World(
        names=[f"node-{k}" for k in range(n)],
        cpu=np.full(n, plain.milli_cpu(node["cpu"]), np.int64),
        mem=np.full(n, plain.mem_bytes(node["memory"]), np.int64),
        pods=np.full(n, int(node["pods"]), np.int64),
        pod_cpu=plain.milli_cpu(pod["cpu"]),
        pod_mem=plain.mem_bytes(pod["memory"]),
        controllers=[cfg["setup_controller"], mp["labels"]["name"]],
        existing=int(cfg["existing_pods"]),
        set_pods=int(cfg["set_pods"]),
        setup_ns=cfg["namespace"],
        set_ns=cfg["set_namespace"],
        labels=({"name": cfg["setup_controller"]}, dict(mp["labels"])),
        term_labels=dict(sel["matchLabels"]),
        term_namespaces=tuple(term.get("namespaces") or ()),
        generate_name=mp["generateName"],
    )


def capacity(world: World) -> int:
    """plain's capacity; a set larger than the cluster stops the stream
    at its node count, since no two of its pods share a node."""
    if world.set_pods > world.n:
        return min(world.capacity(), world.existing + world.n)
    return world.capacity()


def warm_sizes(world: World, batch: int) -> List[int]:
    """plain's list, then batches of batch * 5/8 and * 3/4 pods.  A
    batch's pods here land on as many nodes as it has pods, less where
    two sets share one, so plain's list may never reach the snapshot
    cache's largest dirty-row scatter (more than half a batch of rows,
    at most a quarter of the node arena); these two do."""
    return plain.warm_sizes(world, batch) + [batch * 5 // 8,
                                             batch * 3 // 4]


def stream(world: World, n_pods: int) -> np.ndarray:
    """i32[n]: the set of pod i of the stream (0 = setup, the first
    `existing` pods; then sets 1, 2, ... of `set_pods` each)."""
    i = np.arange(n_pods)
    return np.where(i < world.existing, 0,
                    1 + (i - world.existing) // max(world.set_pods, 1)
                    ).astype(np.int32)


def build_encoder(world: World, cfg: dict):
    """plain's nodes (each labelled with its hostname by make_node) and
    the setup controller's SelectorSpread selector."""
    from kubernetes_tpu.api.factory import make_node
    from kubernetes_tpu.codec import SnapshotEncoder

    node = cfg["node"]
    enc = SnapshotEncoder()
    enc.add_nodes([make_node(name, cpu=str(node["cpu"]),
                             mem=str(node["memory"]), pods=int(node["pods"]),
                             labels={HOSTNAME: name})
                   for name in world.names])
    enc.add_spread_selector(cfg["namespace"], world.labels[0],
                            kind="ReplicationController")
    return enc


def pod_object(world: World, cfg: dict, i: int, s: int):
    """Pod i of the stream, of set s."""
    from kubernetes_tpu.api.factory import make_pod

    pod = cfg["pod"]
    s = int(s)
    kw = dict(cpu=str(pod["cpu"]), mem=str(pod["memory"]),
              limits={"cpu": str(pod["cpu"]), "memory": str(pod["memory"])},
              labels=dict(world.pod_labels(s)), namespace=world.namespace(s))
    if s == 0:
        return make_pod(f"{world.controllers[0]}-pod-{i}", **kw)
    return make_pod(f"{world.generate_name}{i}",
                    affinity=cfg["measured_pod"]["affinity"], **kw)


def _related(world: World, memo: dict, t: int, s: int) -> bool:
    """A pod of set t and a pod of set s may not share a hostname domain:
    either's term matches the other."""
    key = (t, s) if t <= s else (s, t)
    hit = memo.get(key)
    if hit is None:
        hit = memo[key] = (world.term_matches(t, s)
                           or world.term_matches(s, t))
    return hit


def guarantees(world: World, node_of: Dict[int, int]) -> dict:
    """plain's guarantees, and the pairs of bound pods on one node where
    either's anti-affinity term matches the other."""
    ids = np.fromiter(node_of.keys(), np.int64, len(node_of))
    nodes = np.fromiter(node_of.values(), np.int64, len(node_of))
    sets = stream(world, int(ids.max()) + 1 if len(ids) else 0)[ids]
    cnt = {int(s): np.bincount(nodes[sets == s], minlength=world.n)
           for s in np.unique(sets)}
    memo: dict = {}
    keys = sorted(cnt)
    pairs = 0
    for a, t in enumerate(keys):
        for s in keys[a:]:
            if not _related(world, memo, t, s):
                continue
            c = cnt[t]
            pairs += int((c * (c - 1) // 2).sum() if s == t
                         else (c * cnt[s]).sum())
    return {**plain.guarantees(world, node_of),
            "antiaffinity_violations": pairs}


class State(plain.State):
    """plain's usage and the setup controller's per-node counts, and for
    each set the nodes that hold one of its pods."""

    def __init__(self, world):
        super().__init__(world)
        self.occ: Dict[int, np.ndarray] = {}   # set -> bool[N]

    def copy(self) -> "State":
        out = State.__new__(State)
        out.cpu, out.mem = self.cpu.copy(), self.mem.copy()
        out.pods, out.count = self.pods.copy(), self.count.copy()
        out.occ = {s: v.copy() for s, v in self.occ.items()}
        return out

    def add(self, world, sets: np.ndarray, nodes: np.ndarray) -> None:
        ok = nodes >= 0
        s, h = sets[ok], nodes[ok]
        cnt = np.bincount(h, minlength=world.n)
        self.cpu += cnt * world.pod_cpu
        self.mem += cnt * world.pod_mem
        self.pods += cnt
        # only the setup controller selects its pods
        np.add.at(self.count[0], h[s == 0], 1)
        for k in np.unique(s):
            occ = self.occ.setdefault(int(k), np.zeros(world.n, bool))
            occ[h[s == k]] = True


class Reference(plain.Reference):
    def __init__(self, world, dtype=np.float32):
        super().__init__(world, dtype)
        self.state = State(world)
        self._memo: dict = {}

    def related(self, t: int, s: int) -> bool:
        return _related(self.w, self._memo, t, s)

    def forbidden(self, st: State, t: int) -> np.ndarray:
        """bool[N]: nodes holding a pod that set t's pods may not join,
        in either direction of MatchInterPodAffinity's anti-affinity."""
        out = np.zeros(self.w.n, bool)
        for s, occ in st.occ.items():
            if self.related(t, s):
                out |= occ
        return out

    def score(self, st: State, t: int) -> np.ndarray:
        # row 1 of the counts stays 0: SelectorSpread is constant there
        return super().score(st, min(int(t), 1))

    def collides(self, sets: np.ndarray, hosts: np.ndarray) -> bool:
        """Two pods of the answer share a node where either's term
        matches the other."""
        us = [int(t) for t in np.unique(sets)]
        for a, t in enumerate(us):
            ht = hosts[sets == t]
            for s in us[a:]:
                if not self.related(t, s):
                    continue
                if s == t:
                    if len(np.unique(ht)) < len(ht):
                        return True
                elif np.intersect1d(ht, hosts[sets == s]).size:
                    return True
        return False

    def sequential(self, sets: Sequence[int], st: State) -> np.ndarray:
        """One pod at a time, each against the pods before it."""
        st = st.copy()
        out = np.full(len(sets), -1, np.int64)
        forb = {int(t): self.forbidden(st, int(t)) for t in np.unique(sets)}
        for b, t in enumerate(sets):
            t = int(t)
            ties = self.ties(self.fits(st) & ~forb[t], self.score(st, t))
            if len(ties):
                h = out[b] = ties[(self.li + b) % len(ties)]
                st.add(self.w, np.array([t]), out[b:b + 1])
                for u, f in forb.items():
                    if self.related(u, t):
                        f[h] = True
        return out

    def place(self, sets: Sequence[int]) -> Tuple[np.ndarray, str]:
        """The reference's answer for the next popped batch (node per pod,
        -1 = unschedulable; which rule decided it)."""
        sets = np.asarray(sets, np.int64)
        st = self.state
        mask = self.fits(st)
        hosts = np.full(len(sets), -1, np.int64)
        for t in np.unique(sets):
            ties = self.ties(mask & ~self.forbidden(st, int(t)),
                             self.score(st, int(t)))
            if len(ties):
                idx = np.flatnonzero(sets == t)
                hosts[idx] = ties[(self.li + idx) % len(ties)]
        if (hosts >= 0).all() and not self.collides(sets, hosts):
            after = st.copy()
            after.add(self.w, sets, hosts)
            if (after.cpu <= self.w.cpu).all() and (
                    after.mem <= self.w.mem).all() and (
                    after.pods <= self.w.pods).all():
                return hosts, "batch-start"
        return self.sequential(sets, st), "sequential"
