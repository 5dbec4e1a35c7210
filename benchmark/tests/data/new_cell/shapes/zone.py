"""A test-only shape: the plain deployment with half of its nodes (the
even ones) labelled zone=zone1, and the test controller's pod template
carrying a nodeSelector on that label, so that its reference filters
each template's nodes by label.  The setup controller's pods select no
label."""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from benchmark.shapes import plain
from benchmark.shapes.plain import stream, warm_sizes  # noqa: F401

LABEL = ("zone", "zone1")
LIMITS = {**plain.LIMITS, "selector_violations": 0}


@dataclasses.dataclass
class World(plain.World):
    labelled: np.ndarray = None   # bool[N]: the node carries LABEL
    selects: tuple = (False, True)  # per template: a nodeSelector on LABEL


def build_world(cfg: dict) -> World:
    base = plain.build_world(cfg)
    return World(**{f.name: getattr(base, f.name)
                    for f in dataclasses.fields(plain.World)},
                 labelled=np.arange(base.n) % 2 == 0)


def capacity(world: World) -> int:
    """The labelled nodes' pods: a stream whose test pods all select
    the label fits there."""
    per = np.minimum(np.minimum(world.cpu // world.pod_cpu,
                                world.mem // world.pod_mem), world.pods)
    return int(per[world.labelled].sum())


def build_encoder(world: World, cfg: dict):
    from kubernetes_tpu.api.factory import make_node
    from kubernetes_tpu.codec import SnapshotEncoder

    node = cfg["node"]
    enc = SnapshotEncoder()
    enc.add_nodes([make_node(name, cpu=str(node["cpu"]),
                             mem=str(node["memory"]), pods=int(node["pods"]),
                             labels=dict([LABEL]) if lab else None)
                   for name, lab in zip(world.names, world.labelled)])
    for ctl in world.controllers:
        enc.add_spread_selector(cfg["namespace"], {"name": ctl},
                                kind="ReplicationController")
    return enc


def pod_object(world: World, cfg: dict, i: int, ctl: int):
    from kubernetes_tpu.api.factory import make_pod

    pod = cfg["pod"]
    name = world.controllers[int(ctl)]
    return make_pod(f"{name}-pod-{i}", namespace=cfg["namespace"],
                    cpu=str(pod["cpu"]), mem=str(pod["memory"]),
                    limits={"cpu": str(pod["cpu"]),
                            "memory": str(pod["memory"])},
                    labels={"name": name},
                    node_selector=(dict([LABEL]) if world.selects[int(ctl)]
                                   else None))


def guarantees(world: World, node_of: Dict[int, int]) -> dict:
    """The plain guarantees, and no pod on a node its template's
    selector excludes."""
    ids = np.fromiter(node_of.keys(), np.int64, len(node_of))
    nodes = np.fromiter(node_of.values(), np.int64, len(node_of))
    ts = stream(world, int(ids.max()) + 1 if len(ids) else 0)[ids]
    wrong = np.asarray(world.selects)[ts] & ~world.labelled[nodes]
    return {**plain.guarantees(world, node_of),
            "selector_violations": int(wrong.sum())}


class Reference(plain.Reference):
    """The plain reference, each template's nodes filtered by its
    selector."""

    def eligible(self, c: int) -> np.ndarray:
        """bool[N]: the nodes template c's selector admits."""
        if self.w.selects[c]:
            return self.w.labelled
        return np.ones(self.w.n, bool)

    def sequential(self, ctls, st):
        st = st.copy()
        out = np.full(len(ctls), -1, np.int64)
        for b, c in enumerate(ctls):
            t = self.ties(self.fits(st) & self.eligible(int(c)),
                          self.score(st, int(c)))
            if len(t):
                out[b] = t[(self.li + b) % len(t)]
                st.add(self.w, np.array([c]), out[b:b + 1])
        return out

    def place(self, ctls):
        ctls = np.asarray(ctls, np.int64)
        st = self.state
        mask = self.fits(st)
        hosts = np.full(len(ctls), -1, np.int64)
        for c in np.unique(ctls):
            t = self.ties(mask & self.eligible(int(c)),
                          self.score(st, int(c)))
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
