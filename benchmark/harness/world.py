"""The world of a configuration file (benchmark/configs/<name>.json).

A configuration is a scheduler_perf deployment as upstream writes it: a
node template repeated `nodes` times, one pod template, `existing_pods`
pods of the setup controller scheduled before anything is timed, and
then the test controller's pods, which the traffic mix offers.  Each
controller is a ReplicationController whose selector (`name=<ctl>`) is
the SelectorSpread selector of its pods.

Two views of the same data: plain arrays (World) that the reference
reads, and the program's API objects (Node, Pod) that the system under
test reads.  The arrays never come from the objects.  Nothing here
depends on the seed: upstream's workload has no random part, and the
traffic mix draws its arrival order from the seed.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import List

import numpy as np

_SUFFIX = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40,
           "k": 10 ** 3, "M": 10 ** 6, "G": 10 ** 9}


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


def load_config(root: str, name: str) -> dict:
    path = os.path.join(root, "benchmark", "configs", f"{name}.json")
    with open(path) as f:
        cfg = json.load(f)
    if cfg.get("name") != name:
        raise ValueError(f"{path}: name {cfg.get('name')!r} != {name!r}")
    return cfg


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


def controller_of(world: World, n_pods: int) -> np.ndarray:
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
