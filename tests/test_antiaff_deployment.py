"""The sp5k-antiaff deployment (scheduler_perf's
BenchmarkSchedulingPodAntiAffinity) at a small size, through the live
Scheduler.run_once path, against the benchmark's own reference
(benchmark/shapes/antiaff.py) with check.py's replay: every popped batch
is compared, and the guarantees are counted over every bound pod."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import byname
from benchmark.harness import check as chk
from benchmark.harness import drive
from kubernetes_tpu.codec import SnapshotEncoder
from kubernetes_tpu.config.types import KubeSchedulerConfiguration
from kubernetes_tpu.models import speculative
from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.scheduler import Scheduler, SchedulerConfig
from kubernetes_tpu.utils import metrics as m

from fixtures import make_node

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPE = byname.load(ROOT, "shapes", "antiaff")


def small_cfg(nodes=48, existing=16, set_pods=12, batch=32, cpu="4"):
    cfg = byname.load_config(ROOT, "sp5k-antiaff")
    cfg = json.loads(json.dumps(cfg))
    cfg.update(nodes=nodes, existing_pods=existing, set_pods=set_pods)
    cfg["node"]["cpu"] = cpu
    cfg["scheduler"]["batchSize"] = batch
    return cfg


class Deployment:
    """The deployment's world, pods and a Scheduler built as the
    benchmark builds them, with the benchmark's seams (popped batches,
    binds)."""

    def __init__(self, cfg, n_pods):
        self.world = SHAPE.build_world(cfg)
        self.sets = SHAPE.stream(self.world, n_pods)
        self.pods = [SHAPE.pod_object(self.world, cfg, i, s)
                     for i, s in enumerate(self.sets)]
        self.queue = drive.make_queue()
        self.seams = drive.Seams()
        kcfg = KubeSchedulerConfiguration.from_dict(cfg["scheduler"])
        self.sched = Scheduler(
            cache=SchedulerCache(SHAPE.build_encoder(self.world, cfg)),
            queue=self.queue, binder=self.seams.binder,
            config=SchedulerConfig.from_component_config(kcfg))
        self.added = 0

    def offer(self, k):
        for p in self.pods[self.added:self.added + k]:
            self.queue.add(p)
        self.added += k

    def drain(self):
        while self.queue.has_schedulable():
            self.sched.run_once(timeout=0.0)

    def replay(self, shape=SHAPE):
        """check.py's comparison and the guarantees, over every batch."""
        index = {p.name: i for i, p in enumerate(self.pods)}
        node_id = {n: i for i, n in enumerate(self.world.names)}
        batches = [[index[n] for n in b] for b in self.queue.popped]
        node_of = {index[n]: node_id[v] for n, v in self.seams.bound.items()}
        cmp = chk.compare(shape, self.world, batches, self.sets, node_of)
        return cmp, shape.guarantees(self.world, node_of), batches, node_of


@pytest.mark.parametrize("packed", [False, True], ids=["host", "packed"])
def test_every_batch_matches_the_reference(packed, monkeypatch):
    """Seeded arrival chunks make different batches (one set or several,
    full or partial); over the seeds both of the reference's rules
    decide some batch, and the program matches it on every one."""
    monkeypatch.setattr(speculative, "FORCE_PACKED_PATH", packed)
    cfg = small_cfg()
    rules = {}
    for seed in (3, 11, 2 ** 31 + 5):
        rng = np.random.default_rng(seed)
        d = Deployment(cfg, 16 + 12 * 20)
        d.offer(16)
        d.drain()
        while d.added < len(d.pods):
            d.offer(int(rng.integers(1, 40)))
            d.sched.run_once(timeout=0.0)
        d.drain()
        cmp, guar, batches, node_of = d.replay()
        assert cmp["mismatches"] == 0, (seed, cmp)
        assert len(node_of) == len(d.pods)
        assert guar == {"overcommitted_nodes": 0,
                        "antiaffinity_violations": 0}
        for k, v in cmp["rules"].items():
            rules[k] = rules.get(k, 0) + v
    assert rules.get("batch-start", 0) > 0 and rules.get("sequential", 0) > 0


def _collision():
    """Four 1-cpu nodes, three setup pods (one on each of three nodes),
    then a batch of two pods of one set: the empty node scores highest
    for both, so round 1 puts them on one node."""
    d = Deployment(small_cfg(nodes=4, existing=3, set_pods=2, batch=4,
                             cpu="1"), 5)
    d.offer(3)
    d.drain()
    assert len({d.seams.bound[p.name] for p in d.pods[:3]}) == 3
    return d


@pytest.mark.parametrize("packed", [False, True], ids=["host", "packed"])
def test_in_batch_anti_affinity_collision_takes_the_exact_scan(packed,
                                                               monkeypatch):
    monkeypatch.setattr(speculative, "FORCE_PACKED_PATH", packed)
    d = _collision()
    redo0 = m.ENGINE_BATCHES.value(path="redo")
    stall0 = d.sched.phase_seconds["fence_redo"]
    d.offer(2)
    d.drain()
    assert m.ENGINE_BATCHES.value(path="redo") == redo0 + 1
    assert d.sched.phase_seconds["fence_redo"] > stall0
    cmp, guar, batches, node_of = d.replay()
    assert cmp["rules"] == {"batch-start": 1, "sequential": 1}
    assert cmp["mismatches"] == 0
    assert guar["antiaffinity_violations"] == 0
    # one pod at a time: the first takes the empty node, the second one
    # of the others
    empty = (set(range(4)) - {node_of[i] for i in range(3)}).pop()
    assert node_of[3] == empty and node_of[4] != empty


class _NoAntiAffinity(SHAPE.Reference):
    """Planted fault: the reference forgets the anti-affinity mask."""

    def forbidden(self, st, t):
        return np.zeros(self.w.n, bool)

    def collides(self, sets, hosts):
        return False


class _FaultyShape:
    LIMITS = SHAPE.LIMITS
    Reference = _NoAntiAffinity
    guarantees = staticmethod(SHAPE.guarantees)


def test_reference_without_the_mask_disagrees_and_counts_violations():
    d = _collision()
    d.offer(2)
    d.drain()
    cmp, _, batches, _ = d.replay(_FaultyShape)
    assert cmp["mismatches"] > 0
    faulty = chk.control_placements(_FaultyShape, d.world, batches, d.sets,
                                    np.float32)
    assert SHAPE.guarantees(d.world, faulty)["antiaffinity_violations"] > 0


def test_node_pair_index_is_the_snapshots_incidence():
    """encoder.node_pair_index lists each node row's pairs, the rows of
    the snapshot's topo_pairs, as nodes, labels and keys come and go."""
    enc = SnapshotEncoder()
    enc.add_nodes([make_node(f"n{i}", labels={
        "zone": f"z{i % 3}", **({"rack": f"r{i % 2}"} if i % 4 else {})})
        for i in range(10)])
    enc.register_topology_key("zone")

    def check():
        snap = enc.snapshot()
        idx, keys = enc.node_pair_index()
        assert idx.shape[0] == snap.topo_pairs.shape[0]
        assert sorted(k for k in keys if k >= 0) == sorted(enc.topo_keys)
        for row in range(idx.shape[0]):
            want = set(np.flatnonzero(snap.topo_pairs[row]).tolist())
            assert set(idx[row][idx[row] >= 0].tolist()) == want, row

    check()
    enc.register_topology_key("rack")
    enc.remove_node("n3")
    enc.add_node(make_node("n10", labels={"zone": "z9", "rack": "r7"}))
    check()
