"""Factored pair rows from the encoder.

A batch that is not affinity-lean or not volume-lean can leave
encode_pods with its TP-wide pair leaves as PairRows: one row per pod
class plus each pod's row index.  pack_tree ships them as the very
("uniq", "idx") entries, meta and bytes its row hashing gives for the
dense batch, so the device program is the same; every reader that does
not go through pack_tree's factoring gets the dense arrays."""

import dataclasses

import jax
import numpy as np
import pytest

from kubernetes_tpu.api.storage import PersistentVolume, PersistentVolumeClaim
from kubernetes_tpu.codec import SnapshotEncoder, transfer
from kubernetes_tpu.codec.faults import (
    FAULT_PERSISTENT,
    FaultInjector,
    install_injector,
)
from kubernetes_tpu.codec.schema import PAIR_FIELDS, PairRows, factor_row_limit
from kubernetes_tpu.codec.transfer import pack_tree, unpack_tree
from kubernetes_tpu.models import speculative
from kubernetes_tpu.runtime.flightrecorder import FlightRecorder
from kubernetes_tpu.utils import metrics as m

from fixtures import make_node, make_pod
from test_antiaff_deployment import SHAPE, Deployment, small_cfg
from test_affinity_spans import _anti_pods, _cycle, _plain, _sched

ZONE = "failure-domain.beta.kubernetes.io/zone"
AFF_FIELDS = PAIR_FIELDS[:4]
VOL_FIELDS = PAIR_FIELDS[4:]


def _bound(pod, node):
    return dataclasses.replace(
        pod, spec=dataclasses.replace(pod.spec, node_name=node))


def _antiaff(nodes, set_pods, committed, batch):
    """The antiaff deployment's encoder with its first `committed` pods
    (the setup pods, then whole and part sets) bound one a node, and the
    next `batch` pods of the stream."""
    cfg = small_cfg(nodes=nodes, existing=16, set_pods=set_pods)
    world = SHAPE.build_world(cfg)
    enc = SHAPE.build_encoder(world, cfg)
    sets = SHAPE.stream(world, committed + batch)
    pods = [SHAPE.pod_object(world, cfg, i, s) for i, s in enumerate(sets)]
    enc.add_pods([_bound(p, world.names[i % nodes])
                  for i, p in enumerate(pods[:committed])])
    # a row per set, and the padding's zero row
    pad = batch < 1 << (batch - 1).bit_length()
    return enc, pods[committed:], len(set(sets[committed:].tolist())) + pad


def _mixed():
    """No live terms in the cluster, and a batch of three plain pods of
    one static class, a pod with a PVC bound to a zoned PV, and anti-affine
    pods of two sets: neither affinity- nor volume-lean."""
    enc = SnapshotEncoder()
    enc.add_nodes([make_node(f"n{i}", labels={
        "kubernetes.io/hostname": f"n{i}", ZONE: f"z{i % 3}"})
        for i in range(24)])
    enc.add_pv(PersistentVolume.from_dict({
        "metadata": {"name": "pv1", "labels": {ZONE: "z1"}},
        "spec": {"gcePersistentDisk": {"pdName": "d"},
                 "capacity": {"storage": "10Gi"}}}))
    enc.add_pvc(PersistentVolumeClaim.from_dict({
        "metadata": {"name": "c1", "namespace": "default"},
        "spec": {"volumeName": "pv1"}}))
    anti = [make_pod(f"a{j}", cpu="100m", labels={"app": f"s{j % 2}"},
                     affinity=_anti_affinity(f"s{j % 2}")) for j in range(6)]
    pods = ([make_pod(f"p{j}", cpu="100m") for j in range(3)]
            + [make_pod("v", cpu="100m", volumes=[
                {"persistentVolumeClaim": {"claimName": "c1"}}])]
            + anti)
    # rows: the volume pod's, one per set, and the zero row the plain
    # pods share with the padding
    return enc, pods, 4


def _anti_affinity(app):
    return {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [
            {"labelSelector": {"matchLabels": {"app": app}},
             "topologyKey": "kubernetes.io/hostname"}]}}


def _leaves(batch):
    return {f.name: np.asarray(getattr(batch, f.name))
            for f in dataclasses.fields(batch)}


def _same_wire(a, b):
    (bufs_a, meta_a), (bufs_b, meta_b) = a, b
    assert meta_a == meta_b
    for x, y in zip(bufs_a, bufs_b):
        assert x.dtype == y.dtype and np.array_equal(x, y)


CASES = {
    # 32 pods over the end of set 2 and the start of set 3
    "two_sets": (lambda: _antiaff(64, 24, 16 + 30, 32), None),
    # 30 pods over sets 2-4, two padding rows
    "three_sets": (lambda: _antiaff(64, 12, 16 + 18, 30), None),
    "mixed": (_mixed, None),
    # real leaf bytes: f32[512, TP] pair weights pass pack_tree's 1 MiB
    "full_bytes": (lambda: _antiaff(520, 200, 16 + 300, 512),
                   transfer._FACTOR_MIN_BYTES),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_factored_batch_ships_as_the_dense_batch(case, monkeypatch):
    build, min_bytes = CASES[case]
    # small shapes: factor every multi-row leaf on the wire
    monkeypatch.setattr(transfer, "_FACTOR_MIN_BYTES",
                        0 if min_bytes is None else min_bytes)
    enc, pods, n_rows = build()
    dense = enc.encode_pods(pods)
    fact = enc.encode_pods(pods, pair_rows=True)
    vol = case == "mixed"
    for f in PAIR_FIELDS:
        leaf = getattr(fact, f)
        if f in AFF_FIELDS or vol:
            assert isinstance(leaf, PairRows), f
            assert leaf.shape == np.shape(getattr(dense, f))
            assert leaf.rows.shape[0] == n_rows, f
        else:
            assert isinstance(leaf, np.ndarray), f
    # every consumer off the wire sees today's arrays
    want = _leaves(dense)
    got = _leaves(fact.dense())
    for k, v in want.items():
        assert got[k].dtype == v.dtype and np.array_equal(
            got[k], v, equal_nan=True), k
    # the wire: the same uniq/idx entries, meta (the jit key) and bytes
    wire = pack_tree({"pods": fact}, factor=True)
    _same_wire(wire, pack_tree({"pods": dense}, factor=True))
    assert any(e[3] == "uniq" for e in wire[1][1])
    bufs, meta = wire
    out = jax.jit(lambda b: unpack_tree(b, meta))(bufs)
    for k, v in want.items():
        assert np.array_equal(np.asarray(getattr(out["pods"], k)), v,
                              equal_nan=True), k


@pytest.mark.parametrize("extra", [0, 1], ids=["at_limit", "past_limit"])
def test_more_classes_than_the_limit_stay_dense(extra, monkeypatch):
    """A full 64-pod batch may ship at most factor_row_limit(64) = 32
    distinct rows factored: 32 classes leave as PairRows, 33 dense."""
    monkeypatch.setattr(transfer, "_FACTOR_MIN_BYTES", 0)
    enc = SnapshotEncoder()
    enc.add_nodes([make_node(f"n{i}", labels={"kubernetes.io/hostname":
                                              f"n{i}"}) for i in range(40)])
    # bound anti-affine pods make each set's forbidden row differ
    k = factor_row_limit(64) + extra
    enc.add_pods([_bound(make_pod(f"e{j}", cpu="100m",
                                  labels={"app": f"s{j}"},
                                  affinity=_anti_affinity(f"s{j}")),
                         f"n{j % 40}") for j in range(k)])
    pods = [make_pod(f"a{j}", cpu="100m", labels={"app": f"s{j % k}"},
                     affinity=_anti_affinity(f"s{j % k}")) for j in range(64)]
    fact = enc.encode_pods(pods, pair_rows=True)
    dense = enc.encode_pods(pods)
    for f in AFF_FIELDS:
        leaf = getattr(fact, f)
        if extra:
            assert isinstance(leaf, np.ndarray), f
        else:
            assert isinstance(leaf, PairRows) and leaf.rows.shape[0] == k, f
    _same_wire(pack_tree({"pods": fact}, factor=True),
               pack_tree({"pods": dense}, factor=True))
    want, got = _leaves(dense), _leaves(fact.dense())
    assert all(np.array_equal(got[n], v, equal_nan=True)
               for n, v in want.items())


def test_unfactored_pack_and_lean_batches_get_dense_arrays(monkeypatch):
    """pack_tree(factor=False), the CPU backend's own choice, ships the
    factored batch as the dense one; lean batches never hold PairRows."""
    monkeypatch.setattr(transfer, "_FACTOR_MIN_BYTES", 0)
    enc, pods, _ = _antiaff(64, 24, 16 + 30, 32)
    fact = enc.encode_pods(pods, pair_rows=True)
    wire = pack_tree({"pods": fact}, factor=False)
    _same_wire(wire, pack_tree({"pods": enc.encode_pods(pods)},
                               factor=False))
    assert not any(e[3] for e in wire[1][1])
    assert not transfer.factors_by_default()  # this suite runs on the CPU
    _same_wire(pack_tree({"pods": fact}),
               pack_tree({"pods": fact}, factor=False))
    plain = SnapshotEncoder()
    plain.add_nodes([make_node(f"n{i}") for i in range(8)])
    lean = plain.encode_pods([make_pod(f"p{j}", cpu="100m")
                              for j in range(5)], pair_rows=True)
    for f in PAIR_FIELDS:
        assert isinstance(getattr(lean, f), np.ndarray)
        assert np.shape(getattr(lean, f))[-1] == 1
    assert lean.dense() is lean


def _pair_counts():
    return {f: m.ENCODE_PAIR_LEAVES.value(form=f)
            for f in ("factored", "dense")}


def test_encode_pairs_key_and_counter_only_for_non_lean_batches():
    fr = FlightRecorder()
    sched, queue = _sched(fr)
    c0 = _pair_counts()
    _cycle(sched, queue, _plain("plain", 3))
    assert sched.phase_seconds["encode_pairs"] == 0.0
    assert "encode_pairs" not in [c.name for c in fr.spans()[-1].children]
    assert _pair_counts() == c0
    _cycle(sched, queue, _anti_pods("web", 3))
    # the CPU backend packs unfactored, so the live cycle keeps dense rows
    assert _pair_counts() == {"factored": c0["factored"],
                              "dense": c0["dense"] + 1}
    ph = sched.phase_seconds
    assert 0.0 < ph["encode_pairs"] < ph["encode"]
    root = fr.spans()[-1]
    kids = [c for c in root.children if c.name == "encode_pairs"]
    encode = [c for c in root.children if c.name == "encode"][0]
    assert len(kids) == 1
    assert encode.start <= kids[0].start and kids[0].end <= encode.end
    # the three pods of one set, and the padding row
    assert kids[0].attrs["pair_rows"] == 2


def _force_factoring(monkeypatch):
    """What the chip does: pack_tree factors by default, the speculative
    engine takes its packed launch, and leaves of any size factor."""
    monkeypatch.setattr(transfer, "factors_by_default", lambda: True)
    monkeypatch.setattr(speculative, "FORCE_PACKED_PATH", True)
    monkeypatch.setattr(transfer, "_FACTOR_MIN_BYTES", 0)


def _checked_wire(monkeypatch):
    """Wrap the engine's pack_tree: each launch's wire must equal the
    wire of its dense batch.  Returns the count of factored launches."""
    seen = {"factored": 0}

    def pack(tree, factor=None):
        got = pack_tree(tree, factor)
        pods = tree["pods"]
        if any(isinstance(getattr(pods, f), PairRows) for f in PAIR_FIELDS):
            seen["factored"] += 1
            _same_wire(got, pack_tree(dict(tree, pods=pods.dense()), factor))
        return got

    monkeypatch.setattr(speculative, "pack_tree", pack)
    return seen


def test_live_factored_cycles_match_the_reference(monkeypatch):
    """The live Scheduler with the chip's packing on: every non-lean
    batch leaves the encoder factored, each launch's wire equals its
    dense batch's, and every placement is the reference's."""
    _force_factoring(monkeypatch)
    seen = _checked_wire(monkeypatch)
    c0 = _pair_counts()
    rng = np.random.default_rng(2 ** 31 + 7)
    d = Deployment(small_cfg(), 16 + 12 * 12)
    d.offer(16)
    d.drain()
    while d.added < len(d.pods):
        d.offer(int(rng.integers(1, 40)))
        d.sched.run_once(timeout=0.0)
    d.drain()
    cmp, guar, batches, node_of = d.replay()
    assert cmp["mismatches"] == 0, cmp
    assert len(node_of) == len(d.pods)
    assert guar == {"overcommitted_nodes": 0, "antiaffinity_violations": 0}
    c1 = _pair_counts()
    assert c1["dense"] == c0["dense"]
    assert c1["factored"] - c0["factored"] == seen["factored"] > 0


def _degraded_run():
    """A warm-up batch on the device, then a persistent fence fault: the
    next batch's launch fails and the CPU engine answers it, and with
    the breaker held open the batch after goes to the CPU engine
    without a launch.  Returns the placements and the encode counters
    before, after the first faulted batch, and at the end."""
    d = Deployment(small_cfg(), 16 + 12 * 4)
    health = d.sched.device_health
    health.backoff_base_s = health.backoff_max_s = 0.001
    health.open_duration_s = 3600.0  # no canary in this test
    d.offer(16)
    d.drain()
    inj = FaultInjector(seed=7)
    remove = install_injector(inj)
    try:
        inj.arm("fence", kind=FAULT_PERSISTENT)
        deg0 = m.DEGRADED_CYCLES.value
        counts = [_pair_counts()]
        d.offer(24)
        d.drain()
        assert m.DEGRADED_CYCLES.value > deg0
        assert not health.device_available
        counts.append(_pair_counts())
        d.offer(24)
        d.drain()
        counts.append(_pair_counts())
    finally:
        remove()
    _, guar, _, node_of = d.replay()
    assert len(node_of) == len(d.pods)
    assert guar["antiaffinity_violations"] == 0
    return node_of, counts


def test_degraded_cycles_encode_dense(monkeypatch):
    """A batch the CPU engine answers is placed as without factoring;
    with the breaker open the live cycle encodes it dense."""
    want, _ = _degraded_run()
    _force_factoring(monkeypatch)
    seen = _checked_wire(monkeypatch)
    got, (c0, c1, c2) = _degraded_run()
    assert got == want
    # the faulted batch was launched factored (then retried and given
    # up); with the breaker open neither a launch nor a factored encode
    assert c1["factored"] > c0["factored"]
    assert seen["factored"] >= c1["factored"] - c0["factored"]
    assert c2["factored"] == c1["factored"] and c2["dense"] > c1["dense"]
