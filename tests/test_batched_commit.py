"""Batched/pipelined commit path: state equivalence vs the per-pod loop,
and incremental (dirty-row) snapshot correctness.

The acceptance bar for the batched commit rebuild (ISSUE 1): batched
commit produces byte-identical cache/encoder state and identical emitted
events vs the per-pod loop on a mixed success/FitError/extender-error
batch, and the dirty-row incremental re-encode matches a full re-encode
after adds/deletes/updates.
"""

import dataclasses

import numpy as np
import pytest

from kubernetes_tpu.codec import SnapshotEncoder
from kubernetes_tpu.codec.transfer import DeviceSnapshotCache
from kubernetes_tpu.extender.client import ExtenderError
from kubernetes_tpu.runtime import (
    PriorityQueue,
    Scheduler,
    SchedulerCache,
    SchedulerConfig,
)

from fixtures import TEST_DIMS, ZONE_KEY, make_node, make_pod


def snapshots_equal(a, b, msg=""):
    for f in dataclasses.fields(a):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f.name)), np.asarray(getattr(b, f.name)),
            err_msg=f"{msg}field {f.name}",
        )


def encoder_state_equal(e1, e2, same_slots=True):
    """Byte-identical snapshot tensors + equivalent pod bookkeeping.

    same_slots=False relaxes the pod-ARENA slot ids (m): when a bind fails
    mid-batch, the per-pod loop frees the slot before later pods assume
    (they reuse it) while the batched path assumes the whole batch first —
    a pure permutation of an internal index; each pod's own arena row must
    still carry identical content either way."""
    snapshots_equal(e1.snapshot(full=True), e2.snapshot(full=True))
    assert set(e1.pods) == set(e2.pods)
    for key, r1 in e1.pods.items():
        r2 = e2.pods[key]
        if same_slots:
            assert r1.m == r2.m, key
        assert (r1.node_row, r1.priority) == (r2.node_row, r2.priority)
        np.testing.assert_array_equal(r1.req, r2.req)
        np.testing.assert_array_equal(r1.nonzero, r2.nonzero)
        for enc, rec in ((e1, r1), (e2, r2)):
            assert bool(enc.p_alive[rec.m])
            assert enc.p_node[rec.m] == rec.node_row
            assert enc.p_ns[rec.m] == enc.interner.lookup(rec.key[0])
    assert e1.generation == e2.generation


# ---------------------------------------------------------- encoder batch


def _mixed_pods(n=10):
    pods = []
    for i in range(n):
        kw = dict(
            cpu=f"{100 + 10 * (i % 3)}m", mem="128Mi",
            labels={"app": f"dep-{i % 3}", "idx": str(i)},
            node_name=f"n{i % 4}",
        )
        if i % 4 == 0:
            kw["ports"] = [{"hostPort": 8000 + i, "protocol": "TCP"}]
        if i % 5 == 0:
            kw["volumes"] = [
                {"gcePersistentDisk": {"pdName": f"pd-{i % 2}"}}
            ]
        if i == 3:
            # affinity term with NOVEL strings: interner id assignment
            # must follow add_pod's per-pod order (labels then terms per
            # pod) or every interned-id tensor diverges afterwards
            kw["affinity"] = {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "labelSelector": {
                        "matchLabels": {"novel-sel-key": "novel-sel-val"}},
                    "topologyKey": "novel.example.com/topo",
                }]}}
        if i == 7:
            kw["node_name"] = "absent-node"  # unassigned row (-1)
        pods.append(make_pod(f"p{i}", **kw))
    return pods


def test_add_pods_matches_sequential_add_pod():
    encs = [SnapshotEncoder(TEST_DIMS), SnapshotEncoder(TEST_DIMS)]
    for enc in encs:
        for i in range(4):
            enc.add_node(make_node(
                f"n{i}", cpu="8", mem="16Gi",
                labels={ZONE_KEY: f"z-{i % 2}"},
            ))
        enc.add_spread_selector("default", {"app": "dep-0"})
    pods = _mixed_pods()
    for p in pods:
        encs[0].add_pod(p)
    encs[1].add_pods(pods)
    encoder_state_equal(encs[0], encs[1])
    # the interner vocabularies (and therefore every id-bearing tensor,
    # not just the ones compared above) assigned ids in the same order
    assert len(encs[0].interner) == len(encs[1].interner)
    assert encs[0].interner.lookup("novel-sel-val") == \
        encs[1].interner.lookup("novel-sel-val")


@pytest.mark.parametrize("resident,freed,batch", [
    (0, 0, 70),     # fresh slots only, the arena doubles at 64
    (10, 4, 3),     # freed slots only
    (60, 5, 12),    # freed slots first, then fresh ones past the cap
])
def test_add_pods_arena_slots_follow_add_pod(resident, freed, batch):
    """Arena slots: freed ones off the free list first, then fresh ones,
    with the arena grown where add_pod would grow it."""
    encs = [SnapshotEncoder(TEST_DIMS), SnapshotEncoder(TEST_DIMS)]
    old = [make_pod(f"old-{i}", cpu="10m", mem="16Mi", node_name="n0")
           for i in range(resident)]
    new = [make_pod(f"new-{i}", cpu="10m", mem="16Mi", node_name="n0",
                    labels={"app": "new"}) for i in range(batch)]
    for enc in encs:
        enc.add_node(make_node("n0", cpu="64", mem="256Gi", pods=500))
        for p in old:
            enc.add_pod(p)
        for p in old[:freed]:
            enc.remove_pod(p)
    for p in new:
        encs[0].add_pod(p)
    encs[1].add_pods(new)
    assert encs[0]._cap_m == encs[1]._cap_m
    assert encs[0]._free_m == encs[1]._free_m
    encoder_state_equal(encs[0], encs[1])


def test_add_pods_duplicate_keys_in_one_batch():
    """Degenerate but legal: the same pod key twice in one batch.  The
    per-pod loop replaces the earlier record; the batched path must not
    leak a ghost arena slot double-charging the node."""
    encs = [SnapshotEncoder(TEST_DIMS), SnapshotEncoder(TEST_DIMS)]
    for enc in encs:
        enc.add_node(make_node("n0", cpu="8", mem="16Gi"))
    dup_a = make_pod("dup", cpu="100m", mem="64Mi", node_name="n0")
    dup_b = make_pod("dup", cpu="300m", mem="256Mi", node_name="n0")
    other = make_pod("other", cpu="50m", mem="32Mi", node_name="n0")
    for p in (dup_a, other, dup_b):
        encs[0].add_pod(p)
    encs[1].add_pods([dup_a, other, dup_b])
    encoder_state_equal(encs[0], encs[1])
    row = encs[1].node_rows["n0"]
    assert encs[1].a_requested[row, 0] == 350.0  # 300 + 50, not 450


def test_add_pods_replaces_existing_records():
    encs = [SnapshotEncoder(TEST_DIMS), SnapshotEncoder(TEST_DIMS)]
    for enc in encs:
        enc.add_node(make_node("n0", cpu="8", mem="16Gi"))
        enc.add_pod(make_pod("dup", cpu="100m", mem="64Mi", node_name="n0"))
    updated = make_pod("dup", cpu="300m", mem="256Mi", node_name="n0")
    encs[0].add_pod(updated)
    encs[1].add_pods([updated])
    encoder_state_equal(encs[0], encs[1])


# ----------------------------------------------------- commit by pod class


_ANTI_AFFINITY = {"podAntiAffinity": {
    "requiredDuringSchedulingIgnoredDuringExecution": [{
        "labelSelector": {"matchLabels": {"cls-sel-key": "cls-sel-val"}},
        "topologyKey": "cls.example.com/topo",
    }]}}


def _controller_pods(prefix, n, ns="default", app="web", cpu="100m",
                     **kw):
    # controller-stamped: identical content but the name, so one class
    return [
        make_pod(f"{prefix}-{i}", namespace=ns, cpu=cpu, mem="128Mi",
                 labels={"app": app, "tier": f"{app}-tier"}, **kw)
        for i in range(n)
    ]


def _class_batch(case):
    """A pending batch for one case, with the pods interleaved so that a
    class's pods are not contiguous."""
    a = _controller_pods("a", 4)
    b = _controller_pods("b", 4, ns="other-ns", app="novel-app-b",
                         cpu="250m")
    extra = {
        "two_controllers": [],
        "host_port": [make_pod(
            "hp", cpu="100m", mem="64Mi", labels={"app": "hp"},
            ports=[{"hostPort": 8080, "protocol": "TCP"}])],
        "volume": [make_pod(
            "vol", cpu="100m", mem="64Mi", labels={"app": "vol"},
            volumes=[{"gcePersistentDisk": {"pdName": "pd-cls"}}])],
        "affinity": [make_pod(
            "aff", cpu="100m", mem="64Mi", labels={"app": "aff-novel"},
            affinity=_ANTI_AFFINITY)],
        "init_container": _controller_pods(
            "init", 3, app="init", init_requests=[{"cpu": "500m"}]),
    }
    if case == "mixed":
        rest = [p for k in ("host_port", "volume", "affinity",
                            "init_container") for p in extra[k]]
    else:
        rest = extra.get(case, [])
    pods = []
    for i in range(max(len(a), len(b), len(rest))):
        for group in (a, b, rest):
            if i < len(group):
                pods.append(group[i])
    return pods


def _class_encoders(live_term_groups):
    encs = [SnapshotEncoder(TEST_DIMS), SnapshotEncoder(TEST_DIMS)]
    for enc in encs:
        for i in range(4):
            enc.add_node(make_node(
                f"n{i}", cpu="16", mem="32Gi",
                labels={ZONE_KEY: f"z-{i % 2}"},
            ))
        enc.add_spread_selector("default", {"app": "web"})
        if live_term_groups:
            # a resident pod with affinity terms: every pending pod's
            # static key is None, so every pod commits per pod
            enc.add_pod(make_pod(
                "resident", cpu="100m", mem="64Mi", labels={"app": "r"},
                node_name="n0", affinity=_ANTI_AFFINITY,
            ))
    return encs


@pytest.mark.parametrize("case", [
    "two_controllers", "host_port", "volume", "affinity", "init_container",
    "term_groups", "mixed",
])
@pytest.mark.parametrize("given_rows", [False, True])
def test_class_add_pods_matches_add_pod_loop(case, given_rows):
    """Class-aware add_pods (the classes encode_pods handed out, and the
    node rows where the caller passes them) leaves arena state, interner
    ids, generation and dirty rows byte-identical to the add_pod loop.
    The last pod names a node the arena does not hold: no row."""
    encs = _class_encoders(live_term_groups=case == "term_groups")
    pending = _class_batch("mixed" if case == "term_groups" else case)
    classes = np.full(len(pending), -1, np.int32)
    encs[0].encode_pods(pending)
    encs[1].encode_pods(pending, classes)
    nodes = [f"n{i % 4}" for i in range(len(pending) - 1)] + ["n-gone"]
    assumed = [p.assumed_on(n) for p, n in zip(pending, nodes)]
    rows = None
    if given_rows:
        rows = np.array([encs[1].node_rows.get(n, -1) for n in nodes],
                        np.int32)
        assert rows[-1] == -1 and (rows[:-1] >= 0).all()
    for enc in encs:
        enc.snapshot()
        enc.take_dirty_rows()
    for p in assumed:
        encs[0].add_pod(p)
    n_classes = encs[1].add_pods(assumed, classes, rows)

    keyed = classes >= 0
    if case == "term_groups":
        assert not keyed.any() and n_classes == 0
    else:
        assert n_classes == len(set(classes[keyed].tolist())) >= 2
        for p, c in zip(pending, classes):
            per_pod = bool(p.spec.affinity or p.spec.volumes)
            assert (c < 0) == per_pod, p.name
    assert encs[0]._dirty_pod_rows == encs[1]._dirty_pod_rows
    encoder_state_equal(encs[0], encs[1])
    assert encs[0].interner._strs == encs[1].interner._strs
    assert list(encs[0]._label_cols) == list(encs[1]._label_cols)
    for kid, col in encs[0]._label_cols.items():
        np.testing.assert_array_equal(col, encs[1]._label_cols[kid])
    assert encs[0].ns_rows == encs[1].ns_rows
    np.testing.assert_array_equal(encs[0].a_ns_usage, encs[1].a_ns_usage)
    for key, r0 in encs[0].pods.items():
        r1 = encs[1].pods[key]
        assert (r0.labels, r0.ports, r0.disk_vols, r0.group_refs) == \
            (r1.labels, r1.ports, r1.disk_vols, r1.group_refs), key
        np.testing.assert_array_equal(r0.vol_counts, r1.vol_counts)


def test_class_add_pods_ids_follow_first_pod_of_class():
    """Novel strings are interned when their class is first seen in batch
    order: a per-pod pod between two classes gets its ids in between."""
    encs = _class_encoders(live_term_groups=False)
    pending = [
        *_controller_pods("x", 2, app="first-novel"),
        make_pod("aff", cpu="100m", mem="64Mi", labels={"app": "mid-novel"},
                 affinity=_ANTI_AFFINITY),
        *_controller_pods("y", 2, app="last-novel"),
    ]
    classes = np.full(len(pending), -1, np.int32)
    encs[1].encode_pods(pending, classes)
    encs[0].encode_pods(pending)
    assert classes.tolist() == [0, 0, -1, 1, 1]
    assumed = [p.assumed_on("n1") for p in pending]
    for p in assumed:
        encs[0].add_pod(p)
    encs[1].add_pods(assumed, classes)
    ids = [encs[1].interner.lookup(s) for s in
           ("first-novel", "mid-novel", "last-novel")]
    assert ids == sorted(ids)
    assert encs[0].interner._strs == encs[1].interner._strs
    encoder_state_equal(encs[0], encs[1])


def test_class_add_pods_volume_column_regrow_mid_batch():
    """A CSI pod with a driver no column exists for, between two pods of
    one class, widens the volume-count axis mid-batch: the class's later
    pods must get the widened record vectors add_pod would give them."""
    from kubernetes_tpu.api.storage import (
        PersistentVolume, PersistentVolumeClaim,
    )

    encs = _class_encoders(live_term_groups=False)
    for enc in encs:
        enc.add_pv(PersistentVolume.from_dict({
            "metadata": {"name": "pv-fresh"},
            "spec": {"capacity": {"storage": "1Gi"},
                     "accessModes": ["ReadWriteOnce"],
                     "csi": {"driver": "fresh-driver",
                             "volumeHandle": "h"}},
        }))
        enc.add_pvc(PersistentVolumeClaim.from_dict({
            "metadata": {"name": "c-fresh", "namespace": "default"},
            "spec": {"volumeName": "pv-fresh"},
        }))
    a = _controller_pods("a", 4)
    csi = make_pod("csi", cpu="100m", mem="64Mi", volumes=[
        {"persistentVolumeClaim": {"claimName": "c-fresh"}}])
    pending = a[:2] + [csi] + a[2:]
    # classes from another encoder's encode, so the column first appears
    # inside add_pods here
    classes = np.full(len(pending), -1, np.int32)
    SnapshotEncoder(TEST_DIMS).encode_pods(pending, classes)
    assert classes.tolist() == [0, 0, -1, 0, 0]
    vt0 = encs[1].dims.VT
    assumed = [p.assumed_on(f"n{i % 4}") for i, p in enumerate(pending)]
    for p in assumed:
        encs[0].add_pod(p)
    encs[1].add_pods(assumed, classes)
    assert encs[1].dims.VT > vt0
    encoder_state_equal(encs[0], encs[1])
    assert encs[0].interner._strs == encs[1].interner._strs
    for key, r0 in encs[0].pods.items():
        np.testing.assert_array_equal(r0.vol_counts,
                                      encs[1].pods[key].vol_counts)
    np.testing.assert_array_equal(encs[0].a_volcnt, encs[1].a_volcnt)


@pytest.mark.parametrize("kind", ["plain", "affinity", "volume", "labels"])
def test_assumed_on_matches_copy(kind):
    """Pod.assumed_on is copy.copy of the pod and of its spec with
    node_name set: equal field for field, sharing what copy.copy shares,
    and never the PodSpec."""
    import copy

    kw = {
        "plain": {},
        "affinity": {"affinity": _ANTI_AFFINITY},
        "volume": {"volumes": [{"gcePersistentDisk": {"pdName": "pd"}}]},
        "labels": {"labels": {"app": "a", "tier": "t"},
                   "node_selector": {ZONE_KEY: "z-0"},
                   "ports": [{"hostPort": 80, "protocol": "TCP"}]},
    }[kind]
    pod = make_pod("orig", cpu="100m", mem="64Mi", **kw)
    got = pod.assumed_on("n7")
    spec = copy.copy(pod.spec)
    spec.node_name = "n7"
    want = copy.copy(pod)
    want.spec = spec
    assert type(got) is type(want) and type(got.spec) is type(want.spec)
    assert got == want
    assert got.spec is not pod.spec and got.spec.node_name == "n7"
    assert pod.spec.node_name == ""
    for f in dataclasses.fields(pod):
        if f.name != "spec":
            assert getattr(got, f.name) is getattr(pod, f.name), f.name
    for f in dataclasses.fields(pod.spec):
        if f.name != "node_name":
            assert getattr(got.spec, f.name) is getattr(pod.spec, f.name)
    assert vars(got.spec) == vars(want.spec) and vars(got) == vars(want)


def test_assume_winners_rows_of_a_removed_node():
    """The batched commit hands add_pods the engine's rows; a row whose
    node left after dispatch charges no row, as add_pod's lookup of the
    assumed pod's node name ("") does."""
    import types

    sched = _mk_scheduler(batched=True, with_extender=False)
    enc = sched.cache.encoder
    ref = SnapshotEncoder(TEST_DIMS)
    for i in range(6):
        ref.add_node(make_node(f"n{i}", cpu="4", mem="8Gi", pods=20,
                               labels={ZONE_KEY: f"z-{i % 2}"}))
    pods = _controller_pods("w", 5)
    classes = np.full(len(pods), -1, np.int32)
    enc.encode_pods(pods, classes)
    ref.encode_pods(pods)
    hosts = np.array([enc.node_rows[n] for n in
                      ("n0", "n2", "n1", "n2", "n0")] + [-1] * 3, np.int32)
    for e in (enc, ref):
        e.remove_node("n2")
    winners = []
    for i, pod in enumerate(pods):
        name = enc.row_name(int(hosts[i]))
        winners.append((i, pod, pod.assumed_on(name), name))
    assert [w[3] for w in winners] == ["n0", "", "n1", "", "n0"]
    commit = types.SimpleNamespace(attrs={})
    sched._assume_winners(types.SimpleNamespace(classes=classes), winners,
                          hosts, commit)
    for _, _, assumed, _ in winners:
        ref.add_pod(assumed)
    assert commit.attrs["commit_classes"] == 1
    assert [enc.pods[("default", f"w-{i}")].node_row for i in range(5)] \
        == [ref.pods[("default", f"w-{i}")].node_row for i in range(5)]
    encoder_state_equal(ref, enc)


@pytest.mark.parametrize("case,want", [
    ("plain", (6, 0, 3)),
    ("affinity", (5, 1, 2)),
    ("volume", (5, 1, 2)),
    ("per_pod_commit", (0, 6, 0)),
])
def test_commit_path_counter(case, want):
    """scheduler_commit_pods_total{path} counts each committed winner
    once, by how its encoder inputs were derived; the commit span carries
    commit_classes, and commit_state + commit_tail split commit."""
    from kubernetes_tpu.utils import metrics as m

    sched = _mk_scheduler(batched=case != "per_pod_commit",
                          with_extender=False)
    pods = _controller_pods("c", 3) + _controller_pods(
        "d", 2, ns="other-ns", app="d")
    pods.append({
        "plain": make_pod("lone", cpu="100m", mem="64Mi", labels={"app": "c"}),
        "per_pod_commit": make_pod("lone", cpu="100m", mem="64Mi"),
        "affinity": make_pod("aff", cpu="100m", mem="64Mi",
                             labels={"app": "aff"}, affinity=_ANTI_AFFINITY),
        "volume": make_pod("vol", cpu="100m", mem="64Mi",
                           volumes=[{"gcePersistentDisk": {"pdName": "pd"}}]),
    }[case])
    before = [m.COMMIT_PODS.value(path=p) for p in ("class", "per_pod")]
    results = sched.schedule_cycle(pods)
    assert all(r.node for r in results), results
    got = [m.COMMIT_PODS.value(path=p) - b
           for p, b in zip(("class", "per_pod"), before)]
    assert got == list(want[:2])
    ph = sched.phase_seconds
    if case != "per_pod_commit":
        commit = sched.flight_recorder.spans()[-1].find("commit")
        assert commit.attrs["commit_classes"] == want[2]
    assert ph["commit_state"] > 0 and ph["commit_tail"] > 0
    assert ph["commit_state"] + ph["commit_tail"] == pytest.approx(
        ph["commit"], rel=1e-9)


# ------------------------------------------------------- commit equivalence


class _FailingExtender:
    """Minimal extender double: non-ignorable filter error for one pod."""

    class _Cfg:
        filter_verb = "filter"
        prioritize_verb = ""
        bind_verb = ""

    config = _Cfg()
    is_ignorable = False
    is_binder = False
    supports_preemption = False

    def __init__(self, fail_name):
        self.fail_name = fail_name

    def is_interested(self, pod):
        return pod.name == self.fail_name

    def filter(self, pod, names):
        raise ExtenderError("extender down")


def _mk_scheduler(batched, pipeline=False, with_extender=True):
    cache = SchedulerCache(SnapshotEncoder(TEST_DIMS))
    for i in range(6):
        cache.add_node(make_node(
            f"n{i}", cpu="4", mem="8Gi", pods=20,
            labels={ZONE_KEY: f"z-{i % 2}"},
        ))
    queue = PriorityQueue()
    binder = lambda pod, node: pod.name != "bind-fail"  # noqa: E731
    sched = Scheduler(
        cache=cache,
        queue=queue,
        binder=binder,
        config=SchedulerConfig(
            batch_size=16, engine="sequential", disable_preemption=True,
            batched_commit=batched, pipeline_commit=pipeline,
        ),
        extenders=[_FailingExtender("ext-fail")] if with_extender else None,
    )
    return sched


def _commit_batch_pods():
    pods = [make_pod(f"ok-{i}", cpu="200m", mem="256Mi",
                     labels={"app": "a"}) for i in range(6)]
    # FitError: nothing can hold 64 cpus
    pods.append(make_pod("fit-fail", cpu="64", mem="128Gi"))
    # non-ignorable extender error
    pods.append(make_pod("ext-fail", cpu="100m", mem="64Mi"))
    # assumed then rejected by the binder (optimistic rollback)
    pods.append(make_pod("bind-fail", cpu="100m", mem="64Mi"))
    pods.append(make_pod("ok-last", cpu="100m", mem="64Mi"))
    return pods


def _event_tuples(recorder):
    return [
        (e.kind, e.namespace, e.name, e.type, e.reason, e.message, e.count)
        for e in recorder.events()
    ]


def _queue_state(q):
    return (
        sorted(q._unschedulable),
        sorted(q._active_entry),
        sorted(q._backoff_entry),
    )


def test_batched_commit_state_equivalent_to_perpod_loop():
    """Mixed success / FitError / extender-error / bind-failure batch: the
    batched commit path must leave byte-identical encoder state, identical
    events (order included), identical results and queue state."""
    s_batched = _mk_scheduler(batched=True)
    s_perpod = _mk_scheduler(batched=False)
    pods = _commit_batch_pods()
    r1 = s_batched.schedule_cycle(list(pods))
    r2 = s_perpod.schedule_cycle(list(pods))

    assert [(r.pod.name, r.node) for r in r1] == [
        (r.pod.name, r.node) for r in r2
    ]
    # the batch really was mixed
    by_name = {r.pod.name: r.node for r in r1}
    assert by_name["fit-fail"] is None
    assert by_name["ext-fail"] is None
    assert by_name["bind-fail"] is None
    assert by_name["ok-0"] is not None and by_name["ok-last"] is not None

    encoder_state_equal(
        s_batched.cache.encoder, s_perpod.cache.encoder, same_slots=False
    )
    assert set(s_batched.cache._assumed) == set(s_perpod.cache._assumed)
    assert _event_tuples(s_batched.recorder) == _event_tuples(s_perpod.recorder)
    assert _queue_state(s_batched.queue) == _queue_state(s_perpod.queue)


def test_pipelined_commit_matches_sync_run():
    """Double-buffered cycles must converge to the same cache state and
    placement set as strictly synchronous cycles."""
    s_pipe = _mk_scheduler(batched=True, pipeline=True, with_extender=False)
    s_sync = _mk_scheduler(batched=True, pipeline=False, with_extender=False)
    waves = [
        [make_pod(f"w{w}-p{i}", cpu="150m", mem="128Mi",
                  labels={"app": f"dep-{w}"})
         for i in range(5)]
        for w in range(4)
    ]
    for s in (s_pipe, s_sync):
        placed = 0
        for wave in waves:
            for p in wave:
                s.queue.add(p)
            placed += s.run_once(timeout=0.05)
        placed += s.flush_pipeline()
        assert placed == 20
    assert s_pipe._in_flight is None
    encoder_state_equal(s_pipe.cache.encoder, s_sync.cache.encoder)
    got_pipe = {(r.pod.name, r.node) for r in s_pipe.results}
    got_sync = {(r.pod.name, r.node) for r in s_sync.results}
    assert got_pipe == got_sync


def test_batched_commit_e2e_survives_bind_echo_delete():
    """A bind's informer echo deletes the bound pod from the queue —
    consuming its enqueue stamp.  The batched tail must take stamps BEFORE
    the bind fan-out, or the e2e histogram silently loses the queue wait.
    The binder here deletes synchronously: the worst-case echo timing."""
    import time

    from kubernetes_tpu.utils import metrics as m

    cache = SchedulerCache(SnapshotEncoder(TEST_DIMS))
    cache.add_node(make_node("n0", cpu="4", mem="8Gi"))
    queue = PriorityQueue()
    sched = Scheduler(
        cache=cache, queue=queue,
        binder=lambda pod, node: queue.delete(pod) or True,
        config=SchedulerConfig(
            batch_size=4, engine="sequential", disable_preemption=True,
        ),
    )
    fresh = m.Histogram("test_e2e_batched", "")
    orig = m.E2E_LATENCY
    m.E2E_LATENCY = fresh
    try:
        queue.add(make_pod("echoed", cpu="100m", mem="64Mi"))
        time.sleep(0.03)
        assert sched.run_once(timeout=0.2) == 1
    finally:
        m.E2E_LATENCY = orig
    assert fresh.total == 1
    assert fresh.sum >= 0.03  # queue wait included despite the echo delete


# ------------------------------------------------------ incremental encode


def test_incremental_snapshot_matches_full_reencode():
    """Dirty-row re-encode == full re-encode across adds/deletes/updates of
    both nodes and pods, with snapshots interleaved so the cow path (not
    the full-rebuild path) is what's being exercised."""
    enc = SnapshotEncoder(TEST_DIMS)
    for i in range(6):
        enc.add_node(make_node(
            f"n{i}", cpu="8", mem="16Gi",
            labels={ZONE_KEY: f"z-{i % 3}"},
        ))
    enc.add_spread_selector("default", {"app": "a"})

    def check(msg):
        inc = enc.snapshot()
        full = enc.snapshot(full=True)
        snapshots_equal(inc, full, msg=msg + ": ")

    check("initial")
    # pod adds (single + batched)
    enc.add_pod(make_pod("p0", cpu="100m", mem="64Mi",
                         labels={"app": "a"}, node_name="n0"))
    check("pod add")
    enc.add_pods([
        make_pod(f"p{i}", cpu="200m", mem="128Mi", labels={"app": "a"},
                 node_name=f"n{i % 3}",
                 ports=[{"hostPort": 9000 + i, "protocol": "TCP"}])
        for i in range(1, 5)
    ])
    check("batched pod add")
    # node label update (topology move)
    enc.update_node(make_node("n1", cpu="8", mem="16Gi",
                              labels={ZONE_KEY: "z-9"}))
    check("node update")
    # pod delete
    enc.remove_pod(make_pod("p2", node_name="n2"))
    check("pod delete")
    # node delete (detaches resident pods) + row-reusing re-add
    enc.remove_node("n0")
    check("node delete")
    enc.add_node(make_node("n6", cpu="2", mem="4Gi",
                           labels={ZONE_KEY: "z-1"}))
    check("row reuse")
    # unchanged state: incremental returns shared (identity) leaves
    s1 = enc.snapshot()
    s2 = enc.snapshot()
    assert s2.label_keys is s1.label_keys
    assert s2.requested is s1.requested


def test_incremental_snapshot_unchanged_fields_share_identity():
    enc = SnapshotEncoder(TEST_DIMS)
    for i in range(4):
        enc.add_node(make_node(f"n{i}", cpu="4", mem="8Gi"))
    s1 = enc.snapshot()
    enc.add_pod(make_pod("p", cpu="100m", mem="64Mi", node_name="n1"))
    s2 = enc.snapshot()
    # pod commits touch only the aggregate fields
    assert s2.label_keys is s1.label_keys
    assert s2.taint_key is s1.taint_key
    assert s2.topo_pairs is s1.topo_pairs
    assert s2.requested is not s1.requested
    row = enc.node_rows["n1"]
    assert s2.requested[row, 0] == 100.0
    assert s1.requested[row, 0] == 0.0  # old snapshot untouched (cow)


def test_device_snapshot_cache_dirty_row_scatter():
    """update(cluster, dirty_rows=...) must leave device contents equal to
    a fresh full upload through adds/commits/updates/removes."""
    enc = SnapshotEncoder(TEST_DIMS)
    for i in range(8):
        enc.add_node(make_node(f"n{i}", cpu="4", mem="8Gi",
                               labels={ZONE_KEY: f"z-{i % 2}"}))
    cache = DeviceSnapshotCache()

    def sync_and_check(msg):
        snap = enc.snapshot()
        dirty = enc.take_dirty_rows()
        dev = cache.update(snap, dirty_rows=dirty)
        full = enc.snapshot(full=True)
        for f in dataclasses.fields(full):
            np.testing.assert_array_equal(
                np.asarray(getattr(dev, f.name)),
                np.asarray(getattr(full, f.name)),
                err_msg=f"{msg}: field {f.name}",
            )

    sync_and_check("initial (full upload)")
    # a small commit: exactly the scatter-eligible shape (1 row of 8)
    enc.add_pod(make_pod("p0", cpu="500m", mem="512Mi", node_name="n3"))
    sync_and_check("single-row commit")
    enc.add_pods([
        make_pod(f"q{i}", cpu="100m", mem="64Mi", node_name=f"n{i}")
        for i in range(2)
    ])
    sync_and_check("two-row batched commit")
    enc.update_node(make_node("n5", cpu="2", mem="4Gi",
                              labels={ZONE_KEY: "z-7"}))
    sync_and_check("node update")
    enc.remove_pod(make_pod("q0", node_name="n0"))
    sync_and_check("pod remove")
    enc.remove_node("n7")
    sync_and_check("node remove")


def test_take_dirty_rows_accumulates_across_snapshots():
    """A snapshot taken WITHOUT a device update (the gang launch path) must
    not lose its rows for the next update's scatter."""
    enc = SnapshotEncoder(TEST_DIMS)
    for i in range(8):
        enc.add_node(make_node(f"n{i}", cpu="4", mem="8Gi"))
    cache = DeviceSnapshotCache()
    cache.update(enc.snapshot(), dirty_rows=enc.take_dirty_rows())
    enc.add_pod(make_pod("a", cpu="100m", mem="64Mi", node_name="n1"))
    enc.snapshot()          # consumed by someone else; no take, no update
    enc.add_pod(make_pod("b", cpu="100m", mem="64Mi", node_name="n2"))
    snap = enc.snapshot()
    dirty = enc.take_dirty_rows()
    # rows from BOTH snapshots must be in the take
    rows = set(np.asarray(dirty).tolist())
    assert {enc.node_rows["n1"], enc.node_rows["n2"]} <= rows
    dev = cache.update(snap, dirty_rows=dirty)
    full = enc.snapshot(full=True)
    np.testing.assert_array_equal(
        np.asarray(dev.requested), np.asarray(full.requested)
    )


def test_take_dirty_rows_full_rebuild_returns_none():
    enc = SnapshotEncoder(TEST_DIMS)
    enc.add_node(make_node("n0", cpu="4", mem="8Gi"))
    enc.snapshot()
    enc.take_dirty_rows()
    # force an arena regrow (mark-all) by exceeding node capacity
    for i in range(1, 3 * TEST_DIMS.N):
        enc.add_node(make_node(f"n{i}", cpu="4", mem="8Gi"))
    enc.snapshot()
    assert enc.take_dirty_rows() is None
