"""Sequential-commit batch scheduling: B pods in ONE device launch.

The reference schedules strictly one pod per cycle (scheduler.go:438
scheduleOne); at 5k nodes that caps throughput at the per-cycle host latency.
Here the host loop drains B pods from the queue, encodes them once, and a
single jitted program places them *sequentially* under `lax.scan`: each step
filters+scores pod i against the *current* on-device cluster state, picks a
host (argmax + round-robin tie-break), and commits the placement by updating
the dynamic state columns — so pod i+1 sees pod i's resources, ports, and
spreading counts exactly as if the reference had scheduled them one by one.

Dynamic state inside the scan (everything else is precomputed static):
  requested[N, R], nonzero[N, 2]        — PodFitsResources + resource scores
  spread_extra[B, N]                    — SelectorSpreadPriority in-batch
                                          increments (AND-match cross matrix;
                                          [G + 1, N] by spread group for
                                          spread-lean batches)
  port_used[N, PV]                      — PodFitsHostPorts within the batch,
                                          over a batch-local port vocabulary
                                          with a precomputed conflict matrix
                                          (wildcard-IP semantics preserved)
  dom_cnt[G, KQ, N], forb_node[G, N],   — in-batch inter-pod affinity
  pref_node[G, N]                         state per node by label group
                                          and topology key
                                          (predicateMetadata.AddPod
                                          analog) when aff_state is given
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.codec import transfer
from kubernetes_tpu.codec.schema import (
    ClusterTensors,
    FilterConfig,
    PAD,
    PodBatch,
    _pow2,
)
from kubernetes_tpu.ops.predicates import filter_batch
from kubernetes_tpu.ops.priorities import (
    MAX_PRIORITY,
    balanced_allocation_score,
    inter_pod_affinity_score,
    image_locality,
    least_requested_score,
    most_requested_score,
    node_affinity,
    node_capacity2,
    node_label_priority,
    node_prefer_avoid_pods,
    pod_group_onehot,
    pod_spread_match,
    resource_limits,
    spread_counts,
    spread_score_from_counts,
    taint_toleration,
    zone_layout,
)
from kubernetes_tpu.ops.select import (
    TopKQuality,
    limit_feasible,
    num_feasible_nodes_device,
    select_host,
    select_topk,
)
from kubernetes_tpu.codec.schema import (
    DEFAULT_PRIORITY_WEIGHTS,
    NUM_REASONS,
    PRIO_INDEX,
    REASON_EXTENDER,
    ScoreConfig,
)


class Attribution(NamedTuple):
    """Per-pod decision attribution, emitted only by the engine's
    attribution variant (make_sequential_scheduler(attribution=True)) so
    the default executable is byte-identical to before.

    reason_counts[b, k]: how many live nodes rejected pod b with reason k
    as the FIRST failure in PREDICATE_ORDER (the reference podFitsOnNode
    short-circuit attribution; the aggregate GeneralPredicates row never
    attributes — its constituents do); the last column (REASON_EXTENDER)
    counts nodes every predicate passed but the extra mask vetoed
    (extender filter / tensor Filter plugin / nominated-pod block).
    Evaluated at the pod's OWN scan step, so in-batch commits (resources,
    ports, affinity) are reflected exactly as selectHost saw them.

    top_nodes/top_scores: the k best-scoring feasible node rows for the
    pod (-1 where fewer than k are feasible); top_components: the
    weighted per-plugin score addends of those rows on the
    schema.SCORE_COMPONENTS axis (PRIORITY_ORDER + "Extra")."""

    reason_counts: Any   # i32[B, NUM_REASONS]
    top_nodes: Any       # i32[B, TK]
    top_scores: Any      # f32[B, TK]
    top_components: Any  # f32[B, TK, NUM_SCORE_COMPONENTS]


@dataclass
class BatchPortState:
    """Batch-local host-port vocabulary (see module docstring)."""

    pod_ports: Any      # bool[B, PV]  ports requested by each pod
    conflict: Any       # bool[PV, PV] do two batch ports conflict


jax.tree_util.register_dataclass(
    BatchPortState,
    data_fields=["pod_ports", "conflict"],
    meta_fields=[],
)


@dataclass
class NominatedState:
    """Nominated pods (preemptors awaiting their victims' graceful exit).

    The two-pass fit evaluation (ref generic_scheduler.go:598-664
    podFitsOnNode) adds nominated pods with priority >= the scheduled pod's
    to their nominated node before filtering, so a preempted-for claim is
    visible to later cycles; the pod must ALSO fit without them (pass two).
    Resource claims live here (they interact with the scan's running
    state); port claims and anti-affinity contributions are host-computed
    per cycle into the extra_mask (encode_nominated_block)."""

    node: Any   # i32[K] nominated node row (-1 = unused slot)
    prio: Any   # i32[K]
    req: Any    # f32[K, R]


jax.tree_util.register_dataclass(
    NominatedState, data_fields=["node", "prio", "req"], meta_fields=[]
)


@dataclass
class BatchAffinityState:
    """In-batch inter-pod-affinity cross-match tensors.

    The per-pod pair tensors in PodBatch are computed against the PRE-batch
    snapshot; these matrices let the speculative engine's rounds update
    affinity state as co-batched pods land (the tensorization of
    predicateMetadata's incremental AddPod, ref
    algorithm/predicates/metadata.go:64-94), so a later pod's
    MatchInterPodAffinity sees an earlier one's placement.  The sequential
    scan reads the factored form (LeanBatchAffinity) instead.

    Orientation: step axis first.  aff_match[j, i, t] = "batch pod j matches
    pod i's required-affinity term t" (namespaces + selector); anti_match
    likewise for pod i's anti terms; anti_own[j, t, i] = "pod i matches pod
    j's anti term t" (the committed pod's anti-affinity forbids later
    matching pods from its topology domains)."""

    aff_match: Any   # bool[B, B, PT]
    anti_match: Any  # bool[B, B, AT]
    anti_own: Any    # bool[B, AT, B]
    aff_own: Any     # bool[B, PT, B]  [j, t, i]: i matches j's aff term t
                     # (hard-affinity symmetric score, encoder K_AFF_REQ)
    # preferred (soft) terms — both directions of the IPA score:
    pref_topo_key: Any  # i32[B, PP]  topology key id of each preferred term
    pref_weight: Any    # f32[B, PP]  signed weight (+affinity / -anti)
    pref_match: Any     # bool[B, B, PP]  [j, i, t]: j matches i's pref term t
    pref_own: Any       # bool[B, PP, B]  [j, t, i]: i matches j's pref term t


jax.tree_util.register_dataclass(
    BatchAffinityState,
    data_fields=["aff_match", "anti_match", "anti_own", "aff_own",
                 "pref_topo_key", "pref_weight", "pref_match", "pref_own"],
    meta_fields=[],
)


class LeanBatchAffinity(NamedTuple):
    """Factored form of BatchAffinityState — what actually crosses the
    host->device link.

    Controller-stamped batches repeat a handful of (namespace, labels)
    shapes, so every dense [B, ., B] cross-match tensor is low-rank:
    match[owner i, term t, candidate j] = gm[i, t, group(j)].  Shipping the
    factors (G = distinct label groups, padded to a power of two; the last
    pad column is all-False and absorbs padding pods) is ~KBs where the
    dense tensors are ~40MB at batch 2048 — which matters because a
    remote-attached accelerator bills per byte moved.  densify() rebuilds
    the dense tensors ON DEVICE with one gather per family."""

    gid: Any            # i32[B]      candidate j -> label-group id
    aff_gm: Any         # bool[B, PT, G]  owner i's aff term t matches group g
    anti_gm: Any        # bool[B, AT, G]
    pref_gm: Any        # bool[B, PP, G]
    pref_topo_key: Any  # i32[B, PP]
    pref_weight: Any    # f32[B, PP]
    # i32[N, KP]: each node row's topology pair under each registered key
    # (PAD = none), the snapshot's topo_pairs as pair ids, and i32[KP] the
    # key of each column (PAD = none): the sequential scan keeps its
    # in-batch affinity state per node with them
    node_pairs: Any
    node_pair_keys: Any


def densify_batch_affinity(lean: LeanBatchAffinity) -> BatchAffinityState:
    """Rebuild the dense cross-match tensors from the factored form —
    called INSIDE jit so only the factors cross the link."""
    gid = lean.gid
    aff_own = jnp.take(lean.aff_gm, gid, axis=2)    # [owner i, t, cand j]
    anti_own = jnp.take(lean.anti_gm, gid, axis=2)
    pref_own = jnp.take(lean.pref_gm, gid, axis=2)
    return BatchAffinityState(
        aff_match=jnp.transpose(aff_own, (2, 0, 1)),   # [step j, i, t]
        anti_match=jnp.transpose(anti_own, (2, 0, 1)),
        anti_own=anti_own,
        aff_own=aff_own,
        pref_topo_key=lean.pref_topo_key,
        pref_weight=lean.pref_weight,
        pref_match=jnp.transpose(pref_own, (2, 0, 1)),
        pref_own=pref_own,
    )


_X = jax.lax.Precision.HIGHEST  # exact f32 products: these carry counts


def _in_batch(gm, carry, q):
    """f32[T, N]: for each term t of a pod (its group-match row gm[t] over
    G label groups), the carry [G, KQ, N] at the term's key column q[t],
    summed over the groups the term matches."""
    return jnp.einsum("tg,gtn->tn", gm.astype(jnp.float32),
                      jnp.take(carry, q, axis=1), precision=_X)


def _owner(gm, dom, q):
    """f32[G, N]: a committed pod's terms (group-match rows gm [T, G]),
    each over its domain dom[q[t]] ([KQ, N]), by the groups they match."""
    return jnp.einsum("tg,tn->gn", gm.astype(jnp.float32),
                      jnp.take(dom, q, axis=0), precision=_X)


def batch_has_pod_affinity(pods: Sequence) -> bool:
    """True if any pod carries ANY pod-(anti-)affinity terms (required or
    preferred) — the signal to run the affinity-aware scan variant so
    co-batched pods see each other in both the filter and the IPA score."""
    for p in pods:
        a = p.spec.affinity
        if a is not None and (
            a.pod_affinity is not None or a.pod_anti_affinity is not None
        ):
            return True
    return False


def encode_batch_affinity(encoder, pods: Sequence) -> LeanBatchAffinity:
    """Host-side precompute of the in-batch cross-match FACTORS (the
    engines densify on device — see LeanBatchAffinity); term slot order
    matches SnapshotEncoder._encode_pod_affinity (required[:PT] /
    required[:AT] in spec order)."""
    from kubernetes_tpu.api import labels as klabels

    d = encoder.dims
    B = encoder.batch_pad(len(pods))
    nb = len(pods)

    # Controller-stamped batches repeat a handful of (namespace, labels)
    # shapes and an equally small set of terms, so the dense owner x term x
    # candidate tensors are low-rank: group candidates by (namespace, label
    # signature), memoize each distinct (selector, namespaces) term's
    # GROUP-match vector, and ship only the factors (LeanBatchAffinity) —
    # the engines densify on device with one gather per tensor family.
    gid_of: dict = {}
    pod_gid = np.empty(max(nb, 1), np.int32)
    reps: list = []  # one (namespace, labels) representative per group
    for j, p in enumerate(pods):
        sig = (p.namespace, tuple(sorted(p.labels.items())))
        g = gid_of.get(sig)
        if g is None:
            g = gid_of[sig] = len(reps)
            reps.append((p.namespace, p.labels))
        pod_gid[j] = g
    # pad the group axis to a power of two, at least 8, so that batches
    # of one workload (a batch may straddle a few controllers' pods) share
    # one program; the LAST column stays all-False in every gm tensor and
    # absorbs batch-padding pods, so they can never match a term
    G = _pow2(len(reps) + 1, 8)
    gid = np.full(B, G - 1, np.int32)
    if nb:
        gid[:nb] = pod_gid[:nb]
    _match_memo: dict = {}

    def _term_gvec(term, owner_ns):
        """bool[G] group-match vector for one term, memoized across the
        batch by (requirements, namespaces)."""
        sel = klabels.selector_from_label_selector(term.label_selector)
        if sel is None:
            return None
        nss = term.namespaces or (owner_ns,)
        key = (tuple(sel.requirements), frozenset(nss))
        vec = _match_memo.get(key)
        if vec is None:
            vec = np.zeros(G, bool)
            vec[: len(reps)] = np.fromiter(
                ((ns in nss) and sel.matches(lbls) for ns, lbls in reps),
                bool, count=len(reps),
            )
            vec.setflags(write=False)  # rows are shared across owners
            _match_memo[key] = vec
        return vec

    A = np.zeros((B, d.PT, G), bool)   # [owner i, term t, group g]
    N = np.zeros((B, d.AT, G), bool)

    def _fill(out, terms, i, owner, slot=None):
        for t, term in enumerate(terms):
            vec = _term_gvec(term, owner.namespace)
            if vec is None:
                continue
            out[i, slot if slot is not None else t, :] = vec

    # preferred terms: owner-major lists (signed weights), then the same
    # cross-match fill as required terms
    pref_lists = []
    for pod in pods:
        terms = []
        a = pod.spec.affinity
        if a is not None:
            if a.pod_affinity is not None:
                terms += [(+float(w.weight), w.term)
                          for w in a.pod_affinity.preferred]
            if a.pod_anti_affinity is not None:
                terms += [(-float(w.weight), w.term)
                          for w in a.pod_anti_affinity.preferred]
        pref_lists.append(terms)
    PP = _pow2(max([len(t) for t in pref_lists] + [1]))
    P = np.zeros((B, PP, G), bool)       # [owner i, term t, group g]
    p_key = np.zeros((B, PP), np.int32)
    p_w = np.zeros((B, PP), np.float32)

    for i, pod in enumerate(pods):
        a = pod.spec.affinity
        if a is None:
            continue
        if a.pod_affinity is not None:
            _fill(A, a.pod_affinity.required[: d.PT], i, pod)
        if a.pod_anti_affinity is not None:
            _fill(N, a.pod_anti_affinity.required[: d.AT], i, pod)
        for t, (w, term) in enumerate(pref_lists[i][:PP]):
            p_w[i, t] = w
            p_key[i, t] = encoder.register_topology_key(term.topology_key)
            _fill(P, [term], i, pod, slot=t)
    return LeanBatchAffinity(
        gid=gid, aff_gm=A, anti_gm=N, pref_gm=P,
        pref_topo_key=p_key, pref_weight=p_w,
        **dict(zip(("node_pairs", "node_pair_keys"),
                   encoder.node_pair_index())),
    )


def encode_nominated(encoder, nominated_pairs, k_min: int = 8):
    """Host helper: (pod, node_name) pairs -> NominatedState (power-of-two
    padded), or None when empty."""
    pairs = [
        (p, encoder.node_rows.get(n, -1)) for p, n in nominated_pairs
    ]
    pairs = [(p, r) for p, r in pairs if r >= 0]
    if not pairs:
        return None
    K = _pow2(len(pairs), k_min)
    node = np.full(K, -1, np.int32)
    prio = np.zeros(K, np.int32)
    req = np.zeros((K, encoder.dims.R), np.float32)
    for i, (p, r) in enumerate(pairs):
        node[i] = r
        prio[i] = p.spec.priority
        v = encoder._req_vector(p.resource_request())
        req[i, : v.shape[0]] = v
    return NominatedState(node=node, prio=prio, req=req)


def encode_nominated_block(encoder, nominated_pairs, pods: Sequence,
                           n_pods: int, n_nodes: int):
    """Host precompute of the pass-one effects nominated pods have BEYOND
    resources: host-port claims and required anti-affinity (both
    directions) — closing the NominatedState parity gap (VERDICT r2).

    Returns bool[n_pods, n_nodes] with True = infeasible in pass one, or
    None when no nominated pod contributes.  Folded into the engines'
    extra_mask, so both engines see it without new device plumbing.

    Required AFFINITY that only a nominated pod satisfies needs no
    tensor: podFitsOnNode's second pass (WITHOUT nominated pods,
    generic_scheduler.go:598-664) must also succeed, so a nominated pod
    can never flip an affinity-infeasible node feasible.  What CAN flip
    feasible->infeasible — port conflicts and anti-affinity — is exactly
    what this mask carries."""
    from kubernetes_tpu.cpuref.reference import _term_matches_pod

    pairs = [
        (p, encoder.node_rows.get(n, -1)) for p, n in nominated_pairs
    ]
    pairs = [(p, r) for p, r in pairs if 0 <= r < n_nodes]
    if not pairs:
        return None

    def anti_terms(pod):
        aff = pod.spec.affinity
        if aff is None or aff.pod_anti_affinity is None:
            return ()
        return aff.pod_anti_affinity.required

    # rows sharing a topology (key, value) — the domain an anti term blocks
    def rows_in_domain(key: str, value):
        if value is None:
            return []
        return [
            row for row, node in encoder._row_node.items()
            if row < n_nodes and node.labels.get(key) == value
        ]

    block = np.zeros((n_pods, n_nodes), bool)
    any_block = False
    domain_cache: dict = {}

    def domain_rows(key: str, value):
        # hoisted per (key, value): the blocked rows depend only on the
        # nominated pod's node + term key, not on the batch pod
        if value is None:
            return []
        ck = (key, value)
        if ck not in domain_cache:
            domain_cache[ck] = rows_in_domain(key, value)
        return domain_cache[ck]

    for k_pod, r in pairs:
        k_node = encoder._row_node.get(r)
        if k_node is None:
            continue
        k_prio = k_pod.spec.priority
        k_ports = list(encoder._pod_ports(k_pod))
        k_anti = anti_terms(k_pod)
        for b, pod in enumerate(pods):
            if b >= n_pods:
                break
            if k_prio < pod.spec.priority:
                continue  # only >=-priority nominated pods join pass one
            # host-port claim on the nominated node (host_ports.go
            # CheckConflict: same port and same-or-wildcard IP)
            for pp1, ip1 in encoder._pod_ports(pod):
                if any(pp1 == pp2 and (ip1 == ip2 or ip1 == 0 or ip2 == 0)
                       for pp2, ip2 in k_ports):
                    block[b, r] = True
                    any_block = True
                    break
            # nominated pod's anti terms reject this pod across the domain
            for t in k_anti:
                if _term_matches_pod(t, k_pod, pod):
                    for row in domain_rows(
                            t.topology_key, k_node.labels.get(t.topology_key)):
                        block[b, row] = True
                        any_block = True
            # this pod's anti terms reject nodes whose domain now holds
            # a matching nominated pod
            for t in anti_terms(pod):
                if _term_matches_pod(t, pod, k_pod):
                    for row in domain_rows(
                            t.topology_key, k_node.labels.get(t.topology_key)):
                        block[b, row] = True
                        any_block = True
    return block if any_block else None


def encode_batch_ports(encoder, pods: Sequence) -> BatchPortState:
    """Host-side precompute of the batch port vocabulary.

    Conflict semantics mirror nodeinfo/host_ports.go CheckConflict:
    same protocol+port and (same IP or either wildcard)."""
    vocab = {}
    plist = []
    for pod in pods:
        for pp, ip in encoder._pod_ports(pod):
            if (pp, ip) not in vocab:
                vocab[(pp, ip)] = len(plist)
                plist.append((pp, ip))
    PV = _pow2(max(len(plist), 1))
    B = encoder.batch_pad(len(pods))
    pod_ports = np.zeros((B, PV), bool)
    for b, pod in enumerate(pods):
        for pp, ip in encoder._pod_ports(pod):
            pod_ports[b, vocab[(pp, ip)]] = True
    conflict = np.zeros((PV, PV), bool)
    for i, (pp1, ip1) in enumerate(plist):
        for j, (pp2, ip2) in enumerate(plist):
            conflict[i, j] = pp1 == pp2 and (ip1 == ip2 or ip1 == 0 or ip2 == 0)
    # NB: conflicts vs EXISTING node occupancy are the static
    # PodFitsHostPorts predicate's job; only in-batch claims live here
    return BatchPortState(pod_ports=pod_ports, conflict=conflict)


def _dynamic_scores(cluster, req_cpu_mem, requested2, zone_key_id, counts,
                    rtc_xs, rtc_ys, zones=None):
    """The state-dependent priorities, recomputed per scan step from the
    shared scoring cores in ops/priorities.py.

    req_cpu_mem: f32[2] nonzero request of the current pod;
    requested2: f32[N, 2] current nonzero usage;
    counts: f32[N] pods matching ALL the pod's spread selectors per node
    (pre-batch base + in-batch commits)."""
    cap = node_capacity2(cluster)                            # [N, 2]
    req = requested2 + req_cpu_mem[None, :]
    least = least_requested_score(req, cap)                  # [N]
    most = most_requested_score(req, cap)
    balanced = balanced_allocation_score(req, cap)
    spread = spread_score_from_counts(counts, cluster, zone_key_id, zones)
    util = jnp.where(cap > 0, req * 100.0 / jnp.maximum(cap, 1e-30), 100.0)
    rtc = jnp.floor(jnp.sum(jnp.interp(util, rtc_xs, rtc_ys), axis=-1) / 2.0)
    return least, most, balanced, spread, rtc


def _replicated_on_cluster_mesh(cluster):
    # lives in parallel/mesh.py with the rest of the mesh placement
    # logic; lazy import keeps this module importable without jax.sharding
    from kubernetes_tpu.parallel.mesh import replicated_on_cluster_mesh

    return replicated_on_cluster_mesh(cluster)


from collections import OrderedDict

_SEQ_CACHE: "OrderedDict" = OrderedDict()
_SEQ_CACHE_CAP = 32  # bounds pinned executables (autoscaler what-if scale)


def make_sequential_scheduler(
    cfg: FilterConfig = FilterConfig(),
    weights=None,
    unsched_taint_key: int = 0,
    zone_key_id: int = 5,
    score_cfg: Optional[ScoreConfig] = None,
    percentage_of_nodes_to_score: int = 100,
    donate_cluster: bool = False,
    attribution: bool = False,
    attribution_topk: int = 3,
    quality_topk: int = 0,
):
    """Build (or fetch the memoized) jitted sequential-commit scheduler.

    Returns fn(cluster, pods, ports: BatchPortState, last_index0) ->
      (hosts i32[B] (-1 = unschedulable), new_cluster) where new_cluster has
      the committed requested/nonzero columns.

    With attribution=True (a STATIC flag: a separate executable, the
    default one unchanged) the launch additionally returns an Attribution
    pytree — per-pod first-failing-predicate node counts plus a top-k
    per-plugin score breakdown — computed inside the same scan against
    the exact per-step state, so winners are bit-identical either way
    (pinned by tests/test_ledger.py).

    With quality_topk=K > 0 (another STATIC output-only flag — the
    placement-quality observatory, runtime/quality.py) the launch ALSO
    returns an ops/select.TopKQuality pytree: per pod, the K best
    feasible node rows with the winner pinned at column 0, their total
    scores, and the feasible-candidate count the selector argmaxed
    over — all read off the same per-step (mask, total, host) the
    placement used, so winners stay bit-identical flag-on/off (pinned
    by tests/test_quality.py).  Output order when both flags are on:
    (hosts, new_cluster, Attribution, TopKQuality).

    Buffer donation (accelerator backends only; XLA:CPU has no donation):
    the per-batch argument buffers are not donated — no output has their
    shapes, so the chip's compiler cannot use them (PR 21's chip run
    warned "Some donated buffers were not usable" for every one).
    `donate_cluster=True` donates the cluster argument itself: the returned
    new_cluster then updates requested/nonzero IN PLACE (the static leaves
    alias straight through), which is correct ONLY for callers that chain
    the returned state and never reuse the input (bench.py's raw loop) —
    the live Scheduler keeps its snapshot resident in DeviceSnapshotCache
    across cycles and must NOT donate it."""
    if score_cfg is None:
        score_cfg = ScoreConfig()
    accel = jax.default_backend() != "cpu"
    key = (
        cfg,
        tuple(np.asarray(weights, np.float32)) if weights is not None else None,
        unsched_taint_key,
        zone_key_id,
        score_cfg,
        percentage_of_nodes_to_score,
        donate_cluster and accel,
        attribution,
        attribution_topk,
        quality_topk,
    )
    hit = _SEQ_CACHE.get(key)
    if hit is not None:
        _SEQ_CACHE.move_to_end(key)
        return hit
    w = np.asarray(
        DEFAULT_PRIORITY_WEIGHTS if weights is None else weights, np.float32
    )
    w_least = float(w[PRIO_INDEX["LeastRequestedPriority"]])
    w_most = float(w[PRIO_INDEX["MostRequestedPriority"]])
    w_bal = float(w[PRIO_INDEX["BalancedResourceAllocation"]])
    w_spread = float(w[PRIO_INDEX["SelectorSpreadPriority"]])
    w_rtc = float(w[PRIO_INDEX["RequestedToCapacityRatioPriority"]])
    rtc_xs = np.asarray([p[0] for p in score_cfg.rtc_shape], np.float32)
    rtc_ys = np.asarray([p[1] for p in score_cfg.rtc_shape], np.float32)

    def schedule_impl(cluster: ClusterTensors, pods: PodBatch, ports: BatchPortState,
                      last_index0: jnp.ndarray, nominated: Optional[NominatedState] = None,
                      extra_mask=None, extra_score=None,
                      aff_state: Optional[LeanBatchAffinity] = None):
        """extra_mask bool[B, N] / extra_score f32[B, N]: the framework's
        tensor-level Filter/Score plugin outputs, folded into the static
        pass (one launch total — the TPU-shaped plugin seam).

        aff_state: in-batch affinity cross-matches (LeanBatchAffinity);
        when given, MatchInterPodAffinity moves from the static pass into
        the scan with carried per-topology-pair extras, so co-batched pods
        see each other's placements (kills the batch>1 affinity-blindness
        gap)."""
        if aff_state is not None and not isinstance(
                aff_state, LeanBatchAffinity):
            raise TypeError("the sequential scan takes the factored "
                            "affinity state (encode_batch_affinity)")
        B = pods.n_pods
        G = cluster.group_counts.shape[1]
        # ---- static pass: every predicate except the dynamic ones, plus the
        # static score components, in one batched launch
        mask_static, per_pred = filter_batch(cluster, pods, cfg, unsched_taint_key)
        # static mask must EXCLUDE resources (recomputed in-scan); keep the
        # initial ports check (vs pre-batch occupancy) — in-scan adds claims.
        from kubernetes_tpu.codec.schema import PRED_INDEX

        res_idx = PRED_INDEX["PodFitsResources"]
        gen_idx = PRED_INDEX["GeneralPredicates"]
        non_resource = jnp.ones((per_pred.shape[1],), bool)
        non_resource = non_resource.at[res_idx].set(False)
        non_resource = non_resource.at[gen_idx].set(False)
        if aff_state is not None:
            # affinity is re-evaluated per step against (static | in-batch)
            # pair state instead of statically
            non_resource = non_resource.at[PRED_INDEX["MatchInterPodAffinity"]].set(
                False
            )
        static_mask = jnp.all(per_pred | ~non_resource[None, :, None], axis=1)
        # GeneralPredicates minus resources = host+ports+selector
        host_idx = PRED_INDEX["PodFitsHost"]
        ports_idx = PRED_INDEX["PodFitsHostPorts"]
        sel_idx = PRED_INDEX["PodMatchNodeSelector"]
        static_mask = (
            static_mask
            & per_pred[:, host_idx]
            & per_pred[:, ports_idx]
            & per_pred[:, sel_idx]
            & cluster.valid[None]
            & pods.valid[:, None]
        )
        if extra_mask is not None:
            static_mask = static_mask & extra_mask
        # static score components (state-independent priorities); with
        # in-batch affinity the IPA score moves into the scan (its raw pair
        # weights gain in-batch contributions and must renormalize)
        static_score = (
            (
                0.0
                if aff_state is not None
                else w[PRIO_INDEX["InterPodAffinityPriority"]]
                * inter_pod_affinity_score(cluster, pods)
            )
            + w[PRIO_INDEX["NodePreferAvoidPodsPriority"]] * node_prefer_avoid_pods(cluster, pods)
            + w[PRIO_INDEX["NodeAffinityPriority"]] * node_affinity(cluster, pods)
            + w[PRIO_INDEX["TaintTolerationPriority"]] * taint_toleration(cluster, pods)
            + w[PRIO_INDEX["ImageLocalityPriority"]] * image_locality(cluster, pods)
        )
        if w[PRIO_INDEX["NodeLabelPriority"]]:
            static_score = static_score + w[PRIO_INDEX["NodeLabelPriority"]] * node_label_priority(
                cluster, pods, score_cfg
            )
        if w[PRIO_INDEX["ResourceLimitsPriority"]]:
            static_score = static_score + w[PRIO_INDEX["ResourceLimitsPriority"]] * resource_limits(
                cluster, pods
            )
        if extra_score is not None:
            static_score = static_score + extra_score
        if attribution:
            # per-plugin attribution inputs (static flag: the default
            # executable never materializes these): the per-predicate
            # stack (already computed above) and the weighted static
            # score components — threaded through the scan so the
            # per-step slices see the SAME state the placement math does
            from kubernetes_tpu.ops.priorities import static_score_components

            comp_static = static_score_components(
                cluster, pods, w, score_cfg,
                include_ipa=(aff_state is None), extra_score=extra_score,
            )
            tk = min(attribution_topk, cluster.n_nodes)
        else:
            comp_static = None
        # quality top-k width: static, clamped to the arena (a 2-node
        # toy cluster cannot rank 3 rows)
        tkq = min(quality_topk, cluster.n_nodes) if quality_topk else 0
        feas_limit = (
            num_feasible_nodes_device(
                jnp.sum(cluster.valid.astype(jnp.int32)),
                percentage_of_nodes_to_score,
            )
            if percentage_of_nodes_to_score < 100  # 0 = adaptive
            else None
        )
        # in-batch spread cross-matches (countMatchingPods AND semantics).
        # A spread-lean batch (every pod in <= 1 group) carries them per
        # group, [G + 1, N]: pod i's in-batch counts are its group's row
        # (row G takes the groupless pods, whose counts stay 0).  Other
        # batches carry [B, N] rows through the shared helper, so the
        # speculative engine's bookkeeping is guaranteed identical
        lean_spread = pods.spread_counts.shape[-1] != cluster.n_nodes
        if lean_spread:
            onehot_g = pod_group_onehot(pods, G)              # [B, G]
            has_group = jnp.any(onehot_g > 0, axis=-1)
            spread_xs = (
                jnp.where(has_group, jnp.argmax(onehot_g, axis=-1), G),
                has_group,
            )
        else:
            spread_match = pod_spread_match(pods, G)          # [B, B] [i, j]
            spread_xs = None

        # SelectorSpread's zone layout is the same at every step
        zones = zone_layout(cluster, zone_key_id)
        if aff_state is not None:
            # the pods' pre-batch pair sets in node space, once: a node is
            # in a pair set when one of its pairs is.  The steps keep the
            # in-batch part in node space too, so no step reads the
            # [N, TP] incidence
            topo_t = cluster.topo_pairs.astype(jnp.float32).T  # [TP, N]

            def on_nodes(x, weights=False):
                """[..., TP] -> [..., N]: a node is in a pair set when
                one of its pairs is, or collects its pairs' weights.  An
                affinity-lean batch's pair tensors are 1 wide and all
                zero (codec/encoder.py)."""
                if x.shape[-1] != topo_t.shape[0]:
                    return jnp.zeros(x.shape[:-1] + (cluster.n_nodes,),
                                     jnp.float32 if weights else bool)
                if weights:
                    return jnp.matmul(x, topo_t, precision=_X)
                return jnp.matmul(x.astype(jnp.float32), topo_t) > 0

            GA = aff_state.aff_gm.shape[2]
            # node_pairs with a last column of PAD: the column of a term
            # whose key no node carries
            np_ext = jnp.concatenate(
                [aff_state.node_pairs,
                 jnp.full((cluster.n_nodes, 1), PAD, jnp.int32)], axis=1)
            KQ = np_ext.shape[1]
            key_on_node = (np_ext >= 0).T                      # [KQ, N]

            def key_col(keys):
                """i32[...] topology key ids -> their np_ext column."""
                hit = ((keys[..., None] == aff_state.node_pair_keys)
                       & (aff_state.node_pair_keys >= 0))
                return jnp.where(jnp.any(hit, axis=-1),
                                 jnp.argmax(hit, axis=-1), KQ - 1)

        w_ipa = float(w[PRIO_INDEX["InterPodAffinityPriority"]])
        hard_w = float(cfg.hard_pod_affinity_weight)

        def step(state, xs):
            (requested, nonzero2, spread_extra, port_used, last_idx,
             dom_cnt, forb_node, pref_node) = state
            (smask, sscore, req, nz2, spread_base, pprio, pport, step_no,
             spread_at, aff_xs, attr_xs) = xs
            if lean_spread:
                spread_row, has_group_j = spread_at
                spread_now = jnp.where(has_group_j, spread_extra[spread_row],
                                       0.0)
            else:
                spread_now = spread_extra[step_no]
            # dynamic resource fit (PodFitsResources on current state)
            fit = ~jnp.any(
                (req[None, :] > 0)
                & (requested + req[None, :] > cluster.allocatable),
                axis=-1,
            )
            if nominated is not None:
                # two-pass nominated evaluation (podFitsOnNode,
                # generic_scheduler.go:598-664): pass one adds nominated pods
                # with priority >= this pod's to their nominated node; the
                # no-nominated pass is `fit` itself (resource fit is monotone,
                # so pass one implies pass two here)
                w = (
                    (nominated.prio >= pprio) & (nominated.node >= 0)
                ).astype(jnp.float32)                         # [K]
                onehot_nom = (
                    nominated.node[:, None]
                    == jnp.arange(requested.shape[0])[None, :]
                ).astype(jnp.float32)                         # [K, N]
                extra = jnp.einsum(
                    "k,kn,kr->nr", w, onehot_nom, nominated.req
                )                                             # [N, R]
                fit_nom = ~jnp.any(
                    (req[None, :] > 0)
                    & (requested + extra + req[None, :] > cluster.allocatable),
                    axis=-1,
                )
                fit = fit & fit_nom
            # in-batch port conflicts: used claims x conflict matrix
            claimed_conflict = (port_used.astype(jnp.float32) @ ports.conflict.astype(jnp.float32)) > 0
            port_bad = jnp.any(pport[None, :] & claimed_conflict, axis=-1)
            mask = smask & fit & ~port_bad
            if aff_state is not None:
                # MatchInterPodAffinity against (pre-batch | in-batch)
                # state.  In-batch, from the carry: dom_cnt[g, q, n]
                # counts the batch's committed pods of label group g whose
                # node shares n's pair under key column q, so the
                # committed pods matching this pod's term t are its
                # group-match row aff_gm[t] times dom_cnt at t's column
                (pre_aff_j, pre_any_j, aff_valid_j, aff_self_j, qa_j,
                 pre_anti_j, anti_valid_j, qn_j, pre_forb_j, pre_raw_j,
                 gid_j, aff_gm_j, anti_gm_j, pref_gm_j, pref_wt_j,
                 qp_j) = aff_xs
                dom_any = (dom_cnt > 0).astype(jnp.float32)        # [G, KQ, N]
                in_aff = _in_batch(aff_gm_j, dom_any, qa_j) > 0    # [PT, N]
                aff_hit = pre_aff_j | in_aff
                any_match = pre_any_j | jnp.any(in_aff, axis=-1)   # [PT]
                node_has_key = key_on_node[qa_j]                   # [PT, N]
                bootstrap = ~any_match[:, None] & aff_self_j[:, None] & node_has_key
                term_ok = aff_hit | bootstrap | ~aff_valid_j[:, None]
                aff_ok = jnp.all(term_ok, axis=0)                  # [N]
                anti_hit = pre_anti_j | (_in_batch(anti_gm_j, dom_any, qn_j) > 0)
                viol2 = jnp.any(anti_hit & anti_valid_j[:, None], axis=0)
                # earlier committed pods' anti terms that match this pod
                viol1 = pre_forb_j | forb_node[gid_j]              # [N]
                mask = mask & aff_ok & ~viol1 & ~viol2
            least, most, balanced, spread, rtc = _dynamic_scores(
                cluster, nz2, nonzero2, zone_key_id,
                spread_base + spread_now, rtc_xs, rtc_ys, zones,
            )
            total = (
                sscore
                + w_least * least
                + w_most * most
                + w_bal * balanced
                + w_spread * spread
                + w_rtc * rtc
            )
            if aff_state is not None:
                # IPA score over (pre-batch | in-batch) raw pair weights,
                # renormalized per step (interpod_affinity.go fScore):
                # the earlier committed pods' terms by this pod's group
                # (pref_node), and this pod's preferred terms over the
                # committed pods they match, +-w each over their domains
                cand = jnp.sum(_in_batch(
                    pref_gm_j.astype(jnp.float32) * pref_wt_j[:, None],
                    dom_cnt, qp_j), axis=0)                        # [N]
                raw = pre_raw_j + pref_node[gid_j] + cand          # [N]
                big = jnp.float32(3.4e38)
                mn = jnp.min(jnp.where(cluster.valid, raw, big))
                mx = jnp.max(jnp.where(cluster.valid, raw, -big))
                spread_r = mx - mn
                ipa = jnp.where(
                    spread_r > 0,
                    jnp.floor(MAX_PRIORITY * (raw - mn) / spread_r),
                    0.0,
                )
                ipa_term = w_ipa * jnp.where(cluster.valid, ipa, 0.0)
                total = total + ipa_term
            if attribution:
                pp_j, comp_j = attr_xs
                # re-point the dynamic predicates at their IN-SCAN
                # verdicts so the first-failure attribution matches what
                # the placement mask actually saw at this step
                ports_ok = pp_j[ports_idx] & ~port_bad
                rows = pp_j.at[res_idx].set(fit)
                rows = rows.at[ports_idx].set(ports_ok)
                # the aggregate row never attributes — its constituents
                # (host/ports/selector/resources) name the precise reason
                rows = rows.at[gen_idx].set(True)
                if aff_state is not None:
                    rows = rows.at[
                        PRED_INDEX["MatchInterPodAffinity"]
                    ].set(aff_ok & ~viol1 & ~viol2)
                failed = ~rows                                  # [K, N]
                ff = jnp.argmax(failed, axis=0)
                any_fail = jnp.any(failed, axis=0)
                rejected = ~mask & cluster.valid
                reason = jnp.where(
                    rejected,
                    jnp.where(any_fail, ff, REASON_EXTENDER),
                    NUM_REASONS,            # feasible (never counted)
                )
                counts = jnp.sum(
                    reason[:, None] == jnp.arange(NUM_REASONS)[None, :],
                    axis=0, dtype=jnp.int32,
                )                                               # [NUM_REASONS]
                comp_full = comp_j                              # [C, N]
                comp_full = comp_full.at[
                    PRIO_INDEX["LeastRequestedPriority"]].set(w_least * least)
                comp_full = comp_full.at[
                    PRIO_INDEX["MostRequestedPriority"]].set(w_most * most)
                comp_full = comp_full.at[
                    PRIO_INDEX["BalancedResourceAllocation"]].set(
                        w_bal * balanced)
                comp_full = comp_full.at[
                    PRIO_INDEX["SelectorSpreadPriority"]].set(
                        w_spread * spread)
                comp_full = comp_full.at[
                    PRIO_INDEX["RequestedToCapacityRatioPriority"]].set(
                        w_rtc * rtc)
                if aff_state is not None:
                    comp_full = comp_full.at[
                        PRIO_INDEX["InterPodAffinityPriority"]].set(ipa_term)
                neg = jnp.float32(-3.4e38)
                top_vals, top_idx = jax.lax.top_k(
                    jnp.where(mask, total, neg), tk
                )
                top_comp = jnp.transpose(comp_full[:, top_idx])  # [TK, C]
                attr_out = (
                    counts,
                    jnp.where(top_vals > neg / 2, top_idx, -1).astype(
                        jnp.int32),
                    top_vals,
                    top_comp,
                )
            else:
                attr_out = None
            if percentage_of_nodes_to_score < 100:  # 0 = adaptive
                # adaptive node sampling (numFeasibleNodesToFind) with the
                # reference's rotating start offset
                mask = limit_feasible(mask, feas_limit, last_idx)
            host, feasible = select_host(total, mask, last_idx)
            # quality top-k (static output-only flag): the winner-pinned
            # ranking + feasible count off the exact (mask, total, host)
            # the selection above used — including the adaptive-sampling
            # cut, so "feasible" means candidates actually considered
            qual_out = (
                select_topk(total, mask, host, feasible, tkq)
                if tkq else None
            )
            # commit
            commit = feasible
            onehot = (jnp.arange(requested.shape[0]) == host) & commit  # [N]
            requested = requested + onehot[:, None] * req[None, :]
            nonzero2 = nonzero2 + onehot[:, None] * nz2[None, :]
            # later pods whose selector set this pod covers see it at its node
            if lean_spread:
                spread_extra = spread_extra.at[spread_row].add(
                    onehot.astype(jnp.float32))
            else:
                spread_extra = spread_extra + (
                    spread_match[:, step_no][:, None] * onehot[None, :]
                )
            port_used = port_used | (onehot[:, None] & pport[None, :])
            if aff_state is not None:
                # predicateMetadata.AddPod analog: the nodes sharing a
                # domain with the committed pod's node, per key column,
                # join its label group's count, and its own terms reach
                # later pods by the groups they match, over each term's
                # domain
                hp = np_ext[host]                                  # [KQ]
                dom = ((np_ext == hp[None, :]) & (hp[None, :] >= 0)
                       & commit).T.astype(jnp.float32)             # [KQ, N]
                dom_cnt = dom_cnt.at[gid_j].add(dom)
                forb_node = forb_node | (_owner(anti_gm_j, dom, qn_j) > 0)
                # hard-affinity symmetry: the committed pod's required
                # affinity terms add hard_w per matching later pod per node
                # of the domain (encoder K_AFF_REQ group semantics); its
                # preferred terms add +-w for each later pod they match
                # (existing-pod K_AFF_PREF/K_ANTI_PREF group semantics)
                pref_node = (
                    pref_node + hard_w * _owner(aff_gm_j, dom, qa_j)
                    + _owner(pref_gm_j.astype(jnp.float32)
                             * pref_wt_j[:, None], dom, qp_j)
                )
            out_host = jnp.where(feasible, host, -1)
            return (
                (requested, nonzero2, spread_extra, port_used, last_idx + 1,
                 dom_cnt, forb_node, pref_node),
                (out_host, attr_out, qual_out),
            )

        PV = ports.pod_ports.shape[1]
        if aff_state is not None:
            N = cluster.n_nodes
            extras_init = (
                jnp.zeros((GA, KQ, N), jnp.float32),
                jnp.zeros((GA, N), bool),
                jnp.zeros((GA, N), jnp.float32),
            )
        else:  # unused: scalar placeholders keep the carry structure cheap
            extras_init = tuple(jnp.zeros(()) for _ in range(3))
        init = (
            cluster.requested,
            cluster.nonzero_req,
            jnp.zeros((G + 1 if lean_spread else B, cluster.n_nodes),
                      jnp.float32),
            jnp.zeros((cluster.n_nodes, PV), bool),
            last_index0.astype(jnp.int32),
        ) + extras_init
        if aff_state is not None:
            aff_xs_in = (
                on_nodes(pods.aff_term_pairs),                 # [B, PT, N]
                jnp.any(pods.aff_term_pairs, axis=-1),         # [B, PT]
                pods.aff_term_valid,
                pods.aff_term_self,
                key_col(pods.aff_term_topo_key),               # [B, PT]
                on_nodes(pods.anti_term_pairs),                # [B, AT, N]
                pods.anti_term_valid,
                key_col(pods.anti_term_topo_key),              # [B, AT]
                on_nodes(pods.forbidden_pairs),                # [B, N]
                on_nodes(pods.pref_pair_weights, weights=True),  # [B, N]
                aff_state.gid,
                aff_state.aff_gm,
                aff_state.anti_gm,
                aff_state.pref_gm,
                aff_state.pref_weight,
                key_col(aff_state.pref_topo_key),              # [B, PP]
            )
        else:
            aff_xs_in = None
        xs = (
            static_mask,
            static_score,
            pods.req,
            pods.nonzero_req,
            # device-derived for spread-lean batches (no [B, N] upload)
            spread_counts(cluster, pods),
            pods.priority,
            ports.pod_ports,
            jnp.arange(B, dtype=jnp.int32),
            spread_xs,
            aff_xs_in,
            # extra-mask vetoes need no tensor here: a node rejected with
            # every predicate passing can ONLY be an extra-mask veto
            (per_pred, comp_static) if attribution else None,
        )
        (requested, nonzero2, *_), (hosts, attr_ys, qual_ys) = jax.lax.scan(
            step, init, xs
        )
        import dataclasses as _dc

        new_cluster = _dc.replace(
            cluster,
            requested=requested,
            nonzero_req=nonzero2,
        )
        outs = (hosts, new_cluster)
        if attribution:
            outs = outs + (Attribution(*attr_ys),)
        if tkq:
            outs = outs + (TopKQuality(*qual_ys),)
        return outs

    # donation (see the maker docstring): the cluster only, for
    # chained-state callers on accelerator backends.  XLA:CPU implements
    # no donation — plain jit there keeps warning noise out of the
    # tier-1 suite.
    schedule = jax.jit(
        schedule_impl,
        donate_argnums=(0,) if donate_cluster and accel else (),
    )

    def schedule_entry(cluster, pods, ports, last_index0, nominated=None,
                       extra_mask=None, extra_score=None, aff_state=None):
        """Host entry: on accelerator backends, move the batch pytrees to
        the device via an explicit (async) device_put first, which matters
        for the [B, ., B] affinity cross-match tensors.  device_put is a
        no-op passthrough for leaves already on the device.  A
        mesh-sharded cluster (the multi-chip live path) pins the
        computation to its mesh, so the batch buffers replicate over the
        SAME devices — a plain device_put would commit them to device 0
        and conflict."""
        if jax.default_backend() != "cpu":
            tree = (pods, ports, nominated, extra_mask, extra_score,
                    aff_state)
            transfer.note_transfer_tree("h2d", "batch_replicate", tree)
            dst = _replicated_on_cluster_mesh(cluster)
            pods, ports, nominated, extra_mask, extra_score, aff_state = (
                jax.device_put(tree, dst)
                if dst is not None else jax.device_put(tree)
            )
        return schedule(cluster, pods, ports, last_index0, nominated,
                        extra_mask, extra_score, aff_state)

    # the raw traceable fn for callers composing INSIDE jit (the
    # speculative engine's in-program lax.cond redo): the UNJITTED impl —
    # it inlines into the outer trace, where donation has no meaning
    schedule_entry.jitted = schedule_impl
    # the jitted program the entry launches (the compile tests lower it
    # for a described chip)
    schedule_entry.program = schedule
    # engine identity tag: consumers whose correctness depends on the
    # strictly sequential one-at-a-time commit order (models/gang.py's
    # cross-gang required-affinity drop guard) assert on this
    schedule_entry.engine_kind = "sequential"
    # attribution variants return (hosts, new_cluster, Attribution);
    # callers handling either arity key off this
    schedule_entry.attribution = attribution
    # quality variants append a TopKQuality as the LAST output (after
    # Attribution when both flags are on); 0 = off
    schedule_entry.quality_topk = quality_topk

    _SEQ_CACHE[key] = schedule_entry
    while len(_SEQ_CACHE) > _SEQ_CACHE_CAP:
        _SEQ_CACHE.popitem(last=False)
    return schedule_entry
