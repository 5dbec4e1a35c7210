"""Score priorities as batched tensor kernels.

Each mirrors one reference priority (pkg/scheduler/algorithm/priorities/*) on
the whole pods x nodes grid, including the Map/Reduce normalization semantics
(priorities/types.go:28-34, reduce.go NormalizeReduce) and the weighted sum
(core/generic_scheduler.go:767-772).  Reference scores are int64 on a 0..10
scale with integer truncation; we reproduce the truncation with floor() so the
parity suite can compare exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.codec.schema import (
    ClusterTensors,
    NUM_PRIORITIES,
    PAD,
    PodBatch,
    PRIO_INDEX,
    RES_MEMORY,
    RES_MILLICPU,
)
from kubernetes_tpu.ops.predicates import _eval_exprs

MAX_PRIORITY = 10.0
_PREFER_NO_SCHEDULE = 1
_TOL_EXISTS = 1

# ImageLocality thresholds (priorities/image_locality.go:33-36)
_IMG_MIN = 23.0 * 1024 * 1024
_IMG_MAX = 1000.0 * 1024 * 1024

# SelectorSpread zone weighting (priorities/selector_spreading.go:34)
_ZONE_WEIGHT = 2.0 / 3.0


def _fdiv_floor(a, b):
    """Integer-division semantics of the reference's int64 math (operands are
    non-negative here, so trunc == floor)."""
    return jnp.floor(a / jnp.maximum(b, 1e-30))


def _normalize_reduce(counts, max_priority=MAX_PRIORITY, reverse=False):
    """reduce.go NormalizeReduce over the node axis: score = max_priority *
    count / maxCount (floored), reversed if asked; all-max when maxCount==0
    and reverse."""
    maxc = jnp.max(counts, axis=-1, keepdims=True)
    score = _fdiv_floor(max_priority * counts, maxc)
    if reverse:
        score = max_priority - score
    return jnp.where(maxc > 0, score, max_priority if reverse else 0.0)


# ----------------------------------------------------------------- resources
# State-parameterized cores, shared with the sequential-commit scan
# (models/batched.py) where `requested` is the in-scan mutable state.


def node_capacity2(cluster: ClusterTensors):
    """(milliCPU, memory) allocatable -> f32[N, 2]."""
    return jnp.stack(
        [cluster.allocatable[:, RES_MILLICPU], cluster.allocatable[:, RES_MEMORY]],
        axis=-1,
    )


def least_requested_score(req2, cap2):
    """least_requested.go leastRequestedScore over (cpu, mem) pairs:
    ((cap-req)*10/cap + ...)/2, int-floored at each step.
    req2 [..., N, 2], cap2 [N, 2] -> [..., N]."""
    per = _fdiv_floor((cap2 - req2) * MAX_PRIORITY, cap2)
    per = jnp.where((cap2 == 0) | (req2 > cap2), 0.0, per)
    return jnp.floor(jnp.sum(per, axis=-1) / 2.0)


def most_requested_score(req2, cap2):
    per = _fdiv_floor(req2 * MAX_PRIORITY, cap2)
    per = jnp.where((cap2 == 0) | (req2 > cap2), 0.0, per)
    return jnp.floor(jnp.sum(per, axis=-1) / 2.0)


def balanced_allocation_score(req2, cap2):
    """balanced_resource_allocation.go:41-67:
    int64((1 - |cpuFraction - memFraction|) * 10); 0 if either fraction >= 1."""
    frac = req2 / jnp.maximum(cap2, 1e-30)
    over = jnp.any((frac >= 1.0) | (cap2 == 0), axis=-1)
    diff = jnp.abs(frac[..., 0] - frac[..., 1])
    return jnp.where(over, 0.0, jnp.floor((1.0 - diff) * MAX_PRIORITY))


def _requested_with_pod(cluster: ClusterTensors, pods: PodBatch):
    """nonzero-request (cpu, mem) per (pod, node) if the pod were placed
    (resource_allocation.go:49-58)."""
    return pods.nonzero_req[:, None, :] + cluster.nonzero_req[None]   # [B, N, 2]


def least_requested(cluster: ClusterTensors, pods: PodBatch):
    """LeastRequestedPriority (priorities/least_requested.go)."""
    return least_requested_score(
        _requested_with_pod(cluster, pods), node_capacity2(cluster)[None]
    )


def most_requested(cluster: ClusterTensors, pods: PodBatch):
    """MostRequestedPriority (priorities/most_requested.go) — used by the
    ClusterAutoscalerProvider profile (defaults.go registerAlgorithmProvider)."""
    return most_requested_score(
        _requested_with_pod(cluster, pods), node_capacity2(cluster)[None]
    )


def balanced_allocation(cluster: ClusterTensors, pods: PodBatch):
    """BalancedResourceAllocation (balanced_resource_allocation.go:41-67)."""
    return balanced_allocation_score(
        _requested_with_pod(cluster, pods), node_capacity2(cluster)[None]
    )


# ------------------------------------------------------------ node affinity


def node_affinity(cluster: ClusterTensors, pods: PodBatch):
    """NodeAffinityPriority (priorities/node_affinity.go): sum the weights of
    matching preferredDuringScheduling terms, then NormalizeReduce(10, false)."""
    if pods.pref_weight.shape[1] == 0:
        # affinity-lean batch: no preferred terms anywhere -> all-zero counts
        return jnp.zeros((pods.n_pods, cluster.n_nodes), jnp.float32)
    m = _eval_exprs(
        cluster,
        pods.pref_expr_key,
        pods.pref_expr_op,
        pods.pref_expr_vals,
        pods.pref_expr_nval,
        pods.pref_expr_num,
        pods.pref_expr_valid,
    )                                                        # [B, PS, E, N]
    term_ok = jnp.all(m, axis=2) & pods.pref_term_valid[..., None]
    counts = jnp.sum(jnp.where(term_ok, pods.pref_weight[..., None], 0.0), axis=1)
    return _normalize_reduce(counts)


# ---------------------------------------------------------- taint toleration


def taint_toleration(cluster: ClusterTensors, pods: PodBatch):
    """TaintTolerationPriority (priorities/taint_toleration.go): count
    intolerable PreferNoSchedule taints, NormalizeReduce(10, true)."""
    tk = pods.tol_key[:, :, None, None]
    to = pods.tol_op[:, :, None, None]
    tv = pods.tol_val[:, :, None, None]
    te = pods.tol_effect[:, :, None, None]
    tvalid = pods.tol_valid[:, :, None, None]
    ntk = cluster.taint_key[None, None]
    ntv = cluster.taint_val[None, None]
    nte = cluster.taint_effect[None, None]
    tol = (
        tvalid
        & ((te == PAD) | (te == nte))
        & ((tk == 0) | (tk == ntk))
        & ((to == _TOL_EXISTS) | (tv == ntv))
    )
    tolerated = jnp.any(tol, axis=1)                         # [B, N, T]
    prefer = cluster.taint_effect == _PREFER_NO_SCHEDULE     # [N, T]
    counts = jnp.sum((prefer[None] & ~tolerated).astype(jnp.float32), axis=-1)
    return _normalize_reduce(counts, reverse=True)


# ------------------------------------------------------------- image locality


def image_locality(cluster: ClusterTensors, pods: PodBatch):
    """ImageLocalityPriority (priorities/image_locality.go): sum spread-scaled
    sizes of the pod's images present on the node, clamp to [23MB, 1000MB],
    scale to 0..10.  Spread scaling is folded into cluster.image_size at
    snapshot time."""
    pid = pods.image_ids[:, :, None, None]                   # [B, C, 1, 1]
    nid = cluster.image_id[None, None]                       # [1, 1, N, I]
    hit = (pid != PAD) & (pid == nid)
    summed = jnp.sum(
        jnp.where(hit, cluster.image_size[None, None], 0.0), axis=(1, 3)
    )                                                        # [B, N]
    clamped = jnp.clip(summed, _IMG_MIN, _IMG_MAX)
    return jnp.floor(MAX_PRIORITY * (clamped - _IMG_MIN) / (_IMG_MAX - _IMG_MIN))


# -------------------------------------------------------- prefer-avoid-pods


def node_prefer_avoid_pods(cluster: ClusterTensors, pods: PodBatch):
    """NodePreferAvoidPodsPriority (priorities/node_prefer_avoid_pods.go):
    0 if the node's preferAvoidPods annotation names the pod's RC/RS
    controller, else 10.  Registered with weight 10000."""
    owner = pods.owner_uid[:, None, None]                    # [B, 1, 1]
    avoid = (owner != PAD) & (owner == cluster.avoid_owner[None])   # [B, N, A]
    return jnp.where(jnp.any(avoid, axis=-1), 0.0, MAX_PRIORITY)


# ------------------------------------------------------------ selector spread


def zone_layout(cluster: ClusterTensors, zone_key_id: int):
    """(node_in_zone bool[N], zone_of_node i32[N] pair id, have_zones):
    the part of SelectorSpread's zone aggregation that depends on the
    cluster alone.  GetZoneKey gives each node at most ONE zone pair, so
    the argmax column is exact."""
    zmask = cluster.pair_topo_key == zone_key_id             # [TP]
    zpairs_b = cluster.topo_pairs & zmask[None]              # [N, TP] bool
    node_in_zone = jnp.any(zpairs_b, axis=-1)                # [N]
    zone_of_node = jnp.argmax(zpairs_b, axis=-1)             # [N] pair id
    return node_in_zone, zone_of_node, jnp.any(node_in_zone)


def spread_score_from_counts(counts, cluster: ClusterTensors, zone_key_id: int,
                             zones=None):
    """The SelectorSpread reduce (selector_spreading.go:95-140) given per-node
    matching-pod counts [..., N]: fScore = (1-2/3)*nodeScore + 2/3*zoneScore,
    int-truncated.  Zone aggregation is a segment-sum over each node's zone
    pair id (scatter + gather, O(B*N)).  `zones` is zone_layout's result
    for a caller that reuses it across calls (a scan's steps); with it, a
    cluster without zones skips the aggregation."""
    max_node = jnp.max(counts, axis=-1, keepdims=True)
    # guarded denominator: the zero branch is selected away, but dividing
    # by 0 first would trip the checkify float guards (tests/test_checkify)
    node_score = jnp.where(
        max_node > 0,
        MAX_PRIORITY * (max_node - counts) / jnp.maximum(max_node, 1.0),
        MAX_PRIORITY,
    )

    # zone aggregation as a segment-sum over each node's zone pair id:
    # O(B*N) scatter+gather instead of two [.., N] x [N, TP] matmuls over
    # the WHOLE pair vocabulary (hostname pairs make TP ~ N, so the matmul
    # form costs B*N*TP flops — negligible on the MXU, seconds on the CPU
    # fallback)
    def zoned(node_in_zone, zone_of_node, have_zones):
        TP = cluster.topo_pairs.shape[1]
        lead = counts.shape[:-1]
        n = counts.shape[-1]
        flat = counts.reshape((-1, n))
        contrib = jnp.where(node_in_zone[None, :], flat, 0.0)
        zsums = jnp.zeros((flat.shape[0], TP), flat.dtype)
        zsums = zsums.at[:, zone_of_node].add(contrib)       # [M, TP]
        zcount_per_node = zsums[:, zone_of_node].reshape(lead + (n,))
        max_zone = jnp.max(zsums, axis=-1).reshape(lead + (1,))
        zone_score = jnp.where(
            max_zone > 0,
            MAX_PRIORITY * (max_zone - zcount_per_node)
            / jnp.maximum(max_zone, 1.0),
            MAX_PRIORITY,
        )
        blended = jnp.where(
            have_zones & node_in_zone,
            (1.0 - _ZONE_WEIGHT) * node_score + _ZONE_WEIGHT * zone_score,
            node_score,
        )
        return jnp.floor(blended)

    if zones is None:
        return zoned(*zone_layout(cluster, zone_key_id))
    # no zones: the blend above is the node score alone
    return jax.lax.cond(zones[2], lambda: zoned(*zones),
                        lambda: jnp.floor(node_score))


def pod_group_onehot(pods: PodBatch, n_groups: int):
    """[B, G] one-hot of each pod's spread groups."""
    return (
        (pods.group_ids[:, :, None] == jnp.arange(n_groups)[None, None])
        & pods.group_valid[..., None]
    ).astype(jnp.float32).sum(axis=1)


def pod_spread_match(pods: PodBatch, n_groups: int):
    """f32[B, B] [i, j]: committing pod j raises pod i's spread count at
    j's node — i.e. j matches ALL of i's selectors, expressed as "i's
    group set is a subset of j's" over the one-hots (groups are
    namespace-scoped, so the ns check rides along).  countMatchingPods
    AND semantics (selector_spreading.go:95-140); shared by BOTH engines
    so their in-batch bookkeeping can never desync."""
    from jax import lax as _lax

    onehot = pod_group_onehot(pods, n_groups)                # [B, G]
    has_groups = jnp.any(pods.group_valid, axis=1)           # [B]
    return (
        has_groups[:, None]
        & (jnp.matmul(onehot, (1.0 - onehot).T,
                      precision=_lax.Precision.HIGHEST) == 0)
    ).astype(jnp.float32)


def selector_spread(cluster: ClusterTensors, pods: PodBatch, zone_key_id: int = 5):
    """SelectorSpreadPriority (priorities/selector_spreading.go:77-140):
    per-node counts of existing pods matching ALL the pod's selectors
    (countMatchingPods AND semantics), then the zone-weighted reduce.
    zone_key_id is the interned id of the encoder's synthetic GetZoneKey
    topology key (region+zone grouping).

    Counts source: spread-lean batches (every pod in <= 1 group — the
    common shape) derive counts on device from the snapshot's per-group
    columns; multi-group batches ship exact host-computed AND counts."""
    counts = spread_counts(cluster, pods)
    return spread_score_from_counts(counts, cluster, zone_key_id)


def spread_counts(cluster: ClusterTensors, pods: PodBatch):
    """f32[B, N] matching-pod counts (see selector_spread)."""
    if pods.spread_counts.shape[-1] != cluster.n_nodes:
        onehot = pod_group_onehot(pods, cluster.group_counts.shape[1])
        return onehot @ cluster.group_counts.T               # [B, N]
    return pods.spread_counts


# --------------------------------------------------------- inter-pod affinity


def inter_pod_affinity_score(cluster: ClusterTensors, pods: PodBatch):
    """InterPodAffinityPriority (priorities/interpod_affinity.go): signed
    weight sums over topology pairs (preferred affinity/anti-affinity of the
    incoming pod, preferred+hard-symmetric terms of existing pods — all folded
    into pref_pair_weights by the encoder), then the min/max normalize
    fScore = 10 * (sum - min) / (max - min)."""
    if pods.pref_pair_weights.shape[-1] != cluster.topo_pairs.shape[-1]:
        # lean batch: no affinity exposure anywhere -> all sums identical
        # (zero) -> score 0 on every node, computed for free
        return jnp.zeros((pods.n_pods, cluster.n_nodes), jnp.float32)
    sums = pods.pref_pair_weights @ cluster.topo_pairs.astype(jnp.float32).T
    valid = cluster.valid[None]
    big = jnp.float32(3.4e38)
    mn = jnp.min(jnp.where(valid, sums, big), axis=-1, keepdims=True)
    mx = jnp.max(jnp.where(valid, sums, -big), axis=-1, keepdims=True)
    spread = mx - mn
    score = jnp.where(
        spread > 0,
        jnp.floor(MAX_PRIORITY * (sums - mn) / jnp.maximum(spread, 1e-30)),
        0.0,
    )
    return jnp.where(valid, score, 0.0)


# --------------------------------------------------- policy-driven priorities


def node_label_priority(cluster: ClusterTensors, pods: PodBatch, score_cfg):
    """NodeLabelPriority (priorities/node_label.go): per configured
    (key, presence) pref: 10 when presence matches, else 0; weighted sum of
    prefs, then NOT normalized (each pref is its own PriorityConfig in the
    reference — we fold them with their weights here)."""
    B, N = pods.n_pods, cluster.n_nodes
    total = jnp.zeros((B, N), jnp.float32)
    for key_id, presence, weight in score_cfg.label_prefs:
        present = jnp.any(cluster.label_keys == key_id, axis=-1)  # [N]
        score = jnp.where(present == bool(presence), MAX_PRIORITY, 0.0)
        total = total + weight * score[None, :]
    return total


def requested_to_capacity_ratio(cluster: ClusterTensors, pods: PodBatch, score_cfg):
    """RequestedToCapacityRatioPriority (priorities/
    requested_to_capacity_ratio.go): per-resource utilization% mapped through
    the configured piecewise-linear curve, averaged over (cpu, mem)."""
    req = _requested_with_pod(cluster, pods)                 # [B, N, 2]
    cap = node_capacity2(cluster)[None]
    util = jnp.where(cap > 0, req * 100.0 / jnp.maximum(cap, 1e-30), 100.0)
    pts = score_cfg.rtc_shape
    xs = jnp.asarray([p[0] for p in pts], jnp.float32)
    ys = jnp.asarray([p[1] for p in pts], jnp.float32)
    score = jnp.interp(util, xs, ys)                         # clamps at ends
    return jnp.floor(jnp.sum(score, axis=-1) / 2.0)


def resource_limits(cluster: ClusterTensors, pods: PodBatch):
    """ResourceLimitsPriority (priorities/resource_limits.go, feature-gated):
    1 if the node's allocatable satisfies the pod's cpu+mem limits and at
    least one limit is set, else 0."""
    cap = node_capacity2(cluster)[None]                      # [1, N, 2]
    lim = pods.limits2[:, None, :]                           # [B, 1, 2]
    ok = jnp.all((lim == 0) | (cap >= lim), axis=-1)
    any_lim = jnp.any(pods.limits2 > 0, axis=-1)[:, None]
    return jnp.where(ok & any_lim, 1.0, 0.0)


# ------------------------------------------------------------------ combined


def score_batch(cluster: ClusterTensors, pods: PodBatch, weights=None,
                score_cfg=None, zone_key_id: int = 5,
                skip_zero_weight: bool = False, need_per: bool = True):
    """All priorities + weighted sum -> (total f32[B, N], per f32[B, P, N]).

    weights follows PRIORITY_ORDER; defaults to the stock weights
    (default provider set at 1 / 10000, policy-only functions at 0)."""
    if score_cfg is None:
        from kubernetes_tpu.codec.schema import ScoreConfig

        score_cfg = ScoreConfig()
    if weights is None:
        from kubernetes_tpu.codec.schema import DEFAULT_PRIORITY_WEIGHTS

        weights = DEFAULT_PRIORITY_WEIGHTS
    w_host = np.asarray(weights, np.float32)
    makers = {
        "SelectorSpreadPriority":
            lambda: selector_spread(cluster, pods, zone_key_id),
        "InterPodAffinityPriority":
            lambda: inter_pod_affinity_score(cluster, pods),
        "LeastRequestedPriority": lambda: least_requested(cluster, pods),
        "BalancedResourceAllocation":
            lambda: balanced_allocation(cluster, pods),
        "NodePreferAvoidPodsPriority":
            lambda: node_prefer_avoid_pods(cluster, pods),
        "NodeAffinityPriority": lambda: node_affinity(cluster, pods),
        "TaintTolerationPriority": lambda: taint_toleration(cluster, pods),
        "ImageLocalityPriority": lambda: image_locality(cluster, pods),
        "MostRequestedPriority": lambda: most_requested(cluster, pods),
        "NodeLabelPriority":
            lambda: node_label_priority(cluster, pods, score_cfg),
        "RequestedToCapacityRatioPriority":
            lambda: requested_to_capacity_ratio(cluster, pods, score_cfg),
        "ResourceLimitsPriority": lambda: resource_limits(cluster, pods),
    }
    # with skip_zero_weight (the engines' hot path), zero-weight
    # priorities contribute nothing to the total — skip their kernels
    # entirely (weights are trace-time constants; the stock set zeroes
    # the 4 policy-only functions, and RTC alone is ~20% of a
    # CPU-fallback round).  Their stack rows become zeros, so callers
    # needing the full per-priority breakdown (parity/golden tests, the
    # one-launch generic path) keep the default full computation.
    zero = None
    if not need_per:
        # total-only hot path (the engines): accumulate the weighted sum
        # without materializing the [B, P, N] stack (~0.5GB at batch
        # 2048 x 5k nodes)
        total = jnp.zeros((pods.n_pods, cluster.n_nodes), jnp.float32)
        for name, _ in sorted(PRIO_INDEX.items(), key=lambda kv: kv[1]):
            w_i = float(w_host[PRIO_INDEX[name]])
            if w_i != 0.0:
                total = total + w_i * makers[name]()
        return total, None
    per = []
    for name, _ in sorted(PRIO_INDEX.items(), key=lambda kv: kv[1]):
        if not skip_zero_weight or w_host[PRIO_INDEX[name]] != 0.0:
            per.append(makers[name]())
        else:
            if zero is None:
                zero = jnp.zeros((pods.n_pods, cluster.n_nodes),
                                 jnp.float32)
            per.append(zero)
    stack = jnp.stack(per, axis=1)                           # [B, P, N]
    w = jnp.asarray(w_host, jnp.float32)
    total = jnp.einsum("bpn,p->bn", stack, w)
    return total, stack


def static_score_components(cluster: ClusterTensors, pods: PodBatch,
                            weights, score_cfg, include_ipa: bool = True,
                            extra_score=None):
    """f32[B, C, N] WEIGHTED static score addends on the attribution
    component axis (schema.SCORE_COMPONENTS = PRIORITY_ORDER + "Extra").

    The state-dependent priorities (least/most/balanced/spread/RTC — and
    InterPodAffinity when the in-batch scan owns it) stay zero here; the
    sequential-commit scan fills them per step against the current
    committed state, so the per-plugin breakdown sums to the exact score
    selectHost saw.  Only built under the engines' attribution flag — the
    default executable never materializes the stack."""
    from kubernetes_tpu.codec.schema import NUM_SCORE_COMPONENTS

    w = np.asarray(weights, np.float32)
    B, N = pods.n_pods, cluster.n_nodes
    comp = jnp.zeros((B, NUM_SCORE_COMPONENTS, N), jnp.float32)

    def put(name, fn):
        w_i = float(w[PRIO_INDEX[name]])
        if w_i != 0.0:
            return comp.at[:, PRIO_INDEX[name]].set(w_i * fn())
        return comp

    comp = put("NodePreferAvoidPodsPriority",
               lambda: node_prefer_avoid_pods(cluster, pods))
    comp = put("NodeAffinityPriority", lambda: node_affinity(cluster, pods))
    comp = put("TaintTolerationPriority",
               lambda: taint_toleration(cluster, pods))
    comp = put("ImageLocalityPriority", lambda: image_locality(cluster, pods))
    comp = put("NodeLabelPriority",
               lambda: node_label_priority(cluster, pods, score_cfg))
    comp = put("ResourceLimitsPriority",
               lambda: resource_limits(cluster, pods))
    if include_ipa:
        comp = put("InterPodAffinityPriority",
                   lambda: inter_pod_affinity_score(cluster, pods))
    if extra_score is not None:
        comp = comp.at[:, NUM_SCORE_COMPONENTS - 1].set(extra_score)
    return comp
