"""Preemption as a vectorized what-if over all nodes at once.

Reference flow (core/generic_scheduler.go):
  Preempt (:310-369) -> nodesWherePreemptionMightHelp (failure must be
  resolvable, :65-123 unresolvablePredicateFailureErrors) ->
  selectNodesForPreemption over all nodes in parallel (:964-998) ->
  selectVictimsOnNode remove-all-lower-priority + PDB-grouped reprieve loop
  (:1054-1128) -> pickOneNodeForPreemption 6-criteria lexicographic pick
  (:837-962).

TPU shape:
  * the "remove all lower-priority pods, does it fit?" what-if is one
    segment-sum over the assigned-pod arena, for ALL nodes simultaneously.
    The fit check runs over an EXTENDED resource axis: the host appends
    columns encoding host-port conflicts, disk-volume conflicts, and the
    five Max*VolumeCount budgets (each a count the victims free up), so the
    same `used - freed + req <= allocatable` comparison covers
    PodFitsResources, PodFitsHostPorts, NoDiskConflict, and the volume-count
    predicates — the resolvable predicate set selectVictimsOnNode re-runs
    (remaining resolvable predicates — inter-pod anti-affinity — are gated
    host-side by the scheduler's nomination verification);
  * the reprieve loop — re-add victims while the preemptor still fits —
    runs as a lax.scan over the host-sorted victim list, PDB-violating
    victims first then non-violating, highest priority first within each
    group (filterPodsWithPDBViolation + the two reprieve passes).  Steps
    touching different nodes are independent, so one global scan reprieves
    every candidate node in the same launch;
  * node pick: all six pickOneNodeForPreemption criteria — min PDB
    violations, min highest victim priority, min (exact, offset) priority
    sum, min victim count, latest earliest-start of highest-priority
    victims, first index.

The host then deletes the victims, records the nominated node on the
preemptor (queue nominatedPods map), and requeues.
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.codec.schema import PRED_INDEX

# Failures preemption can NEVER fix (generic_scheduler.go:65-123
# unresolvablePredicateFailureErrors): evicting pods does not change node
# labels/taints/conditions/name or volume topology.  Note the Max*VolumeCount
# predicates are NOT here (attach budgets free up when victims leave) while
# CheckVolumeBinding/NoVolumeZoneConflict ARE (ErrVolumeBindConflict,
# ErrVolumeNodeConflict, ErrVolumeZoneConflict).  The required-affinity-rules
# component of MatchInterPodAffinity (ErrPodAffinityRulesNotMatch) is also
# unresolvable and handled separately via `aff_rules_ok` (the anti-affinity
# components of the same predicate row ARE resolvable).
UNRESOLVABLE = (
    "CheckNodeCondition",
    "CheckNodeUnschedulable",
    "PodFitsHost",
    "PodMatchNodeSelector",
    "PodToleratesNodeTaints",
    "PodToleratesNodeNoExecuteTaints",
    "CheckNodeLabelPresence",
    "CheckNodeMemoryPressure",
    "CheckNodePIDPressure",
    "CheckNodeDiskPressure",
    "NoVolumeZoneConflict",
    "CheckVolumeBinding",
)

INT_MIN = np.iinfo(np.int32).min
INT_MAX = np.iinfo(np.int32).max
_F32_MAX = np.float32(np.finfo(np.float32).max)


class PreemptionResult(NamedTuple):
    node: jnp.ndarray          # i32 chosen node row (-1 = preemption helps nowhere)
    victim_mask: jnp.ndarray   # bool[M] pods to evict (on the chosen node)
    n_victims: jnp.ndarray     # i32
    n_pdb_violations: jnp.ndarray  # i32 victims whose eviction violates a PDB


def preemption_candidates(per_pred, valid, aff_rules_ok=None):
    """bool[B, N]: nodes where preemption might help — the pod does not fit,
    but no unresolvable predicate failed (nodesWherePreemptionMightHelp).

    aff_rules_ok: bool[B, N] from ops.predicates.required_affinity_ok; when
    given, nodes failing the pod's required affinity rules are excluded
    (ErrPodAffinityRulesNotMatch is unresolvable, but the combined
    MatchInterPodAffinity row can't distinguish it from resolvable
    anti-affinity failures)."""
    fits = jnp.all(per_pred, axis=1)
    unresolvable_idx = jnp.asarray([PRED_INDEX[p] for p in UNRESOLVABLE])
    hard_fail = jnp.any(~per_pred[:, unresolvable_idx, :], axis=1)
    if aff_rules_ok is not None:
        hard_fail = hard_fail | ~aff_rules_ok
    return (~fits) & (~hard_fail) & valid[None]


def sorted_victim_slots(pods_priority, pods_valid, pods_node, pod_priority,
                        pods_violating=None, pods_start=None, cap: int = 1024):
    """Host helper: arena indices of potential victims in reprieve order
    (generic_scheduler.go:1085-1115): PDB-violating victims first, then
    non-violating; within each group highest priority first, then earliest
    start time (util.MoreImportantPod).  -1-padded to a power of two."""
    prio = np.asarray(pods_priority)
    ok = np.asarray(pods_valid) & (np.asarray(pods_node) >= 0) & (prio < pod_priority)
    idx = np.nonzero(ok)[0]
    viol = (
        np.asarray(pods_violating)[idx]
        if pods_violating is not None
        else np.zeros(len(idx), bool)
    )
    start = (
        np.asarray(pods_start)[idx]
        if pods_start is not None
        else np.zeros(len(idx), np.float32)
    )
    order = np.lexsort((start, -prio[idx], ~viol))  # violating group first
    idx = idx[order]
    k = 1
    while k < max(len(idx), 1) and k < cap:
        k *= 2
    idx = idx[:k]
    out = np.full(k, -1, np.int32)
    out[: len(idx)] = idx
    return out


def dense_start_ranks(starts) -> np.ndarray:
    """f32[M] dense ranks of f64 start times: rank comparisons on device are
    exactly the f64 time comparisons (f32 would quantize epoch seconds to
    ~128s and merge distinct start times)."""
    starts = np.asarray(starts, np.float64)
    _, inv = np.unique(starts, return_inverse=True)
    return inv.astype(np.float32)


_PREEMPT_EVAL_CACHE: dict = {}


def make_preempt_eval(cfg, unsched_taint_key: int):
    """Memoized jitted candidate evaluation (filter_batch +
    required_affinity_ok + preemption_candidates in ONE launch) — called
    eagerly these are ~30 op-by-op dispatches per preempt().  Memoized
    per (cfg, key) like make_sequential_scheduler's _SEQ_CACHE, so many
    Scheduler instances with one config share one pinned executable."""
    key = (cfg, unsched_taint_key)
    hit = _PREEMPT_EVAL_CACHE.get(key)
    if hit is not None:
        return hit
    from kubernetes_tpu.ops.predicates import (
        filter_batch,
        required_affinity_ok,
    )

    @jax.jit
    def run(cluster, batch):
        _, per_pred = filter_batch(cluster, batch, cfg, unsched_taint_key)
        aff_ok = required_affinity_ok(cluster, batch)
        return preemption_candidates(per_pred, cluster.valid, aff_ok)

    if len(_PREEMPT_EVAL_CACHE) > 64:
        _PREEMPT_EVAL_CACHE.clear()
    _PREEMPT_EVAL_CACHE[key] = run
    return run


def pick_preemption_node(encoder, pod, cands, arena, slots, violating, max_vols):
    """Shared host driver for the pick -> verify -> veto loop (used by both
    the scheduler's preempt and the extender's /preempt verb):

      1. preempt_one picks (node, victims) over the extended what-if arrays;
      2. verify_nomination re-runs the full object-level predicate set with
         the victims removed (the part the counting what-if cannot model —
         anti-affinity state);
      3. a veto masks the node and re-picks.

    Returns (node_row, victim_arena_indices, victim_pods, PreemptionResult)
    with node_row == -1 when preemption helps nowhere.

    The counting what-if is device-exact for resources, ports, disk
    conflicts AND identity-deduped volume attach budgets (VERDICT r4 #4),
    so the object-level verify_nomination pass runs only when the what-if
    cannot model the failure: required (anti-)affinity terms live in the
    cluster or on the preemptor, service-affinity pins are configured, or
    the pick produced ZERO victims (the original failure then lies outside
    the modeled predicate set).  KTPU_PREEMPT_VERIFY=always restores the
    unconditional debug-mode check."""
    pod_req_ext, requested_ext, allocatable_ext, pods_ext = (
        encoder.preemption_arrays(pod, max_vols)
    )
    # identity-deduped volume credit: zero the per-pod volume-count
    # columns (the linear path PARITY §3 documented) and let the vid
    # tables drive both the initial credit and the reprieve deltas
    vol_tables = encoder.victim_volume_tables(slots)
    R_plus2 = requested_ext.shape[1] - vol_tables[4].shape[1]
    pods_ext = pods_ext.copy()
    pods_ext[:, R_plus2:] = 0.0
    aff = pod.spec.affinity
    need_verify = (
        os.environ.get("KTPU_PREEMPT_VERIFY", "") == "always"
        or encoder.has_required_pod_terms()
        or (aff is not None
            and (aff.pod_affinity is not None
                 or aff.pod_anti_affinity is not None))
        or bool(encoder.service_affinity_keys)
    )
    start_ranks = dense_start_ranks(arena.start)
    cands = np.asarray(cands).copy()
    while cands.any():
        res = preempt_one(
            requested_ext,
            allocatable_ext,
            pod_req_ext,
            cands,
            arena.node,
            arena.priority,
            pods_ext,
            violating,
            start_ranks,
            slots,
            vol_tables=vol_tables,
            has_vols=True,
        )
        row = int(res.node)
        if row < 0:
            return -1, [], [], None
        victim_ms = np.nonzero(np.asarray(res.victim_mask))[0]
        victims = [
            encoder.pods[arena.keys[m]].pod
            for m in victim_ms
            if arena.keys[m] in encoder.pods
            and encoder.pods[arena.keys[m]].pod is not None
        ]
        if not (need_verify or len(victims) == 0):
            return row, victim_ms, victims, res
        if verify_nomination(encoder, pod, row, victims, max_vols):
            return row, victim_ms, victims, res
        cands[row] = False
    return -1, [], [], None


def verify_nomination(encoder, pod, row: int, victims, max_vols) -> bool:
    """Host-side nomination gate: re-run the full object-level predicate set
    on the picked node with the victims removed — the analog of
    selectVictimsOnNode's podFitsOnNode what-if (generic_scheduler.go:
    1096-1100), covering what the device counting what-if cannot (inter-pod
    anti-affinity state after victim removal).  Also catches the zero-victim
    false positive: a candidate node where the what-if "fits" with no
    evictions means the original failure lies outside the modeled predicate
    set, and this check vetoes it unless the pod genuinely fits."""
    from kubernetes_tpu.cpuref import CPUScheduler

    node = encoder._row_node.get(row)
    if node is None:
        return False
    vic = {(v.namespace, v.name) for v in victims}
    remaining = [
        rec.pod
        for rec in encoder.pods.values()
        if rec.pod is not None and rec.node_row >= 0 and rec.key not in vic
    ]
    nodes = [n for n in encoder._row_node.values() if n is not None]
    ref = CPUScheduler(
        nodes,
        remaining,
        services=list(encoder._service_selectors),
        max_vols=max_vols,
        pvs=list(encoder.pvs.values()),
        pvcs=list(encoder.pvcs.values()),
        storage_classes=list(encoder.storage_classes.values()),
        service_affinity_labels=[
            encoder.interner.string(k)
            for k in encoder.service_affinity_keys
        ],
    )
    return all(ref.predicates(pod, node).values())


def _exact_prio_sum(vic_m, pods_priority, seg, n_segments):
    """Per-node victim priority sum, exact for any i32 priorities.

    The reference sums int64(prio) + 2^31 per victim
    (pickOneNodeForPreemption criterion 3).  Without x64 we split each
    offset priority u = prio + 2^31 (uint32 range) into hi = u >> 16 and
    lo = u & 0xffff; per-node sums of hi and lo stay far inside i32 for any
    realistic victim count, and (hi_sum, lo_sum_carry_normalized) compares
    lexicographically identically to the exact 48-bit sum."""
    # offset into [0, 2^32): xor-ing the sign bit on the uint32 view equals
    # adding 2^31, mapping i32 priorities monotonically onto unsigned
    offs = pods_priority.astype(jnp.uint32) ^ jnp.uint32(0x80000000)
    hi = (offs >> 16).astype(jnp.int32)
    lo = (offs & 0xFFFF).astype(jnp.int32)
    ones = vic_m.astype(jnp.int32)
    hi_sum = jax.ops.segment_sum(hi * ones, seg, num_segments=n_segments)
    lo_sum = jax.ops.segment_sum(lo * ones, seg, num_segments=n_segments)
    # normalize the carry so (hi, lo) is a true lexicographic key
    hi_sum = hi_sum + (lo_sum >> 16)
    lo_sum = lo_sum & 0xFFFF
    return hi_sum, lo_sum


@partial(jax.jit, static_argnames=("has_vols",))
def preempt_one(
    requested: jnp.ndarray,     # f32[N, R'] current usage, extended columns
    allocatable: jnp.ndarray,   # f32[N, R'] limits, extended columns
    pod_req: jnp.ndarray,       # f32[R'] the preemptor's request
    candidates: jnp.ndarray,    # bool[N] from preemption_candidates
    pods_node: jnp.ndarray,     # i32[M] arena: pod -> node row (-1 unassigned)
    pods_priority: jnp.ndarray, # i32[M]
    pods_req: jnp.ndarray,      # f32[M, R'] per-pod usage, extended columns
                                # (volume-count columns ZEROED when
                                # vol_tables drive the identity credit)
    pods_violating: jnp.ndarray,  # bool[M] eviction would violate a PDB
    pods_start: jnp.ndarray,    # f32[M] start-time dense ranks
                                # (dense_start_ranks; order == f64 times)
    victim_slots: jnp.ndarray,  # i32[Kv] from sorted_victim_slots
    vol_tables=None,            # encoder.victim_volume_tables(slots):
                                # identity-deduped attach credit (shared
                                # volumes freed ONCE, and only when every
                                # holder is evicted) — VERDICT r4 #4
    has_vols: bool = False,     # static: vol_tables present (jit variant)
) -> PreemptionResult:
    N = requested.shape[0]
    M = pods_node.shape[0]
    # pad slots (-1) are redirected out of bounds and dropped — a plain
    # where(...,0) would race duplicate writes against arena index 0
    slot_idx = jnp.where(victim_slots >= 0, victim_slots, M)
    listed = jnp.zeros(M, bool).at[slot_idx].set(True, mode="drop")
    seg = jnp.where(pods_node >= 0, pods_node, N)
    freed_all = jax.ops.segment_sum(
        pods_req * listed[:, None].astype(jnp.float32), seg, num_segments=N + 1
    )[:N]                                                    # [N, R']
    if has_vols:
        slot_vids, vid_type, vid_total, vid_listed, freed_vol_init = vol_tables
        VT = freed_vol_init.shape[1]
        RV = requested.shape[1] - VT                         # first vol column
        # exact initial credit: a volume counts as freed iff ALL its
        # holders are listed victims (host-computed per identity)
        freed_all = freed_all.at[:, RV:].add(freed_vol_init)
    need = pod_req[None] > 0

    def fits(freed_row, node_row):
        return ~jnp.any(
            (pod_req > 0)
            & (requested[node_row] - freed_row + pod_req > allocatable[node_row])
        )

    fits_all = ~jnp.any(
        need & (requested - freed_all + pod_req[None] > allocatable),
        axis=-1,
    )
    possible = candidates & fits_all                         # [N]

    # ---- reprieve: re-add victims (PDB-violating first, priority desc)
    # while the pod still fits.  With vol_tables the carry also tracks
    # per-volume evicted-holder counts: reprieving the FIRST holder of a
    # fully-freed volume restores the attachment (delta 1); reprieving
    # further holders adds nothing — the exact inverse of the identity-
    # deduped initial credit.
    def step(carry, x):
        freed, evicted = carry
        if has_vols:
            m, vids = x
        else:
            m = x
        valid_slot = m >= 0
        mi = jnp.maximum(m, 0)
        n = jnp.clip(pods_node[mi], 0, N - 1)
        new_row = freed[n] - pods_req[mi]
        if has_vols:
            vv = jnp.where(vids >= 0, vids, vid_type.shape[0] - 1)
            was_full = (evicted[vv] >= vid_total[vv]) & (vids >= 0)
            delta = jnp.zeros(VT, jnp.float32).at[vid_type[vv]].add(
                was_full.astype(jnp.float32), mode="drop"
            )
            new_row = new_row.at[RV:].add(-delta)
        keep = fits(new_row, n) & valid_slot & possible[n]
        freed = freed.at[n].set(jnp.where(keep, new_row, freed[n]))
        if has_vols:
            evicted = jnp.where(
                keep, evicted.at[vv].add(-1, mode="drop"), evicted
            )
        return (freed, evicted), keep

    if has_vols:
        init_evicted = vid_listed
        xs = (victim_slots, slot_vids)
    else:
        init_evicted = jnp.zeros((1,), jnp.int32)
        xs = victim_slots
    (_, _), kept = jax.lax.scan(step, (freed_all, init_evicted), xs)
    kept_mask = jnp.zeros(M, bool).at[slot_idx].set(kept, mode="drop")
    vic_m = listed & ~kept_mask                              # final victims [M]

    ones = vic_m.astype(jnp.int32)
    n_victims = jax.ops.segment_sum(ones, seg, num_segments=N + 1)[:N]
    n_viol = jax.ops.segment_sum(
        (vic_m & pods_violating).astype(jnp.int32), seg, num_segments=N + 1
    )[:N]
    max_prio = jax.ops.segment_max(
        jnp.where(vic_m, pods_priority, INT_MIN), seg, num_segments=N + 1
    )[:N]
    sum_hi, sum_lo = _exact_prio_sum(vic_m, pods_priority, seg, N + 1)
    sum_hi, sum_lo = sum_hi[:N], sum_lo[:N]
    # criterion 5 key: earliest start among this node's highest-priority
    # victims (GetEarliestPodStartTime); later is better
    is_top = vic_m & (pods_priority == max_prio[jnp.clip(pods_node, 0, N - 1)])
    earliest_top = jax.ops.segment_min(
        jnp.where(is_top, pods_start, _F32_MAX), seg, num_segments=N + 1
    )[:N]

    # lexicographic pick (pickOneNodeForPreemption criteria 1-6):
    best = possible

    def _narrow_min(best, key):
        m = jnp.min(jnp.where(best, key, INT_MAX))
        return best & (key == m)

    best = _narrow_min(best, n_viol)          # 1. min PDB violations
    best = _narrow_min(best, max_prio)        # 2. min highest victim priority
    best = _narrow_min(best, sum_hi)          # 3. min priority sum (exact,
    best = _narrow_min(best, sum_lo)          #    split into hi/lo halves)
    best = _narrow_min(best, n_victims)       # 4. min victim count
    m5 = jnp.max(jnp.where(best, earliest_top, -_F32_MAX))
    best = best & (earliest_top == m5)        # 5. latest earliest start
    ok = jnp.any(possible)
    node = jnp.where(ok, jnp.argmax(best).astype(jnp.int32), -1)  # 6. first
    victim_mask = vic_m & (pods_node == node) & ok
    viol_count = jnp.sum(victim_mask & pods_violating).astype(jnp.int32)
    return PreemptionResult(
        node, victim_mask, jnp.sum(victim_mask), viol_count
    )
