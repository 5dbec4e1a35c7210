"""Speculative parallel placement: the high-throughput engine.

The sequential-commit scan (models/batched.py) reproduces one-pod-at-a-time
semantics exactly, but a `lax.scan` step is latency-bound, so B pods cost B
sequential steps.  This engine places the WHOLE batch in one device launch:

  round r (all rounds run inside ONE jitted while_loop — no host round
  trips: the design goal is zero syncs between upload and the final
  hosts fetch):
    1. mask/score every remaining pod against the current in-loop cluster
       state (filter_batch + score_batch over the pods x nodes grid — MXU
       work), argmax with per-pod staggered tie-break
       (ops/select.select_hosts_batch);
    2. commit on device, in batch order: pod b is accepted iff its proposed
       node still fits the resources of b PLUS every earlier same-node
       proposer this round, none of b's host ports conflict with ports
       already claimed on the node or wanted by an earlier same-node
       proposer, and (affinity batches) no earlier accepted proposer this
       round creates a required anti-affinity violation with b in a shared
       topology domain.  "Earlier same-node proposer" is a strictly-lower-
       triangle incidence product — the conflict-repair bookkeeping is a
       handful of small matmuls, not a host loop.  Rejected pods get
       emask[b, node] = False (progress: a pod never re-picks a node it was
       bounced from) and go to round r+1 against the updated columns.

In-batch REQUIRED (anti-)affinity (VERDICT r3 #3 — previously scan-only):
the carry holds per-topology-pair extras (xaff/xanti/xforb/xpref, the
tensorization of predicateMetadata.AddPod, ref
algorithm/predicates/metadata.go:64-94) that the sequential scan keeps
per node by label group,
batch-updated once per round from that round's accepted placements via
einsums over the BatchAffinityState cross-match tensors.  Two orderings
keep this faithful to the sequential semantics:
  * bootstrap gating: a pod whose required affinity term has no match
    anywhere may self-bootstrap ONLY if no earlier-in-batch pod that could
    satisfy the term is still pending — so one group founder places first
    and mates then co-locate in its domain, exactly as the one-at-a-time
    scan would, instead of the whole group scattering in round 1;
  * deferred retirement: a pod with no feasible node stays active while
    the round commits anything (its mates may land and open domains);
    retirement happens on the first commit-free round, which bounds the
    loop (every round commits >= 1 pod, clears >= 1 emask bit, or is the
    last).
Nominated pods (preemptors awaiting victims' exit) join the commit check:
claims from >=-priority nominated pods on the proposed node are added to
the fit test (podFitsOnNode pass one, ref generic_scheduler.go:598-664);
their port/anti-affinity pass-one effects arrive host-precomputed through
extra_mask (models/batched.py encode_nominated_block), shared with the
sequential engine.

The commit is slightly more conservative than a sequential host commit:
earlier proposers count against a node's budget even if they themselves end
up bounced on ports, so an accepted placement NEVER overcommits, but a pod
can be bounced a round earlier than strictly necessary (it simply re-picks
next round).  Every PREDICATE is enforced on the accepted state.  In-batch
score freshness: resource balance, spreading counts AND the inter-pod-
affinity score all refresh between rounds from the carry.

Transfer discipline (the wire bills per leaf AND per byte):
  * the PodBatch/port/affinity tensors are packed into three flat buffers
    (codec/transfer.py) — one RTT per dtype kind instead of ~60;
  * the cluster snapshot should be device-put ONCE by the caller and
    chained between batches (the returned new_cluster reuses the resident
    static leaves) — bench.py does; the scheduler runtime uploads through
    the encoder's incremental device-snapshot cache.

Termination: each round every active pod is accepted (retired), bounced
(clears one emask bit), or — on a commit-free round — retired infeasible;
bounded by B + B*N rounds.  Typical convergence: round 1 commits ~all pods
(staggered ties make collisions rare by construction) — ~1 parallel launch
per batch instead of B scan steps, the path to the >=10k pods/s north star
(BASELINE.json).

Reference for the semantics being reproduced at batch scale:
core/generic_scheduler.go Schedule (:184-254) / selectHost (:284-296);
the 16-goroutine scan it replaces is workqueue.ParallelizeUntil at :518.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kubernetes_tpu.codec.schema import (
    ClusterTensors,
    DEFAULT_PRIORITY_WEIGHTS,
    FilterConfig,
    PodBatch,
    PRED_INDEX,
    PRIO_INDEX,
)
from kubernetes_tpu.codec.transfer import pack_tree, unpack_tree
from kubernetes_tpu.ops.predicates import filter_batch
from kubernetes_tpu.ops.priorities import (
    MAX_PRIORITY,
    pod_group_onehot,
    pod_spread_match,
    score_batch,
    spread_counts,
    spread_score_from_counts,
)
from kubernetes_tpu.ops.select import (
    TopKQuality,
    limit_feasible,
    num_feasible_nodes_device,
    select_hosts_batch,
    select_topk_batch,
)

_X = lax.Precision.HIGHEST  # exact f32 matmuls: these carry counts, not ML

# Test hook: route the CPU backend through the packed device path
# (_impl: device while_loop rounds + in-program lax.cond exactness redo)
# instead of the host-driven rounds, so the TPU program is testable on the
# CPU-only CI mesh.
FORCE_PACKED_PATH = False

from collections import OrderedDict

_SPEC_CACHE: "OrderedDict" = OrderedDict()
_SPEC_CACHE_CAP = 32  # bounds pinned executables (same policy as _SEQ_CACHE)


def make_speculative_scheduler(
    cfg: FilterConfig = FilterConfig(),
    weights=None,
    unsched_taint_key: int = 0,
    zone_key_id: int = 5,
    score_cfg=None,
    percentage_of_nodes_to_score: int = 100,
    hybrid: bool = True,
    donate_cluster: bool = False,
    quality_topk: int = 0,
):
    """Same call contract as make_sequential_scheduler:
    fn(cluster, pods, ports, last_index0, nominated=None, extra_mask=None,
    extra_score=None, aff_state=None) -> (hosts i32[B] (-1 unschedulable),
    new_cluster with committed requested/nonzero columns).  hosts is
    returned as a device array so the caller can overlap its fetch with the
    next batch's dispatch.

    Memoized by configuration (the _SEQ_CACHE policy): every Scheduler
    instance with the same knobs shares ONE jitted program, so e.g. the
    bench's raw-engine loop and its live-path Scheduler compile once.
    FORCE_PACKED_PATH is read per call, so the memo never staleness-locks
    the CPU test hook.

    quality_topk=K > 0 (STATIC, output-only — the placement-quality
    observatory seam, runtime/quality.py): the call returns
    (hosts, new_cluster, ops/select.TopKQuality) instead of the pair.
    Each pod's winner-pinned top-k rows + scores + feasible count are
    captured AT THE ROUND IT WAS ACCEPTED (so they reflect exactly the
    carry state its commit saw); the hybrid exactness redo returns the
    sequential scan's quality instead, so the pytree always describes
    the placements actually committed.  Winners are bit-identical
    flag-on/off (pinned by tests/test_quality.py).

    Buffer donation (accelerator device path only): donate_cluster=True
    donates the cluster (the in-place chained-state pattern): correct
    only for callers that consume the returned new_cluster and never
    touch the input again (bench.py's raw loop); the live Scheduler's
    resident snapshot cache must NOT donate."""
    key = (
        cfg,
        tuple(np.asarray(weights, np.float32)) if weights is not None else None,
        unsched_taint_key,
        zone_key_id,
        score_cfg,
        percentage_of_nodes_to_score,
        hybrid,
        donate_cluster,
        quality_topk,
    )
    hit = _SPEC_CACHE.get(key)
    if hit is not None:
        _SPEC_CACHE.move_to_end(key)
        return hit
    w_all = np.asarray(
        DEFAULT_PRIORITY_WEIGHTS if weights is None else weights, np.float32
    )
    w_ipa = float(w_all[PRIO_INDEX["InterPodAffinityPriority"]])
    # affinity batches move the IPA score from score_batch's static pass
    # into the per-round dynamic evaluation (it must see in-batch commits)
    w_no_ipa = w_all.copy()
    w_no_ipa[PRIO_INDEX["InterPodAffinityPriority"]] = 0.0
    hard_w = float(cfg.hard_pod_affinity_weight)

    def _round(cluster, pods, pod_ports, conflict, escore, nom, aff, c):
        """One propose-and-commit round (shared by the on-device while_loop
        and the host-driven CPU loop).  nom: NominatedState or None;
        aff: BatchAffinityState, LeanBatchAffinity, or None (every entry
        point accepts the lean form and densifies it in _parts /
        densify_batch_affinity)."""
        B = pods.valid.shape[0]
        N = cluster.allocatable.shape[0]
        reqf = pods.req.astype(jnp.float32)
        nzf = pods.nonzero_req.astype(jnp.float32)
        pports = pod_ports.astype(jnp.bool_)
        pports_f = pod_ports.astype(jnp.float32)
        conflict_f = conflict.astype(jnp.float32)
        tril = jnp.tril(jnp.ones((B, B), jnp.float32), k=-1)
        cl = dataclasses.replace(
            cluster, requested=c["req"], nonzero_req=c["nz"]
        )
        if aff is not None:
            topo = cluster.topo_pairs.astype(jnp.float32)     # [N, TP]
            # topology-key -> pair-slot masks (cheap broadcasts; XLA CSEs
            # them across the uses below)
            aff_kp = (
                pods.aff_term_topo_key[:, :, None]
                == cluster.pair_topo_key[None, None]
            )                                                 # [B, PT, TP]
            anti_kp = (
                pods.anti_term_topo_key[:, :, None]
                == cluster.pair_topo_key[None, None]
            )                                                 # [B, AT, TP]
            # bootstrap gating: pod i may self-bootstrap term t only when
            # no EARLIER-in-batch pod that could satisfy t is still pending
            # (batch order = the order the sequential scan would commit);
            # the gate folds into aff_term_self, so the SHARED
            # MatchInterPodAffinity predicate (ops/predicates.py) evaluates
            # the unioned (pre-batch | in-batch) state unchanged
            earlier_alive = tril * c["active"].astype(jnp.float32)[None, :]
            cb = jnp.einsum(
                "jit,ij->it", aff.aff_match.astype(jnp.float32),
                earlier_alive, precision=_X,
            ) <= 0                                            # [B, PT]
            pods_eval = dataclasses.replace(
                pods,
                aff_term_pairs=pods.aff_term_pairs | c["xaff"],
                anti_term_pairs=pods.anti_term_pairs | c["xanti"],
                forbidden_pairs=pods.forbidden_pairs | c["xforb"],
                aff_term_self=pods.aff_term_self & cb,
            )
        else:
            pods_eval = pods
        mask, _ = filter_batch(cl, pods_eval, cfg, unsched_taint_key,
                               need_per=False)
        # spread freshness (VERDICT r2 item 6): counts refresh between
        # repair rounds exactly like resources — base snapshot counts plus
        # the in-batch commits accumulated in the carry, so same-batch
        # service mates repel from round 2 on instead of piling up until
        # the next cycle's snapshot
        lean_spread = pods.spread_counts.shape[-1] != N
        w_use = (w_no_ipa if aff is not None else w_all)
        if lean_spread:
            # lean batches (every pod in <= 1 spread group): the whole
            # SelectorSpread score is a function of the pod's GROUP, so
            # compute it once per group over [G, N] (G ~ tens) and
            # broadcast with a one-hot matmul — 10-20x less work than the
            # per-pod [B, N] evaluation the generic path does.  The carry
            # tracks in-batch commits at group granularity ("spread"
            # [G, N]), which for single-group pods is exactly the
            # pod_spread_match bookkeeping.
            counts_g = cluster.group_counts.T + c["spread"]   # [G, N]
            scores_g = spread_score_from_counts(
                counts_g, cluster, zone_key_id)               # [G, N]
            onehot_g = pod_group_onehot(
                pods, cluster.group_counts.shape[1])          # [B, G]
            has_g = jnp.any(onehot_g > 0, axis=-1)
            sp = jnp.matmul(onehot_g, scores_g, precision=_X)
            # a groupless pod has zero counts everywhere -> score 10
            sp = jnp.where(has_g[:, None], sp, MAX_PRIORITY)
            w_use = np.array(w_use, np.float32)
            w_spread = float(w_use[PRIO_INDEX["SelectorSpreadPriority"]])
            w_use[PRIO_INDEX["SelectorSpreadPriority"]] = 0.0
            pods_r = pods
        else:
            pods_r = dataclasses.replace(
                pods, spread_counts=spread_counts(cl, pods) + c["spread"]
            )
        total, _ = score_batch(
            cl, pods_r, weights=w_use,
            score_cfg=score_cfg, zone_key_id=zone_key_id,
            skip_zero_weight=True, need_per=False,
        )
        if lean_spread:
            total = total + w_spread * sp
        mask = mask & c["active"][:, None] & c["emask"] & pods.valid[:, None]
        if aff is not None:
            # dynamic IPA score (interpod_affinity.go fScore) over
            # (pre-batch | in-batch) raw pair weights, renormalized per pod
            raw = jnp.matmul(
                pods.pref_pair_weights + c["xpref"], topo.T, precision=_X
            )                                                 # [B, N]
            big = jnp.float32(3.4e38)
            mn = jnp.min(
                jnp.where(cluster.valid[None], raw, big), axis=1,
                keepdims=True,
            )
            mx = jnp.max(
                jnp.where(cluster.valid[None], raw, -big), axis=1,
                keepdims=True,
            )
            spr = mx - mn
            ipa = jnp.where(
                spr > 0, jnp.floor(MAX_PRIORITY * (raw - mn) / spr), 0.0
            )
            total = total + w_ipa * jnp.where(cluster.valid[None], ipa, 0.0)
        if percentage_of_nodes_to_score < 100:  # 0 = adaptive
            lim = num_feasible_nodes_device(
                jnp.sum(cl.valid.astype(jnp.int32)),
                percentage_of_nodes_to_score,
            )
            starts = c["li"] + jnp.arange(B, dtype=jnp.int32)
            mask = jax.vmap(limit_feasible, in_axes=(0, None, 0))(
                mask, lim, starts
            )
        if escore is not None:
            total = total + escore
        hosts, feasible = select_hosts_batch(total, mask, c["li"])
        prop = c["active"] & feasible            # proposers this round
        # earlier same-node proposers: an equality comparison masked by
        # the strict lower triangle (batch order = commit order) — B^2
        # elementwise work, NOT a [B,N] incidence matmul, so the commit
        # bookkeeping stays cheap on the CPU fallback too
        same = (
            (hosts[:, None] == hosts[None, :])
            & prop[:, None] & prop[None, :]
        )
        prior = same.astype(jnp.float32) * tril              # [B, B]
        cum_req = jnp.matmul(prior, reqf, precision=_X)      # [B, R]
        node_req = c["req"][hosts]                           # [B, R]
        alloc_h = cluster.allocatable[hosts]
        if nom is not None:
            # podFitsOnNode pass one: nominated pods with priority >= this
            # pod's claim resources on their nominated node (resource fit
            # is monotone, so pass one implies the no-nominated pass two)
            nw = (
                (nom.prio[None, :] >= pods.priority[:, None])
                & (nom.node[None, :] >= 0)
                & (nom.node[None, :] == hosts[:, None])
            ).astype(jnp.float32)                            # [B, K]
            nom_extra = jnp.matmul(nw, nom.req, precision=_X)  # [B, R]
        else:
            nom_extra = jnp.float32(0.0)
        over = (reqf > 0) & (node_req + cum_req + nom_extra + reqf > alloc_h)
        fits = ~jnp.any(over, axis=1)
        # ports: conflict with claims already on the node OR with an
        # earlier same-node proposer's wanted ports
        prior_ports = jnp.matmul(prior, pports_f, precision=_X) > 0
        claimed_h = c["claimed"][hosts]                      # [B, PV]
        blocked = jnp.matmul(
            (claimed_h | prior_ports).astype(jnp.float32),
            conflict_f, precision=_X,
        ) > 0
        pconf = jnp.any(pports & blocked, axis=1)
        accept = prop & fits & ~pconf
        if aff is not None:
            # same-round required-anti ordering: pod b is rejected when an
            # earlier proposer j shares a topology domain with b under one
            # of b's anti terms (j matches the term) or one of j's anti
            # terms (b matches it).  D[o, t, c] = "candidate c's proposed
            # node is in owner o's term-t domain at o's proposed node".
            H = topo[hosts]                                   # [B, TP]
            a_own = anti_kp.astype(jnp.float32) * H[:, None, :]  # [B, AT, TP]
            D = jnp.einsum("otp,cp->otc", a_own, H, precision=_X) > 0
            # am1[b, t, j] = "pod j matches pod b's required anti term t"
            am1 = jnp.transpose(aff.anti_match, (1, 2, 0))    # [B, AT, B]
            v1 = jnp.any(D & am1, axis=1)                     # [b, j]
            v2 = jnp.any(D & aff.anti_own, axis=1)            # [j, b]
            conf_ba = v1 | v2.T                               # [b, j]
            earlier_prop = (tril > 0) & prop[None, :]
            aviol = jnp.any(conf_ba & earlier_prop, axis=1)
            accept = accept & ~aviol
        # ---- hybrid exactness sentinel (VERDICT r4 #3): the engine's only
        # semantic divergence from the one-at-a-time scan is ORDER
        # INVERSION — a later pod committing while an earlier pod is
        # passed over (bounced or still infeasible), where the commit can
        # INTERFERE with what the earlier pod would have gotten
        # one-at-a-time.  Interference = j's accepted node was feasible
        # for i this round (capacity/ports race), or i and j are related
        # through required (anti-)affinity terms in either direction
        # (domain races, including a later mate opening a domain the scan
        # would never have opened for i).  When the flag trips, schedule()
        # discards the speculative result and redoes the batch through
        # the exact sequential scan — so the scheduled/unschedulable
        # split always matches scan semantics.  Orderly multi-round
        # convergence (founder-then-mates bootstrap chains) does NOT trip
        # it: gated mates are infeasible (empty mask row) and unrelated
        # to other groups' founders.
        if aff is not None:
            passed_over = c["active"] & ~accept          # [i]
            later = tril.T > 0                           # [i, j]: j > i
            interf = mask[:, hosts]                      # [i, j] = mask[i, host_j]
            a_any = jnp.any(aff.aff_match, axis=2)       # [x, y]: x sats y's aff
            n_any = jnp.any(aff.anti_match, axis=2)      # [x, y]: x matches y's anti
            rel = a_any | a_any.T | n_any | n_any.T      # either direction
            interf = interf | rel
            inv_new = jnp.any(
                passed_over[:, None] & accept[None, :] & later & interf
            )
            # an in-batch anti-affinity bounce: the bounced pod re-picks
            # next round against the carry, an answer that is neither
            # the batch-start one nor the one-at-a-time one, so the
            # batch takes the exact scan (the inversion term above only
            # sees LATER accepted peers)
            inv_new = inv_new | jnp.any(prop & aviol)
        else:
            # plain batches: the inversion term is subsumed by the other
            # two sentinels, so skip its [B, B] work on the hot path.
            # Invariant: a passed-over pod is either infeasible this
            # round (it retires with hosts=-1 -> the unscheduled sentinel
            # fires) or bounced — and in any round with a bounce, the
            # EARLIEST bounced proposer on that node has only accepted
            # pods before it (prior_acc == prior for it), so its bounce
            # is a real_bounce and that sentinel fires.  This subsumption
            # argument does NOT carry to affinity batches (aviol bounces
            # are excluded from real_bounce; domain openings retire
            # nothing), which keep the full inversion term above.
            inv_new = jnp.asarray(False)
        accf = accept[:, None].astype(jnp.float32)
        # the accept pass is conservative (earlier proposers count even
        # if they themselves bounce), which never overcommits but can
        # bounce a pod that would fit the truly-accepted state.  Only
        # ban the node (emask clear) when the bounce ALSO holds against
        # accepted-only prior state — a conservatively-bounced pod keeps
        # the node and retries next round.
        prior_acc = prior * accept[None, :].astype(jnp.float32)
        cum_acc = jnp.matmul(prior_acc, reqf, precision=_X)
        over_acc = (reqf > 0) & (node_req + cum_acc + nom_extra + reqf > alloc_h)
        fits_acc = ~jnp.any(over_acc, axis=1)
        prior_ports_acc = jnp.matmul(prior_acc, pports_f, precision=_X) > 0
        blocked_acc = jnp.matmul(
            (claimed_h | prior_ports_acc).astype(jnp.float32),
            conflict_f, precision=_X,
        ) > 0
        pconf_acc = jnp.any(pports & blocked_acc, axis=1)
        real_bounce = prop & ~accept & (~fits_acc | pconf_acc)
        if aff is not None:
            # an anti-violation against an ACCEPTED peer needs no emask
            # ban: next round's xanti/xforb exclude the whole domain
            aviol_acc = jnp.any(
                conf_ba & (tril > 0) & accept[None, :], axis=1
            )
            real_bounce = real_bounce & ~aviol_acc
        acc_node = accf * (
            hosts[:, None] == jnp.arange(N, dtype=jnp.int32)[None, :]
        ).astype(jnp.float32)                                # [B, N]
        if lean_spread:
            # group-granular commit counts ([G, N] carry)
            spread_next = c["spread"] + jnp.matmul(
                onehot_g.T, acc_node, precision=_X)
        else:
            # the SAME AND-subset match the sequential scan uses
            # (ops/priorities.py pod_spread_match)
            spread_match = pod_spread_match(
                pods, cluster.group_counts.shape[1])         # [B, B] [i, j]
            spread_next = c["spread"] + jnp.matmul(
                spread_match, acc_node, precision=_X)
        # committed state lands via scatter-add on the node axis (a
        # segment-sum; XLA lowers it to a cheap scatter on every
        # backend, where the old one_hot.T matmuls cost B*N*R flops)
        out = {
            "hosts": jnp.where(accept, hosts, c["hosts"]),
            "req": c["req"].at[hosts].add(reqf * accf),
            "nz": c["nz"].at[hosts].add(nzf * accf),
            "spread": spread_next,
            "claimed": c["claimed"].at[hosts].max(
                pports & accept[:, None]
            ),
            # really-bounced proposers never re-pick the node that
            # bounced them (progress: the first active proposer of any
            # contended node is always accepted or really bounced)
            "emask": c["emask"] & ~(
                real_bounce[:, None]
                & (jnp.arange(N, dtype=jnp.int32)[None, :]
                   == hosts[:, None])
            ),
            "li": c["li"] + jnp.int32(B),
            # the three contention signals the hybrid redo triggers on
            # (see schedule()): order inversion with interference, any
            # REAL capacity/port bounce (under pressure, round-1
            # simultaneity alone can change the packing — different
            # tie-break SETS — without any pod being passed over), and
            # any pod left unscheduled (checked host-side on the result)
            "inv": c["inv"] | inv_new | jnp.any(real_bounce),
        }
        if quality_topk:
            # quality top-k (static output-only flag): capture each
            # accepted pod's winner-pinned ranking + feasible count AT
            # ITS COMMIT ROUND, off the exact (mask, total, hosts) the
            # acceptance above used; bounced/pending pods keep -1 until
            # their round, retired-infeasible pods keep -1 forever
            qb = select_topk_batch(
                total, mask, hosts, feasible, min(quality_topk, N)
            )
            upd = accept[:, None]
            out["topn"] = jnp.where(upd, qb.top_nodes, c["topn"])
            out["tops"] = jnp.where(upd, qb.top_scores, c["tops"])
            out["feas"] = jnp.where(accept, qb.feasible, c["feas"])
        if aff is None:
            # retired: accepted, or nothing feasible this round
            out["active"] = c["active"] & feasible & ~accept
        else:
            # deferred retirement: while the round commits anything, an
            # infeasible pod stays active (a mate's landing may open its
            # domain next round).  A commit-free round retires only the
            # FIRST infeasible pod in batch order — exactly the pod the
            # sequential scan would fail next — so a later founder whose
            # bootstrap was gated by that pod gets its round with the
            # blocker finally dead instead of being mass-retired with it.
            any_acc = jnp.any(accept)
            inf = c["active"] & ~feasible
            first_inf = inf & (jnp.cumsum(inf.astype(jnp.int32)) == 1)
            out["active"] = (
                (c["active"] & feasible & ~accept)
                | jnp.where(any_acc, inf, inf & ~first_inf)
            )
            # predicateMetadata.AddPod analog, batched over this round's
            # accepted placements: their topology pairs flow into the
            # pending pods' affinity state for the next round
            accN = accf * H                                   # [B(j), TP]
            am_f = aff.aff_match.astype(jnp.float32)
            nm_f = aff.anti_match.astype(jnp.float32)
            out["xaff"] = c["xaff"] | (
                (jnp.einsum("jit,jp->itp", am_f, accN, precision=_X) > 0)
                & aff_kp
            )
            out["xanti"] = c["xanti"] | (
                (jnp.einsum("jit,jp->itp", nm_f, accN, precision=_X) > 0)
                & anti_kp
            )
            keyed_anti = anti_kp.astype(jnp.float32) * accN[:, None, :]
            out["xforb"] = c["xforb"] | (
                jnp.einsum(
                    "jti,jtp->ip", aff.anti_own.astype(jnp.float32),
                    keyed_anti, precision=_X,
                ) > 0
            )
            keyed_aff = aff_kp.astype(jnp.float32) * accN[:, None, :]
            xpref = c["xpref"] + hard_w * jnp.einsum(
                "jti,jtp->ip", aff.aff_own.astype(jnp.float32), keyed_aff,
                precision=_X,
            )
            # preferred (soft) terms, both directions (scan parity):
            # 1. pending pods' own preferred terms the accepted pods match
            pref_kp = (
                aff.pref_topo_key[:, :, None]
                == cluster.pair_topo_key[None, None]
            )                                                 # [B, PP, TP]
            m1 = jnp.einsum(
                "jit,jp->itp", aff.pref_match.astype(jnp.float32), accN,
                precision=_X,
            )
            xpref = xpref + jnp.sum(
                m1 * aff.pref_weight[:, :, None]
                * pref_kp.astype(jnp.float32),
                axis=1,
            )
            # 2. the accepted pods' preferred terms add +-w per matching
            #    pending pod over the landing domain
            keyed_pref = pref_kp.astype(jnp.float32) * accN[:, None, :]
            xpref = xpref + jnp.einsum(
                "jti,jt,jtp->ip", aff.pref_own.astype(jnp.float32),
                aff.pref_weight, keyed_pref, precision=_X,
            )
            out["xpref"] = xpref
        return out

    def _more_rounds(c, aff):
        """Another round while a pod is active; an affinity batch whose
        sentinel tripped stops at once, since the exact redo answers it
        (its rounds would otherwise go on re-picking, one bounce a pod)."""
        if hybrid and aff is not None:
            return jnp.any(c["active"]) & ~c["inv"]
        return jnp.any(c["active"])

    def _init_carry(cluster, pods, pod_ports, last_index0, emask0, has_aff):
        B = pods.valid.shape[0]
        N = cluster.allocatable.shape[0]
        # lean batches carry in-batch spread commits per GROUP (see _round)
        lean_spread = pods.spread_counts.shape[-1] != N
        S = cluster.group_counts.shape[1] if lean_spread else B
        c = {
            "hosts": jnp.full((B,), -1, jnp.int32),
            "req": cluster.requested.astype(jnp.float32),
            "nz": cluster.nonzero_req.astype(jnp.float32),
            "spread": jnp.zeros((S, N), jnp.float32),
            "claimed": jnp.zeros((N, pod_ports.shape[1]), jnp.bool_),
            "emask": emask0,
            "active": pods.valid,
            "li": jnp.asarray(last_index0, jnp.int32),
            "inv": jnp.asarray(False),
        }
        if has_aff:
            TP = cluster.topo_pairs.shape[1]
            PT = pods.aff_term_pairs.shape[1]
            AT = pods.anti_term_pairs.shape[1]
            c["xaff"] = jnp.zeros((B, PT, TP), jnp.bool_)
            c["xanti"] = jnp.zeros((B, AT, TP), jnp.bool_)
            c["xforb"] = jnp.zeros((B, TP), jnp.bool_)
            c["xpref"] = jnp.zeros((B, TP), jnp.float32)
        if quality_topk:
            tkq = min(quality_topk, N)
            c["topn"] = jnp.full((B, tkq), -1, jnp.int32)
            c["tops"] = jnp.zeros((B, tkq), jnp.float32)
            c["feas"] = jnp.zeros((B,), jnp.int32)
        return c

    def _parts(tree):
        from kubernetes_tpu.models.batched import (
            LeanBatchAffinity,
            densify_batch_affinity,
        )

        pods = tree["pods"]
        aff = tree.get("aff")
        if isinstance(aff, LeanBatchAffinity):
            # only the factors crossed the link; rebuild the dense
            # cross-match tensors on device (one gather per family).
            # _parts is the single chokepoint every jitted path
            # (_packed, _round_host, _carry_init) funnels through.
            aff = densify_batch_affinity(aff)
        return (
            pods, tree["pp"], tree["cf"], tree.get("emask"),
            tree.get("escore"), tree.get("nom"), aff,
        )

    def _impl(cluster, tree, last_index0):
        pods, pod_ports, conflict, emask0, escore, nom, aff = _parts(tree)
        # the exact scan takes the factored form, as it crossed the link
        lean_aff = tree.get("aff")
        B = pods.valid.shape[0]
        N = cluster.allocatable.shape[0]
        if emask0 is None:
            emask0 = jnp.ones((B, N), jnp.bool_)
        else:
            emask0 = emask0.astype(jnp.bool_)
        init = _init_carry(
            cluster, pods, pod_ports, last_index0, emask0, aff is not None
        )
        out = lax.while_loop(
            lambda c: _more_rounds(c, aff),
            lambda c: _round(
                cluster, pods, pod_ports, conflict, escore, nom, aff, c
            ),
            init,
        )
        rounds = (out["li"] - jnp.asarray(last_index0, jnp.int32)) // B
        # third contention sentinel, ON DEVICE: a pod left unscheduled
        # means capacity/domain pressure, under which any placement
        # difference can change the split
        inv = out["inv"] | jnp.any(pods.valid & (out["hosts"] < 0))
        if hybrid:
            # device-resident exactness redo: fold the sequential-scan
            # fallback into the SAME program behind lax.cond (XLA executes
            # only the taken branch), so the caller never syncs on the
            # sentinel — the old host-side bool(np.asarray(inv)) check
            # serialized the whole pipeline on device compute + a scalar
            # D2H RTT every batch.  Uncontended batches pay one predicate;
            # contended ones run the exact scan on device.
            from kubernetes_tpu.models.batched import BatchPortState

            # .jitted = the raw traceable fn (schedule_entry's host-side
            # device_put wrapper must not run inside this traced branch)
            seq = _exact_scan().jitted
            ports_state = BatchPortState(pod_ports, conflict)

            def _redo(_):
                souts = seq(
                    cluster, pods, ports_state, last_index0, nom,
                    emask0, escore, lean_aff,
                )
                h2, c2 = souts[0], souts[1]
                base = (
                    h2.astype(jnp.int32),
                    c2.requested.astype(jnp.float32),
                    c2.nonzero_req.astype(jnp.float32),
                )
                if quality_topk:
                    # the redo's quality describes the placements
                    # actually committed (the scan's), same widths by
                    # construction (same N, same static K)
                    q2 = souts[2]
                    base = base + (q2.top_nodes, q2.top_scores, q2.feasible)
                return base

            def _keep(_):
                base = (
                    out["hosts"].astype(jnp.int32),
                    out["req"].astype(jnp.float32),
                    out["nz"].astype(jnp.float32),
                )
                if quality_topk:
                    base = base + (out["topn"], out["tops"], out["feas"])
                return base

            picked = lax.cond(inv, _redo, _keep, None)
            hosts, req, nz = picked[:3]
            qual = TopKQuality(*picked[3:]) if quality_topk else None
            return hosts, req, nz, rounds, inv, qual
        qual = (
            TopKQuality(out["topn"], out["tops"], out["feas"])
            if quality_topk else None
        )
        return out["hosts"], out["req"], out["nz"], rounds, inv, qual

    @lru_cache(maxsize=64)
    def _packed(meta):
        def run(cluster, bufs, last_index0):
            tree = unpack_tree(bufs, meta)
            hosts, req, nz, rounds, inv, qual = _impl(
                cluster, tree, last_index0
            )
            # new_cluster is assembled INSIDE the jit so that under
            # donation the untouched static leaves alias input->output
            # (identity) and req/nz land in the donated buffers — the
            # in-place chained-state update
            new_cluster = dataclasses.replace(
                cluster, requested=req, nonzero_req=nz
            )
            return hosts, new_cluster, rounds, inv, qual

        # cluster donation is the maker's opt-in for chained-state
        # callers.  The packed batch buffers are not donated: no output
        # has their shape, so the chip's compiler cannot use them ("Some
        # donated buffers were not usable", PR 21's chip run).  XLA:CPU
        # implements no donation (FORCE_PACKED_PATH tests run this path
        # on cpu) — plain jit there avoids per-call donation warnings.
        donate = (
            (0,) if donate_cluster and jax.default_backend() != "cpu"
            else ()
        )
        return jax.jit(run, donate_argnums=donate)

    # ---- CPU path: host-driven rounds.  XLA:CPU executes while_loop bodies
    # without intra-op thread parallelism, so the SAME round as a
    # free-standing jit runs ~8x faster on the multicore host; the handful
    # of tiny host syncs per batch cost nothing there.

    @lru_cache(maxsize=64)
    def _materialize(meta):
        """Unpack + densify ONCE per batch: the per-round jits below take
        the materialized parts pytree directly, so the lean affinity
        state's dense reconstruction doesn't repeat every repair round."""

        @jax.jit
        def run(bufs):
            return _parts(unpack_tree(bufs, meta))

        return run

    @jax.jit
    def _round_host(cluster, parts, c):
        pods, pod_ports, conflict, _em, escore, nom, aff = parts
        return _round(
            cluster, pods, pod_ports, conflict, escore, nom, aff, c
        )

    @jax.jit
    def _carry_init(cluster, parts, last_index0):
        pods, pod_ports, _cf, emask0, _es, _nom, aff = parts
        B = pods.valid.shape[0]
        N = cluster.allocatable.shape[0]
        if emask0 is None:
            emask0 = jnp.ones((B, N), jnp.bool_)
        else:
            emask0 = emask0.astype(jnp.bool_)
        return _init_carry(
            cluster, pods, pod_ports, last_index0, emask0, aff is not None
        )

    def _host_rounds(cluster, bufs, meta, last_index0):
        parts = _materialize(meta)(bufs)
        c = _carry_init(cluster, parts, np.int32(last_index0))
        rounds = 0
        while bool(np.asarray(_more_rounds(c, parts[6]))):
            c = _round_host(cluster, parts, c)
            rounds += 1
        qual = (
            TopKQuality(c["topn"], c["tops"], c["feas"])
            if quality_topk else None
        )
        return c["hosts"], c["req"], c["nz"], rounds, c["inv"], qual

    def _exact_scan():
        """The memoized sequential scan both redo paths share (in-_impl
        lax.cond on device, host-side redo on CPU) — one construction
        site so the two cannot diverge.  make_sequential_scheduler is
        _SEQ_CACHE-memoized, so calling per redo costs nothing."""
        from kubernetes_tpu.models.batched import make_sequential_scheduler

        return make_sequential_scheduler(
            cfg=cfg, weights=weights,
            unsched_taint_key=unsched_taint_key,
            zone_key_id=zone_key_id, score_cfg=score_cfg,
            percentage_of_nodes_to_score=percentage_of_nodes_to_score,
            quality_topk=quality_topk,
        )

    def schedule(cluster: ClusterTensors, pods: PodBatch, ports,
                 last_index0, nominated=None, extra_mask=None,
                 extra_score=None, aff_state=None):
        on_cpu = jax.default_backend() == "cpu" and not FORCE_PACKED_PATH
        tree = {"pods": pods, "pp": ports.pod_ports, "cf": ports.conflict}
        if extra_mask is not None:
            tree["emask"] = np.asarray(extra_mask, bool)
        if extra_score is not None:
            tree["escore"] = np.asarray(extra_score, np.float32)
        if nominated is not None:
            tree["nom"] = nominated
        if aff_state is not None:
            tree["aff"] = aff_state
        # the optional extras ride the same packed buffers (<=3 RTTs); the
        # tree's key set is part of meta, so each combination jits once
        bufs, meta = pack_tree(tree)
        if not on_cpu:
            # explicit async copy of the packed buffers (codec/
            # transfer.py's wire rules).  A mesh-sharded cluster
            # (multi-chip live path) pins the launch to its mesh: the
            # packed buffers replicate there instead of committing to
            # device 0 (which would conflict).
            from kubernetes_tpu.parallel.mesh import (
                replicated_on_cluster_mesh,
            )

            from kubernetes_tpu.codec.transfer import note_transfer_tree

            note_transfer_tree("h2d", "batch_replicate", bufs)
            dst = replicated_on_cluster_mesh(cluster)
            bufs = (
                jax.device_put(bufs, dst)
                if dst is not None else jax.device_put(bufs)
            )
        if on_cpu:
            hosts, req, nz, rounds, inv, qual = _host_rounds(
                cluster, bufs, meta, last_index0
            )
        else:
            hosts, new_cluster, rounds, inv, qual = _packed(meta)(
                cluster, bufs, np.int32(last_index0)
            )
            # the exactness redo already ran ON DEVICE behind lax.cond
            # (_impl), so nothing here syncs: hosts/new_cluster are final
            # and the pipeline stays fully async.  last_redo is the device
            # sentinel scalar — fetching it (bool()/int()) blocks on the
            # batch, so only observability/tests should touch it.
            schedule.last_rounds = rounds
            schedule.last_redo = inv if hybrid else False
            if quality_topk:
                return hosts, new_cluster, qual
            return hosts, new_cluster
        schedule.last_rounds = rounds  # observability: repair rounds used
        schedule.last_redo = False
        if hybrid and not bool(np.asarray(inv)):
            # CPU path (host-driven rounds): the unscheduled-pod sentinel
            # is checked host-side — hosts are already host-resident
            hn = np.asarray(hosts)
            valid = np.asarray(pods.valid, bool)
            inv = bool((hn[valid] < 0).any())
        if hybrid and bool(np.asarray(inv)):
            # order inversion with interference detected: the split could
            # deviate from one-at-a-time semantics, so redo the WHOLE
            # batch through the exact sequential scan (the speculative
            # commits above never touched the caller's cluster).  This
            # costs one scan on the contended batches only — uncontended
            # batches (the common case: round 1 commits everything, or
            # orderly founder->mates chains) keep the parallel fast path.
            # With quality on the scan's own TopKQuality rides along as
            # the third output — same arity either way.
            schedule.last_redo = True
            return _exact_scan()(
                cluster, pods, ports, last_index0, nominated,
                extra_mask, extra_score, aff_state,
            )
        new_cluster = dataclasses.replace(cluster, requested=req, nonzero_req=nz)
        if quality_topk:
            return hosts, new_cluster, qual
        return hosts, new_cluster

    # engine identity tag (see models/batched.py): multi-round placement
    # with repair — NOT sequential-commit ordered; gang scheduling's
    # cross-gang drop guard must never run on this engine
    schedule.engine_kind = "speculative"
    # the jitted packed program per meta — what the device path
    # launches; the compile tests lower it for a described chip
    schedule.packed = _packed
    # quality variants return (hosts, new_cluster, TopKQuality); 0 = off
    schedule.quality_topk = quality_topk
    _SPEC_CACHE[key] = schedule
    while len(_SPEC_CACHE) > _SPEC_CACHE_CAP:
        _SPEC_CACHE.popitem(last=False)
    return schedule
