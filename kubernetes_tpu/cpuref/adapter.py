"""CpuEngineAdapter: the degraded-mode engine behind the device breaker.

BASELINE names "graceful fallback to the CPU path" as part of the north
star; this adapter is that path's engine seam.  While the device breaker
(runtime/health.DeviceHealth) is open, Scheduler routes each cycle's
placement through the object-level golden scheduler (cpuref/reference.py)
instead of the XLA engine — same pods in, same winners-shape out
(i32[B] node ROWS, -1 = unschedulable), so the entire commit tail
(assume, bind, events, metrics, requeues, preemption bookkeeping) runs
unchanged and the audit trail is indistinguishable from a device cycle.

Equivalence contract (pinned by tests/test_device_faults.py): on the same
snapshot the adapter reproduces the device engine's placements —
  * sequential-commit semantics: pod i sees pods 0..i-1 of its own batch
    already placed (resources, ports, spread counts, affinity pairs);
  * selectHost parity: winner = ties[(last_index0 + i) % len(ties)] with
    ties enumerated in device ROW order (ops/select.py select_host);
  * extender verdicts fold in as the same mask/score addends;
  * nominated pods are charged to their nominated nodes (pass one of the
    two-pass evaluation), matching encode_nominated + the nominated block.
Scores are computed in Python floats vs the device's f32; the float-blend
priorities can drift by 1 (the documented parity tolerance, PARITY.md), so
bit-identity holds whenever score gaps exceed that drift — which the
degraded-path tests arrange, and real ties resolve identically because the
rotation index, not the float, picks the winner.

Framework tensor plugins and extenders need no special handling here:
both run HOST-side in _encode_and_dispatch before the engine choice, and
their verdicts arrive as the extra_mask/extra_score addends either engine
consumes.  The one deliberate non-goal: percentageOfNodesToScore sampling
is ignored (all nodes scanned — degraded mode trades a little extra CPU
for the simpler exact scan, and a superset scan can only improve
placement).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kubernetes_tpu.api.types import Pod
from kubernetes_tpu.codec.schema import DEFAULT_PRIORITY_WEIGHTS, PRIORITY_ORDER
from kubernetes_tpu.cpuref.reference import CPUScheduler


class CpuEngineAdapter:
    """Builds a CPUScheduler view of the live cache per cycle and runs the
    sequential-commit placement loop over it.  Stateless between calls —
    every batch re-reads the encoder's retained objects under the cache
    lock, so degraded cycles always see the freshest committed state (the
    same property a new device snapshot would have)."""

    def __init__(self, cache, config):
        self.cache = cache      # runtime.cache.SchedulerCache
        self.config = config    # runtime.scheduler.SchedulerConfig

    # ------------------------------------------------------------ plumbing

    def _golden(self, extra_pods: Sequence[Pod] = ()):
        """(CPUScheduler, nodes-in-row-order, name->row) from the encoder's
        retained objects.  Caller holds the cache lock."""
        enc = self.cache.encoder
        rows = sorted((row, name) for name, row in enc.node_rows.items())
        nodes = [enc._row_node[row] for row, _ in rows]
        row_of = {name: row for row, name in rows}
        pods = [
            rec.pod
            for rec in enc.pods.values()
            if rec.pod is not None and rec.pod.spec.node_name
        ]
        golden = CPUScheduler(
            nodes,
            pods + list(extra_pods),
            list(enc._service_selectors),
            max_vols=tuple(self.config.filter_config.max_vols),
            pvs=list(enc.pvs.values()),
            pvcs=list(enc.pvcs.values()),
            storage_classes=list(enc.storage_classes.values()),
            service_affinity_labels=[
                enc.interner.string(k) for k in enc.service_affinity_keys
            ],
        )
        return golden, nodes, row_of

    def _weights(self) -> Dict[str, float]:
        w = self.config.weights
        if w is None:
            w = DEFAULT_PRIORITY_WEIGHTS
        return dict(zip(PRIORITY_ORDER, np.asarray(w, np.float64).tolist()))

    # ------------------------------------------------------------- engine

    def schedule_batch(
        self,
        pods: Sequence[Pod],
        last_index0: int,
        extra_mask: Optional[np.ndarray] = None,
        extra_score: Optional[np.ndarray] = None,
        nominated: Sequence[Tuple[Pod, str]] = (),
        masked: frozenset = frozenset(),
        row_map: Optional[Dict[str, int]] = None,
    ) -> np.ndarray:
        """Place `pods` sequentially against the live cache state.

        extra_mask/extra_score are the device path's [Bp, N] extender/
        framework addends (row-indexed; Bp >= len(pods) from the pow2 pad);
        their COLUMNS are indexed by `row_map`, the snapshot-time
        name->row map the fan-out was built against — the live encoder's
        rows may have been recycled/regrown by informer threads since
        (scheduler.py documents this race for the extender path).  A node
        absent from row_map (added after the snapshot) is treated as
        masked when a mask exists: the device path would not have seen it
        either.  `masked` holds batch indices whose extender errored (the
        commit tail routes them by ext_failed regardless of the winner
        value).  Returns i32[len(pods)] LIVE device node rows (they feed
        enc.row_name), -1 = unschedulable."""
        hosts = np.full(len(pods), -1, np.int32)
        with self.cache._lock:
            nom_assumed = [
                p.assumed_on(node) for p, node in nominated
            ]
            golden, nodes, row_of = self._golden(extra_pods=nom_assumed)
            name_of_row = {row_of[n.name]: n.name for n in nodes}
            mask_col = row_of if row_map is None else row_map
            weights = self._weights()

            def mask_ok(i, node):
                if extra_mask is None:
                    return True
                col = mask_col.get(node.name)
                if col is None or col >= extra_mask.shape[1]:
                    return False  # node unknown to the snapshot/fan-out
                return bool(extra_mask[i, col])

            for i, pod in enumerate(pods):
                if i in masked:
                    continue
                feasible = [
                    node
                    for node in nodes
                    if mask_ok(i, node) and golden.fits(pod, node)
                ]
                if not feasible:
                    continue
                totals = golden.total_scores(pod, weights)
                scores = []
                for node in feasible:
                    s = float(totals.get(node.name, 0.0))
                    if extra_score is not None:
                        col = mask_col.get(node.name)
                        if col is not None and col < extra_score.shape[1]:
                            s += float(extra_score[i, col])
                    scores.append(s)
                best = max(scores)
                # ties enumerate in ROW order (feasible preserves `nodes`,
                # which is row-sorted) — the select_host rotation contract
                ties = [
                    row_of[node.name]
                    for node, s in zip(feasible, scores)
                    if s == best
                ]
                win_row = ties[(int(last_index0) + i) % len(ties)]
                hosts[i] = win_row
                # in-batch sequential commit: later pods see this placement
                win_name = name_of_row[win_row]
                assumed = pod.assumed_on(win_name)
                golden.pods.append(assumed)
                golden.by_node[win_name].append(assumed)
        return hosts

    # --------------------------------------------------------- preemption

    def preempt_candidates(self, pod: Pod, n_cap: int) -> np.ndarray:
        """bool[n_cap] by device row: nodes where preemption might help —
        the pod does not fit, but no UNRESOLVABLE predicate fails and its
        required-affinity rules hold (nodesWherePreemptionMightHelp,
        generic_scheduler.go:1013-1053 — the CPU stand-in for the device
        preempt eval while the breaker is open).  The host-side victim
        pick (models/preemption.pick_preemption_node) re-verifies every
        candidate, so a superset mask stays safe."""
        cands = np.zeros(int(n_cap), bool)
        with self.cache._lock:
            golden, nodes, row_of = self._golden()
            for node in nodes:
                preds = golden.predicates(pod, node)
                if all(preds.values()):
                    continue  # already fits: preemption not needed here
                if not all(
                    preds[p] for p in CPUScheduler.UNRESOLVABLE if p in preds
                ):
                    continue
                if not golden._affinity_rules_ok(pod, node):
                    continue
                row = row_of[node.name]
                if row < len(cands):
                    cands[row] = True
        return cands
