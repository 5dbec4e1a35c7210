"""Incremental snapshot encoder: API objects -> device tensors.

The TPU-native redesign of the scheduler cache's snapshot path
(ref pkg/scheduler/internal/cache/cache.go:210-222 UpdateNodeInfoSnapshot):
node and pod mutations update numpy arenas in place (the analog of the
generation-numbered NodeInfo list), and `snapshot()` emits a `ClusterTensors`
copy tagged with a generation counter.  String work (label interning, selector
matching against existing pods) happens here, vectorized over numpy columns,
so the device kernels see only integer ids — the tensorization of
predicateMetadata's topologyPairsMaps (algorithm/predicates/metadata.go:64-94).

Inter-pod-affinity bookkeeping: existing pods' (anti-)affinity terms are
grouped by signature (selector, namespaces, topologyKey, kind, weight) — pods
stamped out by one controller share one group — and each group maintains a
per-topology-pair member count.  Encoding an incoming pod evaluates each
group's selector against that one pod (cheap) instead of scanning every
existing pod (the same asymptotic trick as the reference's metadata maps).
"""

from __future__ import annotations

import contextlib
import dataclasses
from collections import Counter
from dataclasses import dataclass, field
from itertools import chain
from typing import (
    Callable, Dict, List, NamedTuple, Optional, Sequence, Set, Tuple,
)

import numpy as np

from kubernetes_tpu.api import labels as klabels
from kubernetes_tpu.api.types import (
    DEFAULT_MEMORY_REQUEST,
    DEFAULT_MILLI_CPU_REQUEST,
    Node,
    Pod,
    PodAffinityTerm,
    RESOURCE_CPU,
    RESOURCE_EPHEMERAL_STORAGE,
    RESOURCE_MEMORY,
    RESOURCE_PODS,
)
from kubernetes_tpu.codec.interner import Interner
from kubernetes_tpu.codec.schema import (
    ClusterTensors,
    EFFECT_CODES,
    FIELD_NODE_NAME,
    NUM_VOL_TYPES,
    PAD,
    PadDims,
    PairRows,
    PodBatch,
    RES_EPHEMERAL,
    RES_EXT0,
    RES_MEMORY,
    RES_MILLICPU,
    RES_PODS,
    SEL_OP_CODES,
    TOL_OP_CODES,
    VOL_AZURE,
    VOL_CINDER,
    VOL_CSI,
    VOL_EBS,
    VOL_GCE,
    WILDCARD,
    _pow2,
    factor_row_limit,
)

def normalized_image(name: str) -> str:
    """priorities/image_locality.go:99-109 normalizedImageName: append the
    default tag when the reference has none after the last path segment."""
    if name.rfind(":") <= name.rfind("/"):
        return name + ":latest"
    return name


HOSTNAME_KEY = "kubernetes.io/hostname"
ZONE_KEY = "failure-domain.beta.kubernetes.io/zone"
REGION_KEY = "failure-domain.beta.kubernetes.io/region"
# synthetic topology key for GetZoneKey (pkg/util/node/node.go:126-143):
# the SelectorSpread zone reduce groups nodes by region+zone CONCAT, not the
# zone label alone.  The NUL prefix keeps it out of any user label vocabulary.
GETZONE_KEY = "\x00getzonekey"

# kinds of existing-pod affinity term groups
K_ANTI_REQ, K_ANTI_PREF, K_AFF_REQ, K_AFF_PREF = 0, 1, 2, 3

# attachable-volumes-* allocatable key -> attach-count column (ref the
# AttachVolumeLimit feature's allocatable keys); the one mapping both the
# per-node and bulk ingest paths consume (_vol_limit_col)
_VOL_LIMIT_COLS = {
    "attachable-volumes-aws-ebs": VOL_EBS,
    "attachable-volumes-gce-pd": VOL_GCE,
    "attachable-volumes-azure-disk": VOL_AZURE,
}


def _sel_requirements(raw_selector: Optional[dict]) -> Optional[klabels.Selector]:
    return klabels.selector_from_label_selector(raw_selector)


class PodsArena(NamedTuple):
    """Assigned-pod arena view for preemption what-ifs (see pods_snapshot)."""

    node: np.ndarray        # i32[M] node row (-1 unassigned)
    priority: np.ndarray    # i32[M]
    req: np.ndarray         # f32[M, R]
    nonzero: np.ndarray     # f32[M, 2]
    valid: np.ndarray       # bool[M] assigned & alive
    start: np.ndarray       # f64[M] status.startTime epoch seconds
    keys: List              # [M] (ns, name) or None
    uids: List              # [M] metadata.uid or ""


@dataclass
class _TermGroup:
    """One distinct (anti-)affinity term shared by many existing pods."""

    kind: int
    topo_key_id: int
    namespaces: frozenset            # namespace strings
    selector: klabels.Selector
    weight: float
    pair_counts: np.ndarray          # f32[TP-cap] matching member pods per topology pair
    members: int = 0


class _CommitInputs(NamedTuple):
    """What committing a pod derives from its content alone
    (SnapshotEncoder._pod_commit_inputs); equal for every pod of one
    encode_pods class."""
    req: np.ndarray                  # f32[R-cap]
    nonzero: np.ndarray              # f32[2]
    ports: List[Tuple[int, int]]     # (proto/port id, ip id)
    disk_vols: List[int]             # disk-conflict advertise tokens
    vol_counts: np.ndarray           # f32[VT]
    cnt_vols: object                 # per-type unique volume id sets
    ns_id: int                       # interned namespace
    label_ids: List[Tuple[int, int]]  # interned (key, value) pairs
    tenant: int                      # per-namespace usage row


@dataclass
class _PodRecord:
    key: Tuple[str, str]
    labels: Dict[str, str]
    ns: str
    node_row: int                    # -1 unassigned
    m: int                           # pod-arena index
    req: np.ndarray                  # f32[R-cap]
    nonzero: np.ndarray              # f32[2]
    ports: List[Tuple[int, int]]     # (proto/port id, ip id)
    disk_vols: List[int]
    vol_counts: np.ndarray           # f32[VT] (unique per pod)
    cnt_vols: list = None            # per-type unique volume id sets
    priority: int = 0
    group_refs: List[Tuple] = field(default_factory=list)  # term-group signatures
    pod: Optional[Pod] = None        # the full object (victim deletion, host
                                     # what-if verification, PDB matching)
    start_time: float = 0.0          # status.startTime (preemption criterion 5)
    uid: str = ""                    # metadata.uid (extender MetaPod victims)


class SnapshotEncoder:
    """API objects -> numpy arenas -> incremental ClusterTensors snapshots.

    Dirty-row contract (the ONE place it is documented; the snapshot,
    transfer, and mutation paths all reference this):

      * Every mutation marks what it touched: node events mark their row
        via _mark_node_dirty (EVERY per-row field of that row may have
        changed); pod commits mark only their node row via _mark_pod_dirty
        (only the aggregate fields — requested/nonzero/ports/vols — may
        have changed).  Batch ingest (add_pods / add_nodes) marks once per
        batch.  Wholesale rewrites — arena retile/regrow, pad-dim or
        vocabulary growth, topology-key backfill, _reapply_pods_to_arena —
        call _mark_all_dirty instead: content correctness NEVER depends on
        a mutation site remembering to mark precisely, because imprecise
        sites must escalate to the full flag.

      * snapshot() consumes the marks: dirty rows re-encode copy-on-write
        per field, untouched fields return the SAME array object as the
        previous snapshot (consumers detect no-change by identity, so
        snapshot arrays are immutable by contract).  A set _snap_dirty_all
        forces a from-scratch rebuild of every field.

      * take_dirty_rows() is the transfer handshake: it accumulates the
        rows applied by snapshots since the previous take (plus pending
        marks) so the device cache can scatter-update exactly those rows.
        The accumulator survives snapshots that are consumed WITHOUT a
        device update (e.g. gang launches) — rows keep accumulating until
        taken.  Any full rebuild (arena regrow, _mark_all_dirty) poisons
        the accumulator: the next take returns None, meaning "resync every
        field; row identity may have moved".  Single-consumer: exactly one
        DeviceSnapshotCache may take; a second taker would starve the
        first of its rows.
    """

    def __init__(self, dims: Optional[PadDims] = None,
                 hard_pod_affinity_weight: float = 1.0):
        self.dims = dims or PadDims()
        self.interner = Interner()
        self.generation = 0
        # transient pod-batch pad-width override (the express lane's small
        # pre-compiled shape): when set, encode_pods and the batch helpers
        # pad to pow2(len(pods), override) WITHOUT growing the sticky
        # dims.B floor, so a 64-wide express batch keeps its own compiled
        # program next to the bulk lane's full-width one.  Set through
        # batch_width() only (restores on exit).
        self._batch_width: Optional[int] = None
        # HardPodAffinitySymmetricWeight (ref apis/config/types.go, default 1)
        self.hard_pod_affinity_weight = hard_pod_affinity_weight

        self._field_node_name = self.interner.intern(FIELD_NODE_NAME)
        assert self._field_node_name == 1, "FIELD_NODE_NAME_ID contract"
        self.hostname_key = self.interner.intern(HOSTNAME_KEY)
        self.zone_key = self.interner.intern(ZONE_KEY)
        self.region_key = self.interner.intern(REGION_KEY)
        self.getzone_key = self.interner.intern(GETZONE_KEY)
        # zone_key_id=5 default in ops/models signatures rides this order
        assert self.getzone_key == 5, "GETZONE_KEY intern-order contract"
        self.topo_keys: Set[int] = {self.hostname_key, self.zone_key, self.region_key}

        # topology-pair vocabulary
        self._pair_vocab: Dict[Tuple[int, int], int] = {}
        self._pair_topo_key: List[int] = []

        # resource columns beyond the core four
        self._res_cols: Dict[str, int] = {}

        # ---- node arena ----
        self._cap_n = self.dims.N
        self.node_rows: Dict[str, int] = {}
        self._row_node: Dict[int, Node] = {}
        self._free_rows: List[int] = []
        self._next_row = 0
        self._row_pods: Dict[int, Set[Tuple[str, str]]] = {}
        self._node_ports: Dict[int, Counter] = {}
        self._node_disk_vols: Dict[int, Counter] = {}
        # attachable-count volumes: per row per TYPE id refcounts, plus the
        # reverse id -> rows index (per-(pod,node) overlap tensors)
        self._node_cnt_vols: Dict[int, list] = {}
        self._cnt_vol_rows: list = [dict() for _ in range(self.dims.VT)]
        # per-CSI-driver attach-count columns (csi_volume_predicate.go
        # counts/limits PER DRIVER): driver name -> column >= NUM_VOL_TYPES
        self._vol_cols: Dict[str, int] = {}
        self._alloc_node_arena()

        # ---- existing-pod arena (vectorized selector matching) ----
        self._cap_m = 64
        self.pods: Dict[Tuple[str, str], _PodRecord] = {}
        self._free_m: List[int] = []
        self._next_m = 0
        self.p_alive = np.zeros(self._cap_m, dtype=bool)
        self.p_ns = np.full(self._cap_m, PAD, dtype=np.int32)
        self.p_node = np.full(self._cap_m, PAD, dtype=np.int32)
        self._label_cols: Dict[int, np.ndarray] = {}

        # affinity term groups of existing pods
        self.term_groups: Dict[Tuple, _TermGroup] = {}

        # spreading groups (services / RCs / RSs / StatefulSets)
        # ref priorities/selector_spreading.go getSelectors
        self._spread: List[Tuple[str, klabels.Selector]] = []  # (namespace, selector)
        self._spread_kinds: List[str] = []  # "Service" | "ReplicaSet" | ...
        # raw (namespace, matchLabels) of Service entries — the cpuref
        # what-if (preemption victim verification) needs dict selectors
        self._service_selectors: List[Tuple[str, Dict[str, str]]] = []

        # CheckServiceAffinity label keys (interned), empty = predicate off
        self.service_affinity_keys: List[int] = []

        # image -> number of nodes having it (for ImageLocality spread scaling,
        # ref priorities/image_locality.go scaledImageScore)
        self._image_nodes: Counter = Counter()

        # storage objects (PV/PVC/StorageClass), consumed by the volume
        # predicates and the volume binder (ref pkg/scheduler/volumebinder)
        self.pvs: Dict[str, object] = {}
        self.pvcs: Dict[Tuple[str, str], object] = {}
        self.storage_classes: Dict[str, object] = {}

        # template-row cache for encode_pods: pods stamped out by one
        # controller share an identical spec, so their encoded batch row is
        # identical.  Keyed by content; invalidated when the spread-group
        # registry or pad dims change.  Pods with (anti-)affinity are never
        # cached (their pair tensors depend on current cluster state).
        self._pod_row_cache: Dict[Tuple, Dict[str, np.ndarray]] = {}
        self._pod_cache_token: Tuple = ()
        self._req_memo: Dict[Tuple, Tuple[np.ndarray, np.ndarray]] = {}
        self._empty_vcounts: np.ndarray | None = None

        # ---- per-namespace usage/quota columns (ISSUE 14) ----
        # tenant axis for placement fairness: committed (node-assigned)
        # requests aggregated per namespace, maintained incrementally on
        # the same add/remove seams as a_requested, plus an optional
        # per-namespace quota row (+inf = unbounded).  The conflict
        # reconciler's dominant-resource-fairness tiebreak and quota
        # admission read these under the cache lock; they ride the
        # encoder (not ClusterTensors) so engine pytree shapes — and
        # therefore every compiled executable — are untouched.
        self.ns_rows: Dict[str, int] = {}
        self._cap_t = 8
        self.a_ns_usage = np.zeros((self._cap_t, self.dims.R), np.float32)
        self.a_ns_quota = np.full(
            (self._cap_t, self.dims.R), np.inf, np.float32
        )
        self.ns_quota_set = False  # any finite quota configured?

        # ---- incremental snapshot bookkeeping ----
        # see the class docstring for the dirty-row contract
        self._snap: Optional[ClusterTensors] = None
        self._snap_dirty_all = True
        self._dirty_node_rows: Set[int] = set()
        self._dirty_pod_rows: Set[int] = set()
        self._gc_dirty = True          # group_counts (pod/spread dependent)
        self._snap_pairs_len = -1      # pair_topo_key rebuild detector
        # rows refreshed by snapshots since the last take_dirty_rows();
        # None = a full rebuild happened (consumer must full-sync)
        self._snap_rows_acc: Optional[Set[int]] = set()

    # ---------------------------------------------------- dirty bookkeeping

    def _mark_all_dirty(self) -> None:
        self._snap_dirty_all = True
        self._gc_dirty = True

    def _mark_node_dirty(self, row: int) -> None:
        self._dirty_node_rows.add(row)

    def _mark_pod_dirty(self, row: int) -> None:
        if row >= 0:
            self._dirty_pod_rows.add(row)

    def take_dirty_rows(self) -> Optional[np.ndarray]:
        """Rows whose snapshot content may differ from what the transfer
        consumer last uploaded; None after a full rebuild.  Extra rows are
        harmless (the scatter rewrites identical values).  Semantics —
        accumulation across snapshots, rebuild poisoning, the single-
        consumer rule — are in the class docstring's dirty-row contract."""
        if self._snap_rows_acc is None or self._snap_dirty_all:
            self._snap_rows_acc = set()
            return None
        rows = self._snap_rows_acc | self._dirty_node_rows | self._dirty_pod_rows
        self._snap_rows_acc = set()
        return np.asarray(sorted(rows), np.int32)

    # ------------------------------- per-namespace usage/quota (ISSUE 14)

    def _ns_row(self, ns: str) -> int:
        """Tenant index of a namespace, allocating (and growing the
        usage/quota arrays, quota inf-padded) on first sight."""
        t = self.ns_rows.get(ns)
        if t is None:
            t = len(self.ns_rows)
            self.ns_rows[ns] = t
            while t >= self._cap_t:
                self._cap_t *= 2
                for attr, fill in (
                    ("a_ns_usage", 0.0), ("a_ns_quota", np.inf)
                ):
                    src = getattr(self, attr)
                    new = np.full(
                        (self._cap_t, src.shape[1]), fill, np.float32
                    )
                    new[: src.shape[0]] = src
                    setattr(self, attr, new)
        return t

    def set_namespace_quota(self, ns: str, limits: Dict) -> None:
        """Per-namespace placement quota: committed usage beyond this is
        vetoed by the conflict reconciler at commit (ISSUE 14).  `limits`
        maps resource name -> quantity (string, number, or Quantity);
        unnamed resources stay unbounded (+inf)."""
        from kubernetes_tpu.api.resource import parse_quantity

        t = self._ns_row(ns)
        row = np.full(self.dims.R, np.inf, np.float32)
        for name, q in (limits or {}).items():
            q = parse_quantity(q)
            col = self._res_col(name)
            # _res_col may have grown dims.R (and the ns arrays with it,
            # via the shared R-grow path): refresh the row buffer
            if row.shape[0] != self.dims.R:
                old = row
                row = np.full(self.dims.R, np.inf, np.float32)
                row[: old.shape[0]] = old
            row[col] = q.milli if name == RESOURCE_CPU else float(q)
        self.a_ns_quota[t, : row.shape[0]] = row
        self.ns_quota_set = bool(
            np.isfinite(self.a_ns_quota[: len(self.ns_rows)]).any()
        )

    def namespace_usage(self) -> Dict[str, dict]:
        """{namespace: {"usage": [R floats], "quota": [R floats|None]}} —
        the /debug/replicas tenant table (host-side, O(T*R))."""
        out: Dict[str, dict] = {}
        for ns, t in self.ns_rows.items():
            quota = self.a_ns_quota[t]
            out[ns] = {
                "usage": [round(float(x), 3) for x in self.a_ns_usage[t]],
                "quota": [
                    (round(float(x), 3) if np.isfinite(x) else None)
                    for x in quota
                ],
            }
        return out

    def capacity_totals(self) -> np.ndarray:
        """f32[R] cluster-wide allocatable totals over valid rows — the
        dominant-resource-fairness denominator."""
        return self.a_allocatable[self.a_valid].sum(axis=0)

    # ------------------------------------------------------------------ arena

    def _alloc_node_arena(self) -> None:
        d, n = self.dims, self._cap_n
        f32 = np.float32
        i32 = np.int32
        self.a_allocatable = np.zeros((n, d.R), f32)
        self.a_requested = np.zeros((n, d.R), f32)
        self.a_nonzero = np.zeros((n, 2), f32)
        self.a_valid = np.zeros(n, bool)
        self.a_unsched = np.zeros(n, bool)
        self.a_notready = np.zeros(n, bool)
        self.a_mempress = np.zeros(n, bool)
        self.a_diskpress = np.zeros(n, bool)
        self.a_pidpress = np.zeros(n, bool)
        self.a_name = np.full(n, PAD, i32)
        self.a_lkeys = np.full((n, d.L), PAD, i32)
        self.a_lvals = np.full((n, d.L), PAD, i32)
        self.a_lnums = np.full((n, d.L), np.nan, f32)
        self.a_tkey = np.full((n, d.T), PAD, i32)
        self.a_tval = np.full((n, d.T), PAD, i32)
        self.a_teff = np.full((n, d.T), PAD, i32)
        self.a_ppp = np.full((n, d.P), PAD, i32)
        self.a_pip = np.full((n, d.P), PAD, i32)
        self.a_pused = np.zeros((n, d.P), bool)
        self.a_topo = np.zeros((n, self.dims.TP), bool)
        self.a_img_id = np.full((n, d.I), PAD, i32)
        self.a_img_sz = np.zeros((n, d.I), f32)
        self.a_avoid = np.full((n, d.A), PAD, i32)
        self.a_volcnt = np.zeros((n, d.VT), f32)
        self.a_vollim = np.full((n, d.VT), np.inf, f32)
        self.a_dvol = np.full((n, d.DVN), PAD, i32)
        # per-topo-key per-node value/pair id (host-side helper columns)
        self._node_pair_id: Dict[int, np.ndarray] = {
            k: np.full(n, PAD, i32) for k in self.topo_keys
        }

    def _grow_nodes(self) -> None:
        old = self._cap_n
        # Double while small (few recompiles on the way up), then grow in
        # 25% steps rounded to a 512 lane-friendly multiple: at 5k nodes a
        # pow2 pad would run the whole pods x nodes grid at 8192 wide — 60%
        # wasted MXU/VPU work per launch — where 5120 wastes 2.4%.
        if old < 2048:
            new = old * 2
        else:
            new = -(-(old + old // 4) // 512) * 512
        self.dims = dataclasses.replace(self.dims, N=new)
        self._regrow_node_arena(old)

    def ensure_node_capacity(self, n: int) -> None:
        """Grow the node arena (normal growth-schedule steps) until it
        holds >= n rows.  The sharded Scheduler floors the arena at the
        mesh device count at startup: every width on the growth schedule
        (pow2 up to 2048, then 512-multiples) divides over a pow2 mesh of
        <= 512 devices once the arena is at least that wide, so the
        divisibility check in DeviceSnapshotCache.update can never fire
        mid-run from a fleet that stayed small.  Growth also continues
        until the width DIVIDES n: a non-standard PadDims.N base reaches
        a divisible width in a few doublings (12 -> 24 divides 8; each
        doubling adds a factor of two, and every 512-multiple above 2048
        divides any pow2 mesh of <= 512).  Bounded so a pathological
        (non-pow2) n is rejected as a config error HERE, at startup — not
        mid-cycle, where it would read as a device fault and flap the
        breaker into permanent CPU degradation."""
        if n <= 0:
            return
        # dry-run the growth schedule first: a pathological shard count is
        # rejected without allocating a single oversized arena
        target = self._cap_n
        for _ in range(64):
            if target >= n and target % n == 0:
                break
            target = (target * 2 if target < 2048
                      else -(-(target + target // 4) // 512) * 512)
        else:
            raise ValueError(
                f"node arena growth never reaches a width divisible over "
                f"{n} shards from base {self._cap_n} (use a pow2 shard "
                "count <= 512)"
            )
        while self._cap_n < target:
            self._grow_nodes()

    def _regrow_node_arena(self, old_cap: int) -> None:
        """Retile the node arena (bigger N or wider pad dims), preserving the
        overlapping region."""
        names = [a for a in dir(self) if a.startswith("a_")]
        keep = {a: getattr(self, a) for a in names}
        keep_pair = self._node_pair_id
        self._cap_n = self.dims.N
        self._alloc_node_arena()
        for a, src in keep.items():
            new = getattr(self, a)
            sl = tuple(slice(0, min(s, ns)) for s, ns in zip(src.shape, new.shape))
            new[sl] = src[sl]
        for k, col in keep_pair.items():
            if k in self._node_pair_id:
                n = min(old_cap, self._cap_n)
                self._node_pair_id[k][:n] = col[:n]
        self._mark_all_dirty()

    def _grow_pods(self) -> None:
        old = self._cap_m
        self._cap_m *= 2
        for name in ("p_alive", "p_ns", "p_node"):
            src = getattr(self, name)
            new = np.full(self._cap_m, False if src.dtype == bool else PAD, src.dtype)
            new[:old] = src
            setattr(self, name, new)
        for k, col in list(self._label_cols.items()):
            new = np.full(self._cap_m, PAD, np.int32)
            new[:old] = col
            self._label_cols[k] = new

    def _grow_pairs(self, min_tp: Optional[int] = None) -> None:
        """Topology-pair vocabulary outgrew TP: double it.  With `min_tp`,
        replay the doubling schedule to the final width in ONE realloc
        (the bulk ingest path registers a whole batch's pairs first, then
        resizes once; the per-miss caller doubles step by step)."""
        tp = self.dims.TP
        if min_tp is None:
            tp *= 2
        else:
            while tp < min_tp:
                tp *= 2
            if tp == self.dims.TP:
                return
        self.dims = dataclasses.replace(self.dims, TP=tp)
        new = np.zeros((self._cap_n, self.dims.TP), bool)
        new[:, : self.a_topo.shape[1]] = self.a_topo
        self.a_topo = new
        for g in self.term_groups.values():
            nc = np.zeros(self.dims.TP, np.float32)
            nc[: g.pair_counts.shape[0]] = g.pair_counts
            g.pair_counts = nc
        self._mark_all_dirty()

    # ------------------------------------------------------------- vocabulary

    def _pair_id(self, key_id: int, val_id: int) -> int:
        pid = self._pair_vocab.get((key_id, val_id))
        if pid is None:
            pid = len(self._pair_topo_key)
            self._pair_vocab[(key_id, val_id)] = pid
            self._pair_topo_key.append(key_id)
            if pid >= self.dims.TP:
                self._grow_pairs()
        return pid

    def register_topology_key(self, key: str) -> int:
        """Ensure `key` is tracked as a topology key; backfill existing nodes."""
        kid = self.interner.intern(key)
        if kid in self.topo_keys:
            return kid
        self.topo_keys.add(kid)
        self._mark_all_dirty()  # backfill below rewrites a_topo across rows
        self._node_pair_id[kid] = np.full(self._cap_n, PAD, np.int32)
        for name, row in self.node_rows.items():
            node = self._row_node[row]
            val = node.labels.get(key)
            if val is not None:
                pid = self._pair_id(kid, self.interner.intern(val))
                self.a_topo[row, pid] = True
                self._node_pair_id[kid][row] = pid
        return kid

    def node_pair_index(self) -> Tuple[np.ndarray, np.ndarray]:
        """(i32[arena rows, KP], i32[KP]): the topology pair of each node
        row under each registered topology key (PAD where the node has no
        such label) and each column's key (PAD for the padding), KP the
        key count padded to a power of two: the rows of the snapshot's
        topo_pairs incidence, as pair ids."""
        keys = sorted(self.topo_keys)
        kp = _pow2(len(keys))
        out = np.full((self._cap_n, kp), PAD, np.int32)
        for q, kid in enumerate(keys):
            out[:, q] = self._node_pair_id[kid][: self._cap_n]
        cols = np.full(kp, PAD, np.int32)
        cols[: len(keys)] = keys
        return out, cols

    def _vol_limit_col(self, name: str) -> Optional[int]:
        """Attach-limit column for an attachable-volumes-* allocatable key,
        or None when the key constrains nothing (malformed empty-driver
        keys — the golden ignores them too).  May register a per-driver
        column (and so grow VT)."""
        col = _VOL_LIMIT_COLS.get(name)
        if col is None and name.startswith("attachable-volumes-csi-"):
            driver = name[len("attachable-volumes-csi-"):]
            col = self._vol_col(driver) if driver else None
        elif col is None and "csi" in name:
            col = VOL_CSI
        return col

    @staticmethod
    def _cond_bits(cond: Dict[str, str]) -> Tuple[bool, bool, bool, bool]:
        """(not_ready, mem_pressure, disk_pressure, pid_pressure) from a
        status.conditions map — CheckNodeConditionPredicate semantics
        (predicates.go: Ready!=True, OutOfDisk==True, or
        NetworkUnavailable==True fail the node).  The one decode both the
        per-node and bulk ingest paths consume."""
        return (
            cond.get("Ready", "True") != "True"
            or cond.get("OutOfDisk", "False") == "True"
            or cond.get("NetworkUnavailable", "False") == "True",
            cond.get("MemoryPressure", "False") == "True",
            cond.get("DiskPressure", "False") == "True",
            cond.get("PIDPressure", "False") == "True",
        )

    def _res_col(self, name: str) -> int:
        if name == RESOURCE_CPU:
            return RES_MILLICPU
        if name == RESOURCE_MEMORY:
            return RES_MEMORY
        if name == RESOURCE_EPHEMERAL_STORAGE:
            return RES_EPHEMERAL
        if name == RESOURCE_PODS:
            return RES_PODS
        col = self._res_cols.get(name)
        if col is None:
            col = RES_EXT0 + len(self._res_cols)
            if col >= self.dims.R:
                old = self.dims.R
                self.dims = dataclasses.replace(self.dims, R=_pow2(col + 1))
                for attr in ("a_allocatable", "a_requested"):
                    src = getattr(self, attr)
                    new = np.zeros((self._cap_n, self.dims.R), np.float32)
                    new[:, :old] = src
                    setattr(self, attr, new)
                # the tenant usage/quota columns track dims.R in lockstep
                # (quota pads +inf = the new resource starts unbounded)
                for attr, fill in (
                    ("a_ns_usage", 0.0), ("a_ns_quota", np.inf)
                ):
                    src = getattr(self, attr)
                    new = np.full(
                        (self._cap_t, self.dims.R), fill, np.float32
                    )
                    new[:, :old] = src
                    setattr(self, attr, new)
                for rec in self.pods.values():
                    r = np.zeros(self.dims.R, np.float32)
                    r[:old] = rec.req
                    rec.req = r
                self._mark_all_dirty()
            self._res_cols[name] = col
        return col

    def _req_vector(self, requests: Dict) -> np.ndarray:
        v = np.zeros(self.dims.R, np.float32)
        for name, q in requests.items():
            col = self._res_col(name)
            v[col] = q.milli if name == RESOURCE_CPU else float(q)
        v[RES_PODS] = 1.0
        return v

    # --------------------------------------- read-only accessors (ISSUE 15)

    def res_col_readonly(self, name: str) -> "Optional[int]":
        """Resource name -> column index WITHOUT interning: core columns
        map directly, extended resources resolve only if some committed
        pod/node already established them, else None.  The capacity
        planner's catalog encoder routes through here — a side
        observer must never grow dims.R or dirty the arena."""
        if name == RESOURCE_CPU:
            return RES_MILLICPU
        if name == RESOURCE_MEMORY:
            return RES_MEMORY
        if name == RESOURCE_EPHEMERAL_STORAGE:
            return RES_EPHEMERAL
        if name == RESOURCE_PODS:
            return RES_PODS
        return self._res_cols.get(name)

    def backlog_req_vector(self, pod: Pod) -> np.ndarray:
        """READ-ONLY f32[R] request vector for a NOT-YET-PLACED pod (the
        capacity planner's backlog encoding): same column layout and
        units as _req_vector, but unknown extended resources are
        dropped instead of growing the resource axis — encoding a
        backlog must not mutate the arena, mark rows dirty, or perturb
        the interner (placement bit-identity planner on/off rides on
        this)."""
        v = np.zeros(self.dims.R, np.float32)
        for name, q in pod.resource_request().items():
            col = self.res_col_readonly(name)
            if col is None:
                continue
            v[col] = q.milli if name == RESOURCE_CPU else float(q)
        v[RES_PODS] = 1.0
        return v

    # ----------------------------------------------------------------- nodes

    def add_node(self, node: Node) -> int:
        if node.name in self.node_rows:
            return self.update_node(node)
        if self._free_rows:
            row = self._free_rows.pop()
        else:
            row = self._next_row
            self._next_row += 1
            while row >= self._cap_n:
                self._grow_nodes()
        self.node_rows[node.name] = row
        self._node_ports[row] = Counter()
        self._node_disk_vols[row] = Counter()
        self._write_node_row(row, node)
        self._mark_node_dirty(row)
        self.generation += 1
        return row

    def update_node(self, node: Node) -> int:
        row = self.node_rows[node.name]
        old = self._row_node.get(row)
        if old is not None:
            for img in old.status.images:
                if img.names:
                    self._image_nodes[img.names[0]] -= 1
        # topology labels may change: lift resident pods' pair contributions
        # off the old pairs, rewrite the row, then re-apply on the new pairs
        resident = [
            self.pods[key] for key in self._row_pods.get(row, ()) if key in self.pods
        ]
        for rec in resident:
            self._shift_pod_pairs(rec, add=False)
        self._write_node_row(row, node)
        for rec in resident:
            self._shift_pod_pairs(rec, add=True)
        self._mark_node_dirty(row)
        self.generation += 1
        return row

    def remove_node(self, name: str) -> None:
        row = self.node_rows.pop(name)
        node = self._row_node.pop(row, None)
        if node is not None:
            for img in node.status.images:
                if img.names:
                    self._image_nodes[img.names[0]] -= 1
        # detach pods still charged to this row (the informer's pod deletes
        # arrive separately, ref cache.go RemoveNode keeps pod entries):
        # their term-group pair contributions and arena links must not leak
        # into whichever node reuses the row.  Group *membership* stays (the
        # pod still exists); only the per-pair placement contribution goes.
        for key in list(self._row_pods.get(row, ())):
            rec = self.pods.get(key)
            if rec is None:
                continue
            self._shift_pod_pairs(rec, add=False)
            rec.node_row = -1
            self.p_node[rec.m] = PAD
            # the detached pod no longer holds committed capacity: its
            # tenant usage retires with the row's aggregates below
            self.a_ns_usage[
                self._ns_row(rec.ns), : rec.req.shape[0]
            ] -= rec.req
        self._row_pods.pop(row, None)
        # zero the aggregates so row reuse starts clean
        self.a_requested[row, :] = 0.0
        self.a_nonzero[row, :] = 0.0
        self.a_volcnt[row, :] = 0.0
        self._node_ports[row] = Counter()
        self._node_disk_vols[row] = Counter()
        # drop this row from the attachable-volume reverse index
        old_cnts = self._node_cnt_vols.pop(row, None)
        if old_cnts is not None:
            for t, ctr in enumerate(old_cnts):
                for vid in ctr:
                    rows = self._cnt_vol_rows[t].get(vid)
                    if rows is not None:
                        rows.discard(row)
                        if not rows:
                            del self._cnt_vol_rows[t][vid]
        self._rebuild_node_ports(row)
        self._rebuild_node_vols(row)
        self.a_valid[row] = False
        self.a_topo[row, :] = False
        for col in self._node_pair_id.values():
            col[row] = PAD
        self._free_rows.append(row)
        self._mark_node_dirty(row)
        self._gc_dirty = True  # detached pods left p_node
        self.generation += 1

    def add_nodes(self, nodes: Sequence[Node]) -> List[int]:
        """Batched add_node: a columnar encode of many NEW node rows that
        produces byte-identical arena state to calling add_node(n) for each
        node in order (pinned by tests/test_bulk_nodes.py), amortizing the
        per-node numpy overhead — the cold-start / failover re-sync wall
        (node_encode_seconds in bench.py):

          * per-row numpy slice writes (~40 per node in _write_node_row)
            collapse into one fancy-indexed scatter per FIELD per batch;
          * string interning runs through the per-node registration pass
            in add_node's exact order (name, labels, taints, GetZoneKey,
            images, avoid), so interner ids, resource/volume columns, and
            the topology-pair vocabulary are assigned identically;
          * pad-dim growth (L/T/I, N) happens ONCE up front for the whole
            batch instead of regrowing per offending node (bump() rounds
            to pow2 of the max, so final dims match the sequential loop);
          * dirty-row marks and the generation counter advance once per
            batch, not once per node.

        Batches containing a duplicate name or a name already resident
        take the exact per-node path (those are update batches, where the
        old-row teardown must interleave per node).  Returns the assigned
        rows, same values the per-node loop would return."""
        nodes = list(nodes)
        if not nodes:
            return []
        names = [n.name for n in nodes]
        if len(set(names)) != len(names) or any(
            n in self.node_rows for n in names
        ):
            return [self.add_node(n) for n in nodes]

        # -- pass 0: pad-dim growth to fit the whole batch
        d0 = self.dims
        grow = {}
        max_l = max(len(n.metadata.labels) for n in nodes)
        max_t = max(len(n.spec.taints) for n in nodes)
        max_i = max(len(n.status.images) for n in nodes)
        if max_l > d0.L:
            grow["L"] = max_l
        if max_t > d0.T:
            grow["T"] = max_t
        if max_i > d0.I:
            grow["I"] = max_i
        if grow:
            self.dims = self.dims.bump(**grow)
            self._regrow_node_arena(self._cap_n)
            self._reapply_pods_to_arena()

        # -- pass 1: row allocation (free rows first — the same pop order
        # the per-node loop uses).  The arena is pre-sized to the FINAL
        # capacity by replaying _grow_nodes' growth schedule arithmetic
        # without the intermediate reallocs (one regrow, not ~13 at 5k
        # nodes; the final cap — and therefore every arena shape — is
        # byte-identical to the sequential loop's)
        n_new = len(nodes) - min(len(self._free_rows), len(nodes))
        if n_new:
            max_row = self._next_row + n_new - 1
            cap = self._cap_n
            while max_row >= cap:
                cap = cap * 2 if cap < 2048 else -(-(cap + cap // 4) // 512) * 512
            if cap != self._cap_n:
                self.dims = dataclasses.replace(self.dims, N=cap)
                self._regrow_node_arena(self._cap_n)
        rows: List[int] = []
        reused: List[int] = []    # rows recycled off the free list (these
        #                           carry stale content needing row resets)
        node_rows = self.node_rows
        row_node = self._row_node
        node_ports = self._node_ports
        node_dvols = self._node_disk_vols
        free_rows = self._free_rows
        # Counter.__new__ skips the __init__/update call chain; a Counter
        # is a plain dict subclass, so the uninitialized instance IS the
        # empty Counter (== Counter(), same type, same methods)
        counter_new = Counter.__new__
        for node in nodes:
            if free_rows:
                row = free_rows.pop()
                reused.append(row)
            else:
                row = self._next_row
                self._next_row += 1
            rows.append(row)
            node_rows[node.metadata.name] = row
            row_node[row] = node
            node_ports[row] = counter_new(Counter)
            node_dvols[row] = counter_new(Counter)

        # -- pass 2: vocabulary registration + integer row data, per node
        # in add_node's exact order.  This pass only touches dicts/lists
        # (interner, _res_cols/_vol_cols, pair vocabulary — all of whose
        # id-assignment order must match the per-node loop); every numpy
        # write waits for pass 3, AFTER any R/VT/TP growth has settled.
        it = self.interner
        intern = it.intern
        intern_many = it.intern_many
        # topology-pair registration without per-miss a_topo doubling: the
        # vocabulary appends here in the per-node order _pair_id would
        # use, and the (N x TP) incidence tensor resizes ONCE after the
        # loop by replaying the doubling schedule (identical final TP; the
        # sequential loop pays up to ~9 full-width reallocs at 5k nodes)
        pv = self._pair_vocab
        pv_get = pv.get
        ptk = self._pair_topo_key
        gz_memo: Dict[Tuple[str, str], str] = {}
        name_ids: List[int] = []
        # condition/unschedulable EXCEPTIONS only (healthy schedulable
        # fleets append nothing; pass 3 scatters just the outliers over a
        # False default)
        unsched_k: List[int] = []
        notready_k: List[int] = []
        mempress_k: List[int] = []
        diskpress_k: List[int] = []
        pidpress_k: List[int] = []
        alloc_n: List[int] = []       # per-node resource-entry count
        alloc_c: List[int] = []
        alloc_v: List[float] = []
        lim_k: List[int] = []         # attachable-volume limit writes
        lim_c: List[int] = []
        lim_v: List[float] = []
        lab_n: List[int] = []         # per-node label count (k/j columns
        lab_kid: List[int] = []       #   derive via np.repeat/arange)
        lab_vid: List[int] = []
        tnt_k: List[int] = []
        tnt_j: List[int] = []
        tnt_kid: List[int] = []
        tnt_vid: List[int] = []
        tnt_eff: List[int] = []
        topo_k: List[int] = []        # (batch idx, pair id) True incidences
        topo_pid: List[int] = []
        pair_cols: Dict[int, List[int]] = {k: [] for k in self.topo_keys}
        topo_key_strs = [
            (kid, it.string(kid), pair_cols[kid].append)
            for kid in self.topo_keys
        ]
        topo_k_app = topo_k.append
        topo_pid_app = topo_pid.append
        img_k: List[int] = []
        img_j: List[int] = []
        img_id: List[int] = []
        img_sz: List[float] = []
        img_names: List[str] = []     # _image_nodes increments, batched
        av_k: List[int] = []
        av_j: List[int] = []
        av_id: List[int] = []
        # allocatable-dict memo: stamped node fleets share one allocatable
        # content, so the exact Fraction math (milli/__float__, ~6us/node
        # at 5k) and column resolution run once per DISTINCT content;
        # values are (res cols, res vals, limit cols, limit vals)
        alloc_memo: Dict[Tuple, Tuple] = {}
        res_memo: Dict[str, int] = {}
        # image-name cap simulation: the per-node loop caps each row's
        # flattened image NAMES at the dims.I in effect when that node is
        # written (I bumps lazily off the image COUNT of the node itself),
        # so a many-names node written before the bumping node truncates
        # at the old width — replay that schedule for byte-identity
        run_i = d0.I
        import json

        ready_only = {"Ready": "True"}
        for k, node in enumerate(nodes):
            cond = node.status.conditions
            if node.spec.unschedulable:
                unsched_k.append(k)
            if cond != ready_only:  # != the healthy-fleet shape: decode
                nr, mp, dp, pp = self._cond_bits(cond)
                if nr:
                    notready_k.append(k)
                if mp:
                    mempress_k.append(k)
                if dp:
                    diskpress_k.append(k)
                if pp:
                    pidpress_k.append(k)
            # whole-dict memo: a stamped fleet shares one allocatable
            # content (parse_quantity canonicalizes values to shared
            # instances with cached hashes, so the tuple key hashes in
            # ~0.5us and dict equality takes the identity fast path)
            akey = tuple(node.status.allocatable.items())
            hit = alloc_memo.get(akey)
            if hit is None:
                cols: List[int] = []
                vals: List[float] = []
                lcols: List[int] = []
                lvals: List[float] = []
                for name, q in node.status.allocatable.items():
                    if name.startswith("attachable-volumes-"):
                        col = self._vol_limit_col(name)
                        if col is not None:
                            lcols.append(col)
                            lvals.append(float(q))
                        continue
                    col = res_memo.get(name)
                    if col is None:
                        col = res_memo[name] = self._res_col(name)
                    cols.append(col)
                    vals.append(
                        q.milli if name == RESOURCE_CPU else float(q)
                    )
                hit = alloc_memo[akey] = (cols, vals, lcols, lvals)
            cols, vals, lcols, lvals = hit
            alloc_n.append(len(cols))
            alloc_c.extend(cols)
            alloc_v.extend(vals)
            if lcols:
                lim_k.extend([k] * len(lcols))
                lim_c.extend(lcols)
                lim_v.extend(lvals)
            # one stacked intern for everything this node names, in
            # _write_node_row's exact order (name, label k/v pairs, taint
            # key/value pairs, GetZoneKey combo, image names, avoid uids)
            # so novel-id assignment is position-identical to the loop
            labels = node.metadata.labels
            lab_items = sorted(labels.items())
            taints = node.spec.taints
            region = labels.get(REGION_KEY, "")
            zone = labels.get(ZONE_KEY, "")
            imgs = node.status.images
            capped_imgs: "List[Tuple[str, float]] | Tuple" = ()
            if imgs:
                if len(imgs) > run_i:
                    run_i = _pow2(len(imgs))
                capped_imgs = []
                j = 0
                for img in imgs:
                    for name in img.names:
                        if j >= run_i:
                            break
                        capped_imgs.append((name, float(img.size_bytes)))
                        j += 1
            # (slot, uid) pairs: empty uids CONSUME a slot but write
            # nothing, matching _write_node_row's enumerate-then-filter
            uids: "List[Tuple[int, str]] | Tuple" = ()
            ann = node.metadata.annotations.get(
                "scheduler.alpha.kubernetes.io/preferAvoidPods"
            )
            if ann:
                try:
                    avoid = json.loads(ann)
                    raw = [
                        e.get("podSignature", {})
                        .get("podController", {})
                        .get("uid", "")
                        for e in avoid.get("preferAvoidPods", [])
                    ]
                    uids = [(j, u) for j, u in enumerate(raw[: self.dims.A]) if u]
                except (ValueError, AttributeError):
                    uids = []
            nl = len(lab_items)
            nt = len(taints)
            # the name interns FIRST (as _write_node_row does) and alone:
            # it is the one always-novel string, so the stacked
            # intern_many below usually takes its all-hits fast path
            name_ids.append(intern(node.metadata.name))
            strs: List[str] = []
            if nl:
                strs.extend(chain.from_iterable(lab_items))
            if nt:
                strs.extend(
                    chain.from_iterable((t.key, t.value) for t in taints)
                )
            if region or zone:
                gzk = (region, zone)
                gz = gz_memo.get(gzk)
                if gz is None:
                    gz = gz_memo[gzk] = region + ":\x00:" + zone
                strs.append(gz)
            if capped_imgs:
                strs.extend(nm for nm, _ in capped_imgs)
            if uids:
                strs.extend(u for _, u in uids)
            ids = intern_many(strs)
            # slice-unpack the stacked ids (C-speed strides, not per-item
            # python appends): keys at even offsets, values at odd
            lab_n.append(nl)
            if nl:
                lab_kid.extend(ids[0:2 * nl:2])
                lab_vid.extend(ids[1:1 + 2 * nl:2])
            base = 2 * nl
            if nt:
                tnt_k.extend([k] * nt)
                tnt_j.extend(range(nt))
                tnt_kid.extend(ids[base:base + 2 * nt:2])
                tnt_vid.extend(ids[base + 1:base + 2 * nt:2])
                for t in taints:
                    tnt_eff.append(EFFECT_CODES.get(t.effect, 0))
            pos = base + 2 * nt
            # topology pairs: label values are interned by now, so the
            # pair-vocabulary registration order matches the per-node loop
            labels_get = labels.get
            for kid, key_str, col_append in topo_key_strs:
                val = labels_get(key_str)
                if val is not None:
                    key2 = (kid, intern(val))
                    pid = pv_get(key2)
                    if pid is None:
                        pid = len(ptk)
                        pv[key2] = pid
                        ptk.append(kid)
                    topo_k_app(k)
                    topo_pid_app(pid)
                    col_append(pid)
                else:
                    col_append(PAD)
            if region or zone:
                key2 = (self.getzone_key, ids[pos])
                pid = pv_get(key2)
                if pid is None:
                    pid = len(ptk)
                    pv[key2] = pid
                    ptk.append(self.getzone_key)
                topo_k_app(k)
                topo_pid_app(pid)
                pos += 1
            for j, (nm, sz) in enumerate(capped_imgs):
                img_k.append(k)
                img_j.append(j)
                img_id.append(ids[pos])
                pos += 1
                img_sz.append(sz)
                img_names.append(nm)
            for j, _u in uids:
                av_k.append(k)
                av_j.append(j)
                av_id.append(ids[pos])
                pos += 1
        if img_names:
            self._image_nodes.update(img_names)
        # replay _grow_pairs' doubling schedule in one realloc
        self._grow_pairs(min_tp=len(ptk))

        # -- pass 3: columnar arena writes (arrays fetched AFTER pass 2 —
        # R/VT/TP growth replaces them).  Row resets apply ONLY to rows
        # recycled off the free list: those keep their previous label/
        # taint/allocatable content until overwritten (remove_node clears
        # only the aggregates), so exactly the slices _write_node_row
        # rewrites are reset.  FRESH rows skip resets entirely — the arena
        # default (PAD/0/inf/nan/False from _alloc_node_arena) is
        # byte-identical to the reset value — and a no-reuse batch is a
        # contiguous row range, so the full-batch column writes go through
        # slice assignment instead of per-element fancy indexing.
        # Port/volume row rebuilds are SKIPPED: a new row's counters are
        # empty and its port/vol slices are already PAD/False (fresh from
        # _alloc, or reset by remove_node before the row was freed).
        i32, f32 = np.int32, np.float32
        if reused:
            rows_arr = np.asarray(rows, np.intp)
            idx: "slice | np.ndarray" = rows_arr
            row0 = 0
            r = np.asarray(reused, np.intp)
            self.a_unsched[r] = False
            self.a_notready[r] = False
            self.a_mempress[r] = False
            self.a_diskpress[r] = False
            self.a_pidpress[r] = False
            self.a_allocatable[r] = 0.0
            self.a_vollim[r] = np.inf
            self.a_lkeys[r] = PAD
            self.a_lvals[r] = PAD
            self.a_lnums[r] = np.nan
            self.a_tkey[r] = PAD
            self.a_tval[r] = PAD
            self.a_teff[r] = PAD
            self.a_topo[r] = False
            self.a_img_id[r] = PAD
            self.a_img_sz[r] = 0.0
            self.a_avoid[r] = PAD
        else:
            rows_arr = None
            row0 = rows[0]
            idx = slice(row0, row0 + len(rows))

        def rowsel(ks):
            ka = np.asarray(ks, np.intp)
            return ka + row0 if rows_arr is None else rows_arr[ka]

        def scatter2(dst, ks, js, vals, dtype):
            dst[rowsel(ks), np.asarray(js, np.intp)] = np.asarray(vals, dtype)

        self.a_valid[idx] = True
        self.a_name[idx] = np.asarray(name_ids, i32)
        # condition/unschedulable outliers over the False default
        if unsched_k:
            self.a_unsched[rowsel(unsched_k)] = True
        if notready_k:
            self.a_notready[rowsel(notready_k)] = True
        if mempress_k:
            self.a_mempress[rowsel(mempress_k)] = True
        if diskpress_k:
            self.a_diskpress[rowsel(diskpress_k)] = True
        if pidpress_k:
            self.a_pidpress[rowsel(pidpress_k)] = True
        if alloc_c:
            # the batch-index column derives from the per-node counts
            # (np.repeat beats 5k python [k]*n extends)
            alloc_k_arr = np.repeat(
                np.arange(len(nodes), dtype=np.intp),
                np.asarray(alloc_n, np.intp),
            )
            self.a_allocatable[
                alloc_k_arr + row0 if rows_arr is None else rows_arr[alloc_k_arr],
                np.asarray(alloc_c, np.intp),
            ] = np.asarray(alloc_v, f32)
        if lim_k:
            scatter2(self.a_vollim, lim_k, lim_c, lim_v, f32)
        if lab_kid:
            lab_n_arr = np.asarray(lab_n, np.intp)
            lab_k_arr = np.repeat(
                np.arange(len(nodes), dtype=np.intp), lab_n_arr
            )
            # per-node slot index: 0..nl-1 per node, C-speed
            starts = np.cumsum(lab_n_arr) - lab_n_arr
            lab_j_arr = (
                np.arange(len(lab_kid), dtype=np.intp)
                - np.repeat(starts, lab_n_arr)
            )
            lr = lab_k_arr + row0 if rows_arr is None else rows_arr[lab_k_arr]
            self.a_lkeys[lr, lab_j_arr] = np.asarray(lab_kid, i32)
            self.a_lvals[lr, lab_j_arr] = np.asarray(lab_vid, i32)
            # numeric label column (Gt/Lt operands): one parse per
            # DISTINCT value id, gathered C-speed over the whole batch
            vid_arr = np.asarray(lab_vid, np.intp)
            lut = np.full(int(vid_arr.max()) + 1, np.nan, f32)
            s = it.string
            for vid in set(lab_vid):
                v = s(vid)
                try:
                    lut[vid] = float(int(v))
                except ValueError:
                    pass
            self.a_lnums[lr, lab_j_arr] = lut[vid_arr]
        if tnt_k:
            scatter2(self.a_tkey, tnt_k, tnt_j, tnt_kid, i32)
            scatter2(self.a_tval, tnt_k, tnt_j, tnt_vid, i32)
            scatter2(self.a_teff, tnt_k, tnt_j, tnt_eff, i32)
        if topo_k:
            self.a_topo[rowsel(topo_k), np.asarray(topo_pid, np.intp)] = True
        for kid, vals in pair_cols.items():
            self._node_pair_id[kid][idx] = np.asarray(vals, i32)
        if img_k:
            scatter2(self.a_img_id, img_k, img_j, img_id, i32)
            scatter2(self.a_img_sz, img_k, img_j, img_sz, f32)
        if av_k:
            scatter2(self.a_avoid, av_k, av_j, av_id, i32)

        self._dirty_node_rows.update(rows)
        self.generation += len(nodes)
        return rows

    def update_nodes(self, nodes: Sequence[Node]) -> List[int]:
        """Bulk upsert for informer re-list / failover re-sync.  NEW nodes
        flush through the columnar add_nodes path (consecutive runs keep
        arrival order, so interner/vocabulary id assignment matches the
        per-node loop); resident nodes whose stored object compares EQUAL
        are skipped outright — no row write, no dirty mark, no generation
        bump (a re-listed unchanged node is not a change; this is the warm
        re-encode fast path bench.py reports) — and changed nodes take
        update_node.  Returns each node's arena row."""
        nodes = list(nodes)
        rows: List[int] = [-1] * len(nodes)
        run: List[int] = []

        def flush():
            if run:
                for i, r in zip(run, self.add_nodes([nodes[i] for i in run])):
                    rows[i] = r
                run.clear()

        for i, node in enumerate(nodes):
            row = self.node_rows.get(node.name)
            if row is None:
                run.append(i)
                continue
            flush()
            if self._row_node.get(row) == node:
                rows[i] = row
            else:
                rows[i] = self.update_node(node)
        flush()
        return rows

    def _write_node_row(self, row: int, node: Node) -> None:
        d = self.dims
        it = self.interner
        self._row_node[row] = node
        # pad-dim growth checks
        grow = {}
        if len(node.labels) > d.L:
            grow["L"] = len(node.labels)
        if len(node.spec.taints) > d.T:
            grow["T"] = len(node.spec.taints)
        if len(node.status.images) > d.I:
            grow["I"] = len(node.status.images)
        if grow:
            self.dims = self.dims.bump(**grow)
            self._regrow_node_arena(self._cap_n)
            self._reapply_pods_to_arena()
        self.a_valid[row] = True
        self.a_name[row] = it.intern(node.name)
        self.a_unsched[row] = node.spec.unschedulable
        (
            self.a_notready[row],
            self.a_mempress[row],
            self.a_diskpress[row],
            self.a_pidpress[row],
        ) = self._cond_bits(node.status.conditions)
        # allocatable (+ per-node attachable-volume limits, ref the
        # AttachVolumeLimit feature's attachable-volumes-* allocatable keys)
        self.a_allocatable[row, :] = 0.0
        self.a_vollim[row, :] = np.inf
        for name, q in node.status.allocatable.items():
            if name.startswith("attachable-volumes-"):
                col = self._vol_limit_col(name)
                if col is not None:
                    self.a_vollim[row, col] = float(q)
                continue
            col = self._res_col(name)
            self.a_allocatable[row, col] = (
                q.milli if name == RESOURCE_CPU else float(q)
            )
        # labels
        self.a_lkeys[row, :] = PAD
        self.a_lvals[row, :] = PAD
        self.a_lnums[row, :] = np.nan
        for j, (k, v) in enumerate(sorted(node.labels.items())):
            self.a_lkeys[row, j] = it.intern(k)
            self.a_lvals[row, j] = it.intern(v)
            try:
                self.a_lnums[row, j] = float(int(v))
            except ValueError:
                pass
        # taints
        self.a_tkey[row, :] = PAD
        self.a_tval[row, :] = PAD
        self.a_teff[row, :] = PAD
        for j, t in enumerate(node.spec.taints):
            self.a_tkey[row, j] = it.intern(t.key)
            self.a_tval[row, j] = it.intern(t.value)
            self.a_teff[row, j] = EFFECT_CODES.get(t.effect, 0)
        # topology pairs
        self.a_topo[row, :] = False
        for kid in self.topo_keys:
            key = it.string(kid)
            val = node.labels.get(key)
            col = self._node_pair_id[kid]
            if val is not None:
                pid = self._pair_id(kid, it.intern(val))
                self.a_topo[row, pid] = True
                col[row] = pid
            else:
                col[row] = PAD
        # GetZoneKey pair (util/node/node.go:126-143): region + ":\x00:" + zone,
        # present when either label is non-empty; this is the grouping unit of
        # the SelectorSpread zone reduce (two same-named zones in different
        # regions are distinct).
        region = node.labels.get(REGION_KEY, "")
        zone = node.labels.get(ZONE_KEY, "")
        if region or zone:
            gz_pid = self._pair_id(
                self.getzone_key, it.intern(region + ":\x00:" + zone)
            )
            self.a_topo[row, gz_pid] = True
        # images: EVERY name of an image is a lookup key (the reference's
        # imageStates maps each entry of image.Names to the same state)
        self.a_img_id[row, :] = PAD
        self.a_img_sz[row, :] = 0.0
        j = 0
        for img in node.status.images:
            for name in img.names:
                if j >= self.dims.I:
                    break
                self.a_img_id[row, j] = it.intern(name)
                self.a_img_sz[row, j] = float(img.size_bytes)
                self._image_nodes[name] += 1
                j += 1
        # prefer-avoid-pods annotation
        # ref api/v1/pod/util.go GetAvoidPodsFromNodeAnnotations + priorities/
        # node_prefer_avoid_pods.go: annotation lists controller refs to avoid.
        self.a_avoid[row, :] = PAD
        import json

        ann = node.metadata.annotations.get("scheduler.alpha.kubernetes.io/preferAvoidPods")
        if ann:
            try:
                avoid = json.loads(ann)
                uids = [
                    e.get("podSignature", {})
                    .get("podController", {})
                    .get("uid", "")
                    for e in avoid.get("preferAvoidPods", [])
                ]
                for j, u in enumerate(uids[: d.A]):
                    if u:
                        self.a_avoid[row, j] = it.intern(u)
            except (ValueError, AttributeError):
                pass
        self._rebuild_node_ports(row)
        self._rebuild_node_vols(row)

    def _reapply_pods_to_arena(self) -> None:
        """After an arena retile, re-accumulate pod aggregates into node rows."""
        self.a_requested[:, :] = 0.0
        self.a_nonzero[:, :] = 0.0
        self.a_volcnt[:, :] = 0.0
        self.a_ns_usage[:, :] = 0.0
        self._node_cnt_vols.clear()
        self._cnt_vol_rows = [dict() for _ in range(self.dims.VT)]
        for rec in self.pods.values():
            if rec.node_row >= 0:
                self.a_requested[rec.node_row, : rec.req.shape[0]] += rec.req
                self.a_nonzero[rec.node_row] += rec.nonzero
                self.a_ns_usage[
                    self._ns_row(rec.ns), : rec.req.shape[0]
                ] += rec.req
                if rec.cnt_vols:
                    cnts = self._node_cnt_vols.setdefault(
                        rec.node_row,
                        [Counter() for _ in range(self.dims.VT)],
                    )
                    for t, ids in enumerate(rec.cnt_vols):
                        for vid in ids:
                            cnts[t][vid] += 1
                            self._cnt_vol_rows[t].setdefault(
                                vid, set()
                            ).add(rec.node_row)
                        self.a_volcnt[rec.node_row, t] = len(cnts[t])
        for row in self._node_ports:
            self._rebuild_node_ports(row)
            self._rebuild_node_vols(row)
        self._mark_all_dirty()

    def _rebuild_node_ports(self, row: int) -> None:
        self.a_ppp[row, :] = PAD
        self.a_pip[row, :] = PAD
        self.a_pused[row, :] = False
        ports = self._node_ports.get(row, Counter())
        if len(ports) > self.dims.P:
            self.dims = self.dims.bump(P=len(ports))
            self._regrow_node_arena(self._cap_n)
            self._reapply_pods_to_arena()
            return
        for j, (pp, ip) in enumerate(sorted(ports)):
            self.a_ppp[row, j] = pp
            self.a_pip[row, j] = ip
            self.a_pused[row, j] = True

    def _rebuild_node_vols(self, row: int) -> None:
        self.a_dvol[row, :] = PAD
        vols = self._node_disk_vols.get(row, Counter())
        if len(vols) > self.dims.DVN:
            self.dims = self.dims.bump(DVN=len(vols))
            self._regrow_node_arena(self._cap_n)
            self._reapply_pods_to_arena()
            return
        for j, v in enumerate(sorted(vols)):
            self.a_dvol[row, j] = v

    # ------------------------------------------------------------------ pods

    def _pod_ports(self, pod: Pod) -> List[Tuple[int, int]]:
        out = []
        for p in pod.host_ports():
            pp = self.interner.intern(f"{p.protocol or 'TCP'}/{p.host_port}")
            ip = p.host_ip
            if ip in ("", "0.0.0.0"):
                ipid = 0
            else:
                ipid = self.interner.intern(ip)
            out.append((pp, ipid))
        return out

    def _vol_col(self, csi_driver: str) -> int:
        """Attach-count column for a CSI driver ('' = the generic CSI
        column).  New drivers widen the VT axis — node arenas, per-record
        vectors, and the per-node/per-id bookkeeping all regrow, the same
        discipline _res_col applies to extended resources."""
        if not csi_driver:
            return VOL_CSI
        col = self._vol_cols.get(csi_driver)
        if col is not None:
            return col
        col = NUM_VOL_TYPES + len(self._vol_cols)
        if col >= self.dims.VT:
            old = self.dims.VT
            self.dims = dataclasses.replace(self.dims, VT=_pow2(col + 1))
            grow = self.dims.VT - old
            for attr, fill in (("a_volcnt", 0.0), ("a_vollim", np.inf)):
                src_arr = getattr(self, attr)
                new = np.full((self._cap_n, self.dims.VT), fill, np.float32)
                new[:, :old] = src_arr
                setattr(self, attr, new)
            self._cnt_vol_rows += [dict() for _ in range(grow)]
            for counters in self._node_cnt_vols.values():
                counters.extend(Counter() for _ in range(grow))
            wide_empty = np.zeros(self.dims.VT, np.float32)
            wide_empty.setflags(write=False)
            self._empty_vcounts = wide_empty
            for rec in self.pods.values():
                if not rec.cnt_vols:  # () sentinel (no volumes) stays ()
                    rec.vol_counts = wide_empty  # keep records shared
                    continue
                v = np.zeros(self.dims.VT, np.float32)
                v[: rec.vol_counts.shape[0]] = rec.vol_counts
                rec.vol_counts = v
                rec.cnt_vols = list(rec.cnt_vols) + [
                    set() for _ in range(grow)
                ]
            self._mark_all_dirty()
        self._vol_cols[csi_driver] = col
        return col

    def _pod_vols(self, pod: Pod) -> Tuple[List[int], List[int], np.ndarray, list]:
        """(disk-conflict CHECK tokens, disk-conflict ADVERTISE tokens,
        per-filter-type UNIQUE new volume counts, per-type unique id sets).

        ref predicates.go NoDiskConflict (isVolumeConflict :295-328) and
        MaxVolumeCount filters.  Counts dedupe by volume identity
        (filterVolumes keys a map by unique id).  Conflict tokens encode
        the read-only allowance: GCE-PD / RBD / ISCSI mounts that are BOTH
        read-only don't conflict, so volume V advertises "V#any" (+"V#rw"
        when read-write) and checks "V#any" when read-write but only
        "V#rw" when read-only; EBS conflicts regardless (one token).
        """
        if not pod.spec.volumes:  # hot path: most pods mount nothing
            # shared read-only zero vector + empty cnt_ids sentinel: the
            # cache-commit path calls this once per bound pod, and per-call
            # allocation of VT sets dominated the commit profile.  Every
            # consumer iterates cnt_ids with enumerate, so () is safe; the
            # zeros array is marked unwriteable and replaced per-record on
            # VT regrow (_vol_col), so sharing cannot alias a mutation.
            z = self._empty_vcounts
            if z is None or z.shape[0] != self.dims.VT:
                z = np.zeros(self.dims.VT, np.float32)
                z.setflags(write=False)
                self._empty_vcounts = z
            return [], [], z, ()
        disk: List[int] = []       # check tokens (the pod's own mounts)
        disk_adv: List[int] = []   # advertise tokens (what a node shows)
        cnt_ids: list = [set() for _ in range(self.dims.VT)]

        def allow_ro(base: str, ro: bool) -> None:
            it = self.interner
            disk_adv.append(it.intern(base + "#any"))
            if not ro:
                disk_adv.append(it.intern(base + "#rw"))
            disk.append(it.intern(base + ("#rw" if ro else "#any")))

        for v in pod.spec.volumes:
            if "gcePersistentDisk" in v:
                g = v["gcePersistentDisk"]
                base = "gce/" + g.get("pdName", "")
                allow_ro(base, bool(g.get("readOnly")))
                cnt_ids[VOL_GCE].add(self.interner.intern(base))
            elif "awsElasticBlockStore" in v:
                vid = self.interner.intern("ebs/" + v["awsElasticBlockStore"].get("volumeID", ""))
                disk.append(vid)
                disk_adv.append(vid)
                cnt_ids[VOL_EBS].add(vid)
            elif "rbd" in v:
                # identity = monitor OVERLAP + pool + image (predicates.go
                # :264-272 haveOverlap): one token per monitor, so any
                # shared monitor collides
                r = v["rbd"]
                # no monitors -> no tokens (haveOverlap([], x) is false)
                for mon in r.get("monitors", []) or ():
                    allow_ro(
                        "rbd/%s/%s/%s" % (mon, r.get("pool", "rbd"), r.get("image", "")),
                        bool(r.get("readOnly")),
                    )
            elif "iscsi" in v:
                # identity = IQN alone (predicates.go:253-262 — multi-path
                # target portals reach the same LUNs)
                r = v["iscsi"]
                allow_ro("iscsi/%s" % r.get("iqn", ""),
                         bool(r.get("readOnly")))
            elif "azureDisk" in v:
                cnt_ids[VOL_AZURE].add(
                    self.interner.intern("azd/" + v["azureDisk"].get("diskName", ""))
                )
            elif "cinder" in v:
                cnt_ids[VOL_CINDER].add(
                    self.interner.intern("cinder/" + v["cinder"].get("volumeID", ""))
                )
            elif "persistentVolumeClaim" in v:
                # resolve the claim to count the bound PV's attachment type
                pvc = self.pvcs.get(
                    (pod.namespace, v["persistentVolumeClaim"].get("claimName", ""))
                )
                if pvc is not None and pvc.volume_name:
                    pv = self.pvs.get(pvc.volume_name)
                    if pv is not None:
                        from kubernetes_tpu.api import storage as kstorage

                        col = {
                            kstorage.SRC_EBS: VOL_EBS,
                            kstorage.SRC_GCE: VOL_GCE,
                            kstorage.SRC_CSI: VOL_CSI,
                            kstorage.SRC_AZURE: VOL_AZURE,
                            kstorage.SRC_CINDER: VOL_CINDER,
                        }.get(pv.source_kind)
                        if col is not None:
                            if pv.source_kind == kstorage.SRC_CSI:
                                # per-driver accounting: each CSI driver
                                # gets its own count/limit column
                                col = self._vol_col(pv.csi_driver)
                                if col >= len(cnt_ids):
                                    cnt_ids.extend(
                                        set() for _ in
                                        range(col + 1 - len(cnt_ids))
                                    )
                            prefix = {
                                VOL_EBS: "ebs/", VOL_GCE: "gce/",
                                VOL_CSI: "csi/", VOL_AZURE: "azd/",
                                VOL_CINDER: "cinder/",
                            }.get(col, "csi/")
                            ident = pv.source_id or ("pvname/" + pv.name)
                            cnt_ids[col].add(
                                self.interner.intern(prefix + ident)
                            )
        if len(cnt_ids) < self.dims.VT:  # a driver column appeared mid-scan
            cnt_ids.extend(set() for _ in range(self.dims.VT - len(cnt_ids)))
        counts = np.asarray([len(ids) for ids in cnt_ids], np.float32)
        return disk, disk_adv, counts, cnt_ids

    def _nonzero(self, pod: Pod) -> np.ndarray:
        cpu = 0.0
        mem = 0.0
        for c in pod.spec.containers:
            cpu += (
                c.requests[RESOURCE_CPU].milli
                if RESOURCE_CPU in c.requests
                else DEFAULT_MILLI_CPU_REQUEST
            )
            mem += (
                float(c.requests[RESOURCE_MEMORY])
                if RESOURCE_MEMORY in c.requests
                else DEFAULT_MEMORY_REQUEST
            )
        return np.array([cpu, mem], np.float32)

    def _req_pair(self, pod: Pod) -> Tuple[np.ndarray, np.ndarray]:
        """(req, nonzero) of a pod to commit, memoized by container request
        content: cache commits of controller-stamped identical pods skip
        the exact Fraction summation (~60us/pod).  rec.req arrays are
        never mutated in place (the R-regrow path replaces them), so
        sharing is safe."""
        # unsorted items(): two insertion orders of the same content just
        # occupy two memo slots mapping to equal arrays — correct either
        # way, and skipping 3 sorts/pod matters at 10k commits/s
        rk = (
            tuple(tuple(c.requests.items()) for c in pod.spec.containers),
            () if not pod.spec.init_containers else tuple(
                tuple(c.requests.items())
                for c in pod.spec.init_containers
            ),
        )
        hit = self._req_memo.get(rk)
        if hit is None or hit[0].shape[0] != self.dims.R:
            if len(self._req_memo) > 4096:
                self._req_memo.clear()
            hit = (self._req_vector(pod.resource_request()), self._nonzero(pod))
            self._req_memo[rk] = hit
        return hit

    def add_pod(self, pod: Pod) -> None:
        """Add an assigned (or assumed) pod: accumulate into its node's row and
        the vectorized pod index (ref internal/cache/cache.go AddPod/AssumePod)."""
        key = (pod.namespace, pod.name)
        if key in self.pods:
            self.remove_pod(pod)
        if self._free_m:
            m = self._free_m.pop()
        else:
            m = self._next_m
            self._next_m += 1
            if m >= self._cap_m:
                self._grow_pods()
        node_row = self.node_rows.get(pod.spec.node_name, -1)
        req, nonzero = self._req_pair(pod)
        ports = self._pod_ports(pod)
        disk_check, disk_adv, vcounts, cnt_ids = self._pod_vols(pod)
        disk = disk_adv  # the NODE advertises; rec stores what to retract
        rec = _PodRecord(
            key=key,
            labels=dict(pod.labels),
            ns=pod.namespace,
            node_row=node_row,
            m=m,
            req=req,
            nonzero=nonzero,
            ports=ports,
            disk_vols=disk,
            vol_counts=vcounts,
            cnt_vols=cnt_ids,
            priority=pod.spec.priority,
            pod=pod,
            start_time=pod.status.start_time,
            uid=pod.metadata.uid,
        )
        self.pods[key] = rec
        self.p_alive[m] = True
        self.p_ns[m] = self.interner.intern(pod.namespace)
        self.p_node[m] = node_row
        for k, v in pod.labels.items():
            kid = self.interner.intern(k)
            col = self._label_cols.get(kid)
            if col is None:
                col = np.full(self._cap_m, PAD, np.int32)
                self._label_cols[kid] = col
            col[m] = self.interner.intern(v)
        if node_row >= 0:
            self._row_pods.setdefault(node_row, set()).add(key)
            self.a_requested[node_row, : req.shape[0]] += req
            self.a_nonzero[node_row] += nonzero
            # tenant usage column (ISSUE 14): committed requests only —
            # an unassigned pod exerts no placement-fairness pressure
            self.a_ns_usage[
                self._ns_row(pod.namespace), : req.shape[0]
            ] += req
            if ports:  # rebuilds are row-wide sorts: skip when untouched
                for pp_ip in ports:
                    self._node_ports[node_row][pp_ip] += 1
                self._rebuild_node_ports(node_row)
            if disk:
                for dv in disk:
                    self._node_disk_vols[node_row][dv] += 1
                self._rebuild_node_vols(node_row)
            # attachable-count state dedupes by volume identity: the node's
            # used count is the number of DISTINCT ids per type
            if cnt_ids:
                cnts = self._node_cnt_vols.get(node_row)
                if cnts is None:
                    cnts = self._node_cnt_vols[node_row] = [
                        Counter() for _ in range(self.dims.VT)
                    ]
                for t, ids in enumerate(cnt_ids):
                    for vid in ids:
                        cnts[t][vid] += 1
                        self._cnt_vol_rows[t].setdefault(vid, set()).add(
                            node_row
                        )
                    self.a_volcnt[node_row, t] = len(cnts[t])
        self._register_pod_terms(pod, rec)
        self._mark_pod_dirty(node_row)
        self._gc_dirty = True
        self.generation += 1

    def _pod_commit_inputs(self, pod: Pod) -> _CommitInputs:
        """`pod`'s commit inputs, interned in add_pod's order (ports,
        volumes, namespace, labels).  add_pods derives them once per
        class and the class's pods share them."""
        req, nonzero = self._req_pair(pod)
        ports = self._pod_ports(pod)
        _, disk_adv, vcounts, cnt_ids = self._pod_vols(pod)
        it = self.interner
        ns_id = it.intern(pod.namespace)
        label_ids = [(it.intern(k), it.intern(v)) for k, v in pod.labels.items()]
        for kid, _ in label_ids:
            if kid not in self._label_cols:
                self._label_cols[kid] = np.full(self._cap_m, PAD, np.int32)
        return _CommitInputs(req, nonzero, ports, disk_adv, vcounts,
                             cnt_ids, ns_id, label_ids,
                             self._ns_row(pod.namespace))

    def add_pods(self, pods: Sequence[Pod],
                 classes: Optional[np.ndarray] = None,
                 rows: Optional[np.ndarray] = None) -> int:
        """Batched add_pod: one pass that produces byte-identical arena
        state to calling add_pod(p) for each pod in order, amortizing the
        per-pod numpy overhead (the host-commit wall of the live control
        plane):

          * row aggregates apply as ONE ordered np.add.at scatter instead
            of 2B row-slice adds (same accumulation order -> identical
            floats);
          * the pod-arena columns (alive/ns/node, label columns) write via
            fancy indexing, grouped per label key or per class;
          * port/volume row rebuilds (row-wide sorts) run once per TOUCHED
            row after all pods applied, not once per pod;
          * the generation counter advances by len(pods) in one step.

        `classes` is encode_pods' `classes_out` for these pods, in this
        order.  It plans the commit per pod class: a class's inputs
        (_pod_commit_inputs) derive once, from its first pod here, which
        is where add_pod would intern any new string; its other pods
        share them, write their label columns once per class, and gather
        their scatter rows from one row per class.  Class -1 pods derive
        their own.  Returns the number of classes committed: 0 when every
        pod derived its own inputs, as on the replacement path.

        `rows`, where the caller already holds them, are the node rows the
        pods are charged to: node_rows.get(pod.spec.node_name, -1) each,
        which is what add_pods looks up without them.

        Equivalence is pinned by tests/test_batched_commit.py."""
        if not pods:
            return 0
        # Replacement batches take the exact per-pod path: duplicate keys
        # within the batch would corrupt the two-pass layout, and replacing
        # already-resident keys would reorder the -old/+new float
        # accumulation on shared node rows (per-pod interleaves per pod;
        # the batched passes would group all removes first), breaking the
        # byte-identical contract in the low-order bits.  The hot path —
        # assuming a cycle's freshly-scheduled winners — never replaces.
        batch_keys = [(p.metadata.namespace, p.metadata.name) for p in pods]
        if len(set(batch_keys)) != len(batch_keys) or any(
            k in self.pods for k in batch_keys
        ):
            for pod in pods:
                self.add_pod(pod)
            return 0
        # -- pass 1: arena-slot allocation (growth first, so all later
        # vectorized writes target the final arrays)
        # add_pod's order: freed slots off the list's end, then fresh
        # ones, doubling the arena at each crossing
        free_m = self._free_m
        n_free = min(len(free_m), len(pods))
        ms = [free_m.pop() for _ in range(n_free)]
        n_new = len(pods) - n_free
        ms += range(self._next_m, self._next_m + n_new)
        self._next_m += n_new
        while self._cap_m < self._next_m:
            self._grow_pods()
        # -- pass 2: per-pod records + bookkeeping collection
        if rows is None:
            node_row_of = self.node_rows.get
            rows = [node_row_of(p.spec.node_name, -1) for p in pods]
        else:
            rows = rows.tolist()
        inputs: List[_CommitInputs] = []  # distinct, in first use order
        src: List[int] = []          # per pod: its index into `inputs`
        shared: Dict[int, int] = {}  # class -> index into `inputs`
        members: Dict[int, List[int]] = {}  # shared input -> arena slots
        label_writes: Dict[int, Tuple[List[int], List[int]]] = {}
        touched_ports: Set[int] = set()
        touched_vols: Set[int] = set()
        vol_rows: Set[int] = set()
        cls = [-1] * len(pods) if classes is None else classes.tolist()
        dims0 = self.dims
        pods_map, row_pods = self.pods, self._row_pods
        for j, pod in enumerate(pods):
            m = ms[j]
            key = batch_keys[j]
            node_row = rows[j]
            c = cls[j]
            i = shared.get(c, -1) if c >= 0 else -1
            fresh = i < 0
            if fresh:
                i = len(inputs)
                inputs.append(self._pod_commit_inputs(pod))
                if self.dims is not dims0:
                    # a resource or volume column appeared: inputs derived
                    # before it are narrower than add_pod's would now be
                    shared.clear()
                    dims0 = self.dims
                if c >= 0:
                    shared[c] = i
                    members[i] = [m]
                else:
                    for kid, vid in inputs[i].label_ids:
                        tgt = label_writes.setdefault(kid, ([], []))
                        tgt[0].append(m)
                        tgt[1].append(vid)
            else:
                members[i].append(m)
            req, nonzero, ports, disk_adv, vcounts, cnt_ids = inputs[i][:6]
            meta = pod.metadata
            rec = _PodRecord(
                key=key,
                labels=dict(meta.labels),
                ns=key[0],
                node_row=node_row,
                m=m,
                req=req,
                nonzero=nonzero,
                ports=ports,
                disk_vols=disk_adv,
                vol_counts=vcounts,
                cnt_vols=cnt_ids,
                priority=pod.spec.priority,
                pod=pod,
                start_time=pod.status.start_time,
                uid=meta.uid,
            )
            pods_map[key] = rec
            src.append(i)
            if fresh:
                # term registration stays IN the per-pod pass: it interns
                # the term's selector/topology strings, and id assignment
                # must follow add_pod's per-pod order (ns, labels, terms).
                # A class's later pods carry no affinity (the static key
                # requires none), so theirs is a no-op.
                self._register_pod_terms(pod, rec)
            if node_row >= 0:
                row_pods.setdefault(node_row, set()).add(key)
                if ports:
                    for pp_ip in ports:
                        self._node_ports[node_row][pp_ip] += 1
                    touched_ports.add(node_row)
                if disk_adv:
                    for dv in disk_adv:
                        self._node_disk_vols[node_row][dv] += 1
                    touched_vols.add(node_row)
                if cnt_ids:
                    cnts = self._node_cnt_vols.get(node_row)
                    if cnts is None:
                        cnts = self._node_cnt_vols[node_row] = [
                            Counter() for _ in range(self.dims.VT)
                        ]
                    for t, ids in enumerate(cnt_ids):
                        for vid in ids:
                            cnts[t][vid] += 1
                            self._cnt_vol_rows[t].setdefault(vid, set()).add(
                                node_row
                            )
                    vol_rows.add(node_row)
        # -- pass 3: vectorized arena writes
        ms_arr = np.asarray(ms, np.intp)
        src_arr = np.asarray(src, np.intp)
        self.p_alive[ms_arr] = True
        self.p_ns[ms_arr] = np.asarray(
            [inp.ns_id for inp in inputs], np.int32
        )[src_arr]
        self.p_node[ms_arr] = np.asarray(rows, np.int32)
        for kid, (kms, vids) in label_writes.items():
            self._label_cols[kid][np.asarray(kms, np.intp)] = np.asarray(
                vids, np.int32
            )
        for i, cms in members.items():
            cms_arr = np.asarray(cms, np.intp)
            for kid, vid in inputs[i].label_ids:
                self._label_cols[kid][cms_arr] = vid
        rows_arr = np.asarray(rows, np.intp)
        on_node = rows_arr >= 0
        if on_node.any():
            # one row per distinct input, gathered per pod: the [B, R]
            # matrix np.stack over the records would build
            req_stack = np.stack([inp.req for inp in inputs])[src_arr]
            nz_stack = np.stack([inp.nonzero for inp in inputs])[src_arr]
            t_arr = np.asarray(
                [inp.tenant for inp in inputs], np.intp
            )[src_arr]
            on_rows = rows_arr[on_node]
            np.add.at(self.a_requested, on_rows, req_stack[on_node])
            np.add.at(self.a_nonzero, on_rows, nz_stack[on_node])
            # tenant usage columns, same ordered-scatter shape
            np.add.at(self.a_ns_usage, t_arr[on_node], req_stack[on_node])
            self._dirty_pod_rows.update(on_rows.tolist())
        for row in vol_rows:
            cnts = self._node_cnt_vols[row]
            for t in range(self.dims.VT):
                self.a_volcnt[row, t] = len(cnts[t])
        for row in touched_ports:
            self._rebuild_node_ports(row)
        for row in touched_vols:
            self._rebuild_node_vols(row)
        self._gc_dirty = True
        self.generation += len(pods)
        return len(members)

    def remove_pod(self, pod: Pod) -> None:
        key = (pod.namespace, pod.name)
        rec = self.pods.pop(key, None)
        if rec is None:
            return
        m = rec.m
        self.p_alive[m] = False
        self.p_ns[m] = PAD
        self.p_node[m] = PAD
        for col in self._label_cols.values():
            col[m] = PAD
        self._free_m.append(m)
        row = rec.node_row
        if row >= 0:
            self._row_pods.get(row, set()).discard(key)
            self.a_requested[row, : rec.req.shape[0]] -= rec.req
            self.a_nonzero[row] -= rec.nonzero
            self.a_ns_usage[
                self._ns_row(rec.ns), : rec.req.shape[0]
            ] -= rec.req
            if rec.ports:  # rebuilds are row-wide sorts: skip when untouched
                c = self._node_ports[row]
                for pp_ip in rec.ports:
                    c[pp_ip] -= 1
                    if c[pp_ip] <= 0:
                        del c[pp_ip]
                self._rebuild_node_ports(row)
            if rec.disk_vols:
                c = self._node_disk_vols[row]
                for dv in rec.disk_vols:
                    c[dv] -= 1
                    if c[dv] <= 0:
                        del c[dv]
                self._rebuild_node_vols(row)
            cnts = self._node_cnt_vols.get(row)
            if cnts is not None:
                for t, ids in enumerate(rec.cnt_vols):
                    for vid in ids:
                        cnts[t][vid] -= 1
                        if cnts[t][vid] <= 0:
                            del cnts[t][vid]
                            rows = self._cnt_vol_rows[t].get(vid)
                            if rows is not None:
                                rows.discard(row)
                                if not rows:
                                    del self._cnt_vol_rows[t][vid]
                    self.a_volcnt[row, t] = len(cnts[t])
        self._unregister_pod_terms(rec)
        self._mark_pod_dirty(row)
        self._gc_dirty = True
        self.generation += 1

    # ------------------------------------------------- affinity term grouping

    def _iter_pod_terms(self, pod: Pod):
        aff = pod.spec.affinity
        if aff is None:
            return
        if aff.pod_anti_affinity:
            for t in aff.pod_anti_affinity.required:
                yield K_ANTI_REQ, 1.0, t
            for wt in aff.pod_anti_affinity.preferred:
                yield K_ANTI_PREF, float(wt.weight), wt.term
        if aff.pod_affinity:
            for t in aff.pod_affinity.required:
                yield K_AFF_REQ, 1.0, t
            for wt in aff.pod_affinity.preferred:
                yield K_AFF_PREF, float(wt.weight), wt.term

    def _term_sig(self, kind: int, weight: float, term: PodAffinityTerm, pod_ns: str):
        namespaces = frozenset(term.namespaces or (pod_ns,))
        sel = _sel_requirements(term.label_selector)
        sel_key = tuple(sel.requirements) if sel is not None else None
        return (kind, weight, term.topology_key, namespaces, sel_key)

    def _register_pod_terms(self, pod: Pod, rec: _PodRecord) -> None:
        for kind, weight, term in self._iter_pod_terms(pod):
            if not term.topology_key:
                continue
            kid = self.register_topology_key(term.topology_key)
            sig = self._term_sig(kind, weight, term, pod.namespace)
            g = self.term_groups.get(sig)
            if g is None:
                sel = _sel_requirements(term.label_selector)
                g = _TermGroup(
                    kind=kind,
                    topo_key_id=kid,
                    namespaces=frozenset(term.namespaces or (pod.namespace,)),
                    selector=sel if sel is not None else klabels.Selector(()),
                    weight=weight,
                    pair_counts=np.zeros(self.dims.TP, np.float32),
                )
                self.term_groups[sig] = g
            g.members += 1
            if rec.node_row >= 0:
                pid = self._node_pair_id[kid][rec.node_row]
                if pid >= 0:
                    g.pair_counts[pid] += 1
            rec.group_refs.append(sig)

    def _shift_pod_pairs(self, rec: _PodRecord, add: bool) -> None:
        """Add/remove rec's term-group pair contributions for its current
        node_row (used when the pod's node assignment or the node's topology
        labels change, without touching group membership)."""
        if rec.node_row < 0:
            return
        delta = 1.0 if add else -1.0
        for sig in rec.group_refs:
            g = self.term_groups.get(sig)
            if g is None:
                continue
            pid = self._node_pair_id[g.topo_key_id][rec.node_row]
            if pid >= 0:
                g.pair_counts[pid] += delta

    def _unregister_pod_terms(self, rec: _PodRecord) -> None:
        for sig in rec.group_refs:
            g = self.term_groups.get(sig)
            if g is None:
                continue
            g.members -= 1
            if rec.node_row >= 0:
                pid = self._node_pair_id[g.topo_key_id][rec.node_row]
                if pid >= 0:
                    g.pair_counts[pid] -= 1
            if g.members <= 0:
                del self.term_groups[sig]

    # -------------------------------------------------------------- storage

    def add_pv(self, pv) -> None:
        self.pvs[pv.name] = pv
        self.generation += 1

    def remove_pv(self, name: str) -> None:
        self.pvs.pop(name, None)
        self.generation += 1

    def add_pvc(self, pvc) -> None:
        self.pvcs[(pvc.namespace, pvc.name)] = pvc
        self.generation += 1

    def remove_pvc(self, namespace: str, name: str) -> None:
        self.pvcs.pop((namespace, name), None)
        self.generation += 1

    def add_storage_class(self, sc) -> None:
        self.storage_classes[sc.name] = sc
        self.generation += 1

    def remove_storage_class(self, name: str) -> None:
        self.storage_classes.pop(name, None)
        self.generation += 1

    def _rows_matching_pv_topology(self, pv) -> List[int]:
        """Node rows compatible with a PV's nodeAffinity (exact host-side
        evaluation — ref volumebinder checking PV.spec.nodeAffinity)."""
        from kubernetes_tpu.cpuref.reference import match_node_selector_term

        rows = []
        for name, row in self.node_rows.items():
            node = self._row_node[row]
            if pv.node_affinity is not None:
                if not any(
                    match_node_selector_term(t, node)
                    for t in pv.node_affinity.terms
                ):
                    continue
            rows.append(row)
        return rows

    def _rows_matching_pv_zone(self, pv) -> Optional[List[int]]:
        """Node rows matching the PV's zone/region labels, or None if the PV
        carries no zone labels (no restriction) — ref predicates.go
        NoVolumeZoneConflict (:616-741); multi-zone PV label values use the
        "__" separator (volumehelpers.LabelZonesToSet)."""
        restricting = {}
        for key in (HOSTNAME_KEY, ZONE_KEY, REGION_KEY):
            val = pv.labels.get(key)
            if val is not None:
                restricting[key] = set(val.split("__"))
        if not restricting:
            return None
        rows = []
        for name, row in self.node_rows.items():
            node = self._row_node[row]
            if all(node.labels.get(k) in vs for k, vs in restricting.items()):
                rows.append(row)
        return rows

    def _rows_to_pairs(self, rows: List[int]) -> np.ndarray:
        pairs = np.zeros(self.dims.TP, bool)
        col = self._node_pair_id[self.hostname_key]
        for r in rows:
            pid = col[r]
            if pid >= 0:
                pairs[pid] = True
        return pairs

    def _candidate_pvs(self, pvc) -> List[object]:
        """Available PVs that could satisfy an unbound claim (class, size,
        access modes) — the volume binder's FindPodVolumes matching."""
        out = []
        for pv in self.pvs.values():
            if pv.phase not in ("Available",):
                continue
            if pv.storage_class != pvc.storage_class:
                continue
            if pvc.request is not None and pv.capacity is not None and pv.capacity < pvc.request:
                continue
            if pvc.access_modes and not set(pvc.access_modes) <= set(pv.access_modes):
                continue
            out.append(pv)
        return out

    def _pod_volume_terms(self, pod: Pod):
        """(zone_terms, bind_terms, fail_all): per-PVC topology restrictions
        as hostname-pair sets.  (Attachment-type counts are handled by
        _pod_vols, which both add_pod and encode_pods use.)"""
        zone_terms: List[np.ndarray] = []
        bind_terms: List[np.ndarray] = []
        fail_all = False
        for v in pod.spec.volumes:
            claim = v.get("persistentVolumeClaim")
            if not claim:
                continue
            pvc = self.pvcs.get((pod.namespace, claim.get("claimName", "")))
            if pvc is None:
                fail_all = True  # missing PVC: unschedulable (ErrMissingPVC)
                continue
            if pvc.volume_name:
                pv = self.pvs.get(pvc.volume_name)
                if pv is None:
                    fail_all = True
                    continue
                zrows = self._rows_matching_pv_zone(pv)
                if zrows is not None:
                    zone_terms.append(self._rows_to_pairs(zrows))
                if pv.node_affinity is not None:
                    bind_terms.append(
                        self._rows_to_pairs(self._rows_matching_pv_topology(pv))
                    )
            else:
                sc = self.storage_classes.get(pvc.storage_class)
                cands = self._candidate_pvs(pvc)
                if cands:
                    allowed = np.zeros(self.dims.TP, bool)
                    for pv in cands:
                        rows = self._rows_matching_pv_topology(pv)
                        zrows = self._rows_matching_pv_zone(pv)
                        if zrows is not None:
                            rows = [r for r in rows if r in set(zrows)]
                        allowed |= self._rows_to_pairs(rows)
                    bind_terms.append(allowed)
                elif sc is not None and sc.provisioner:
                    # dynamic provisioning: WaitForFirstConsumer defers to
                    # the chosen node; Immediate will provision anywhere
                    pass
                else:
                    fail_all = True
        return zone_terms, bind_terms, fail_all

    # ------------------------------------------------------------- spreading

    def set_service_affinity_keys(self, key_ids: Sequence[int]) -> None:
        """Configure the CheckServiceAffinity homogeneity labels (Policy
        serviceAffinity argument, predicates.go:993-1067)."""
        self.service_affinity_keys = list(key_ids)
        self._pod_row_cache.clear()

    def adopt_filter_config(self, cfg):
        """Normalize a FilterConfig against THIS encoder: intern any
        still-string service-affinity labels and register the keys so
        encode_pods emits the candidate columns.  Returns the (possibly
        replaced) config — the single entry point for runtime components
        (Scheduler, ExtenderServer)."""
        if cfg.service_affinity_labels:
            import dataclasses as _dc

            ids = tuple(
                self.interner.intern(x) if isinstance(x, str) else int(x)
                for x in cfg.service_affinity_labels
            )
            if ids != tuple(cfg.service_affinity_labels):
                cfg = _dc.replace(cfg, service_affinity_labels=ids)
            self.set_service_affinity_keys(ids)
        return cfg

    def add_spread_selector(self, namespace: str, match_labels: Dict[str, str],
                            kind: str = "Service") -> None:
        """Register a Service/RC/RS/StatefulSet selector for SelectorSpread
        (ref priorities/selector_spreading.go getSelectors).  `kind` matters
        to CheckServiceAffinity, whose backfill gate counts only Services
        (GetPodServices, predicates.go:978)."""
        self._spread.append((namespace, klabels.selector_from_match_labels(match_labels)))
        self._spread_kinds.append(kind)
        if kind == "Service":
            self._service_selectors.append((namespace, dict(match_labels)))
        if len(self._spread) > self.dims.G:
            self.dims = self.dims.bump(G=len(self._spread))
        self._gc_dirty = True
        self.generation += 1

    def _match_selector_vec(
        self, sel: klabels.Selector, ns_ids: Optional[Sequence[int]]
    ) -> np.ndarray:
        """Vectorized selector match over the existing-pod arena -> bool[M]."""
        m = self.p_alive.copy()
        if ns_ids is not None:
            m &= np.isin(self.p_ns, np.asarray(list(ns_ids), np.int32))
        for r in sel.requirements:
            kid = self.interner.lookup(r.key)
            col = self._label_cols.get(kid) if kid >= 0 else None
            if col is None:
                vals = np.full(self._cap_m, PAD, np.int32)
            else:
                vals = col
            if r.operator == klabels.IN:
                ids = [self.interner.lookup(v) for v in r.values]
                m &= np.isin(vals, np.asarray([i for i in ids if i >= 0] or [-2], np.int32))
            elif r.operator == klabels.NOT_IN:
                ids = [self.interner.lookup(v) for v in r.values]
                m &= ~np.isin(vals, np.asarray([i for i in ids if i >= 0] or [-2], np.int32))
            elif r.operator == klabels.EXISTS:
                m &= vals != PAD
            elif r.operator == klabels.DOES_NOT_EXIST:
                m &= vals == PAD
            else:  # Gt/Lt: rare — fall back to per-pod python
                keep = np.zeros(self._cap_m, bool)
                for rec in self.pods.values():
                    keep[rec.m] = r.matches(rec.labels)
                m &= keep
        return m

    # ------------------------------------------------------------- snapshot

    # ClusterTensors field -> arena attribute, split by what dirties them:
    # pod commits touch only the aggregate fields, node events touch every
    # per-row field of the affected row.
    _POD_FIELDS = (
        ("requested", "a_requested"), ("nonzero_req", "a_nonzero"),
        ("vol_counts", "a_volcnt"), ("port_pp", "a_ppp"),
        ("port_ip", "a_pip"), ("port_used", "a_pused"),
        ("disk_vol_ids", "a_dvol"),
    )
    _NODE_FIELDS = (
        ("allocatable", "a_allocatable"), ("valid", "a_valid"),
        ("unschedulable", "a_unsched"), ("not_ready", "a_notready"),
        ("mem_pressure", "a_mempress"), ("disk_pressure", "a_diskpress"),
        ("pid_pressure", "a_pidpress"), ("node_name_id", "a_name"),
        ("label_keys", "a_lkeys"), ("label_vals", "a_lvals"),
        ("label_nums", "a_lnums"), ("taint_key", "a_tkey"),
        ("taint_val", "a_tval"), ("taint_effect", "a_teff"),
        ("topo_pairs", "a_topo"), ("image_id", "a_img_id"),
        ("avoid_owner", "a_avoid"), ("vol_limits", "a_vollim"),
    )

    def _pair_topo_key_arr(self) -> np.ndarray:
        pk = np.full(self.dims.TP, PAD, np.int32)
        if self._pair_topo_key:
            pk[: len(self._pair_topo_key)] = np.asarray(self._pair_topo_key, np.int32)
        return pk

    def _image_size_arr(self) -> np.ndarray:
        # image spread scaling (image_locality.go scaledImageScore):
        # scaled = size * numNodesWithImage / totalNodes
        total = max(len(self.node_rows), 1)
        scale = np.ones_like(self.a_img_sz)
        ids = self.a_img_id
        if self._image_nodes:
            lut = np.zeros(len(self.interner), np.float32)
            for name, cnt in self._image_nodes.items():
                iid = self.interner.lookup(name)
                if iid >= 0:
                    lut[iid] = cnt / total
            scale = np.where(ids >= 0, lut[np.maximum(ids, 0)], 0.0)
        return (self.a_img_sz * scale).astype(np.float32)

    def snapshot(self, full: bool = False) -> ClusterTensors:
        """Point-in-time ClusterTensors.  Incremental by default per the
        class docstring's dirty-row contract (cow re-encode of dirty rows,
        identity-reuse of untouched fields — treat the arrays as
        immutable); `full=True` forces a from-scratch rebuild."""
        if full or self._snap is None or self._snap_dirty_all:
            snap = self._snapshot_full()
            self._snap_rows_acc = None  # consumer must full-sync
        else:
            snap = self._snapshot_incremental()
        self._snap = snap
        self._snap_dirty_all = False
        self._dirty_node_rows.clear()
        self._dirty_pod_rows.clear()
        self._gc_dirty = False
        self._snap_pairs_len = len(self._pair_topo_key)
        return snap

    def _snapshot_full(self) -> ClusterTensors:
        fields = {
            name: getattr(self, attr).copy()
            for name, attr in self._POD_FIELDS + self._NODE_FIELDS
        }
        return ClusterTensors(
            # per-group per-node matching-pod counts: the device-side source
            # for SelectorSpread when the batch is spread-lean (every pod in
            # <= 1 group); multi-group batches ship exact AND counts in
            # PodBatch.spread_counts instead
            group_counts=self._group_counts(),
            pair_topo_key=self._pair_topo_key_arr(),
            image_size=self._image_size_arr(),
            **fields,
        )

    def _snapshot_incremental(self) -> ClusterTensors:
        prev = self._snap
        node_d = self._dirty_node_rows
        pod_d = self._dirty_pod_rows | node_d
        changed: Dict[str, np.ndarray] = {}

        def cow(spec, rows_idx):
            for name, attr in spec:
                src = getattr(self, attr)
                new = getattr(prev, name).copy()
                new[rows_idx] = src[rows_idx]
                changed[name] = new

        if pod_d:
            cow(self._POD_FIELDS, np.asarray(sorted(pod_d), np.intp))
        if node_d:
            cow(self._NODE_FIELDS, np.asarray(sorted(node_d), np.intp))
            # the per-image scale divides by the node count, so any node
            # event rescales every row
            changed["image_size"] = self._image_size_arr()
        if self._gc_dirty or prev.group_counts.shape != (self._cap_n, self.dims.G):
            changed["group_counts"] = self._group_counts()
        if len(self._pair_topo_key) != self._snap_pairs_len:
            changed["pair_topo_key"] = self._pair_topo_key_arr()
        if self._snap_rows_acc is not None:
            self._snap_rows_acc |= pod_d
        if not changed:
            return prev
        return dataclasses.replace(prev, **changed)

    def row_name(self, row: int) -> str:
        """Node name for an arena row (O(1); _row_node is kept consistent by
        add/update/remove_node)."""
        node = self._row_node.get(row)
        return node.name if node is not None else ""

    def pods_snapshot(self) -> "PodsArena":
        """Per-pod device tensors for preemption what-ifs: the assigned-pod
        arena as a PodsArena view (node_row, priority, req, nonzero, valid,
        start, keys, uids).

        M is the padded pod capacity; `keys` maps arena index -> (ns, name)
        and `uids` -> metadata.uid for decoding victim picks on the host."""
        M = self._cap_m
        node = np.full(M, PAD, np.int32)
        prio = np.zeros(M, np.int32)
        req = np.zeros((M, self.dims.R), np.float32)
        nz = np.zeros((M, 2), np.float32)
        valid = np.zeros(M, bool)
        # f64: epoch-second timestamps quantize to ~128s in f32; device
        # kernels receive dense RANKS (models.preemption.dense_start_ranks)
        start = np.zeros(M, np.float64)
        keys: List = [None] * M
        uids: List = [""] * M
        for rec in self.pods.values():
            m = rec.m
            node[m] = rec.node_row
            prio[m] = rec.priority
            req[m, : rec.req.shape[0]] = rec.req
            nz[m] = rec.nonzero
            valid[m] = rec.node_row >= 0
            start[m] = rec.start_time
            keys[m] = rec.key
            uids[m] = rec.uid
        return PodsArena(node, prio, req, nz, valid, start, keys, uids)

    def preemption_arrays(self, pod: Pod, max_vols=(39.0, 16.0, 1e9, 16.0, 1e9)):
        """Extended what-if arrays for models.preemption.preempt_one.

        selectVictimsOnNode re-runs all predicates after victim removal
        (generic_scheduler.go:1054-1128); the resolvable ones with per-pod
        device state — resources, host ports, disk conflicts, volume-count
        budgets — fold into one `used - freed + req <= allocatable` check by
        appending columns to the resource axis:

          col R     : count of pods whose host ports conflict with `pod`
                      (limit 0.5, pod "requests" 0.25 -> remaining must be 0)
          col R+1   : count of pods holding one of `pod`'s exclusive disk
                      volumes (same encoding)
          col R+2.. : the five Max*VolumeCount budgets

        Returns (pod_req_ext f32[E], requested_ext f32[N, E],
        allocatable_ext f32[N, E], pods_req_ext f32[M, E])."""
        # _pod_vols can grow dims.VT (first-seen CSI driver): call it
        # BEFORE sizing the ext arrays (the encode_pods pre-registration
        # discipline)
        want_ports = self._pod_ports(pod)
        want_disk, _, new_vols, _ = self._pod_vols(pod)
        R = self.dims.R
        E = R + 2 + self.dims.VT
        M, N = self._cap_m, self._cap_n
        want_disk_set = set(want_disk)

        pods_ext = np.zeros((M, E), np.float32)
        for rec in self.pods.values():
            m = rec.m
            pods_ext[m, : rec.req.shape[0]] = rec.req
            if want_ports and rec.node_row >= 0:
                for pp, ip in rec.ports:
                    if any(
                        pp == wpp and (ip == wip or ip == WILDCARD or wip == WILDCARD)
                        for wpp, wip in want_ports
                    ):
                        pods_ext[m, R] = 1.0
                        break
            if want_disk_set and rec.node_row >= 0:
                if any(dv in want_disk_set for dv in rec.disk_vols):
                    pods_ext[m, R + 1] = 1.0
            pods_ext[m, R + 2 :] = rec.vol_counts

        requested_ext = np.zeros((N, E), np.float32)
        requested_ext[:, :R] = self.a_requested
        arena_nodes = np.array(
            [rec.node_row for rec in self.pods.values()], np.int32
        ).reshape(-1)
        arena_ms = np.array([rec.m for rec in self.pods.values()], np.int32).reshape(-1)
        if len(arena_ms):
            on_node = arena_nodes >= 0
            np.add.at(
                requested_ext[:, R], arena_nodes[on_node], pods_ext[arena_ms[on_node], R]
            )
            np.add.at(
                requested_ext[:, R + 1],
                arena_nodes[on_node],
                pods_ext[arena_ms[on_node], R + 1],
            )
        # the pending pod's volumes already attached on a node consume no
        # NEW attachment there (filterVolumes already-mounted subtraction):
        # credit them against the node's distinct-attached counts
        requested_ext[:, R + 2 :] = np.maximum(
            self.a_volcnt - self._vol_overlap([pod])[0].T, 0.0
        )

        allocatable_ext = np.zeros((N, E), np.float32)
        allocatable_ext[:, :R] = self.a_allocatable
        allocatable_ext[:, R] = 0.5
        allocatable_ext[:, R + 1] = 0.5
        defaults = np.asarray(max_vols, np.float32)
        if defaults.shape[0] < self.dims.VT:
            # per-CSI-driver columns inherit the CSI default cap
            defaults = np.concatenate([
                defaults,
                np.full(self.dims.VT - defaults.shape[0],
                        float(max_vols[VOL_CSI]), np.float32),
            ])
        allocatable_ext[:, R + 2 :] = np.minimum(defaults[None], self.a_vollim)

        pod_req_ext = np.zeros(E, np.float32)
        req = self._req_vector(pod.resource_request())
        pod_req_ext[: req.shape[0]] = req
        pod_req_ext[R] = 0.25 if want_ports else 0.0
        pod_req_ext[R + 1] = 0.25 if want_disk_set else 0.0
        pod_req_ext[R + 2 :] = new_vols
        return pod_req_ext, requested_ext, allocatable_ext, pods_ext

    def victim_volume_tables(self, slots):
        """Identity-deduped volume-credit tables for the preemption what-if
        (VERDICT r4 #4 — closes PARITY §3's linear-subtraction over-credit):
        victims sharing one volume must free ONE attachment, and a volume
        also held by a non-victim frees none.

        Per distinct (node, type, volume-id) held by a LISTED victim:
          vid_total[j]  — holders on the node among ALL assigned pods
          vid_listed[j] — holders among the listed victims
        A volume is freed iff every holder is evicted (evicted == total);
        the reprieve scan decrements evicted counts as victims return.
        Arrays carry one sentinel tail slot (total 2^30, never full) that
        out-of-range gathers hit.

        Returns (slot_vids i32[Kv, VMAX] aligned row-for-row with `slots`,
        vid_type i32[VID+1], vid_total i32[VID+1], vid_listed i32[VID+1],
        freed_vol_init f32[N, VT])."""
        N, VT = self._cap_n, self.dims.VT
        m_to_rec = {rec.m: rec for rec in self.pods.values()}
        vid_index: Dict[tuple, int] = {}
        vid_type: List[int] = []
        vid_total: List[int] = []
        vid_listed: List[int] = []
        per_slot: List[List[int]] = []
        for s in np.asarray(slots).tolist():
            vids: List[int] = []
            rec = m_to_rec.get(int(s)) if s >= 0 else None
            if rec is not None and rec.cnt_vols and rec.node_row >= 0:
                cnts = self._node_cnt_vols.get(rec.node_row)
                for t, ids in enumerate(rec.cnt_vols):
                    for vid in ids:
                        keyv = (rec.node_row, t, vid)
                        j = vid_index.get(keyv)
                        if j is None:
                            j = vid_index[keyv] = len(vid_type)
                            vid_type.append(t)
                            vid_total.append(
                                int(cnts[t][vid]) if cnts else 1)
                            vid_listed.append(0)
                        vid_listed[j] += 1
                        vids.append(j)
            per_slot.append(vids)
        vmax = 1
        while vmax < max((len(v) for v in per_slot), default=1):
            vmax *= 2
        nv = 1
        while nv < max(len(vid_type), 1):
            nv *= 2
        slot_vids = np.full((len(per_slot), vmax), -1, np.int32)
        for i, vids in enumerate(per_slot):
            slot_vids[i, : len(vids)] = vids
        t_arr = np.full(nv + 1, VT, np.int32)      # sentinel type -> dropped
        t_arr[: len(vid_type)] = vid_type
        tot = np.full(nv + 1, 1 << 30, np.int32)   # sentinel never full
        tot[: len(vid_total)] = vid_total
        lst = np.zeros(nv + 1, np.int32)
        lst[: len(vid_listed)] = vid_listed
        freed_vol_init = np.zeros((N, VT), np.float32)
        for (row, t, _vid), j in vid_index.items():
            if vid_listed[j] >= vid_total[j]:
                freed_vol_init[row, t] += 1.0
        return slot_vids, t_arr, tot, lst, freed_vol_init

    def has_required_pod_terms(self) -> bool:
        """Any live required (anti-)affinity term in the cluster — the
        condition under which the counting preemption what-if cannot be
        trusted alone and the object-level nomination verify must run."""
        return any(
            g.members > 0 and g.kind in (K_ANTI_REQ, K_AFF_REQ)
            for g in self.term_groups.values()
        )

    # ------------------------------------------------------------ pod batch

    def batch_pad(self, n: int) -> int:
        """Effective pod-batch pad width for an n-pod batch: the transient
        batch_width() override when one is active (never growing dims.B),
        else the sticky pow2 floor dims.B.  EVERY batch-shaped tensor cut
        for one encode must use this (encode_pods, _vol_overlap, and the
        models/batched.py port/affinity helpers) or shapes diverge between
        the batch leaves and the engine retraces per cycle."""
        if self._batch_width is not None:
            return _pow2(max(n, 1), self._batch_width)
        return _pow2(max(n, 1), max(self.dims.B, 1))

    @contextlib.contextmanager
    def batch_width(self, width: Optional[int]):
        """Context manager pinning the pod-batch pad width for the encode
        calls inside it (width=None is a no-op passthrough).  The express
        lane wraps its encode in batch_width(express_batch_size) so its
        small batches compile once at that shape instead of re-padding to
        the bulk lane's sticky dims.B."""
        prev = self._batch_width
        self._batch_width = width
        try:
            yield self
        finally:
            self._batch_width = prev

    def encode_pods(self, pods: Sequence[Pod],
                    classes_out: Optional[np.ndarray] = None,
                    affinity_phase: Optional[Callable] = None,
                    pair_rows: bool = False,
                    pair_phase: Optional[Callable] = None) -> PodBatch:
        """Encode pending pods into a PodBatch, precomputing the
        inter-pod-affinity pair tensors against current cluster state.

        `classes_out`, an i32[len(pods)] filled with -1 by the caller,
        receives each pod's class: pods with equal static keys
        (_pod_static_key) share one index, numbered in order of first
        appearance; pods with no static key (affinity, volumes, live
        term_groups, unhashable content) stay -1.  add_pods takes the
        same array to commit the winners class by class.

        `affinity_phase`, a context-manager factory, is entered around
        the pass that fills the pods' inter-pod-affinity term and pair
        columns (only batches that are not affinity-lean have one).

        The TP-wide pair leaves (PAIR_FIELDS) of a batch that is not
        affinity-lean or not volume-lean hold one row per pod class.
        `pair_rows` asks for them as PairRows (the class rows and each
        pod's row index), which pack_tree ships without expanding them;
        a batch with more classes than pack_tree's factoring limit still
        gets dense leaves.  `pair_phase(rows, factored)`, a context-
        manager factory, is entered around spreading the class rows
        (only batches with TP-wide leaves have one)."""
        d = self.dims
        B = self.batch_pad(len(pods))
        if self._batch_width is None and B > d.B:
            self.dims = d = dataclasses.replace(d, B=B)
        # grow per-pod dims to fit
        need = dict(Q=1, TT=1, NS=1, S=1, E=1, V=1, PS=1, PT=1, AT=1, GP=1, C=1,
                    DV=1, VZ=1, VB=1)
        for pod in pods:
            need["Q"] = max(need["Q"], len(pod.host_ports()))
            # pod-side disk-conflict check tokens: one per gce/ebs/iscsi
            # volume, one PER MONITOR for rbd (the overlap identity) — the
            # DV axis must fit them all or conflicts silently vanish
            n_disk = 0
            for v in pod.spec.volumes:
                if "rbd" in v:
                    n_disk += len(v["rbd"].get("monitors", []) or ())
                elif ("gcePersistentDisk" in v or "awsElasticBlockStore" in v
                      or "iscsi" in v):
                    n_disk += 1
            need["DV"] = max(need["DV"], n_disk)
            n_pvc = sum(1 for v in pod.spec.volumes if "persistentVolumeClaim" in v)
            need["VZ"] = max(need["VZ"], n_pvc)
            need["VB"] = max(need["VB"], n_pvc)
            need["TT"] = max(need["TT"], len(pod.spec.tolerations))
            need["NS"] = max(need["NS"], len(pod.spec.node_selector))
            need["C"] = max(need["C"], len(pod.spec.containers))
            aff = pod.spec.affinity
            na = aff.node_affinity if aff else None
            if na and na.required:
                need["S"] = max(need["S"], len(na.required.terms))
                for t in na.required.terms:
                    need["E"] = max(need["E"], len(t.match_expressions) + len(t.match_fields))
                    for e in t.match_expressions:
                        need["V"] = max(need["V"], len(e.values))
            if na:
                need["PS"] = max(need["PS"], len(na.preferred))
                for p in na.preferred:
                    need["E"] = max(need["E"], len(p.preference.match_expressions))
                    for e in p.preference.match_expressions:
                        need["V"] = max(need["V"], len(e.values))
            if aff and aff.pod_affinity:
                need["PT"] = max(need["PT"], len(aff.pod_affinity.required))
            if aff and aff.pod_anti_affinity:
                need["AT"] = max(need["AT"], len(aff.pod_anti_affinity.required))
        bump = {k: v for k, v in need.items() if v > getattr(d, k)}
        if bump:
            self.dims = d = self.dims.bump(**bump)
        # topology keys must be registered before encoding pair tensors, and
        # extended-resource columns before the out arrays are allocated
        # (a mid-loop dims.R bump would orphan the already-allocated arrays)
        for pod in pods:
            for _, _, term in self._iter_pod_terms(pod):
                if term.topology_key:
                    self.register_topology_key(term.topology_key)
            # resource column registration needs only the NAMES — iterate
            # container dicts directly instead of summing Quantities
            # (resource_request is exact-Fraction math, ~15us/pod)
            for c in pod.spec.containers:
                for rname in c.requests:
                    self._res_col(rname)
            for c in pod.spec.init_containers:
                for rname in c.requests:
                    self._res_col(rname)
            # CSI driver columns must exist BEFORE the out arrays are cut
            # (same reason as resource columns: a mid-loop dims.VT bump
            # would orphan already-allocated batch arrays)
            for v in pod.spec.volumes:
                claim = v.get("persistentVolumeClaim")
                if not claim:
                    continue
                pvc = self.pvcs.get((pod.namespace, claim.get("claimName", "")))
                if pvc is not None and pvc.volume_name:
                    pv = self.pvs.get(pvc.volume_name)
                    if pv is not None and pv.source_kind == "csi" and pv.csi_driver:
                        self._vol_col(pv.csi_driver)
        d = self.dims
        it = self.interner
        f32, i32 = np.float32, np.int32

        def zi(*shape):
            return np.full(shape, PAD, i32)

        def zf(*shape):
            return np.zeros(shape, f32)

        def zb(*shape):
            return np.zeros(shape, bool)

        # ---- lean widths: the pair tensors are [.., TP] with TP the whole
        # topology-pair vocabulary (hostname pairs dominate: ~1 per node).
        # For a batch with no inter-pod-affinity exposure / no volumes they
        # are provably all-zero, so emit width-1 placeholders instead — the
        # kernels gate on shape (ops/predicates._is_lean) and skip the work.
        # At 5k nodes this removes ~70MB of zero upload per 512-pod batch.
        aff_lean = not self.term_groups and not any(
            p.spec.affinity is not None
            and (
                p.spec.affinity.pod_affinity is not None
                or p.spec.affinity.pod_anti_affinity is not None
            )
            for p in pods
        )
        vol_lean = not any(p.spec.volumes for p in pods)
        TPA = 1 if aff_lean else d.TP
        TPV = 1 if vol_lean else d.TP
        SA = max(len(self.service_affinity_keys), 1)
        # node-affinity lean widths: a batch where NO pod carries required /
        # preferred nodeAffinity emits zero-width term tensors, and the
        # selector/affinity kernels skip statically on shape — the expr
        # evaluation is [B, S, E, N, L] work, the single hottest kernel on
        # the CPU fallback for affinity-free workloads
        def _na(p):
            return p.spec.affinity.node_affinity if p.spec.affinity else None

        SL = 0 if not any(
            _na(p) and _na(p).required for p in pods
        ) else d.S
        PSL = 0 if not any(
            _na(p) and _na(p).preferred for p in pods
        ) else d.PS

        out = dict(
            valid=zb(B),
            req=zf(B, d.R),
            nonzero_req=zf(B, 2),
            limits2=zf(B, 2),
            priority=np.zeros(B, i32),
            best_effort=zb(B),
            ns_id=zi(B),
            owner_uid=zi(B),
            node_name_req=zi(B),
            port_pp=zi(B, d.Q),
            port_ip=zi(B, d.Q),
            port_valid=zb(B, d.Q),
            tol_key=zi(B, d.TT),
            tol_op=np.zeros((B, d.TT), i32),
            tol_val=zi(B, d.TT),
            tol_effect=zi(B, d.TT),
            tol_valid=zb(B, d.TT),
            ns_keys=zi(B, d.NS),
            ns_vals=zi(B, d.NS),
            ns_valid=zb(B, d.NS),
            has_req_affinity=zb(B),
            term_valid=zb(B, SL),
            expr_key=zi(B, SL, d.E),
            expr_op=np.zeros((B, SL, d.E), i32),
            expr_vals=zi(B, SL, d.E, d.V),
            expr_nval=np.zeros((B, SL, d.E), i32),
            expr_num=np.full((B, SL, d.E), np.nan, f32),
            expr_valid=zb(B, SL, d.E),
            pref_weight=zf(B, PSL),
            pref_term_valid=zb(B, PSL),
            pref_expr_key=zi(B, PSL, d.E),
            pref_expr_op=np.zeros((B, PSL, d.E), i32),
            pref_expr_vals=zi(B, PSL, d.E, d.V),
            pref_expr_nval=np.zeros((B, PSL, d.E), i32),
            pref_expr_num=np.full((B, PSL, d.E), np.nan, f32),
            pref_expr_valid=zb(B, PSL, d.E),
            aff_term_valid=zb(B, d.PT),
            aff_term_self=zb(B, d.PT),
            aff_term_topo_key=zi(B, d.PT),
            anti_term_valid=zb(B, d.AT),
            anti_term_topo_key=zi(B, d.AT),
            anti_term_self=zb(B, d.AT),
            group_ids=zi(B, d.GP),
            group_valid=zb(B, d.GP),
            svc_aff_fixed=zi(B, SA),
            image_ids=zi(B, d.C),
            image_bytes=zf(B, d.C),
            new_vol_counts=zf(B, d.VT),
            disk_vol_ids=zi(B, d.DV),
            vol_zone_valid=zb(B, d.VZ),
            vol_bind_valid=zb(B, d.VB),
            vol_fail_all=zb(B),
        )

        # interner ids are append-only (stable), so only pad-dim or
        # spread-registry changes invalidate cached rows
        # NOTE: SL/PSL in the token means a lean<->full flip flushes the
        # whole row cache; accepted — scheduler batches are formed per
        # cycle from queue order, so affinity presence rarely oscillates,
        # and a flush costs one re-encode, not correctness
        token = (self.dims, len(self._spread), aff_lean, vol_lean, SL, PSL,
                 tuple(self.service_affinity_keys))
        cnt_ids_by_b: dict = {}
        if token != self._pod_cache_token:
            self._pod_row_cache.clear()
            self._pod_cache_token = token

        # pods grouped by static key into classes: each class's cache-hit
        # pods take one broadcast assignment per field instead of a
        # per-pod python loop — controller-stamped workloads have ~20
        # distinct rows across thousands of pods, so this is ~100x fewer
        # numpy calls.  The class index per pod is what `classes_out`
        # hands the commit (add_pods), which shares the same classes.
        class_of: Dict[Tuple, int] = {}
        class_keys: List[Tuple] = []
        hit_groups: List[List[int]] = []
        # CALL-LOCAL row sharing for the pods the cross-call cache must
        # refuse (affinity / live term_groups, where rows depend on cluster
        # state): within one encode_pods call the state is frozen (callers
        # hold the cache lock), so same-content pods share a row.  Keyed by
        # the static key EXTENDED with the affinity content signature;
        # pods with volumes stay per-pod (PVC rows also carry per-call
        # binder assumptions).
        local_first: Dict[Tuple, int] = {}
        local_hits: Dict[int, List[int]] = {}
        # (b, static class or -1) of each pod whose row is encoded below
        todo: List[Tuple[int, int]] = []
        for b, pod in enumerate(pods):
            ck = self._pod_static_key(pod)
            c = -1
            if ck is not None:
                c = class_of.get(ck, -1)
                if c < 0:
                    c = class_of[ck] = len(class_keys)
                    class_keys.append(ck)
                    hit_groups.append([])
                    # the class's first pod: a row-cache miss encodes
                    # below and fills the cache for the rest of the class
                    hit = ck in self._pod_row_cache
                else:
                    hit = True
                if classes_out is not None:
                    classes_out[b] = c
                if hit:
                    hit_groups[c].append(b)
                    continue
            else:
                lk = self._pod_local_key(pod)
                if lk is not None:
                    first = local_first.get(lk)
                    if first is not None:
                        local_hits.setdefault(first, []).append(b)
                        continue
                    local_first[lk] = b
            todo.append((b, c))

        # the TP-wide pair leaves of the families the batch is not lean
        # in (the lean ones stay [B, ..., 1] zeros in `out`).  They hold a
        # row per call-local class or keyless pod: row_of[b] is pod b's.
        # The padding and every static-class pod share a zero row, since
        # _pod_static_key admits no pod with affinity or volumes, nor any
        # while live terms exist.  With pair_rows the leaves leave as
        # PairRows (the class rows and row_of) unless there are more
        # rows than pack_tree ships factored; else each class's row is
        # copied to its pods.
        pair_spec = dict(
            forbidden_pairs=((), bool, aff_lean),
            aff_term_pairs=((d.PT,), bool, aff_lean),
            anti_term_pairs=((d.AT,), bool, aff_lean),
            pref_pair_weights=((), f32, aff_lean),
            vol_zone_pairs=((d.VZ,), bool, vol_lean),
            vol_bind_pairs=((d.VB,), bool, vol_lean),
        )
        for k, (trail, dt, lean) in pair_spec.items():
            if lean:
                out[k] = np.zeros((B,) + trail + (1,), dt)
        wide = not (aff_lean and vol_lean)
        n_rows = 0
        row_of = np.zeros(B, np.int32)
        if wide:
            n_rows = int(len(pods) < B or bool(class_keys))
            for b, cls in todo:
                if cls < 0:
                    row_of[b] = n_rows
                    n_rows += 1
            for first, idxs in local_hits.items():
                row_of[idxs] = row_of[first]
        factored = wide and pair_rows and n_rows <= factor_row_limit(B)
        pairs = {
            k: np.zeros((n_rows if factored else B,) + trail + (d.TP,), dt)
            for k, (trail, dt, lean) in pair_spec.items() if not lean
        }

        aff_rows: List[Tuple[int, int]] = []
        for b, cls in todo:
            pod = pods[b]
            r = int(row_of[b]) if factored else b
            out["valid"][b] = True
            req = self._req_vector(pod.resource_request())
            out["req"][b, : req.shape[0]] = req
            out["nonzero_req"][b] = self._nonzero(pod)
            # summed container limits (ResourceLimitsPriority,
            # priorities/resource_limits.go getResourceLimits)
            lim_cpu = lim_mem = 0.0
            for c in pod.spec.containers:
                if RESOURCE_CPU in c.limits:
                    lim_cpu += c.limits[RESOURCE_CPU].milli
                if RESOURCE_MEMORY in c.limits:
                    lim_mem += float(c.limits[RESOURCE_MEMORY])
            out["limits2"][b] = (lim_cpu, lim_mem)
            out["priority"][b] = pod.spec.priority
            out["best_effort"][b] = all(
                not c.requests and not c.limits for c in pod.spec.containers
            )
            out["ns_id"][b] = it.intern(pod.namespace)
            # NodePreferAvoidPods only applies to RC/RS-owned pods
            # (ref priorities/node_prefer_avoid_pods.go:41-55)
            if pod.metadata.owner_uid and pod.metadata.owner_kind in (
                "ReplicationController",
                "ReplicaSet",
            ):
                out["owner_uid"][b] = it.intern(pod.metadata.owner_uid)
            if pod.spec.node_name:
                out["node_name_req"][b] = it.intern(pod.spec.node_name)
            for j, (pp, ip) in enumerate(self._pod_ports(pod)[: d.Q]):
                out["port_pp"][b, j] = pp
                out["port_ip"][b, j] = ip
                out["port_valid"][b, j] = True
            for j, t in enumerate(pod.spec.tolerations[: d.TT]):
                out["tol_key"][b, j] = it.intern(t.key) if t.key else 0
                out["tol_op"][b, j] = TOL_OP_CODES.get(t.operator, 0)
                out["tol_val"][b, j] = it.intern(t.value)
                out["tol_effect"][b, j] = EFFECT_CODES.get(t.effect, PAD) if t.effect else PAD
                out["tol_valid"][b, j] = True
            for j, (k, v) in enumerate(sorted(pod.spec.node_selector.items())[: d.NS]):
                out["ns_keys"][b, j] = it.intern(k)
                out["ns_vals"][b, j] = it.lookup(v) if it.lookup(v) >= 0 else it.intern(v)
                out["ns_valid"][b, j] = True
            aff = pod.spec.affinity
            na = aff.node_affinity if aff else None
            if na and na.required is not None:
                out["has_req_affinity"][b] = True
                for s, term in enumerate(na.required.terms[: d.S]):
                    out["term_valid"][b, s] = True
                    e = 0
                    for expr in term.match_expressions:
                        if e >= d.E:
                            break
                        self._encode_expr(out, "expr", b, s, e, expr.key, expr.operator, expr.values)
                        e += 1
                    for expr in term.match_fields:
                        if e >= d.E:
                            break
                        # matchFields only supports metadata.name (ref
                        # apis/core/validation: NodeFieldSelectorKeys)
                        self._encode_expr(
                            out, "expr", b, s, e, FIELD_NODE_NAME,
                            expr.operator, expr.values, is_field=True,
                        )
                        e += 1
            if na:
                for s, pterm in enumerate(na.preferred[: d.PS]):
                    out["pref_term_valid"][b, s] = True
                    out["pref_weight"][b, s] = float(pterm.weight)
                    for e, expr in enumerate(pterm.preference.match_expressions[: d.E]):
                        self._encode_expr(
                            out, "pref_expr", b, s, e, expr.key, expr.operator, expr.values
                        )
            # the pair columns come in one pass after this loop; a row
            # the cache keeps has none (no affinity, no live term group)
            aff_rows.append((b, r))
            for j, kid in enumerate(self.service_affinity_keys):
                v = pod.spec.node_selector.get(it.string(kid))
                if v is not None:
                    out["svc_aff_fixed"][b, j] = it.intern(v)
            gi = 0
            for g, (ns, sel) in enumerate(self._spread):
                if gi >= d.GP:
                    break
                if ns == pod.namespace and sel.matches(pod.labels):
                    out["group_ids"][b, gi] = g
                    out["group_valid"][b, gi] = True
                    gi += 1
            for j, c in enumerate(pod.spec.containers[: d.C]):
                if c.image:
                    out["image_ids"][b, j] = it.lookup(
                        normalized_image(c.image)
                    )
            disk, _, vcounts, cnt_ids = self._pod_vols(pod)
            cnt_ids_by_b[b] = cnt_ids
            out["new_vol_counts"][b] = vcounts
            for j, dv in enumerate(disk[: d.DV]):
                out["disk_vol_ids"][b, j] = dv
            zone_terms, bind_terms, fail_all = self._pod_volume_terms(pod)
            out["vol_fail_all"][b] = fail_all
            for j, prs in enumerate(zone_terms[: d.VZ]):
                pairs["vol_zone_pairs"][r, j] = prs[: d.TP]
                out["vol_zone_valid"][b, j] = True
            for j, prs in enumerate(bind_terms[: d.VB]):
                pairs["vol_bind_pairs"][r, j] = prs[: d.TP]
                out["vol_bind_valid"][b, j] = True
            if cls >= 0:
                self._pod_row_cache[class_keys[cls]] = {
                    k: np.copy(v[b]) for k, v in out.items()
                }

        if not aff_lean and aff_rows:
            with (affinity_phase or contextlib.nullcontext)():
                for b, r in aff_rows:
                    self._encode_pod_affinity(out, b, pods[b], pairs, r)

        for first, idxs in local_hits.items():
            ia = np.asarray(idxs, np.intp)
            for k, v in out.items():
                v[ia] = v[first]
            if first in cnt_ids_by_b:
                for b2 in idxs:
                    cnt_ids_by_b[b2] = cnt_ids_by_b[first]

        for ck, idxs in zip(class_keys, hit_groups):
            if not idxs:
                continue
            cached = self._pod_row_cache[ck]
            ia = np.asarray(idxs, np.intp)
            for k, v in cached.items():
                out[k][ia] = v

        if wide:
            with (pair_phase(n_rows, factored) if pair_phase
                  else contextlib.nullcontext()):
                if factored:
                    pairs = {k: PairRows(v, row_of) for k, v in pairs.items()}
                else:
                    for first, idxs in local_hits.items():
                        ia = np.asarray(idxs, np.intp)
                        for v in pairs.values():
                            v[ia] = v[first]
        out.update(pairs)

        # state-dependent, so computed fresh every call (outside the row
        # cache): per-node counts of existing pods matching ALL of each pod's
        # spread selectors — countMatchingPods AND semantics
        # (selector_spreading.go:165-187), not one count per selector.
        # Lean form: when every pod belongs to <= 1 spread group, the AND
        # degenerates to that group's column of cluster.group_counts — the
        # device derives counts from the snapshot (selector_spread gates on
        # shape) and the [B, N] host tensor is skipped entirely.
        if not (out["group_valid"].sum(axis=1) > 1).any():
            spread = np.zeros((out["group_ids"].shape[0], 1), np.float32)
        else:
            spread = self._spread_and_counts(out)
        d0, d1 = self._service_affinity_candidates(pods, out)
        return PodBatch(
            **out, spread_counts=spread, svc_aff_d0=d0, svc_aff_d1=d1,
            vol_overlap=self._vol_overlap(pods, cnt_ids_by_b),
        )

    def _vol_overlap(self, pods, cnt_ids_by_b=None) -> np.ndarray:
        """f32[B, VT, N] count of the pod's attachable volumes
        ALREADY mounted on each node (filterVolumes' already-mounted
        subtraction: they add no new attachment); [B, VT, 1] lean
        placeholder when no pod carries volumes.  `cnt_ids_by_b` reuses the
        id sets the encode loop already computed."""
        B = self.batch_pad(len(pods))
        if not any(getattr(p.spec, "volumes", None) for p in pods):
            return np.zeros((B, self.dims.VT, 1), np.float32)
        out = np.zeros((B, self.dims.VT, self._cap_n), np.float32)
        for b, pod in enumerate(pods):
            if not pod.spec.volumes:
                continue
            cnt_ids = (cnt_ids_by_b or {}).get(b)
            if cnt_ids is None:
                _, _, _, cnt_ids = self._pod_vols(pod)
            for t, ids in enumerate(cnt_ids):
                for vid in ids:
                    for row in self._cnt_vol_rows[t].get(vid, ()):
                        out[b, t, row] += 1.0
        return out

    def _service_affinity_candidates(self, pods, out):
        """(d0, d1) i32[B]: first same-namespace arena pod whose labels
        superset-match the pod's own labels (CreateSelectorFromLabels of
        pod.Labels, predicates.go serviceAffinityMetadataProducer), and the
        first such pod on a DIFFERENT node — together they resolve
        FilterOutPods(evaluated node) per node on device.  Gated on some
        service selecting the pod (GetPodServices non-empty)."""
        B = out["group_ids"].shape[0]
        d0 = np.full(B, -1, np.int32)
        d1 = np.full(B, -1, np.int32)
        if not self.service_affinity_keys:
            return d0, d1
        for b, pod in enumerate(pods):
            # gate: some SERVICE selects the pod (GetPodServices; RC/RS/SS
            # spread selectors don't count, predicates.go:978)
            if not any(
                kind == "Service" and ns == pod.namespace
                and sel.matches(pod.labels)
                for (ns, sel), kind in zip(self._spread, self._spread_kinds)
            ):
                continue
            nsid = self.interner.lookup(pod.namespace)
            if nsid < 0:
                continue
            sel = klabels.selector_from_match_labels(pod.labels)
            m = self._match_selector_vec(sel, [nsid])
            nodes = self.p_node[m & (self.p_node >= 0)]
            if nodes.size:
                d0[b] = nodes[0]
                other = nodes[nodes != nodes[0]]
                if other.size:
                    d1[b] = other[0]
        return d0, d1

    def _group_counts(self) -> np.ndarray:
        counts = np.zeros((self._cap_n, self.dims.G), np.float32)
        for gi, (ns, sel) in enumerate(self._spread):
            nsid = self.interner.lookup(ns)
            if nsid < 0:
                continue
            matched = self._match_selector_vec(sel, [nsid])
            nodes = self.p_node[matched]
            nodes = nodes[nodes >= 0]
            if nodes.size:
                counts[:, gi] = np.bincount(
                    nodes, minlength=self._cap_n
                )[: self._cap_n].astype(np.float32)
        return counts

    def _spread_and_counts(self, out) -> np.ndarray:
        """f32[B, N] from the batch's group_ids/group_valid rows: existing
        alive pods per node matching every one of the pod's spread groups
        (a pod with no groups contributes all-zero counts, which the reduce
        maps to the uniform MAX_PRIORITY — the len(selectors)==0 score-0
        path of CalculateSpreadPriorityMap)."""
        B = out["group_ids"].shape[0]
        counts = np.zeros((B, self._cap_n), np.float32)
        mask_cache: Dict[int, np.ndarray] = {}
        for b in range(B):
            gs = out["group_ids"][b][out["group_valid"][b]]
            if gs.size == 0:
                continue
            m = None
            for g in gs:
                g = int(g)
                mg = mask_cache.get(g)
                if mg is None:
                    ns, sel = self._spread[g]
                    nsid = self.interner.lookup(ns)
                    mg = (
                        self._match_selector_vec(sel, [nsid])
                        if nsid >= 0
                        else np.zeros(self._cap_m, bool)
                    )
                    mask_cache[g] = mg
                m = mg if m is None else (m & mg)
            nodes = self.p_node[m]
            nodes = nodes[nodes >= 0]
            if nodes.size:
                counts[b] = np.bincount(
                    nodes, minlength=self._cap_n
                )[: self._cap_n].astype(np.float32)
        return counts

    def _pod_key_base(self, pod: Pod):
        """The shared content-key body both caching keys build on: every
        non-affinity pod attribute an encoded row depends on.  Raises
        TypeError for unhashable content (callers translate to None)."""
        return (
            pod.namespace,
            tuple(sorted(pod.labels.items())),
            tuple(sorted(pod.spec.node_selector.items())),
            # the *resolved* image id goes into the key: a lookup miss
            # (image not yet on any node) must not freeze ImageLocality
            # at 0 once the image appears and gets interned
            # Quantity is a frozen dataclass over Fraction: hashable and
            # ordered, so the exact objects key the row directly (str()
            # round-trips cost Fraction formatting, ~10us/pod)
            tuple(
                (self.interner.lookup(normalized_image(c.image)),
                 tuple(sorted(c.requests.items())),
                 # limits participate in the row (limits2, best_effort):
                 # two pods differing only in limits must not share a row
                 tuple(sorted(c.limits.items())),
                 tuple(c.ports))
                for c in pod.spec.containers
            ),
            tuple(
                (c.image,
                 tuple(sorted(c.requests.items())),
                 tuple(sorted(c.limits.items())))
                for c in pod.spec.init_containers
            ),
            pod.spec.tolerations,
            pod.spec.node_name,
            pod.spec.priority,
            pod.metadata.owner_uid,
            pod.metadata.owner_kind,
        )

    def _pod_local_key(self, pod: Pod):
        """Key for CALL-LOCAL row sharing (encode_pods): the cross-call
        gate fields (affinity content) join the shared key base, since
        within one call the cluster state every row depends on is frozen.
        Pods with volumes return None — their rows also carry per-call
        binder assumptions keyed by pod identity (CheckVolumeBinding
        assume bookkeeping), so sharing could alias distinct claims."""
        if pod.spec.volumes:
            return None

        def _ts(t):
            # canonical selector form — the same _sel_requirements
            # canonicalization _term_sig uses, so semantically identical
            # terms (matchLabels vs equivalent matchExpressions) share
            sel = _sel_requirements(t.label_selector)
            sel_key = tuple(sel.requirements) if sel is not None else None
            return (sel_key, t.topology_key, frozenset(t.namespaces))

        aff = pod.spec.affinity
        try:
            if aff is None:
                aff_sig = None
            else:
                pa, paa = aff.pod_affinity, aff.pod_anti_affinity
                aff_sig = (
                    aff.node_affinity,  # frozen dataclasses: hashable
                    None if pa is None else (
                        tuple(_ts(t) for t in pa.required),
                        tuple((w.weight, _ts(w.term)) for w in pa.preferred),
                    ),
                    None if paa is None else (
                        tuple(_ts(t) for t in paa.required),
                        tuple((w.weight, _ts(w.term)) for w in paa.preferred),
                    ),
                )
            return (aff_sig,) + self._pod_key_base(pod)
        except TypeError:
            return None

    def _pod_static_key(self, pod: Pod):
        """Cache key for state-independent pods; None disables caching.

        A pod with no affinity of its own is still state-dependent when ANY
        existing pod carries (anti-)affinity terms: its forbidden_pairs /
        pref_pair_weights rows come from matching those terms, whose pair
        counts move with every placement."""
        if pod.spec.affinity is not None or pod.spec.volumes or self.term_groups:
            return None
        try:
            return self._pod_key_base(pod)
        except TypeError:
            return None

    def _encode_expr(self, out, prefix, b, s, e, key, op, values,
                     is_field: bool = False) -> None:
        it = self.interner
        out[f"{prefix}_key"][b, s, e] = it.intern(key)
        out[f"{prefix}_op"][b, s, e] = SEL_OP_CODES[op]
        out[f"{prefix}_valid"][b, s, e] = True
        if not is_field and klabels.requirement_is_unbuildable(key, op, values):
            # the requirement cannot be built (NodeSelectorRequirements
            # AsSelector errors), so the TERM never matches — encode as
            # In-with-no-values (matches nothing); matchFields exempt
            out[f"{prefix}_op"][b, s, e] = SEL_OP_CODES[klabels.IN]
            out[f"{prefix}_nval"][b, s, e] = 0
            return
        if op in (klabels.GT, klabels.LT):
            try:
                out[f"{prefix}_num"][b, s, e] = float(int(values[0]))
            except (ValueError, IndexError):
                out[f"{prefix}_num"][b, s, e] = np.nan
        else:
            nv = 0
            for v in values[: out[f"{prefix}_vals"].shape[-1]]:
                vid = it.lookup(v)
                out[f"{prefix}_vals"][b, s, e, nv] = vid if vid >= 0 else it.intern(v)
                nv += 1
            out[f"{prefix}_nval"][b, s, e] = nv

    def _matches_one(self, sel: klabels.Selector, namespaces: frozenset, pod: Pod) -> bool:
        return pod.namespace in namespaces and sel.matches(pod.labels)

    def _term_pairs(self, term: PodAffinityTerm, pod_ns: str) -> Tuple[np.ndarray, int]:
        """f32[TP] count of existing pods matching `term` per topology pair
        (counts matter: the priority adds weight once per matching pod,
        ref priorities/interpod_affinity.go processExistingPod)."""
        kid = self.interner.lookup(term.topology_key)
        pairs = np.zeros(self.dims.TP, np.float32)
        sel = _sel_requirements(term.label_selector)
        if sel is None or kid < 0:
            return pairs, kid
        ns_ids = [
            self.interner.lookup(n)
            for n in (term.namespaces or (pod_ns,))
            if self.interner.lookup(n) >= 0
        ]
        if not ns_ids:
            return pairs, kid
        matched = self._match_selector_vec(sel, ns_ids)
        nodes = self.p_node[matched]
        nodes = nodes[nodes >= 0]
        if nodes.size:
            pids = self._node_pair_id[kid][nodes]
            pids = pids[pids >= 0]
            if pids.size:
                pairs += np.bincount(pids, minlength=self.dims.TP).astype(np.float32)
        return pairs, kid

    def _encode_pod_affinity(self, out, b: int, pod: Pod, pairs,
                             r: int) -> None:
        """Fill forbidden/affinity pair tensors for one incoming pod: its
        term columns at out[...][b], its pair columns at pairs[...][r].

        forbidden_pairs: existing pods' required anti-affinity terms that match
        this pod forbid their topology pairs (ref predicates.go
        satisfiesExistingPodsAntiAffinity via metadata
        topologyPairsAntiAffinityPodsMap).
        pref_pair_weights: soft scoring weight per pair — combines the incoming
        pod's preferred terms and existing pods' preferred (anti-)affinity and
        hard-affinity symmetry (ref priorities/interpod_affinity.go).
        """
        d = self.dims
        hard_w = self.hard_pod_affinity_weight
        for sig, g in self.term_groups.items():
            if g.members <= 0:
                continue
            if not self._matches_one(g.selector, g.namespaces, pod):
                continue
            if g.kind == K_ANTI_REQ:
                pairs["forbidden_pairs"][r] |= g.pair_counts[: d.TP] > 0
            elif g.kind == K_ANTI_PREF:
                pairs["pref_pair_weights"][r] -= g.weight * g.pair_counts[: d.TP]
            elif g.kind == K_AFF_PREF:
                pairs["pref_pair_weights"][r] += g.weight * g.pair_counts[: d.TP]
            elif g.kind == K_AFF_REQ and hard_w:
                pairs["pref_pair_weights"][r] += hard_w * g.pair_counts[: d.TP]
        aff = pod.spec.affinity
        if aff is None:
            return
        if aff.pod_affinity:
            for j, term in enumerate(aff.pod_affinity.required[: d.PT]):
                cnt, kid = self._term_pairs(term, pod.namespace)
                pairs["aff_term_pairs"][r, j] = cnt > 0
                out["aff_term_valid"][b, j] = True
                out["aff_term_topo_key"][b, j] = kid
                sel = _sel_requirements(term.label_selector)
                out["aff_term_self"][b, j] = bool(
                    sel is not None
                    and pod.namespace in (term.namespaces or (pod.namespace,))
                    and sel.matches(pod.labels)
                )
            for wt in aff.pod_affinity.preferred:
                cnt, _ = self._term_pairs(wt.term, pod.namespace)
                pairs["pref_pair_weights"][r] += float(wt.weight) * cnt
        if aff.pod_anti_affinity:
            for j, term in enumerate(aff.pod_anti_affinity.required[: d.AT]):
                cnt, kid = self._term_pairs(term, pod.namespace)
                pairs["anti_term_pairs"][r, j] = cnt > 0
                out["anti_term_valid"][b, j] = True
                out["anti_term_topo_key"][b, j] = kid
                sel = _sel_requirements(term.label_selector)
                out["anti_term_self"][b, j] = bool(
                    sel is not None
                    and pod.namespace in (term.namespaces or (pod.namespace,))
                    and sel.matches(pod.labels)
                )
            for wt in aff.pod_anti_affinity.preferred:
                cnt, _ = self._term_pairs(wt.term, pod.namespace)
                pairs["pref_pair_weights"][r] -= float(wt.weight) * cnt
