"""Host-side encoding: Snapshot + pod batch → dense device arrays.

With the device mirror attached (``ops/mirror.py``), the full
cluster-plane build below runs only on cold start and on reseed
(journal gaps / inexpressible deltas) — steady state scatters watch
deltas into the resident planes and the per-batch work reduces to the
pod-row delta encode, which is the drained pods' own h2d prep.

The reference's PreFilter phase builds per-pod maps over all nodes
(``interpodaffinity/filtering.go:162-235``, ``podtopologyspread/
filtering.go:198-273``); this encoder materializes the same information
once per batch as tensors:

- node capacity/usage matrices ``[N, R]`` (int32: milli-CPU, KiB memory,
  KiB ephemeral, whole-unit scalars),
- topology value codes ``[N, K]`` per tracked topology key,
- per *static profile* node masks ``[U, N]`` — a profile is the tuple of a
  pod's node-static predicates (nodeName, nodeSelector, required node
  affinity, tolerations, unschedulable) evaluated with the SAME host
  plugin code the serial path runs, guaranteeing differential equality,
- tracked spread-constraint count matrices ``[SC, V]`` and per-pod match
  vectors,
- tracked (anti-)affinity term count/owner matrices ``[T, V]`` and
  membership masks.

Volume feasibility is tensorized (VERDICT r2 #1; reference
``plugins/volumebinding/volume_binding.go:82-269``, ``volumezone/
volume_zone.go``, ``nodevolumelimits/csi.go``): a pod whose PVCs are all
BOUND is expressible — its PV node-affinity/zone feasibility folds into
the static profile mask (computed with the real host plugins), and CSI
attach limits become extra resource columns (one per CSINode-limited
driver) so in-batch attach consumption re-masks exactly like CPU/memory.
Only Reserve/PreBind statefulness (assume/commit of UNBOUND matches)
stays host-side.

Pods the tensor model cannot express (unbound PVC volumes, shared RWX/ROX
claims, inline cloud-disk volumes, host ports, extender interest) are
flagged ``inexpressible`` and fall back to the serial path — the
clean-fallback contract.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np

from kubernetes_tpu.api import labels as labelslib
from kubernetes_tpu.api.types import CPU, EPHEMERAL_STORAGE, MEMORY, PODS, Pod
from kubernetes_tpu.scheduler.framework.cycle_state import CycleState
from kubernetes_tpu.scheduler.framework.plugins import mesh_locality
from kubernetes_tpu.scheduler.framework.plugins.helpers import (
    pod_matches_node_selector_and_affinity,
)
from kubernetes_tpu.scheduler.framework.plugins.node_unschedulable import (
    NodeUnschedulable,
)
from kubernetes_tpu.scheduler.framework.plugins.taint_toleration import (
    TaintToleration,
)
from kubernetes_tpu.scheduler.snapshot import Snapshot
from kubernetes_tpu.scheduler.types import NodeInfo, PodInfo, Resource

HOSTNAME_KEY = "kubernetes.io/hostname"

# base resource columns; scalar/extended resources get appended per batch
BASE_RESOURCES = 3  # cpu (milli), memory (KiB), ephemeral (KiB)

# attach-limit resource columns (one per CSI driver with a CSINode
# limit) live in a reserved namespace so they can never collide with a
# real extended-resource name
ATTACH_COL_PREFIX = "attachable#csi#"
# a node/driver without a published limit is unconstrained; the sentinel
# must survive int32 arithmetic over a full batch of subtractions
NO_LIMIT = 1_000_000_000

# access modes implying a volume may be shared by multiple pods; the
# attach-column model counts per-pod distinct volumes and would
# double-count a share landing twice on one node, so such pods keep the
# host path (csi.go counts len(in_use | wanted) — set semantics)
SHARED_ACCESS_MODES = ("ReadWriteMany", "ReadOnlyMany")


def _resource_row(r: Resource, names: List[str]) -> List[int]:
    row = [r.milli_cpu, _kib(r.memory), _kib(r.ephemeral_storage)]
    for name in names[BASE_RESOURCES:]:
        row.append(r.scalar_resources.get(name, 0))
    return row


def _kib(b: int) -> int:
    return -((-b) // 1024)


def _round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


# below this many real nodes a sharded fill costs more in thread
# handoff than it wins — the per-node plugin loop is microseconds there
ENCODE_SHARD_MIN_NODES = 512


def _node_slices(n: int, shards: int) -> List[slice]:
    """Contiguous node-column ranges, one per encode worker — the SAME
    even split the mesh's NamedSharding uses for the node axis, so each
    worker emits exactly the columns one device shard will hold."""
    if shards <= 1 or n <= 0:
        return [slice(0, n)]
    step = -(-n // shards)
    return [slice(s, min(s + step, n)) for s in range(0, n, step)]


def _constraint_key(pod: Pod, c, sel: labelslib.Selector) -> tuple:
    """Dedup identity of a topology-spread constraint. Shared by the full
    and incremental encoders — the two must never diverge or incremental
    batches would map pods onto the wrong tracked constraint."""
    return (
        c.topology_key, c.max_skew,
        c.when_unsatisfiable == "DoNotSchedule",
        pod.namespace, repr(sel),
    )


def _term_key(t) -> tuple:
    """Dedup identity of an (anti-)affinity term (same sharing contract)."""
    return (t.topology_key, repr(t.selector), tuple(sorted(t.namespaces)))


def _simple_label_eq(selector: labelslib.Selector):
    """(key, value) when the selector is a single ``key IN (value)``
    requirement — the overwhelmingly common shape — else None."""
    reqs = selector.requirements
    if (
        not getattr(selector, "_nothing", False)
        and len(reqs) == 1
        and reqs[0].operator == labelslib.IN
        and len(reqs[0].values) == 1
    ):
        return (reqs[0].key, reqs[0].values[0])
    return None


def _build_match_index(items):
    """Split tracked constraints/terms into an inverted index of simple
    single-label selectors ((key, value) → [(idx, item)]) plus the
    complex remainder. Filling per-pod match masks via the index is
    O(pod labels) instead of O(tracked items): workloads with many
    modulo-k groups (e.g. 100 anti-affinity colors) otherwise spend
    longer matching selectors on the host than solving on device."""
    simple: Dict[tuple, list] = {}
    complex_items = []
    for idx, item in enumerate(items):
        kv = _simple_label_eq(item.selector)
        if kv is None:
            complex_items.append((idx, item))
        else:
            simple.setdefault(kv, []).append((idx, item))
    return simple, complex_items


def _count_by_domain(row: np.ndarray, codes: np.ndarray,
                     node_counts: List[int]) -> None:
    """Add each node's count into its topology value's column of
    ``row``; nodes without the key (the sentinel column) count nowhere."""
    sentinel = row.shape[0] - 1
    np.add.at(row, codes, np.asarray(node_counts, dtype=row.dtype))
    row[sentinel] = 0


@dataclass
class _TrackedConstraint:
    """One distinct topology-spread constraint shared by batch pods."""

    key_idx: int
    max_skew: int
    selector: labelslib.Selector
    namespace: str
    hard: bool  # DoNotSchedule vs ScheduleAnyway

    def matches(self, pod: Pod) -> bool:
        return pod.namespace == self.namespace and self.selector.matches(
            pod.metadata.labels
        )


@dataclass
class _TrackedTerm:
    """One distinct (anti-)affinity term."""

    key_idx: int
    selector: labelslib.Selector
    namespaces: frozenset

    def matches(self, pod: Pod) -> bool:
        return pod.namespace in self.namespaces and self.selector.matches(
            pod.metadata.labels
        )


@dataclass
class EncodedCluster:
    """Node-side arrays (all numpy; shipped to device by the solver)."""

    node_names: List[str]
    num_real_nodes: int
    resource_names: List[str]
    allocatable: np.ndarray        # [N, R] int32
    requested: np.ndarray          # [N, R] int32
    nonzero_requested: np.ndarray  # [N, 2] int32 (cpu milli, mem KiB) for scoring
    pod_count: np.ndarray          # [N] int32
    max_pods: np.ndarray           # [N] int32
    topo_keys: List[str] = field(default_factory=list)
    topo_codes: Optional[np.ndarray] = None   # [N, K] int32, V = missing
    topo_num_values: Optional[np.ndarray] = None  # [K] int32
    # shared-volume attach planes (VERDICT r4 next #5): slot s of a
    # shared CSI volume; sv_attached[s, n] = 1 when that volume is
    # already attached on node n — a pod re-using it there consumes NO
    # further attach budget (csi.go len(in_use | wanted) set semantics,
    # tensorized as conditional per-node demand carried in solver state)
    sv_attached: Optional[np.ndarray] = None  # [SV, N] int32 (0/1)
    sv_keys: Optional[np.ndarray] = None      # [SV] int64 stable hashes


@dataclass
class EncodedBatch:
    """Pod-side arrays + tracked dynamic constraint state."""

    pods: List[Pod]
    num_real_pods: int
    requests: np.ndarray           # [B, R] int32
    nonzero_requests: np.ndarray   # [B, 2] int32
    profile_idx: np.ndarray        # [B] int32 into static masks
    static_masks: np.ndarray       # [U, N] bool — node-static predicates
    affinity_masks: np.ndarray     # [U, N] bool — nodeSelector/affinity only
    static_scores: np.ndarray      # [U, N] float32 — static score plugins
    inexpressible: np.ndarray      # [B] bool — pod must use serial path

    # spread constraints
    sc_key_idx: np.ndarray         # [SC] int32
    sc_max_skew: np.ndarray        # [SC] int32
    sc_hard: np.ndarray            # [SC] bool
    sc_counts: np.ndarray          # [SC, V+1] int32 (existing matching pods)
    sc_domain: np.ndarray          # [U, SC, V+1] bool (eligible domains)
    pod_sc: np.ndarray             # [B, SC] bool — constraint belongs to pod
    pod_sc_match: np.ndarray       # [B, SC] bool — pod counts toward constraint

    # (anti-)affinity terms
    term_key_idx: np.ndarray       # [T] int32
    term_counts: np.ndarray        # [T, V+1] int32 (existing matched pods)
    term_owners: np.ndarray        # [T, V+1] int32 (existing anti-term owners)
    match_by: np.ndarray           # [B, T] bool — pod matched by term
    own_aff: np.ndarray            # [B, T] bool — pod requires term (affinity)
    own_anti: np.ndarray           # [B, T] bool — pod requires term (anti)
    pref_weight: np.ndarray        # [B, T] float32 — preferred term weights

    num_values: int                # V (shared topo-value space size)
    # per-pod shared-volume reference: [B, 2] int32 (slot or SV
    # sentinel, attach resource column); None when the epoch has no
    # shared CSI volumes (layout & compiled shapes identical to before)
    pod_sv: Optional[np.ndarray] = None


@dataclass
class EncodedPodBatch:
    """Pod-side-only arrays for an incremental batch against an existing
    encoding space (the device already holds the cluster/static arrays and
    the carried dynamic count state)."""

    pods: List[Pod]
    num_real_pods: int
    requests: np.ndarray           # [B, R] int32
    nonzero_requests: np.ndarray   # [B, 2] int32
    profile_idx: np.ndarray        # [B] int32
    inexpressible: np.ndarray      # [B] bool
    pod_sc: np.ndarray             # [B, SC] bool
    pod_sc_match: np.ndarray       # [B, SC] bool
    match_by: np.ndarray           # [B, T] bool
    own_aff: np.ndarray            # [B, T] bool
    own_anti: np.ndarray           # [B, T] bool
    pref_weight: np.ndarray        # [B, T] float32
    pod_sv: Optional[np.ndarray] = None   # [B, 2] int32


class BatchEncoder:
    """Encodes one (snapshot, pod batch) pair. After a full ``encode`` the
    encoder retains the *encoding space* — resource columns, topology-key/
    value codes, tracked constraints/terms, static profiles — so later
    batches whose pods fit the same space can be encoded pod-side-only
    (``encode_pods_only``) against device-resident cluster state (the
    Generation-LRU of the device mirror, SURVEY.md section 7 hard part 1)."""

    def __init__(self, snapshot: Snapshot, pad_nodes: int = 128,
                 client=None, extra_nodes: Optional[List] = None,
                 node_shards: int = 1):
        self.snapshot = snapshot
        # sharded encode stage (the mesh-native planes contract): the
        # node-column fill — resource rows and the per-profile static
        # predicate/score plugin sweeps, the O(U × N) host cost of a
        # rebuild — splits into ``node_shards`` contiguous column
        # ranges, the SAME even split the solve mesh's NamedSharding
        # uses, and runs on an encode worker pool. Workers write
        # disjoint column slices of preallocated arrays (deterministic:
        # no ordering-dependent state crosses a shard boundary), so a
        # 50k-node plane is emitted per-shard instead of serializing on
        # one host thread before upload. ``node_shards=1`` (every
        # non-mesh backend) is the exact serial path.
        self.node_shards = max(1, int(node_shards))
        self.node_infos = [ni for ni in snapshot.list() if ni.node is not None]
        # virtual node columns (the cluster autoscaler's what-if hook):
        # hypothetical template nodes appended AFTER the snapshot's real
        # nodes, encoded with the same host plugin code — static masks,
        # taints, topology codes all behave as if the node existed. The
        # caller identifies their columns as the last len(extra_nodes)
        # entries of cluster.node_names (ops/solver.py solve_whatif then
        # score-penalizes or disables them).
        self.num_snapshot_nodes = len(self.node_infos)
        if extra_nodes:
            for node in extra_nodes:
                ni = NodeInfo()
                ni.set_node(node)
                self.node_infos.append(ni)
        self.pad_nodes = pad_nodes
        self._client = client
        self._taint_plugin = TaintToleration()
        self._unsched_plugin = NodeUnschedulable()
        # CSI attach-limit columns: frozen per epoch (CSINode events
        # invalidate the session, so the set cannot drift mid-epoch)
        self._attach_drivers: List[str] = []
        self._attach_col: Dict[str, int] = {}
        # memoized pvc -> frozenset((driver, volume-key)) resolution
        self._pod_attach_cache: Dict[str, frozenset] = {}
        # per-epoch wfc_class_batchable verdicts (PV/SC/CSINode events
        # invalidate the session before the pool property can drift)
        self._wfc_cache: Dict = {}
        # (driver, volume) pairs already attached somewhere — by
        # existing pods (full encode) or earlier batch pods this epoch.
        # A pod re-using one of these rides the serial path: csi.go
        # counts len(in_use | wanted) (set semantics), the additive
        # column model would double-count the share and diverge.
        self._attached_volumes: set = set()
        # SHARED-volume slots: (driver, volume) -> slot. Shared claims
        # get a per-volume attach plane in solver state instead of the
        # additive column demand — their demand is per-NODE conditional
        # (1 only where the volume isn't attached yet). Enumerated from
        # the cluster's PVs at full encode; a pod whose shared volume
        # isn't slotted forces a rebuild (encode_pods_only → None).
        self._sv_slots: Dict[tuple, int] = {}
        self._sv_keys: List[tuple] = []
        self._sv_pad: int = 0
        self._vol_shared_cache: Dict[str, bool] = {}
        # encoding space retained by the last full encode()
        self._resource_names: Optional[List[str]] = None
        self._key_index: Optional[Dict[str, int]] = None
        self._con_index: Optional[Dict[tuple, int]] = None
        self._constraints: Optional[List[_TrackedConstraint]] = None
        self._term_index: Optional[Dict[tuple, int]] = None
        self._terms: Optional[List[_TrackedTerm]] = None
        self._profiles: Optional[Dict[tuple, int]] = None
        self._num_values: int = 0
        self._con_match_idx = ({}, [])
        self._term_match_idx = ({}, [])
        # what the last full encode's cluster-side counts read: the pods
        # the nodes' label-signature counts stood for, and the distinct
        # signatures matched against the tracked constraints and terms
        self.count_pods = 0
        self.count_sigs = 0
        # delta-column pod-plane pool (streaming-scheduler encode
        # stage): the padded pod-side matrices stay RESIDENT between
        # batches, keyed by their shape tuple, and each encode zeroes
        # only the rows the previous batch dirtied before filling the
        # new batch's rows — a b_pad-sized allocation per cycle becomes
        # an O(real rows) touch. Safe to reuse while a solve is in
        # flight because ``pack_podin`` COPIES every pooled array into
        # the packed upload buffer before dispatch (np.concatenate /
        # astype); the arrays a consumer retains past the call
        # (profile_idx, inexpressible — the sidecar carries them in its
        # pending commit dict) and the one ``pack_podin`` returns as a
        # no-copy view (pref_weight) are deliberately allocated fresh
        # every batch and never pooled.
        self._pod_plane_pool: Dict[tuple, Dict] = {}

    # ------------------------------------------------------------------
    def _sharding_active(self) -> bool:
        return (self.node_shards > 1
                and len(self.node_infos) >= ENCODE_SHARD_MIN_NODES)

    def _run_encode_workers(self, tasks: List) -> None:
        """Run zero-arg encode tasks (each owning a disjoint node-column
        slice) on the worker pool; exceptions propagate to the caller
        exactly like the serial loop's would."""
        tasks = list(tasks)
        if len(tasks) <= 1:
            for t in tasks:
                t()
            return
        with ThreadPoolExecutor(
                max_workers=min(len(tasks), self.node_shards)) as pool:
            for f in [pool.submit(t) for t in tasks]:
                f.result()

    def _for_node_shards(self, fill) -> None:
        """Apply ``fill(node_slice)`` to every node-column shard —
        concurrently when sharding is active, else one full-range call
        (the exact serial path)."""
        n = len(self.node_infos)
        if not self._sharding_active():
            fill(slice(0, n))
            return
        self._run_encode_workers(
            [partial(fill, sl)
             for sl in _node_slices(n, self.node_shards)])

    def encode(self, pods: List[Pod], pad_pods: int = 64) -> Tuple[
        EncodedCluster, EncodedBatch
    ]:
        nis = self.node_infos
        n_real = len(nis)
        # coarse node buckets: few distinct compiled shapes (each XLA
        # binary is reused via the persistent cache), bounded padding waste.
        # Above 1024 nodes the bucket stays a multiple of pad_nodes so the
        # sharded solver's divisibility contract (pad_nodes is chosen as a
        # multiple of the mesh nodes axis) still holds.
        gran = (
            self.pad_nodes if n_real <= 1024
            else _round_up(512, self.pad_nodes)
        )
        n_pad = max(_round_up(max(n_real, 1), gran), self.pad_nodes)

        pod_infos = [PodInfo.of(p) for p in pods]
        resource_names = self._collect_resource_names(pod_infos)
        r = len(resource_names)

        allocatable = np.zeros((n_pad, r), dtype=np.int32)
        requested = np.zeros((n_pad, r), dtype=np.int32)
        nonzero_req = np.zeros((n_pad, 2), dtype=np.int32)
        pod_count = np.zeros(n_pad, dtype=np.int32)
        max_pods = np.zeros(n_pad, dtype=np.int32)
        def fill_node_rows(sl: slice) -> None:
            for i in range(sl.start, sl.stop):
                ni = nis[i]
                allocatable[i] = _resource_row(ni.allocatable,
                                               resource_names)
                requested[i] = _resource_row(ni.requested, resource_names)
                nonzero_req[i] = (
                    ni.non_zero_requested.milli_cpu,
                    _kib(ni.non_zero_requested.memory),
                )
                pod_count[i] = len(ni.pods)
                max_pods[i] = ni.allocatable.allowed_pod_number \
                    or 1_000_000

        self._for_node_shards(fill_node_rows)
        sv_attached = None
        sv_keys = None
        if self._attach_col:
            self._collect_shared_volume_slots()
            if self._sv_slots:
                # pad the slot axis (power-of-2, min 8): new shared PVs
                # within the pad reuse the compiled executable
                sv_pad = max(8, 1 << (len(self._sv_slots) - 1).bit_length())
                self._sv_pad = sv_pad
                sv_attached = np.zeros((sv_pad, n_pad), dtype=np.int32)
            self._fill_attach_node_columns(allocatable, requested,
                                           sv_attached)
            if sv_attached is not None:
                import zlib

                keys = np.zeros(sv_attached.shape[0], dtype=np.int64)
                for i, (d, v) in enumerate(self._sv_keys):
                    keys[i] = zlib.crc32(f"{d}\x00{v}".encode())
                sv_keys = keys

        cluster = EncodedCluster(
            node_names=[ni.node.name for ni in nis],
            num_real_nodes=n_real,
            resource_names=resource_names,
            allocatable=allocatable,
            requested=requested,
            nonzero_requested=nonzero_req,
            pod_count=pod_count,
            max_pods=max_pods,
            sv_attached=sv_attached,
            sv_keys=sv_keys,
        )

        batch = self._encode_pods(cluster, pods, pod_infos, n_pad, pad_pods)
        return cluster, batch

    def _collect_resource_names(self, pod_infos: List[PodInfo]) -> List[str]:
        names = [CPU, MEMORY, EPHEMERAL_STORAGE]
        seen = set(names) | {PODS}
        for ni in self.node_infos:
            for name in ni.allocatable.scalar_resources:
                if name not in seen:
                    seen.add(name)
                    names.append(name)
        for pi in pod_infos:
            for name in pi.resource_request.scalar_resources:
                if name not in seen:
                    seen.add(name)
                    names.append(name)
        # CSI attach-limit columns, appended LAST so the cpu/mem column
        # indices the scorers rely on stay 0/1. ALL limited drivers get
        # a column (not just this batch's): a later incremental batch
        # carrying a limited driver then always fits the space.
        self._attach_drivers = self._attach_limit_drivers()
        self._attach_col = {}
        self._attached_volumes = set()  # repopulated by the node fill
        for d in self._attach_drivers:
            self._attach_col[d] = len(names)
            names.append(ATTACH_COL_PREFIX + d)
        return names

    def _attach_limit_drivers(self) -> List[str]:
        """CSI drivers with a published CSINode attach limit anywhere in
        the cluster. Frozen per epoch — CSINode add/update events bump
        the cache's external-mutation counter, invalidating the session
        before the set can drift."""
        if self._client is None:
            return []
        drivers = set()
        for cn in self._client.list_csi_nodes():
            for d in cn.drivers:
                if d.allocatable_count is not None:
                    drivers.add(d.name)
        return sorted(drivers)

    def _pod_attach(self, pod: Pod) -> frozenset:
        """Memoized (driver, volume-key) attach set for a pod (the
        node-side in-use scan touches every existing pod)."""
        from kubernetes_tpu.scheduler.framework.plugins.node_volume_limits import (
            pod_csi_volumes,
        )

        if not pod.spec.volumes:
            return frozenset()
        key = pod.uid or pod.full_name()
        got = self._pod_attach_cache.get(key)
        if got is None:
            got = frozenset(pod_csi_volumes(self._client, pod))
            self._pod_attach_cache[key] = got
        return got

    def _volume_is_shared(self, driver: str, vol_key: str) -> bool:
        """Is (driver, vol_key) a SHARED volume? Memoized per epoch
        (PV/PVC churn rebuilds); the predicate itself is the module's
        single shared-volume rule (``pv_is_shared``), shared with
        ``is_host_only`` so partitioner and encoder can never
        disagree."""
        got = self._vol_shared_cache.get(vol_key)
        if got is None:
            pv = self._client.get_pv(vol_key)
            got = pv is not None and pv_is_shared(self._client, pv)
            self._vol_shared_cache[vol_key] = got
        return got

    def _collect_shared_volume_slots(self) -> None:
        """Per-epoch slots for every SHARED CSI volume the cluster could
        schedule against (the per-claim attach planes' index space).
        Enumerated from PVs so slots are stable for the whole epoch —
        PV/PVC churn bumps the cache mutation seq and rebuilds."""
        self._sv_slots = {}
        self._sv_keys = []
        self._vol_shared_cache = {}
        for pv in self._client.list_pvs():
            driver = getattr(pv, "csi_driver", "")
            if not driver or driver not in self._attach_col:
                continue
            if pv_is_shared(self._client, pv):
                key = (driver, pv.name)
                if key not in self._sv_slots:
                    self._sv_slots[key] = len(self._sv_keys)
                    self._sv_keys.append(key)

    def _fill_attach_node_columns(self, allocatable: np.ndarray,
                                  requested: np.ndarray,
                                  sv_attached=None) -> None:
        """Per-node attach budgets: allocatable = the CSINode limit (or
        the NO_LIMIT sentinel), requested = distinct in-use volumes,
        CLAMPED to the limit — an already-over-limit node must reject
        pods that attach (requested + req > limit) while still admitting
        volume-free pods (requested + 0 <= limit), matching csi.go's
        ``len(in_use | wanted) > limit`` which only fires for pods with
        wanted volumes."""
        for i, ni in enumerate(self.node_infos):
            in_use: Dict[str, set] = {}
            for pi in ni.pods:
                for d, v in self._pod_attach(pi.pod):
                    in_use.setdefault(d, set()).add(v)
                    if d in self._attach_col:
                        self._attached_volumes.add((d, v))
            cn = self._client.get_csi_node(ni.node.name)
            limits: Dict[str, int] = {}
            if cn is not None:
                for drv in cn.drivers:
                    if drv.allocatable_count is not None:
                        limits[drv.name] = drv.allocatable_count
            for dname, col in self._attach_col.items():
                limit = limits.get(dname, NO_LIMIT)
                allocatable[i, col] = limit
                requested[i, col] = min(len(in_use.get(dname, ())), limit)
            if sv_attached is not None:
                for d, vols in in_use.items():
                    # an already-OVER-limit node keeps its attached
                    # bits CLEAR: the shared pod's demand then reads 1
                    # and the clamped column rejects it — matching the
                    # host filter, which refuses ANY csi-volume pod on
                    # an over-limit node (csi.go attached+new > limit);
                    # a demand-0 pass-through would diverge
                    if len(vols) > limits.get(d, NO_LIMIT):
                        continue
                    for v in vols:
                        slot = self._sv_slots.get((d, v))
                        if slot is not None:
                            sv_attached[slot, i] = 1

    # ------------------------------------------------------------------
    def _encode_pods(self, cluster: EncodedCluster, pods: List[Pod],
                     pod_infos: List[PodInfo], n_pad: int,
                     pad_pods: int) -> EncodedBatch:
        b_real = len(pods)

        # -------- topology keys: collect from constraints and terms
        topo_keys: List[str] = []
        key_index: Dict[str, int] = {}

        def key_idx(key: str) -> int:
            if key not in key_index:
                key_index[key] = len(topo_keys)
                topo_keys.append(key)
            return key_index[key]

        # tracked spread constraints (dedup); the per-pod membership masks
        # are filled later by encode_pods_only via the same indices
        constraints: List[_TrackedConstraint] = []
        con_index: Dict[tuple, int] = {}
        for pod in pods:
            for c in pod.spec.topology_spread_constraints:
                if not c.topology_key:
                    continue
                sel = labelslib.selector_from_label_selector(c.label_selector)
                key = _constraint_key(pod, c, sel)
                if key not in con_index:
                    con_index[key] = len(constraints)
                    constraints.append(
                        _TrackedConstraint(
                            key_idx(c.topology_key), c.max_skew, sel,
                            pod.namespace,
                            c.when_unsatisfiable == "DoNotSchedule",
                        )
                    )

        # tracked terms: batch pods' required aff/anti + preferred, plus
        # existing pods' required anti-affinity (owners)
        terms: List[_TrackedTerm] = []
        term_index: Dict[tuple, int] = {}

        def term_for(t) -> int:
            key = _term_key(t)
            if key not in term_index:
                term_index[key] = len(terms)
                terms.append(
                    _TrackedTerm(key_idx(t.topology_key), t.selector, t.namespaces)
                )
            return term_index[key]

        for pi in pod_infos:
            for t in pi.required_affinity_terms:
                term_for(t)
            for t in pi.required_anti_affinity_terms:
                term_for(t)
            for wt in pi.preferred_affinity_terms:
                term_for(wt.term)
            for wt in pi.preferred_anti_affinity_terms:
                term_for(wt.term)

        existing_anti_terms: List[Tuple[int, object]] = []  # (term idx, owner node)
        for ni in self.snapshot.have_pods_with_required_anti_affinity_list():
            if ni.node is None:
                continue
            for existing in ni.pods_with_required_anti_affinity:
                for t in existing.required_anti_affinity_terms:
                    existing_anti_terms.append((term_for(t), ni.node))

        # -------- topology value coding (shared value space, padded)
        k = len(topo_keys)
        value_codes: List[Dict[str, int]] = [dict() for _ in range(k)]
        topo_codes = np.full((n_pad, max(k, 1)), -1, dtype=np.int32)
        for i, ni in enumerate(self.node_infos):
            labels = ni.node.metadata.labels
            for ki, key in enumerate(topo_keys):
                if key in labels:
                    vc = value_codes[ki]
                    v = labels[key]
                    if v not in vc:
                        vc[v] = len(vc)
                    topo_codes[i, ki] = vc[v]
        num_values = max((len(vc) for vc in value_codes), default=0)
        num_values = max(num_values, 1)
        cluster.topo_keys = topo_keys
        cluster.topo_codes = topo_codes
        cluster.topo_num_values = np.array(
            [len(vc) for vc in value_codes] or [0], dtype=np.int32
        )
        # missing key -> sentinel column V
        topo_codes[topo_codes < 0] = num_values

        # -------- static profiles
        profiles: Dict[tuple, int] = {}
        profile_pods: List[Pod] = []
        for pod in pods:
            key = self._static_profile_key(pod)
            if key not in profiles:
                profiles[key] = len(profile_pods)
                profile_pods.append(pod)
        u = max(len(profile_pods), 1)
        static_masks = np.zeros((u, n_pad), dtype=bool)
        affinity_masks = np.zeros((u, n_pad), dtype=bool)
        static_scores = np.zeros((u, n_pad), dtype=np.float32)
        if self._sharding_active():
            # the O(U × N) plugin sweep is the rebuild's dominant host
            # cost: one task per (profile, node shard), each emitting
            # the columns of exactly one device shard. The per-POD
            # volume context (host-only verdict, plugin construction,
            # vb.pre_filter's client resolution) is hoisted out and
            # computed once per profile — only the per-NODE loops fan
            # out to the workers.
            contexts = [self._volume_ctx(pod) for pod in profile_pods]
            self._run_encode_workers([
                partial(self._compute_static, pod, static_masks[ui],
                        affinity_masks[ui], static_scores[ui], sl,
                        contexts[ui])
                for ui, pod in enumerate(profile_pods)
                for sl in _node_slices(len(self.node_infos),
                                       self.node_shards)
            ])
        else:
            for ui, pod in enumerate(profile_pods):
                self._compute_static(pod, static_masks[ui],
                                     affinity_masks[ui],
                                     static_scores[ui])

        # retain the encoding space, then fill the pod-side arrays with
        # THE SAME code the incremental path uses — a single
        # implementation cannot diverge between the two paths
        self._resource_names = cluster.resource_names
        self._key_index = key_index
        self._con_index = con_index
        self._constraints = constraints
        self._term_index = term_index
        self._terms = terms
        self._profiles = profiles
        self._num_values = num_values
        self._con_match_idx = _build_match_index(constraints)
        self._term_match_idx = _build_match_index(terms)
        pb = self.encode_pods_only(pods, pad_pods)
        if pb is None:  # cannot happen: every pod was just registered
            raise RuntimeError("pod-side encode failed against a space "
                               "built from the same pods")
        b_pad = pb.requests.shape[0]

        # -------- cluster-side spread constraint arrays
        n_nodes = len(self.node_infos)
        sc_node, term_node = self._node_match_counts(constraints, terms)
        sc = max(len(constraints), 1)
        sc_key_idx = np.zeros(sc, dtype=np.int32)
        sc_max_skew = np.ones(sc, dtype=np.int32)
        sc_hard = np.zeros(sc, dtype=bool)
        sc_counts = np.zeros((sc, num_values + 1), dtype=np.int32)
        sc_domain = np.zeros((u, sc, num_values + 1), dtype=bool)
        for ci, con in enumerate(constraints):
            sc_key_idx[ci] = con.key_idx
            sc_max_skew[ci] = con.max_skew
            sc_hard[ci] = con.hard
            _count_by_domain(sc_counts[ci],
                             topo_codes[:n_nodes, con.key_idx], sc_node[ci])
            # eligible domains per profile
            for ui in range(len(profile_pods)):
                for i in range(len(self.node_infos)):
                    if affinity_masks[ui, i]:
                        code = topo_codes[i, con.key_idx]
                        if code < num_values:
                            sc_domain[ui, ci, code] = True

        # -------- cluster-side term arrays
        t_n = max(len(terms), 1)
        term_key_idx = np.zeros(t_n, dtype=np.int32)
        term_counts = np.zeros((t_n, num_values + 1), dtype=np.int32)
        term_owners = np.zeros((t_n, num_values + 1), dtype=np.int32)
        for ti, term in enumerate(terms):
            term_key_idx[ti] = term.key_idx
            _count_by_domain(term_counts[ti],
                             topo_codes[:n_nodes, term.key_idx],
                             term_node[ti])
        node_idx = {ni.node.name: i for i, ni in enumerate(self.node_infos)}
        for ti, owner_node in existing_anti_terms:
            i = node_idx[owner_node.name]
            code = topo_codes[i, terms[ti].key_idx]
            if code < num_values:
                term_owners[ti, code] += 1

        return EncodedBatch(
            pods=pods,
            num_real_pods=b_real,
            requests=pb.requests,
            nonzero_requests=pb.nonzero_requests,
            profile_idx=pb.profile_idx,
            static_masks=static_masks,
            affinity_masks=affinity_masks,
            static_scores=static_scores,
            inexpressible=pb.inexpressible,
            sc_key_idx=sc_key_idx,
            sc_max_skew=sc_max_skew,
            sc_hard=sc_hard,
            sc_counts=sc_counts,
            sc_domain=sc_domain,
            pod_sc=pb.pod_sc,
            pod_sc_match=pb.pod_sc_match,
            term_key_idx=term_key_idx,
            term_counts=term_counts,
            term_owners=term_owners,
            match_by=pb.match_by,
            own_aff=pb.own_aff,
            own_anti=pb.own_anti,
            pref_weight=pb.pref_weight,
            pod_sv=pb.pod_sv,
            num_values=num_values,
        )

    def _node_match_counts(self, constraints: List[_TrackedConstraint],
                           terms: List[_TrackedTerm]):
        """Per tracked constraint and per tracked term, the existing pods
        of each node it counts: a constraint skips terminating pods, a
        term does not. Read from the nodes' label-signature counts, with
        one selector match per distinct signature instead of one per
        pod."""
        n = len(self.node_infos)
        sc_node = [[0] * n for _ in constraints]
        term_node = [[0] * n for _ in terms]
        hits: Dict[tuple, tuple] = {}
        pods_read = 0
        if constraints or terms:
            for i, ni in enumerate(self.node_infos):
                for sig, count in ni.label_sig_counts.items():
                    hit = hits.get(sig)
                    if hit is None:
                        ns, labels, terminating = sig
                        labels = dict(labels)
                        hit = hits[sig] = (
                            [ci for ci, con in enumerate(constraints)
                             if not terminating and ns == con.namespace
                             and con.selector.matches(labels)],
                            [ti for ti, term in enumerate(terms)
                             if ns in term.namespaces
                             and term.selector.matches(labels)],
                        )
                    pods_read += count
                    for ci in hit[0]:
                        sc_node[ci][i] += count
                    for ti in hit[1]:
                        term_node[ti][i] += count
        self.count_pods = pods_read
        self.count_sigs = len(hits)
        return sc_node, term_node

    # ------------------------------------------------------------------
    def encode_pods_only(self, pods: List[Pod],
                         pad_pods: int) -> Optional[EncodedPodBatch]:
        """Encode ONLY the pod-side arrays of ``pods`` against the space
        retained by the last full ``encode``. Returns None when any pod
        does not fit that space (new scalar resource, untracked topology
        constraint/term, unseen static profile) — the caller then rebuilds
        the session with a full encode."""
        if self._resource_names is None:
            return None
        b_real = len(pods)
        # ONE compiled shape for every batch up to pad_pods (the sidecar's
        # max_batch): a pow2 bucket between b_real and pad_pods would
        # recompile mid-run on a partially-filled drain. Rounded to 8 for
        # the pallas kernel's SMEM sublane tiling.
        b_pad = _round_up(
            pad_pods if b_real <= pad_pods
            else 1 << (b_real - 1).bit_length(), 8
        )
        resource_names = self._resource_names
        known_resources = set(resource_names)
        constraints = self._constraints
        terms = self._terms
        r = len(resource_names)
        sc = max(len(constraints), 1)
        t_n = max(len(terms), 1)

        # pooled (delta-column) planes: zero only the previously-dirty
        # rows, then fill the new batch's — see _pod_plane_pool
        key = (b_pad, r, sc, t_n, self._sv_pad)
        bufs = self._pod_plane_pool.get(key)
        if bufs is None:
            bufs = {
                "requests": np.zeros((b_pad, r), dtype=np.int32),
                "nonzero_requests": np.zeros((b_pad, 2),
                                             dtype=np.int32),
                "pod_sc": np.zeros((b_pad, sc), dtype=bool),
                "pod_sc_match": np.zeros((b_pad, sc), dtype=bool),
                "match_by": np.zeros((b_pad, t_n), dtype=bool),
                "own_aff": np.zeros((b_pad, t_n), dtype=bool),
                "own_anti": np.zeros((b_pad, t_n), dtype=bool),
                "dirty": 0,
            }
            if self._sv_pad:
                # sentinel slot = the padded dim (never a real plane)
                bufs["pod_sv"] = np.full((b_pad, 2), (self._sv_pad, 0),
                                         dtype=np.int32)
            self._pod_plane_pool[key] = bufs
        else:
            dirty = bufs["dirty"]
            for name in ("requests", "nonzero_requests", "pod_sc",
                         "pod_sc_match", "match_by", "own_aff",
                         "own_anti"):
                bufs[name][:dirty] = 0
            if self._sv_pad:
                bufs["pod_sv"][:dirty] = (self._sv_pad, 0)
        # rows filled below — recorded BEFORE the loop so an early
        # bail (pod outside the space → rebuild) still marks them
        bufs["dirty"] = b_real
        requests = bufs["requests"]
        nonzero_requests = bufs["nonzero_requests"]
        pod_sc = bufs["pod_sc"]
        pod_sc_match = bufs["pod_sc_match"]
        match_by = bufs["match_by"]
        own_aff = bufs["own_aff"]
        own_anti = bufs["own_anti"]
        pod_sv = bufs.get("pod_sv")
        # NOT pooled: retained by the sidecar's pending dict past this
        # call (profile_idx, inexpressible) or returned as a no-copy
        # view by pack_podin (pref_weight)
        profile_idx = np.zeros(b_pad, dtype=np.int32)
        inexpressible = np.zeros(b_pad, dtype=bool)
        pref_weight = np.zeros((b_pad, t_n), dtype=np.float32)

        for bi, pod in enumerate(pods):
            pi = PodInfo.of(pod)
            if any(
                name not in known_resources
                for name in pi.resource_request.scalar_resources
            ):
                return None
            ui = self._profiles.get(self._static_profile_key(pod))
            if ui is None:
                return None
            profile_idx[bi] = ui
            requests[bi] = _resource_row(pi.resource_request, resource_names)
            nonzero_requests[bi] = (
                pi.non_zero_request.milli_cpu,
                _kib(pi.non_zero_request.memory),
            )
            inexpressible[bi] = self._is_inexpressible(pod)
            if self._attach_col and not inexpressible[bi] and \
                    pod.spec.volumes:
                relevant = {
                    (d, v) for d, v in self._pod_attach(pod)
                    if d in self._attach_col
                }
                shared = {p for p in relevant if p in self._sv_slots}
                unslotted_shared = {
                    (d, v) for d, v in relevant - shared
                    if self._volume_is_shared(d, v)
                }
                if unslotted_shared:
                    # a shared volume that post-dates this epoch's slot
                    # enumeration: rebuild so it gets a plane (the PV
                    # write that created it bumped the mutation seq)
                    return None
                relevant -= shared
                if len(shared) > 1:
                    # one conditional-demand plane per pod per step; a
                    # multi-shared-volume pod keeps the host path
                    inexpressible[bi] = True
                elif shared:
                    d, v = next(iter(shared))
                    pod_sv[bi] = (self._sv_slots[(d, v)],
                                  self._attach_col[d])
                if inexpressible[bi]:
                    pass
                elif relevant & self._attached_volumes:
                    # NON-shared volume reused by an existing or
                    # earlier-batch pod: serial path for exact
                    # set-union semantics
                    inexpressible[bi] = True
                else:
                    self._attached_volumes |= relevant
                    for d, _v in relevant:
                        requests[bi, self._attach_col[d]] += 1

            for c in pod.spec.topology_spread_constraints:
                if not c.topology_key:
                    continue
                sel = labelslib.selector_from_label_selector(c.label_selector)
                ci = self._con_index.get(_constraint_key(pod, c, sel))
                if ci is None:
                    return None
                pod_sc[bi, ci] = True
            for ci in self.matched_constraints(pod):
                pod_sc_match[bi, ci] = True
            labels = pod.metadata.labels or {}

            def tracked(t) -> Optional[int]:
                return self._term_index.get(_term_key(t))

            for t in pi.required_affinity_terms:
                ti = tracked(t)
                if ti is None:
                    return None
                own_aff[bi, ti] = True
            for t in pi.required_anti_affinity_terms:
                ti = tracked(t)
                if ti is None:
                    return None
                own_anti[bi, ti] = True
            for wt in pi.preferred_affinity_terms:
                ti = tracked(wt.term)
                if ti is None:
                    return None
                pref_weight[bi, ti] += float(wt.weight)
            for wt in pi.preferred_anti_affinity_terms:
                ti = tracked(wt.term)
                if ti is None:
                    return None
                pref_weight[bi, ti] -= float(wt.weight)
            simple_terms, complex_terms = self._term_match_idx
            for kv in labels.items():
                for ti, term in simple_terms.get(kv, ()):
                    if pod.namespace in term.namespaces:
                        match_by[bi, ti] = True
            for ti, term in complex_terms:
                match_by[bi, ti] = term.matches(pod)

        return EncodedPodBatch(
            pods=pods,
            num_real_pods=b_real,
            requests=requests,
            nonzero_requests=nonzero_requests,
            profile_idx=profile_idx,
            inexpressible=inexpressible,
            pod_sc=pod_sc,
            pod_sc_match=pod_sc_match,
            match_by=match_by,
            own_aff=own_aff,
            own_anti=own_anti,
            pref_weight=pref_weight,
            pod_sv=pod_sv,
        )

    def matched_constraints(self, pod: Pod) -> List[int]:
        """Indices of the tracked spread constraints whose namespace and
        selector match ``pod``: the pods each one counts. The pod rows'
        ``pod_sc_match`` and the mirror's spread deltas both read it."""
        simple_cons, complex_cons = self._con_match_idx
        out = [ci for kv in (pod.metadata.labels or {}).items()
               for ci, con in simple_cons.get(kv, ())
               if pod.namespace == con.namespace]
        out.extend(ci for ci, con in complex_cons if con.matches(pod))
        return out

    # ------------------------------------------------------------------
    def _static_profile_key(self, pod: Pod) -> tuple:
        spec = pod.spec
        aff_repr = ""
        if spec.affinity is not None and spec.affinity.node_affinity is not None:
            na = spec.affinity.node_affinity
            req = na.required_during_scheduling_ignored_during_execution
            aff_repr = repr(
                [
                    [(e.key, e.operator, tuple(e.values)) for e in t.match_expressions]
                    + [("f:" + e.key, e.operator, tuple(e.values)) for e in t.match_fields]
                    for t in (req.node_selector_terms if req else [])
                ]
            ) + repr(
                [
                    (p.weight,
                     [(e.key, e.operator, tuple(e.values))
                      for e in p.preference.match_expressions])
                    for p in na.preferred_during_scheduling_ignored_during_execution
                ]
            )
        return (
            spec.node_name,
            tuple(sorted(spec.node_selector.items())),
            aff_repr,
            tuple(
                (t.key, t.operator, t.value, t.effect) for t in spec.tolerations
            ),
            tuple(sorted(c.image for c in spec.containers)),
            self._volume_profile_identity(pod),
            # mesh-block component: two gangs anchor to different mesh
            # coordinates, so their static score columns must differ;
            # () for every unlabeled pod — existing keys unchanged
            mesh_locality.profile_component(pod),
        )

    def _volume_profile_identity(self, pod: Pod) -> tuple:
        """Volume component of the static profile key: two pods share a
        profile only when their PVC-backed volumes impose the SAME
        node feasibility — i.e. the multiset of (PV node-affinity, PV
        zone labels) matches. Distinct PVs with no affinity/zone all
        reduce to the same identity, so the 1-claim-per-pod bench
        workloads collapse to one profile."""
        if self._client is None:
            return ()
        from kubernetes_tpu.scheduler.framework.plugins.volume_zone import (
            TOPOLOGY_LABELS,
        )

        ident = []
        for v in pod.spec.volumes:
            if not v.persistent_volume_claim:
                continue
            pvc = self._client.get_pvc(pod.namespace, v.persistent_volume_claim)
            if pvc is None:
                ident.append(("missing", v.persistent_volume_claim))
                continue
            if not pvc.volume_name:
                if wfc_class_batchable(self._client,
                                       pvc.storage_class_name,
                                       self._wfc_cache):
                    # node-independent pool: feasibility is a property
                    # of the CLASS, so every such pod shares a profile
                    # (a per-claim identity would explode U to one
                    # profile per pod)
                    ident.append(("wfc", pvc.storage_class_name))
                else:
                    # host-only shapes; identity only needs stability
                    ident.append(("unbound", v.persistent_volume_claim))
                continue
            pv = self._client.get_pv(pvc.volume_name)
            if pv is None:
                ident.append(("missing-pv", pvc.volume_name))
                continue
            zones = tuple(
                (lb, pv.metadata.labels[lb])
                for lb in TOPOLOGY_LABELS
                if lb in pv.metadata.labels
            )
            ident.append(("pv", repr(pv.node_affinity), zones))
        return tuple(sorted(ident))

    # sentinel: "compute the volume context yourself" (the serial path);
    # the sharded sweep precomputes one context per profile and shares
    # it across that profile's shard tasks
    _VOL_CTX_UNSET = object()

    def _volume_ctx(self, pod: Pod):
        """Per-POD half of the volume-feasibility work: the host-only
        verdict, plugin construction and ``vb.pre_filter``'s client
        resolution — node-independent, so the sharded sweep computes it
        ONCE per profile instead of once per (profile, shard). Returns
        None when the pod imposes no expressible volume constraint,
        else ``(vb, vz, state, prefilter_failed)``; the CycleState is
        written only by pre_filter here and read-only in the per-node
        filters, so sharing it across shard workers is safe."""
        if not (
            self._client is not None
            and any(v.persistent_volume_claim for v in pod.spec.volumes)
            and not is_host_only(pod, self._client, self._wfc_cache)
        ):
            return None
        from kubernetes_tpu.scheduler.framework.plugins.volume_binding import (  # noqa: E501
            VolumeBinding,
        )
        from kubernetes_tpu.scheduler.framework.plugins.volume_zone import (
            VolumeZone,
        )

        handle = _ClientHandle(self._client)
        vb = VolumeBinding(handle)
        vz = VolumeZone(handle)
        state = CycleState()
        failed = vb.pre_filter(state, pod) is not None
        return (vb, vz, state, failed)

    def _compute_static(self, pod: Pod, mask: np.ndarray,
                        affinity_mask: np.ndarray,
                        scores: np.ndarray,
                        node_range: Optional[slice] = None,
                        vol_ctx=_VOL_CTX_UNSET) -> None:
        """Evaluate node-static predicates/scores with the real host
        plugins so the device path is differentially exact.
        ``node_range`` restricts the sweep to one node-column shard
        (the sharded encode stage) — every plugin here is per-node
        stateless, so a sharded sweep is bit-identical to the serial
        one. ``vol_ctx`` is the precomputed per-pod volume context
        (``_volume_ctx``); left unset, it is computed here (the serial
        path's one call per profile)."""
        if node_range is None:
            node_range = slice(0, len(self.node_infos))
        state = CycleState()
        # mesh-adjacency scorer, hoisted per profile: the anchor/grid
        # extent is a whole-cluster property, so it is computed from
        # the FULL node list even when this task sweeps one shard —
        # the sharded sweep stays bit-identical to the serial one.
        # Label-gated BEFORE materializing the node list: unlabeled
        # profiles (every existing workload) must not pay an O(N)
        # allocation per (profile, shard) task
        mesh_fn = None
        if mesh_locality.enabled() and mesh_locality.mesh_block(pod):
            mesh_fn = mesh_locality.profile_scorer(
                pod, [n.node for n in self.node_infos])
        for i in range(node_range.start, node_range.stop):
            ni = self.node_infos[i]
            node = ni.node
            ok_affinity = pod_matches_node_selector_and_affinity(pod, node)
            affinity_mask[i] = ok_affinity
            ok = ok_affinity
            if ok and pod.spec.node_name and pod.spec.node_name != node.name:
                ok = False
            if ok and self._unsched_plugin.filter(state, pod, ni) is not None:
                ok = False
            if ok and self._taint_plugin.filter(state, pod, ni) is not None:
                ok = False
            mask[i] = ok
            if ok:
                scores[i] = self._static_score(pod, ni)
                if mesh_fn is not None:
                    scores[i] += mesh_fn(node)
        if vol_ctx is self._VOL_CTX_UNSET:
            vol_ctx = self._volume_ctx(pod)
        if vol_ctx is not None:
            self._apply_volume_feasibility(pod, mask, node_range,
                                           vol_ctx)

    def _apply_volume_feasibility(self, pod: Pod, mask: np.ndarray,
                                  node_range: Optional[slice],
                                  vol_ctx) -> None:
        """Fold PV node-affinity + zone feasibility into the static mask
        using the REAL host plugins (differential exactness, like the
        other static predicates). Only reached for expressible pods —
        all claims bound — so VolumeBinding's Filter is the pure
        bound-claim affinity check and Reserve/PreBind stay no-ops.

        Note on preemption semantics: the reference reports volume
        conflicts as plain Unschedulable, keeping such nodes preemption
        *candidates*; folding them into the static mask marks them
        UnschedulableAndUnresolvable, pruning them earlier. Outcome-
        equivalent — evicting pods never fixes a PV affinity/zone
        conflict, so the reference's dry-run re-filter would reject the
        node anyway."""
        if node_range is None:
            node_range = slice(0, len(self.node_infos))
        vb, vz, state, prefilter_failed = vol_ctx
        if prefilter_failed:
            # each shard worker clears ITS columns; the verdict is
            # per-pod, so every shard reaches the same branch
            mask[node_range] = False
            return
        for i in range(node_range.start, node_range.stop):
            ni = self.node_infos[i]
            if not mask[i]:
                continue
            if (
                vb.filter(state, pod, ni) is not None
                or vz.filter(state, pod, ni) is not None
            ):
                mask[i] = False

    @staticmethod
    def _static_score(pod: Pod, ni) -> float:
        """Static score contributions (preferred node affinity weights;
        image locality). Dynamic scores live on device."""
        from kubernetes_tpu.scheduler.framework.plugins.helpers import (
            node_selector_term_matches,
        )

        score = 0.0
        aff = pod.spec.affinity
        if aff is not None and aff.node_affinity is not None:
            for term in aff.node_affinity.preferred_during_scheduling_ignored_during_execution:
                if term.weight and node_selector_term_matches(term.preference, ni.node):
                    score += term.weight
        for c in pod.spec.containers:
            state = ni.image_states.get(c.image)
            if state is not None:
                score += min(state.size / (1024 * 1024 * 1024), 1.0)  # ≤1 pt/GiB
        return score

    def _is_inexpressible(self, pod: Pod) -> bool:
        return is_host_only(pod, self._client, self._wfc_cache)


def wfc_class_batchable(client, sc_name: str, cache=None) -> bool:
    """True when an UNBOUND claim of this storage class is expressible
    on the batch path:

    - WaitForFirstConsumer binding (Immediate unbound claims are
      unschedulable until the PV controller acts — host semantics);
    - the provisioner has no published CSINode attach limit anywhere
      (otherwise the claim consumes attach budget the columns must
      track per claim);
    - every candidate PV (Available, unclaimed, same class) is free of
      node affinity — the match result is then identical on every
      node, so scheduling carries NO volume constraint and the actual
      PV assignment can happen at commit time.

    O(PVs + CSINodes) per class; callers scanning many pods pass a
    per-drain ``cache`` dict so one drain pays one scan per class."""
    if not sc_name:
        return False
    if cache is not None and ("wfc", sc_name) in cache:
        return cache[("wfc", sc_name)]
    verdict = False
    sc = client.get_storage_class(sc_name)
    if sc is not None and \
            sc.volume_binding_mode == "WaitForFirstConsumer":
        limited = any(
            d.name == sc.provisioner and d.allocatable_count is not None
            for cn in client.list_csi_nodes() for d in cn.drivers
        )
        if not limited:
            verdict = all(
                pv.node_affinity is None
                for pv in client.list_pvs()
                if pv.phase == "Available" and pv.claim_ref is None
                and pv.storage_class_name == sc_name
            )
    if cache is not None:
        cache[("wfc", sc_name)] = verdict
    return verdict


def pv_is_shared(client, pv) -> bool:
    """THE shared-volume predicate (single rule for is_host_only, slot
    enumeration, and the incremental encoder): a PV is shared when it —
    or the claim its ``claim_ref`` names — carries a RWX/ROX access
    mode. A one-sided binding (PVC shared, PV silent with no
    claim_ref) is deliberately NOT shared under this rule everywhere
    at once: such pods stay on the additive/serial path consistently
    instead of flapping between classifiers."""
    if any(m in SHARED_ACCESS_MODES for m in pv.access_modes):
        return True
    if pv.claim_ref:
        ns, _, nm = pv.claim_ref.partition("/")
        pvc = client.get_pvc(ns, nm)
        return pvc is not None and any(
            m in SHARED_ACCESS_MODES for m in pvc.access_modes)
    return False


def is_host_only(pod: Pod, client=None, cache=None) -> bool:
    """Pods needing host-only machinery take the serial path — the single
    source of truth shared by the encoder and the sidecar's partitioner.

    Host-only: inline cloud-disk volumes (``VolumeRestrictions``'
    node-pod conflict scan and the in-tree attach limits are dynamic
    host-side checks), host ports (``UsedPorts`` conflict tracking), and
    PVC volumes that are NOT plainly bound — with one carve-out: an
    unbound WaitForFirstConsumer claim whose class is attach-irrelevant
    and whose candidate PV pool is NODE-INDEPENDENT (no candidate
    carries node affinity) imposes no per-node constraint at all, so it
    batches; the sidecar assigns an actual PV from the pool at COMMIT
    time (the Reserve/PreBind moment) and falls back to the serial path
    if the pool ran dry with no provisioner. Other unbound claims need
    the stateful per-node ``VolumeBinding`` match machinery, and
    CSI-attached shared (RWX/ROX) claims would double-count in the
    attach-column model (a shared claim with no CSI driver consumes no
    attach budget, so it batches). A bound claim with a live PV is
    otherwise fully expressible:
    feasibility is the PV's static node affinity/zone plus the CSI
    attach-limit resource columns. Without a ``client`` every PVC pod is
    conservatively host-only (the pre-round-3 contract)."""
    for v in pod.spec.volumes:
        if (
            v.gce_persistent_disk or v.aws_elastic_block_store
            or v.azure_disk or v.rbd or v.iscsi
        ):
            return True
    if any(p.host_port > 0 for c in pod.spec.containers for p in c.ports):
        return True
    shared_csi = 0
    for v in pod.spec.volumes:
        if not v.persistent_volume_claim:
            continue
        if client is None:
            return True
        pvc = client.get_pvc(pod.namespace, v.persistent_volume_claim)
        if pvc is None:
            return True
        if not pvc.volume_name:
            if not wfc_class_batchable(client, pvc.storage_class_name,
                                       cache):
                return True
            continue
        pv = client.get_pv(pvc.volume_name)
        if pv is None:
            return True
        if getattr(pv, "csi_driver", "") and pv_is_shared(client, pv):
            # CSI-attached shared volumes batch via the per-volume
            # attach planes (conditional per-node demand carried in
            # solver state — csi.go's len(in_use | wanted) set
            # semantics). One plane reference per pod per step: a pod
            # with SEVERAL shared CSI volumes keeps the host path.
            shared_csi += 1
            if shared_csi > 1:
                return True
    return False


class _ClientHandle:
    """Minimal framework-handle shim for running the host volume plugins
    inside the encoder (they only touch ``handle.client``)."""

    __slots__ = ("client",)

    def __init__(self, client):
        self.client = client
