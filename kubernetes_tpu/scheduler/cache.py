"""Assume-aware cluster cache with incremental snapshotting.

Behavioral equivalent of the reference's ``pkg/scheduler/internal/cache/cache.go``:
optimistically-bound ("assumed") pods with a TTL (30s default, cache.go:42),
a doubly-linked list of NodeInfos ordered by most-recently-updated Generation
so ``update_snapshot`` touches only the changed prefix (cache.go:203-287),
and cluster-wide image-state aggregation.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Set

from kubernetes_tpu.api.types import TAINT_NODE_UNREACHABLE, Node, Pod
from kubernetes_tpu.scheduler.node_tree import NodeTree
from kubernetes_tpu.scheduler.snapshot import Snapshot
from kubernetes_tpu.scheduler.types import (
    ImageStateSummary,
    NodeInfo,
    get_pod_key,
    next_generation,
)

DEFAULT_ASSUME_TTL = 30.0
CLEANUP_INTERVAL = 1.0


def _pod_mirror_changed(old: Pod, new: Pod) -> bool:
    """Whether a pod update changes anything the device mirror tracks
    (requests, affinity, node assignment, labels, deletion). Status-only
    patches — the overwhelming majority of live-informer MODIFIED events —
    must not invalidate the mirror."""
    return (
        old.spec != new.spec
        or old.metadata.labels != new.metadata.labels
        or old.metadata.deletion_timestamp != new.metadata.deletion_timestamp
    )


def _node_mirror_changed(old: Node, new: Node) -> bool:
    """Whether a node update changes anything the device mirror tracks
    (allocatable/images via status, taints/unschedulable via spec,
    topology via labels). Heartbeat-only updates must not invalidate."""
    if old is None:
        return True
    # status.conditions carries heartbeat timestamps — deliberately excluded
    return (
        old.status.allocatable != new.status.allocatable
        or old.status.images != new.status.images
        or old.spec != new.spec
        or old.metadata.labels != new.metadata.labels
    )


class _PodState:
    __slots__ = ("pod", "deadline", "binding_finished")

    def __init__(self, pod: Pod):
        self.pod = pod
        self.deadline: Optional[float] = None
        self.binding_finished = False


class _NodeInfoListItem:
    __slots__ = ("info", "next", "prev")

    def __init__(self, info: NodeInfo):
        self.info = info
        self.next: Optional["_NodeInfoListItem"] = None
        self.prev: Optional["_NodeInfoListItem"] = None


class _ImageState:
    """Cluster-wide per-image state. Exposed directly (shared, live) as the
    NodeInfo image-state summary so num_nodes never goes stale as other
    nodes gain/lose the image."""

    __slots__ = ("size", "nodes")

    def __init__(self, size: int):
        self.size = size
        self.nodes: Set[str] = set()

    @property
    def num_nodes(self) -> int:
        return len(self.nodes)


class SchedulerCache:
    def __init__(self, ttl: float = DEFAULT_ASSUME_TTL, now=time.monotonic):
        self._ttl = ttl
        self._now = now
        self._lock = threading.RLock()
        # Monotonic counter of every NodeInfo-affecting mutation. The TPU
        # solver session snapshots it after committing a batch; a mismatch
        # at the next batch means the cluster changed underneath the
        # device-resident state mirror, which must then be rebuilt.
        # Informer confirmations of assumed pods (add_pod with a matching
        # nodeName) change nothing the device mirror tracks, so they do
        # not bump it.
        self._mutation_seq = 0
        # Counter of node-SET changes only (a node appearing or
        # vanishing, not updates). The solver session anchors its
        # encoded node planes to this: mutation_seq arithmetic can be
        # laundered by compensating bumps, but an encoding built over a
        # node set from another epoch must never serve the incremental
        # path (chaos_nodes: mass deletion must force a re-encode, not
        # a spin of declines against ghost columns).
        self._node_set_seq = 0
        # commit timestamp (Event.ts) of the NEWEST watch event the
        # event handlers applied to this cache — the snapshot-staleness
        # SLI's anchor: at solve time, staleness = now - last_event_ts.
        # A bare float write/read (GIL-atomic) — no lock on the
        # event-ingestion hot path.
        self.last_event_ts = 0.0
        self._nodes: Dict[str, _NodeInfoListItem] = {}
        self._head: Optional[_NodeInfoListItem] = None
        self._node_tree = NodeTree()
        self._assumed_pods: Set[str] = set()
        self._pod_states: Dict[str, _PodState] = {}
        self._image_states: Dict[str, _ImageState] = {}
        self._stop = threading.Event()
        self._cleanup_thread: Optional[threading.Thread] = None
        # device-mirror delta journal (ops.mirror.DeltaJournal): when
        # attached, every mutation_seq bump is noted as a compact delta
        # record so the solver session can SCATTER the window into the
        # device-resident planes instead of rebuilding. None (default)
        # costs one attribute test per mutation.
        self._journal = None

    # ------------------------------------------------------------------
    # linked-list maintenance (cache.go moveNodeInfoToHead / removeNodeInfoFromList)
    def _move_to_head(self, name: str) -> None:
        item = self._nodes.get(name)
        if item is None or item is self._head:
            return
        if item.prev is not None:
            item.prev.next = item.next
        if item.next is not None:
            item.next.prev = item.prev
        if self._head is not None:
            self._head.prev = item
        item.next = self._head
        item.prev = None
        self._head = item

    def _remove_from_list(self, name: str) -> None:
        item = self._nodes.get(name)
        if item is None:
            return
        if item.prev is not None:
            item.prev.next = item.next
        if item.next is not None:
            item.next.prev = item.prev
        if item is self._head:
            self._head = item.next
        del self._nodes[name]

    def _ensure_node(self, name: str) -> _NodeInfoListItem:
        item = self._nodes.get(name)
        if item is None:
            item = _NodeInfoListItem(NodeInfo())
            self._nodes[name] = item
            if self._head is not None:
                self._head.prev = item
            item.next = self._head
            self._head = item
        return item

    # ------------------------------------------------------------------
    def attach_delta_journal(self, journal) -> None:
        """Attach a mirror delta journal: every later ``mutation_seq``
        bump emits one record (under the cache lock, so record order ==
        seq order). Re-attaching replaces the journal — an earlier
        session's journal simply stops receiving records and its next
        window read reports a gap (→ reseed)."""
        with self._lock:
            self._journal = journal

    def _note(self, kind: str, a=None, b=None) -> None:
        """Journal the mutation just bumped (caller holds the lock and
        has ALREADY incremented ``_mutation_seq``)."""
        if self._journal is not None:
            self._journal.note(self._mutation_seq, kind, a, b)

    # ------------------------------------------------------------------
    @property
    def mutation_seq(self) -> int:
        with self._lock:
            return self._mutation_seq

    @property
    def node_set_seq(self) -> int:
        with self._lock:
            return self._node_set_seq

    def commit_target_flags(self, names) -> Dict[str, Optional[Node]]:
        """Commit-time liveness probe for a batch of bind targets: ONE
        lock acquisition for the whole batch, set lookups per name.
        Returns ONLY the suspect entries — ``name -> None`` when the
        node is gone from the cache (deleted, or never seen), ``name ->
        Node`` when it exists but is cordoned or carries taints the
        commit guard must test against the pod's tolerations. Names
        absent from the result are fully bindable. The common no-churn
        batch returns an empty dict, so the guard costs O(1) per commit
        and nothing allocates on the happy path."""
        flagged: Dict[str, Optional[Node]] = {}
        with self._lock:
            for name in names:
                item = self._nodes.get(name)
                node = item.info.node if item is not None else None
                if node is None:
                    flagged[name] = None
                elif node.spec.unschedulable or any(
                    t.key == TAINT_NODE_UNREACHABLE
                    for t in node.spec.taints
                ):
                    flagged[name] = node
        return flagged

    def commit_fits(self, items) -> List[Optional[str]]:
        """Commit-time capacity probe for a batch of (pod, node_name)
        bind targets — the multi-replica conflict guard's cache half:
        a replica about to commit checks that each target still has
        room AGAINST THE LIVE CACHE, which by now includes the pods its
        sibling replicas bound since this batch was solved (their bind
        events apply to every replica's cache). Cumulative within the
        batch (two pods of this batch on one node charge it twice).
        Returns a positional reason-or-None list; node existence and
        taint staleness remain ``commit_target_flags``'s job."""
        from kubernetes_tpu.scheduler.types import (
            compute_pod_resource_request,
        )

        out: List[Optional[str]] = [None] * len(items)
        with self._lock:
            extra: Dict[str, List[int]] = {}
            for i, (pod, node_name) in enumerate(items):
                item = self._nodes.get(node_name)
                if item is None or item.info.node is None:
                    continue
                info = item.info
                req = compute_pod_resource_request(pod)
                add = extra.setdefault(node_name, [0, 0, 0])
                alloc = info.allocatable
                if (alloc.milli_cpu and info.requested.milli_cpu + add[0]
                        + req.milli_cpu > alloc.milli_cpu) or \
                   (alloc.memory and info.requested.memory + add[1]
                        + req.memory > alloc.memory) or \
                   (alloc.allowed_pod_number and len(info.pods) + add[2]
                        + 1 > alloc.allowed_pod_number):
                    out[i] = "capacity"
                    continue
                add[0] += req.milli_cpu
                add[1] += req.memory
                add[2] += 1
        return out

    def note_external_mutation(self) -> None:
        """Record a state change the cache itself doesn't track (PV /
        PVC / StorageClass / CSINode / Service object churn). The batch
        sidecar's device mirror encodes volume feasibility and attach
        budgets from those objects, so their mutations must invalidate
        the mirror exactly like pod/node mutations do — the bump makes
        ``SolverSession.mirror_current``'s arithmetic fail."""
        with self._lock:
            self._mutation_seq += 1
            self._note("external")

    def note_event_ts(self, ts: float) -> None:
        """Advance the newest-applied-event commit timestamp (called by
        the event handlers once per delivered batch; monotonic by
        construction, but a relist can replay out of order — keep the
        max)."""
        if ts > self.last_event_ts:
            self.last_event_ts = ts

    # ------------------------------------------------------------------
    # pods
    def assume_pod(self, pod: Pod) -> None:
        key = get_pod_key(pod)
        with self._lock:
            if key in self._pod_states:
                raise ValueError(f"pod {key} is in the cache, so can't be assumed")
            self._mutation_seq += 1
            # serial-path bind: NOT device-applied (the solve carry
            # never placed this pod) — the mirror scatters it
            self._note("assume", pod)
            self._add_pod_locked(pod)
            self._pod_states[key] = _PodState(pod)
            self._assumed_pods.add(key)

    def assume_pods(self, pods: List[Pod]) -> List[Optional[str]]:
        """Bulk assume under ONE lock (the batch commit path). Returns a
        positional list of error messages (None = assumed). Semantics are
        exactly N sequential ``assume_pod`` calls: one mutation_seq bump
        per successful assume, per-pod already-cached failures."""
        errors: List[Optional[str]] = [None] * len(pods)
        with self._lock:
            for i, pod in enumerate(pods):
                key = get_pod_key(pod)
                if key in self._pod_states:
                    errors[i] = (
                        f"pod {key} is in the cache, so can't be assumed"
                    )
                    continue
                self._mutation_seq += 1
                # bulk-commit assume: the solve carry already applied
                # this pod on device — the mirror must NOT re-scatter
                self._note("assume_bulk", pod)
                self._add_pod_locked(pod)
                self._pod_states[key] = _PodState(pod)
                self._assumed_pods.add(key)
        return errors

    def add_pods(self, pods: List[Pod]) -> None:
        """Bulk informer-confirmed adds under one lock (the batched
        bind-transition delivery): same per-pod semantics as add_pod."""
        with self._lock:
            for pod in pods:
                self._add_pod_confirmed_locked(pod)

    def _add_pod_confirmed_locked(self, pod: Pod) -> None:
        key = get_pod_key(pod)
        if key in self._assumed_pods:
            state = self._pod_states[key]
            if state.pod.spec.node_name != pod.spec.node_name:
                # scheduler result differs from api truth: relocate
                self._mutation_seq += 1
                self._note("pod_move", state.pod, pod)
                self._remove_pod_locked(state.pod)
                self._add_pod_locked(pod)
            self._assumed_pods.discard(key)
            self._pod_states[key] = _PodState(pod)
        elif key in self._pod_states:
            # duplicate add: treat as update
            self._mutation_seq += 1
            self._note("pod_update", self._pod_states[key].pod, pod)
            self._update_pod_locked(self._pod_states[key].pod, pod)
            self._pod_states[key] = _PodState(pod)
        else:
            self._mutation_seq += 1
            self._note("pod_add", pod)
            self._add_pod_locked(pod)
            self._pod_states[key] = _PodState(pod)

    def finish_binding_many(self, pods: List[Pod],
                            now: Optional[float] = None) -> None:
        """Bulk finish_binding under one lock: starts the assumed-pod TTL
        for every pod in the committed batch."""
        deadline = (now if now is not None else self._now()) + self._ttl
        with self._lock:
            for pod in pods:
                key = get_pod_key(pod)
                state = self._pod_states.get(key)
                if state is not None and key in self._assumed_pods:
                    state.binding_finished = True
                    state.deadline = deadline

    def finish_binding(self, pod: Pod, now: Optional[float] = None) -> None:
        key = get_pod_key(pod)
        with self._lock:
            state = self._pod_states.get(key)
            if state is not None and key in self._assumed_pods:
                state.binding_finished = True
                state.deadline = (now if now is not None else self._now()) + self._ttl

    def forget_pod(self, pod: Pod) -> None:
        key = get_pod_key(pod)
        with self._lock:
            if key not in self._assumed_pods:
                raise ValueError(f"pod {key} wasn't assumed, so can't be forgotten")
            self._mutation_seq += 1
            self._note("pod_del", self._pod_states[key].pod)
            self._remove_pod_locked(self._pod_states[key].pod)
            del self._pod_states[key]
            self._assumed_pods.discard(key)

    def add_pod(self, pod: Pod) -> None:
        """Informer-confirmed pod add (cache.go AddPod)."""
        with self._lock:
            self._add_pod_confirmed_locked(pod)

    def update_pod(self, old: Pod, new: Pod) -> None:
        key = get_pod_key(old)
        with self._lock:
            if key in self._assumed_pods:
                raise ValueError(f"assumed pod {key} shouldn't be updated")
            if _pod_mirror_changed(old, new):
                self._mutation_seq += 1
                self._note("pod_update", old, new)
            self._update_pod_locked(old, new)
            self._pod_states[key] = _PodState(new)

    def remove_pod(self, pod: Pod) -> None:
        with self._lock:
            self._remove_pod_confirmed_locked(pod)

    def remove_pods(self, pods: List[Pod]) -> None:
        """Bulk informer-confirmed deletes under one lock (the batched
        delete delivery): same per-pod semantics as remove_pod."""
        with self._lock:
            for pod in pods:
                self._remove_pod_confirmed_locked(pod)

    def _remove_pod_confirmed_locked(self, pod: Pod) -> None:
        key = get_pod_key(pod)
        state = self._pod_states.get(key)
        if state is None:
            return
        self._mutation_seq += 1
        self._note("pod_del", state.pod)
        self._remove_pod_locked(state.pod)
        del self._pod_states[key]
        self._assumed_pods.discard(key)

    def get_pod(self, pod: Pod) -> Optional[Pod]:
        with self._lock:
            state = self._pod_states.get(get_pod_key(pod))
            return state.pod if state else None

    def is_assumed_pod(self, pod: Pod) -> bool:
        with self._lock:
            return get_pod_key(pod) in self._assumed_pods

    def pod_count(self) -> int:
        with self._lock:
            return sum(len(item.info.pods) for item in self._nodes.values())

    def _add_pod_locked(self, pod: Pod) -> None:
        item = self._ensure_node(pod.spec.node_name)
        item.info.add_pod(pod)
        self._move_to_head(pod.spec.node_name)

    def _remove_pod_locked(self, pod: Pod) -> None:
        item = self._nodes.get(pod.spec.node_name)
        if item is not None:
            item.info.remove_pod(pod)
            if item.info.node is None and not item.info.pods:
                self._remove_from_list(pod.spec.node_name)
            else:
                self._move_to_head(pod.spec.node_name)

    def _update_pod_locked(self, old: Pod, new: Pod) -> None:
        self._remove_pod_locked(old)
        self._add_pod_locked(new)

    # ------------------------------------------------------------------
    # nodes
    def add_node(self, node: Node) -> None:
        with self._lock:
            self._mutation_seq += 1
            item = self._ensure_node(node.name)
            if item.info.node is None:
                self._node_set_seq += 1
                self._note("node_add", node)
            else:
                # re-add of a known node is an update in mirror terms
                self._note("node_update", item.info.node, node)
            self._remove_node_image_states(item.info.node)
            item.info.set_node(node)
            self._add_node_image_states(node, item.info)
            self._node_tree.add_node(node)
            self._move_to_head(node.name)

    def update_node(self, old: Node, new: Node) -> None:
        with self._lock:
            if _node_mirror_changed(old, new):
                self._mutation_seq += 1
                self._note("node_update", old, new)
            item = self._ensure_node(new.name)
            self._remove_node_image_states(item.info.node)
            item.info.set_node(new)
            self._add_node_image_states(new, item.info)
            self._node_tree.update_node(old, new)
            self._move_to_head(new.name)

    def remove_node(self, node: Node) -> None:
        with self._lock:
            item = self._nodes.get(node.name)
            if item is None:
                return
            self._mutation_seq += 1
            self._note("node_del", node)
            if item.info.node is not None:
                self._node_set_seq += 1
            item.info.remove_node()
            self._remove_node_image_states(node)
            # keep the entry while pods remain (they'll be removed by events)
            if not item.info.pods:
                self._remove_from_list(node.name)
            else:
                self._move_to_head(node.name)
            self._node_tree.remove_node(node)

    def node_count(self) -> int:
        with self._lock:
            return self._node_tree.num_nodes

    def _add_node_image_states(self, node: Node, ni: NodeInfo) -> None:
        summaries: Dict[str, _ImageState] = {}
        for img in node.status.images:
            for name in img.names:
                state = self._image_states.get(name)
                if state is None:
                    state = _ImageState(img.size_bytes)
                    self._image_states[name] = state
                state.size = img.size_bytes
                state.nodes.add(node.name)
                summaries[name] = state
        ni.image_states = summaries

    def _remove_node_image_states(self, node: Optional[Node]) -> None:
        if node is None:
            return
        for img in node.status.images:
            for name in img.names:
                state = self._image_states.get(name)
                if state is not None:
                    state.nodes.discard(node.name)
                    if not state.nodes:
                        del self._image_states[name]

    # ------------------------------------------------------------------
    # snapshot
    def update_snapshot(self, snapshot: Snapshot) -> None:
        """Incremental O(changed-nodes) update (cache.go:203-287): walk the
        generation-ordered list from the head, stop at the first item whose
        generation the snapshot has already seen."""
        with self._lock:
            balanced_generation = 0
            update_all_lists = False
            updated_affinity = False

            item = self._head
            while item is not None and item.info.generation > snapshot.generation:
                info = item.info
                name = info.node.name if info.node is not None else None
                if name is None:
                    item = item.next
                    continue
                if balanced_generation == 0:
                    # generation of the most recently updated node
                    balanced_generation = info.generation
                existing = snapshot.node_info_map.get(name)
                if existing is None:
                    update_all_lists = True
                    snapshot.node_info_map[name] = info.clone()
                else:
                    if (
                        bool(existing.pods_with_affinity)
                        != bool(info.pods_with_affinity)
                        or bool(existing.pods_with_required_anti_affinity)
                        != bool(info.pods_with_required_anti_affinity)
                    ):
                        updated_affinity = True
                    # copy IN PLACE: the snapshot's ordered lists hold the
                    # same NodeInfo objects as the map
                    existing.copy_from(info)
                item = item.next

            if balanced_generation:
                snapshot.generation = balanced_generation
            elif self._head is not None:
                snapshot.generation = max(
                    snapshot.generation, self._head.info.generation
                )

            # Reconcile deletions only when the snapshot can have shrunk
            # (cache.go guards with len(snapshot map) > nodeTree.numNodes —
            # a removal leaves the map larger than the live-node count, so
            # the common no-deletion cycle stays O(changed prefix)).
            if len(snapshot.node_info_map) > self._node_tree.num_nodes:
                live = {
                    n
                    for n, it in self._nodes.items()
                    if it.info.node is not None
                }
                for name in [n for n in snapshot.node_info_map if n not in live]:
                    del snapshot.node_info_map[name]
                update_all_lists = True

            if update_all_lists or updated_affinity or len(
                snapshot.node_info_list
            ) != len(snapshot.node_info_map):
                self._update_snapshot_lists(snapshot)

    def _update_snapshot_lists(self, snapshot: Snapshot) -> None:
        """Rebuild ordered lists in zone-interleaved node_tree order
        (cache.go:289 updateNodeInfoSnapshotList)."""
        order = self._node_tree.list()
        snapshot.node_info_list = [
            snapshot.node_info_map[n] for n in order if n in snapshot.node_info_map
        ]
        snapshot.have_pods_with_affinity_node_info_list = [
            ni for ni in snapshot.node_info_list if ni.pods_with_affinity
        ]
        snapshot.have_pods_with_required_anti_affinity_node_info_list = [
            ni for ni in snapshot.node_info_list if ni.pods_with_required_anti_affinity
        ]

    # ------------------------------------------------------------------
    # dump (debugger support) and expiry
    def dump(self):
        with self._lock:
            return {
                "nodes": {
                    n: item.info.clone() for n, item in self._nodes.items()
                },
                "assumed_pods": set(self._assumed_pods),
            }

    def run(self) -> None:
        """Start the assumed-pod expiry goroutine-equivalent (cache.go:42)."""
        if self._cleanup_thread is not None:
            return
        self._cleanup_thread = threading.Thread(
            target=self._cleanup_loop, daemon=True, name="cache-expiry"
        )
        self._cleanup_thread.start()

    def stop(self) -> None:
        self._stop.set()

    def _cleanup_loop(self) -> None:
        while not self._stop.wait(CLEANUP_INTERVAL):
            self.cleanup_expired_assumed_pods()

    def cleanup_expired_assumed_pods(self, now: Optional[float] = None) -> None:
        now = now if now is not None else self._now()
        with self._lock:
            for key in list(self._assumed_pods):
                state = self._pod_states.get(key)
                if state is None:
                    self._assumed_pods.discard(key)
                    continue
                if state.binding_finished and state.deadline is not None and now >= state.deadline:
                    # expire: the bind never became visible; undo the assume
                    self._mutation_seq += 1
                    self._note("pod_del", state.pod)
                    self._remove_pod_locked(state.pod)
                    del self._pod_states[key]
                    self._assumed_pods.discard(key)
