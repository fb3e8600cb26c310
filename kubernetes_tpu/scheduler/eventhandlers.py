"""Watch-event handlers: the state-ingestion path (reference
``pkg/scheduler/eventhandlers.go:364-467 addAllEventHandlers``): unassigned
pods feed the queue, assigned pods feed the cache (plus affinity wakeups),
and node/PV/PVC/Service/StorageClass/CSINode events trigger targeted queue
moves. Change-type detection for node updates mirrors
``nodeSchedulingPropertiesChange`` (:469)."""

from __future__ import annotations

from kubernetes_tpu.api.types import FAILED, SUCCEEDED, Node, Pod
from kubernetes_tpu.apiserver.store import ADDED, DELETED, MODIFIED, Event
from kubernetes_tpu.observability import get_tracer
from kubernetes_tpu.scheduler import events as ev
# gang (coscheduling) group label; a new member activates unschedulable
# siblings via the queue's gang wakeup
from kubernetes_tpu.scheduler.framework.plugins.coscheduling import (
    GROUP_NAME_LABEL as GANG_GROUP_LABEL,
)


def assigned(pod: Pod) -> bool:
    return bool(pod.spec.node_name)


def schedulable(pod: Pod) -> bool:
    """Mirrors the pod informer's field selector (scheduler.go:652-658):
    terminal-phase pods are invisible to the scheduler."""
    return pod.status.phase not in (SUCCEEDED, FAILED)


class EventHandlers:
    def __init__(self, scheduler):
        self.sched = scheduler

    def responsible_for(self, pod: Pod) -> bool:
        """Profile match + (multi-replica mode) pod-hash queue
        ownership: a pending pod belongs to exactly one replica's
        queue. Assigned-pod events are NOT filtered here — every
        replica caches every bound pod, whoever bound it, or the
        capacity its siblings consumed would be invisible."""
        if pod.spec.scheduler_name not in self.sched.profiles:
            return False
        shard = self.sched.pod_shard
        return shard is None or shard(pod)

    def caches_node(self, name: str) -> bool:
        """Node-pool sharding (multi-replica mode): a replica given a
        disjoint node pool caches — and therefore solves over — only
        its own nodes, so concurrent replicas cannot conflict on
        capacity by construction."""
        shard = self.sched.node_shard
        return shard is None or shard(name)

    # ------------------------------------------------------------------
    def handle_many(self, events) -> None:
        """Batched watch delivery (the store's ``_dispatch_many``): runs
        of homogeneous pod events collapse to one lock acquisition on the
        cache/queue side, while ordering relative to any other event kind
        is preserved by flushing the pending run first. The two runs that
        matter at throughput scale are bind transitions (commit) and
        unassigned adds (admission)."""
        sched = self.sched
        # snapshot-staleness anchor: one max() over the batch, recorded
        # only AFTER the whole batch is applied (below) — "newest event
        # reflected" must never run ahead of what the cache holds
        newest = max((e.ts for e in events if getattr(e, "ts", 0.0)),
                     default=0.0)
        bind_run = []    # Pods newly assigned (MODIFIED, old unassigned)
        add_run = []     # unassigned schedulable ADDED pods
        delete_run = []  # assigned DELETED pods (mass preemption)
        node_run = []    # ADDED nodes (relist replay / mass registration)

        def flush():
            if node_run:
                # a relist replaying N nodes must cost ONE queue wakeup,
                # not N move-alls over every pending pod
                for n in node_run:
                    sched.cache.add_node(n)
                sched.queue.move_all_to_active_or_backoff_queue(
                    ev.NODE_ADD
                )
                node_run.clear()
            if bind_run:
                sched.cache.add_pods(bind_run)
                sched.queue.delete_many(bind_run)
                sched.queue.assigned_pods_updated(bind_run)
                bind_run.clear()
            if add_run:
                sched.queue.add_many(add_run)
                groups = {
                    g for p in add_run
                    if (g := p.metadata.labels.get(GANG_GROUP_LABEL))
                }
                sched.queue.gang_members_added(groups)
                add_run.clear()
            if delete_run:
                with get_tracer().span("cache.remove_pods",
                                       pods=len(delete_run)):
                    sched.cache.remove_pods(delete_run)
                for p in delete_run:
                    if p.metadata.labels.get(GANG_GROUP_LABEL):
                        for fwk in sched.profiles.values():
                            gang = fwk.get_plugin("Coscheduling")
                            if gang is not None:
                                gang.note_member_deleted(p)
                # ONE wake-up for the whole run: a per-victim move-all
                # is what made bulk preemption O(victims x pending)
                sched.queue.move_all_to_active_or_backoff_queue(
                    ev.ASSIGNED_POD_DELETE
                )
                delete_run.clear()

        runs = (bind_run, add_run, delete_run, node_run)

        def run_for(target):
            if any(r for r in runs if r is not target):
                flush()
            return target

        for event in events:
            if event.kind == "Node" and event.type == ADDED:
                if not self.caches_node(event.obj.name):
                    continue   # another replica's node pool
                run_for(node_run).append(event.obj)
                continue
            if event.kind == "Pod":
                pod = event.obj
                if (
                    event.type == MODIFIED
                    and assigned(pod)
                    and event.old_obj is not None
                    and not assigned(event.old_obj)
                ):
                    run_for(bind_run).append(pod)
                    continue
                if (
                    event.type == ADDED
                    and not assigned(pod)
                    and schedulable(pod)
                    and self.responsible_for(pod)
                ):
                    run_for(add_run).append(pod)
                    continue
                if event.type == DELETED and assigned(pod):
                    run_for(delete_run).append(pod)
                    continue
            flush()
            self._handle_one(event)
        flush()
        if newest:
            sched.cache.note_event_ts(newest)

    def handle(self, event: Event) -> None:
        self._handle_one(event)
        ts = getattr(event, "ts", 0.0)
        if ts:
            self.sched.cache.note_event_ts(ts)

    def _handle_one(self, event: Event) -> None:
        kind = event.kind
        if kind == "Pod":
            self._handle_pod(event)
        elif kind == "Node":
            self._handle_node(event)
        elif kind == "Service":
            self._move(event, {
                ADDED: ev.SERVICE_ADD, MODIFIED: ev.SERVICE_UPDATE,
                DELETED: ev.SERVICE_DELETE,
            })
        elif kind == "PersistentVolume":
            self._storage_mutated()
            self._move(event, {ADDED: ev.PV_ADD, MODIFIED: ev.PV_UPDATE})
        elif kind == "PersistentVolumeClaim":
            self._storage_mutated()
            self._move(event, {ADDED: ev.PVC_ADD, MODIFIED: ev.PVC_UPDATE})
        elif kind == "StorageClass":
            self._storage_mutated()
            self._move(event, {ADDED: ev.STORAGE_CLASS_ADD})
        elif kind == "CSINode":
            self._storage_mutated()
            self._move(event, {ADDED: ev.CSI_NODE_ADD, MODIFIED: ev.CSI_NODE_UPDATE})

    def _storage_mutated(self) -> None:
        """Storage objects (PV/PVC/StorageClass/CSINode) feed the batch
        sidecar's device mirror (volume masks, attach columns); ANY
        mutation — including DELETED, which has no queue-move event
        (deletion never helps a pending pod) — must invalidate the
        mirror like a cache mutation would. Services are excluded: the
        encoder reads no Service state."""
        self.sched.cache.note_external_mutation()

    def _move(self, event: Event, mapping) -> None:
        name = mapping.get(event.type)
        if name:
            self.sched.queue.move_all_to_active_or_backoff_queue(name)

    # ------------------------------------------------------------------
    def _handle_pod(self, event: Event) -> None:
        sched = self.sched
        pod: Pod = event.obj
        old: Pod = event.old_obj

        if event.type == ADDED:
            if assigned(pod):
                sched.cache.add_pod(pod)
                sched.queue.assigned_pod_added(pod)
            elif schedulable(pod) and self.responsible_for(pod):
                sched.queue.add(pod)
                group = pod.metadata.labels.get(GANG_GROUP_LABEL)
                if group:
                    sched.queue.gang_members_added({group})
        elif event.type == MODIFIED:
            if assigned(pod):
                if old is not None and not assigned(old):
                    # bind transition: confirm the assume, leave the queue
                    sched.cache.add_pod(pod)
                    sched.queue.delete(pod)
                else:
                    sched.cache.update_pod(old or pod, pod)
                sched.queue.assigned_pod_updated(pod)
            elif schedulable(pod) and self.responsible_for(pod):
                if not self._skip_pod_update(old, pod):
                    sched.queue.update(old, pod)
        elif event.type == DELETED:
            if assigned(pod):
                sched.cache.remove_pod(pod)
                sched.queue.move_all_to_active_or_backoff_queue(
                    ev.ASSIGNED_POD_DELETE
                )
                # a deleted bound gang member releases its Permit
                # arrival slot (a re-created gang must re-gate)
                if pod.metadata.labels.get(GANG_GROUP_LABEL):
                    for fwk in sched.profiles.values():
                        gang = fwk.get_plugin("Coscheduling")
                        if gang is not None:
                            gang.note_member_deleted(pod)
            else:
                sched.queue.delete(pod)
                # a Permit-parked pod must be rejected so its assumed
                # resources and gang slot are released (reference
                # deletePodFromSchedulingQueue → fwk.RejectWaitingPod)
                for fwk in sched.profiles.values():
                    fwk.reject_waiting_pod(pod.uid)

    def _skip_pod_update(self, old: Pod, new: Pod) -> bool:
        """Reference skipPodUpdate: an update to an *assumed* pod that only
        touches server-side fields must not churn the queue."""
        if old is None:
            return False
        if not self.sched.cache.is_assumed_pod(new):
            return False
        return (
            old.spec == new.spec
            and old.metadata.labels == new.metadata.labels
        )

    # ------------------------------------------------------------------
    def _handle_node(self, event: Event) -> None:
        sched = self.sched
        node: Node = event.obj
        old: Node = event.old_obj
        if not self.caches_node(node.name):
            return   # another replica's node pool (multi-replica mode)
        if event.type == ADDED:
            sched.cache.add_node(node)
            sched.queue.move_all_to_active_or_backoff_queue(ev.NODE_ADD)
        elif event.type == MODIFIED:
            sched.cache.update_node(old or node, node)
            change = self._node_scheduling_properties_change(old, node)
            if change:
                sched.queue.move_all_to_active_or_backoff_queue(change)
        elif event.type == DELETED:
            sched.cache.remove_node(node)

    @staticmethod
    def _node_scheduling_properties_change(old: Node, new: Node):
        """eventhandlers.go:469: only changes that could make pending pods
        schedulable wake the queue."""
        if old is None:
            return ev.NODE_ADD
        if old.spec.unschedulable != new.spec.unschedulable:
            return ev.NODE_SPEC_UNSCHEDULABLE_CHANGE
        if old.status.allocatable != new.status.allocatable:
            return ev.NODE_ALLOCATABLE_CHANGE
        if old.metadata.labels != new.metadata.labels:
            return ev.NODE_LABEL_CHANGE
        if old.spec.taints != new.spec.taints:
            return ev.NODE_TAINT_CHANGE
        return None
