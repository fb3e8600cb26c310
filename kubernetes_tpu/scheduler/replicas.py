"""Multi-replica scheduler mode: M brains over one (partitioned) store.

The single-scheduler HA story (``run_with_leader_election``) keeps one
brain active and the rest warm — correct, but the active brain is still
the throughput ceiling. This module runs M brains CONCURRENTLY:

- **pod-hash sharding** (``shard_pods``): every pending pod hashes to
  exactly one replica's queue (crc32 of its uid/name — the same
  cross-process-stable hash family the store partitions use), so no two
  replicas ever race on the same pod. Assigned-pod events still feed
  every replica's cache: each brain sees the capacity its siblings
  consume, just one watch-propagation hop late.
- **node-pool sharding** (``shard_nodes``): optionally, each replica
  also caches a disjoint node pool — conflicts become impossible by
  construction (the measured scale row runs this shape; solving over
  nodes/M also keeps the encoded pod×node planes M× smaller).
- **optimistic conflict resolution on bind** (replicas sharing nodes):
  the commit-time guards arbitrate. Cache half:
  ``commit_capacity_guard`` probes ``SchedulerCache.commit_fits`` at
  commit so a fit a sibling consumed since the solve is refused and
  requeued (``stale_binds_rejected_total{path=capacity}``). Store
  half: the partitioned store's bind-time capacity ledger
  (``CapacityConflictError`` → ``path=bind_conflict``) and the
  same-pod bind CAS (``already assigned`` → ``path=replica_conflict``)
  reject the loser, whose commit unwinds through PR 3's
  unreserve/forget/requeue machinery — two brains cannot double-bind
  a pod or a node.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Callable, List, Optional

from kubernetes_tpu.scheduler.scheduler import Scheduler


def pod_shard_fn(index: int, count: int) -> Callable:
    """Queue-ownership predicate: does this pending pod hash to replica
    ``index``? Keyed by uid when present (stable across requeues), the
    full name otherwise."""

    def owns(pod) -> bool:
        key = pod.uid or f"{pod.namespace}/{pod.metadata.name}"
        return zlib.crc32(key.encode()) % count == index

    return owns


def node_shard_fn(index: int, count: int) -> Callable[[str], bool]:
    """Node-pool predicate: does this node belong to replica
    ``index``'s disjoint pool?"""

    def owns(name: str) -> bool:
        return zlib.crc32(name.encode()) % count == index

    return owns


@dataclass
class ReplicaSpec:
    """How one replica participates in the set. ``shard_pods=False``
    (every replica responsible for every pod) is the conflict-chaos
    configuration: replicas deliberately race, and the bind CAS +
    capacity guards must resolve every collision."""

    index: int
    count: int
    shard_pods: bool = True
    shard_nodes: bool = False
    capacity_guard: bool = True


def install_replica_sharding(sched: Scheduler, spec: ReplicaSpec) -> Scheduler:
    """Wire one scheduler instance into the replica set (idempotent;
    call before ``start()``/``run()`` so the initial replay is already
    filtered)."""
    sched.replica_name = f"replica-{spec.index}"
    if spec.count > 1 and spec.shard_pods:
        sched.pod_shard = pod_shard_fn(spec.index, spec.count)
    if spec.count > 1 and spec.shard_nodes:
        sched.node_shard = node_shard_fn(spec.index, spec.count)
    # the capacity guard matters exactly when replicas share nodes
    sched.commit_capacity_guard = bool(
        spec.capacity_guard and spec.count > 1 and not spec.shard_nodes)
    return sched


class SchedulerReplicaSet:
    """M concurrently-scheduling replicas. ``client_factory(i)`` builds
    each replica's client — over REST every replica needs its OWN
    partition-aware client (its own watch streams and token buckets);
    in-process replicas may all share the store."""

    def __init__(self, client_factory: Callable[[int], object],
                 count: int = 2, shard_pods: bool = True,
                 shard_nodes: bool = False, capacity_guard: bool = True,
                 use_batch: bool = False, max_batch: int = 4096,
                 provider: str = "GangSchedulingProvider",
                 event_client_factory: Optional[Callable] = None):
        from kubernetes_tpu.config.feature_gates import FeatureGates

        self.replicas: List[Scheduler] = []
        self.batch_schedulers: List[object] = []
        for i in range(count):
            sched = Scheduler.create(
                client_factory(i),
                feature_gates=FeatureGates(
                    {"TPUBatchScheduler": use_batch}),
                provider=provider,
                event_client=event_client_factory(i)
                if event_client_factory else None,
            )
            install_replica_sharding(sched, ReplicaSpec(
                index=i, count=count, shard_pods=shard_pods,
                shard_nodes=shard_nodes, capacity_guard=capacity_guard))
            if use_batch:
                from kubernetes_tpu.sidecar import attach_batch_scheduler

                self.batch_schedulers.append(
                    attach_batch_scheduler(sched, max_batch=max_batch))
            self.replicas.append(sched)

    def run(self) -> None:
        for sched in self.replicas:
            sched.run()

    def bound_count(self) -> int:
        """Pods the set has committed (sum of per-replica commit
        metrics — the same series the REST harness counts from)."""
        total = 0
        for sched in self.replicas:
            s = sched.metrics.e2e_scheduling_duration._series.get(
                ("scheduled",))
            total += s[2] if s else 0
        return total

    def wait_for_inflight_bindings(self, timeout: float = 30.0) -> None:
        for sched in self.replicas:
            sched.wait_for_inflight_bindings(timeout=timeout)

    def pending_count(self) -> int:
        return sum(s.queue.pending_active_count() for s in self.replicas)

    def stop(self) -> None:
        for sched in self.replicas:
            sched.stop()
