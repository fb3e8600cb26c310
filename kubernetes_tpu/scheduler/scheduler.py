"""The scheduler: configurator + the scheduleOne loop.

Behavioral equivalent of the reference's ``pkg/scheduler/scheduler.go``
(Scheduler struct :61-88, Run :311-315, scheduleOne :427-600, assume :359,
bind :381, skipPodSchedule :620) and ``factory.go`` (Configurator :90-184,
MakeDefaultErrorFunc :316-362). One pod per cycle: Pop → Schedule → assume →
Reserve → Permit → async binding cycle; failures re-queue through the
error function with the moveRequestCycle protocol.

The TPU batch path (``kubernetes_tpu.sidecar``) plugs in behind the
``TPUBatchScheduler`` feature gate: when enabled the loop drains pod
*batches* and delegates assignment to the device solver, falling back to
this serial path whenever the sidecar declines a pod (clean fallback, like
an ``IsIgnorable`` extender — SURVEY.md section 5).
"""

from __future__ import annotations

import logging
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional

from kubernetes_tpu.api.types import (
    NO_SCHEDULE,
    TAINT_NODE_UNREACHABLE,
    TAINT_NODE_UNSCHEDULABLE,
    Node,
    Pod,
    PodCondition,
    Taint,
    shallow_copy,
)
from kubernetes_tpu.apiserver.store import ClusterStore
from kubernetes_tpu.config.feature_gates import FeatureGates
from kubernetes_tpu.config.types import KubeSchedulerConfiguration
from kubernetes_tpu.metrics import SchedulerMetrics
from kubernetes_tpu.observability import get_tracer
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.core import GenericScheduler, ScheduleResult
from kubernetes_tpu.scheduler.eventhandlers import EventHandlers, assigned
from kubernetes_tpu.scheduler.extender import HTTPExtender
from kubernetes_tpu.scheduler.framework import interface as fw
from kubernetes_tpu.scheduler.framework.cycle_state import CycleState
from kubernetes_tpu.scheduler.framework.plugins import new_in_tree_registry
from kubernetes_tpu.scheduler.framework.runtime import Framework, Registry
from kubernetes_tpu.scheduler.provider import PROVIDERS
from kubernetes_tpu.scheduler.queue import SchedulingQueue
from kubernetes_tpu.scheduler.types import PodInfo, QueuedPodInfo
from kubernetes_tpu.utils.clock import RealClock

PLUGIN_METRICS_SAMPLE_PERCENT = 10  # scheduler.go:56

_logger = logging.getLogger("kubernetes_tpu.scheduler")


def commit_target_stale(pod: Pod, node: Optional[Node]) -> Optional[str]:
    """Commit-time stale-node verdict for one (pod, flagged node) pair:
    the reason string when binding ``pod`` there would bind into the
    void, None when the pod may proceed (e.g. it tolerates the taint).
    ``node`` comes from ``SchedulerCache.commit_target_flags`` — None
    means the node vanished from the cache between snapshot and commit.
    Only called for flagged nodes, so the toleration scans here are off
    the no-churn hot path entirely."""
    if node is None:
        return "deleted"
    tolerations = pod.spec.tolerations
    if node.spec.unschedulable:
        cordon = Taint(TAINT_NODE_UNSCHEDULABLE, "", NO_SCHEDULE)
        if not any(t.tolerates(cordon) for t in tolerations):
            return "cordoned"
    for taint in node.spec.taints:
        if taint.key != TAINT_NODE_UNREACHABLE:
            continue
        if not any(t.tolerates(taint) for t in tolerations):
            return "unreachable"
    return None


class _Deps:
    """The Handle dependency bundle shared by all profile frameworks."""

    def __init__(self, scheduler: "Scheduler"):
        self._scheduler = scheduler
        self.parallelizer = None  # set by configurator

    def snapshot(self):
        return self._scheduler.algorithm.snapshot

    @property
    def client(self) -> ClusterStore:
        return self._scheduler.client

    @property
    def pod_nominator(self):
        return self._scheduler.queue

    @property
    def feature_gates(self) -> FeatureGates:
        return self._scheduler.feature_gates

    @property
    def extenders(self):
        return self._scheduler.algorithm.extenders

    @property
    def event_recorder(self):
        """The profile's EventRecorder (reference Handle.EventRecorder)."""
        return self._scheduler.recorder


class Scheduler:
    def __init__(
        self,
        client: ClusterStore,
        cache: SchedulerCache,
        queue: SchedulingQueue,
        profiles: Dict[str, Framework],
        algorithm: GenericScheduler,
        feature_gates: FeatureGates,
        metrics: SchedulerMetrics,
        clock=None,
        event_client=None,
    ):
        self.client = client
        self.cache = cache
        self.queue = queue
        self.profiles = profiles
        self.algorithm = algorithm
        self.feature_gates = feature_gates
        self.metrics = metrics
        self.clock = clock or RealClock()
        self._stop = threading.Event()
        self._bind_pool = ThreadPoolExecutor(max_workers=64,
                                             thread_name_prefix="binder")
        self._inflight_bindings = 0
        self._inflight_lock = threading.Lock()
        self._inflight_zero = threading.Condition(self._inflight_lock)
        self.batch_scheduler = None  # set by kubernetes_tpu.sidecar when gated on
        self._watch_handle = None
        # degraded mode: set while the client's circuit breaker is open
        # (apiserver unreachable). Binding pauses — the loop stops
        # popping — while watch ingestion keeps the cache warm; in-flight
        # binding cycles fail against the dead server and requeue through
        # the normal error function. Recovery clears the flag and wakes
        # every parked pod.
        self._degraded = threading.Event()
        self._degraded_since = 0.0
        self._degraded_lock = threading.Lock()
        self.event_handlers = EventHandlers(self)
        from kubernetes_tpu.client.events import EventRecorder

        # events flow through their OWN client when one is provided
        # (reference: the scheduler's EventBroadcaster writes through a
        # separate events client with its own rate limit — kube-
        # scheduler's eventClient in cmd/kube-scheduler/app/options).
        # Over REST this matters: 30k "Scheduled" events sharing the
        # bind client's token bucket would silently halve the bind
        # budget the reference never charges.
        self.recorder = EventRecorder(event_client or client,
                                      "default-scheduler")
        # bulk binds go async (the serial path's binding-goroutine
        # model, applied to whole batches) when the client is remote:
        # a REST round trip on the commit path would serialize every
        # batch cycle on wire latency. In-process stores bind inline —
        # same call, microseconds, and tests see bound pods
        # synchronously.
        self.async_bulk_bind = hasattr(client, "breaker")
        # cache mutations performed by the LAST commit_assignments_bulk
        # call (assumes + sync forgets): the sidecar's device-mirror
        # accounting needs the true count — gang members parked at
        # Permit are assumed but not committed, and counting only
        # commits made every gang batch invalidate the session (the
        # r5 state-only-rebuild-per-batch churn).
        self.last_bulk_commit_mutations = 0
        # -- multi-replica mode (scheduler/replicas.py installs these):
        # pod_shard(pod)->bool decides queue ownership (pod-hash
        # sharding: each pending pod belongs to exactly one replica);
        # node_shard(name)->bool restricts this replica's cache to a
        # disjoint node pool; commit_capacity_guard adds a commit-time
        # cache capacity probe (the optimistic-conflict guard for
        # replicas sharing ALL nodes — a sibling's binds land in this
        # cache via watch events, so a fit that evaporated since the
        # solve is refused and requeued instead of oversubscribing).
        self.pod_shard: Optional[Callable[[Pod], bool]] = None
        self.node_shard: Optional[Callable[[str], bool]] = None
        self.commit_capacity_guard = False
        self.replica_name = ""

    # ------------------------------------------------------------------
    @classmethod
    def create(
        cls,
        client: ClusterStore,
        config: Optional[KubeSchedulerConfiguration] = None,
        out_of_tree_registry: Optional[Registry] = None,
        provider: str = "DefaultProvider",
        feature_gates: Optional[FeatureGates] = None,
        metrics: Optional[SchedulerMetrics] = None,
        clock=None,
        event_client=None,
    ) -> "Scheduler":
        """The Configurator (factory.go:90-184 create/createFromProvider)."""
        config = config or KubeSchedulerConfiguration()
        errs = config.validate()
        if errs:
            raise ValueError("invalid scheduler configuration: " + "; ".join(errs))
        feature_gates = feature_gates or FeatureGates(config.feature_gates)
        metrics = metrics or SchedulerMetrics()
        cache = SchedulerCache()
        extenders = [HTTPExtender(e) for e in config.extenders]
        algorithm = GenericScheduler(
            cache,
            extenders=extenders,
            percentage_of_nodes_to_score=config.percentage_of_nodes_to_score,
            feature_gates=feature_gates,
        )

        registry = new_in_tree_registry()
        if out_of_tree_registry:
            registry.merge(out_of_tree_registry)
        default_plugins = PROVIDERS[provider](feature_gates)

        # fully initialize the scheduler BEFORE running plugin factories:
        # factories legitimately touch handle.client / pod_nominator
        # (reference NewFramework receives a working handle). The queue is
        # created first with the default less-func and rewired below —
        # it is empty until start(), so the swap is safe.
        queue = SchedulingQueue(
            clock=clock,
            pod_initial_backoff=config.pod_initial_backoff_seconds,
            pod_max_backoff=config.pod_max_backoff_seconds,
            metrics=metrics,
        )
        sched = cls(
            client, cache, queue, {}, algorithm,
            feature_gates, metrics, clock=clock, event_client=event_client,
        )
        deps = _Deps(sched)
        from kubernetes_tpu.utils.parallelize import Parallelizer

        deps.parallelizer = Parallelizer(config.parallelism)

        for profile in config.profiles:
            sched.profiles[profile.scheduler_name] = Framework(
                registry, profile, default_plugins, deps=deps, metrics=metrics
            )

        # all profiles must share the queue-sort function (profile.go:52)
        less_fns = {
            tuple(p.list_plugins()["queue_sort"])
            for p in sched.profiles.values()
        }
        if len(less_fns) != 1:
            raise ValueError("all profiles must use the same QueueSort plugin")
        any_profile = next(iter(sched.profiles.values()))
        queue._active_q._less = any_profile.queue_sort_less
        queue.sort_key = any_profile.queue_sort_key
        if queue.sort_key is not None:
            queue._active_q.set_sort_key(queue.sort_key)
        return sched

    # ------------------------------------------------------------------
    def start(self) -> None:
        """Wire event handlers and start background machinery (the
        informer-start + queue.Run portion of Run, scheduler.go:311)."""
        if self._watch_handle is None:
            self._watch_handle = self.client.watch(
                self.event_handlers.handle,
                batch_fn=self.event_handlers.handle_many,
            )
        # remote clients expose a circuit breaker; wire it to degraded
        # mode (the in-process store has no transport to lose)
        set_listener = getattr(self.client, "set_degraded_listener", None)
        if set_listener is not None:
            set_listener(self.set_degraded)
        # replay current state (the initial List of ListAndWatch)
        for node in self.client.list_nodes():
            if self.node_shard is not None and \
                    not self.node_shard(node.name):
                continue
            self.cache.add_node(node)
        for pod in self.client.list_pods():
            if assigned(pod):
                self.cache.add_pod(pod)
            elif self.event_handlers.responsible_for(pod):
                self.queue.add(pod)
        self.cache.run()
        self.queue.run()
        self.recorder.start()

    def run(self) -> threading.Thread:
        """Run the scheduling loop in a thread; returns it."""
        self.start()
        t = threading.Thread(target=self._loop, daemon=True, name="scheduleOne")
        t.start()
        return t

    def run_with_leader_election(self, identity: str = "scheduler-0",
                                 lease_name: str = "kube-scheduler",
                                 clock=None, lease_duration: float = 15.0,
                                 renew_deadline: float = 10.0,
                                 retry_period: float = 2.0):
        """HA wiring (cmd/kube-scheduler/app/server.go:199-208): informers
        start and caches sync BEFORE the election (a standby keeps warm
        state); only the lease holder runs the scheduling loop; losing
        the lease stops this scheduler for good — the reference
        ``klog.Fatalf``s there (server.go:205), because a deposed leader
        must never keep binding against a store another instance now
        owns. Returns the LeaderElector (``.is_leader`` for observers).
        """
        from kubernetes_tpu.client.leaderelection import (
            LeaderElectionConfig,
            LeaderElector,
        )

        self.start()
        self.lost_lease = False

        def on_started() -> None:
            threading.Thread(target=self._loop, daemon=True,
                             name=f"scheduleOne-{identity}").start()

        def on_stopped() -> None:
            # fatal-style: no re-acquire, no second loop
            self.elector.stop()
            if not self._stop.is_set():
                self.lost_lease = True
                self.stop()

        cfg = LeaderElectionConfig(
            lock_name=lease_name,
            identity=identity,
            lease_duration=lease_duration,
            renew_deadline=renew_deadline,
            retry_period=retry_period,
            on_started_leading=on_started,
            on_stopped_leading=on_stopped,
        )
        self.elector = LeaderElector(self.client, cfg, clock=clock)
        self.elector.run_in_thread()
        return self.elector

    def _loop(self) -> None:
        import logging

        logger = logging.getLogger("kubernetes_tpu.scheduler")
        while not self._stop.is_set():
            try:
                if self.batch_scheduler is not None:
                    self.batch_scheduler.run_batch(pop_timeout=0.2)
                else:
                    self.schedule_one(pop_timeout=0.2)
            except Exception:  # noqa: BLE001 — the loop must survive
                logger.exception("scheduling cycle failed; continuing")

    def stop(self) -> None:
        self._stop.set()
        # a stopped scheduler must release leadership: keeping the lease
        # renewing would block standby failover forever
        elector = getattr(self, "elector", None)
        if elector is not None:
            elector.stop()
        self.queue.close()
        self.cache.stop()
        if self._watch_handle is not None:
            self._watch_handle.stop()
            self._watch_handle = None
        self.recorder.stop()
        self._bind_pool.shutdown(wait=False)

    # -- degraded mode -------------------------------------------------
    def is_degraded(self) -> bool:
        return self._degraded.is_set()

    def set_degraded(self, degraded: bool) -> None:
        """Flip degraded mode (idempotent; the client's circuit-breaker
        listener). Entering pauses binding — new pops stop, in-flight
        binds fail-and-requeue on their own. Leaving accounts the
        outage into ``degraded_mode_seconds`` and moves every parked
        pod back to active so recovery is immediate, not
        backoff-delayed."""
        from kubernetes_tpu.metrics.fabric_metrics import fabric_metrics

        with self._degraded_lock:
            if degraded == self._degraded.is_set():
                return
            if degraded:
                self._degraded_since = time.monotonic()
                self._degraded.set()
                fabric_metrics().degraded_mode.set(1.0)
            else:
                self._degraded.clear()
                elapsed = time.monotonic() - self._degraded_since
                fabric_metrics().degraded_mode.set(0.0)
                fabric_metrics().degraded_mode_seconds.inc(amount=elapsed)
        if degraded:
            # outside the lock (dump is disk I/O — a recovery flip must
            # not wait on it): postmortem-before-the-mortem — degraded
            # entry means the apiserver is unreachable and a crash may
            # follow, so flush the flight recorder NOW (best-effort)
            tracer = get_tracer()
            tracer.event("sched.degraded_enter")
            if tracer.enabled and len(tracer):
                tracer.dump(reason="degraded", min_interval_s=5.0)
            return
        # outside the lock: queue wakeup can take the queue lock
        from kubernetes_tpu.scheduler import events as ev

        self.queue.move_all_to_active_or_backoff_queue(ev.CLIENT_RECOVERED)

    def wait_for_inflight_bindings(self, timeout: float = 30.0) -> bool:
        with self._inflight_zero:
            deadline = time.monotonic() + timeout
            while self._inflight_bindings > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._inflight_zero.wait(remaining)
            return True

    # ------------------------------------------------------------------
    def framework_for_pod(self, pod: Pod) -> Framework:
        fwk = self.profiles.get(pod.spec.scheduler_name)
        if fwk is None:
            raise KeyError(
                f"profile not found for scheduler name {pod.spec.scheduler_name!r}"
            )
        return fwk

    def skip_pod_schedule(self, fwk: Framework, pod: Pod) -> bool:
        """scheduler.go:620: deleting pods and already-assumed pods skip."""
        if pod.metadata.deletion_timestamp is not None:
            return True
        if self.cache.is_assumed_pod(pod):
            return True
        if assigned(pod):
            return True
        return False

    # ------------------------------------------------------------------
    def schedule_one(self, pop_timeout: Optional[float] = None) -> bool:
        """One scheduling cycle (scheduler.go:427). Returns False when the
        queue yielded nothing."""
        if self._degraded.is_set():
            # circuit open: binding is paused. Don't pop — a popped pod
            # would only fail its bind and burn a backoff round.
            time.sleep(min(pop_timeout or 0.05, 0.05))
            return False
        qpi = self.queue.pop(timeout=pop_timeout)
        if qpi is None:
            return False
        pod = qpi.pod
        try:
            fwk = self.framework_for_pod(pod)
        except KeyError:
            return True
        if self.skip_pod_schedule(fwk, pod):
            return True

        self.schedule_pod_serial(fwk, qpi)
        return True

    def schedule_pod_serial(self, fwk: Framework, qpi: QueuedPodInfo) -> None:
        """The serial algorithm + commit for one popped pod (the body of
        scheduleOne). Also the fallback path for pods the batch solver
        declines."""
        pod = qpi.pod
        pod_scheduling_cycle = self.queue.scheduling_cycle
        start = time.monotonic()
        state = CycleState()
        state.record_plugin_metrics = (
            random.randrange(100) < PLUGIN_METRICS_SAMPLE_PERCENT
        )

        try:
            result = self.algorithm.schedule(state, fwk, pod)
        except fw.FitError as fit_err:
            self._handle_fit_error(fwk, state, qpi, fit_err, pod_scheduling_cycle)
            self.metrics.schedule_attempts.inc("unschedulable", fwk.profile_name)
            return
        except Exception as err:  # noqa: BLE001 - mirrors the error func path
            self._record_failure(fwk, qpi, err, "SchedulerError", "",
                                 pod_scheduling_cycle)
            self.metrics.schedule_attempts.inc("error", fwk.profile_name)
            return

        self.metrics.scheduling_algorithm_duration.observe(time.monotonic() - start)
        self.commit_assignment(fwk, state, qpi, result, pod_scheduling_cycle,
                               start)

    def fail_unschedulable(self, fwk: Framework, qpi: QueuedPodInfo,
                           fit_err: "fw.FitError", cycle: int,
                           candidate_hints=None,
                           run_post_filter: bool = True) -> None:
        """Record an unschedulable outcome decided OUTSIDE the serial
        algorithm (the batch solver's declined pods): same PostFilter/
        preemption + requeue flow as the serial FitError branch, without
        re-running the full filter chain the device already evaluated.
        PreFilter still runs: preemption's dry-run re-executes Filter
        plugins against the CycleState, which must carry their
        PreFilter-computed data. ``candidate_hints`` (ranked node names
        from the batch preemption screen) bound the dry-run's candidate
        scan; the dry-run revalidates every hinted node.
        ``run_post_filter=False`` skips preemption when the caller has
        already proven it can't help (every node static-infeasible —
        nodesWherePreemptionMightHelp would be empty)."""
        state = CycleState()
        if run_post_filter and fwk.has_post_filter_plugins():
            # the serial path refreshes the snapshot inside Schedule; here
            # the device solve may have ridden the incremental mirror, so
            # the snapshot the preemption dry-run (and PreFilter) reads
            # could predate this epoch's commits — refresh (O(changed))
            self.algorithm.update_snapshot()
            fwk.run_pre_filter_plugins(state, qpi.pod)
            if candidate_hints is not None:
                from kubernetes_tpu.scheduler.framework.plugins import (
                    default_preemption as dp,
                )

                state.write(dp.DefaultPreemption.HINTS_KEY, candidate_hints)
            self._handle_fit_error(fwk, state, qpi, fit_err, cycle)
        else:
            self._record_failure(fwk, qpi, fit_err, "Unschedulable", "",
                                 cycle)
        self.metrics.schedule_attempts.inc("unschedulable", fwk.profile_name)

    def commit_assignment(
        self,
        fwk: Framework,
        state: CycleState,
        qpi: QueuedPodInfo,
        result: ScheduleResult,
        pod_scheduling_cycle: int,
        start: float,
        sync_bind: bool = False,
    ) -> bool:
        """assume → Reserve → Permit → (async) binding cycle — the commit
        half of scheduleOne, shared by the serial and batch paths.

        Returns True only when the pod was fully committed in this call
        (synchronous bind reached PostBind). Async commits return False;
        callers that must know (the batch session's device-state
        accounting) use sync_bind."""
        pod = qpi.pod
        # stale-node guard (chaos_nodes): the algorithm ran against a
        # snapshot that may predate a node death/cordon/unreachable
        # taint — binding there would bind into the void (the store
        # accepts binds to nonexistent nodes). One cache probe per
        # commit; requeue through the normal error function.
        flagged = self.cache.commit_target_flags((result.suggested_host,))
        if flagged:
            reason = commit_target_stale(pod, flagged[result.suggested_host])
            if reason is not None:
                self._reject_stale_commit(
                    fwk, qpi, result.suggested_host, reason, "serial",
                    pod_scheduling_cycle)
                return False
        if self.commit_capacity_guard and self.cache.commit_fits(
                ((pod, result.suggested_host),))[0] is not None:
            # multi-replica optimistic conflict: a sibling's binds
            # (applied to this cache via watch events) consumed the
            # capacity this solve counted on — refuse and requeue, the
            # next attempt solves against the post-conflict world
            self._reject_stale_commit(
                fwk, qpi, result.suggested_host,
                "out of capacity (concurrent replica commits)",
                "capacity", pod_scheduling_cycle)
            return False
        # assume: tell the cache the pod is (going to be) bound (scheduler.go:359)
        assumed_pod = shallow_copy(pod)
        assumed_pod.spec = shallow_copy(pod.spec)
        assumed_pod.spec.node_name = result.suggested_host
        # reuse the queue's parse — the copy differs only in nodeName
        PodInfo.derived(assumed_pod, qpi.pod_info)
        try:
            self.cache.assume_pod(assumed_pod)
        except ValueError as err:
            self._record_failure(fwk, qpi, err, "SchedulerError", "",
                                 pod_scheduling_cycle)
            return False
        self.queue.delete_nominated_pod_if_exists(pod)

        # Reserve
        status = fwk.run_reserve_plugins_reserve(state, assumed_pod,
                                                result.suggested_host)
        if not fw.Status.is_ok(status):
            self._forget_and_fail(fwk, state, qpi, assumed_pod, result,
                                  status.as_error(), pod_scheduling_cycle)
            return False

        # Permit
        status = fwk.run_permit_plugins(state, assumed_pod, result.suggested_host)
        if status is not None and status.code not in (fw.SUCCESS, fw.WAIT):
            self._unreserve_forget_fail(fwk, state, qpi, assumed_pod, result,
                                        status.as_error(), pod_scheduling_cycle)
            return False

        with self._inflight_lock:
            self._inflight_bindings += 1
        self.metrics.goroutines.inc("binding")
        if sync_bind and status is None:
            # batch path: bindings are in-process; skipping the thread
            # hop roughly halves per-pod commit cost
            return self._binding_cycle(fwk, state, qpi, assumed_pod, result,
                                       pod_scheduling_cycle, start)
        else:
            # binding cycle runs async (scheduler.go:540): the loop continues
            try:
                self._bind_pool.submit(
                    self._binding_cycle, fwk, state, qpi, assumed_pod,
                    result, pod_scheduling_cycle, start,
                )
            except RuntimeError:
                # pool already shut down (stop() raced a late commit):
                # release the in-flight slot; the pod's state dies with
                # this scheduler instance
                self.metrics.goroutines.dec("binding")
                with self._inflight_zero:
                    self._inflight_bindings -= 1
                    if self._inflight_bindings == 0:
                        self._inflight_zero.notify_all()
        return False

    def commit_assignments_bulk(
        self, fwk: Framework, commits: List[tuple]
    ) -> tuple:
        """Commit a whole solved batch: the semantics of N
        ``commit_assignment(..., sync_bind=True)`` calls with the
        per-pod O(lock + dispatch) overheads amortized — bulk assume
        (one cache lock), bulk bind (one store lock + one batched watch
        delivery), bulk finish-binding. Every per-pod framework hook
        (Reserve, Permit, WaitOnPermit, PreBind, PostBind) still runs
        per pod in order; pods whose Permit returns WAIT drop to the
        async binding cycle exactly as in the serial path.

        ``commits``: list of (qpi, result, cycle, start). Returns
        (committed, failed) where failed counts pods that were rejected
        host-side after the device counted them (the caller's
        device-mirror accounting needs to know).

        Side channel: ``self.last_bulk_commit_mutations`` is set to the
        number of cache mutations THIS call performed synchronously
        (one assume per pod that passed the stale guard, plus one
        forget per sync rejection) — the sidecar validates its device
        mirror against this count, so pods parked at Permit (gangs)
        count via their assume even though they bind asynchronously.

        When ``self.async_bulk_bind`` is set (remote clients), the
        final bulk Bind ships on the binding pool instead of blocking
        this call — the batch loop must not serialize every cycle on a
        wire round trip. Failures there unreserve/forget/requeue
        exactly as the sync path would, just later; the extra forget
        invalidates the device mirror through the normal arithmetic."""
        # --- stale-node guard (chaos_nodes): ONE cache probe for the
        # whole batch; assignments targeting nodes that died, were
        # cordoned, or went unreachable since the solve are refused
        # before assume and requeued — never bound into the void.
        flagged = self.cache.commit_target_flags(
            {r.suggested_host for _, r, _, _ in commits}
        ) if commits else {}
        stale_failed = 0
        if flagged:
            live_commits: List[tuple] = []
            for item in commits:
                qpi, result, cycle, _start = item
                node = flagged.get(result.suggested_host, False)
                reason = commit_target_stale(qpi.pod, node) \
                    if node is not False else None
                if reason is None:
                    live_commits.append(item)
                else:
                    self._reject_stale_commit(
                        fwk, qpi, result.suggested_host, reason, "bulk",
                        cycle)
                    stale_failed += 1
            commits = live_commits
        if self.commit_capacity_guard and commits:
            # multi-replica optimistic conflict guard: ONE cache probe
            # for the whole batch, cumulative per node — targets whose
            # remaining capacity a sibling replica consumed since the
            # solve are refused before assume and requeued
            verdicts = self.cache.commit_fits(
                [(qpi.pod, r.suggested_host)
                 for qpi, r, _, _ in commits])
            if any(v is not None for v in verdicts):
                live_commits = []
                for item, verdict in zip(commits, verdicts):
                    if verdict is None:
                        live_commits.append(item)
                    else:
                        qpi, result, cycle, _start = item
                        self._reject_stale_commit(
                            fwk, qpi, result.suggested_host,
                            "out of capacity (concurrent replica "
                            "commits)", "capacity", cycle)
                        stale_failed += 1
                commits = live_commits
        # --- assume (bulk): share the queue's parse via PodInfo.derived
        prepared: List[tuple] = []
        assumed_pods: List[Pod] = []
        for qpi, result, cycle, start in commits:
            pod = qpi.pod
            assumed = shallow_copy(pod)
            assumed.spec = shallow_copy(pod.spec)
            assumed.spec.node_name = result.suggested_host
            PodInfo.derived(assumed, qpi.pod_info)
            prepared.append((qpi, result, cycle, start, assumed))
            assumed_pods.append(assumed)
        errors = self.cache.assume_pods(assumed_pods)
        live: List[tuple] = []
        for item, err in zip(prepared, errors):
            if err is None:
                live.append(item)
                self.queue.delete_nominated_pod_if_exists(item[0].pod)
            else:
                self._record_failure(fwk, item[0], ValueError(err),
                                     "SchedulerError", "", item[2])
        failed = stale_failed + len(prepared) - len(live)
        # cache-mutation ledger: one assume per live pod so far; every
        # sync rejection below adds its forget
        mutations = len(live)

        # --- Reserve + Permit (per-pod hook contract)
        has_reserve = bool(fwk.reserve_plugins)
        has_permit = bool(fwk.permit_plugins)
        has_pre_bind = bool(fwk.pre_bind_plugins)
        has_post_bind = bool(fwk.post_bind_plugins)
        sync_items: List[tuple] = []   # (qpi, result, cycle, start, assumed, state)
        for qpi, result, cycle, start, assumed in live:
            state = CycleState()
            if has_reserve:
                status = fwk.run_reserve_plugins_reserve(
                    state, assumed, result.suggested_host)
                if not fw.Status.is_ok(status):
                    self._forget_and_fail(fwk, state, qpi, assumed, result,
                                          status.as_error(), cycle)
                    failed += 1
                    mutations += 1
                    continue
            if has_permit:
                status = fwk.run_permit_plugins(state, assumed,
                                                result.suggested_host)
                if status is not None and status.code not in (fw.SUCCESS,
                                                              fw.WAIT):
                    self._unreserve_forget_fail(fwk, state, qpi, assumed,
                                                result, status.as_error(),
                                                cycle)
                    failed += 1
                    mutations += 1
                    continue
                if status is not None and status.code == fw.WAIT:
                    # gang/permit-parked pods bind asynchronously
                    with self._inflight_lock:
                        self._inflight_bindings += 1
                    self.metrics.goroutines.inc("binding")
                    self._bind_pool.submit(
                        self._binding_cycle, fwk, state, qpi, assumed,
                        result, cycle, start,
                    )
                    continue
            sync_items.append((qpi, result, cycle, start, assumed, state))

        # --- PreBind (per pod), then bulk Bind
        bindable: List[tuple] = []
        for qpi, result, cycle, start, assumed, state in sync_items:
            if has_permit:
                # permit returned SUCCESS; WaitOnPermit is then a cheap
                # no-waiting-pod lookup, kept for hook-order parity
                status = fwk.wait_on_permit(assumed)
                if not fw.Status.is_ok(status):
                    self._unreserve_forget_fail(fwk, state, qpi, assumed,
                                                result, status.as_error(),
                                                cycle)
                    failed += 1
                    mutations += 1
                    continue
            if has_pre_bind:
                status = fwk.run_pre_bind_plugins(state, assumed,
                                                  result.suggested_host)
                if not fw.Status.is_ok(status):
                    self._unreserve_forget_fail(fwk, state, qpi, assumed,
                                                result, status.as_error(),
                                                cycle)
                    failed += 1
                    mutations += 1
                    continue
            bindable.append((qpi, result, cycle, start, assumed, state))

        # extender binders (rare) take the per-pod path; the rest bind
        # in one bulk call
        ext_binders = [e for e in self.algorithm.extenders if e.is_binder()]
        bulk: List[tuple] = []
        committed = 0
        for item in bindable:
            qpi, result, cycle, start, assumed, state = item
            if ext_binders and any(e.is_interested(assumed)
                                   for e in ext_binders):
                err = self._bind(fwk, state, assumed, result.suggested_host)
                if err is not None:
                    self._unreserve_forget_fail(fwk, state, qpi, assumed,
                                                result, err, cycle)
                    failed += 1
                    mutations += 1
                else:
                    self._observe_scheduled(fwk, qpi, start,
                                            result.suggested_host)
                    committed += 1
            else:
                bulk.append(item)
        if bulk:
            if self.async_bulk_bind:
                # ship the whole batch's Bind on the binding pool: the
                # commit loop keeps solving while the bulk request is
                # on the wire (the serial path's per-pod binding
                # goroutine, amortized to one per batch). Pods already
                # count as assumed in the mutation ledger; a wire-level
                # failure forgets them asynchronously, which the
                # device-mirror arithmetic reads as an invalidation.
                with self._inflight_lock:
                    self._inflight_bindings += 1
                self.metrics.goroutines.inc("binding")
                try:
                    self._bind_pool.submit(self._complete_bulk_bind,
                                           fwk, bulk, has_post_bind)
                except RuntimeError:
                    # pool already shut down (stop() raced a late
                    # commit): same accounting as the serial submit race
                    self.metrics.goroutines.dec("binding")
                    with self._inflight_zero:
                        self._inflight_bindings -= 1
                        if self._inflight_bindings == 0:
                            self._inflight_zero.notify_all()
            else:
                n = self._bulk_bind_now(fwk, bulk, has_post_bind)
                committed += n
                failed += len(bulk) - n
                mutations += len(bulk) - n   # one forget per rejection
        self.last_bulk_commit_mutations = mutations
        return committed, failed

    def _bulk_bind_now(self, fwk: Framework, bulk: List[tuple],
                       has_post_bind: bool) -> int:
        """The bulk Bind + PostBind + finish-binding tail shared by the
        sync and async paths. Returns the number bound; failures
        unreserve/forget/requeue per pod (each forget bumps the cache
        mutation counter, which the async path relies on to invalidate
        the device mirror)."""
        with get_tracer().span("bind.bulk", pods=len(bulk)):
            statuses = fwk.run_bind_plugins_bulk(
                [i[5] for i in bulk], [i[4] for i in bulk],
                [i[1].suggested_host for i in bulk],
            )
        bound: List[Pod] = []
        observed: List[tuple] = []
        committed = 0
        for item, status in zip(bulk, statuses):
            qpi, result, cycle, start, assumed, state = item
            if not fw.Status.is_ok(status):
                self._unreserve_forget_fail(fwk, state, qpi, assumed,
                                            result, status.as_error(),
                                            cycle)
                continue
            bound.append(assumed)
            if has_post_bind:
                fwk.run_post_bind_plugins(state, assumed,
                                          result.suggested_host)
            observed.append((qpi, start, result.suggested_host))
            committed += 1
        self._observe_scheduled_bulk(fwk, observed)
        self.cache.finish_binding_many(bound)
        return committed

    def _complete_bulk_bind(self, fwk: Framework, bulk: List[tuple],
                            has_post_bind: bool) -> None:
        try:
            try:
                self._bulk_bind_now(fwk, bulk, has_post_bind)
            except Exception as err:  # noqa: BLE001 — transport died
                # (retries exhausted, server gone): every pod in the
                # batch unwinds exactly as a failed sync bind would —
                # unreserve, forget, SchedulerError requeue; the next
                # attempt sees the post-outage world
                for qpi, result, cycle, _start, assumed, state in bulk:
                    self._unreserve_forget_fail(fwk, state, qpi, assumed,
                                                result, err, cycle)
        finally:
            self.metrics.goroutines.dec("binding")
            with self._inflight_zero:
                self._inflight_bindings -= 1
                if self._inflight_bindings == 0:
                    self._inflight_zero.notify_all()

    def _observe_scheduled(self, fwk: Framework, qpi: QueuedPodInfo,
                           start: float, node_name: str = "") -> None:
        now = time.monotonic()
        self.metrics.e2e_scheduling_duration.observe(now - start, "scheduled")
        self.metrics.schedule_attempts.inc("scheduled", fwk.profile_name)
        self.metrics.pod_scheduling_attempts.observe(qpi.attempts)
        self.metrics.pod_scheduling_duration.observe(
            now - qpi.initial_attempt_timestamp, str(qpi.attempts))
        pod = qpi.pod
        tracer = get_tracer()
        if tracer.enabled and pod.uid and tracer.sampled(pod.uid):
            # the bind-completing hop of the pod's causal trace:
            # pop → algorithm/solve → commit → bound
            tracer.record("sched.bind", start, now, trace=pod.uid,
                          node=node_name, attempts=qpi.attempts)
        self.recorder.eventf(
            pod, "Normal", "Scheduled",
            "Successfully assigned %s/%s to %s",
            pod.namespace, pod.name, node_name,
        )

    def _observe_scheduled_bulk(self, fwk: Framework, observed) -> None:
        """Batched ``_observe_scheduled`` for the bulk commit path:
        ``observed`` is a list of (qpi, start, node_name). Metric locks
        are taken once per batch instead of 4x per pod, and the
        Scheduled event's formatting defers to the recorder's flush
        thread."""
        if not observed:
            return
        now = time.monotonic()
        m = self.metrics
        m.e2e_scheduling_duration.observe_many(
            [now - start for _, start, _ in observed], "scheduled")
        m.schedule_attempts.inc("scheduled", fwk.profile_name,
                                amount=len(observed))
        m.pod_scheduling_attempts.observe_many(
            [qpi.attempts for qpi, _, _ in observed])
        by_attempts: dict = {}
        for qpi, _, _ in observed:
            by_attempts.setdefault(qpi.attempts, []).append(
                now - qpi.initial_attempt_timestamp)
        for attempts, values in by_attempts.items():
            m.pod_scheduling_duration.observe_many(values, str(attempts))
        tracer = get_tracer()
        if tracer.enabled:
            for qpi, start, node_name in observed:
                uid = qpi.pod.uid
                if uid and tracer.sampled(uid):
                    tracer.record("sched.bind", start, now, trace=uid,
                                  node=node_name, attempts=qpi.attempts)
        recorder = self.recorder
        for qpi, _, node_name in observed:
            pod = qpi.pod
            recorder.eventf(
                pod, "Normal", "Scheduled",
                "Successfully assigned %s/%s to %s",
                pod.namespace, pod.name, node_name,
            )

    # ------------------------------------------------------------------
    def _binding_cycle(
        self,
        fwk: Framework,
        state: CycleState,
        qpi: QueuedPodInfo,
        assumed_pod: Pod,
        result: ScheduleResult,
        cycle: int,
        start: float,
    ) -> bool:
        try:
            status = fwk.wait_on_permit(assumed_pod)
            if not fw.Status.is_ok(status):
                self._unreserve_forget_fail(fwk, state, qpi, assumed_pod, result,
                                            status.as_error(), cycle)
                return False
            status = fwk.run_pre_bind_plugins(state, assumed_pod,
                                              result.suggested_host)
            if not fw.Status.is_ok(status):
                self._unreserve_forget_fail(fwk, state, qpi, assumed_pod, result,
                                            status.as_error(), cycle)
                return False
            err = self._bind(fwk, state, assumed_pod, result.suggested_host)
            if err is not None:
                self._unreserve_forget_fail(fwk, state, qpi, assumed_pod, result,
                                            err, cycle)
                return False
            fwk.run_post_bind_plugins(state, assumed_pod, result.suggested_host)
            self._observe_scheduled(fwk, qpi, start, result.suggested_host)
            return True
        finally:
            self.metrics.goroutines.dec("binding")
            with self._inflight_zero:
                self._inflight_bindings -= 1
                if self._inflight_bindings == 0:
                    self._inflight_zero.notify_all()

    def _bind(self, fwk: Framework, state: CycleState, pod: Pod,
              node_name: str) -> Optional[Exception]:
        """scheduler.go:381: extender binders take precedence, then the
        framework's bind plugins; FinishBinding starts the assumed TTL."""
        try:
            bound = False
            for ext in self.algorithm.extenders:
                if ext.is_binder() and ext.is_interested(pod):
                    ext.bind(pod, node_name)
                    bound = True
                    break
            if not bound:
                status = fwk.run_bind_plugins(state, pod, node_name)
                if not fw.Status.is_ok(status):
                    return status.as_error()
            self.cache.finish_binding(pod)
            return None
        except Exception as e:  # noqa: BLE001
            return e

    # ------------------------------------------------------------------
    def _handle_fit_error(self, fwk: Framework, state: CycleState,
                          qpi: QueuedPodInfo, fit_err: fw.FitError,
                          cycle: int) -> None:
        """PostFilter (preemption) then record + requeue (scheduler.go:465)."""
        nominated_node = ""
        if fwk.has_post_filter_plugins():
            self.metrics.preemption_attempts.inc()
            # preemption drives client writes (victim deletes, status);
            # a transport failure mid-dry-run must still fall through to
            # record + REQUEUE, not lose the pod
            try:
                result, status = fwk.run_post_filter_plugins(
                    state, qpi.pod, fit_err.filtered_nodes_statuses
                )
            except Exception as post_err:  # noqa: BLE001
                result, status = None, fw.Status(
                    fw.ERROR, f"PostFilter failed: {post_err}")
            if fw.Status.is_ok(status) and result is not None:
                nominated_node = result.nominated_node_name
        self._record_failure(fwk, qpi, fit_err, "Unschedulable",
                             nominated_node, cycle)

    def _forget_and_fail(self, fwk, state, qpi, assumed_pod, result, err,
                         cycle) -> None:
        try:
            self.cache.forget_pod(assumed_pod)
        except ValueError:
            pass
        self._record_failure(fwk, qpi, err, "SchedulerError", "", cycle)

    @staticmethod
    def _note_bind_conflict(err: Exception) -> None:
        """Count a bind the STORE refused because another writer got
        there first — the same-pod CAS losing half of multi-replica
        optimistic concurrency ("already assigned": a sibling replica
        bound this pod; "uid mismatch": it was deleted and recreated in
        flight; "capacity conflict": the partitioned store's bind-time
        ledger arbitrated a node race). The loser unwinds through the
        normal unreserve/forget/requeue path; this just makes the
        conflict visible on the stale-bind series the chaos invariants
        watch."""
        msg = str(err)
        if "capacity conflict" in msg:
            path = "bind_conflict"
        elif "already assigned" in msg or "uid mismatch" in msg:
            path = "replica_conflict"
        else:
            return
        from kubernetes_tpu.metrics.fabric_metrics import fabric_metrics

        fabric_metrics().stale_binds_rejected_total.inc(path)

    def _unreserve_forget_fail(self, fwk, state, qpi, assumed_pod, result,
                               err, cycle) -> None:
        self._note_bind_conflict(err)
        fwk.run_reserve_plugins_unreserve(state, assumed_pod,
                                          result.suggested_host)
        gang = fwk.get_plugin("Coscheduling")
        if gang is not None:
            gang.unreserve_group(assumed_pod)
        self._forget_and_fail(fwk, state, qpi, assumed_pod, result, err, cycle)

    def _reject_stale_commit(self, fwk: Framework, qpi: QueuedPodInfo,
                             node_name: str, reason: str, path: str,
                             cycle: int) -> None:
        """Refuse to commit an assignment whose target node went stale
        between snapshot and commit: count it, then route the pod back
        through the normal error function (SchedulerError → backoff
        requeue; the next attempt solves against the post-churn
        state)."""
        from kubernetes_tpu.metrics.fabric_metrics import fabric_metrics

        fabric_metrics().stale_binds_rejected_total.inc(path)
        _logger.debug("refusing stale bind of %s/%s to %s node %s (%s)",
                      qpi.pod.namespace, qpi.pod.name, reason, node_name,
                      path)
        err = RuntimeError(
            f"commit target node {node_name!r} is {reason} "
            f"(assignment solved against a stale snapshot)")
        self._record_failure(fwk, qpi, err, "SchedulerError", "", cycle)

    def _record_failure(self, fwk: Framework, qpi: QueuedPodInfo,
                        err: Exception, reason: str, nominated_node: str,
                        cycle: int) -> None:
        """recordSchedulingFailure (scheduler.go:319) +
        MakeDefaultErrorFunc (factory.go:316-362)."""
        pod = qpi.pod
        # the operator-facing record (scheduler.go:331 recordSchedulingFailure
        # → FailedScheduling event)
        self.recorder.event(pod, "Warning", "FailedScheduling", str(err))
        # status writes are BEST-EFFORT: over REST they can fail (server
        # down, overload pushback, retry budget spent) and an exception
        # here must never skip the requeue below — a pod dropped between
        # queues is parked forever, the exact lost-pod failure the chaos
        # ring exists to catch
        try:
            self.client.patch_pod_condition(
                pod.namespace, pod.name,
                PodCondition("PodScheduled", "False", reason, str(err)),
            )
            if nominated_node:
                self.client.set_nominated_node_name(pod.namespace,
                                                    pod.name,
                                                    nominated_node)
        except Exception:  # noqa: BLE001 — usually transport loss; a
            # real defect must still be visible in the logs
            _logger.debug("status write failed for %s/%s (requeueing "
                          "regardless)", pod.namespace, pod.name,
                          exc_info=True)
        if nominated_node:
            pod.status.nominated_node_name = nominated_node
            self.queue.add_nominated_pod(pod, nominated_node)
        # requeue only pods that still exist unassigned (factory.go:340);
        # when the existence check itself fails, assume the pod lives and
        # requeue — a later cycle re-checks against recovered state
        try:
            current = self.client.get_pod(pod.namespace, pod.name)
        except Exception:  # noqa: BLE001 — transport failure
            _logger.debug("existence check failed for %s/%s (assuming "
                          "it lives)", pod.namespace, pod.name,
                          exc_info=True)
            current = pod
        if current is not None and not assigned(current):
            try:
                # scheduler-internal failures retry on the backoff curve;
                # only genuine fit failures park for an unblocking event
                self.queue.add_unschedulable_if_not_present(
                    qpi, cycle,
                    prefer_backoff=(reason == "SchedulerError"))
            except ValueError:
                pass
