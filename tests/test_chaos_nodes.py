"""Node-churn resilience (ISSUE 3 tentpole): commit-time stale-node
guards on the batched scheduling path, the solver session's node-epoch
drift trigger, nodelifecycle flap re-registration, the eviction →
requeue rescue pipeline, and — marked slow — the full seeded node-churn
suite (``kubernetes_tpu.harness.chaos_nodes``).

Reference anchors: ``pkg/controller/nodelifecycle`` (monitorNodeHealth,
unreachable taint, pod eviction), ``pkg/controller/podgc`` (gcOrphaned),
and the scheduler's assume/bind contract — the store accepts binds to
nonexistent nodes, so the host-side guard is the only thing standing
between a stale solve and a pod bound into the void.
"""

import time

import pytest

from kubernetes_tpu.api.types import (
    NO_EXECUTE,
    NO_SCHEDULE,
    TAINT_NODE_UNREACHABLE,
    TAINT_NODE_UNSCHEDULABLE,
    Taint,
    Toleration,
)
from kubernetes_tpu.apiserver.store import ClusterStore
from kubernetes_tpu.config.feature_gates import FeatureGates
from kubernetes_tpu.metrics.fabric_metrics import fabric_metrics
from kubernetes_tpu.scheduler.core import ScheduleResult
from kubernetes_tpu.scheduler.framework.cycle_state import CycleState
from kubernetes_tpu.scheduler.scheduler import (
    Scheduler,
    commit_target_stale,
)
from kubernetes_tpu.sidecar import attach_batch_scheduler
from kubernetes_tpu.testing import MakeNode, MakePod

pytestmark = pytest.mark.chaos


def _stale_rejected_total() -> float:
    return sum(v for _, _, v in
               fabric_metrics().stale_binds_rejected_total.collect())


def _make_sched(store, batch: bool = False, max_batch: int = 16):
    gates = FeatureGates({"TPUBatchScheduler": batch})
    sched = Scheduler.create(store, feature_gates=gates)
    bs = attach_batch_scheduler(sched, max_batch=max_batch) if batch \
        else None
    sched.start()
    return sched, bs


def _pop_with_result(sched, node_name: str):
    qpi = sched.queue.pop(timeout=2.0)
    assert qpi is not None
    result = ScheduleResult(suggested_host=node_name, evaluated_nodes=1,
                            feasible_nodes=1)
    return qpi, result


# ---------------------------------------------------------------------------
# commit_target_stale verdicts (pure)


class TestCommitTargetStale:
    def test_missing_node_is_always_stale(self):
        pod = MakePod().name("p").obj()
        assert commit_target_stale(pod, None) == "deleted"

    def test_cordoned_node_rejects_unless_tolerated(self):
        node = MakeNode().name("n").unschedulable().obj()
        pod = MakePod().name("p").obj()
        assert commit_target_stale(pod, node) == "cordoned"
        pod.spec.tolerations.append(
            Toleration(key=TAINT_NODE_UNSCHEDULABLE, operator="Exists",
                       effect=NO_SCHEDULE))
        assert commit_target_stale(pod, node) is None

    def test_unreachable_taint_rejects_unless_tolerated(self):
        node = MakeNode().name("n").obj()
        node.spec.taints.append(
            Taint(TAINT_NODE_UNREACHABLE, "", NO_EXECUTE))
        pod = MakePod().name("p").obj()
        assert commit_target_stale(pod, node) == "unreachable"
        pod.spec.tolerations.append(
            Toleration(key=TAINT_NODE_UNREACHABLE, operator="Exists"))
        assert commit_target_stale(pod, node) is None

    def test_healthy_node_passes(self):
        node = MakeNode().name("n").obj()
        assert commit_target_stale(MakePod().name("p").obj(), node) is None


# ---------------------------------------------------------------------------
# cache probe


class TestCommitTargetFlags:
    def test_only_suspect_nodes_are_flagged(self):
        from kubernetes_tpu.scheduler.cache import SchedulerCache

        cache = SchedulerCache()
        cache.add_node(MakeNode().name("ok").obj())
        cache.add_node(MakeNode().name("cordoned").unschedulable().obj())
        tainted = MakeNode().name("unreachable").obj()
        tainted.spec.taints.append(
            Taint(TAINT_NODE_UNREACHABLE, "", NO_EXECUTE))
        cache.add_node(tainted)
        flags = cache.commit_target_flags(
            {"ok", "cordoned", "unreachable", "ghost"})
        assert "ok" not in flags
        assert flags["ghost"] is None
        assert flags["cordoned"].spec.unschedulable
        assert any(t.key == TAINT_NODE_UNREACHABLE
                   for t in flags["unreachable"].spec.taints)

    def test_node_set_seq_tracks_appear_and_vanish_only(self):
        from kubernetes_tpu.scheduler.cache import SchedulerCache

        cache = SchedulerCache()
        node = MakeNode().name("n").obj()
        seq0 = cache.node_set_seq
        cache.add_node(node)
        assert cache.node_set_seq == seq0 + 1
        updated = MakeNode().name("n").unschedulable().obj()
        cache.update_node(node, updated)       # update: set unchanged
        assert cache.node_set_seq == seq0 + 1
        cache.remove_node(updated)
        assert cache.node_set_seq == seq0 + 2


# ---------------------------------------------------------------------------
# serial-path guard


class TestSerialCommitGuard:
    def test_deleted_node_rejected_and_requeued(self):
        store = ClusterStore()
        store.add_node(MakeNode().name("n1")
                       .capacity({"cpu": "4", "memory": "8Gi"}).obj())
        sched, _ = _make_sched(store)
        try:
            store.create_pod(MakePod().name("p").uid("u")
                             .req({"cpu": "100m"}).obj())
            deadline = time.time() + 5
            while time.time() < deadline and sched.queue.num_active() == 0:
                time.sleep(0.02)
            qpi, result = _pop_with_result(sched, "n1")
            fwk = sched.profiles["default-scheduler"]
            # the node dies between schedule and commit
            sched.cache.remove_node(store.get_node("n1"))
            before = _stale_rejected_total()
            committed = sched.commit_assignment(
                fwk, CycleState(), qpi, result, 0, time.monotonic(),
                sync_bind=True)
            assert committed is False
            assert _stale_rejected_total() == before + 1
            # never bound; requeued, not lost
            assert store.get_pod("default", "p").spec.node_name == ""
            assert not sched.cache.is_assumed_pod(qpi.pod)
        finally:
            sched.stop()

    def test_bulk_commit_filters_stale_targets_only(self):
        store = ClusterStore()
        for name in ("n1", "n2"):
            store.add_node(MakeNode().name(name)
                           .capacity({"cpu": "4", "memory": "8Gi"}).obj())
        sched, _ = _make_sched(store)
        try:
            for i in range(2):
                store.create_pod(MakePod().name(f"p{i}").uid(f"u{i}")
                                 .req({"cpu": "100m"}).obj())
            deadline = time.time() + 5
            while time.time() < deadline and sched.queue.num_active() < 2:
                time.sleep(0.02)
            items, first_cycle = sched.queue.pop_batch(2, timeout=2.0)
            assert len(items) == 2
            fwk = sched.profiles["default-scheduler"]
            # p0 -> n1 (dies), p1 -> n2 (lives)
            targets = {"p0": "n1", "p1": "n2"}
            commits = [
                (qpi, ScheduleResult(
                    suggested_host=targets[qpi.pod.name],
                    evaluated_nodes=2, feasible_nodes=1),
                 first_cycle + i, time.monotonic())
                for i, qpi in enumerate(items)
            ]
            sched.cache.remove_node(store.get_node("n1"))
            before = _stale_rejected_total()
            committed, failed = sched.commit_assignments_bulk(fwk, commits)
            assert committed == 1 and failed == 1
            assert _stale_rejected_total() == before + 1
            assert store.get_pod("default", "p1").spec.node_name == "n2"
            assert store.get_pod("default", "p0").spec.node_name == ""
        finally:
            sched.stop()


# ---------------------------------------------------------------------------
# batch-path guard + session drift


class TestBatchPathStaleRouting:
    def test_node_death_between_solve_and_commit_routes_serial(self):
        """A batch solved against a snapshot containing a node that dies
        mid-cycle (after the solve, before the commit) must not bind
        there: the sidecar's guard routes the pod to the
        serial path, which places it on the surviving node, and the
        session is told the node planes drifted."""
        store = ClusterStore()
        for name in ("n1", "n2"):
            store.add_node(MakeNode().name(name)
                           .capacity({"cpu": "8", "memory": "16Gi"}).obj())
        sched, bs = _make_sched(store, batch=True)
        try:
            store.create_pod(MakePod().name("p").uid("u")
                             .req({"cpu": "100m"}).obj())
            deadline = time.time() + 5
            while time.time() < deadline and sched.queue.num_active() == 0:
                time.sleep(0.02)
            qpis = bs._drain(0.5)
            assert len(qpis) == 1
            assignments, cluster, _seq = bs.session.solve(
                [q.pod for q, _ in qpis])
            pending = {
                "batchable": qpis,
                "assignments": assignments,
                "cluster": cluster,
                "profiles": bs.session.last_profile_idx,
                "inexpressible": bs.session.last_inexpressible,
                "masks": bs.session.static_masks_host,
                "start": time.monotonic(),
                "pad": bs._chunk,
            }
            target = cluster.node_names[int(assignments[0])]
            survivor = "n2" if target == "n1" else "n1"
            # the solved target dies mid-cycle, before the commit
            sched.cache.remove_node(store.get_node(target))
            store.delete_node(target)
            before = _stale_rejected_total()
            serial = []
            committed = bs._commit_pending(pending, serial)
            assert committed == 0
            assert [q.pod.name for q in serial] == ["p"]
            assert _stale_rejected_total() == before + 1
            assert not bs.session.mirror_current()   # drift noted
            # the serial fallback gives the pod a live placement
            bs._run_serial(serial)
            deadline = time.time() + 10
            while time.time() < deadline and \
                    not store.get_pod("default", "p").spec.node_name:
                time.sleep(0.02)
            assert store.get_pod("default", "p").spec.node_name == survivor
        finally:
            sched.stop()

    def test_session_node_epoch_forces_reencode(self):
        """Mass node deletion must force an encoding rebuild: the
        incremental path may not serve an encoding whose node columns
        describe a vanished epoch, even if the mutation arithmetic is
        laundered back into agreement."""
        store = ClusterStore()
        for i in range(4):
            store.add_node(MakeNode().name(f"n{i}")
                           .capacity({"cpu": "8", "memory": "16Gi"}).obj())
        sched, bs = _make_sched(store, batch=True)
        try:
            store.create_pod(MakePod().name("p0").uid("u0")
                             .req({"cpu": "100m"}).obj())
            deadline = time.time() + 10
            while time.time() < deadline and \
                    not store.get_pod("default", "p0").spec.node_name:
                bs.run_batch(pop_timeout=0.05)
            session = bs.session
            rebuilds_before = session.rebuilds
            # forge mutation-arithmetic agreement, but move the node set
            session._last_seq = sched.cache.mutation_seq
            session._poisoned = False
            sched.cache.remove_node(store.get_node("n3"))
            session._last_seq = sched.cache.mutation_seq
            assert not session.mirror_current()
            hits_before = session.incremental_hits
            assignments, cluster, _seq = session.solve(
                [MakePod().name("px").uid("ux")
                 .req({"cpu": "100m"}).obj()])
            # refused the incremental path: rebuilt over the new node set
            assert session.incremental_hits == hits_before
            assert session.rebuilds == rebuilds_before + 1
            assert "n3" not in cluster.node_names
            assert cluster.node_names[int(assignments[0])] != "n3"
        finally:
            sched.stop()

    def test_note_drift_clears_static_fingerprint(self):
        store = ClusterStore()
        store.add_node(MakeNode().name("n1")
                       .capacity({"cpu": "8", "memory": "16Gi"}).obj())
        sched, bs = _make_sched(store, batch=True)
        try:
            session = bs.session
            session.solve([MakePod().name("p").uid("u")
                           .req({"cpu": "100m"}).obj()], warming=True)
            assert session._static_fp is not None
            session.note_drift()
            assert session._static_fp is None
            assert not session.mirror_current()
        finally:
            sched.stop()


# ---------------------------------------------------------------------------
# node flap re-registration (satellite)


class TestNodeFlapReRegistration:
    def test_recreated_node_gets_fresh_grace_and_cache_converges(self):
        """Delete + recreate a node with the same name mid-workload: the
        nodelifecycle on_delete purge must hand the fresh incarnation a
        full grace period (no inherited not-ready clock → no instant
        eviction), and the scheduler cache/node_tree must converge to
        exactly one live node."""
        from kubernetes_tpu.client.informers import SharedInformerFactory
        from kubernetes_tpu.controllers.nodelifecycle import (
            UNREACHABLE_TAINT,
            NodeLifecycleController,
        )
        from kubernetes_tpu.utils.clock import FakeClock

        store = ClusterStore()
        clock = FakeClock(start=100.0)
        store.add_node(MakeNode().name("n1")
                       .capacity({"cpu": "8", "memory": "16Gi"}).obj())
        factory = SharedInformerFactory(store)
        nlc = NodeLifecycleController(store, factory, clock=clock)
        factory.start()
        assert factory.wait_for_cache_sync()
        sched, _ = _make_sched(store)
        try:
            store.create_pod(MakePod().name("p").uid("u")
                             .req({"cpu": "100m"}).obj())
            store.bind("default", "p", "u", "n1")
            # node goes silent far past the grace: NotReady + tainted
            nlc.heartbeat("n1")
            nlc.monitor_node_health()
            clock.step(45.0)
            nlc.monitor_node_health()
            assert any(t.key == UNREACHABLE_TAINT
                       for t in store.get_node("n1").spec.taints)
            # flap: delete, then recreate under the SAME name
            store.delete_node("n1")
            deadline = time.time() + 5
            while time.time() < deadline and "n1" in nlc._not_ready_since:
                time.sleep(0.02)
            assert "n1" not in nlc._not_ready_since
            assert "n1" not in nlc._first_seen
            store.add_node(MakeNode().name("n1")
                           .capacity({"cpu": "8", "memory": "16Gi"}).obj())
            deadline = time.time() + 5
            while time.time() < deadline and \
                    nlc.node_lister.get("n1") is None:
                time.sleep(0.02)
            # the fresh incarnation is inside its own grace period: the
            # monitor must NOT taint or evict, even with no heartbeat yet
            nlc.monitor_node_health()
            node = store.get_node("n1")
            assert not any(t.key == UNREACHABLE_TAINT
                           for t in node.spec.taints)
            # half the grace later, still clean; past it, tainted again
            clock.step(nlc.grace_period / 2)
            nlc.monitor_node_health()
            assert not any(t.key == UNREACHABLE_TAINT
                           for t in store.get_node("n1").spec.taints)
            clock.step(nlc.grace_period)
            nlc.monitor_node_health()
            assert any(t.key == UNREACHABLE_TAINT
                       for t in store.get_node("n1").spec.taints)
            # scheduler cache/node_tree converged across the flap:
            # exactly one live n1
            deadline = time.time() + 5
            while time.time() < deadline and sched.cache.node_count() != 1:
                time.sleep(0.02)
            assert sched.cache.node_count() == 1
            dump = sched.cache.dump()
            live = [n for n, info in dump["nodes"].items()
                    if info.node is not None]
            assert live == ["n1"]
        finally:
            sched.stop()
            factory.stop()


# ---------------------------------------------------------------------------
# rescue pipeline (fast, store-level)


class TestRescuePipeline:
    def test_evicted_pod_is_recreated_and_rescue_latency_observed(self):
        from kubernetes_tpu.client.restcluster import RestClusterClient
        from kubernetes_tpu.apiserver.rest import APIServer
        from kubernetes_tpu.harness.chaos_nodes import PodRescuer

        store = ClusterStore()
        store.add_node(MakeNode().name("n1")
                       .capacity({"cpu": "8", "memory": "16Gi"}).obj())
        server = APIServer(store=store).start()
        client = RestClusterClient(server.url, watch_kinds=())
        rescuer = PodRescuer(store, client, name_prefix="cp-")
        rescuer.start()
        try:
            store.create_pod(MakePod().name("cp-0").uid("u0")
                             .req({"cpu": "100m"}).obj())
            store.bind("default", "cp-0", "u0", "n1")
            # eviction (what nodelifecycle does past the grace)
            store.delete_pod("default", "cp-0")
            deadline = time.time() + 10
            while time.time() < deadline:
                pod = store.get_pod("default", "cp-0")
                if pod is not None and pod.uid == "u0-r1":
                    break
                time.sleep(0.02)
            pod = store.get_pod("default", "cp-0")
            assert pod is not None and pod.uid == "u0-r1"
            assert pod.spec.node_name == ""   # re-enters scheduling
            assert rescuer.pending() == 1
            # replacement binds -> rescue completes with a latency sample
            store.bind("default", "cp-0", "u0-r1", "n1")
            deadline = time.time() + 10
            while time.time() < deadline and rescuer.pending():
                time.sleep(0.02)
            assert rescuer.pending() == 0
            assert len(rescuer.rescues) == 1 and rescuer.rescues[0] >= 0
        finally:
            rescuer.stop()
            server.shutdown_server()


# ---------------------------------------------------------------------------
# the full seeded node-churn suite (slow)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [3, 17, 29, 47, 61])
def test_node_churn_survives_death_flaps_and_stale_commits(seed):
    from kubernetes_tpu.harness.chaos_nodes import run_chaos_nodes

    result = run_chaos_nodes(seed, nodes=16, pods=96,
                             churn_profile="mixed", wait_timeout=120.0)
    assert result["ok"], (
        f"seed {seed}: {result['failure'] or result['invariants']} "
        f"(stats: {result['stats']})"
    )
    # the run was genuinely hostile: churn actually bit
    actions = result["stats"]["churn_actions"]
    assert sum(actions.values()) > 0
    assert actions["kill"] + actions["flap"] > 0
