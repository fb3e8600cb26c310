"""SolverSession tests: device-resident state carry, incremental
pod-side-only encoding, and mutation-seq invalidation.

The correctness bar is differential: many small incremental batches must
produce the same bindings (validity + constraint satisfaction) as one cold
full-encode solve per batch, and any external cache mutation must force a
rebuild rather than solving against a stale device mirror.
"""

import time

from kubernetes_tpu.apiserver.store import ClusterStore
from kubernetes_tpu.config.feature_gates import FeatureGates
from kubernetes_tpu.scheduler.scheduler import Scheduler
from kubernetes_tpu.sidecar import attach_batch_scheduler
from kubernetes_tpu.testing import MakeNode, MakePod


def make_batch_scheduler(store, max_batch=8, validate=False):
    sched = Scheduler.create(
        store, feature_gates=FeatureGates({"TPUBatchScheduler": True})
    )
    bs = attach_batch_scheduler(sched, max_batch=max_batch, validate=validate)
    sched.start()
    return sched, bs


def drain(sched, bs, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        sched.queue.flush_backoff_completed()
        if bs.run_batch(pop_timeout=0.0):
            continue
        if sched.queue.num_active() == 0 and sched.queue.num_backoff() == 0:
            break
        time.sleep(0.02)
    assert sched.wait_for_inflight_bindings()


class TestIncrementalSession:
    def test_many_small_batches_use_incremental_path(self):
        store = ClusterStore()
        for i in range(6):
            store.add_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "16", "memory": "32Gi"}).obj()
            )
        sched, bs = make_batch_scheduler(store, max_batch=8)
        for i in range(64):
            store.create_pod(MakePod().name(f"p{i}").req({"cpu": "1"}).obj())
        drain(sched, bs)
        bound = [p for p in store.list_pods() if p.spec.node_name]
        assert len(bound) == 64
        # 64 pods at max_batch=8 → ≥8 batches; all but the first should
        # ride the device-resident incremental path
        assert bs.session.rebuilds >= 1
        assert bs.session.incremental_hits >= 6
        # capacity respected across the carried state
        per_node = {}
        for p in bound:
            per_node[p.spec.node_name] = per_node.get(p.spec.node_name, 0) + 1
        assert all(c <= 16 for c in per_node.values())
        sched.stop()

    def test_incremental_respects_spread_across_batches(self):
        store = ClusterStore()
        for i in range(9):
            store.add_node(
                MakeNode().name(f"n{i}")
                .label("topology.kubernetes.io/zone", f"z{i % 3}")
                .capacity({"cpu": "64", "memory": "64Gi"}).obj()
            )
        sched, bs = make_batch_scheduler(store, max_batch=6)
        for i in range(30):
            store.create_pod(
                MakePod().name(f"p{i}").uid(f"u{i}")
                .label("app", "web").req({"cpu": "100m"})
                .spread_constraint(
                    1, "topology.kubernetes.io/zone", "DoNotSchedule",
                    {"app": "web"},
                ).obj()
            )
        drain(sched, bs)
        bound = [p for p in store.list_pods() if p.spec.node_name]
        assert len(bound) == 30
        zone_of = {}
        for n in store.list_nodes():
            zone_of[n.name] = n.metadata.labels["topology.kubernetes.io/zone"]
        counts = {}
        for p in bound:
            z = zone_of[p.spec.node_name]
            counts[z] = counts.get(z, 0) + 1
        # maxSkew=1 must hold across batch boundaries (carried counts)
        assert max(counts.values()) - min(counts.values()) <= 1
        assert bs.session.incremental_hits >= 2
        sched.stop()

    def test_external_mutation_invalidates_session(self):
        store = ClusterStore()
        for i in range(4):
            store.add_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "8", "memory": "16Gi"}).obj()
            )
        sched, bs = make_batch_scheduler(store, max_batch=4)
        for i in range(8):
            store.create_pod(MakePod().name(f"a{i}").req({"cpu": "1"}).obj())
        drain(sched, bs)
        rebuilds_before = bs.session.rebuilds
        # external change: a new node appears via the watch path
        store.add_node(
            MakeNode().name("late").capacity({"cpu": "8", "memory": "16Gi"}).obj()
        )
        for i in range(8):
            store.create_pod(MakePod().name(f"b{i}").req({"cpu": "1"}).obj())
        drain(sched, bs)
        assert bs.session.rebuilds > rebuilds_before
        bound = [p for p in store.list_pods() if p.spec.node_name]
        assert len(bound) == 16
        # the late node is actually usable (mirror rebuilt, not stale)
        assert any(p.spec.node_name == "late" for p in bound)
        sched.stop()

    def test_serial_fallback_poisons_session(self):
        """A batch containing a host-only pod (PVC) triggers the serial
        path; if the serial path binds it the mirror must rebuild."""
        store = ClusterStore()
        for i in range(3):
            store.add_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "8", "memory": "16Gi"}).obj()
            )
        sched, bs = make_batch_scheduler(store, max_batch=8)
        for i in range(6):
            store.create_pod(MakePod().name(f"p{i}").req({"cpu": "1"}).obj())
        store.create_pod(
            MakePod().name("pvc-pod").req({"cpu": "1"}).pvc("claim-1").obj()
        )
        drain(sched, bs)
        # all plain pods bound; pvc pod went serial (may bind or stay
        # pending depending on volume plugins — either way the session
        # must have noticed the serial traffic)
        bound = [p for p in store.list_pods() if p.spec.node_name]
        assert len([p for p in bound if p.metadata.name.startswith("p")]) >= 6
        # next batch must rebuild (session was invalidated by serial bind)
        rebuilds = bs.session.rebuilds
        for i in range(4):
            store.create_pod(MakePod().name(f"q{i}").req({"cpu": "1"}).obj())
        drain(sched, bs)
        assert len([p for p in store.list_pods() if p.spec.node_name]) >= 10
        if any(p.metadata.name == "pvc-pod" and p.spec.node_name
               for p in store.list_pods()):
            assert bs.session.rebuilds > rebuilds
        sched.stop()

    def test_validate_mode_matches_host_filters(self):
        """validate=True re-checks every device assignment with the host
        filter chain; carried state must never produce a host-rejected
        placement in a plain workload."""
        store = ClusterStore()
        for i in range(5):
            store.add_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "4", "memory": "8Gi"}).obj()
            )
        sched, bs = make_batch_scheduler(store, max_batch=5, validate=True)
        for i in range(20):
            store.create_pod(MakePod().name(f"p{i}").req({"cpu": "1"}).obj())
        drain(sched, bs)
        bound = [p for p in store.list_pods() if p.spec.node_name]
        assert len(bound) == 20
        sched.stop()

    def test_wide_term_space_falls_back_to_legacy_backend(self):
        """More tracked anti-affinity terms than padded nodes exceeds the
        planes layout's totals plane; the session routes the epoch to the
        legacy backend by shape, before any solve, and still schedules
        everything."""
        store = ClusterStore()
        for i in range(8):
            store.add_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "64", "memory": "64Gi"}).obj()
            )
        sched, bs = make_batch_scheduler(store, max_batch=256)
        # 140 distinct groups > 128 padded nodes
        for i in range(140):
            store.create_pod(
                MakePod().name(f"p{i}").uid(f"u{i}")
                .label("g", f"g{i}").req({"cpu": "100m"})
                .pod_anti_affinity("g", [f"g{i}"], "kubernetes.io/hostname")
                .obj()
            )
        drain(sched, bs, timeout=120)
        bound = [p for p in store.list_pods() if p.spec.node_name]
        assert len(bound) == 140
        assert bs.session._active.name == "xla-legacy"
        sched.stop()


class TestPipelinedBatches:
    def test_stale_pending_is_resolved_not_serialized(self):
        """A held batch whose mirror diverges (external node add between
        its solve and commit) must be re-solved against a fresh snapshot
        and still bind everything correctly."""
        store = ClusterStore()
        for i in range(4):
            store.add_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "8", "memory": "16Gi"}).obj()
            )
        sched, bs = make_batch_scheduler(store, max_batch=8)
        for i in range(24):
            store.create_pod(MakePod().name(f"p{i}").req({"cpu": "1"}).obj())
        # first cycle: solves 8, holds them pending (queue still has 16)
        bs.run_batch(pop_timeout=0.1)
        assert bs._pending is not None
        # external mutation while the batch is in flight
        store.add_node(
            MakeNode().name("late").capacity({"cpu": "8", "memory": "16Gi"}).obj()
        )
        drain(sched, bs)
        bound = [p for p in store.list_pods() if p.spec.node_name]
        assert len(bound) == 24
        per_node = {}
        for p in bound:
            per_node[p.spec.node_name] = per_node.get(p.spec.node_name, 0) + 1
        assert all(c <= 8 for c in per_node.values())
        sched.stop()

    def test_rebuild_mid_pipeline_commits_in_flight_batch_first(self):
        """A second wave introducing a NEW constraint space while a batch
        is pending forces a rebuild; the in-flight batch must commit
        before the rebuild so the fresh snapshot includes it (no
        double-placement / overcommit)."""
        store = ClusterStore()
        for i in range(6):
            store.add_node(
                MakeNode().name(f"n{i}")
                .label("topology.kubernetes.io/zone", f"z{i % 3}")
                .capacity({"cpu": "4", "memory": "8Gi"}).obj()
            )
        sched, bs = make_batch_scheduler(store, max_batch=8)
        # wave 1: plain pods (fills the pipeline)
        for i in range(16):
            store.create_pod(MakePod().name(f"a{i}").req({"cpu": "1"}).obj())
        bs.run_batch(pop_timeout=0.1)    # solve 8, hold pending
        # wave 2: spread-constrained pods -> new tracked constraint ->
        # encode space mismatch -> rebuild path
        for i in range(12):
            store.create_pod(
                MakePod().name(f"s{i}").uid(f"su{i}")
                .label("app", "web").req({"cpu": "500m"})
                .spread_constraint(1, "topology.kubernetes.io/zone",
                                   "DoNotSchedule", {"app": "web"}).obj()
            )
        drain(sched, bs)
        bound = [p for p in store.list_pods() if p.spec.node_name]
        assert len(bound) == 28
        # capacity must hold INCLUDING the batch that was in flight at
        # rebuild time (4 cpu/node: 4x1cpu 'a' pods or mixes)
        cpu_on = {}
        for p in bound:
            m = 1000 if p.metadata.name.startswith("a") else 500
            cpu_on[p.spec.node_name] = cpu_on.get(p.spec.node_name, 0) + m
        assert all(v <= 4000 for v in cpu_on.values()), cpu_on
        # spread invariant for wave 2
        zone_of = {n.name: n.metadata.labels["topology.kubernetes.io/zone"]
                   for n in store.list_nodes()}
        counts = {}
        for p in bound:
            if p.metadata.name.startswith("s"):
                z = zone_of[p.spec.node_name]
                counts[z] = counts.get(z, 0) + 1
        assert max(counts.values()) - min(counts.values()) <= 1
        sched.stop()


class TestCarriedBatchRepartition:
    def test_pod_deleted_in_flight_is_dropped_on_carry(self):
        """ADVICE r1: a pod deleted while its solved batch was in flight
        must be dropped when the discarded batch's pods are carried over,
        not re-committed from a stale QueuedPodInfo."""
        store = ClusterStore()
        for i in range(4):
            store.add_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "8", "memory": "16Gi"}).obj()
            )
        sched, bs = make_batch_scheduler(store, max_batch=8)
        for i in range(16):
            store.create_pod(MakePod().name(f"p{i}").uid(f"u{i}")
                             .req({"cpu": "1"}).obj())
        bs.run_batch(pop_timeout=0.1)      # solve 8, hold pending
        assert bs._pending is not None
        victim = bs._pending["batchable"][0][0].pod
        store.delete_pod(victim.namespace, victim.name)
        # external cache mutation -> mirror diverges -> batch discarded,
        # pods carried over through the fresh partition
        store.add_node(
            MakeNode().name("late").capacity({"cpu": "8", "memory": "16Gi"}).obj()
        )
        drain(sched, bs)
        bound = [p for p in store.list_pods() if p.spec.node_name]
        assert len(bound) == 15
        assert victim.metadata.name not in {p.metadata.name for p in bound}
        sched.stop()


class _FailingBackend:
    """Delegating backend whose prepare() raises — a device that refuses
    the solve (a compile error, a lost chip)."""

    name = "failing"

    def __init__(self, inner):
        self.inner = inner
        self.attempts = 0

    def prepare(self, cluster, batch):
        self.attempts += 1
        raise RuntimeError("device refused the solve")

    def solve_lazy(self, *a):
        return self.inner.solve_lazy(*a)

    def materialize(self, h):
        return self.inner.materialize(h)


def _failing_scheduler(n_nodes=4, max_batch=4):
    from kubernetes_tpu.ops.pallas_solver import XlaPlanesBackend

    store = ClusterStore()
    for i in range(n_nodes):
        store.add_node(
            MakeNode().name(f"n{i}")
            .capacity({"cpu": "64", "memory": "64Gi"}).obj()
        )
    sched = Scheduler.create(
        store, feature_gates=FeatureGates({"TPUBatchScheduler": True}))
    failing = _FailingBackend(XlaPlanesBackend())
    bs = attach_batch_scheduler(sched, max_batch=max_batch,
                                backend=failing)
    sched.start()
    return store, sched, bs, failing


class TestBackendFailure:
    def test_failing_backend_raises_out_of_session(self):
        """No demotion: the chosen backend's exception leaves the
        session, and the session keeps its backend."""
        import pytest

        store, sched, bs, failing = _failing_scheduler()
        pods = [MakePod().name(f"p{i}").uid(f"u{i}")
                .req({"cpu": "100m"}).obj() for i in range(4)]
        for attempt in (1, 2):
            with pytest.raises(RuntimeError, match="refused"):
                bs.session.solve(pods)
            assert failing.attempts == attempt
            assert bs.session.backend is failing
        sched.stop()

    def test_sidecar_counts_rescued_pods(self, caplog):
        """The sidecar's safety net binds every popped pod through the
        serial path, counts each one, and logs the failure at error
        level."""
        import logging

        store, sched, bs, failing = _failing_scheduler(max_batch=4)
        with caplog.at_level(logging.ERROR, logger="kubernetes_tpu.sidecar"):
            for i in range(12):
                store.create_pod(MakePod().name(f"p{i}").uid(f"u{i}")
                                 .req({"cpu": "100m"}).obj())
            drain(sched, bs)
        bound = [p for p in store.list_pods() if p.spec.node_name]
        assert len(bound) == 12
        assert bs.serial_fallback_pods == 12
        assert failing.attempts >= 3
        assert any(r.levelno == logging.ERROR
                   and "fall back to the serial path" in r.getMessage()
                   for r in caplog.records)
        sched.stop()

    def test_healthy_batches_count_no_rescues(self):
        store = ClusterStore()
        for i in range(4):
            store.add_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "64", "memory": "64Gi"}).obj()
            )
        sched, bs = make_batch_scheduler(store, max_batch=4)
        for i in range(12):
            store.create_pod(MakePod().name(f"p{i}").uid(f"u{i}")
                             .req({"cpu": "100m"}).obj())
        drain(sched, bs)
        assert len([p for p in store.list_pods() if p.spec.node_name]) == 12
        assert bs.serial_fallback_pods == 0
        assert bs.session.active_backend == bs.session.backend.name
        sched.stop()
