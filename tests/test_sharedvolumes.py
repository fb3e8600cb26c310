"""Shared CSI volumes on the batch path (VERDICT r4 next #5): per-volume
attach planes carry "volume v attached on node n" in solver state, so a
shared (RWX/ROX) claim's attach demand is CONDITIONAL per node — 1 only
where the volume isn't attached yet — matching csi.go's
``len(in_use | wanted)`` set semantics exactly (reference
``nodevolumelimits/csi.go``). Before round 5 these pods rode the serial
path (the 10% slice that held SchedulingSharedPVs at ~413 pods/s)."""

import time

from kubernetes_tpu.api.types import (
    CSINode,
    CSINodeDriver,
    ObjectMeta,
    PersistentVolume,
    PersistentVolumeClaim,
    Volume,
)
from kubernetes_tpu.apiserver.store import ClusterStore
from kubernetes_tpu.config.feature_gates import FeatureGates
from kubernetes_tpu.scheduler.scheduler import Scheduler
from kubernetes_tpu.sidecar import attach_batch_scheduler
from kubernetes_tpu.testing import MakeNode, MakePod


def _cluster(n_nodes=4, limit=2, driver="csi.x"):
    store = ClusterStore()
    for i in range(n_nodes):
        store.add_node(MakeNode().name(f"n{i}")
                       .capacity({"cpu": "32", "memory": "64Gi"}).obj())
        store.add_csi_node(CSINode(
            metadata=ObjectMeta(name=f"n{i}"),
            drivers=[CSINodeDriver(name=driver,
                                   allocatable_count=limit)],
        ))
    return store


def _shared_claim(store, name, driver="csi.x"):
    store.add_pv(PersistentVolume(
        metadata=ObjectMeta(name=f"pv-{name}"),
        access_modes=["ReadWriteMany"], csi_driver=driver,
        claim_ref=f"default/{name}", phase="Bound",
    ))
    store.add_pvc(PersistentVolumeClaim(
        metadata=ObjectMeta(name=name, namespace="default"),
        access_modes=["ReadWriteMany"], volume_name=f"pv-{name}",
    ))


def _pod(name, claim, cpu="100m"):
    p = MakePod().name(name).uid(f"u-{name}").req({"cpu": cpu}).obj()
    p.spec.volumes = [Volume(name="data",
                             persistent_volume_claim=claim)]
    return p


def _run_batch(store, pods, max_batch=64, timeout=120.0):
    gates = FeatureGates({"TPUBatchScheduler": True})
    sched = Scheduler.create(store, feature_gates=gates,
                             provider="GangSchedulingProvider")
    bs = attach_batch_scheduler(sched, max_batch=max_batch)
    sched.start()
    store.create_pods(pods)
    deadline = time.time() + timeout
    while time.time() < deadline:
        bs.run_batch(pop_timeout=0.05)
        sched.queue.flush_backoff_completed()
        if all(p.spec.node_name or p.status.phase in ("Failed",)
               or any(c.type == "PodScheduled" and c.status == "False"
                      for c in p.status.conditions)
               for p in store.list_pods()):
            break
    sched.wait_for_inflight_bindings()
    placements = {p.metadata.name: p.spec.node_name
                  for p in store.list_pods() if p.spec.node_name}
    backend = bs.session._active.name
    sched.stop()
    return placements, backend


def _attach_sets(store):
    per_node = {}
    for p in store.list_pods():
        if p.spec.node_name and p.spec.volumes:
            pvc = store.get_pvc("default",
                                p.spec.volumes[0].persistent_volume_claim)
            if pvc and pvc.volume_name:
                per_node.setdefault(p.spec.node_name,
                                    set()).add(pvc.volume_name)
    return per_node


class TestSharedVolumePlanes:
    def test_shared_claims_ride_the_batch_path(self):
        """10 pods per shared claim schedule on-device (not serial) and
        never violate the per-node attach limit set-wise."""
        store = _cluster(n_nodes=8, limit=2)
        for c in range(4):
            _shared_claim(store, f"claim{c}")
        pods = [_pod(f"p{i}", f"claim{i % 4}") for i in range(40)]
        placements, backend = _run_batch(store, pods)
        assert len(placements) == 40
        assert backend in ("xla-planes", "cpp")   # the sv-capable backends
        for node, vols in _attach_sets(store).items():
            assert len(vols) <= 2, (node, vols)

    def test_attached_volume_costs_nothing_on_its_node(self):
        """A node whose budget is FULL but already holds the pod's
        volume must still admit the pod (demand 0 there) — the exact
        set-semantics case the additive column model cannot express."""
        store = _cluster(n_nodes=2, limit=1)
        _shared_claim(store, "shared")
        _shared_claim(store, "other")
        # n0 holds pv-shared (existing pod); n1's single slot is
        # consumed by pv-other
        seed0 = _pod("seed0", "shared")
        seed1 = _pod("seed1", "other")
        store.create_pod(seed0)
        store.bind("default", "seed0", seed0.uid, "n0")
        store.create_pod(seed1)
        store.bind("default", "seed1", seed1.uid, "n1")
        placements, _backend = _run_batch(
            store, [_pod("joiner", "shared")])
        # n1 is infeasible (attach 1/1 with a DIFFERENT volume); n0 is
        # free because the volume is already attached there
        assert placements["joiner"] == "n0"

    def test_in_batch_attachment_is_reused(self):
        """Two same-claim pods in ONE batch: the second sees the
        first's attachment in carried solver state. With every other
        node's budget exhausted, both must co-locate."""
        store = _cluster(n_nodes=3, limit=1)
        _shared_claim(store, "shared")
        for i, blocker in enumerate(("blk-a", "blk-b")):
            _shared_claim(store, blocker)
            seed = _pod(f"seed{i}", blocker)
            store.create_pod(seed)
            store.bind("default", f"seed{i}", seed.uid, f"n{i + 1}")
        placements, _backend = _run_batch(
            store, [_pod("first", "shared"), _pod("second", "shared")])
        assert placements["first"] == "n0"
        assert placements["second"] == "n0"   # attach slot reused

    def test_serial_and_batch_agree_on_bound_sets(self):
        """Differential: same pods bound on both paths, attach
        invariant holds on both (the repo's serial==batch contract)."""
        def build():
            store = _cluster(n_nodes=6, limit=2)
            for c in range(5):
                _shared_claim(store, f"claim{c}")
            pods = [_pod(f"p{i}", f"claim{i % 5}") for i in range(60)]
            return store, pods

        store_b, pods = build()
        batch_placements, _ = _run_batch(store_b, pods)

        store_s, pods = build()
        sched = Scheduler.create(
            store_s, feature_gates=FeatureGates(
                {"TPUBatchScheduler": False}),
            provider="GangSchedulingProvider")
        sched.start()
        store_s.create_pods(pods)
        deadline = time.time() + 60
        while time.time() < deadline:
            sched.schedule_one(pop_timeout=0.05)
            sched.queue.flush_backoff_completed()
            if sum(1 for p in store_s.list_pods()
                   if p.spec.node_name) >= len(batch_placements):
                break
        sched.wait_for_inflight_bindings()
        serial_placements = {
            p.metadata.name: p.spec.node_name
            for p in store_s.list_pods() if p.spec.node_name
        }
        sched.stop()
        assert set(serial_placements) == set(batch_placements)
        for store in (store_b, store_s):
            for node, vols in _attach_sets(store).items():
                assert len(vols) <= 2, (node, vols)

    def test_multi_shared_volume_pod_keeps_host_path(self):
        """A pod with TWO shared CSI volumes is inexpressible (one
        plane reference per step) — it still schedules, serially."""
        store = _cluster(n_nodes=2, limit=2)
        _shared_claim(store, "a")
        _shared_claim(store, "b")
        p = MakePod().name("multi").uid("u-multi").req(
            {"cpu": "100m"}).obj()
        p.spec.volumes = [
            Volume(name="v1", persistent_volume_claim="a"),
            Volume(name="v2", persistent_volume_claim="b"),
        ]
        placements, _backend = _run_batch(store, [p])
        assert "multi" in placements

    def test_over_limit_node_rejects_even_attached_volume_pods(self):
        """csi.go rejects ANY csi-volume pod on a node whose existing
        attachments exceed its (shrunk) limit — including a pod whose
        shared volume is already attached there. The device mirrors
        this by clearing attached bits on over-limit nodes (demand
        reads 1, the clamped column rejects)."""
        store = _cluster(n_nodes=2, limit=1)
        _shared_claim(store, "sharedA")
        _shared_claim(store, "sharedB")
        # n0 carries BOTH volumes (over its limit of 1 — e.g. the
        # CSINode limit shrank after they attached)
        for i, c in enumerate(("sharedA", "sharedB")):
            seed = _pod(f"seed{i}", c)
            store.create_pod(seed)
            store.bind("default", f"seed{i}", seed.uid, "n0")
        placements, _backend = _run_batch(store, [_pod("j", "sharedA")])
        # n0 is over-limit (2 > 1): host refuses it; n1 takes the pod
        # with a fresh attachment
        assert placements.get("j") == "n1"

    def test_sharded_matches_single_chip_on_shared_volumes(self):
        """The mesh-sharded backend carries the sv planes too (node-
        sharded, fully local update): placements are IDENTICAL to the
        single-chip batch path on a shared-volume workload."""
        import jax
        import pytest

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices (virtual CPU mesh)")
        from kubernetes_tpu.parallel import ShardedBackend, make_mesh

        def build():
            store = _cluster(n_nodes=8, limit=2)
            for c in range(4):
                _shared_claim(store, f"claim{c}")
            pods = [_pod(f"p{i}", f"claim{i % 4}") for i in range(48)]
            return store, pods

        store_b, pods = build()
        batch_placements, _ = _run_batch(store_b, pods)

        store_m, pods = build()
        gates = FeatureGates({"TPUBatchScheduler": True})
        sched = Scheduler.create(store_m, feature_gates=gates,
                                 provider="GangSchedulingProvider")
        bs = attach_batch_scheduler(
            sched, max_batch=64,
            backend=ShardedBackend(make_mesh(8, batch_axis=2)))
        sched.start()
        store_m.create_pods(pods)
        deadline = time.time() + 120
        while time.time() < deadline:
            bs.run_batch(pop_timeout=0.05)
            sched.queue.flush_backoff_completed()
            if sum(1 for p in store_m.list_pods()
                   if p.spec.node_name) >= 48:
                break
        sched.wait_for_inflight_bindings()
        sharded_placements = {
            p.metadata.name: p.spec.node_name
            for p in store_m.list_pods() if p.spec.node_name
        }
        assert bs.session._active.name == "sharded"
        sched.stop()
        diverged = [
            (k, batch_placements.get(k), sharded_placements.get(k))
            for k in set(batch_placements) | set(sharded_placements)
            if batch_placements.get(k) != sharded_placements.get(k)
        ]
        assert not diverged, diverged[:10]
        for node, vols in _attach_sets(store_m).items():
            assert len(vols) <= 2, (node, vols)
