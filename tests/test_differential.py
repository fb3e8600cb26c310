"""Workload-level differential ring (SURVEY.md section 4 carry-over):
the same randomized workload through the serial host path, the TPU batch
path, and the mesh-sharded batch path must yield equivalent outcomes —
identical bound-pod sets (the paths are serial-equivalent in queue
order), identical batch-vs-sharded placements (the solvers are
differentially exact), and placements that satisfy every constraint from
first principles — plus preemption equivalence under contention and
crash-recovery: a scheduler restart rebuilds all state from the store
(the control plane's "checkpoint" is the API server; SURVEY.md
section 5).

The random mix covers resource fit, hard/soft topology spread, pod
anti-affinity, node selectors, required/preferred node affinity,
preferred pod anti-affinity, taints+tolerations, priorities, gangs
(coscheduling), and PVC pods (serial-fallback contract)."""

import random
import time

import pytest

from kubernetes_tpu.api.resource import parse_quantity
from kubernetes_tpu.api.types import (
    ObjectMeta,
    PersistentVolume,
    PersistentVolumeClaim,
    StorageClass,
)
from kubernetes_tpu.apiserver.store import ClusterStore
from kubernetes_tpu.config.feature_gates import FeatureGates
from kubernetes_tpu.scheduler.scheduler import Scheduler
from kubernetes_tpu.sidecar import attach_batch_scheduler
from kubernetes_tpu.testing import MakeNode, MakePod

ZONE_KEY = "topology.kubernetes.io/zone"
N_ZONES = 4
TAINT_KEY = "dedicated"
TAINT_VAL = "batch"


def _random_cluster(rng, n_nodes, taints=True):
    """Nodes over 4 zones with mixed capacity, gold/std tiers, and ~10%
    tainted (dedicated=batch:NoSchedule)."""
    nodes = []
    for i in range(n_nodes):
        w = (
            MakeNode().name(f"n{i:04d}")
            .label(ZONE_KEY, f"z{i % N_ZONES}")
            .label("tier", "gold" if i % 4 == 0 else "std")
            .capacity({
                "cpu": str(rng.choice([8, 16, 32])),
                "memory": f"{rng.choice([16, 32, 64])}Gi",
            })
        )
        if taints and i % 10 == 9:
            w.taint(TAINT_KEY, TAINT_VAL, "NoSchedule")
        nodes.append(w.obj())
    return nodes


CSI_DRIVER = "csi.diff.driver"
CSI_LIMIT = 16


def _csi_nodes(store: ClusterStore, nodes):
    """CSINode attach limits on every node so bound CSI-PV pods exercise
    the encoder's attach-limit resource columns on the batch path."""
    from kubernetes_tpu.api.types import CSINode, CSINodeDriver

    for n in nodes:
        store.add_csi_node(CSINode(
            metadata=ObjectMeta(name=n.name),
            drivers=[CSINodeDriver(
                name=CSI_DRIVER, node_id=n.name,
                allocatable_count=CSI_LIMIT,
            )],
        ))


def _pvc_setup(store: ClusterStore, claim: str, variant: int = 0):
    """A 1:1 PV/PVC pair in six variants (round-3 coverage — bound
    claims are batch-expressible, VERDICT r2 #1 — plus the round-4
    carve-outs):

    0. bound, CSI driver (attach-limit columns), unconstrained PV
    1. bound, PV zone-labelled z0 (VolumeZone mask)
    2. bound, PV node-affinity to z1 (VolumeBinding bound-claim mask)
    3. unbound immediate — UnschedulableAndUnresolvable on both paths
       (the serial-fallback contract's original coverage)
    4. SHARED RWX claim on a non-CSI PV (one claim, many pods) —
       round-4 batchable (no attach budget)
    5. unbound WaitForFirstConsumer claim over an affinity-free
       Available PV — round-4 batchable with commit-time binding
    """
    from kubernetes_tpu.api.types import (
        NodeSelector, NodeSelectorRequirement, NodeSelectorTerm,
    )

    if store.get_storage_class("diff-sc") is None:
        store.add_storage_class(StorageClass(
            metadata=ObjectMeta(name="diff-sc"),
            provisioner="kubernetes.io/fake",
            volume_binding_mode="Immediate",
        ))
    if variant == 4:
        if store.get_pvc("default", claim) is not None:
            return      # the shared claim exists once, consumed by many
        store.add_pv(PersistentVolume(
            metadata=ObjectMeta(name=f"pv-{claim}"),
            capacity={"storage": parse_quantity("100Gi")},
            storage_class_name="diff-sc",
            access_modes=["ReadWriteMany"],
            claim_ref=f"default/{claim}",
            phase="Bound",
        ))
        store.add_pvc(PersistentVolumeClaim(
            metadata=ObjectMeta(name=claim, namespace="default"),
            storage_class_name="diff-sc",
            requests={"storage": parse_quantity("1Gi")},
            access_modes=["ReadWriteMany"],
            volume_name=f"pv-{claim}",
            phase="Bound",
        ))
        return
    if variant == 5:
        if store.get_storage_class("diff-wfc-sc") is None:
            store.add_storage_class(StorageClass(
                metadata=ObjectMeta(name="diff-wfc-sc"),
                provisioner="kubernetes.io/fake",
                volume_binding_mode="WaitForFirstConsumer",
            ))
        store.add_pv(PersistentVolume(
            metadata=ObjectMeta(name=f"pv-{claim}"),
            capacity={"storage": parse_quantity("1Gi")},
            storage_class_name="diff-wfc-sc",
            phase="Available",
        ))
        store.add_pvc(PersistentVolumeClaim(
            metadata=ObjectMeta(name=claim, namespace="default"),
            storage_class_name="diff-wfc-sc",
            requests={"storage": parse_quantity("1Gi")},
        ))
        return
    if variant == 3:
        store.add_pv(PersistentVolume(
            metadata=ObjectMeta(name=f"pv-{claim}"),
            capacity={"storage": parse_quantity("1Gi")},
            storage_class_name="diff-sc",
        ))
        store.add_pvc(PersistentVolumeClaim(
            metadata=ObjectMeta(name=claim, namespace="default"),
            storage_class_name="diff-sc",
            requests={"storage": parse_quantity("1Gi")},
        ))
        return
    labels = {}
    node_affinity = None
    driver = ""
    if variant == 0:
        driver = CSI_DRIVER
    elif variant == 1:
        labels = {ZONE_KEY: "z0"}
    elif variant == 2:
        node_affinity = NodeSelector(node_selector_terms=[
            NodeSelectorTerm(match_expressions=[
                NodeSelectorRequirement(
                    key=ZONE_KEY, operator="In", values=["z1"]),
            ])
        ])
    store.add_pv(PersistentVolume(
        metadata=ObjectMeta(name=f"pv-{claim}", labels=labels),
        capacity={"storage": parse_quantity("1Gi")},
        storage_class_name="diff-sc",
        claim_ref=f"default/{claim}",
        phase="Bound",
        node_affinity=node_affinity,
        csi_driver=driver,
    ))
    store.add_pvc(PersistentVolumeClaim(
        metadata=ObjectMeta(name=claim, namespace="default"),
        storage_class_name="diff-sc",
        requests={"storage": parse_quantity("1Gi")},
        volume_name=f"pv-{claim}",
        phase="Bound",
    ))


def _random_pods(rng, count, store=None, gangs=False, pvcs=False,
                 priorities=False, apps=20):
    """The randomized constraint mix. ``store`` is required when ``pvcs``
    is set (PV/PVC objects must exist before the pod arrives)."""
    pods = []
    gang_id = 0
    i = 0
    while i < count:
        if gangs and rng.random() < 0.05 and i + 4 <= count:
            # a 4-pod coscheduling gang (Permit-phase all-or-nothing)
            for m in range(4):
                pods.append(
                    MakePod().name(f"p{i:05d}").uid(f"u{i}")
                    .label("app", "gang")
                    .label("pod-group.scheduling.k8s.io/name",
                           f"g{gang_id}")
                    .label("pod-group.scheduling.k8s.io/min-available", "4")
                    .req({"cpu": "500m", "memory": "256Mi"}).obj()
                )
                i += 1
            gang_id += 1
            continue
        app = f"a{i % apps}"
        w = (
            MakePod().name(f"p{i:05d}").uid(f"u{i}")
            .label("app", app)
            .req({
                "cpu": f"{rng.choice([100, 250, 500, 1000])}m",
                "memory": f"{rng.choice([128, 256, 512])}Mi",
            })
        )
        if priorities:
            w.priority(rng.choice([0, 0, 0, 100, 1000]))
        kind = rng.randrange(12)
        if kind == 0:
            # dedicated label group: EVERY pod matching the selector
            # declares the constraint, so the final-state skew invariant
            # is well-defined (a plain pod sharing the label would shift
            # counts the scheduler never polices — upstream semantics)
            sp = f"sp{i % 6}"
            w.label("app", sp)
            w.spread_constraint(2, ZONE_KEY, "DoNotSchedule", {"app": sp})
        elif kind == 1:
            w.pod_anti_affinity("app", [app], "kubernetes.io/hostname")
        elif kind == 2:
            w.node_selector({"tier": "gold"})
        elif kind == 3:
            w.node_affinity_in(ZONE_KEY, ["z0", "z1"])
        elif kind == 4:
            w.preferred_node_affinity(10, "tier", ["gold"])
        elif kind == 5:
            w.preferred_pod_anti_affinity(5, "app", [app],
                                          "kubernetes.io/hostname")
        elif kind == 6:
            ss = f"ss{i % 6}"
            w.label("app", ss)
            w.spread_constraint(3, ZONE_KEY, "ScheduleAnyway", {"app": ss})
        elif kind == 7:
            w.toleration(TAINT_KEY, TAINT_VAL, "NoSchedule")
        elif kind == 8 and pvcs and store is not None:
            variant = i % 6
            claim = "claim-shared-rwx" if variant == 4 else f"claim-{i}"
            _pvc_setup(store, claim, variant=variant)
            w.pvc(claim)
        # remaining kinds: plain fit pods
        pods.append(w.obj())
        i += 1
    return pods


def _pump(sched, bs, deadline_s=120.0):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        sched.queue.flush_backoff_completed()
        progressed = (
            bs.run_batch(pop_timeout=0.0) if bs
            else sched.schedule_one(pop_timeout=0.0)
        )
        if progressed:
            continue
        if sched.queue.num_active() == 0 and sched.queue.num_backoff() == 0:
            break
        time.sleep(0.01)
    assert sched.wait_for_inflight_bindings()


def _run(nodes, pods, mode, store=None, max_batch=512):
    """mode: 'serial' | 'batch' | 'sharded'."""
    store = store or ClusterStore()
    for n in nodes:
        store.add_node(n)
    use_batch = mode != "serial"
    sched = Scheduler.create(
        store, feature_gates=FeatureGates({"TPUBatchScheduler": use_batch}),
        provider="GangSchedulingProvider",
    )
    bs = None
    if use_batch:
        backend = None
        if mode == "sharded":
            from kubernetes_tpu.parallel import ShardedBackend, make_mesh

            backend = ShardedBackend(make_mesh(8, batch_axis=2))
        bs = attach_batch_scheduler(sched, max_batch=max_batch,
                                    backend=backend)
    sched.start()
    store.create_pods(pods)
    _pump(sched, bs)
    bound = {
        p.metadata.name: p.spec.node_name
        for p in store.list_pods() if p.spec.node_name
    }
    sched.stop()
    return bound, store


# ----------------------------------------------------------------------
# the first-principles invariant checker: placements must satisfy every
# constraint independent of any scheduler code path
def _assert_valid(bound, store):
    nodes = {n.name: n for n in store.list_nodes()}
    pods = {p.metadata.name: p for p in store.list_pods()}
    cpu_used = {n: 0 for n in nodes}
    mem_used = {n: 0 for n in nodes}
    for name, node_name in bound.items():
        pod = pods[name]
        node = nodes[node_name]
        req = pod.spec.containers[0].resources.requests
        cpu_used[node_name] += int(req["cpu"].milli_value())
        mem_used[node_name] += int(req["memory"].value())
        # node selector
        for k, val in pod.spec.node_selector.items():
            assert node.metadata.labels.get(k) == val, name
        # required node affinity (In terms only, as generated here)
        aff = pod.spec.affinity
        if aff is not None and aff.node_affinity is not None:
            sel = (aff.node_affinity
                   .required_during_scheduling_ignored_during_execution)
            if sel is not None:
                ok = False
                for term in sel.node_selector_terms:
                    term_ok = all(
                        node.metadata.labels.get(expr.key) in expr.values
                        for expr in term.match_expressions
                        if expr.operator == "In"
                    )
                    ok = ok or term_ok
                assert ok, f"{name}: node affinity violated on {node_name}"
        # taints: every NoSchedule taint must be tolerated
        for taint in node.spec.taints:
            if taint.effect != "NoSchedule":
                continue
            tolerated = any(
                t.tolerates(taint) for t in pod.spec.tolerations
            )
            assert tolerated, (
                f"{name} on {node_name}: untolerated taint {taint.key}"
            )
    for n in nodes:
        alloc = nodes[n].status.allocatable
        assert cpu_used[n] <= int(alloc["cpu"].milli_value()), n
        assert mem_used[n] <= int(alloc["memory"].value()), n
    # hostname anti-affinity: at most one pod per (app, node) among
    # pods that declare it
    seen = set()
    for name, node_name in bound.items():
        pod = pods[name]
        aff = pod.spec.affinity
        if aff is None or aff.pod_anti_affinity is None:
            continue
        if not (aff.pod_anti_affinity
                .required_during_scheduling_ignored_during_execution):
            continue
        key = (pod.metadata.labels.get("app"), node_name)
        assert key not in seen, f"anti-affinity violated on {node_name}"
        seen.add(key)
    # hard topology-spread: final max-min skew over eligible domains must
    # respect maxSkew (each placement respected it stepwise, and domain
    # minima only grow, so the final state inherits the bound)
    constraints = {}
    for name, node_name in bound.items():
        pod = pods[name]
        for sc in pod.spec.topology_spread_constraints:
            if sc.when_unsatisfiable != "DoNotSchedule":
                continue
            app = pod.metadata.labels.get("app")
            constraints.setdefault(
                (sc.topology_key, app), sc.max_skew
            )
    for (key, app), max_skew in constraints.items():
        domain_values = {
            n.metadata.labels.get(key)
            for n in nodes.values() if key in n.metadata.labels
        }
        counts = {v: 0 for v in domain_values}
        for name, node_name in bound.items():
            if pods[name].metadata.labels.get("app") != app:
                continue
            v = nodes[node_name].metadata.labels.get(key)
            if v in counts:
                counts[v] += 1
        if counts:
            skew = max(counts.values()) - min(counts.values())
            assert skew <= max_skew, (
                f"spread {key}/{app}: skew {skew} > {max_skew} ({counts})"
            )
    # gang all-or-nothing
    gangs = {}
    for name, pod in pods.items():
        g = pod.metadata.labels.get("pod-group.scheduling.k8s.io/name")
        if g:
            gangs.setdefault(g, []).append(name)
    for g, members in gangs.items():
        n_bound = sum(1 for m in members if m in bound)
        assert n_bound in (0, len(members)), (
            f"gang {g}: {n_bound}/{len(members)} bound (not all-or-nothing)"
        )
    # volume feasibility: bound-PV zone labels and node affinity must
    # admit the chosen node; CSI attach counts within CSINode limits
    from kubernetes_tpu.scheduler.framework.plugins.helpers import (
        node_matches_node_selector,
    )

    attach = {}
    for name, node_name in bound.items():
        pod = pods[name]
        node = nodes[node_name]
        for v in pod.spec.volumes:
            if not v.persistent_volume_claim:
                continue
            pvc = store.get_pvc(pod.namespace, v.persistent_volume_claim)
            if pvc is None or not pvc.volume_name:
                continue
            pv = store.get_pv(pvc.volume_name)
            if pv is None:
                continue
            zone = pv.metadata.labels.get(ZONE_KEY)
            if zone is not None:
                assert node.metadata.labels.get(ZONE_KEY) in \
                    set(zone.split("__")), (
                        f"{name}: PV zone {zone} violated on {node_name}"
                    )
            assert node_matches_node_selector(node, pv.node_affinity), (
                f"{name}: PV node affinity violated on {node_name}"
            )
            if pv.csi_driver:
                attach.setdefault(
                    (node_name, pv.csi_driver), set()
                ).add(pv.name)
    for (node_name, drv), vols in attach.items():
        cn = store.get_csi_node(node_name)
        if cn is None:
            continue
        for d in cn.drivers:
            if d.name == drv and d.allocatable_count is not None:
                assert len(vols) <= d.allocatable_count, (
                    f"{node_name}: {len(vols)} {drv} attachments > "
                    f"{d.allocatable_count}"
                )


# ----------------------------------------------------------------------
class TestSerialBatchEquivalence:
    """VERDICT r1 #4: >=200 nodes / >=2k pods x >=10 seeds, full
    constraint mix, serial == batch on bound sets + invariants."""

    @pytest.mark.parametrize("seed", [7, 23, 99, 131, 204, 311, 442,
                                      557, 613, 787])
    def test_randomized_workloads(self, seed):
        rng = random.Random(seed)
        nodes = _random_cluster(rng, 200)
        store_s = ClusterStore()
        _csi_nodes(store_s, nodes)
        pods = _random_pods(rng, 2000, store=store_s, gangs=True,
                            pvcs=True, priorities=True)
        serial_bound, serial_store = _run(nodes, pods, "serial",
                                          store=store_s)
        rng = random.Random(seed)
        nodes = _random_cluster(rng, 200)
        store_b = ClusterStore()
        _csi_nodes(store_b, nodes)
        pods = _random_pods(rng, 2000, store=store_b, gangs=True,
                            pvcs=True, priorities=True)
        batch_bound, batch_store = _run(nodes, pods, "batch",
                                        store=store_b)
        assert set(serial_bound) == set(batch_bound), (
            f"seed {seed}: bound sets differ: "
            f"{sorted(set(serial_bound) ^ set(batch_bound))[:20]}"
        )
        _assert_valid(serial_bound, serial_store)
        _assert_valid(batch_bound, batch_store)


class TestShardedEquivalence:
    """serial == batch == sharded at the workload level: the sharded
    backend rides the full sidecar path on the 8-device CPU mesh, and
    its placements must be IDENTICAL to the single-chip batch path
    (differential exactness), which must match serial on bound sets."""

    @pytest.mark.parametrize("seed", [11, 47, 83])
    def test_three_way(self, seed):
        def make(seed):
            rng = random.Random(seed)
            nodes = _random_cluster(rng, 200)
            pods = _random_pods(rng, 600, priorities=False)
            return nodes, pods

        nodes, pods = make(seed)
        serial_bound, serial_store = _run(nodes, pods, "serial")
        nodes, pods = make(seed)
        batch_bound, batch_store = _run(nodes, pods, "batch")
        nodes, pods = make(seed)
        sharded_bound, sharded_store = _run(nodes, pods, "sharded")

        assert batch_bound == sharded_bound, (
            f"seed {seed}: batch vs sharded placements diverge: "
            f"{[(k, batch_bound.get(k), sharded_bound.get(k)) for k in set(batch_bound) ^ set(sharded_bound) or list(batch_bound)[:1] if batch_bound.get(k) != sharded_bound.get(k)][:10]}"
        )
        assert set(serial_bound) == set(batch_bound)
        _assert_valid(serial_bound, serial_store)
        _assert_valid(batch_bound, batch_store)
        _assert_valid(sharded_bound, sharded_store)


class TestPreemptionEquivalence:
    """Contention + priorities: high-priority pods must preempt enough
    victims to bind on BOTH paths (the batch path's mass-decline branch
    feeds the same PostFilter/preemption flow), and every evicted victim
    must be lower-priority than some preemptor."""

    @pytest.mark.parametrize("seed", [5, 61])
    def test_preemption_under_contention(self, seed):
        for mode in ("serial", "batch"):
            rng = random.Random(seed)
            nodes = _random_cluster(rng, 40, taints=False)
            # fill the cluster solid with low-priority 1-cpu pods
            total_cpu = sum(
                int(n.status.allocatable["cpu"].milli_value()) // 1000
                for n in nodes
            )
            fillers = [
                MakePod().name(f"low{i:04d}").uid(f"lu{i}")
                .label("app", "low").priority(0)
                .req({"cpu": "1", "memory": "64Mi"}).obj()
                for i in range(total_cpu)
            ]
            store = ClusterStore()
            for n in nodes:
                store.add_node(n)
            use_batch = mode == "batch"
            sched = Scheduler.create(store, feature_gates=FeatureGates(
                {"TPUBatchScheduler": use_batch}))
            bs = attach_batch_scheduler(sched, max_batch=256) \
                if use_batch else None
            sched.start()
            try:
                self._drive(sched, bs, store, fillers, total_cpu, mode)
            finally:
                sched.stop()

    def _drive(self, sched, bs, store, fillers, total_cpu, mode):
            store.create_pods(fillers)
            _pump(sched, bs)
            n_filled = sum(
                1 for p in store.list_pods() if p.spec.node_name
            )
            assert n_filled == total_cpu  # solid
            # now 100 high-priority pods: all must preempt their way in
            high = [
                MakePod().name(f"high{i:03d}").uid(f"hu{i}")
                .label("app", "high").priority(1000)
                .req({"cpu": "1", "memory": "64Mi"}).obj()
                for i in range(100)
            ]
            store.create_pods(high)
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                sched.queue.flush_backoff_completed()
                if bs is not None:
                    bs.run_batch(pop_timeout=0.0)
                else:
                    sched.schedule_one(pop_timeout=0.0)
                bound_high = sum(
                    1 for p in store.list_pods()
                    if p.metadata.labels.get("app") == "high"
                    and p.spec.node_name
                )
                if bound_high == 100:
                    break
                time.sleep(0.005)
            sched.wait_for_inflight_bindings()
            bound_high = sum(
                1 for p in store.list_pods()
                if p.metadata.labels.get("app") == "high"
                and p.spec.node_name
            )
            assert bound_high == 100, (
                f"{mode}: only {bound_high}/100 high-priority pods bound"
            )
            # every victim evicted was lower-priority (only "low" pods
            # may have disappeared)
            remaining = {p.metadata.name for p in store.list_pods()}
            assert all(p.metadata.name in remaining for p in high)
            bound = {
                p.metadata.name: p.spec.node_name
                for p in store.list_pods() if p.spec.node_name
            }
            _assert_valid(bound, store)


class TestPreemptionPlannerEquivalence:
    """Round-3 victim planner under HETEROGENEOUS contention: mixed
    victim sizes and priorities, PDB-covered pods the planner must
    never evict, and preemptors needing MULTI-victim sets — on both
    paths, with invariants on who died."""

    @pytest.mark.parametrize("seed", [17, 43])
    def test_mixed_priority_preemption(self, seed):
        from kubernetes_tpu.api.types import (
            ObjectMeta, PodDisruptionBudget,
        )
        from kubernetes_tpu.api.labels import LabelSelector

        for mode in ("serial", "batch"):
            rng = random.Random(seed)
            nodes = _random_cluster(rng, 30, taints=False)
            store = ClusterStore()
            for n in nodes:
                store.add_node(n)
            # mixed fillers: priorities 0/10/50, sizes 1-2 cpu; a
            # PDB-protected subset that must survive
            fillers = []
            for i, n in enumerate(nodes):
                cap = int(n.status.allocatable["cpu"].milli_value()) // 1000
                used = 0
                j = 0
                while used + 1 <= cap:
                    size = rng.choice([1, 1, 2])
                    if used + size > cap:
                        size = 1
                    prio = rng.choice([0, 0, 10, 50])
                    protected = rng.random() < 0.1
                    w = (MakePod().name(f"f{i:02d}-{j}")
                         .uid(f"fu{i}-{j}")
                         .label("app", "protected" if protected else "low")
                         .priority(prio)
                         .req({"cpu": str(size), "memory": "64Mi"}))
                    fillers.append(w.obj())
                    used += size
                    j += 1
            pdb = PodDisruptionBudget(
                metadata=ObjectMeta(name="guard", namespace="default"),
                label_selector=LabelSelector(
                    match_labels={"app": "protected"}),
            )
            pdb.status.disruptions_allowed = 0
            store.add_pdb(pdb)
            use_batch = mode == "batch"
            sched = Scheduler.create(store, feature_gates=FeatureGates(
                {"TPUBatchScheduler": use_batch}))
            bs = attach_batch_scheduler(sched, max_batch=128) \
                if use_batch else None
            sched.start()
            try:
                store.create_pods(fillers)
                _pump(sched, bs)
                protected_before = {
                    p.metadata.name for p in store.list_pods()
                    if p.metadata.labels.get("app") == "protected"
                }
                # 40 high-priority preemptors needing 2 cpu each
                # (multi-victim sets where fillers are 1-cpu)
                high = [
                    MakePod().name(f"high{i:02d}").uid(f"hi{i}")
                    .label("app", "high").priority(1000)
                    .req({"cpu": "2", "memory": "64Mi"}).obj()
                    for i in range(40)
                ]
                store.create_pods(high)
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    sched.queue.flush_backoff_completed()
                    if bs is not None:
                        if bs.run_batch(pop_timeout=0.0):
                            continue
                    else:
                        sched.schedule_one(pop_timeout=0.0)
                    n_high = sum(
                        1 for p in store.list_pods()
                        if p.metadata.labels.get("app") == "high"
                        and p.spec.node_name
                    )
                    if n_high == 40:
                        break
                    time.sleep(0.005)
                sched.wait_for_inflight_bindings()
                n_high = sum(
                    1 for p in store.list_pods()
                    if p.metadata.labels.get("app") == "high"
                    and p.spec.node_name
                )
                assert n_high == 40, (
                    f"seed {seed} {mode}: {n_high}/40 preemptors bound"
                )
                # PDB-protected pods all survived on both paths
                protected_after = {
                    p.metadata.name for p in store.list_pods()
                    if p.metadata.labels.get("app") == "protected"
                }
                assert protected_after == protected_before, (
                    f"seed {seed} {mode}: PDB-protected pods evicted: "
                    f"{sorted(protected_before - protected_after)}"
                )
                # only priority < 1000 pods may have vanished
                assert all(
                    p.metadata.labels.get("app") != "high" or
                    p.spec.node_name
                    for p in store.list_pods()
                )
                bound = {
                    p.metadata.name: p.spec.node_name
                    for p in store.list_pods() if p.spec.node_name
                }
                _assert_valid(bound, store)
            finally:
                sched.stop()


class TestUnschedulableEquivalence:
    """Deterministically-impossible pods must be declined by BOTH paths
    (and by the device's mass-decline fast path), never bound."""

    def test_impossible_pods(self):
        rng = random.Random(3)
        nodes = _random_cluster(rng, 50)
        possible = _random_pods(rng, 200)
        impossible = [
            MakePod().name(f"imp{i:03d}").uid(f"iu{i}")
            .node_selector({"tier": "platinum"})  # matches nothing
            .req({"cpu": "100m"}).obj()
            for i in range(100)
        ]
        for mode in ("serial", "batch"):
            bound, store = _run(nodes, possible + impossible, mode)
            assert len(bound) == 200, mode
            assert not any(n.startswith("imp") for n in bound), mode
            _assert_valid(bound, store)


class TestCrashRecovery:
    def test_scheduler_restart_resumes_from_store(self):
        """Kill the scheduler mid-workload; a fresh instance rebuilds
        cache/queue from the store (list+watch) and finishes. Nothing is
        persisted locally — exactly the reference's recovery model."""
        store = ClusterStore()
        for i in range(6):
            store.add_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "8", "memory": "16Gi"}).obj()
            )
        sched1 = Scheduler.create(
            store, feature_gates=FeatureGates({"TPUBatchScheduler": True})
        )
        bs1 = attach_batch_scheduler(sched1, max_batch=8)
        sched1.start()
        for i in range(40):
            store.create_pod(
                MakePod().name(f"p{i}").uid(f"u{i}").req({"cpu": "500m"}).obj()
            )
        # schedule a little, then crash (stop without draining). Two
        # cycles: the pipelined batch path solves on the first and
        # commits on the second — and a batch still in flight at crash
        # time must be recoverable from the store regardless.
        bs1.run_batch(pop_timeout=0.1)
        bs1.run_batch(pop_timeout=0.0)
        sched1.wait_for_inflight_bindings()
        sched1.stop()
        partial = sum(1 for p in store.list_pods() if p.spec.node_name)
        assert 0 < partial < 40

        sched2 = Scheduler.create(
            store, feature_gates=FeatureGates({"TPUBatchScheduler": True})
        )
        bs2 = attach_batch_scheduler(sched2, max_batch=8)
        sched2.start()  # replays store state: bound pods -> cache, rest -> queue
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            sched2.queue.flush_backoff_completed()
            if bs2.run_batch(pop_timeout=0.0):
                continue
            if sched2.queue.num_active() == 0 and \
                    sched2.queue.num_backoff() == 0:
                break
            time.sleep(0.01)
        assert sched2.wait_for_inflight_bindings()
        bound = [p for p in store.list_pods() if p.spec.node_name]
        assert len(bound) == 40
        # capacity respected across the restart boundary (8 cpu, 500m)
        per_node = {}
        for p in bound:
            per_node[p.spec.node_name] = per_node.get(p.spec.node_name, 0) + 1
        assert all(c <= 16 for c in per_node.values())
        sched2.stop()


class TestShardedFullMix:
    """VERDICT r4 next #4: the mesh-sharded backend under the FULL
    constraint mix — priorities+preemption, gangs, and PVCs at 200
    nodes / 2000 pods across 5 seeds. Three-way: sharded placements
    must be IDENTICAL to single-chip batch; both must match serial on
    bound sets; every placement passes the first-principles checker."""

    @pytest.mark.parametrize("seed", [7, 23, 131, 442, 787])
    def test_three_way_full_mix(self, seed):
        def make(seed):
            rng = random.Random(seed)
            nodes = _random_cluster(rng, 200)
            store = ClusterStore()
            _csi_nodes(store, nodes)
            pods = _random_pods(rng, 2000, store=store, gangs=True,
                                pvcs=True, priorities=True)
            return nodes, pods, store

        nodes, pods, store_s = make(seed)
        serial_bound, serial_store = _run(nodes, pods, "serial",
                                          store=store_s)
        nodes, pods, store_b = make(seed)
        batch_bound, batch_store = _run(nodes, pods, "batch",
                                        store=store_b)
        nodes, pods, store_m = make(seed)
        sharded_bound, sharded_store = _run(nodes, pods, "sharded",
                                            store=store_m)
        diverged = [
            (k, batch_bound.get(k), sharded_bound.get(k))
            for k in set(batch_bound) | set(sharded_bound)
            if batch_bound.get(k) != sharded_bound.get(k)
        ]
        assert not diverged, (
            f"seed {seed}: batch vs sharded diverge on "
            f"{len(diverged)} pods: {diverged[:10]}"
        )
        assert set(serial_bound) == set(batch_bound), (
            f"seed {seed}: serial vs batch bound sets differ: "
            f"{sorted(set(serial_bound) ^ set(batch_bound))[:20]}"
        )
        _assert_valid(serial_bound, serial_store)
        _assert_valid(batch_bound, batch_store)
        _assert_valid(sharded_bound, sharded_store)
