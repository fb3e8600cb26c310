"""The 10×-tier scale harness: partitioned control plane under kubemark.

Drives the full sharded deployment shape — P apiserver processes (one
store partition each, its own GIL: the Pathways-style sharded
coordinator), a kubemark ``HollowFleet`` registering tens of thousands
of hollow nodes and heartbeating their leases over the fabric, creator
children streaming pods across namespaces (so the (kind,
namespace-hash) partition key spreads them), and M scheduler replicas
in the parent (pod-hash queue sharding + disjoint node pools by
default), each with its own partition-aware client merging one watch
stream per (kind, partition).

The committed ``scale10x`` bench row (bench.py --config scale10x) runs
TWO arms at the same scale — partitions=P vs partitions=1 — plus the
in-process **conflict chaos cell** (replicas deliberately overlapping
with the capacity guard + bind-time ledger arbitrating), and reports:

- aggregate pods/s per arm and the partitioned/single speedup
  ("sharding must pay for itself, not just exist");
- invariants: zero lost pods, zero double-binds (every pod bound
  exactly once, no node oversubscribed — checked against per-partition
  server truth, not client-side optimism), and in the conflict cell
  ``stale_binds_rejected_total`` > 0 with every conflict resolved;
- the PR 8 observability wire-up: every partition server and scheduler
  replica registry federated (``federation_instances`` ≥ partitions +
  replicas), SLO verdicts from the live engine, and a ``shards[...]``
  diag segment.

Child mains here must stay jax-free (harness/__init__ contract): the
scheduler — and so the solver — runs only in the parent.
"""

from __future__ import annotations

import multiprocessing as mp
import time
from typing import Callable, Dict, List, Optional

from kubernetes_tpu.harness.burst import make_burst_pods, stream_arrivals

SCHEDULER_TOKEN = "scale-scheduler-token"
CREATOR_TOKEN = "scale-creator-token"
KUBEMARK_TOKEN = "scale-kubemark-token"

POD_CPU_MILLI = 500
POD_MEMORY = "500Mi"


def scale_namespaces(partitions: int, per_partition: int = 2) -> List[str]:
    """Namespaces whose hashes cover every partition (the partition key
    is (kind, namespace-hash): a single-namespace burst would hash
    whole into one shard). Greedily picks names until each partition
    owns ``per_partition`` of them."""
    if partitions <= 1:
        return ["default"]
    from kubernetes_tpu.apiserver.partition import partition_for

    want = {p: per_partition for p in range(partitions)}
    out: List[str] = []
    i = 0
    while any(v > 0 for v in want.values()) and i < 10_000:
        ns = f"scale-{i}"
        p = partition_for("Pod", ns, None, partitions)
        if want.get(p, 0) > 0:
            want[p] -= 1
            out.append(ns)
        i += 1
    return out


# ---------------------------------------------------------------------------
# child mains (spawned; jax-free)


def _scale_apiserver_main(conn, index: int, count: int,
                          wal_dir: Optional[str]) -> None:
    """One partition of the sharded control plane: a plain ClusterStore
    (partition ``index`` of the keyspace) behind a full APIServer —
    authn, RBAC, admission, APF, watch coalescing all live."""
    from kubernetes_tpu.apiserver.rbac import provision_bootstrap_policy
    from kubernetes_tpu.apiserver.rest import APIServer
    from kubernetes_tpu.apiserver.store import ClusterStore
    from kubernetes_tpu.apiserver.wal import attach_wal
    from kubernetes_tpu.utils.gctune import tune_for_throughput

    tune_for_throughput()
    store = ClusterStore()
    wal = attach_wal(store, wal_dir, snapshot_every=200_000,
                     async_serialize=True) if wal_dir else None
    authz = provision_bootstrap_policy(store)
    authz.add_user_to_group("scale-creator", "system:masters")
    authz.add_user_to_group("scale-kubemark", "system:masters")
    tokens = {SCHEDULER_TOKEN: "system:kube-scheduler",
              CREATOR_TOKEN: "scale-creator",
              KUBEMARK_TOKEN: "scale-kubemark"}
    server = APIServer(store=store, authorizer=authz, tokens=tokens,
                       partition=(index, count)).start()
    conn.send(server.url)
    while True:
        msg = conn.recv()
        if msg == "stop":
            break
        if msg == "counts":
            # invariant inputs computed CHILD-side (shipping 500k pods
            # to the parent to re-derive them would dwarf the row):
            # per-node requested milli-CPU for pods THIS partition
            # holds, allocatable for nodes it holds — the parent joins
            # across partitions (a pod and its node usually live in
            # different shards).
            pods = store.list_pods()
            node_req: Dict[str, int] = {}
            for p in pods:
                if p.spec.node_name:
                    node_req[p.spec.node_name] = node_req.get(
                        p.spec.node_name, 0) + POD_CPU_MILLI
            node_alloc: Dict[str, int] = {}
            for n in store.list_nodes():
                q = (n.status.allocatable or n.status.capacity or {}).get(
                    "cpu")
                node_alloc[n.name] = int(q.milli_value()) if q is not None \
                    else 1 << 62
            if wal is not None:
                wal.drain()
            conn.send({
                "partition": index,
                "pods_total": len(pods),
                "pods_bound": sum(1 for p in pods if p.spec.node_name),
                "node_req": node_req,
                "node_alloc": node_alloc,
                "nodes": len(node_alloc),
            })
    server.shutdown_server()
    if wal is not None:
        wal.close()
    conn.send("stopped")


def _scale_driver_main(conn, urls: List[str], qps: Optional[float],
                       creator_clients: int) -> None:
    """The kubemark + workload driver child: registers the hollow
    fleet (bulk NodeList posts fanned per partition + ONE shared
    heartbeat thread renewing leases through the lease verb) and
    streams pod bursts through partition-aware creator clients."""
    from kubernetes_tpu.client.restcluster import RestClusterClient
    from kubernetes_tpu.kubemark import HollowFleet
    from kubernetes_tpu.utils.gctune import tune_for_throughput

    tune_for_throughput()
    fleet_client = RestClusterClient(urls[0], partition_urls=urls,
                                     token=KUBEMARK_TOKEN, qps=None)
    fleet = HollowFleet(fleet_client, interval=30.0)
    creators = [RestClusterClient(urls[0], partition_urls=urls,
                                  token=CREATOR_TOKEN, qps=qps)
                for _ in range(max(1, creator_clients))]
    CHUNK = 1024
    while True:
        msg = conn.recv()
        if msg == "stop":
            break
        cmd = msg[0]
        if cmd == "nodes":
            _cmd, count, cpu = msg
            try:
                fleet.register(count, cpu=str(cpu), progress=None)
                fleet.start()
            except Exception as e:  # noqa: BLE001 — the parent must
                # see the real registration failure, not an unpack
                # error on the shutdown sentinel
                conn.send(("error", str(e)[:500]))
                continue
            conn.send(("done", count))
        elif cmd == "pods":
            _cmd, count, offset, namespaces = msg
            # shared open-loop injection helper at rate=∞ (lazy
            # per-chunk pod construction: a 500k-pod burst must never
            # materialize at once), per-chunk client rotation. The
            # reported count is the SERVER-CONFIRMED create total —
            # a partial bulk create must not masquerade as complete
            rotation, confirmed = [0], [0]

            def send(items):
                client = creators[rotation[0] % len(creators)]
                rotation[0] += 1
                confirmed[0] += client.create_objects_bulk(
                    "Pod", items)

            def gen():
                for lo in range(0, count, CHUNK):
                    for p in make_burst_pods(
                            min(CHUNK, count - lo),
                            cpu_milli=POD_CPU_MILLI, memory=POD_MEMORY,
                            name_prefix="scale-", uid_prefix="sc-",
                            offset=offset + lo, namespaces=namespaces):
                        yield (0.0, p)

            try:
                stream_arrivals(gen(), send, chunk=CHUNK,
                                time_scale=0.0)
                conn.send(("done", confirmed[0]))
            except Exception as e:  # noqa: BLE001
                conn.send(("error", str(e)[:500]))
    fleet.stop()
    conn.send("stopped")


# ---------------------------------------------------------------------------
# parent-side arms


def _shard_diag(partitions: int, replicas: int, conflicts: int,
                capacity_rejects: int, balance: Optional[float],
                watch_streams: Optional[int]) -> None:
    import sys

    from kubernetes_tpu.harness import diagfmt

    seg = diagfmt.format_shards({
        "partitions": partitions, "replicas": replicas,
        "conflicts": conflicts, "capacity_rejects": capacity_rejects,
        "balance": balance, "watch_streams": watch_streams,
    })
    print(diagfmt.format_diag([seg]), file=sys.stderr, flush=True)


def _conflict_counts() -> Dict[str, float]:
    from kubernetes_tpu.metrics.fabric_metrics import fabric_metrics

    return {lbl[0]: v for _, lbl, v
            in fabric_metrics().stale_binds_rejected_total.collect()}


def _conflict_delta(before: Dict[str, float]) -> Dict[str, int]:
    after = _conflict_counts()
    return {k: int(v - before.get(k, 0.0)) for k, v in after.items()
            if v - before.get(k, 0.0) > 0}


def run_scale_arm_rest(
    nodes: int,
    pods: int,
    partitions: int,
    replicas: int = 2,
    use_batch: bool = True,
    max_batch: int = 1024,
    qps: Optional[float] = 5000.0,
    creator_clients: int = 4,
    node_cpu: int = 32,
    shard_nodes: bool = True,
    wal: bool = False,
    wait_timeout: float = 1800.0,
    progress: Optional[Callable[[str], None]] = None,
) -> Dict:
    """One measured arm over the REAL fabric: P apiserver processes, a
    hollow fleet, creator children, M scheduler replicas in-parent."""
    import tempfile

    from kubernetes_tpu.client.restcluster import RestClusterClient
    from kubernetes_tpu.harness.perf import (
        ThroughputCollector,
        attach_slo_baseline,
        collect_freshness,
        reset_sli_window,
    )
    from kubernetes_tpu.observability.devprof import get_devprof
    from kubernetes_tpu.scheduler.replicas import SchedulerReplicaSet

    reset_sli_window()
    get_devprof().reset(workload=f"scale10x/p{partitions}")
    conflicts_before = _conflict_counts()
    ctx = mp.get_context("spawn")
    wal_root = tempfile.mkdtemp(prefix="ktpu-scale-wal-") if wal else None

    servers = []
    urls: List[str] = []
    for i in range(partitions):
        parent_conn, child_conn = ctx.Pipe()
        seg = f"{wal_root}/p{i}" if wal_root else None
        if seg:
            import os

            os.makedirs(seg, exist_ok=True)
        proc = ctx.Process(target=_scale_apiserver_main,
                           args=(child_conn, i, partitions, seg),
                           daemon=True)
        proc.start()
        servers.append((parent_conn, proc))
        urls.append(parent_conn.recv())

    drv_conn, drv_child = ctx.Pipe()
    drv_proc = ctx.Process(target=_scale_driver_main,
                           args=(drv_child, urls, qps, creator_clients),
                           daemon=True)
    drv_proc.start()

    namespaces = scale_namespaces(partitions)
    rs = None   # SchedulerReplicaSet (lazily imported — jax-free module)
    collector = None
    row: Dict = {}

    def teardown() -> None:
        try:
            drv_conn.send("stop")
        except (BrokenPipeError, OSError):
            pass
        for conn, _proc in servers:
            try:
                conn.send("stop")
            except (BrokenPipeError, OSError):
                pass
        for conn, proc in [(drv_conn, drv_proc)] + list(servers):
            try:
                if conn.poll(5.0):
                    conn.recv()
            except (EOFError, OSError):
                pass
            proc.join(timeout=5.0)
            if proc.is_alive():
                proc.terminate()
        if wal_root:
            import shutil

            shutil.rmtree(wal_root, ignore_errors=True)

    try:
        # routing sanity: every endpoint must serve the partition index
        # the clients will route to it (shuffled URLs fail HERE, not as
        # silently half-empty shards)
        probe = RestClusterClient(urls[0], partition_urls=urls,
                                  token=SCHEDULER_TOKEN, qps=None)
        probe.check_partition_topology()
        probe._drop_conn()

        # -- kubemark fleet ------------------------------------------
        drv_conn.send(("nodes", nodes, node_cpu))
        status, n = drv_conn.recv()
        if status == "error":
            raise RuntimeError(f"hollow-fleet registration failed: {n}")
        if progress:
            progress(f"scale10x[p{partitions}]: {n} hollow nodes "
                     f"registered")

        # -- scheduler replicas --------------------------------------
        def client_factory(i: int):
            return RestClusterClient(urls[0], partition_urls=urls,
                                     token=SCHEDULER_TOKEN, qps=qps)

        rs = SchedulerReplicaSet(
            client_factory, count=replicas, shard_pods=True,
            shard_nodes=shard_nodes, capacity_guard=not shard_nodes,
            use_batch=use_batch, max_batch=max_batch,
            event_client_factory=client_factory)
        attach_slo_baseline(rs.replicas[0])
        rs.run()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            cached = sum(s.cache.node_count() for s in rs.replicas)
            want = nodes if shard_nodes else nodes * replicas
            if cached >= want:
                break
            time.sleep(0.1)
        if progress:
            progress(f"scale10x[p{partitions}]: replica caches warm "
                     f"({[s.cache.node_count() for s in rs.replicas]})")
        if use_batch:
            samples = make_burst_pods(8, cpu_milli=POD_CPU_MILLI,
                                      memory=POD_MEMORY,
                                      namespaces=namespaces)
            for bs in rs.batch_schedulers:
                if bs is not None:
                    bs.warmup(sample_pods=samples)

        # -- measured burst ------------------------------------------
        collector = ThroughputCollector(count_fn=rs.bound_count)
        collector.start()
        t0 = time.monotonic()
        drv_conn.send(("pods", pods, 0, namespaces))
        done = False
        deadline = time.monotonic() + wait_timeout
        created = None
        last_note = 0.0
        while time.monotonic() < deadline:
            if created is None and drv_conn.poll(0.0):
                status, created = drv_conn.recv()
                if status == "error":
                    raise RuntimeError(f"creator failed: {created}")
            bound = rs.bound_count()
            if bound >= pods:
                done = True
                break
            if progress and time.monotonic() - last_note > 10:
                last_note = time.monotonic()
                progress(f"scale10x[p{partitions}]: {bound}/{pods} bound")
            time.sleep(0.2)
        if not done:
            raise TimeoutError(
                f"scale10x[p{partitions}]: bound {rs.bound_count()}"
                f"/{pods} before deadline")
        rs.wait_for_inflight_bindings()
        elapsed = time.monotonic() - t0
        collector.stop()

        # -- server truth + invariants -------------------------------
        node_alloc: Dict[str, int] = {}
        node_req: Dict[str, int] = {}
        pods_bound = pods_total = 0
        part_pods: List[int] = []
        for conn, _proc in servers:
            conn.send("counts")
            counts = conn.recv()
            pods_bound += counts["pods_bound"]
            pods_total += counts["pods_total"]
            part_pods.append(counts["pods_total"])
            node_alloc.update(counts["node_alloc"])
            for name, req in counts["node_req"].items():
                node_req[name] = node_req.get(name, 0) + req
        oversubscribed = sum(
            1 for name, req in node_req.items()
            if req > node_alloc.get(name, 1 << 62))
        # double-binds checked against server truth, two ways: a pod
        # bound to two nodes within one store is impossible (one key),
        # so the cross-partition failure mode is a DUPLICATED pod (a
        # misroute landing one logical pod in two shards — totals then
        # exceed the distinct names created) plus node oversubscription
        dup_pods = max(0, pods_total - pods)
        double_binds = oversubscribed + dup_pods
        conflicts = _conflict_delta(conflicts_before)

        # -- federation: every partition server + replica registry ---
        from kubernetes_tpu.metrics import default_registry
        from kubernetes_tpu.metrics.federation import metrics_federation

        fed = metrics_federation()
        for i, url in enumerate(urls):
            fed.forget_instance(f"apiserver-p{i}")
            try:
                fed.scrape(url, instance=f"apiserver-p{i}",
                           token=SCHEDULER_TOKEN, fold=True)
            except Exception:  # noqa: BLE001 — best-effort per child
                pass
        for i, sched in enumerate(rs.replicas):
            fed.forget_instance(f"scheduler-{i}")
            fed.absorb_registry(sched.metrics.registry,
                                instance=f"scheduler-{i}")
        fed.forget_instance("scheduler")
        fed.absorb_registry(default_registry(), instance="scheduler")
        federation_instances = sorted(fed.instances())

        p99_ms = max(
            s.metrics.e2e_scheduling_duration.quantile(
                0.99, "scheduled") * 1000
            for s in rs.replicas)
        balance = (min(part_pods) / max(part_pods)) \
            if part_pods and max(part_pods) else None
        watch_streams = sum(len(s.client._watch_threads)
                            for s in rs.replicas)
        _shard_diag(partitions, replicas,
                    sum(v for k, v in conflicts.items()
                        if k != "capacity"),
                    conflicts.get("capacity", 0), balance, watch_streams)
        row = {
            "partitions": partitions,
            "replicas": replicas,
            "nodes": nodes,
            "pods": pods,
            "pods_per_sec": round(pods / elapsed, 1) if elapsed else 0.0,
            "time_to_all_bound_s": round(elapsed, 1),
            "p99_latency_ms": round(p99_ms),
            "throughput": collector.summary(),
            "server_pods_bound": pods_bound,
            "server_pods_total": pods_total,
            "lost_pods": max(0, pods - pods_bound),
            "double_binds": double_binds,
            "oversubscribed_nodes": oversubscribed,
            "duplicated_pods": dup_pods,
            "conflicts": conflicts,
            "partition_balance": round(balance, 3)
            if balance is not None else None,
            "watch_streams": watch_streams,
            "federation_instances": federation_instances,
            "freshness": collect_freshness(),
        }
        if pods_bound < pods:
            raise RuntimeError(
                f"store truth disagrees: servers bound {pods_bound} "
                f"< expected {pods}")
        return row
    finally:
        if collector is not None:
            collector.stop()
        if rs is not None:
            rs.stop()
        teardown()


def run_scale_arm_inproc(
    nodes: int,
    pods: int,
    partitions: int,
    replicas: int = 2,
    use_batch: bool = False,
    max_batch: int = 512,
    node_cpu: int = 32,
    shard_pods: bool = True,
    shard_nodes: bool = True,
    capacity_guard: Optional[bool] = None,
    wait_timeout: float = 300.0,
    progress: Optional[Callable[[str], None]] = None,
) -> Dict:
    """The in-process arm: a ``PartitionedStore`` (per-partition async
    watch dispatch + bind-time capacity ledger) under a hollow fleet
    and M replicas — the tier-1-fast mini-scale shape, and the
    conflict chaos cell's substrate (``shard_pods=False`` makes every
    replica race on every pod on purpose)."""
    from kubernetes_tpu.apiserver.partition import PartitionedStore
    from kubernetes_tpu.harness.perf import (
        collect_freshness,
        reset_sli_window,
    )
    from kubernetes_tpu.kubemark import HollowFleet
    from kubernetes_tpu.scheduler.replicas import SchedulerReplicaSet

    reset_sli_window()
    conflicts_before = _conflict_counts()
    if capacity_guard is None:
        capacity_guard = not shard_nodes
    store = PartitionedStore(partitions, async_dispatch=partitions > 1,
                             capacity_guard=capacity_guard)
    namespaces = scale_namespaces(partitions)
    fleet = HollowFleet(store, interval=30.0)
    fleet.register(nodes, cpu=str(node_cpu))
    fleet.start()
    rs = SchedulerReplicaSet(
        lambda i: store, count=replicas, shard_pods=shard_pods,
        shard_nodes=shard_nodes, capacity_guard=capacity_guard,
        use_batch=use_batch, max_batch=max_batch)
    rs.run()
    t0 = time.monotonic()
    try:
        burst = make_burst_pods(pods, cpu_milli=POD_CPU_MILLI,
                                memory=POD_MEMORY, name_prefix="scale-",
                                uid_prefix="sc-", namespaces=namespaces)
        store.create_pods(burst)
        deadline = time.monotonic() + wait_timeout
        while time.monotonic() < deadline:
            bound = sum(1 for p in store.list_pods() if p.spec.node_name)
            if bound >= pods and rs.pending_count() == 0:
                break
            time.sleep(0.05)
        rs.wait_for_inflight_bindings()
        store.drain()
        elapsed = time.monotonic() - t0

        all_pods = store.list_pods()
        bound = [p for p in all_pods if p.spec.node_name]
        node_req: Dict[str, int] = {}
        for p in bound:
            node_req[p.spec.node_name] = node_req.get(
                p.spec.node_name, 0) + POD_CPU_MILLI
        oversubscribed = sum(
            1 for name, req in node_req.items()
            if req > node_cpu * 1000)
        conflicts = _conflict_delta(conflicts_before)

        # federation: absorb every partition's registry + replicas
        from kubernetes_tpu.metrics.federation import metrics_federation

        fed = metrics_federation()
        for i, reg in enumerate(store.partition_registries()):
            fed.forget_instance(f"partition-{i}")
            fed.absorb_registry(reg, instance=f"partition-{i}")
        for i, sched in enumerate(rs.replicas):
            fed.forget_instance(f"scheduler-{i}")
            fed.absorb_registry(sched.metrics.registry,
                                instance=f"scheduler-{i}")
        federation_instances = sorted(fed.instances())

        part_pods = [len(p.list_pods()) for p in store.parts]
        balance = (min(part_pods) / max(part_pods)) \
            if max(part_pods) else None
        _shard_diag(partitions, replicas,
                    sum(v for k, v in conflicts.items()
                        if k != "capacity"),
                    conflicts.get("capacity", 0), balance, None)
        return {
            "partitions": partitions,
            "replicas": replicas,
            "nodes": nodes,
            "pods": pods,
            "pods_per_sec": round(pods / elapsed, 1) if elapsed else 0.0,
            "time_to_all_bound_s": round(elapsed, 1),
            "bound": len(bound),
            "lost_pods": max(0, pods - len(bound)),
            "double_binds": oversubscribed,
            "oversubscribed_nodes": oversubscribed,
            "conflicts": conflicts,
            "partition_balance": round(balance, 3)
            if balance is not None else None,
            "federation_instances": federation_instances,
            "freshness": collect_freshness(),
        }
    finally:
        rs.stop()
        fleet.stop()
        store.stop()


def run_conflict_cell(nodes: int = 10, pods: int = 38,
                      partitions: int = 2, replicas: int = 2,
                      node_cpu: int = 2,
                      progress: Optional[Callable[[str], None]] = None
                      ) -> Dict:
    """The conflict chaos cell: replicas with OVERLAPPING
    responsibility (no pod-hash sharding, shared node pool) over a
    tight cluster — every pod is raced by every brain, so the bind CAS
    + capacity guards must arbitrate constantly. Invariants: every pod
    bound exactly once, zero oversubscription, and conflicts actually
    occurred (``stale_binds_rejected_total`` > 0 — a cell that never
    conflicted proved nothing)."""
    cell = run_scale_arm_inproc(
        nodes=nodes, pods=pods, partitions=partitions,
        replicas=replicas, use_batch=False, node_cpu=node_cpu,
        shard_pods=False, shard_nodes=False, capacity_guard=True,
        wait_timeout=120.0, progress=progress)
    cell["conflicts_total"] = sum(cell["conflicts"].values())
    cell["ok"] = (cell["lost_pods"] == 0 and cell["double_binds"] == 0
                  and cell["conflicts_total"] > 0)
    return cell


def run_scale10x_row(
    nodes: int = 50_000,
    pods: int = 500_000,
    partitions: int = 4,
    replicas: int = 2,
    use_batch: bool = True,
    max_batch: int = 1024,
    qps: Optional[float] = 5000.0,
    node_cpu: int = 32,
    wait_timeout: float = 2400.0,
    progress: Optional[Callable[[str], None]] = None,
) -> Dict:
    """The committed bench row: partitioned arm, single-partition arm
    (same scale — the A/B that shows sharding pays for itself), and
    the conflict chaos cell."""
    arm = run_scale_arm_rest(
        nodes=nodes, pods=pods, partitions=partitions,
        replicas=replicas, use_batch=use_batch, max_batch=max_batch,
        qps=qps, node_cpu=node_cpu, wait_timeout=wait_timeout,
        progress=progress)
    single = run_scale_arm_rest(
        nodes=nodes, pods=pods, partitions=1, replicas=replicas,
        use_batch=use_batch, max_batch=max_batch, qps=qps,
        node_cpu=node_cpu, wait_timeout=wait_timeout, progress=progress)
    cell = run_conflict_cell(progress=progress)
    speedup = (arm["pods_per_sec"] / single["pods_per_sec"]) \
        if single["pods_per_sec"] else 0.0
    row = {
        "metric": (f"pods_scheduled_per_sec[Scale10x {nodes}nodes/"
                   f"{pods}pods, partitioned fabric {partitions}p x "
                   f"{replicas}r]"),
        "value": arm["pods_per_sec"],
        "unit": "pods/s",
        "p99_latency_ms": arm.get("p99_latency_ms", 0),
        "scale": {"nodes": nodes, "pods": pods,
                  "partitions": partitions, "replicas": replicas},
        "ab": {
            "partitioned_pods_per_sec": arm["pods_per_sec"],
            "single_partition_pods_per_sec": single["pods_per_sec"],
            "speedup": round(speedup, 3),
            "sharding_pays": speedup >= 1.0,
        },
        "invariants": {
            "lost_pods": arm["lost_pods"] + single["lost_pods"],
            "double_binds": arm["double_binds"] + single["double_binds"],
        },
        "conflict_cell": {
            "conflicts": cell["conflicts"],
            "conflicts_total": cell["conflicts_total"],
            "lost_pods": cell["lost_pods"],
            "double_binds": cell["double_binds"],
            "ok": cell["ok"],
        },
        "partition_balance": arm.get("partition_balance"),
        "watch_streams": arm.get("watch_streams"),
        "federation_instances": arm.get("federation_instances", []),
        "freshness": arm.get("freshness", {}),
    }
    return row
