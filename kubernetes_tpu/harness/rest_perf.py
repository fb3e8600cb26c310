"""scheduler_perf over the REAL API fabric (VERDICT r4 missing #1).

The reference's scheduler_perf runs an in-process apiserver + real etcd
and every client goes through REST at QPS/Burst 5000
(``test/integration/scheduler_perf/util.go:61-68``,
``test/integration/util/util.go:57``). The store-direct harness
(``perf.py``) deliberately excludes that cost; this harness includes it:

- **apiserver process**: ClusterStore + WAL (the etcd analog) served by
  ``APIServer`` — authn (bearer tokens), RBAC bootstrap policy,
  admission, watch cache, max-in-flight lanes all live.
- **creator process(es)**: build workload objects from the same
  declarative ops and POST them through ``RestClusterClient`` — bulk
  {Kind}List bodies whose token bucket charges PER OBJECT, so the wire
  discipline is the reference's per-client 5000 QPS regardless of
  batching.
- **scheduler (this process, owns the TPU)**: fed by watch-driven
  list+watch streams over chunked HTTP (server-coalesced binary
  chunks, O(batches) syscalls), binds through bulk BindingList
  requests shipped on the binding pool (cycles never serialize on the
  bind round trip), bulk PodStatusList for status sweeps — all via the
  binary codec. "Scheduled" events ride a SEPARATE client+bucket, the
  reference's own events-client discipline.

Process topology mirrors the reference deployment (apiserver, client,
scheduler are separate processes); it also gives each Python runtime
its own GIL, which is what the reference gets for free from Go.

Throughput is counted from the scheduler's commit metric (successful
REST binds); at the end the apiserver process REPORTS its own
bound-pod count and the two must agree — the measured number is
store-truth, not client-side optimism.
"""

from __future__ import annotations

import json
import multiprocessing as mp
import os
import tempfile
import time
from typing import Callable, List, Optional

from kubernetes_tpu.harness.workloads import make_workload

SCHEDULER_TOKEN = "rest-perf-scheduler-token"
CREATOR_TOKEN = "rest-perf-creator-token"


# ---------------------------------------------------------------------------
# child mains (spawned; must stay jax-free — see harness/__init__)


def _apiserver_main(conn, wal_dir: Optional[str],
                    extra_tokens: Optional[dict] = None) -> None:
    from kubernetes_tpu.apiserver.rbac import provision_bootstrap_policy
    from kubernetes_tpu.apiserver.rest import APIServer
    from kubernetes_tpu.apiserver.store import ClusterStore
    from kubernetes_tpu.apiserver.wal import attach_wal
    from kubernetes_tpu.utils.gctune import tune_for_throughput

    profiler = _maybe_profiler("apiserver")
    tune_for_throughput()
    store = ClusterStore()
    # async WAL writer: serialization rides a background thread instead
    # of every request's critical section (etcd pipelines raft appends
    # the same way); bounded loss window on crash, same as fsync=False
    wal = attach_wal(store, wal_dir, snapshot_every=200_000,
                     async_serialize=True) if wal_dir else None
    authz = provision_bootstrap_policy(store)
    authz.add_user_to_group("perf-creator", "system:masters")
    tokens = {SCHEDULER_TOKEN: "system:kube-scheduler",
              CREATOR_TOKEN: "perf-creator"}
    # extra identities (the noisy-tenant QoS harness's aggressor
    # tenants): authenticated but NOT control-plane/masters, so APF
    # routes them to the workload level, one fair-queued flow each.
    # They get a viewer-ish role — enough to mount list storms, watch
    # herds, and bulk ConfigMap abuse, nothing privileged.
    tokens.update(extra_tokens or {})
    if extra_tokens:
        from kubernetes_tpu.api.types import (
            ClusterRole, ClusterRoleBinding, ObjectMeta, PolicyRule,
            RBACSubject, RoleRef,
        )

        store.add_cluster_role(ClusterRole(
            metadata=ObjectMeta(name="qos-tenant"),
            rules=[PolicyRule(verbs=["get", "list", "watch"],
                              resources=["pods", "nodes", "services"]),
                   PolicyRule(verbs=["get", "list", "watch", "create"],
                              resources=["configmaps"])]))
        store.add_cluster_role_binding(ClusterRoleBinding(
            metadata=ObjectMeta(name="qos-tenants"),
            subjects=[RBACSubject(kind="User", name=u)
                      for u in extra_tokens.values()],
            role_ref=RoleRef(kind="ClusterRole", name="qos-tenant")))
    server = APIServer(
        store=store,
        authorizer=authz,
        tokens=tokens,
    ).start()
    conn.send(server.url)
    while True:
        msg = conn.recv()
        if msg == "stop":
            break
        if msg == "counts":
            pods = store.list_pods()
            if wal is not None:
                wal.drain()   # async writer: count only settled bytes
            conn.send({
                "pods_total": len(pods),
                "pods_bound": sum(1 for p in pods if p.spec.node_name),
                "wal_entries": _wal_lines(wal_dir),
            })
    server.shutdown_server()
    if wal is not None:
        wal.close()
    _stop_profiler(profiler)
    conn.send("stopped")


class _SamplingProfiler:
    """All-threads stack sampler for the spawned fabric children (the
    parent's profiler cannot see them, and cProfile only observes the
    thread that enabled it — useless for a thread-per-connection
    server). Samples ``sys._current_frames()`` on an interval and dumps
    a self-time histogram per function to
    ``$KTPU_PROFILE_REST/<role>.txt`` on shutdown."""

    def __init__(self, role: str, interval: float = 0.002):
        import collections
        import threading

        self.role = role
        self.interval = interval
        self.counts: dict = collections.Counter()
        self.samples = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"profiler-{role}")
        self._thread.start()

    def _run(self) -> None:
        import sys
        import time as _time

        me = self._thread.ident
        while not self._stop.is_set():
            for tid, frame in sys._current_frames().items():
                if tid == me:
                    continue
                code = frame.f_code
                key = (f"{code.co_filename.rsplit('/', 1)[-1]}:"
                       f"{code.co_firstlineno}:{code.co_name}")
                self.counts[key] += 1
                self.samples += 1
            _time.sleep(self.interval)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)
        out = os.environ.get("KTPU_PROFILE_REST", "")
        try:
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, f"{self.role}.txt"), "w") as f:
                f.write(f"samples={self.samples}\n")
                for key, n in sorted(self.counts.items(),
                                     key=lambda kv: -kv[1])[:60]:
                    f.write(f"{n:8d}  {key}\n")
        except OSError:
            pass


def _maybe_profiler(role: str):
    if not os.environ.get("KTPU_PROFILE_REST"):
        return None
    return _SamplingProfiler(role)


def _stop_profiler(profiler) -> None:
    if profiler is not None:
        profiler.stop()


def _wal_lines(wal_dir: Optional[str]) -> int:
    if not wal_dir:
        return 0
    path = os.path.join(wal_dir, "wal.jsonl")
    try:
        with open(path, "rb") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


def _real_failures(resp) -> list:
    """Bulk-create failures that are NOT 409s. The client retries a
    dropped keep-alive; a create applied server-side before the drop
    comes back AlreadyExists on the retry — for a creator whose goal is
    'these pods exist', that IS success, not a row-aborting error."""
    return [f for f in (resp.get("failures") or ())
            if f.get("code") != 409]


def _creator_main(conn, url: str, name: str, nodes: int, init_pods: int,
                  measure_pods: int, qps: Optional[float],
                  n_clients: int) -> None:
    """Executes create ops on demand. ``n_clients`` round-robins pod
    creation across that many QPS-capped clients (each with its OWN
    5000-QPS bucket, the reference's per-client discipline)."""
    from kubernetes_tpu.api.types import Node, Pod
    from kubernetes_tpu.client.restcluster import RestClusterClient
    from kubernetes_tpu.harness.burst import stream_arrivals

    profiler = _maybe_profiler(f"creator-{name}")
    clients = [RestClusterClient(url, token=CREATOR_TOKEN, qps=qps)
               for _ in range(max(1, n_clients))]
    ops = make_workload(name, nodes=nodes, init_pods=init_pods,
                        measure_pods=measure_pods)
    CHUNK = 512
    while True:
        msg = conn.recv()
        if msg == "stop":
            break
        op_idx = msg
        op = ops[op_idx]
        if op["opcode"] == "createNodes":
            objs = [Node.from_dict(op["nodeTemplate"](i))
                    for i in range(op["count"])]
            for lo in range(0, len(objs), CHUNK):
                chunk = objs[lo:lo + CHUNK]
                code, resp = clients[0]._request(
                    "POST", "/api/v1/nodes",
                    {"kind": "NodeList", "items": chunk},
                    charge=len(chunk))
                if code >= 400 or _real_failures(resp):
                    conn.send(("error", op_idx, str(resp)[:500]))
                    break
            else:
                conn.send(("done", op_idx, len(objs)))
            continue
        if op["opcode"] == "createPods":
            template = op["podTemplate"]
            offset = op.get("offset", 0)
            count = op["count"]
            # the shared open-loop injection helper at rate=∞: lazy
            # per-chunk pod construction, per-chunk client rotation —
            # the same loop the replay engine paces with real due times
            rotation = [0]

            def send(items):
                client = clients[rotation[0] % len(clients)]
                rotation[0] += 1
                code, resp = client._request(
                    "POST", "/api/v1/namespaces/default/pods",
                    {"kind": "PodList", "items": items},
                    charge=len(items))
                if code >= 400 or _real_failures(resp):
                    raise RuntimeError(str(resp)[:500])

            try:
                sent = stream_arrivals(
                    ((0.0, Pod.from_dict(template(offset + i)))
                     for i in range(count)),
                    send, chunk=CHUNK, time_scale=0.0)
                conn.send(("done", op_idx, sent))
            except RuntimeError as e:
                conn.send(("error", op_idx, str(e)))
            continue
        conn.send(("done", op_idx, 0))
    _stop_profiler(profiler)
    conn.send("stopped")


# ---------------------------------------------------------------------------
# parent (scheduler + TPU)


def run_workload_rest(
    name: str,
    nodes: int,
    measure_pods: int,
    init_pods: int = 0,
    max_batch: int = 4096,
    qps: Optional[float] = 5000.0,
    n_creator_clients: int = 2,
    use_batch: bool = True,
    wait_timeout: float = 1200.0,
    wal: bool = True,
    progress: Optional[Callable[[str], None]] = None,
    result_hook: Optional[Callable[[object, object], None]] = None,
    extra_tokens: Optional[dict] = None,
    on_measure_start: Optional[Callable[[str], Callable[[], None]]] = None,
):
    """Run one workload with every byte crossing the REST fabric.
    Returns a ``BenchmarkResult`` whose ``metrics`` carry the apiserver
    process's own final counts for cross-checking."""
    from kubernetes_tpu.api.types import Pod
    from kubernetes_tpu.client.restcluster import RestClusterClient
    from kubernetes_tpu.config.feature_gates import FeatureGates
    from kubernetes_tpu.harness.perf import (
        BenchmarkResult,
        ThroughputCollector,
    )
    from kubernetes_tpu.observability import get_tracer
    from kubernetes_tpu.scheduler.scheduler import Scheduler
    from kubernetes_tpu.sidecar import attach_batch_scheduler
    from kubernetes_tpu.utils.gctune import tune_for_throughput

    tune_for_throughput()
    # per-row flight-recorder + devprof windows (diag line + the row's
    # ``telemetry`` sub-object; the scheduler — and so the solver —
    # runs in THIS process, only the apiserver/creators are children)
    get_tracer().clear()
    from kubernetes_tpu.harness.perf import (
        attach_slo_baseline,
        collect_critical_path,
        collect_freshness,
        reset_sli_window,
    )
    from kubernetes_tpu.observability.devprof import get_devprof

    get_devprof().reset(workload=f"{name}/rest")
    reset_sli_window()
    ctx = mp.get_context("spawn")
    wal_dir = tempfile.mkdtemp(prefix="ktpu-wal-") if wal else None

    api_conn, api_child = ctx.Pipe()
    api_proc = ctx.Process(target=_apiserver_main,
                           args=(api_child, wal_dir, extra_tokens),
                           daemon=True)
    api_proc.start()
    url = api_conn.recv()

    cre_conn, cre_child = ctx.Pipe()
    cre_proc = ctx.Process(
        target=_creator_main,
        args=(cre_child, url, name, nodes, init_pods, measure_pods, qps,
              n_creator_clients),
        daemon=True)
    cre_proc.start()

    client = RestClusterClient(url, token=SCHEDULER_TOKEN, qps=qps)
    # the recorder's "Scheduled" events ride their OWN client+bucket
    # (the reference scheduler's separate events client): sharing the
    # bind client's bucket would charge ~1 token per scheduled pod
    # against the bind budget — rate the reference never pays
    event_client = RestClusterClient(url, token=SCHEDULER_TOKEN, qps=qps)
    gates = FeatureGates({"TPUBatchScheduler": use_batch})
    sched = Scheduler.create(client, feature_gates=gates,
                             provider="GangSchedulingProvider",
                             event_client=event_client)
    bs = attach_batch_scheduler(sched, max_batch=max_batch) \
        if use_batch else None
    attach_slo_baseline(sched)
    # live SLO evaluation while the fabric runs: the engine's tick
    # thread samples the SLIs so a mid-run burn-rate breach fires its
    # flight-recorder dump DURING the run, not at the postmortem
    from kubernetes_tpu.observability.slo import get_slo_engine

    slo_engine = get_slo_engine()
    if slo_engine.enabled:
        slo_engine.start(interval_s=1.0)
    sched.start()

    def bound_count() -> int:
        s = sched.metrics.e2e_scheduling_duration._series.get(
            ("scheduled",))
        return s[2] if s else 0

    def run_op(op_idx: int) -> int:
        cre_conn.send(op_idx)
        # pump the scheduler while the creator streams objects in
        while not cre_conn.poll(0.0):
            if bs is not None:
                bs.run_batch(pop_timeout=0.01)
            else:
                if not sched.schedule_one(pop_timeout=0.01):
                    time.sleep(0.002)
        status, _idx, n = cre_conn.recv()
        if status == "error":
            raise RuntimeError(f"creator op {op_idx} failed: {n}")
        return n

    def pump_until(target: int, deadline: float) -> None:
        while time.monotonic() < deadline:
            sched.queue.flush_backoff_completed()
            progressed = bs.run_batch(pop_timeout=0.01) if bs is not None \
                else sched.schedule_one(pop_timeout=0.01)
            if bound_count() >= target:
                return
            if not progressed:
                time.sleep(0.002)
        raise TimeoutError(
            f"workload {name}: bound {bound_count()}/{target} "
            f"before deadline")

    def teardown_children() -> None:
        """Always runs — a failed row must not leak an apiserver process
        holding a 30k-pod store (or its WAL tempdir) into the next
        matrix row."""
        try:
            cre_conn.send("stop")
        except (BrokenPipeError, OSError):
            pass
        try:
            api_conn.send("stop")
        except (BrokenPipeError, OSError):
            pass
        for conn, proc in ((cre_conn, cre_proc), (api_conn, api_proc)):
            try:
                if conn.poll(5.0):
                    conn.recv()
            except (EOFError, OSError):
                pass
            proc.join(timeout=5.0)
            if proc.is_alive():
                proc.terminate()
        if wal_dir:
            import shutil

            shutil.rmtree(wal_dir, ignore_errors=True)

    collector = None
    measure_start = 0.0
    expected_bound = 0
    created_pods = 0
    federation_instances: List[str] = []
    stop_companions: Optional[Callable[[], None]] = None
    ops = make_workload(name, nodes=nodes, init_pods=init_pods,
                        measure_pods=measure_pods)
    try:
        for i, op in enumerate(ops):
            opcode = op["opcode"]
            if opcode == "createNodes":
                run_op(i)
                # the cache learns nodes via the watch stream; solving
                # before they land would decline the first batches
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline and \
                        sched.cache.node_count() < op["count"]:
                    time.sleep(0.02)
                if progress:
                    progress(f"{name}/rest: {sched.cache.node_count()} "
                             f"nodes")
            elif opcode == "createPods":
                collect = op.get("collectMetrics", False)
                if collect and bs is not None:
                    from kubernetes_tpu.ops.encode import is_host_only

                    template = op["podTemplate"]
                    offset = op.get("offset", 0)
                    samples = [Pod.from_dict(template(offset + j))
                               for j in range(min(200, op["count"]))]
                    samples = [p for p in samples
                               if not is_host_only(p, client)]
                    warm = bs.warmup(sample_pods=samples) if samples \
                        else 0.0
                    if progress and warm > 0.05:
                        progress(f"{name}/rest: solver warmup {warm:.1f}s")
                if collect:
                    if on_measure_start is not None \
                            and stop_companions is None:
                        # companion load (the QoS harness's aggressor
                        # tenants) starts exactly when measurement does
                        # and runs through the whole measured window
                        stop_companions = on_measure_start(url)
                    collector = ThroughputCollector(count_fn=bound_count)
                    measure_start = time.monotonic()
                    collector.start()
                n = run_op(i)
                created_pods += n
                if progress:
                    progress(f"{name}/rest: {created_pods} pods created")
                if not op.get("skipWaitToCompletion", False):
                    expected_bound += n
                    pump_until(expected_bound,
                               time.monotonic() + wait_timeout)
            elif opcode == "barrier":
                pump_until(expected_bound, time.monotonic() + wait_timeout)
        sched.wait_for_inflight_bindings(timeout=30.0)
        duration = time.monotonic() - measure_start if measure_start \
            else 0.0
        if stop_companions is not None:
            stop_companions()
            stop_companions = None
        # cross-process metrics, the generic path: scrape the child
        # apiserver's /metrics, parse the exposition, and merge EVERY
        # family into the federation under an ``instance`` label —
        # fold=True also folds the child's counters (the APF rejections
        # among them) into this process's same-name counters by
        # cumulative delta, so bench.py's diag segments keep reading
        # their usual local series with no per-family absorb mapping.
        # The /debug/apf JSON snapshot is fetched ONLY for the diag
        # line's queue-wait/peak-seat numbers (server-side histogram
        # state a counter fold cannot reconstruct).
        apf_snapshot = None
        from kubernetes_tpu.metrics import default_registry
        from kubernetes_tpu.metrics.apf_metrics import apf_metrics
        from kubernetes_tpu.metrics.federation import metrics_federation

        # the fold lands only on counters THIS process has declared —
        # instantiate the APF families before scraping (the legacy
        # absorb path did this implicitly)
        apfm = apf_metrics()
        fed = metrics_federation()
        # each row spawns a FRESH apiserver under the same instance
        # name: forget the previous child's series AND fold baselines
        # so this child's totals fold in full (not as a bogus delta)
        fed.forget_instance("apiserver")
        fed.forget_instance("scheduler")
        fed.scrape(url, instance="apiserver", token=SCHEDULER_TOKEN,
                   fold=True)
        # the parent is a component too: mirror its registry through
        # the same render→parse path so the merged view is complete —
        # independently of the child scrape, which is best-effort (a
        # dying child must not erase the parent from the merged view)
        fed.absorb_registry(default_registry(), instance="scheduler")
        federation_instances = sorted(fed.instances())
        try:
            code, snap = client._request("GET", "/debug/apf")
            if code == 200 and isinstance(snap, dict):
                apf_snapshot = snap
                apfm.last_snapshot = snap
        except Exception:  # noqa: BLE001 — introspection is best-effort
            pass
        # fleet trace: scrape the child's /debug/trace ring (with the
        # half-RTT clock-offset handshake) while it is still alive,
        # merge with this process's ring, and attribute the sampled
        # pods' critical path — best-effort like the metrics scrape
        critpath, fleet_doc = collect_critical_path(
            remote=[("apiserver", url)], token=SCHEDULER_TOKEN)
        trace_out = os.environ.get("KTPU_FLEET_TRACE_OUT")
        if trace_out and fleet_doc is not None:
            try:
                with open(trace_out, "w") as f:
                    json.dump(fleet_doc, f)
            except Exception:  # noqa: BLE001
                pass
        if result_hook is not None:
            result_hook(sched, bs)
    except BaseException:
        if stop_companions is not None:
            stop_companions()
        teardown_children()
        raise
    finally:
        if collector:
            collector.stop()
        if slo_engine.enabled:
            slo_engine.stop()
        sched.stop()

    # cross-check against the apiserver's own truth (and WAL durability)
    try:
        api_conn.send("counts")
        server_counts = api_conn.recv()
    finally:
        teardown_children()

    measured = sum(op["count"] for op in ops
                   if op["opcode"] == "createPods"
                   and op.get("collectMetrics"))
    e2e = sched.metrics.e2e_scheduling_duration
    metrics = {
        "Perc50": e2e.quantile(0.50, "scheduled") * 1000,
        "Perc90": e2e.quantile(0.90, "scheduled") * 1000,
        "Perc99": e2e.quantile(0.99, "scheduled") * 1000,
        "server_pods_bound": server_counts["pods_bound"],
        "server_pods_total": server_counts["pods_total"],
        "wal_entries": server_counts["wal_entries"],
        "scheduler_bound": bound_count(),
        "apf": apf_snapshot,
        "federation_instances": federation_instances,
    }
    if server_counts["pods_bound"] < expected_bound:
        raise RuntimeError(
            f"store truth disagrees: server bound "
            f"{server_counts['pods_bound']} < expected {expected_bound}")
    dp = get_devprof()
    telemetry = dp.summary() if dp.enabled else {}
    return BenchmarkResult(
        name=f"{name}/rest",
        total_pods=created_pods,
        measured_pods=measured,
        duration_seconds=duration,
        pods_per_second=(measured / duration) if duration > 0 else 0.0,
        throughput=collector.summary() if collector else {},
        metrics=metrics,
        telemetry=telemetry,
        freshness=collect_freshness(telemetry),
        critical_path=critpath,
    )
