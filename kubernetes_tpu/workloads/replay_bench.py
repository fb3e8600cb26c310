"""Replay bench rows + chaos cells: the scenario families measured
through the REAL fabric, judged by SLO verdicts and invariants.

``run_replay_row`` runs one family OPEN-LOOP over the REST fabric
(apiserver child process with WAL/RBAC/APF, arrivals through
authenticated clients, scheduler fed by watch streams) and emits a
BENCH-JSON row whose headline is **arrival→bind latency** — per-pod
schedule latency measured from the arrival instant, the number a
submitting user experiences — next to rate-normalized throughput, the
family's hard invariants, PR 8's SLO verdicts, and the
``replay[...]`` diag segment. Family extras:

- ``gangs`` runs TWO arms — MeshLocality scored vs adjacency-blind —
  and the row carries the adjacency A/B (scored must beat blind);
- ``tenancy`` runs the PR 4 autoscaler (node-group capacity bought
  mid-trace) and PR 6 APF together: each tenant's arrivals ride its
  own authenticated client, so serve and batch are separate fair-
  queued flows; the row splits arrival→bind latency per class;
- ``storm`` reports the preemption ledger and the
  no-priority-inversion-at-quiesce verdict.

``run_replay_cell`` is the chaos-matrix face (``--suite replay``):
store-direct mini-replays per (family × seed) asserting the
invariants — zero lost pods, gang atomicity, no priority inversion.
"""

from __future__ import annotations

import math
import multiprocessing as mp
import tempfile
import time
from typing import Callable, Dict, List, Optional

from kubernetes_tpu.workloads.scenarios import (
    REPLAY_FAMILIES,
    TENANCY_NODE_CPU,
    FamilySpec,
    _tenancy_sizing,
)
from kubernetes_tpu.workloads.trace import Trace

SCHEDULER_TOKEN = "replay-scheduler-token"
CREATOR_TOKEN = "replay-creator-token"
SERVE_LATENCY_BUDGET_S = 2.0


def tenant_tokens(spec: FamilySpec) -> Dict[str, str]:
    return {f"{t}-token": t for t in spec.tenants}


# ---------------------------------------------------------------------------
# apiserver child (spawned; must stay jax-free — see harness/__init__)


def _apiserver_main(conn, wal_dir: Optional[str],
                    extra_tokens: Optional[dict] = None) -> None:
    """Like the REST harness's apiserver child, but replay tenants get
    a role that can SUBMIT workloads (create/delete pods) — the
    tenancy family's tenants are real users of the fabric, not
    read-only aggressors."""
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
    authz.add_user_to_group("replay-creator", "system:masters")
    tokens = {SCHEDULER_TOKEN: "system:kube-scheduler",
              CREATOR_TOKEN: "replay-creator"}
    tokens.update(extra_tokens or {})
    if extra_tokens:
        from kubernetes_tpu.api.types import (
            ClusterRole, ClusterRoleBinding, ObjectMeta, PolicyRule,
            RBACSubject, RoleRef,
        )

        store.add_cluster_role(ClusterRole(
            metadata=ObjectMeta(name="replay-tenant"),
            rules=[PolicyRule(
                verbs=["get", "list", "watch", "create", "delete"],
                resources=["pods"])]))
        store.add_cluster_role_binding(ClusterRoleBinding(
            metadata=ObjectMeta(name="replay-tenants"),
            subjects=[RBACSubject(kind="User", name=u)
                      for u in extra_tokens.values()],
            role_ref=RoleRef(kind="ClusterRole", name="replay-tenant")))
    server = APIServer(store=store, authorizer=authz,
                       tokens=tokens).start()
    conn.send(server.url)
    while True:
        msg = conn.recv()
        if msg == "stop":
            break
        if msg == "counts":
            pods = store.list_pods()
            if wal is not None:
                wal.drain()
            conn.send({
                "pods_total": len(pods),
                "pods_bound": sum(1 for p in pods if p.spec.node_name),
            })
    server.shutdown_server()
    if wal is not None:
        wal.close()
    conn.send("stopped")


# ---------------------------------------------------------------------------
# one replay run (store-direct or REST)


def _pump_to_quiesce(sched, bs, engine, deadline: float,
                     settle_s: float = 1.0) -> None:
    """Drive the scheduler until the replay is over: trace exhausted,
    due expiries delivered, queues drained, and no progress for
    ``settle_s`` (deletions re-activate parked pods, so 'drained' must
    hold for a settle window, not an instant)."""
    quiet_since = None
    while time.monotonic() < deadline:
        sched.queue.flush_backoff_completed()
        progressed = bs.run_batch(pop_timeout=0.01) if bs is not None \
            else sched.schedule_one(pop_timeout=0.01)
        now = time.monotonic()
        if progressed:
            quiet_since = None
            continue
        busy = (not engine.injection_done.is_set()
                or engine.due_expiries() > 0
                or sched.queue.pending_active_count() > 0)
        if busy:
            quiet_since = None
        elif quiet_since is None:
            quiet_since = now
        elif now - quiet_since >= settle_s:
            return
        time.sleep(0.005)
    raise TimeoutError("replay did not quiesce before deadline")


def run_replay_once(
    family: str,
    seed: int = 11,
    scale: float = 1.0,
    time_scale: float = 1.0,
    *,
    rest: bool = False,
    use_batch: bool = True,
    max_batch: int = 1024,
    qps: Optional[float] = 5000.0,
    wait_timeout: float = 600.0,
    scored: bool = True,
    expire: bool = True,
    autoscale: Optional[bool] = None,
    trace: Optional[Trace] = None,
    progress: Optional[Callable[[str], None]] = None,
):
    """One replay run. Returns ``(stats, extras)`` where ``extras``
    carries the observability sub-objects (telemetry/freshness),
    server truth for REST runs, and autoscaler/apf ledgers when those
    layers were active. ``scored=False`` is the adjacency-blind arm."""
    from kubernetes_tpu.api.types import Node
    from kubernetes_tpu.config.feature_gates import FeatureGates
    from kubernetes_tpu.harness.perf import (
        attach_slo_baseline,
        collect_freshness,
        reset_sli_window,
    )
    from kubernetes_tpu.observability import get_tracer
    from kubernetes_tpu.observability.devprof import get_devprof
    from kubernetes_tpu.observability.slo import get_slo_engine
    from kubernetes_tpu.scheduler.framework.plugins import mesh_locality
    from kubernetes_tpu.scheduler.scheduler import Scheduler
    from kubernetes_tpu.sidecar import attach_batch_scheduler
    from kubernetes_tpu.utils.gctune import tune_for_throughput
    from kubernetes_tpu.workloads.replay import ReplayEngine

    spec = REPLAY_FAMILIES[family]
    if autoscale is None:
        autoscale = spec.autoscale
    if trace is None:
        trace = spec.build(seed, scale)
    tune_for_throughput()
    get_tracer().clear()
    get_devprof().reset(workload=f"replay/{family}")
    reset_sli_window()
    prev_scored = mesh_locality.enabled()
    mesh_locality.configure(scored)

    extras: Dict = {"family": family, "seed": seed, "scale": scale}
    ctx = api_conn = api_proc = None
    wal_dir = None
    clients: List = []
    ca = factory = None
    engine = None
    sched = None
    slo_engine = get_slo_engine()
    try:
        if rest:
            from kubernetes_tpu.client.restcluster import (
                RestClusterClient,
            )

            ctx = mp.get_context("spawn")
            wal_dir = tempfile.mkdtemp(prefix="ktpu-replay-wal-")
            api_conn, api_child = ctx.Pipe()
            api_proc = ctx.Process(
                target=_apiserver_main,
                args=(api_child, wal_dir, tenant_tokens(spec)),
                daemon=True)
            api_proc.start()
            url = api_conn.recv()
            client = RestClusterClient(url, token=SCHEDULER_TOKEN,
                                       qps=qps)
            event_client = RestClusterClient(url, token=SCHEDULER_TOKEN,
                                             qps=qps)
            creator = RestClusterClient(url, token=CREATOR_TOKEN,
                                        qps=qps)
            clients = [client, event_client, creator]
            tenant_clients = {}
            for tenant, token in ((t, f"{t}-token")
                                  for t in spec.tenants):
                # tenants ride the public JSON wire: the binary codec
                # (pickle) is gated to trusted control-plane
                # identities, and an untrusted tenant speaking JSON is
                # also the honest multi-tenant wire shape
                c = RestClusterClient(url, token=token, qps=qps,
                                      binary=False)
                tenant_clients[tenant] = c
                clients.append(c)
            target, sched_client = creator, client
        else:
            from kubernetes_tpu.apiserver.store import ClusterStore

            store = ClusterStore()
            target = sched_client = store
            event_client = None
            tenant_clients = {}

        # -- node fleet (node-group-owned when the autoscaler plays) --
        if autoscale:
            from kubernetes_tpu.autoscaler import (
                NodeGroup,
                NodeGroupRegistry,
            )

            n_serve, n_batch, initial = _tenancy_sizing(scale)
            need = max(initial + 1, math.ceil(
                initial / 0.45))
            registry = NodeGroupRegistry()
            group = registry.add(NodeGroup(
                "ng-replay", cpu=str(TENANCY_NODE_CPU), memory="32Gi",
                min_size=initial, max_size=need + 4,
                boot_latency=0.4))
            initial_nodes = [group.node_template(i)
                             for i in range(initial)]
        else:
            registry = None
            initial_nodes = [Node.from_dict(d)
                             for d in spec.node_specs(scale)]
        if rest:
            target.create_objects_bulk("Node", initial_nodes)
        else:
            for n in initial_nodes:
                target.add_node(n)

        # -- scheduler (always the gang provider: every family's gang
        #    semantics ride the coscheduling machinery) --
        gates = FeatureGates({"TPUBatchScheduler": use_batch})
        sched = Scheduler.create(
            sched_client, feature_gates=gates,
            provider="GangSchedulingProvider",
            event_client=event_client)
        bs = attach_batch_scheduler(sched, max_batch=max_batch) \
            if use_batch else None
        attach_slo_baseline(sched)
        if rest and slo_engine.enabled:
            slo_engine.start(interval_s=1.0)
        sched.start()
        if rest:
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and \
                    sched.cache.node_count() < len(initial_nodes):
                time.sleep(0.02)
        if bs is not None:
            from kubernetes_tpu.workloads.trace import events_to_pods

            samples = events_to_pods(trace.events[:128])
            warm = bs.warmup(sample_pods=samples) if samples else 0.0
            if progress and warm > 0.05:
                progress(f"replay/{family}: solver warmup {warm:.1f}s")

        # -- autoscaler (the tenancy family's capacity acquisition) --
        if autoscale:
            from kubernetes_tpu.autoscaler import ClusterAutoscaler
            from kubernetes_tpu.client.informers import (
                SharedInformerFactory,
            )

            ca_client = target   # masters identity over REST; store
            factory = SharedInformerFactory(ca_client)
            ca = ClusterAutoscaler(ca_client, factory,
                                   registry=registry)
            ca.RESYNC_SECONDS = 0.2
            ca.scale_up_cooldown = 0.5
            ca.max_virtual_per_group = 128
            ca.scale_down_enabled = False
            ca.queue_introspect = sched.queue
            factory.start()
            factory.wait_for_cache_sync()
            ca.run()

        # -- the replay itself --
        engine = ReplayEngine(
            target, trace, time_scale=time_scale, expire=expire,
            tenant_targets=tenant_clients or None, progress=progress)
        t0 = time.monotonic()
        engine.start()
        _pump_to_quiesce(sched, bs, engine,
                         time.monotonic() + wait_timeout)
        sched.wait_for_inflight_bindings(timeout=30.0)
        extras["wall_s"] = round(time.monotonic() - t0, 2)
        stats = engine.finish()
        engine = None

        # -- observability collection --
        if rest:
            from kubernetes_tpu.metrics import default_registry
            from kubernetes_tpu.metrics.federation import (
                metrics_federation,
            )

            fed = metrics_federation()
            fed.forget_instance("apiserver")
            fed.forget_instance("scheduler")
            try:
                fed.scrape(url, instance="apiserver",
                           token=SCHEDULER_TOKEN, fold=True)
            except Exception:  # noqa: BLE001 — best-effort
                pass
            fed.absorb_registry(default_registry(),
                                instance="scheduler")
            extras["federation_instances"] = sorted(fed.instances())
            try:
                code, snap = client._request("GET", "/debug/apf")
                if code == 200 and isinstance(snap, dict):
                    rejected = sum(
                        sum((lv.get("rejected") or {}).values())
                        for lv in (snap.get("levels") or {}).values())
                    extras["apf"] = {"rejections": rejected}
            except Exception:  # noqa: BLE001
                pass
        if ca is not None:
            extras["autoscaler"] = {
                "scaleup_decisions": ca.scale_up_events,
                "nodes_provisioned": ca.provisioner.provisioned_total,
                "nodes_end": len(target.list_nodes()),
            }
        dp = get_devprof()
        extras["telemetry"] = dp.summary() if dp.enabled else {}
        extras["freshness"] = collect_freshness(extras["telemetry"])
        extras["p99_e2e_ms"] = round(
            sched.metrics.e2e_scheduling_duration.quantile(
                0.99, "scheduled") * 1000, 1)
        if rest:
            try:
                api_conn.send("counts")
                extras["server"] = api_conn.recv()
            except (OSError, EOFError):
                pass
        return stats, extras
    finally:
        mesh_locality.configure(prev_scored)
        if engine is not None:
            try:
                engine.finish()
            except Exception:  # noqa: BLE001
                pass
        if ca is not None:
            ca.stop()
        if factory is not None:
            factory.stop()
        if rest and slo_engine.enabled:
            slo_engine.stop()
        if sched is not None:
            sched.stop()
        for c in clients:
            stop = getattr(c, "close", None)
            if stop is not None:
                try:
                    stop()
                except Exception:  # noqa: BLE001
                    pass
        if api_conn is not None:
            try:
                api_conn.send("stop")
                if api_conn.poll(5.0):
                    api_conn.recv()
            except (OSError, EOFError, BrokenPipeError):
                pass
            api_proc.join(timeout=5.0)
            if api_proc.is_alive():
                api_proc.terminate()
        if wal_dir:
            import shutil

            shutil.rmtree(wal_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# family verdicts


def family_verdicts(spec: FamilySpec, stats,
                    serve_budget_s: float = SERVE_LATENCY_BUDGET_S
                    ) -> Dict[str, bool]:
    out: Dict[str, bool] = {}
    for check in spec.checks:
        if check == "lost":
            # zero-lost covers the whole pipeline: every trace event
            # was actually injected (no swallowed send failures) and
            # every injected pod is accounted at quiesce
            out["zero_lost_pods"] = (
                stats.lost == 0
                and stats.injected == stats.expected
                and not stats.send_errors)
        elif check == "inversion":
            out["no_priority_inversion"] = \
                stats.priority_inversions == 0
        elif check == "gangs":
            out["gang_atomicity"] = stats.gangs_partial == 0
        elif check == "serve_latency":
            # a run where no serve pod ever bound must FAIL, not pass
            # vacuously with a defaulted 0.0 p99 (e.g. a wedged
            # autoscaler leaving the whole serve class pending)
            lat = stats.arrival_to_bind.get("serve") or {}
            out["serve_p99_within_budget"] = (
                lat.get("count", 0) > 0
                and lat.get("p99", 0.0) <= serve_budget_s)
        # "adjacency" is judged at the A/B level (needs both arms)
    return out


def _replay_diag(stats) -> None:
    import sys

    from kubernetes_tpu.harness import diagfmt

    seg = diagfmt.format_replay({
        "family": stats.family,
        "rate": stats.offered_rate,
        "p99_arrival_to_bind_ms": stats.latency_p99_ms(),
        "preempted": stats.preempted,
        "gangs_intact": stats.gangs_partial == 0,
        "lost": stats.lost,
        "expired": stats.expired,
        "inversions": stats.priority_inversions,
    })
    print(diagfmt.format_diag([seg]), file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the bench row


def run_replay_row(
    family: str,
    seed: int = 11,
    scale: float = 1.0,
    time_scale: float = 1.0,
    *,
    rest: bool = True,
    max_batch: int = 1024,
    qps: Optional[float] = 5000.0,
    wait_timeout: float = 900.0,
    progress: Optional[Callable[[str], None]] = None,
) -> dict:
    """One committed replay bench row (``bench.py --config
    replay:<family>``). The gang family runs scored + adjacency-blind
    arms; the row's verdicts are the family invariants PLUS the SLO
    verdicts from PR 8's engine."""
    spec = REPLAY_FAMILIES[family]
    trace = spec.build(seed, scale)

    def note(msg: str) -> None:
        if progress:
            progress(f"[replay:{family}] {msg}")

    note(f"{len(trace.events)} arrivals over "
         f"{trace.duration_s * time_scale:.0f}s "
         f"(offered {trace.offered_rate / max(time_scale, 1e-9):.1f} "
         f"pods/s), seed {seed}, "
         f"{'REST fabric' if rest else 'store-direct'}")
    stats, extras = run_replay_once(
        family, seed, scale, time_scale, rest=rest,
        max_batch=max_batch, qps=qps, wait_timeout=wait_timeout,
        trace=trace, progress=progress)
    _replay_diag(stats)
    verdicts = family_verdicts(spec, stats)
    offered = stats.offered_rate
    value = (stats.ever_bound / stats.last_bind_s
             if stats.last_bind_s > 0 else 0.0)
    n_nodes = len(spec.node_specs(scale))
    row = {
        "metric": (
            f"replay_{family}[{spec.title}, {n_nodes}nodes/"
            f"{len(trace.events)}pods offered "
            f"{offered:.1f}/s seed={seed}, "
            f"{'REST fabric' if rest else 'store-direct'} open-loop]"),
        "value": round(value, 1),
        "unit": "pods/s",
        "offered_rate_pods_per_sec": round(offered, 2),
        "rate_normalized_throughput": round(
            value / offered, 3) if offered > 0 else 0.0,
        "p99_arrival_to_bind_ms": round(stats.latency_p99_ms()),
        "p50_arrival_to_bind_ms": round(
            stats.arrival_to_bind.get("all", {}).get("p50", 0.0)
            * 1000),
        "injected": stats.injected,
        "ever_bound": stats.ever_bound,
        "expired": stats.expired,
        "preempted": stats.preempted,
        "pending_at_end": stats.pending_at_end,
        "lost_pods": stats.lost,
        "priority_inversions": stats.priority_inversions,
        "gangs": {"total": stats.gangs_total,
                  "placed": stats.gangs_placed,
                  "partial": stats.gangs_partial},
        "latency_by_class_ms": {
            cls: {"p50": round(v.get("p50", 0.0) * 1000),
                  "p99": round(v.get("p99", 0.0) * 1000)}
            for cls, v in stats.arrival_to_bind.items()
            if cls != "all"},
        "invariants": verdicts,
        "invariants_ok": all(verdicts.values()),
    }
    fresh = extras.get("freshness") or {}
    if fresh:
        row["freshness"] = fresh
        slo = fresh.get("slo") or {}
        gated = {n: v for n, v in slo.items()
                 if n not in spec.slo_exempt}
        row["slo_verdicts_ok"] = (
            all(v == "ok" for v in gated.values()) if gated else None)
        row["slo_gated"] = sorted(gated)
    if extras.get("telemetry"):
        row["telemetry"] = extras["telemetry"]
    for key in ("federation_instances", "autoscaler", "apf", "server"):
        if extras.get(key):
            row[key] = extras[key]
    if family == "gangs":
        note("adjacency-blind baseline arm")
        blind_stats, _blind_extras = run_replay_once(
            family, seed, scale, time_scale, rest=rest,
            max_batch=max_batch, qps=qps, wait_timeout=wait_timeout,
            trace=trace, scored=False, progress=progress)
        _replay_diag(blind_stats)
        scored_adj = stats.mean_gang_adjacency
        blind_adj = blind_stats.mean_gang_adjacency
        row["adjacency_ab"] = {
            "scored_mean_gang_adjacency": round(scored_adj, 3)
            if scored_adj is not None else None,
            "blind_mean_gang_adjacency": round(blind_adj, 3)
            if blind_adj is not None else None,
            "scored_beats_blind": (
                scored_adj is not None and blind_adj is not None
                and scored_adj < blind_adj),
        }
        # the A/B verdict joins the invariants DICT (not just the
        # rolled-up bool): perf_report names failed invariants from
        # the dict, so the two must never disagree
        row["invariants"]["adjacency_scored_beats_blind"] = \
            row["adjacency_ab"]["scored_beats_blind"]
        row["invariants_ok"] = all(row["invariants"].values())
    note(f"{stats.ever_bound}/{stats.injected} bound, p99 "
         f"arrival→bind {row['p99_arrival_to_bind_ms']}ms, "
         f"preempted {stats.preempted}, lost {stats.lost}, "
         f"invariants_ok {row['invariants_ok']}")
    return row


# ---------------------------------------------------------------------------
# chaos cell (tools/chaos_matrix.py --suite replay)


def run_replay_cell(
    seed: int,
    family: str = "storm",
    nodes: int = 0,
    pods: int = 120,
    wait_timeout: float = 180.0,
    progress: Optional[Callable[[str], None]] = None,
) -> dict:
    """One (family × seed) chaos cell: a compressed store-direct
    mini-replay asserting the family invariants — zero lost pods, gang
    atomicity (never a partially-placed gang), no priority inversion
    at quiesce. Cell size comes from the family scale knob — the
    LARGER of the two requests wins (``pods`` relative to the ~1200-pod
    full-scale traces, ``nodes`` relative to the ~120-node storm
    fleet); the family's own node/pod ratio is part of its shape, so
    the knobs steer scale rather than set exact counts."""
    scale = min(1.0, max(0.05, pods / 1200.0, nodes / 120.0))
    spec = REPLAY_FAMILIES[family]
    stats, _extras = run_replay_once(
        family, seed, scale, time_scale=0.2, rest=False,
        max_batch=256, wait_timeout=wait_timeout, progress=progress)
    verdicts = family_verdicts(spec, stats)
    ok = all(verdicts.values())
    failures = [k for k, v in verdicts.items() if not v]
    return {
        "seed": seed,
        "profile": family,
        "ok": ok,
        "failure": ", ".join(failures),
        "stats": {
            "injected": stats.injected,
            "ever_bound": stats.ever_bound,
            "expired": stats.expired,
            "preempted": stats.preempted,
            "lost": stats.lost,
            "gangs_partial": stats.gangs_partial,
            "inversions": stats.priority_inversions,
            "p99_arrival_to_bind_ms": round(stats.latency_p99_ms()),
        },
    }
