"""Federation storm: cluster-loss failover + saturation spillover.

The reshard/upgrade chaos families proved one partitioned control
plane survives its own seams; this harness is the tier above — K
INDEPENDENT clusters (each its own spawned apiserver + in-parent
scheduler replica, the upgrade harness's cell shape) behind the
federation layer, judged by the cluster-granularity twins of the same
invariants:

- **cluster loss**: SIGKILL an entire cell's process mid-storm → the
  ``ClusterRebalancer`` observes the dead ledger, fires failover, and
  every pod registered to the dead cell re-creates (same NAMES — the
  lost-pod invariant is name-keyed) on survivors; 0 lost fleet-wide,
  re-placement within ``RECOVERY_BUDGET_S``, and the surviving cells'
  watch streams never relist (confinement: only the dead cell's
  stream stops);
- **saturation spillover**: one cluster's capacity pinned far below
  its tenants' demand → overflow lands remotely (the what-if solve
  steers around the saturated column) while the saturated cell's own
  arrival→bind SLO stays green because it never queues what it
  cannot hold;
- **gang atomicity**: a gang is one placement unit; at quiesce every
  gang's members live on exactly one cluster;
- **bounded degradation**: the federation scheduler down → every
  create still routes (home hashing) and every cell keeps binding
  locally; ``run_degradation_differential`` holds the federation-on
  and federation-down arms to bit-identical bound sets at
  single-cluster scope.

``run_federation_row`` commits the bench rows (``bench.py --config
federation``), ``run_chaos_federation`` the seeded matrix cells
(``tools/chaos_matrix.py --suite federation``), and
``run_federation_mini_cell`` / ``run_degradation_differential`` the
tier-1 faces. ``tools/perf_report.py`` gates the committed rows
(``federation_flags``): lost pods, a cross-cluster gang split, a red
per-cluster SLO, or recovery ratio < 0.8 all fail ``--strict``.
"""

from __future__ import annotations

import math
import random
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from kubernetes_tpu.harness.workloads import node_template
from kubernetes_tpu.workloads.trace import Trace, generate_trace

FEDERATION_SCENARIOS = ("spill", "loss-early", "loss-mid", "loss-late",
                        "spill-loss")

FEDERATION_QPS = 300.0
RECOVERY_BUDGET_S = 30.0
P99_PER_CLUSTER_BUDGET_MS = 2500.0
RECOVERY_RATIO_FLOOR = 0.8

# where in the injection window the kill lands, per scenario
_KILL_AT = {"loss-early": 0.25, "loss-mid": 0.5, "loss-late": 0.75,
            "spill-loss": 0.5}


def build_federation_trace(seed: int, pods: int,
                           qps: float = FEDERATION_QPS,
                           namespaces: int = 12,
                           gang_every: int = 10,
                           gang_size: int = 4) -> Trace:
    """Open-loop arrivals fanned across ``namespaces`` tenants (the
    namespace is the federation's placement affinity key), with every
    ``gang_every``-th run of ``gang_size`` consecutive arrivals folded
    into one gang (same namespace — a gang is one tenant's job). No
    lifetimes: zero-lost is exactly "every arrival bound"."""
    from dataclasses import replace

    trace = generate_trace(
        seed, pods, pods / qps, family="federation",
        name_prefix="fed-", cpu_alpha=1.8, cpu_lo=100, cpu_hi=500,
        lifetime_modes=None, burst_factor=1.0, burst_period_s=0.0,
    )
    spread = [f"fed-{i}" for i in range(namespaces)]
    events = [replace(e, namespace=spread[i % len(spread)])
              for i, e in enumerate(trace.events)]
    i = 0
    g = 0
    while i + gang_size <= len(events):
        if (i // gang_size) % gang_every == gang_every - 1:
            gang = f"fg-{g}"
            g += 1
            ns = events[i].namespace
            for j in range(i, i + gang_size):
                events[j] = replace(events[j], gang=gang,
                                    gang_size=gang_size, namespace=ns)
        i += gang_size
    trace.events[:] = events
    return trace


def _cluster_nodes(cid: int, count: int, node_cpu: int) -> List[dict]:
    """Per-cluster node dicts with cluster-prefixed names — the bind
    records' node name is how the harness attributes a bind to a
    cluster."""
    out = []
    for i in range(count):
        d = node_template(i, cpu=str(node_cpu), memory="64Gi")
        name = f"c{cid}-node-{i}"
        d["metadata"]["name"] = name
        d["metadata"]["labels"]["kubernetes.io/hostname"] = name
        out.append(d)
    return out


def _fleet_sizing(trace: Trace, clusters: int, node_cpu: int,
                  scenario: str) -> Dict[int, Tuple[int, int]]:
    """(node count, node cpu cores) per cluster. Loss scenarios:
    survivors alone must absorb the whole trace (capacity is sized
    over K−1). Spill scenarios: cluster 0's capacity is pinned to
    ~45% of its home tenants' demand (tenants fan round-robin, so the
    home share is 1/K of total) — more than half its offered load MUST
    land remotely — while the siblings carry the slack."""
    demand_milli = sum(e.cpu_milli for e in trace.events)
    lossy = scenario in _KILL_AT
    spill = scenario.startswith("spill")
    carriers = max(clusters - 1, 1) if lossy else clusters
    per = max(
        2,
        math.ceil(demand_milli * 1.4 / carriers / (node_cpu * 1000)),
        math.ceil(len(trace.events) * 1.25 / carriers / 110),
    )
    sizing = {cid: (per, node_cpu) for cid in range(clusters)}
    if spill and clusters > 1:
        home_milli = demand_milli / clusters
        count0 = max(1, math.ceil(
            len(trace.events) / clusters * 0.6 / 110))
        cpu0 = max(1, round(home_milli * 0.45 / count0 / 1000))
        sizing[0] = (count0, cpu0)
    return sizing


def _gang_splits(name_cluster: Dict[str, int], trace: Trace) -> int:
    """Count gangs whose members ended on more than one cluster."""
    gangs: Dict[str, set] = {}
    for e in trace.events:
        if e.gang and e.name in name_cluster:
            gangs.setdefault(e.gang, set()).add(name_cluster[e.name])
    return sum(1 for members in gangs.values() if len(members) > 1)


def _per_cluster_latency(engine, clusters: int) -> Dict[str, dict]:
    """Per-cluster bound count + arrival→bind p99 from the engine's
    bind records (node ``c{k}-node-*`` → cluster k)."""
    buckets: Dict[int, List[float]] = {k: [] for k in range(clusters)}
    with engine._lock:
        bind = dict(engine._bind)
        arrival = dict(engine._arrival)
    for name, (t_rel, node) in bind.items():
        if not node.startswith("c"):
            continue
        try:
            cid = int(node.split("-", 1)[0][1:])
        except ValueError:
            continue
        if cid in buckets and name in arrival:
            buckets[cid].append(max(0.0, t_rel - arrival[name]))
    out: Dict[str, dict] = {}
    for cid, lats in buckets.items():
        if not lats:
            out[f"c{cid}"] = {"bound": 0, "p99_ms": 0.0}
            continue
        lats.sort()
        p99 = lats[min(len(lats) - 1, int(0.99 * len(lats)))]
        out[f"c{cid}"] = {"bound": len(lats),
                          "p99_ms": round(p99 * 1000.0, 1)}
    return out


# ---------------------------------------------------------------------------
# the spawned storm (bench rows + chaos cells)


class _FederationCells:
    """K independent spawned cells — each the upgrade harness's
    1-partition apiserver child (same child main: WAL, tokens, counts
    protocol), plus its in-parent scheduler fleet."""

    def __init__(self, count: int, progress: Optional[Callable] = None):
        import multiprocessing as mp
        import tempfile

        self.count = count
        self.progress = progress
        self.ctx = mp.get_context("spawn")
        self.wal_root = tempfile.mkdtemp(prefix="ktpu-federation-wal-")
        self.children: Dict[int, list] = {}
        self.urls: Dict[int, str] = {}

    def start(self) -> Dict[int, str]:
        import os

        from kubernetes_tpu.harness.upgrade import (
            _upgrade_apiserver_main,
        )

        for cid in range(self.count):
            seg = os.path.join(self.wal_root, f"c{cid}")
            os.makedirs(seg, exist_ok=True)
            parent_conn, child_conn = self.ctx.Pipe()
            proc = self.ctx.Process(
                target=_upgrade_apiserver_main,
                args=(child_conn, 0, 1, seg, False, False),
                daemon=True)
            proc.start()
            self.children[cid] = [parent_conn, proc]
        for cid, (conn, _proc) in self.children.items():
            self.urls[cid] = conn.recv()
        return dict(self.urls)

    def kill(self, cid: int) -> None:
        """SIGKILL the whole cell — the cluster-loss seam."""
        _conn, proc = self.children[cid]
        proc.kill()
        proc.join(timeout=5.0)

    def counts(self, cid: int, timeout: float = 10.0) -> Optional[dict]:
        conn, proc = self.children[cid]
        if not proc.is_alive():
            return None
        try:
            conn.send("counts")
            if conn.poll(timeout):
                return conn.recv()
        except (BrokenPipeError, EOFError, OSError):
            pass
        return None

    def teardown(self) -> None:
        import shutil

        for conn, _proc in self.children.values():
            try:
                conn.send("stop")
            except (BrokenPipeError, OSError):
                pass
        for conn, proc in self.children.values():
            try:
                if conn.poll(3.0):
                    conn.recv()
            except (EOFError, OSError):
                pass
            proc.join(timeout=3.0)
            if proc.is_alive():
                proc.terminate()
        shutil.rmtree(self.wal_root, ignore_errors=True)


def run_federation_storm(
    *,
    clusters: int = 3,
    pods: int = 900,
    qps: float = FEDERATION_QPS,
    seed: int = 18,
    scenario: str = "spill",
    node_cpu: int = 16,
    max_batch: int = 256,
    wait_timeout: float = 600.0,
    progress: Optional[Callable[[str], None]] = None,
) -> dict:
    """One federation storm over spawned cells. Returns the raw result
    surface; ``run_federation_row`` shapes the committed row and
    ``run_chaos_federation`` the matrix verdict."""
    from kubernetes_tpu.api.types import Node
    from kubernetes_tpu.client.restcluster import RestClusterClient
    from kubernetes_tpu.federation import (
        CapacityLedger,
        ClusterRebalancer,
        FederatedClusterClient,
        FederationScheduler,
        HomeMap,
    )
    from kubernetes_tpu.harness.perf import (
        attach_slo_baseline,
        collect_freshness,
        reset_sli_window,
    )
    from kubernetes_tpu.harness.upgrade import (
        CREATOR_TOKEN,
        SCHEDULER_TOKEN,
        _ReplicaFleet,
    )
    from kubernetes_tpu.observability import get_tracer
    from kubernetes_tpu.observability.devprof import get_devprof
    from kubernetes_tpu.utils.gctune import tune_for_throughput
    from kubernetes_tpu.workloads.replay import ReplayEngine
    from kubernetes_tpu.workloads.trace import events_to_pods

    if scenario not in FEDERATION_SCENARIOS:
        raise ValueError(
            f"unknown federation scenario {scenario!r} "
            f"(have: {', '.join(FEDERATION_SCENARIOS)})")
    tune_for_throughput()
    get_tracer().clear()
    reset_sli_window()
    get_devprof().reset(workload=f"federation/{scenario}")
    rng = random.Random(seed)
    namespaces = 12
    trace = build_federation_trace(seed, pods, qps,
                                   namespaces=namespaces)
    sizing = _fleet_sizing(trace, clusters, node_cpu, scenario)

    cells = _FederationCells(clusters, progress=progress)
    urls = cells.start()
    # RestClusterClient / _ReplicaFleet stay lazy imports (jax-heavy)
    all_clients: List = []
    fleets: Dict[int, object] = {}
    engine = None
    rebalancer = None
    probe_stop = threading.Event()

    def make_client(cid: int, token: str, watch_kinds=()):
        c = RestClusterClient(urls[cid], partition_urls=[urls[cid]],
                              token=token, watch_kinds=watch_kinds,
                              max_retries=4)
        all_clients.append(c)
        return c

    try:
        # per-cell creator clients (the federation's send/watch fabric)
        # and probe clients (the ledger's capacity poll)
        creators = {cid: make_client(cid, CREATOR_TOKEN,
                                     watch_kinds=("Pod",))
                    for cid in range(clusters)}
        probes = {cid: make_client(cid, CREATOR_TOKEN)
                  for cid in range(clusters)}

        for cid in range(clusters):
            nodes = [Node.from_dict(d) for d in
                     _cluster_nodes(cid, *sizing[cid])]
            for lo in range(0, len(nodes), 512):
                creators[cid].create_objects_bulk(
                    "Node", nodes[lo:lo + 512])
        if progress:
            progress(f"federation[{scenario}]: {clusters} cells, "
                     f"nodes per cluster {dict(sizing)}, "
                     f"{len(trace.events)} arrivals @ {qps:.0f}/s")

        # each cell's own scheduler brain (count=1 replica fleet)
        samples = events_to_pods(trace.events[:128])
        for cid in range(clusters):
            fleet = _ReplicaFleet(
                lambda j, _cid=cid: make_client(
                    _cid, SCHEDULER_TOKEN,
                    watch_kinds=("Pod", "Node")),
                count=1, use_batch=True, max_batch=max_batch,
                progress=progress)
            for sched in fleet.replicas:
                attach_slo_baseline(sched)
            fleet.run()
            fleets[cid] = fleet
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            if all(min(f.cache_nodes()) >= sizing[cid][0]
                   for cid, f in fleets.items()):
                break
            time.sleep(0.1)
        for fleet in fleets.values():
            fleet.warmup(samples)

        # federation layer: ledger ← probe loop, scheduler, client,
        # rebalancer
        ledger = CapacityLedger()
        home_map = HomeMap(list(range(clusters)), pin={
            f"fed-{i}": i % clusters for i in range(namespaces)})
        fed_sched = FederationScheduler(ledger,
                                        home_of=home_map.home_of)
        fed_client = FederatedClusterClient(
            dict(creators), fed_sched, ledger, home_map=home_map)

        fail_count: Dict[int, int] = {cid: 0 for cid in range(clusters)}

        def probe_loop() -> None:
            while not probe_stop.wait(0.25):
                for cid in list(probes):
                    if not ledger.alive(cid):
                        continue
                    try:
                        ns = probes[cid].list_nodes()
                        ps = probes[cid].list_pods()
                        ledger.refresh_from(cid, ns, ps)
                        fail_count[cid] = 0
                    except Exception:  # noqa: BLE001 — the cell may
                        fail_count[cid] += 1   # be dead; two misses
                        if fail_count[cid] >= 2:   # confirm it
                            ledger.mark_dead(cid)

        # one synchronous probe pass so placement starts informed
        for cid in range(clusters):
            ledger.refresh_from(cid, probes[cid].list_nodes(),
                                probes[cid].list_pods())
        probe = threading.Thread(target=probe_loop, daemon=True,
                                 name="federation-ledger-probe")
        probe.start()
        rebalancer = ClusterRebalancer(fed_client, interval_s=0.3)
        rebalancer.run()

        engine = ReplayEngine(fed_client, trace, time_scale=1.0,
                              expire=False, progress=progress)
        t_start = time.monotonic()
        engine.start()

        # ---- the seam: SIGKILL one whole cell mid-storm --------------
        victim: Optional[int] = None
        t_kill_rel = 0.0
        orphans: List[str] = []
        orphans_unbound: List[str] = []
        if scenario in _KILL_AT:
            at = _KILL_AT[scenario] * trace.duration_s
            while time.monotonic() - t_start < at \
                    and not engine.injection_done.is_set():
                time.sleep(0.05)
            # spill-loss kills a NON-saturated cell: the spillover load
            # and the loss then land on the same survivors
            victim = (rng.randrange(1, clusters)
                      if scenario == "spill-loss" and clusters > 1
                      else rng.randrange(clusters))
            with fed_client._lock:
                orphans = [name for (ns, name), cid
                           in fed_client._route.items()
                           if cid == victim]
            with engine._lock:
                bound_now = set(engine._bind)
            orphans_unbound = [n for n in orphans
                               if n not in bound_now]
            t_kill_rel = time.monotonic() - t_start
            if progress:
                progress(f"federation[{scenario}]: SIGKILL cluster "
                         f"{victim} ({len(orphans)} registered, "
                         f"{len(orphans_unbound)} unbound)")
            cells.kill(victim)
            # the dead cell's brain: stop it in the background — its
            # client calls may block on the dead socket
            threading.Thread(target=fleets.pop(victim).stop,
                             daemon=True).start()
            # the rebalancer observes the dead ledger and fires
            # failover; if the loop misses its window, fail over
            # directly (the invariant is the re-placement, not the
            # messenger)
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if any(a["action"]["op"] == "failover"
                       for a in rebalancer.actions):
                    break
                time.sleep(0.1)
            else:
                ledger.mark_dead(victim)
                fed_client.failover_cluster(victim, progress=progress)

        # ---- quiesce: every arrival bound ----------------------------
        want = len(trace.events)
        deadline = time.monotonic() + wait_timeout
        last_note = 0.0
        while time.monotonic() < deadline:
            with engine._lock:
                bound = len(engine._bind)
            if engine.injection_done.is_set() and bound >= want:
                break
            if progress and time.monotonic() - last_note > 10.0:
                last_note = time.monotonic()
                progress(f"federation[{scenario}]: {bound}/{want} "
                         f"bound")
            time.sleep(0.1)
        for fleet in fleets.values():
            fleet.wait_for_inflight_bindings()
        per_cluster = _per_cluster_latency(engine, clusters)
        with engine._lock:
            bind_final = dict(engine._bind)
        stats = engine.finish()
        engine = None
        time.sleep(0.5)

        # ---- invariants ----------------------------------------------
        # fleet-wide server truth from the SURVIVING cells
        name_cluster: Dict[str, int] = {}
        server_bound = 0
        for cid in range(clusters):
            if cid == victim:
                continue
            counts = cells.counts(cid)
            if counts is None:
                continue
            for ns, name, _rv, is_bound in counts["pods"]:
                name_cluster[name] = cid
                if is_bound:
                    server_bound += 1
        gang_splits = _gang_splits(name_cluster, trace)
        # recovery: of the victim's pods unbound at the kill, how many
        # re-bound on survivors inside the budget
        recovered = 0
        for n in orphans_unbound:
            rec = bind_final.get(n)
            if rec is not None \
                    and rec[0] - t_kill_rel <= RECOVERY_BUDGET_S:
                recovered += 1
        recovery_ratio = (recovered / len(orphans_unbound)
                          if orphans_unbound else 1.0)
        # relist confinement: the surviving cells' streams never relist
        survivor_relists = 0
        for cid in range(clusters):
            if cid == victim:
                continue
            survivor_relists += sum(
                creators[cid].stream_relists.values())
        for cid, fleet in fleets.items():
            for sched in fleet.replicas:
                survivor_relists += sum(
                    sched.client.stream_relists.values())
        fresh = collect_freshness(
            get_devprof().summary() if get_devprof().enabled else None)
        slo = (fresh or {}).get("slo") or {}
        counters = fed_client.counters()
        result = {
            "scenario": scenario,
            "seed": seed,
            "clusters": clusters,
            "qps": qps,
            "injected": stats.injected,
            "ever_bound": stats.ever_bound,
            "server_bound": server_bound,
            "lost_pods": stats.lost,
            "send_errors": list(stats.send_errors),
            "p99_arrival_to_bind_ms": round(stats.latency_p99_ms()),
            "p50_arrival_to_bind_ms": round(
                stats.arrival_to_bind.get("all", {}).get("p50", 0.0)
                * 1000),
            "last_bind_s": stats.last_bind_s,
            "offered_rate": stats.offered_rate,
            "per_cluster": per_cluster,
            "per_cluster_slo_ok": all(
                v["p99_ms"] <= P99_PER_CLUSTER_BUDGET_MS
                for v in per_cluster.values() if v["bound"] > 0),
            "gangs_total": len(
                {e.gang for e in trace.events if e.gang}),
            "gang_splits": gang_splits,
            "spilled": counters["spilled"],
            "fallback_placements": counters["fallback_placements"],
            "failovers": counters["failovers"],
            "failover_replaced": counters["failover_replaced"],
            "victim": victim,
            "orphans": len(orphans),
            "orphans_unbound_at_kill": len(orphans_unbound),
            "recovered_in_budget": recovered,
            "recovery_budget_s": RECOVERY_BUDGET_S,
            "recovery_ratio": round(recovery_ratio, 3),
            "survivor_relists": survivor_relists,
            "rebalancer_actions": [a["action"]["op"]
                                   for a in rebalancer.actions],
            "freshness": fresh,
            "slo_verdicts_ok": (all(v == "ok" for v in slo.values())
                                if slo else None),
        }
        # ---- fleet trace across the cross-cluster hop ----------------
        try:
            from kubernetes_tpu.observability.fleettrace import (
                collect_fleet_trace,
            )

            doc, cp = collect_fleet_trace(
                remote=[(f"cluster-{cid}", urls[cid])
                        for cid in range(clusters) if cid != victim],
                local=[("federation", get_tracer())],
                token=SCHEDULER_TOKEN, max_pods=25)
            result["fleet_trace_doc"] = doc
            result["critical_path"] = cp
        except Exception:  # noqa: BLE001 — tracing must not fail a row
            pass
        return result
    finally:
        probe_stop.set()
        if rebalancer is not None:
            rebalancer.stop()
        if engine is not None:
            try:
                engine.finish()
            except Exception:  # noqa: BLE001 — teardown must not mask
                pass
        for fleet in fleets.values():
            try:
                fleet.stop()
            except Exception:  # noqa: BLE001
                pass
        for c in all_clients:
            try:
                c._stop_watches()
                c._drop_conn()
            except Exception:  # noqa: BLE001
                pass
        cells.teardown()
        import gc

        gc.collect()


# ---------------------------------------------------------------------------
# the committed rows + diag


def _federation_ok(res: dict) -> Tuple[bool, str]:
    checks = {
        "lost_pods": res["lost_pods"] == 0,
        "all_bound": res["ever_bound"] >= res["injected"] > 0,
        "send_errors": not res["send_errors"],
        "gangs_atomic": res["gang_splits"] == 0,
        "relist_confinement": res["survivor_relists"] == 0,
        "per_cluster_slo": res["per_cluster_slo_ok"],
        "recovery": (res["recovery_ratio"] >= RECOVERY_RATIO_FLOOR
                     if res["victim"] is not None else True),
        "slo": res["slo_verdicts_ok"] is not False,
    }
    if res["scenario"].startswith("spill"):
        checks["spilled"] = res["spilled"] > 0
    if res["victim"] is not None:
        checks["failed_over"] = res["failovers"] >= 1
    bad = [k for k, ok in checks.items() if not ok]
    return not bad, " ".join(bad)


def _federation_diag(res: dict) -> None:
    import sys

    from kubernetes_tpu.harness import diagfmt

    seg = diagfmt.format_federation({
        "clusters": res["clusters"],
        "spilled": res["spilled"],
        "failovers": res["failovers"],
        "lost": res["lost_pods"],
        "recovery": res["recovery_ratio"],
    })
    if seg:
        print(diagfmt.format_diag([seg]), file=sys.stderr, flush=True)


def run_federation_row(
    pods: int = 900,
    qps: float = FEDERATION_QPS,
    seed: int = 18,
    *,
    mode: str = "spill",
    clusters: int = 3,
    node_cpu: int = 16,
    max_batch: int = 256,
    wait_timeout: float = 600.0,
    progress: Optional[Callable[[str], None]] = None,
) -> dict:
    """One committed federation row (``bench.py --config federation``
    emits two: ``mode='spill'`` and ``mode='loss'``). Headline =
    rate-normalized throughput + per-cluster p99 arrival→bind, verdict
    surface = lost/gang/relist/recovery invariants, gated by
    ``perf_report``'s ``federation_flags``."""
    scenario = "loss-mid" if mode == "loss" else mode
    res = run_federation_storm(
        clusters=clusters, pods=pods, qps=qps, seed=seed,
        scenario=scenario, node_cpu=node_cpu, max_batch=max_batch,
        wait_timeout=wait_timeout, progress=progress)
    ok, why = _federation_ok(res)
    value = (res["ever_bound"] / res["last_bind_s"]
             if res["last_bind_s"] > 0 else 0.0)
    offered = res["offered_rate"]
    label = ("cluster-loss SIGKILL" if res["victim"] is not None
             else "saturation spillover")
    row = {
        "metric": (
            f"federation_{mode}[open-loop {qps:.0f}/s "
            f"{clusters}clusters {label}, {pods}pods seed={seed}, "
            f"REST fabric]"),
        "value": round(value, 1),
        "unit": "pods/s",
        "offered_rate_pods_per_sec": round(offered, 2),
        "rate_normalized_throughput": round(
            value / offered, 3) if offered > 0 else 0.0,
        "p99_arrival_to_bind_ms": res["p99_arrival_to_bind_ms"],
        "p50_arrival_to_bind_ms": res["p50_arrival_to_bind_ms"],
        "per_cluster": res["per_cluster"],
        "per_cluster_slo_ok": res["per_cluster_slo_ok"],
        "injected": res["injected"],
        "ever_bound": res["ever_bound"],
        "lost_pods": res["lost_pods"],
        "gang_splits": res["gang_splits"],
        "spilled": res["spilled"],
        "failovers": res["failovers"],
        "failover_replaced": res["failover_replaced"],
        "recovery_ratio": res["recovery_ratio"],
        "survivor_relists": res["survivor_relists"],
        "fallback_placements": res["fallback_placements"],
        "invariants_ok": ok,
        "invariants": {"failed": why} if why else {},
    }
    fresh = res.get("freshness") or {}
    if fresh:
        row["freshness"] = fresh
        slo = fresh.get("slo") or {}
        row["slo_verdicts_ok"] = res["slo_verdicts_ok"]
        row["slo_gated"] = sorted(slo)
    cp = res.get("critical_path")
    if cp:
        row["critical_path"] = {k: v for k, v in cp.items()
                                if k != "per_pod"}
    _federation_diag(res)
    if progress:
        progress(f"[federation/{mode}] {res['ever_bound']}/"
                 f"{res['injected']} bound, spilled {res['spilled']}, "
                 f"failovers {res['failovers']}, recovery "
                 f"{res['recovery_ratio']:.2f}, lost "
                 f"{res['lost_pods']}, "
                 f"{'OK' if ok else 'FAILED: ' + why}")
    return row


# ---------------------------------------------------------------------------
# chaos cells (tools/chaos_matrix.py --suite federation)


def run_chaos_federation(seed: int, nodes: int = 0, pods: int = 400,
                         wait_timeout: float = 300.0,
                         progress: Optional[Callable] = None,
                         scenario: str = "loss-mid") -> Dict:
    """One seeded (scenario × seed) cell: kill timing × which-cluster
    (seed-chosen victim) × spillover load, compressed to a few hundred
    pods over 3 spawned cells."""
    if scenario not in FEDERATION_SCENARIOS:
        raise ValueError(
            f"unknown federation scenario {scenario!r} "
            f"(have: {', '.join(FEDERATION_SCENARIOS)})")
    res = run_federation_storm(
        clusters=3, pods=pods, qps=max(100.0, pods / 4.0), seed=seed,
        scenario=scenario, node_cpu=16, max_batch=256,
        wait_timeout=wait_timeout, progress=progress)
    ok, why = _federation_ok(res)
    return {
        "seed": seed, "profile": scenario, "ok": ok,
        "failure": "" if ok else (
            f"{why} lost={res['lost_pods']} "
            f"splits={res['gang_splits']} "
            f"relists={res['survivor_relists']} "
            f"recovery={res['recovery_ratio']}"),
        "stats": {
            "injected": res["injected"],
            "ever_bound": res["ever_bound"],
            "spilled": res["spilled"],
            "failovers": res["failovers"],
            "victim": res["victim"],
            "orphans": res["orphans"],
            "recovery_ratio": res["recovery_ratio"],
            "p99_arrival_to_bind_ms": res["p99_arrival_to_bind_ms"],
        },
    }


# ---------------------------------------------------------------------------
# tier-1 faces: in-process mini-cell + the degradation differential


def _inproc_cluster(cid: int, sizing: Tuple[int, int],
                    max_batch: int, samples) -> dict:
    """One in-process cell: store + gang scheduler + batch sidecar —
    the sustained harness's stack, one per cluster."""
    from kubernetes_tpu.api.types import Node
    from kubernetes_tpu.apiserver.store import ClusterStore
    from kubernetes_tpu.config.feature_gates import FeatureGates
    from kubernetes_tpu.harness.perf import attach_slo_baseline
    from kubernetes_tpu.scheduler.scheduler import Scheduler
    from kubernetes_tpu.sidecar import attach_batch_scheduler

    store = ClusterStore()
    for d in _cluster_nodes(cid, *sizing):
        store.add_node(Node.from_dict(d))
    sched = Scheduler.create(
        store, feature_gates=FeatureGates({"TPUBatchScheduler": True}),
        provider="GangSchedulingProvider")
    bs = attach_batch_scheduler(sched, max_batch=max_batch)
    attach_slo_baseline(sched)
    sched.start()
    if samples:
        bs.warmup(sample_pods=samples)
    return {"store": store, "sched": sched, "bs": bs}


def _pump_cells(cells: Dict[int, dict], engine, ledger, deadline: float,
                on_tick: Optional[Callable] = None,
                settle_s: float = 1.0) -> None:
    """Round-robin the live cells' batch schedulers until quiesce —
    the sustained pump fanned across clusters, with a ledger refresh
    (and an optional chaos hook) folded into the loop."""
    quiet_since = None
    last_refresh = 0.0
    while time.monotonic() < deadline:
        if on_tick is not None:
            on_tick()
        now = time.monotonic()
        if now - last_refresh >= 0.2:
            last_refresh = now
            for cid, cell in cells.items():
                if ledger.alive(cid):
                    ledger.refresh_from(cid,
                                        cell["store"].list_nodes(),
                                        cell["store"].list_pods())
        progressed = False
        busy = not engine.injection_done.is_set()
        for cid, cell in cells.items():
            if not ledger.alive(cid):
                continue
            cell["sched"].queue.flush_backoff_completed()
            progressed |= bool(
                cell["bs"].run_batch(pop_timeout=0.002))
            busy |= cell["sched"].queue.pending_active_count() > 0
        now = time.monotonic()
        if progressed or busy:
            quiet_since = None
        elif quiet_since is None:
            quiet_since = now
        elif now - quiet_since >= settle_s:
            return
        time.sleep(0.002)
    raise TimeoutError("federation mini-cell did not quiesce")


def run_federation_mini_cell(
    clusters: int = 3,
    pods: int = 240,
    qps: float = 400.0,
    seed: int = 18,
    *,
    scenario: str = "loss-mid",
    node_cpu: int = 16,
    max_batch: int = 64,
    wait_timeout: float = 120.0,
    progress: Optional[Callable[[str], None]] = None,
) -> dict:
    """CI-fast federation cell: K in-process clusters under the open
    loop, with the cluster-loss seam modeled as "stop the cell's
    scheduler + mark its ledger dead + failover" (the spawned storm
    owns the real SIGKILL). Returns the verdict surface the tier-1
    tests assert on."""
    from kubernetes_tpu.federation import (
        CapacityLedger,
        ClusterRebalancer,
        FederatedClusterClient,
        FederationScheduler,
        HomeMap,
    )
    from kubernetes_tpu.observability import get_tracer
    from kubernetes_tpu.workloads.replay import ReplayEngine
    from kubernetes_tpu.workloads.trace import events_to_pods

    if scenario not in FEDERATION_SCENARIOS:
        raise ValueError(f"unknown federation scenario {scenario!r}")
    get_tracer().clear()
    rng = random.Random(seed)
    namespaces = 6
    trace = build_federation_trace(seed, pods, qps,
                                   namespaces=namespaces)
    sizing = _fleet_sizing(trace, clusters, node_cpu, scenario)
    samples = events_to_pods(trace.events[:64])
    cells = {cid: _inproc_cluster(cid, sizing[cid],
                                  max_batch, samples)
             for cid in range(clusters)}
    ledger = CapacityLedger()
    home_map = HomeMap(list(range(clusters)), pin={
        f"fed-{i}": i % clusters for i in range(namespaces)})
    fed_sched = FederationScheduler(ledger, home_of=home_map.home_of)
    fed_client = FederatedClusterClient(
        {cid: cell["store"] for cid, cell in cells.items()},
        fed_sched, ledger, home_map=home_map)
    for cid, cell in cells.items():
        ledger.refresh_from(cid, cell["store"].list_nodes(),
                            cell["store"].list_pods())
    rebalancer = ClusterRebalancer(fed_client, interval_s=0.1)
    engine = None
    victim: Optional[int] = None
    killed = [False]
    t_kill_rel = [0.0]
    orphans_unbound: List[str] = []
    try:
        engine = ReplayEngine(fed_client, trace, time_scale=1.0,
                              expire=False, progress=progress)
        t_start = time.monotonic()
        kill_at = _KILL_AT.get(scenario)
        if kill_at is not None:
            victim = (rng.randrange(1, clusters)
                      if scenario == "spill-loss" and clusters > 1
                      else rng.randrange(clusters))

        def on_tick() -> None:
            rebalancer.tick()
            if kill_at is None or killed[0]:
                return
            if time.monotonic() - t_start \
                    < kill_at * trace.duration_s \
                    and not engine.injection_done.is_set():
                return
            killed[0] = True
            with fed_client._lock:
                orphans = [name for (ns, name), cid
                           in fed_client._route.items()
                           if cid == victim]
            with engine._lock:
                bound_now = set(engine._bind)
            orphans_unbound[:] = [n for n in orphans
                                  if n not in bound_now]
            t_kill_rel[0] = time.monotonic() - t_start
            cells[victim]["sched"].stop()
            ledger.mark_dead(victim)
            if progress:
                progress(f"mini-cell: cluster {victim} down "
                         f"({len(orphans)} registered)")
            # the rebalancer's next tick observes the death and fires
            # failover through the driver
            rebalancer.tick()

        engine.start()
        _pump_cells(cells, engine, ledger,
                    time.monotonic() + wait_timeout, on_tick=on_tick)
        for cid, cell in cells.items():
            if victim is not None and cid == victim:
                continue
            cell["sched"].wait_for_inflight_bindings(timeout=30.0)
        # the engine observes binds through the watch fan-in, which
        # can lag the store by a delivery tick: settle until the
        # engine's bind ledger catches the server truth (bounded)
        want_bound = sum(
            1 for cid, cell in cells.items() if cid != victim
            for p in cell["store"].list_pods() if p.spec.node_name)
        settle_deadline = time.monotonic() + 10.0
        while time.monotonic() < settle_deadline:
            with engine._lock:
                got = len(engine._bind)
            if got >= want_bound:
                break
            time.sleep(0.02)
        per_cluster = _per_cluster_latency(engine, clusters)
        with engine._lock:
            bind_final = dict(engine._bind)
        stats = engine.finish()
        engine = None
        name_cluster: Dict[str, int] = {}
        for cid, cell in cells.items():
            if cid == victim:
                continue
            for p in cell["store"].list_pods():
                name_cluster[p.metadata.name] = cid
        recovered = sum(
            1 for n in orphans_unbound
            if n in bind_final
            and bind_final[n][0] - t_kill_rel[0] <= RECOVERY_BUDGET_S)
        counters = fed_client.counters()
        return {
            "injected": stats.injected,
            "ever_bound": stats.ever_bound,
            "lost": stats.lost,
            "p99_arrival_to_bind_ms": round(stats.latency_p99_ms()),
            "per_cluster": per_cluster,
            "gang_splits": _gang_splits(name_cluster, trace),
            "spilled": counters["spilled"],
            "failovers": counters["failovers"],
            "failover_replaced": counters["failover_replaced"],
            "fallback_placements": counters["fallback_placements"],
            "victim": victim,
            "orphans_unbound_at_kill": len(orphans_unbound),
            "recovery_ratio": (recovered / len(orphans_unbound)
                               if orphans_unbound else 1.0),
            "rebalancer_actions": [a["action"]["op"]
                                   for a in rebalancer.actions],
        }
    finally:
        if engine is not None:
            try:
                engine.finish()
            except Exception:  # noqa: BLE001 — teardown must not mask
                pass
        for cell in cells.values():
            try:
                cell["sched"].stop()
            except Exception:  # noqa: BLE001
                pass
        import gc

        gc.collect()


def run_degradation_differential(
    pods: int = 160,
    qps: float = 400.0,
    seed: int = 18,
    *,
    node_cpu: int = 16,
    max_batch: int = 64,
    wait_timeout: float = 120.0,
    progress: Optional[Callable[[str], None]] = None,
) -> dict:
    """The degradation invariant, held differentially: the SAME trace
    through a single-cluster federation with the layer UP and with the
    layer DOWN (every create degrades to home routing). Both arms must
    bind the bit-identical set of pod names — federation changes
    WHERE multi-cluster work lands, never WHETHER work binds."""
    from kubernetes_tpu.federation import (
        CapacityLedger,
        FederatedClusterClient,
        FederationScheduler,
        HomeMap,
    )
    from kubernetes_tpu.workloads.replay import ReplayEngine
    from kubernetes_tpu.workloads.trace import events_to_pods

    trace = build_federation_trace(seed, pods, qps, namespaces=4)
    samples = events_to_pods(trace.events[:64])
    sizing = _fleet_sizing(trace, 1, node_cpu, "spill")

    def arm(down: bool) -> Tuple[List[str], dict]:
        cells = {0: _inproc_cluster(0, sizing[0],
                                    max_batch, samples)}
        ledger = CapacityLedger()
        home_map = HomeMap([0])
        fed_sched = FederationScheduler(ledger,
                                        home_of=home_map.home_of)
        fed_sched.set_down(down)
        fed_client = FederatedClusterClient(
            {0: cells[0]["store"]}, fed_sched, ledger,
            home_map=home_map)
        ledger.refresh_from(0, cells[0]["store"].list_nodes(),
                            cells[0]["store"].list_pods())
        engine = None
        try:
            engine = ReplayEngine(fed_client, trace, time_scale=1.0,
                                  expire=False, progress=progress)
            engine.start()
            _pump_cells(cells, engine, ledger,
                        time.monotonic() + wait_timeout)
            cells[0]["sched"].wait_for_inflight_bindings(timeout=30.0)
            stats = engine.finish()
            engine = None
            bound = sorted(
                p.metadata.name for p in cells[0]["store"].list_pods()
                if p.spec.node_name)
            return bound, {"lost": stats.lost,
                           "fallbacks":
                           fed_client.fallback_placements}
        finally:
            if engine is not None:
                try:
                    engine.finish()
                except Exception:  # noqa: BLE001
                    pass
            cells[0]["sched"].stop()

    bound_on, on_meta = arm(down=False)
    bound_down, down_meta = arm(down=True)
    return {
        "bound_on": bound_on,
        "bound_down": bound_down,
        "identical": bound_on == bound_down,
        "on": on_meta,
        "down": down_meta,
    }
