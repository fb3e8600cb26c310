"""Sustained-arrival bench row: open-loop arrivals through the batch
path.

Every store-direct row before this one pre-created its pods in one
burst, so per-pod latency was batch-amortized and the cycle length
never showed up in a committed number. This harness drives the
headline-shaped workload OPEN-LOOP through the replay engine —
pods arrive on a clock at a target QPS (default 5k/s, the REST rows'
client discipline), binds are observed on the engine's own watch
stream, and the row's headline is **p99 arrival→bind latency**: the
number a submitting user experiences.

The row also carries its verdict surface:

- ``freshness.slo.snapshot_staleness`` — the staleness SLI stays
  green only if no solve runs against a stale mirror;
- ``lost_pods`` — the replay engine's zero-lost quiesce invariant.

``run_sustained_cell`` is the tier-1 face: a small, time-compressed
cell asserting the staleness SLO holds and nothing is lost, cheap
enough for the fast suite. ``tools/perf_report.py`` gates the
committed rows (``sustained_flags``): p99 arrival→bind > 500 ms, lost
pods, or a red staleness verdict all fail ``--strict``.
"""

from __future__ import annotations

import math
import time
from typing import Callable, Dict, List, Optional

from kubernetes_tpu.harness.workloads import node_template
from kubernetes_tpu.workloads.trace import Trace, generate_trace

SUSTAINED_QPS = 5000.0
P99_ARRIVAL_TO_BIND_BUDGET_MS = 500.0


def build_sustained_trace(seed: int, pods: int,
                          qps: float = SUSTAINED_QPS) -> Trace:
    """Open-loop steady arrival trace: ``pods`` Poisson arrivals at
    ``qps`` (no burst epochs — the row isolates the steady cycle, not
    the burst absorber), lightly heavy-tailed cpu sizes so pad buckets see
    realistic occupancy, NO lifetimes (zero-lost is then exactly
    "every arrival bound"). Deterministic per (seed, pods, qps) — the
    trace.py contract."""
    return generate_trace(
        seed, pods, pods / qps, family="sustained",
        name_prefix="su-", cpu_alpha=1.8, cpu_lo=100, cpu_hi=500,
        lifetime_modes=None, burst_factor=1.0, burst_period_s=0.0,
    )


def sustained_nodes(trace: Trace, node_cpu: int = 32,
                    headroom: float = 1.25) -> List[dict]:
    """A fleet sized from the trace itself: total cpu demand ×
    ``headroom``, so every arrival fits (the row measures latency, not
    bin-packing pressure) while the cluster stays small enough that
    plane encode/solve cost reflects a realistic node:pod ratio."""
    demand_milli = sum(e.cpu_milli for e in trace.events)
    n = max(
        8,
        math.ceil(demand_milli * headroom / (node_cpu * 1000)),
        # node_template caps max-pods at 110/node: the pods resource
        # must fit every arrival too, or the tail parks unschedulable
        # forever and the run never quiesces
        math.ceil(len(trace.events) * headroom / 110),
    )
    return [node_template(i, cpu=str(node_cpu), memory="64Gi")
            for i in range(n)]


def _pump_to_quiesce(sched, bs, engine, deadline: float,
                     settle_s: float = 1.0) -> None:
    """Drive the scheduler until the replay is over (same loop as the
    replay rows: injection done, queues drained, quiet for a settle
    window — arrivals keep re-waking the queue, so 'drained' must hold
    for a window, not an instant)."""
    quiet_since = None
    while time.monotonic() < deadline:
        sched.queue.flush_backoff_completed()
        progressed = bs.run_batch(pop_timeout=0.01)
        now = time.monotonic()
        if progressed:
            quiet_since = None
            continue
        busy = (not engine.injection_done.is_set()
                or sched.queue.pending_active_count() > 0)
        if busy:
            quiet_since = None
        elif quiet_since is None:
            quiet_since = now
        elif now - quiet_since >= settle_s:
            return
        time.sleep(0.005)
    raise TimeoutError("sustained replay did not quiesce before deadline")


def run_sustained_once(
    trace: Trace,
    *,
    node_cpu: int = 32,
    max_batch: int = 4096,
    wait_timeout: float = 600.0,
    progress: Optional[Callable[[str], None]] = None,
):
    """One open-loop run against an in-process store. Returns
    ``(stats, extras)`` — the replay engine's postmortem plus the
    telemetry/freshness/mirror sub-objects."""
    from kubernetes_tpu.api.types import Node
    from kubernetes_tpu.apiserver.store import ClusterStore
    from kubernetes_tpu.config.feature_gates import FeatureGates
    from kubernetes_tpu.harness.perf import (
        attach_slo_baseline,
        collect_freshness,
        reset_sli_window,
    )
    from kubernetes_tpu.observability import get_tracer
    from kubernetes_tpu.observability.devprof import get_devprof
    from kubernetes_tpu.scheduler.scheduler import Scheduler
    from kubernetes_tpu.sidecar import attach_batch_scheduler
    from kubernetes_tpu.utils.gctune import tune_for_throughput
    from kubernetes_tpu.workloads.replay import ReplayEngine
    from kubernetes_tpu.workloads.trace import events_to_pods

    tune_for_throughput()
    get_tracer().clear()
    get_devprof().reset(workload="sustained")
    reset_sli_window()
    store = ClusterStore()
    for d in sustained_nodes(trace, node_cpu=node_cpu):
        store.add_node(Node.from_dict(d))
    gates = FeatureGates({"TPUBatchScheduler": True})
    sched = Scheduler.create(store, feature_gates=gates,
                             provider="GangSchedulingProvider")
    bs = attach_batch_scheduler(sched, max_batch=max_batch)
    attach_slo_baseline(sched)
    sched.start()
    engine = None
    try:
        samples = events_to_pods(trace.events[:128])
        warm = bs.warmup(sample_pods=samples) if samples else 0.0
        if progress and warm > 0.05:
            progress(f"sustained: solver warmup {warm:.1f}s")
        engine = ReplayEngine(store, trace, time_scale=1.0,
                              expire=False, progress=progress)
        t0 = time.monotonic()
        engine.start()
        _pump_to_quiesce(sched, bs, engine,
                         time.monotonic() + wait_timeout)
        sched.wait_for_inflight_bindings(timeout=30.0)
        wall = time.monotonic() - t0
        stats = engine.finish()
        engine = None
        dp = get_devprof()
        telemetry = dp.summary() if dp.enabled else {}
        extras: Dict = {
            "wall_s": round(wall, 2),
            "telemetry": telemetry,
            "freshness": collect_freshness(telemetry),
            "mirror": bs.mirror_info(telemetry),
            "session": {
                "incremental_hits": bs.session.incremental_hits,
                "rebuilds": bs.session.rebuilds,
            },
        }
        return stats, extras
    finally:
        if engine is not None:
            try:
                engine.finish()
            except Exception:  # noqa: BLE001 — teardown must not mask
                pass
        sched.stop()
        # tune_for_throughput defers collection: reclaim this run's
        # device/plane garbage NOW instead of leaving a multi-hundred-
        # ms GC pause for whatever runs next in the process (the same
        # discipline bench.py applies between rows)
        import gc

        gc.collect()


def run_sustained_row(
    pods: int = 30_000,
    qps: float = SUSTAINED_QPS,
    seed: int = 14,
    *,
    node_cpu: int = 32,
    max_batch: int = 4096,
    wait_timeout: float = 900.0,
    progress: Optional[Callable[[str], None]] = None,
) -> dict:
    """The committed sustained-arrival row (``bench.py --config
    sustained``). Headline = arrival→bind p99 next to rate-normalized
    throughput; verdict surface = zero lost + staleness SLO."""
    trace = build_sustained_trace(seed, pods, qps)
    n_nodes = len(sustained_nodes(trace, node_cpu=node_cpu))
    if progress:
        progress(f"[sustained] {len(trace.events)} arrivals over "
                 f"{trace.duration_s:.1f}s (offered "
                 f"{trace.offered_rate:.0f} pods/s), {n_nodes} nodes, "
                 f"seed {seed}")
    stats, extras = run_sustained_once(
        trace, node_cpu=node_cpu, max_batch=max_batch,
        wait_timeout=wait_timeout, progress=progress)
    _sustained_diag(extras)
    offered = stats.offered_rate
    value = (stats.ever_bound / stats.last_bind_s
             if stats.last_bind_s > 0 else 0.0)
    zero_lost = (stats.lost == 0
                 and stats.injected == stats.expected
                 and not stats.send_errors)
    row = {
        "metric": (
            f"sustained_arrival[open-loop {offered:.0f}/s "
            f"{n_nodes}nodes/{len(trace.events)}pods seed={seed}, "
            f"store-direct replay engine]"),
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
        "pending_at_end": stats.pending_at_end,
        "lost_pods": stats.lost,
        "invariants": {"zero_lost_pods": zero_lost},
        "invariants_ok": zero_lost,
        "mirror": extras.get("mirror"),
        "session": extras.get("session"),
    }
    if extras.get("telemetry"):
        row["telemetry"] = extras["telemetry"]
    fresh = extras.get("freshness") or {}
    if fresh:
        row["freshness"] = fresh
        slo = fresh.get("slo") or {}
        # every SLO gates this row — a sustained 5k/s open-loop run
        # with a sub-500ms latency bar has no excuse for a red verdict
        row["slo_verdicts_ok"] = (
            all(v == "ok" for v in slo.values()) if slo else None)
        row["slo_gated"] = sorted(slo)
    if progress:
        progress(f"[sustained] {stats.ever_bound}/{stats.injected} "
                 f"bound, p99 arrival→bind "
                 f"{row['p99_arrival_to_bind_ms']}ms, lost "
                 f"{stats.lost}")
    return row


def _sustained_diag(extras: Dict) -> None:
    import sys

    from kubernetes_tpu.harness import diagfmt

    seg = diagfmt.format_mirror(extras.get("mirror"))
    if seg:
        print(diagfmt.format_diag([seg]), file=sys.stderr, flush=True)


def run_sustained_cell(
    pods: int = 600,
    qps: float = 400.0,
    seed: int = 14,
    *,
    node_cpu: int = 16,
    max_batch: int = 64,
    wait_timeout: float = 120.0,
    progress: Optional[Callable[[str], None]] = None,
) -> dict:
    """The tier-1 mini-cell: a small open-loop run (compressed scale,
    small pad bucket so several cycles occur) returning just the
    verdict surface — staleness SLO verdict, lost count, p99, encode
    share. The fast suite asserts nothing is lost and the staleness
    verdict stays green, inside the tier-1 time budget."""
    trace = build_sustained_trace(seed, pods, qps)
    stats, extras = run_sustained_once(
        trace, node_cpu=node_cpu, max_batch=max_batch,
        wait_timeout=wait_timeout, progress=progress)
    telemetry = extras.get("telemetry") or {}
    slo = (extras.get("freshness") or {}).get("slo") or {}
    return {
        "injected": stats.injected,
        "ever_bound": stats.ever_bound,
        "lost": stats.lost,
        "p99_arrival_to_bind_ms": round(stats.latency_p99_ms()),
        "staleness_verdict": slo.get("snapshot_staleness"),
        "max_staleness_s": telemetry.get("max_staleness_s"),
        "encode_share": telemetry.get("encode_share", 0.0),
        "mirror": extras.get("mirror"),
        "session": extras.get("session"),
    }
