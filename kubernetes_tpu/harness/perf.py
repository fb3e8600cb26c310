"""scheduler_perf-style benchmark runner (reference
``test/integration/scheduler_perf/``): executes an op list against an
in-process store + scheduler (no kubelets — binding is the finish line,
SURVEY.md section 3.5), samples scheduling throughput at 1 Hz
(``util.go:220-280`` throughputCollector), scrapes the scheduler
histograms, and emits DataItems-shaped JSON (``util.go:101-129``)."""

from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from kubernetes_tpu.api.types import Pod
from kubernetes_tpu.apiserver.store import ClusterStore
from kubernetes_tpu.api.types import Node
from kubernetes_tpu.config.feature_gates import FeatureGates
from kubernetes_tpu.scheduler.scheduler import Scheduler
from kubernetes_tpu.sidecar import attach_batch_scheduler


def _percentile(samples: List[float], q: float) -> float:
    # delegates to the shared jax-free copy (harness/burst.py) — one
    # implementation of the exact-sample percentile across harnesses
    from kubernetes_tpu.harness.burst import sample_percentile

    return sample_percentile(samples, q)


class ThroughputCollector:
    """Samples scheduled-pod count at 1 Hz (util.go throughputCollector).

    ``count_fn`` overrides the counting source — the REST harness counts
    from the scheduler's own commit metric instead of scanning a store
    it doesn't share a process with."""

    def __init__(self, store: Optional[ClusterStore] = None,
                 interval: float = 1.0,
                 count_fn: Optional[Callable[[], int]] = None):
        self.store = store
        self.count_fn = count_fn
        self.interval = interval
        self.samples: List[float] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _count_scheduled(self) -> int:
        if self.count_fn is not None:
            return self.count_fn()
        return sum(1 for p in self.store.list_pods() if p.spec.node_name)

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        last = self._count_scheduled()
        while not self._stop.wait(self.interval):
            now = self._count_scheduled()
            self.samples.append((now - last) / self.interval)
            last = now

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=2.0)

    def summary(self) -> Dict[str, float]:
        # every 1 Hz sample counts, including idle ones (util.go appends
        # unconditionally) — dropping zeros would overstate burst paths
        samples = list(self.samples) or [0.0]
        return {
            "Average": sum(samples) / len(samples),
            "Perc50": _percentile(samples, 0.50),
            "Perc90": _percentile(samples, 0.90),
            "Perc99": _percentile(samples, 0.99),
        }


@dataclass
class BenchmarkResult:
    name: str
    total_pods: int
    measured_pods: int
    duration_seconds: float
    pods_per_second: float
    throughput: Dict[str, float]
    metrics: Dict[str, float] = field(default_factory=dict)
    # devprof per-row summary (compile count, dispatch-vs-block split,
    # pad waste, max-cycle attribution) — bench.py attaches this to the
    # row JSON as the ``telemetry`` sub-object
    telemetry: Dict[str, object] = field(default_factory=dict)
    # freshness SLI summary (watch-delivery p99, max snapshot staleness,
    # SLO verdicts) — bench.py attaches this to the row JSON as the
    # ``freshness`` sub-object
    freshness: Dict[str, object] = field(default_factory=dict)
    # fleet critical-path attribution (per-phase shares of sampled pods'
    # end-to-end latency, unattributed share, max clock skew) — bench.py
    # attaches this to the row JSON as the ``critical_path`` sub-object
    critical_path: Dict[str, object] = field(default_factory=dict)

    def data_items(self) -> dict:
        """DataItems JSON shape (util.go:101-129)."""
        return {
            "version": "v1",
            "dataItems": [
                {
                    "data": self.throughput,
                    "unit": "pods/s",
                    "labels": {"Name": self.name, "Metric": "SchedulingThroughput"},
                },
                {
                    "data": {"Average": self.pods_per_second},
                    "unit": "pods/s",
                    "labels": {"Name": self.name, "Metric": "OverallRate"},
                },
                {
                    "data": self.metrics,
                    "unit": "ms",
                    "labels": {"Name": self.name, "Metric": "SchedulingLatency"},
                },
            ],
        }


def reset_sli_window() -> None:
    """Fresh freshness-SLI + SLO evaluation window per bench row
    (mirrors the tracer clear and the devprof reset): each row's
    ``freshness`` sub-object and SLO verdicts must describe THAT row,
    not the process lifetime. Shared by the store-direct and REST
    harnesses."""
    try:
        from kubernetes_tpu.metrics.freshness_metrics import (
            freshness_metrics,
        )
        from kubernetes_tpu.observability.slo import get_slo_engine

        freshness_metrics().reset_window()
        get_slo_engine().reset(extra_registries=[])
    except Exception:  # noqa: BLE001 — SLIs must never fail a row
        pass


def attach_slo_baseline(sched) -> None:
    """Point the SLO engine at this row's scheduler registry (the e2e
    latency SLI lives there) and take the baseline sample — window
    deltas for cumulative series (the folded APF counters) start from
    here, so a quiet row can never inherit an earlier row's bad
    events."""
    try:
        from kubernetes_tpu.observability.slo import get_slo_engine

        engine = get_slo_engine()
        if engine.enabled:
            engine.add_registry(sched.metrics.registry)
            engine.tick()
    except Exception:  # noqa: BLE001
        pass


def collect_freshness(devprof_summary=None) -> dict:
    """The row's ``freshness`` sub-object: watch-delivery p99, max
    snapshot staleness, and the final SLO verdicts for the window
    opened by ``reset_sli_window``."""
    try:
        from kubernetes_tpu.metrics.freshness_metrics import (
            freshness_row_summary,
        )
        from kubernetes_tpu.observability.slo import get_slo_engine

        engine = get_slo_engine()
        slos = engine.evaluate().get("slos") if engine.enabled else None
        return freshness_row_summary(devprof_summary, slos)
    except Exception:  # noqa: BLE001
        return {}


def collect_critical_path(remote=(), token: str = "", max_pods: int = 25):
    """The row's ``critical_path`` sub-object plus the merged fleet
    trace doc. Always absorbs this process's tracer ring under the
    ``scheduler`` instance; ``remote`` adds (instance, url) apiserver
    children to scrape with skew correction. Returns ``({}, None)``
    when tracing is off or nothing was sampled — attribution must
    never fail a row."""
    try:
        from kubernetes_tpu.observability.fleettrace import (
            collect_fleet_trace,
        )
        from kubernetes_tpu.observability.tracer import get_tracer

        tracer = get_tracer()
        if not tracer.enabled:
            return {}, None
        doc, cp = collect_fleet_trace(
            remote=remote, local=[("scheduler", tracer)],
            token=token, max_pods=max_pods)
        if not cp.get("pods"):
            return {}, None
        row_cp = {k: v for k, v in cp.items() if k != "per_pod"}
        return row_cp, doc
    except Exception:  # noqa: BLE001 — attribution must never fail a row
        return {}, None


def run_workload(
    name: str,
    ops: List[dict],
    use_batch: bool = False,
    max_batch: int = 4096,
    wait_timeout: float = 600.0,
    progress: Optional[Callable[[str], None]] = None,
    backend_factory: Optional[Callable[[], object]] = None,
    result_hook: Optional[Callable[[object, object], None]] = None,
    adaptive_chunk: bool = True,
) -> BenchmarkResult:
    """Execute one workload (scheduler_perf_test.go:309 runWorkload).

    ``backend_factory`` overrides the solver backend (e.g. the
    mesh-sharded planes backend for the multi-chip scaling bench);
    ``result_hook(sched, bs)`` runs after the workload completes, before
    teardown — the scaling bench reads solver-segment histograms there."""
    from kubernetes_tpu.observability import get_tracer
    from kubernetes_tpu.observability.devprof import get_devprof
    from kubernetes_tpu.utils.gctune import tune_for_throughput

    tune_for_throughput()
    # fresh flight-recorder + devprof window per row: the result_hook's
    # diag line and the row's ``telemetry`` sub-object read from rings
    # that must describe THIS workload
    get_tracer().clear()
    get_devprof().reset(workload=name)
    reset_sli_window()
    store = ClusterStore()
    gates = FeatureGates({"TPUBatchScheduler": use_batch})
    # gang scheduling is first-class in this harness (BASELINE config #5):
    # the coscheduling wiring is always on — its queue sort degrades to
    # exactly PrioritySort when no pod declares a gang
    sched = Scheduler.create(store, feature_gates=gates,
                             provider="GangSchedulingProvider")
    bs = attach_batch_scheduler(
        sched, max_batch=max_batch,
        backend=backend_factory() if backend_factory else None,
        adaptive_chunk=adaptive_chunk,
    ) if use_batch else None
    attach_slo_baseline(sched)
    sched.start()

    def pump_until_quiescent(deadline: float, wait_names=None) -> None:
        """Drive scheduling until done. With ``wait_names`` (the
        reference's waitForPodsScheduled: an op waits for ITS pods to be
        scheduled), done = every named pod bound — robust both to pods
        from earlier ops that legitimately pend (Unschedulable's
        impossible pods) and to mid-run victim deletion by preemption
        (victims are other ops' pods). Without names, done = full
        quiescence (queues drained, no bindings in flight). The store
        scan runs at most once per pump iteration, after progress or
        when idle — not in a tight loop against the bind path's lock."""
        def op_done() -> bool:
            bound = sum(
                1 for p in store.list_pods()
                if p.spec.node_name and p.metadata.name in wait_names
            )
            return bound >= len(wait_names)

        while time.monotonic() < deadline:
            sched.queue.flush_backoff_completed()
            if bs is not None:
                progressed = bs.run_batch(pop_timeout=0.01)
            else:
                progressed = sched.schedule_one(pop_timeout=0.01)
            if wait_names is not None and op_done():
                return
            if progressed:
                continue
            if sched.queue.pending_active_count() == 0:
                # async bind failures re-queue; settle them, then re-check
                sched.wait_for_inflight_bindings(timeout=10.0)
                sched.queue.flush_backoff_completed()
                if sched.queue.pending_active_count() == 0 and (
                    wait_names is None or op_done()
                ):
                    return
            time.sleep(0.005)
        raise TimeoutError(
            f"workload {name}: not all pods scheduled before deadline"
        )

    collector: Optional[ThroughputCollector] = None
    measure_start = 0.0
    measured_pods = 0
    created_nodes = 0
    created_pods = 0
    try:
        for op in ops:
            opcode = op["opcode"]
            if opcode == "createNodes":
                for i in range(op["count"]):
                    store.add_node(Node.from_dict(op["nodeTemplate"](created_nodes)))
                    created_nodes += 1
                if progress:
                    progress(f"{name}: {created_nodes} nodes")
            elif opcode == "createPods":
                template = op["podTemplate"]
                offset = op.get("offset", 0)
                collect = op.get("collectMetrics", False)
                if collect and bs is not None:
                    from kubernetes_tpu.ops.encode import is_host_only

                    # compile/cache-load the solver outside the measured
                    # window (JIT warm-up is setup, like the reference's
                    # informer warm-up before scheduler_perf collects).
                    # Warm with a representative SAMPLE of this op's pods:
                    # the compiled shape depends on the deduped constraint/
                    # term/profile space, and workload templates commonly
                    # cycle through modulo-k groups (one pod would warm a
                    # 1-term shape while the real batches carry k terms).
                    samples = [
                        Pod.from_dict(template(offset + i))
                        for i in range(min(200, op["count"]))
                    ]
                    # host-only pods (unbound PVCs, host ports) never
                    # take the batch path — don't compile device shapes
                    # for them (bound-PVC pods DO batch, so the client
                    # must inform the check or their shape stays cold)
                    samples = [
                        p for p in samples if not is_host_only(p, store)
                    ]
                    warm = bs.warmup(sample_pods=samples) if samples else 0.0
                    if progress and warm > 0.05:
                        progress(f"{name}: solver warmup {warm:.1f}s")
                if collect:
                    collector = ThroughputCollector(store)
                    measure_start = time.monotonic()
                    measured_pods = op["count"]
                    collector.start()
                op_names = set()
                new_pods = [
                    Pod.from_dict(template(offset + i))
                    for i in range(op["count"])
                ]
                op_names.update(p.metadata.name for p in new_pods)
                # bulk admission: one store lock + one batched watch
                # delivery (queue.add_many) for the whole op
                store.create_pods(new_pods)
                created_pods += len(new_pods)
                if progress:
                    progress(f"{name}: {created_pods} pods created")
                if not op.get("skipWaitToCompletion", False):
                    # an op waits for ITS pods (scheduler_perf
                    # waitForPodsScheduled), not global quiescence
                    pump_until_quiescent(
                        time.monotonic() + wait_timeout,
                        wait_names=op_names,
                    )
            elif opcode == "setup":
                op["fn"](store)
            elif opcode == "barrier":
                pump_until_quiescent(time.monotonic() + wait_timeout)
            else:
                raise ValueError(f"unknown opcode {opcode!r}")
        sched.wait_for_inflight_bindings(timeout=30.0)
        duration = time.monotonic() - measure_start if measure_start else 0.0
        if result_hook is not None:
            result_hook(sched, bs)
    finally:
        if collector:
            collector.stop()
        sched.stop()

    e2e = sched.metrics.e2e_scheduling_duration
    metrics = {
        "Perc50": e2e.quantile(0.50, "scheduled") * 1000,
        "Perc90": e2e.quantile(0.90, "scheduled") * 1000,
        "Perc99": e2e.quantile(0.99, "scheduled") * 1000,
    }
    dp = get_devprof()
    telemetry = dp.summary() if dp.enabled else {}
    # single-process rows: every span already lives in this tracer, so
    # the fleet merge degenerates to one skew-free "scheduler" track
    critpath, _ = collect_critical_path()
    return BenchmarkResult(
        name=name,
        total_pods=created_pods,
        measured_pods=measured_pods,
        duration_seconds=duration,
        pods_per_second=(measured_pods / duration) if duration > 0 else 0.0,
        throughput=collector.summary() if collector else {},
        metrics=metrics,
        telemetry=telemetry,
        freshness=collect_freshness(telemetry),
        critical_path=critpath,
    )


def write_json(result: BenchmarkResult, path: str) -> None:
    with open(path, "w") as f:
        json.dump(result.data_items(), f, indent=2)
