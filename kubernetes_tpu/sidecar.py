"""The TPU batch scheduler — the north-star component.

Recasts the per-pod serial loop as batched constraint satisfaction on
device (BASELINE.json north_star): drain the pending queue, ship the
snapshot + pod batch to the JAX solver (``kubernetes_tpu.ops``), evaluate
all predicates/scores as dense tensors, commit the returned assignments
through the framework's assume → Reserve → Permit → Bind pipeline so every
host-side contract (cache assume/TTL, volume reservations, gang permits,
events, metrics) is preserved.

The solve loop is a STREAMING PIPELINE (double-buffered, Pathways-style
host/device overlap): per pump cycle the host drains batch N+1 under a
non-blocking queue hint, encodes its delta columns against the live
snapshot, and dispatches its solve — jax dispatch is async, so the
dispatch chains onto batch N's in-flight state carry with no host
sync — then commits batch N−1 while the device crunches, with remote
clients' bulk binds flying on the binding pool (batch N−2 may still be
on the wire). Every correctness guard runs in its original stage:
stale-node probes and ``commit_fits`` at commit time, drift re-encode
via ``mirror_current``/``note_drift``, the mutation-ledger arithmetic
per cycle. ``KTPU_PIPELINE=off`` is the kill-switch: the exact
serialized barrier loop (drain → encode → solve → commit per call),
held bit-identical to the pipeline by the differential guard in
tests/test_pipeline.py. ``devprof`` measures what the overlap wins as
``overlap_share`` (the ``pipeline[...]`` diag segment).

Fallback contract (mirrors how extenders are ``IsIgnorable``,
``core/extender.go:154``; SURVEY.md section 5): any pod the tensor model
can't express — unbound/shared PVC volumes, inline cloud-disk volumes,
host ports, foreign scheduler profiles — and any pod the device marks
unschedulable goes through the UNMODIFIED serial path
(``schedule_pod_serial``), which also supplies preemption. Bound-PVC
pods ride the batch path since round 3 (PV affinity/zone as static
masks, CSI attach limits as resource columns — VERDICT r2 #1). Disabling
the ``TPUBatchScheduler`` feature gate removes the batch path entirely.

Enable with::

    sched = Scheduler.create(store, feature_gates=FeatureGates(
        {"TPUBatchScheduler": True}))
    attach_batch_scheduler(sched)
"""

from __future__ import annotations

import logging
import os
import time
from typing import Dict, List, Optional

_logger = logging.getLogger(__name__)

from kubernetes_tpu.ops.encode import is_host_only
from kubernetes_tpu.ops.session import SolverSession
from kubernetes_tpu.ops.solver import SolverParams
from kubernetes_tpu.scheduler.core import ScheduleResult
from kubernetes_tpu.scheduler.framework.cycle_state import CycleState
from kubernetes_tpu.scheduler.scheduler import Scheduler, commit_target_stale
from kubernetes_tpu.scheduler.types import PodInfo, QueuedPodInfo


class _CommitVolumeBinder:
    """Commit-time PV assignment for batched pods carrying
    node-independent WaitForFirstConsumer claims — the batch path's
    Reserve/PreBind moment (reference ``volume_binding.go`` PreBind →
    BindPodVolumes). Such claims impose no per-node constraint
    (``wfc_class_batchable``), so the solve ignores them and the
    actual PV pops from the class's free pool here, while the store
    lock still serializes against concurrent serial-path binders.
    Lazily snapshots each pool once per commit batch."""

    def __init__(self, client):
        self.client = client
        self._pools: Dict[str, list] = {}
        self.bound = 0

    def _pool(self, sc_name: str) -> list:
        pool = self._pools.get(sc_name)
        if pool is None:
            # node_affinity filter: the drain-time verdict saw an
            # affinity-free pool, but a zonal PV may have become
            # Available since — binding it here would hand a pod a
            # volume its (already chosen) node cannot access
            pool = [
                pv for pv in self.client.list_pvs()
                if pv.phase == "Available" and pv.claim_ref is None
                and pv.storage_class_name == sc_name
                and pv.node_affinity is None
            ]
            # ascending capacity → each claim takes the smallest
            # adequate PV (the reference's smallestPVForClaim ordering)
            def cap_key(pv):
                cap = pv.capacity.get("storage")
                return (cap is None, 0 if cap is None else cap.value())

            pool.sort(key=cap_key)
            self._pools[sc_name] = pool
        return pool

    def finalize(self, pod) -> bool:
        """Bind every still-unbound claim of the pod. False = a pool
        ran dry with no provisioner — the assignment is void and the
        pod must take the serial path for its real status. A partial
        failure unwinds the pod's earlier binds (the serial path's
        Unreserve contract): a pod that ends up pending must not keep
        PVs the next batch needs."""
        done: List[tuple] = []  # (pv name, pvc name) bound for THIS pod
        for v in pod.spec.volumes:
            if not v.persistent_volume_claim:
                continue
            pvc = self.client.get_pvc(pod.namespace,
                                      v.persistent_volume_claim)
            if pvc is None or pvc.volume_name:
                continue      # bound claims were expressible statically
            sc_name = pvc.storage_class_name or ""
            pool = self._pool(sc_name)
            request = pvc.requests.get("storage")
            chosen = None
            for i, pv in enumerate(pool):
                if pvc.access_modes and not \
                        set(pvc.access_modes) <= set(pv.access_modes):
                    continue
                cap = pv.capacity.get("storage")
                if request is not None and (cap is None or cap < request):
                    continue
                chosen = i
                break
            if chosen is not None:
                pv = pool.pop(chosen)
                if not self.client.bind_pv(pv.name, pod.namespace,
                                           pvc.name):
                    self._rollback(pod, done)   # raced away mid-commit
                    return False
                done.append((pv.name, pvc.name))
                self.bound += 1
                continue
            sc = self.client.get_storage_class(sc_name) if sc_name \
                else None
            if sc is None or not sc.provisioner:
                self._rollback(pod, done)
                return False
            # dynamic provisioning satisfies the claim on any node
        return True

    def _rollback(self, pod, done: List[tuple]) -> None:
        for pv_name, pvc_name in done:
            try:
                self.client.unbind_pv(pv_name, pod.namespace, pvc_name)
            except Exception:  # noqa: BLE001 — unwind must not mask
                _logger.exception("PV bind rollback failed: %s", pv_name)
        self.bound -= len(done)


class TPUBatchScheduler:
    # up to this many device-declined pods per batch take the serial
    # path (exact statuses/messages); above it, mass-decline fast path
    DECLINED_SERIAL_LIMIT = 32
    # p99 schedule-latency budget: a pod's latency is roughly one batch
    # cycle (solve + commit), so the drain/pad size adapts to keep each
    # cycle under this (BASELINE.json's p99 target is 2s; budgeting
    # below it leaves headroom for cycle-to-cycle variance)
    LATENCY_BUDGET_S = 1.5
    MIN_CHUNK = 512

    def __init__(
        self,
        scheduler: Scheduler,
        max_batch: int = 4096,
        params: SolverParams = SolverParams(),
        validate: bool = False,
        backend=None,
        adaptive_chunk: bool = True,
        pipeline: Optional[bool] = None,
    ):
        self.sched = scheduler
        self.max_batch = max_batch
        # streaming pipeline kill-switch: ``KTPU_PIPELINE=off`` (or
        # pipeline=False) runs the serialized barrier loop — drain →
        # encode → solve (eager) → commit in ONE call, nothing carried
        # across cycles. The differential guard
        # (tests/test_pipeline.py) asserts a bit-identical bound set
        # between the two arms over identical seeded event sequences.
        if pipeline is None:
            pipeline = os.environ.get(
                "KTPU_PIPELINE", "").lower() not in ("off", "0", "false")
        self.pipeline_enabled = bool(pipeline)
        # max batches simultaneously in flight across the stages
        # (solve N dispatched + commit N−1 pending + N−2's bulk binds
        # on the binding pool) — the ``pipeline[depth=...]`` diag
        self.pipeline_depth_max = 0
        # False pins the drain/pad size at max_batch (no latency-budget
        # tuning): the multi-chip scaling bench needs every mesh size to
        # solve the IDENTICAL batch partition, or slower configurations
        # shrink their chunks and the comparison measures the tuner, not
        # the sharding
        self.adaptive_chunk = adaptive_chunk
        self.params = params
        # differential-debug mode: re-check every device assignment with
        # the host filter chain before committing
        self.validate = validate
        # device-resident state mirror, carried across batches.
        # ``backend`` overrides the platform default (e.g. the
        # multi-chip ShardedBackend over a device mesh).
        self.session = SolverSession(scheduler, params=params,
                                     max_batch=max_batch, backend=backend)
        # one solved-but-uncommitted batch (pipelining: the host commits
        # batch k while the device solves batch k+1)
        self._pending: Optional[dict] = None
        # latency-budget chunking: drain/pad size, halved (power-of-2
        # buckets — each bucket is one compiled executable) whenever a
        # batch cycle overruns the budget. Wide-term workloads that
        # solve slowly get small low-latency batches; fast ones keep
        # the full pipeline width.
        self._chunk = max_batch
        # pad sizes whose executables are known-compiled. A tuner shrink
        # to an UNWARMED bucket must never compile inside a measured
        # cycle: one slow batch would halve the chunk,
        # the new shape's compile would make the NEXT batch slow too,
        # and the cascade lands thousands of pods in 20-50s e2e buckets
        # (VERDICT r4 weak #1, the driver run-1 collapse). Shrinks to
        # unwarmed buckets are pre-warmed with synthetic solves between
        # cycles instead — and the convention is no longer trusted on
        # faith: devprof's compile listener counts any compile that
        # still lands inside a measured cycle
        # (solver_unexpected_compiles_total + flight-recorder dump).
        self._warmed_pads: set = set()
        self._need_warm_pad: Optional[int] = None
        self._warm_samples: List = []
        # XLA compile events MEASURED inside pre-warm solves (devprof
        # listener; legacy builds fall back to one-per-warm) — not the
        # old "assume every warm call compiled" bookkeeping
        self.pad_warms = 0
        self.max_cycle_s = 0.0
        # pods committed from device assignments, and pods a failed
        # solve or commit sent to the serial path (``_rescue``; 0 on a
        # healthy device)
        self.batch_committed_pods = 0
        self.serial_fallback_pods = 0
        # cache mutations the CURRENT cycle's commits performed
        # (accumulated from commit_assignments_bulk's ledger): the
        # session's validity arithmetic must count every sanctioned
        # mutation — assumes of gang pods parked at Permit included —
        # not just committed pods, or every gang batch reads as drift
        # and rebuilds the session (VERDICT r5 weak #4: state_only
        # rebuild per batch, encode at 6.8x the headline's cost)
        self._cycle_mutations = 0

    # ------------------------------------------------------------------
    def _drain(self, pop_timeout: Optional[float]):
        """Pop up to max_batch pods (bulk, one lock). Each pod's
        scheduling cycle is captured AT POP TIME (serial semantics: the
        moveRequestCycle race rule compares against the cycle the pod was
        popped in, scheduling_queue.go:317) — pop_batch consumes one
        cycle per pod, so cycles are reconstructed from the final value."""
        items, first_cycle = self.sched.queue.pop_batch(
            self._chunk, timeout=pop_timeout
        )
        return [(qpi, first_cycle + i) for i, qpi in enumerate(items)]

    def _tune_chunk(self, padded_pods: int, cycle_seconds: float) -> None:
        """Latency-budget chunk sizing, called after each committed
        batch: per-pod cost × chunk must stay under the p99 budget.
        Cost is divided by the PADDED batch size — device latency scales
        with the compiled scan length, so a sparsely-filled drain must
        not read as slow and collapse the chunk. Movement is one
        power-of-2 bucket per batch in either direction: each bucket is
        its own compiled executable, and a single outlier cycle (e.g.
        one absorbing a compile) must not trigger a cascade of unwarmed
        shapes mid-run."""
        if not self.adaptive_chunk:
            return
        if padded_pods <= 0 or cycle_seconds <= 0:
            return
        per_pod = cycle_seconds / padded_pods
        target = int(0.7 * self.LATENCY_BUDGET_S / max(per_pod, 1e-9))
        if target < self._chunk and self._chunk > self.MIN_CHUNK:
            new = self._chunk // 2
        elif target >= 2 * self._chunk and self._chunk < self.max_batch:
            new = self._chunk * 2
        else:
            return
        # MIN_CHUNK floors the bucket — but never above max_batch
        # (tests and small deployments run with tiny max_batch)
        self._chunk = min(self.max_batch, max(self.MIN_CHUNK, new))
        if self._chunk not in self._warmed_pads:
            # compile between cycles, not inside a measured one
            self._need_warm_pad = self._chunk

    def run_batch(self, pop_timeout: Optional[float] = 0.2) -> int:
        """One pump cycle. Default (``KTPU_PIPELINE`` unset): the
        STREAMING pipeline — drain batch N+1 under a non-blocking
        hint, encode its delta columns and dispatch its solve (jax
        dispatch is async, chaining onto batch N's in-flight state
        carry), then commit batch N−1 while the device crunches, its
        bulk binds flying on the binding pool for remote clients. A
        solved batch is held at most one cycle and commits immediately
        when the queue is empty, so single-shot callers see their pods
        bound in the same call. With ``KTPU_PIPELINE=off``: the
        serialized barrier loop (one batch per call, solve blocks,
        commit follows — the differential guard's reference arm).
        Returns the number of pods worked on this cycle."""
        if not self.pipeline_enabled:
            return self._run_batch_serialized(pop_timeout)
        return self._run_batch_pipelined(pop_timeout)

    # -- shared stages --------------------------------------------------
    def _degraded_pause(self, pop_timeout: Optional[float]) -> None:
        # circuit open: the batch path pauses exactly like the
        # serial loop — solved-but-uncommitted work stays pending
        # and commits on the first cycle after recovery. Always
        # sleep: flush() drives this with pop_timeout=0.0 in a
        # while-_pending loop, which must not become a busy spin.
        time.sleep(min(pop_timeout, 0.05) if pop_timeout else 0.01)

    def _service_warm_pad(self) -> None:
        if self._need_warm_pad is None:
            return
        # session.warm_pad discards its outputs, so the resident
        # state — and any pipelined batch's lazy handle — survive;
        # this runs on the very next cycle after a shrink, even
        # under sustained load where something is always in flight
        pad = self._need_warm_pad
        self._need_warm_pad = None
        if pad not in self._warmed_pads and self._warm_samples:
            warmed = self.session.warm_pad(self._warm_samples, pad)
            if warmed is not None:
                # the bucket is live either way; pad_warms counts
                # the compiles devprof MEASURED (0 = executable was
                # already cached and the warm cost ~nothing)
                self._warmed_pads.add(pad)
                self.pad_warms += warmed

    def _partition(self, qpis: List[tuple]):
        """Batchable vs serial-fallback split (one wfc-class scan per
        drain, not one per pod) — identical in both pipeline arms."""
        sched = self.sched
        batchable: List[tuple] = []
        serial: List[QueuedPodInfo] = []
        host_only_cache: dict = {}
        for qpi, cycle in qpis:
            pod = qpi.pod
            fwk = sched.profiles.get(pod.spec.scheduler_name)
            if fwk is None:
                continue
            if sched.skip_pod_schedule(fwk, pod):
                continue
            if fwk.profile_name != "default-scheduler" or \
                    self._needs_serial(pod, host_only_cache):
                serial.append(qpi)
            else:
                batchable.append((qpi, cycle))
        return batchable, serial

    def _select_pad(self, n_batch: int) -> int:
        """Right-size the pad: a partial drain (creator still
        streaming, queue trickle) pays the device scan of its
        SMALLEST already-compiled pow-2 bucket, not the full
        chunk — device latency scales with the padded size, and
        only warmed buckets are eligible so this never compiles
        inside a measured cycle."""
        pad = self._chunk
        for b in sorted(self._warmed_pads):
            if n_batch <= b < pad:
                return b
        return pad

    # -- the pipelined loop ---------------------------------------------
    def _trace_cycle(self, start: float, processed: int,
                     committed: int) -> None:
        """Batch-level ``queue.cycle`` span covering one drain → solve →
        commit pass. Carries no pod trace, so critical-path attribution
        overlays it at the LOWEST priority: it soaks up the per-pod
        assembly gaps no specific span covers (pop → solve dispatch,
        solve handle → pending stamp, guard re-probes between commit
        chunks) without ever masking encode/solve/commit/bind time.
        Idle passes (nothing drained, nothing committed) stay silent."""
        self.batch_committed_pods += committed
        if processed == 0 and committed == 0:
            return
        try:
            from kubernetes_tpu.observability import get_tracer

            tracer = get_tracer()
            if tracer.enabled:
                tracer.record("queue.cycle", start, time.monotonic(),
                              pods=processed, committed=committed)
        except Exception:   # noqa: BLE001 — tracing must not break cycles
            pass

    def _run_batch_pipelined(self, pop_timeout: Optional[float]) -> int:
        sched = self.sched
        if sched.is_degraded():
            self._degraded_pause(pop_timeout)
            return 0
        t_cycle = time.monotonic()
        prev = self._pending
        self._pending = None
        self._service_warm_pad()

        # a pending batch solved against a mirror that has since
        # diverged (external events, failed commits) is suspect: its
        # assignments are discarded and its pods RE-SOLVED this cycle
        # (the solve below rebuilds from a fresh snapshot), keeping
        # them on the batch path instead of serializing up to
        # max_batch pods. Carried-over pods go back through the SAME
        # partition as freshly drained ones, against the live store
        # object — the divergence that discarded the batch may be the
        # pod itself being deleted or updated (e.g. gaining a PVC)
        # while its batch was in flight.
        if prev is not None and not self.session.mirror_current():
            qpis = []
            for qpi, cycle in prev["batchable"]:
                pod = qpi.pod
                live = sched.client.get_pod(pod.namespace, pod.name)
                if live is None or live.uid != pod.uid:
                    continue  # deleted (and maybe recreated) in flight
                if live is not pod:
                    qpi.pod_info = PodInfo.of(live)
                qpis.append((qpi, cycle))
            prev = None
        else:
            # drain stage, hint-gated: the non-blocking peek decides
            # whether a drain is worth attempting at all — with batch
            # N−1's commit pending and nothing queued, skip the pop
            # (and its condition wait) entirely so stage overlap never
            # parks on an empty queue; with work queued, drain without
            # waiting. Only a fully idle pipeline blocks for
            # pop_timeout (the pump loops' idle-wait contract).
            hint_n, _hint_prio = sched.queue.pending_hint()
            if prev is not None and hint_n == 0:
                qpis = []
            else:
                qpis = self._drain(
                    0.0 if (prev is not None or hint_n) else pop_timeout)
        processed = len(qpis)

        batchable, serial = self._partition(qpis)

        committed = 0
        self._cycle_mutations = 0
        seq_anchor = sched.cache.mutation_seq
        if batchable:
            # pad sized from the PARTITIONED batchable count — the raw
            # hint overstates it whenever serial-fallback pods rode the
            # drain, and an overstated bucket is a larger device scan
            pad = self._select_pad(len(batchable))
            # correlate this batch's solver phase spans with its pods'
            # scheduling cycles (the flight recorder's cycle id)
            self.session.trace_cycle = batchable[0][1]
            try:
                res = self.session.solve(
                    [q.pod for q, _ in batchable], lazy=True,
                    incremental_only=prev is not None,
                    pad_to=pad,
                )
                if res is None:
                    # this solve needs a full rebuild, whose snapshot
                    # must include the in-flight batch: commit it first
                    # and settle the mutation accounting BEFORE the
                    # rebuild re-anchors the mirror (no overlap this
                    # cycle — rebuilds are rare)
                    committed += self._commit_pending_safe(prev, serial)
                    self.session.note_committed(self._cycle_mutations,
                                                seq_anchor)
                    self._cycle_mutations = 0
                    processed += len(prev["batchable"])
                    prev = None
                    seq_anchor = sched.cache.mutation_seq
                    res = self.session.solve(
                        [q.pod for q, _ in batchable], lazy=True,
                        pad_to=pad,
                    )
                handle, cluster, _ = res
                # this pad's executable is live now, and these pods are
                # shape-representative for future pre-warms
                self._warmed_pads.add(pad)
                self._warm_samples = [q.pod for q, _ in batchable[:8]]
                self._pending = {
                    "batchable": batchable,
                    "handle": handle,
                    "materializer": self.session.last_materializer,
                    "cluster": cluster,
                    "profiles": self.session.last_profile_idx,
                    "inexpressible": self.session.last_inexpressible,
                    # static masks for THIS batch's profiles — the
                    # session's live fields may describe a newer batch
                    # by the time this one commits
                    "masks": self.session.static_masks_host,
                    "start": time.monotonic(),
                    "pad": pad,
                }
                # pipeline depth at this instant: solve N in flight,
                # batch N−1 solved-but-uncommitted, batch N−2's bulk
                # binds still on the binding pool
                depth = 1 + (1 if prev is not None else 0) + (
                    1 if getattr(sched, "_inflight_bindings", 0) else 0)
                if depth > self.pipeline_depth_max:
                    self.pipeline_depth_max = depth
            except Exception:  # noqa: BLE001 — popped pods must not be lost
                self._rescue("solve", batchable, serial)

        # commit the previous cycle's batch while the device solves
        # (every guard — stale-node probes, commit_fits, drift
        # re-encode — runs inside _commit_pending, stage-unchanged)
        if prev is not None:
            committed += self._commit_pending_safe(prev, serial)
            processed += len(prev["batchable"])

        # nothing else queued: no overlap to win — commit the fresh
        # solve in the same call (also the single-shot caller contract)
        if self._pending is not None and sched.queue.num_active() == 0:
            pending = self._pending
            self._pending = None
            committed += self._commit_pending_safe(pending, serial)

        self._run_serial(serial)
        # session validity: every cache mutation since the anchor must
        # be one this cycle's commits performed (assumes — including
        # gang pods parked at Permit — plus sync rejection forgets,
        # commit_assignments_bulk's ledger). Serial binds, async-bind
        # failures, or external events show up as extra mutations; with
        # the device mirror attached they land in the delta journal and
        # the next solve scatters them into the resident planes, without
        # it they invalidate the session for a full rebuild.
        self.session.note_committed(self._cycle_mutations, seq_anchor)
        self._trace_cycle(t_cycle, processed, committed)
        return processed

    # -- the serialized (kill-switch) loop ------------------------------
    def _run_batch_serialized(self, pop_timeout: Optional[float]) -> int:
        """The ``KTPU_PIPELINE=off`` barrier loop: drain → encode →
        solve (eager — the materializer blocks inside the solve) →
        commit, one batch per call, nothing carried across cycles.
        Every guard runs exactly as in the pipelined arm (same
        ``_commit_pending``); only the overlap is gone. This is the
        differential guard's reference arm and the operational
        kill-switch if the pipeline ever misbehaves in production."""
        sched = self.sched
        if sched.is_degraded():
            self._degraded_pause(pop_timeout)
            return 0
        t_cycle = time.monotonic()
        self._service_warm_pad()
        qpis = self._drain(pop_timeout)
        processed = len(qpis)
        batchable, serial = self._partition(qpis)
        committed = 0
        self._cycle_mutations = 0
        seq_anchor = sched.cache.mutation_seq
        if batchable:
            pad = self._select_pad(len(batchable))
            self.session.trace_cycle = batchable[0][1]
            start = time.monotonic()
            try:
                res = self.session.solve(
                    [q.pod for q, _ in batchable], lazy=False,
                    pad_to=pad,
                )
                handle, cluster, _ = res
                self._warmed_pads.add(pad)
                self._warm_samples = [q.pod for q, _ in batchable[:8]]
                committed += self._commit_pending_safe({
                    "batchable": batchable,
                    "handle": handle,
                    "materializer": None,   # already materialized
                    "cluster": cluster,
                    "profiles": self.session.last_profile_idx,
                    "inexpressible": self.session.last_inexpressible,
                    "masks": self.session.static_masks_host,
                    "start": start,
                    "pad": pad,
                }, serial)
            except Exception:  # noqa: BLE001 — popped pods must not be lost
                self._rescue("solve", batchable, serial)
        self._run_serial(serial)
        self.session.note_committed(self._cycle_mutations, seq_anchor)
        self._trace_cycle(t_cycle, processed, committed)
        return processed

    def pipeline_info(self, telemetry: Optional[Dict] = None
                      ) -> Optional[Dict]:
        """The ``pipeline[...]`` diag segment's payload: max observed
        stage depth plus (when a devprof summary is supplied) the
        overlap share and how many cycles actually overlapped. None
        when the pipeline is off OR never dispatched a batch (a
        serial-only or empty row) — those rows print nothing, the
        quiet-row convention the other diag segments follow."""
        if not self.pipeline_enabled or self.pipeline_depth_max == 0:
            return None
        info: Dict = {"depth": self.pipeline_depth_max}
        if telemetry:
            info["overlap"] = float(telemetry.get("overlap_share", 0.0))
            info["cycles"] = int(telemetry.get("overlapped_cycles", 0))
        return info

    def mirror_info(self, telemetry: Optional[Dict] = None
                    ) -> Optional[Dict]:
        """The ``mirror[...]`` diag segment's payload: delta-journal
        events scattered into the device-resident planes, the bytes
        those index/value triples cost on the link, how often the
        mirror had to fall back to a full reseed, and (when a devprof
        summary is supplied) the surviving encode share. None when the
        mirror is off (``KTPU_MIRROR=off`` or a backend without scatter
        hooks) — quiet-row convention, same as ``pipeline_info``."""
        mirror = getattr(self.session, "_mirror", None)
        if mirror is None:
            return None
        info = mirror.info()
        if telemetry and "encode_share" in telemetry:
            info["encode_share"] = float(telemetry["encode_share"])
        return info

    def flush(self, timeout: float = 60.0) -> int:
        """Commit any held solved-but-uncommitted batch (the pipelining
        tail): a run that stops pumping mid-stream must not strand popped
        pods in ``_pending``. Returns the number of pods processed.
        Bounded by ``timeout``: in degraded mode the commit is paused,
        and a shutdown-path flush must not wait forever on a server
        that may never come back."""
        total = 0
        deadline = time.monotonic() + timeout
        while self._pending is not None and time.monotonic() < deadline:
            total += self.run_batch(pop_timeout=0.0)
        return total

    def _run_serial(self, serial: List[QueuedPodInfo]) -> None:
        sched = self.sched
        seen = set()
        for qpi in serial:
            if qpi.pod.full_name() in seen:
                continue  # appended both pre- and post-solve-failure
            seen.add(qpi.pod.full_name())
            fwk = sched.profiles[qpi.pod.spec.scheduler_name]
            # a partial batch commit may already have assumed some of these
            if sched.skip_pod_schedule(fwk, qpi.pod):
                continue
            sched.schedule_pod_serial(fwk, qpi)

    def warmup(self, sample_pods: Optional[List] = None) -> float:
        """Compile (or cache-load) the solver for this cluster's shapes by
        solving a representative batch. Returns seconds spent. Call after
        nodes exist and before the measured phase — the analog of the
        reference excluding informer warm-up from scheduler_perf's
        measured window.

        The compiled XLA signature depends on the batch's constraint and
        resource dims (spread constraints, affinity terms, topology value
        space, extended resources), so pass ``sample_pods`` drawn from the
        actual workload (e.g. one pod per template); constraints are
        deduped during encoding, so one representative pod per template
        yields the same shapes as the full batch. Without samples, only
        the constraint-free shape is warmed."""
        t0 = time.monotonic()
        sched = self.sched
        try:
            sched.algorithm.update_snapshot()
            if not sched.algorithm.snapshot.list():
                return 0.0
            pods = list(sample_pods) if sample_pods else []
            if not pods:
                from kubernetes_tpu.api.resource import parse_quantity
                from kubernetes_tpu.api.types import (
                    Container, ObjectMeta, Pod, PodSpec, ResourceRequirements,
                )

                pods = [Pod(
                    metadata=ObjectMeta(name="__warmup__", namespace="default"),
                    spec=PodSpec(containers=[Container(
                        name="c",
                        resources=ResourceRequirements(
                            requests={"cpu": parse_quantity("1m")}),
                    )]),
                )]
            # drive the session itself so the ACTIVE backend (pallas
            # kernel or xla scan) compiles for the exact steady-state
            # shapes; then invalidate — warmup pods were solved into the
            # device mirror but never committed on the host
            self.session.solve(pods, warming=True)
            # timed second solve (now cache-hot) estimates the per-pod
            # device rate so the latency-budget chunk is chosen — and
            # its executable compiled — BEFORE the measured phase
            t1 = time.monotonic()
            self.session.solve(pods, warming=True)
            est = time.monotonic() - t1
            # cost scales with the padded size; step until the bucket is
            # stable (runtime tuning moves one bucket per batch, but
            # warmup is free to settle immediately)
            per_pod = est / self.max_batch
            self._warmed_pads.add(self.max_batch)
            self._warm_samples = list(pods)
            prev = None
            while prev != self._chunk:
                prev = self._chunk
                self._tune_chunk(self._chunk, per_pod * self._chunk)
            self._need_warm_pad = None   # warmed HERE, not mid-run
            if self._chunk != self.max_batch:
                self.session.solve(pods, warming=True, pad_to=self._chunk)
                self._warmed_pads.add(self._chunk)
            # one shrink bucket below the settled chunk compiles for
            # free inside the un-measured warmup window, so the tuner's
            # FIRST mid-run shrink never waits on a compile at all
            half = max(self.MIN_CHUNK, self._chunk // 2)
            if half < self._chunk:
                self.session.solve(pods, warming=True, pad_to=half)
                self._warmed_pads.add(half)
            self.session.invalidate()
        except Exception:
            _logger.exception("solver warmup failed (continuing cold)")
        return time.monotonic() - t0

    def mesh_info(self) -> Optional[Dict]:
        """Sharded-solve topology of the session's ACTIVE backend, or
        None off the mesh tier: mesh width, node-axis shard count, and
        whether the solve donates its state buffers. Feeds the bench
        ``diag:`` line's ``mesh[...]`` segment (harness/diagfmt.py) and
        the devscale row's per-arm provenance."""
        be = self.session._active
        mesh = getattr(be, "mesh", None)
        if mesh is None:
            return None
        try:
            shards = int(dict(mesh.shape).get("nodes", mesh.size))
        except Exception:  # noqa: BLE001 — diagnostics only
            shards = int(getattr(mesh, "size", 1))
        return {
            "devices": int(mesh.size),
            "shards": shards,
            "donated": bool(getattr(be, "donate", False)),
        }

    def _needs_serial(self, pod, cache=None) -> bool:
        if is_host_only(pod, self.sched.client, cache):
            return True
        return any(
            ext.is_interested(pod) for ext in self.sched.algorithm.extenders
        )

    def _commit_pending_safe(self, pending: dict,
                             serial: List[QueuedPodInfo]) -> int:
        """_commit_pending, but a failure (e.g. an async device error
        surfacing at materialization) must not lose popped pods: they
        fall back to the serial path (already-assumed ones are skipped
        there by skip_pod_schedule)."""
        try:
            return self._commit_pending(pending, serial)
        except Exception:  # noqa: BLE001
            self._rescue("commit", pending["batchable"], serial)
            return 0

    def _rescue(self, stage: str, batchable: List[tuple],
                serial: List[QueuedPodInfo]) -> None:
        """The pod-safety net: a batch whose solve or commit raised goes
        to the serial path so no popped pod is lost. Every rescued pod
        is counted in ``serial_fallback_pods`` and the failure is logged
        at error level — a device fault is never a quiet slow path."""
        self.serial_fallback_pods += len(batchable)
        _logger.exception(
            "batch %s failed; %d pods fall back to the serial path",
            stage, len(batchable),
        )
        self.session.invalidate()
        serial.extend(q for q, _ in batchable)

    # ------------------------------------------------------------------
    def _commit_pending(self, pending: dict,
                        serial: List[QueuedPodInfo]) -> int:
        """Materialize and commit one solved batch. Returns the number
        of successfully committed pods; declined/rejected pods are
        appended to ``serial`` or failed directly (mass decline)."""
        sched = self.sched
        fwk = sched.profiles["default-scheduler"]
        batchable = pending["batchable"]
        cluster = pending["cluster"]
        start = pending["start"]
        mat = pending["materializer"] or (lambda h: h)
        assignments = mat(pending["handle"])

        t0 = time.monotonic()
        committed = 0
        declined: List[tuple] = []  # (batch index, qpi, cycle)
        commits: List[tuple] = []   # (qpi, result, cycle, start)
        vol_binder = _CommitVolumeBinder(sched.client)
        # stale-node guard (chaos_nodes): ONE cache probe for every
        # distinct target in this batch. The solve ran against an
        # encoding that may predate node churn; assignments whose node
        # has since died / been cordoned / gone unreachable route to
        # the serial path for a fresh verdict, and the session is told
        # the node planes drifted so the next solve re-encodes instead
        # of spinning mass declines against ghost columns.
        stale_flags = sched.cache.commit_target_flags(
            {cluster.node_names[int(a)] for a in assignments if a >= 0}
        )
        # multi-replica capacity guard (replicas sharing all nodes):
        # ONE cumulative cache probe for the whole batch — targets
        # whose remaining capacity a sibling replica consumed since
        # this solve route to the serial path, which re-places them
        # against the post-conflict cache instead of burning a backoff
        # round on a bind the guard would refuse anyway.
        cap_verdicts = None
        if sched.commit_capacity_guard:
            cap_verdicts = sched.cache.commit_fits([
                (qpi.pod,
                 cluster.node_names[int(a)] if a >= 0 else "")
                for (qpi, _), a in zip(batchable, assignments)
            ])
        stale_routed = 0
        capacity_routed = 0
        for bi, ((qpi, cycle), assignment) in enumerate(
            zip(batchable, assignments)
        ):
            if assignment < 0:
                declined.append((bi, qpi, cycle))
                continue
            node_name = cluster.node_names[assignment]
            flag = stale_flags.get(node_name, False)
            if flag is not False and \
                    commit_target_stale(qpi.pod, flag) is not None:
                stale_routed += 1
                serial.append(qpi)
                continue
            if cap_verdicts is not None and cap_verdicts[bi] is not None:
                capacity_routed += 1
                serial.append(qpi)
                continue
            if self.validate and not self._host_validates(fwk, qpi, node_name):
                # the device state counts this pod but the host refused it
                self.session.invalidate()
                serial.append(qpi)
                continue
            if not vol_binder.finalize(qpi.pod):
                # batched WFC claim whose pool ran dry with no
                # provisioner: the device's assignment is void — the
                # serial path will produce the proper unschedulable
                # status (and the mirror no longer matches)
                self.session.invalidate()
                serial.append(qpi)
                continue
            result = ScheduleResult(
                suggested_host=node_name,
                evaluated_nodes=cluster.num_real_nodes,
                feasible_nodes=1,
            )
            commits.append((qpi, result, cycle, start))
        if stale_routed:
            from kubernetes_tpu.metrics.fabric_metrics import fabric_metrics

            fabric_metrics().stale_binds_rejected_total.inc(
                "batch", amount=stale_routed)
            # the device counted these pods onto nodes that are gone:
            # static planes drifted, force a full re-encode
            self.session.note_drift()
        if capacity_routed:
            from kubernetes_tpu.metrics.fabric_metrics import fabric_metrics

            fabric_metrics().stale_binds_rejected_total.inc(
                "capacity", amount=capacity_routed)
            # sibling commits drifted the state planes (not the node
            # set): the mirror no longer matches the cluster
            self.session.invalidate()
        if commits:
            committed, failed = sched.commit_assignments_bulk(fwk, commits)
            self._cycle_mutations += sched.last_bulk_commit_mutations
            if failed:
                # committed on device, rejected on host: mirrors diverged
                self.session.invalidate()
        # Declined pods: with a FEW, re-run the serial path for its exact
        # per-plugin statuses and event messages. Under MASS decline
        # (e.g. thousands of impossible pods) the serial re-run costs
        # ~O(nodes) per pod for information the device already computed,
        # so fail directly with statuses synthesized from the static
        # masks — preemption still runs via PostFilter and correctly
        # prunes static-infeasible nodes.
        if len(declined) <= self.DECLINED_SERIAL_LIMIT:
            serial.extend(qpi for _, qpi, _ in declined)
        else:
            # statuses depend only on the pod's static profile: share one
            # (read-only) map per profile instead of building a
            # nodes-sized dict per declined pod
            statuses_by_profile: dict = {}
            inexpressible = pending["inexpressible"]
            # ONE vectorized preemption screen for the whole declined
            # set: each pod gets ranked candidate hints so its PostFilter
            # dry-runs a handful of nodes instead of the sampled ~10%
            # (per-pod dry-run over hundreds of candidates is what
            # collapses mass-preemption throughput)
            screen = None
            planner = None
            screen_masks: dict = {}
            if fwk.has_post_filter_plugins() and any(
                q.pod.priority() > 0 for _, q, _ in declined
            ):
                from kubernetes_tpu.scheduler.preemption_screen import (
                    build_screen,
                    build_victim_planner,
                )

                sched.algorithm.update_snapshot()
                try:
                    screen = build_screen(sched.algorithm.snapshot)
                    planner = build_victim_planner(
                        sched.algorithm.snapshot,
                        pdbs=sched.client.list_pdbs(),
                    )
                except Exception:  # noqa: BLE001 — hints are advisory
                    _logger.exception("preemption screen build failed")

            def screen_mask(bi: int):
                """This batch's static mask for pod ``bi``, re-ordered to
                the screen's node order (encoder vs snapshot order can
                differ); cached per profile."""
                profiles, masks = pending["profiles"], pending["masks"]
                if profiles is None or masks is None or \
                        bi >= len(profiles):
                    return None
                ui = int(profiles[bi])
                if ui in screen_masks:
                    return screen_masks[ui]
                if ui >= len(masks):
                    screen_masks[ui] = None
                    return None
                by_name = dict(zip(cluster.node_names, masks[ui]))
                import numpy as _np

                aligned = _np.array(
                    [bool(by_name.get(nm, False))
                     for nm in screen.node_names], dtype=bool,
                )
                screen_masks[ui] = aligned
                return aligned
            # batch preemption planning (VERDICT r2 #3): group the
            # declined preemptors by shape — mass declines are runs of
            # identical (priority, requests, static profile) pods — and
            # let the planner propose ONE (node, minimal victim set)
            # per pod from its per-(node, priority) sorted prefix sums
            # with live capacity accounting. Planned pods skip the
            # per-pod PostFilter dry-run entirely; the real filter
            # chain still validates every plan post-eviction.
            from kubernetes_tpu.scheduler.framework.plugins.default_preemption import (  # noqa: E501
                pod_eligible_to_preempt_others,
            )
            from kubernetes_tpu.scheduler.types import (
                compute_pod_resource_request,
            )

            groups: dict = {}   # shape key -> [(bi, qpi, cycle)]
            rest: List[tuple] = []
            for bi, qpi, cycle in declined:
                # an inexpressible pod's -1 is NOT a device verdict (the
                # tensor model simply can't express it) — it keeps the
                # documented serial-fallback contract even here
                if inexpressible is not None and bi < len(inexpressible) \
                        and inexpressible[bi]:
                    serial.append(qpi)
                    continue
                if planner is not None and qpi.pod.priority() > 0 and \
                        pod_eligible_to_preempt_others(
                            qpi.pod, sched.algorithm.snapshot):
                    req = compute_pod_resource_request(qpi.pod)
                    profiles = pending["profiles"]
                    ui = int(profiles[bi]) if profiles is not None and \
                        bi < len(profiles) else -1
                    key = (qpi.pod.priority(), req.milli_cpu,
                           req.memory, ui)
                    groups.setdefault(key, []).append((bi, qpi, cycle))
                else:
                    rest.append((bi, qpi, cycle))
            plans: List[tuple] = []  # (qpi, cycle, node_name, victims)
            for key, members in groups.items():
                got = []
                try:
                    got = planner.plan_group(
                        members[0][1].pod, len(members),
                        static_mask=screen_mask(members[0][0]),
                    )
                except Exception:  # noqa: BLE001 — advisory
                    _logger.exception("victim planning failed")
                for (bi, qpi, cycle), (node_name, victims) in zip(
                        members, got):
                    plans.append((qpi, cycle, node_name, victims))
                rest.extend(members[len(got):])
            # mass decline writes one PodScheduled=False condition per
            # pod: coalesce the whole sweep into one bulk /statuses
            # request (rate-equivalent — the bulk verb charges per
            # item) instead of thousands of serialized PUT round trips
            with sched.client.batched_status_writes():
                for bi, qpi, cycle in rest:
                    hints = None
                    if screen is not None and qpi.pod.priority() > 0:
                        # rotate by position in the declined set:
                        # uniform batches spread over distinct
                        # candidate nodes
                        hints = screen.candidates_for(
                            qpi.pod, static_mask=screen_mask(bi),
                            rotation=bi,
                        )
                    if not self._fail_declined(fwk, qpi, cycle, cluster,
                                               bi, pending["profiles"],
                                               pending["masks"],
                                               statuses_by_profile,
                                               candidate_hints=hints):
                        serial.append(qpi)
            if plans:
                committed += self._execute_preemption_plans(
                    fwk, plans, pending["start"], serial
                )
        now = time.monotonic()
        sched.metrics.batch_solve_duration.observe(now - t0, "commit")
        try:
            from kubernetes_tpu.observability import get_tracer

            tracer = get_tracer()
            if tracer.enabled:
                tracer.record("solve.commit", t0, now,
                              pods=len(batchable), committed=committed,
                              cycle=batchable[0][1] if batchable else -1,
                              pad=pending.get("pad", self.max_batch))
        except Exception:   # noqa: BLE001 — tracing must not break commits
            pass
        self.max_cycle_s = max(self.max_cycle_s, now - start)
        self._tune_chunk(pending.get("pad", self.max_batch), now - start)
        return committed

    def _execute_preemption_plans(self, fwk, plans, start,
                                  serial: List[QueuedPodInfo]) -> int:
        """Execute a batch of (preemptor, node, victims) plans: evict
        all victims in bulk, refresh the snapshot once, then validate
        each preemptor on its planned node with the REAL filter chain
        (against clones carrying the batch's earlier placements — the
        assume semantics without touching the cache) and commit the
        validated set in bulk. A failed validation routes that pod to
        the serial path; its victims are already gone, which the serial
        PostFilter treats as ordinary freed capacity.

        Semantics vs the reference: victims get the same Preempted
        events and waiting-pod rejection (``default_preemption.go:698``
        PrepareCandidate); the preemptor binds in THIS cycle instead of
        being requeued with ``nominatedNodeName`` — outcome-equivalent
        (PreferNominatedNode would pick the same node next cycle,
        ``generic_scheduler.go:250``) minus one full solve round trip,
        which is what makes mass preemption fast."""
        sched = self.sched
        recorder = getattr(fwk, "event_recorder", None)
        doomed: List[tuple] = []
        for qpi, _cycle, node_name, victims in plans:
            for victim in victims:
                if fwk.reject_waiting_pod(victim.uid):
                    continue
                doomed.append((victim.namespace, victim.name))
                if recorder is not None:
                    recorder.event(
                        victim, "Normal", "Preempted",
                        f"Preempted by {qpi.pod.namespace}/"
                        f"{qpi.pod.metadata.name} on node {node_name}",
                    )
        if doomed:
            sched.client.delete_pods(doomed)
        sched.algorithm.update_snapshot()
        snapshot = sched.algorithm.snapshot
        clones: dict = {}
        commits: List[tuple] = []
        from kubernetes_tpu.scheduler.framework import interface as fw_iface

        for qpi, cycle, node_name, _victims in plans:
            ni = clones.get(node_name)
            if ni is None:
                base = snapshot.get(node_name)
                if base is None or base.node is None:
                    serial.append(qpi)
                    continue
                ni = base.clone()
                clones[node_name] = ni
            state = CycleState()
            status = fwk.run_pre_filter_plugins(state, qpi.pod)
            ok = fw_iface.Status.is_ok(status)
            if ok:
                ok = fw_iface.Status.is_ok(
                    fwk.run_filter_plugins_with_nominated_pods(
                        state, qpi.pod, ni
                    )
                )
            if not ok:
                # resource model said yes, full filters said no
                # (topology/affinity effect): exact fallback
                serial.append(qpi)
                continue
            ni.add_pod(qpi.pod)
            result = ScheduleResult(
                suggested_host=node_name,
                evaluated_nodes=len(snapshot.list()),
                feasible_nodes=1,
            )
            commits.append((qpi, result, cycle, start))
        committed = 0
        if commits:
            committed, failed = sched.commit_assignments_bulk(fwk, commits)
            self._cycle_mutations += sched.last_bulk_commit_mutations
            if failed:
                self.session.invalidate()
        # stale-nomination cleanup (default_preemption.go:277-282 via
        # _prepare_candidate): lower-priority pods nominated on a node a
        # batch preemptor just took must lose the nomination, or their
        # phantom reservation keeps filtering other pods off the node
        nominator = getattr(fwk, "pod_nominator", None)
        if nominator is not None:
            max_prio_by_node: dict = {}
            for qpi, _cycle, node_name, _victims in plans:
                prio = qpi.pod.priority()
                cur = max_prio_by_node.get(node_name)
                if cur is None or prio > cur:
                    max_prio_by_node[node_name] = prio
            for node_name, prio in max_prio_by_node.items():
                for pi in list(
                    nominator.nominated_pods_for_node(node_name)
                ):
                    if pi.pod.priority() < prio:
                        nominator.delete_nominated_pod_if_exists(pi.pod)
                        sched.client.clear_nominated_node_name(
                            pi.pod.namespace, pi.pod.name
                        )
        # victim deletions mutated the cache outside the commit
        # accounting: the mirror rebuilds next batch regardless
        return committed

    # shared (read-only) status instances for synthesized fit errors
    _STATUS_STATIC = None
    _STATUS_DYNAMIC = None

    def _fail_declined(self, fwk, qpi: QueuedPodInfo, cycle: int,
                       cluster, batch_index: int, profiles, masks,
                       statuses_by_profile: dict,
                       candidate_hints=None) -> bool:
        """Mark a device-declined pod unschedulable without the serial
        re-run. Returns False when the static context is unavailable
        (caller then uses the serial path). ``profiles`` is the solved
        batch's per-pod profile index array, captured at solve time (the
        session's live fields may already describe a NEWER batch)."""
        from kubernetes_tpu.scheduler.framework import interface as fw_iface

        if profiles is None or batch_index >= len(profiles):
            return False
        ui = int(profiles[batch_index])
        cached = statuses_by_profile.get(ui)
        if cached is None:
            if masks is None or ui >= len(masks):
                return False
            mask = masks[ui][: cluster.num_real_nodes]
            cls = TPUBatchScheduler
            if cls._STATUS_STATIC is None:
                cls._STATUS_STATIC = fw_iface.Status(
                    fw_iface.UNSCHEDULABLE_AND_UNRESOLVABLE,
                    "node(s) didn't satisfy the pod's node-static predicates",
                )
                cls._STATUS_DYNAMIC = fw_iface.Status(
                    fw_iface.UNSCHEDULABLE,
                    "node(s) had insufficient resources or violated "
                    "topology/affinity constraints",
                )
            statuses = {
                name: (cls._STATUS_DYNAMIC if ok else cls._STATUS_STATIC)
                for name, ok in zip(cluster.node_names, mask)
            }
            # the failure message and "preemption could never help" are
            # profile-wide facts: compute them once, not per pod
            # (message aggregation is O(nodes); the PostFilter's
            # candidate prefilter is another O(nodes) scan)
            probe = fw_iface.FitError(
                num_all_nodes=cluster.num_real_nodes,
                filtered_nodes_statuses=statuses,
            )
            cached = (statuses, str(probe), not bool(mask.any()))
            statuses_by_profile[ui] = cached
        statuses, message, all_static = cached
        fit_err = fw_iface.FitError(
            pod=qpi.pod,
            num_all_nodes=cluster.num_real_nodes,
            filtered_nodes_statuses=statuses,
            message=message,
        )
        self.sched.fail_unschedulable(
            fwk, qpi, fit_err, cycle, candidate_hints=candidate_hints,
            # every node failed a NODE-STATIC predicate: preemption can
            # never help (nodesWherePreemptionMightHelp would be empty),
            # so skip the per-pod PostFilter scan entirely
            run_post_filter=not all_static,
        )
        return True

    def _host_validates(self, fwk, qpi: QueuedPodInfo, node_name: str) -> bool:
        from kubernetes_tpu.scheduler.framework import interface as fw_iface

        # the session only refreshes the snapshot on rebuild; validation
        # must see the live cache INCLUDING this batch's earlier commits
        # (incremental update: O(changed nodes) per call)
        self.sched.algorithm.update_snapshot()
        state = CycleState()
        status = fwk.run_pre_filter_plugins(state, qpi.pod)
        if not fw_iface.Status.is_ok(status):
            return False
        ni = self.sched.algorithm.snapshot.get(node_name)
        if ni is None:
            return False
        return fw_iface.Status.is_ok(
            fwk.run_filter_plugins_with_nominated_pods(state, qpi.pod, ni)
        )


def attach_batch_scheduler(
    sched: Scheduler,
    max_batch: int = 4096,
    params: SolverParams = SolverParams(),
    validate: bool = False,
    backend=None,
    adaptive_chunk: bool = True,
    pipeline: Optional[bool] = None,
) -> Optional[TPUBatchScheduler]:
    """Install the batch path iff the TPUBatchScheduler gate is enabled
    (the --feature-gates=TPUBatchScheduler wiring). ``pipeline``
    overrides the ``KTPU_PIPELINE`` kill-switch (None = read the env;
    False = the serialized barrier loop)."""
    if not sched.feature_gates.enabled("TPUBatchScheduler"):
        return None
    bs = TPUBatchScheduler(sched, max_batch=max_batch, params=params,
                           validate=validate, backend=backend,
                           adaptive_chunk=adaptive_chunk,
                           pipeline=pipeline)
    sched.batch_scheduler = bs
    try:
        # the schedule-latency SLO reads the e2e histogram from THIS
        # scheduler's registry — point the SLO engine at it so every
        # batch-path consumer (bench, chaos, qos harnesses) gets live
        # evaluation without per-harness wiring
        from kubernetes_tpu.observability.slo import get_slo_engine

        get_slo_engine().add_registry(sched.metrics.registry)
    except Exception:  # noqa: BLE001 — SLO wiring must never block attach
        pass
    return bs
