"""Device/solver profiling layer: per-solve-cycle hot-path telemetry.

The JAX solve path is the most expensive layer of the pipeline and was,
until this module, a black box: the bench ``diag:`` line hand-counted
commit/device/encode seconds, pad warms were *assumed* from bucket
bookkeeping, and "how much of device time is dispatch vs
``block_until_ready`` wait vs host↔device transfer" was unanswerable.
This recorder measures, per solve cycle:

- **XLA compile events** keyed by padded-shape bucket, via a
  ``jax.monitoring`` event-duration listener
  (``/jax/core/compile/backend_compile_duration``) with a
  timing-heuristic fallback for builds without the listener API —
  detecting *actual* recompiles, including the forbidden
  compile-inside-a-measured-cycle case the sidecar's pre-warm
  bookkeeping only prevents by convention;
- the **dispatch-vs-block split** around the solver call (async XLA
  dispatch time vs ``block_until_ready`` wait at materialization) — the
  direct input for the streaming-scheduler double-buffer design: block
  time is exactly the wall the host would win back by overlapping;
- **host↔device transfer bytes** computed from the encoded plane
  shapes/dtypes (pod stream up per cycle, static/state planes up per
  rebuild, assignments down per materialize);
- **pad occupancy** (real rows ÷ padded rows) per bucket — the scan
  length is the padded size, so waste here is device time burned on
  ghost pods.

Design constraints match ``tracer.py`` (the headline row schedules
thousands of pods/s through the instrumented path): recording is a few
float adds plus one GIL-atomic ``deque.append`` per solve *cycle* (not
per pod), so steady-state overhead is ~0 — the bar PR 2's tracer met,
re-measured by ``bench.py --config profab``.

Three consumers read the ring:

- ``kubernetes_tpu/metrics/solver_metrics.py`` mirrors each completed
  cycle into ``/metrics`` series (``solver_compiles_total{bucket}``,
  ``solver_device_wait_seconds``, ...);
- the bench telemetry stream: ``KTPU_TELEMETRY=<dir>`` writes one JSONL
  record per completed solve cycle, and ``summary()`` becomes the
  ``telemetry`` sub-object on every bench-row JSON;
- the flight recorder: cycle ids stamped on every record correlate with
  the tracer's ``solve.*`` spans, and a compile landing inside a
  measured cycle emits a ``solve.unexpected_compile`` instant plus a
  rate-limited flight-recorder dump (PR 2 machinery) so the postmortem
  is on disk before anyone asks.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

_logger = logging.getLogger(__name__)

DEFAULT_MAX_CYCLES = 4096

# the jax.monitoring event that fires once per real XLA compilation
# (cache hits don't emit it — exactly the "actual recompile" signal)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# timing-heuristic fallback thresholds (no listener API): a cycle whose
# device time exceeds BOTH `ratio × ` the bucket's best-seen time AND
# `floor` seconds above it is attributed a suspected compile
_HEURISTIC_RATIO = 4.0
_HEURISTIC_FLOOR_S = 0.25


class _Cycle(dict):
    """One solve cycle's record. A dict subclass so JSONL serialization
    and ring consumers get plain keys, with the few non-serialized
    control fields kept as attributes. ``dispatch_end`` is the
    monotonic instant the lazy dispatch returned — the start of the
    in-flight device window the streaming pipeline hides host work
    under; ``note_block`` turns it into the cycle's ``overlap_s``."""

    __slots__ = ("pending_block", "done", "dispatch_end")


class DevProfiler:
    """Lock-cheap per-solve-cycle recorder (ring-buffered like the
    tracer). One instance per process via ``get_devprof()``; the solver
    session opens a cycle around each solve, phases accumulate into the
    open record, and completion (at ``end_cycle`` or, for lazy solves,
    at the timed materializer's ``note_block``) mirrors the record into
    /metrics and the JSONL stream."""

    def __init__(
        self,
        enabled: Optional[bool] = None,
        max_cycles: int = DEFAULT_MAX_CYCLES,
        telemetry_dir: Optional[str] = None,
        use_listener: Optional[bool] = None,
    ):
        if enabled is None:
            enabled = os.environ.get("KTPU_DEVPROF", "") != "off"
        self.enabled = enabled
        self._ring: deque = deque(maxlen=max_cycles)
        self._local = threading.local()
        self._lock = threading.Lock()   # JSONL writes + best-time table
        self._seq = 0
        self.workload: str = ""
        # per-bucket best-seen device seconds (heuristic baseline) and
        # the warmed-compile ledger the sidecar's diag reads
        self._best_device_s: Dict[int, float] = {}
        self.warm_compiles = 0          # compiles inside warming cycles
        self.warm_compile_s = 0.0
        self.unexpected_compiles = 0    # compiles inside measured cycles
        self.background_compiles = 0    # compiles with no open cycle
        self._telemetry_path: Optional[str] = None
        self._telemetry_file = None
        tdir = telemetry_dir if telemetry_dir is not None \
            else os.environ.get("KTPU_TELEMETRY") or None
        if tdir:
            try:
                os.makedirs(tdir, exist_ok=True)
                self._telemetry_path = os.path.join(
                    tdir, f"solvercycles-{os.getpid()}.jsonl")
            except OSError:
                _logger.exception("KTPU_TELEMETRY dir unusable; stream off")
        # compile-event listener: jax.monitoring when available (and not
        # forced off for tests via KTPU_DEVPROF_HEURISTIC=1), else the
        # timing heuristic marks suspected compiles from device-time
        # outliers against the bucket's best-seen time
        if use_listener is None:
            use_listener = os.environ.get(
                "KTPU_DEVPROF_HEURISTIC", "") != "1"
        self.listener_active = bool(use_listener) and _install_listener()

    # -- cycle lifecycle ----------------------------------------------
    def begin_cycle(self, cycle: int = -1, pad: int = 0, real: int = 0,
                    warming: bool = False,
                    rebuild: str = "none") -> Optional[_Cycle]:
        """Open a per-solve-cycle record on this thread. ``cycle`` is
        the scheduling-cycle id (the tracer's correlation key), ``pad``
        the padded batch bucket, ``real`` the real (un-padded) pod
        count. ``rebuild`` marks the full/state_only re-encode paths so
        their one-off upload cost never pollutes the steady-state
        dispatch/block series."""
        if not self.enabled:
            return None
        rec = _Cycle(
            seq=self._next_seq(),
            wall=time.time(),
            workload=self.workload,
            cycle=int(cycle),
            pad=int(pad),
            real=int(real),
            warming=bool(warming),
            rebuild=rebuild,
            encode_s=0.0,
            pack_s=0.0,
            scatter_s=0.0,
            dispatch_s=0.0,
            block_s=0.0,
            compiles=0,
            compile_s=0.0,
            compile_suspected=False,
            h2d_bytes=0,
            d2h_bytes=0,
            donated_bytes=0,
            # device-mirror catch-up h2d (index/value triples) — a
            # subset of h2d_bytes kept separately attributable
            scatter_bytes=0,
        )
        rec.pending_block = False
        rec.done = False
        rec.dispatch_end = None
        self._local.active = rec
        self._ring.append(rec)
        return rec

    def phase(self, name: str, seconds: float) -> None:
        """Accumulate a phase duration (encode/pack/dispatch/block) into
        the open cycle. A few float adds — safe on the hot path."""
        rec = getattr(self._local, "active", None)
        if rec is not None and not rec.done:
            rec[name + "_s"] += seconds

    def note_staleness(self, rec: Optional[_Cycle],
                       seconds: float) -> None:
        """Record the snapshot-staleness SLI on an open cycle record
        (age of the newest watch event reflected in the planes this
        cycle solves against) — set once per cycle by the session."""
        if rec is not None and not rec.done:
            rec["staleness_s"] = round(float(seconds), 6)

    def add_bytes(self, direction: str, n: int) -> None:
        """Account a host↔device transfer (direction: h2d | d2h),
        computed by the caller from the encoded array shapes/dtypes —
        measuring the planes we *ship*, not interconnect counters.
        Direction ``donated`` is the separate ledger for planes a
        donated device-persistent buffer made REUSABLE this cycle —
        bytes that never crossed the link. They are excluded from the
        h2d total and the ``solver_transfer_bytes_total`` mirror (a
        resident buffer counted as an upload would make the transfer
        metric lie), but surfaced in ``summary()`` so the donation win
        is a number."""
        rec = getattr(self._local, "active", None)
        if rec is not None and not rec.done:
            rec[direction + "_bytes"] += int(n)

    def end_cycle(self, rec: Optional[_Cycle],
                  pending_block: bool = False) -> None:
        """Close the open cycle. With ``pending_block`` (a lazy solve
        whose materialization — and so its ``block_until_ready`` wait —
        happens cycles later in the commit pipeline) the record stays
        open for ``note_block`` to complete; everything else completes
        now."""
        if rec is None:
            return
        if getattr(self._local, "active", None) is rec:
            self._local.active = None
        if pending_block:
            # the in-flight device window opens HERE: host time spent
            # before the materializer finally blocks is work the
            # pipeline hid under the dispatched solve (overlap_s)
            rec.pending_block = True
            rec.dispatch_end = time.monotonic()
            return
        self._complete(rec)

    def abort(self, rec: Optional[_Cycle]) -> None:
        """Discard an open record that turned out to describe no solve
        (e.g. the incremental encode fell through to a rebuild): removed
        from the ring, never mirrored or streamed."""
        if rec is None:
            return
        rec.done = True
        if getattr(self._local, "active", None) is rec:
            self._local.active = None
        try:
            self._ring.remove(rec)
        except ValueError:
            pass

    def note_block(self, rec: Optional[_Cycle], seconds: float,
                   d2h_bytes: int = 0,
                   start_mono: Optional[float] = None) -> None:
        """Late completion for lazy solves: the timed materializer calls
        this with the measured ``block_until_ready`` wait and the
        assignments' device→host bytes. May run on a different thread
        and several cycles after ``end_cycle`` (the sidecar pipelines
        commit N while N+1 solves). ``start_mono`` is the monotonic
        instant the materializer began blocking: the gap back to this
        cycle's ``dispatch_end`` is host work performed WHILE the solve
        was in flight — the pipeline's ``overlap_s``, the time the
        double-buffered loop won back from the old barrier."""
        if rec is None or rec.done:
            return
        if start_mono is not None and rec.dispatch_end is not None:
            rec["overlap_s"] = round(
                max(0.0, start_mono - rec.dispatch_end), 6)
        rec["block_s"] += seconds
        rec["d2h_bytes"] += int(d2h_bytes)
        rec.pending_block = False
        self._complete(rec)

    # -- compile detection --------------------------------------------
    def on_compile(self, seconds: float) -> None:
        """Called by the process-wide jax.monitoring listener for every
        real XLA compilation. Attribution: the cycle open on the
        compiling thread (jit compiles synchronously inside the dispatch
        call), else background (warmup helpers, unrelated jit use)."""
        if not self.enabled:
            return
        rec = getattr(self._local, "active", None)
        if rec is None or rec.done:
            self.background_compiles += 1
            return
        rec["compiles"] += 1
        rec["compile_s"] += seconds

    def _heuristic_compiles(self, rec: _Cycle) -> None:
        """No listener API: flag a suspected compile when this bucket's
        device time is an extreme outlier against its best-seen time.
        Conservative by design (ratio AND absolute floor) — a host
        stall can double a cycle, but a 4× + 250ms excursion on a warmed
        bucket is a compile or something equally dump-worthy."""
        device_s = rec["dispatch_s"] + rec["block_s"]
        bucket = rec["pad"]
        with self._lock:
            best = self._best_device_s.get(bucket)
            if best is None or device_s < best:
                self._best_device_s[bucket] = device_s
        if (
            best is not None
            and device_s > best * _HEURISTIC_RATIO
            and device_s > best + _HEURISTIC_FLOOR_S
        ):
            rec["compiles"] += 1
            rec["compile_suspected"] = True

    # -- completion ----------------------------------------------------
    def _complete(self, rec: _Cycle) -> None:
        if rec.done:
            return
        rec.done = True
        if not self.listener_active:
            self._heuristic_compiles(rec)
        if rec["compiles"]:
            if rec["warming"]:
                self.warm_compiles += rec["compiles"]
                self.warm_compile_s += rec["compile_s"]
            else:
                # the forbidden case: a compile landed inside a measured
                # cycle — the sidecar's pre-warm discipline failed, and
                # thousands of pods just absorbed the compile into their
                # e2e latency. Counter + tracer instant + flight dump.
                self.unexpected_compiles += rec["compiles"]
                self._flag_unexpected(rec)
        self._mirror_metrics(rec)
        self._write_jsonl(rec)

    def _flag_unexpected(self, rec: _Cycle) -> None:
        try:
            from kubernetes_tpu.metrics.solver_metrics import solver_metrics

            solver_metrics().unexpected_compiles_total.inc(
                amount=rec["compiles"])
        except Exception:  # pragma: no cover — metrics must never break
            pass
        try:
            from kubernetes_tpu.observability import get_tracer

            tracer = get_tracer()
            if tracer.enabled:
                tracer.event("solve.unexpected_compile",
                             cycle=rec["cycle"], pad=rec["pad"],
                             compile_s=round(rec["compile_s"], 4),
                             suspected=rec["compile_suspected"])
                # same stable-filename + rate-limit contract as the
                # degraded-mode dump: a compile storm overwrites one
                # postmortem instead of filling the dump dir
                tracer.dump(reason="unexpected-compile",
                            min_interval_s=5.0)
        except Exception:  # pragma: no cover — dumping is best-effort
            pass

    def _mirror_metrics(self, rec: _Cycle) -> None:
        if rec["warming"]:
            return
        try:
            from kubernetes_tpu.metrics.solver_metrics import solver_metrics

            sm = solver_metrics()
            bucket = str(rec["pad"])
            if rec["compiles"]:
                sm.compiles_total.inc(bucket, amount=rec["compiles"])
                if rec["compile_s"]:
                    sm.compile_seconds.observe(rec["compile_s"])
            sm.device_wait_seconds.observe(rec["block_s"])
            sm.dispatch_seconds.observe(rec["dispatch_s"])
            if rec["pad"]:
                sm.pad_occupancy_ratio.set(
                    rec["real"] / rec["pad"], bucket)
            if rec["h2d_bytes"]:
                sm.transfer_bytes_total.inc(
                    "h2d", amount=float(rec["h2d_bytes"]))
            if rec["d2h_bytes"]:
                sm.transfer_bytes_total.inc(
                    "d2h", amount=float(rec["d2h_bytes"]))
        except Exception:  # pragma: no cover — metrics must never break
            pass

    def _write_jsonl(self, rec: _Cycle) -> None:
        if self._telemetry_path is None:
            return
        try:
            with self._lock:
                if self._telemetry_file is None:
                    self._telemetry_file = open(self._telemetry_path, "a")
                self._telemetry_file.write(json.dumps(rec) + "\n")
                self._telemetry_file.flush()
        except OSError:
            _logger.exception("telemetry stream write failed; stream off")
            self._telemetry_path = None

    def _next_seq(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq

    # -- consumers -----------------------------------------------------
    def cycles(self, include_warming: bool = False) -> List[dict]:
        """Completed cycle records still in the ring, oldest first."""
        return [r for r in list(self._ring)
                if r.done and (include_warming or not r["warming"])]

    def summary(self) -> dict:
        """Aggregate the ring's measured (non-warming) cycles into the
        ``telemetry`` sub-object every bench row carries: compile count,
        device-wait share, pad waste, transfer bytes, and the slowest
        cycle's phase attribution (which phase made the max cycle slow
        is the first question every blown p99 asks)."""
        recs = self.cycles()
        out = {
            "cycles": len(recs),
            "compiles": 0,
            "compile_s": 0.0,
            "unexpected_compiles": self.unexpected_compiles,
            "warm_compiles": self.warm_compiles,
            "device_wait_share": 0.0,
            "overlap_share": 0.0,
            "overlap_s": 0.0,
            "overlapped_cycles": 0,
            "dispatch_s": 0.0,
            "block_s": 0.0,
            "encode_s": 0.0,
            "scatter_s": 0.0,
            "encode_share": 0.0,
            "pad_waste_pct": 0.0,
            "h2d_bytes": 0,
            "d2h_bytes": 0,
            "donated_bytes": 0,
            "scatter_bytes": 0,
            "compile_detector": "listener" if self.listener_active
            else "heuristic",
        }
        if not recs:
            return out
        tot = {"encode_s": 0.0, "pack_s": 0.0, "scatter_s": 0.0,
               "dispatch_s": 0.0, "block_s": 0.0}
        real = padded = 0
        slowest = None
        slowest_total = -1.0
        max_staleness = None
        # pipeline overlap: judged over the LAZY cycles only (the ones
        # that actually opened an in-flight device window) — an eager
        # cycle's block is a barrier by construction and must not
        # dilute the share of the window the host managed to hide
        ov_total = ov_block = 0.0
        overlapped = 0
        for r in recs:
            for k in tot:
                tot[k] += r.get(k, 0.0)
            ov = r.get("overlap_s")
            if ov is not None:
                ov_total += ov
                ov_block += r["block_s"]
                overlapped += 1
            out["compiles"] += r["compiles"]
            out["compile_s"] += r["compile_s"]
            out["h2d_bytes"] += r["h2d_bytes"]
            out["d2h_bytes"] += r["d2h_bytes"]
            out["donated_bytes"] += r.get("donated_bytes", 0)
            out["scatter_bytes"] += r.get("scatter_bytes", 0)
            stale = r.get("staleness_s")
            if stale is not None and (max_staleness is None
                                      or stale > max_staleness):
                max_staleness = stale
            real += r["real"]
            padded += r["pad"] if r["pad"] else r["real"]
            cycle_total = (r["encode_s"] + r["pack_s"] + r["dispatch_s"]
                           + r["block_s"] + r["compile_s"])
            if cycle_total > slowest_total:
                slowest_total, slowest = cycle_total, r
        phase_total = sum(tot.values())
        out["dispatch_s"] = round(tot["dispatch_s"], 4)
        out["block_s"] = round(tot["block_s"], 4)
        out["encode_s"] = round(tot["encode_s"] + tot["pack_s"], 4)
        out["scatter_s"] = round(tot["scatter_s"], 4)
        out["compile_s"] = round(out["compile_s"], 4)
        if phase_total > 0:
            out["device_wait_share"] = round(
                tot["block_s"] / phase_total, 4)
            # the mirror proof metric: host CLUSTER-PLANE build share
            # of the measured phase time. Pod-row delta encode (the
            # drained pods' h2d prep, inherent per-batch work) books
            # under pack_s and is excluded — the mirror's claim is that
            # node-column/full-plane encodes vanish from the sustained
            # row, not that drained pods stop needing encoding.
            out["encode_share"] = round(
                tot["encode_s"] / phase_total, 4)
        out["overlap_s"] = round(ov_total, 4)
        out["overlapped_cycles"] = overlapped
        if ov_total + ov_block > 0:
            # share of the in-flight device window hidden under host
            # work (drain/encode/commit of neighboring batches): 1.0 =
            # the materializer never waited, 0.0 = pure barrier
            out["overlap_share"] = round(
                ov_total / (ov_total + ov_block), 4)
        if padded > 0:
            out["pad_waste_pct"] = round(100.0 * (1.0 - real / padded), 2)
        if max_staleness is not None:
            # freshness SLI: the oldest snapshot any measured cycle
            # solved against (bench rows surface it as
            # freshness.max_snapshot_staleness_ms)
            out["max_staleness_s"] = round(max_staleness, 4)
        if slowest is not None:
            out["max_cycle"] = {
                "cycle": slowest["cycle"],
                "total_s": round(slowest_total, 4),
                "encode_s": round(
                    slowest["encode_s"] + slowest["pack_s"], 4),
                "dispatch_s": round(slowest["dispatch_s"], 4),
                "block_s": round(slowest["block_s"], 4),
                "compiles": slowest["compiles"],
                "rebuild": slowest["rebuild"],
            }
        return out

    def reset(self, workload: str = "") -> None:
        """Fresh window for a new bench row (mirrors the tracer's
        per-row ``clear``): the ring, per-run compile ledgers, and the
        heuristic baseline all restart; the /metrics counters keep
        accumulating (they are process-lifetime by contract)."""
        self._ring.clear()
        self._local = threading.local()
        self.workload = workload
        self.warm_compiles = 0
        self.warm_compile_s = 0.0
        self.unexpected_compiles = 0
        self.background_compiles = 0
        with self._lock:
            self._best_device_s.clear()

    def configure(self, enabled: Optional[bool] = None) -> None:
        if enabled is not None:
            self.enabled = enabled

    def close(self) -> None:
        with self._lock:
            if self._telemetry_file is not None:
                try:
                    self._telemetry_file.close()
                except OSError:
                    pass
                self._telemetry_file = None


# -- process-wide wiring (the legacyregistry pattern) ------------------

_listener_installed = False


def _install_listener() -> bool:
    """Register ONE process-wide jax.monitoring listener that routes
    compile events to whatever profiler is current (jax has no
    per-listener unregister, so the closure indirects through
    ``get_devprof``). Returns False when the API is unavailable — the
    caller falls back to the timing heuristic."""
    global _listener_installed
    if _listener_installed:
        return True
    try:
        from jax import monitoring

        if not hasattr(monitoring, "register_event_duration_secs_listener"):
            return False

        def _on_event(name: str, seconds: float, **kw) -> None:
            if name == _COMPILE_EVENT:
                prof = _default
                if prof is not None:
                    prof.on_compile(seconds)

        monitoring.register_event_duration_secs_listener(_on_event)
        _listener_installed = True
        return True
    except Exception:  # noqa: BLE001 — profiling must never break solves
        _logger.exception("jax.monitoring listener unavailable; "
                          "falling back to the timing heuristic")
        return False


_default: Optional[DevProfiler] = None
_default_lock = threading.Lock()


def get_devprof() -> DevProfiler:
    """Process-wide device profiler. Disabled with KTPU_DEVPROF=off;
    KTPU_TELEMETRY=<dir> streams one JSONL record per solve cycle."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = DevProfiler()
    return _default


def set_devprof(prof: DevProfiler) -> DevProfiler:
    global _default
    _default = prof
    return prof
