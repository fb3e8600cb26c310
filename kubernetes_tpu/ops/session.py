"""Device-resident solver session: the incremental host→device mirror.

The reference keeps its cluster snapshot incrementally updated with a
Generation-ordered LRU (``internal/cache/cache.go:203-287``); this module
is the device half of that idea (SURVEY.md section 7, hard part 1).
Re-encoding and re-uploading the whole cluster every batch costs more than
the solve itself (host→device transfer dominated the profile), so a
session:

- uploads the solve-invariant arrays (allocatable, static predicate masks,
  topology codes) to the device ONCE per cluster epoch,
- carries the dynamic state (per-node requested vectors, pod counts,
  topology/affinity count matrices) ON DEVICE between batches — the scan's
  final carry IS the next batch's initial state,
- encodes only the pod-side arrays per batch (``encode_pods_only``),
- and invalidates on ``SchedulerCache.mutation_seq`` drift: the sidecar
  accounts one expected mutation (the assume) per successfully committed
  pod; anything else that touched the cache — external pod/node events,
  serial-path binds, TTL expiry, failed binds — means the device mirror
  no longer matches the host truth and is rebuilt from a fresh snapshot.

Correctness therefore never depends on the incremental path: any doubt →
full rebuild, which is exactly the v1 behavior.

Which backend solves an epoch is a shape decision made before the solve
(``SolverSession._route``). A backend that raises is not demoted: the
exception leaves the session, and the sidecar counts the pods it rescues.
"""

from __future__ import annotations

import logging
import time
from typing import List, Optional, Tuple

import numpy as np

from kubernetes_tpu.observability import get_tracer
from kubernetes_tpu.observability.devprof import get_devprof
from kubernetes_tpu.observability.tracer import NOOP_SPAN
from kubernetes_tpu.ops.encode import BatchEncoder, EncodedCluster
from kubernetes_tpu.ops.mirror import mirror_enabled
from kubernetes_tpu.ops.solver import (
    SolverParams,
    _solve_packed,
    build_state,
    build_static,
    pack_podin,
)

_logger = logging.getLogger(__name__)

# how a solve refreshed the device state, lightest first: the resident
# state as it was, a mirror scatter, a state-only re-upload, or a full
# host re-encode and upload
REBUILD_KINDS = ("none", "scatter", "state_only", "full")


def _tree_nbytes(tree) -> int:
    """Byte size of every array leaf in a backend's prepared static or
    state pytree — the devprof host→device transfer accounting is
    computed from the shapes/dtypes we actually ship. Only meaningful
    for backends whose ``prepare`` genuinely uploads everything it
    returns; backends that keep donated/persistent device buffers
    declare ``self_accounting`` and report their own transfer bytes
    (counting a device-resident donated plane as an upload would make
    ``solver_transfer_bytes_total`` lie — the devscale proof metric)."""
    import jax

    try:
        return sum(int(getattr(leaf, "nbytes", 0))
                   for leaf in jax.tree_util.tree_leaves(tree))
    except Exception:  # noqa: BLE001 — accounting must never break solves
        return 0


class XlaBackend:
    """Legacy scan backend (per-value count tables + gathers). Slow for
    wide hostname-keyed term spaces, but free of the planes layout's
    structural limits: epochs with more tracked terms than padded nodes
    are routed here."""

    name = "xla-legacy"

    def prepare(self, cluster, batch):
        if cluster.sv_attached is not None:
            # silently ignoring shared-volume planes would let a shared
            # claim double-count; shared-volume epochs must solve on
            # the planes scan (or serial-fall-back loudly)
            raise ValueError(
                "legacy scan does not carry shared-volume planes")
        return (build_static(cluster, batch, device=True),
                build_state(cluster, batch, device=True))

    def solve_lazy(self, params, static, state, pod_ints, pod_floats):
        new_state, assignments = _solve_packed(
            static, state, pod_ints, pod_floats, params
        )
        return assignments, new_state

    @staticmethod
    def materialize(handle):
        return np.asarray(handle)

    def solve(self, params, static, state, pod_ints, pod_floats):
        h, new_state = self.solve_lazy(params, static, state, pod_ints,
                                       pod_floats)
        return self.materialize(h), new_state


def _mesh_width(n_devices: int) -> int:
    """Mesh node-axis width for the sharded tier: the largest power of
    two ≤ the visible device count. Pad buckets are multiples of 128
    lanes, so a power-of-two axis always divides the padded node count
    (a 6-wide mesh would trip the divisibility contract and raise on
    the very first rebuild)."""
    width = 1
    while width * 2 <= n_devices:
        width *= 2
    return width


def default_backend():
    """Backend tiering, mesh-aware since the sharded-by-default solve:

    - ``KTPU_SOLVER=xla|pallas|cpp`` pin the legacy single-device
      backends exactly as before;
    - ``KTPU_SOLVER=sharded`` forces the mesh backend over every
      visible device (a power-of-two mesh; even a 1-device mesh, for
      the shard_map-machinery control arm);
    - ``KTPU_SOLVER=auto`` — and UNSET on real multi-device hardware
      (tpu/gpu) — takes the mesh tier whenever more than one device is
      visible: the hardware, not the host, becomes the ceiling.
      On a CPU host the unset default keeps the single-device planes
      scan even when virtual devices are forced
      (``--xla_force_host_platform_device_count``): virtual host
      devices share the same silicon, so sharding there is a scaling
      test vehicle (bench/devscale set ``auto`` explicitly), not a
      production win — and the tier-1 suite must not silently pay
      mesh compile costs;
    - otherwise: Pallas kernel on TPU (interpret mode on the CPU when
      pinned), native C++ planes solver when the library builds, else
      the gather-free XLA planes scan.

    A single visible device NEVER constructs a mesh on the auto/unset
    paths (guarded by tests/test_backend_guard.py): single-device
    startup pays zero mesh machinery."""
    import os

    import jax

    choice = os.environ.get("KTPU_SOLVER", "")
    if choice == "xla":
        from kubernetes_tpu.ops.pallas_solver import XlaPlanesBackend

        return XlaPlanesBackend()
    if choice == "cpp":
        from kubernetes_tpu.ops.native_backend import CppBackend

        return CppBackend()
    if choice == "pallas":
        from kubernetes_tpu.ops.pallas_solver import PallasBackend

        return PallasBackend()
    n_devices = jax.device_count()
    mesh_tier = (
        choice == "sharded"
        or (choice == "auto" and n_devices > 1)
        or (choice == "" and n_devices > 1
            and jax.default_backend() in ("tpu", "gpu"))
    )
    if mesh_tier:
        from kubernetes_tpu.parallel import ShardedBackend, make_mesh

        return ShardedBackend(make_mesh(_mesh_width(n_devices)))
    if jax.default_backend() == "tpu":
        from kubernetes_tpu.ops.pallas_solver import PallasBackend

        return PallasBackend()
    # gpu/metal/cpu: Mosaic does not lower there. Prefer the native C++
    # planes solver when the library builds, else the XLA planes scan.
    from kubernetes_tpu.ops import native_backend

    if native_backend.available():
        return native_backend.CppBackend()
    from kubernetes_tpu.ops.pallas_solver import XlaPlanesBackend

    return XlaPlanesBackend()


# beyond these per-axis sizes the pallas kernel's Python-unrolled
# constraint loops stop paying off (compile time and per-step vector-op
# count scale linearly in SC/T/U, while the XLA scan handles the same
# axes as single fused [K, N] ops)
PALLAS_MAX_SC = 8
PALLAS_MAX_TERMS = 8
PALLAS_MAX_PROFILES = 8

# backends whose layouts carry the shared-volume attach planes
_SV_BACKENDS = ("xla-planes", "cpp", "sharded")


def _pallas_fits(batch, cluster, backend) -> bool:
    """The pallas kernel takes the epoch: SC/T/U within the unrolled
    kernel's caps, no shared-volume planes, and the resident planes
    within the kernel's VMEM limit."""
    return (
        batch.sc_counts.shape[0] <= PALLAS_MAX_SC
        and batch.term_counts.shape[0] <= PALLAS_MAX_TERMS
        and batch.static_masks.shape[0] <= PALLAS_MAX_PROFILES
        and getattr(batch, "pod_sv", None) is None
        and backend.fits(batch, cluster.allocatable.shape[0])
    )


class SolverSession:
    """Owns the device mirror for one scheduler's batch path."""

    def __init__(self, scheduler, params: SolverParams = SolverParams(),
                 max_batch: int = 4096, pad_nodes: int = 128,
                 backend=None):
        self.sched = scheduler
        self.params = params
        self.max_batch = max_batch
        self.pad_nodes = pad_nodes
        self.backend = backend or default_backend()
        # backend actually used for the current epoch (``_route`` sends
        # shapes the preferred backend cannot hold elsewhere, for that
        # epoch only)
        self._active = self.backend
        from kubernetes_tpu.ops.pallas_solver import XlaPlanesBackend

        self._planes = self.backend if self.backend.name == "xla-planes" \
            else XlaPlanesBackend()
        self._legacy = XlaBackend()
        self._encoder: Optional[BatchEncoder] = None
        self._cluster: Optional[EncodedCluster] = None
        self._static = None   # device-resident solve-invariant arrays
        self._state = None    # device-resident dynamic state (carried)
        self._static_fp = None  # fingerprint of the resident static
        # host-side static predicate masks + the last batch's per-pod
        # profile indices: lets the sidecar synthesize per-node filter
        # statuses for device-declined pods without a serial re-run
        self._static_masks_host = None   # [U, N] bool
        self.last_profile_idx = None     # [B] int32
        self.last_inexpressible = None   # [B] bool
        self._last_seq: int = -1
        # node-SET epoch the resident encoding was built over. The
        # mutation arithmetic alone can be laundered by compensating
        # bumps; an encoding whose node columns describe another epoch
        # (chaos_nodes: mass node death) must rebuild, not keep
        # declining/misassigning against ghost nodes.
        self._node_epoch: int = -1
        self._poisoned = False
        self._warming = False
        # newest-applied-event anchor the LAST staleness sample was
        # taken against: a retry cycle solving an UNCHANGED snapshot
        # accrues no new staleness debt and must not be sampled (a
        # quiet cluster — autoscale row waiting out node boot latency —
        # would otherwise read as an ever-staler snapshot and
        # false-flip the staleness SLO)
        self._staleness_anchor = 0.0
        # telemetry: how often the incremental path was taken
        self.incremental_hits = 0
        self.rebuilds = 0
        self.state_only_rebuilds = 0
        # scheduling-cycle id stamped by the sidecar before each solve so
        # the per-cycle phase spans correlate with the pods' queue cycles
        self.trace_cycle = -1
        # the heaviest state refresh the solves since the sidecar last
        # reset it took (``REBUILD_KINDS``): the ``queue.cycle`` span's
        # ``rebuild`` argument
        self.cycle_rebuild = "none"
        # device-resident cluster mirror (KTPU_MIRROR, default on):
        # watch deltas journaled by the cache are SCATTERED into the
        # donated planes at the next solve instead of forcing a full
        # host encode. Constructed only when the preferred backend
        # exposes the scatter hooks (the legacy scan doesn't) and the
        # scheduler carries a journal-capable cache.
        self._mirror = None
        self._journal = None
        if (
            mirror_enabled()
            and hasattr(self.backend, "scatter_state_add")
            and hasattr(getattr(self.sched, "cache", None),
                        "attach_delta_journal")
        ):
            from kubernetes_tpu.ops.mirror import (
                DeltaJournal,
                DeviceClusterMirror,
            )

            self._journal = DeltaJournal()
            self.sched.cache.attach_delta_journal(self._journal)
            self._mirror = DeviceClusterMirror(self, self._journal)

    # ------------------------------------------------------------------
    def warm_pad(self, pods: List, pad: int) -> Optional[int]:
        """Compile the ``pad``-sized executable WITHOUT touching the
        state mirror: runs one solve against the resident static and a
        spare of the resident state (``_spare``) and discards every
        output, so the live ``self._state`` is untouched. The sidecar
        calls this between cycles when the latency tuner shrinks to a
        bucket that has never compiled —
        the compile must burn an un-measured moment, not a real batch's
        e2e latency. Returns the number of compile events devprof
        MEASURED during the warm (0 = the executable was already cached
        and no warm was actually needed — the sidecar's accounting is
        measured, not assumed), or None when there is no resident mirror
        to warm against (the next real solve is a rebuild, which
        compiles its own pad anyway)."""
        if self._state is None or self._encoder is None or \
                self._cluster is None:
            return None
        dp = get_devprof()
        rec = dp.begin_cycle(cycle=-1, pad=pad, real=len(pods),
                             warming=True) if dp.enabled else None
        try:
            pb = self._encoder.encode_pods_only(pods, pad)
            if pb is None or pb.requests.shape[1] != \
                    self._cluster.allocatable.shape[1]:
                dp.abort(rec)
                rec = None
                return None
            ints, floats = pack_podin(pb)
            dp.add_bytes("h2d", ints.nbytes + floats.nbytes)
            t0 = time.monotonic()
            handle, _discarded_state = self._active.solve_lazy(
                self.params, self._static,
                self._spare(self._active, self._state), ints, floats
            )
            t_disp = time.monotonic()
            out = self._active.materialize(handle)  # block: compile+run
            staging = self._take_staging_s()
            dp.phase("dispatch", max(0.0, t_disp - t0 - staging))
            dp.phase("block", time.monotonic() - t_disp + staging)
            self._flush_backend_bytes(dp)
            dp.add_bytes("d2h", int(getattr(out, "nbytes", 0)))
            # measured compile count when the listener is live; the
            # timing heuristic can only classify at cycle completion,
            # so without a listener the legacy one-warm-per-call
            # assumption stands in
            return rec["compiles"] \
                if rec is not None and dp.listener_active else 1
        except Exception:   # noqa: BLE001 — warming is advisory
            dp.abort(rec)
            rec = None
            return None
        finally:
            dp.end_cycle(rec)

    @staticmethod
    def _spare(backend, state):
        """``state`` as ``backend``'s solve may take it while ``state``
        itself stays valid: a clone (the backend's ``warm_state``) where
        the solve donates or mutates its state input (the donating mesh
        tier, the native planes), else the state itself (jax arrays are
        immutable)."""
        clone = getattr(backend, "warm_state", None)
        return state if clone is None else clone(state)

    def invalidate(self) -> None:
        """Mark the device mirror diverged. Sticky until the next rebuild:
        a later ``note_committed`` must not re-validate (e.g. a host-
        rejected assignment the device already counted leaves the mirror
        wrong even when the mutation arithmetic works out)."""
        self._last_seq = -1
        self._poisoned = True

    def note_drift(self) -> None:
        """Snapshot-drift trigger (chaos_nodes): a commit-time guard
        just refused assignments because their target nodes died, were
        cordoned, or went unreachable after this encoding was built.
        Beyond invalidating, drop the static fingerprint — the NODE
        PLANES themselves are what drifted, so the next rebuild must
        re-encode and re-upload the static arrays rather than take the
        state-only path and keep solving against ghost columns (the
        mass-decline spin this exists to break)."""
        self.invalidate()
        self._static_fp = None

    def mirror_current(self) -> bool:
        """True when the device mirror is still consistent with the host
        cache RIGHT NOW (no unsanctioned mutations since it was last
        validated): the next solve needs neither a catch-up nor a
        rebuild."""
        return (
            not self._poisoned
            and self._last_seq == self.sched.cache.mutation_seq
            and self._node_epoch == self.sched.cache.node_set_seq
        )

    def note_committed(self, expected_mutations: int, seq_before: int) -> None:
        """Called by the sidecar after committing a batch: the session
        stays valid only if the mirror was valid going INTO this batch
        (``_last_seq == seq_before`` — otherwise a zero-mutation batch
        would launder an earlier invalidation) and the cache saw exactly
        the expected number of mutations (one assume per committed pod)
        since ``seq_before``."""
        seq_now = self.sched.cache.mutation_seq
        if (
            not self._poisoned
            and self._last_seq == seq_before
            and seq_now == seq_before + expected_mutations
        ):
            self._last_seq = seq_now
        elif (
            self._mirror is not None
            and not self._poisoned
            and self._last_seq >= 0
        ):
            # mirror arm: unexpected-but-journaled mutations (serial
            # binds, external pod/node events, TTL expiry) no longer
            # force a rebuild — the anchor stays where the device state
            # is known-good and the next solve's catch-up scatters the
            # journal window on top. Anything the journal can't express
            # still reseeds there.
            pass
        else:
            self._last_seq = -1

    # ------------------------------------------------------------------
    def solve(self, pods: List, warming: bool = False,
              pad_to: Optional[int] = None,
              ) -> Tuple[np.ndarray, EncodedCluster, int]:
        """Solve one batch. Returns (assignments, cluster, seq_before)
        where assignments, a host array, map batch index → node index
        in ``cluster.node_names`` (-1 = unschedulable on device).
        ``warming`` suppresses telemetry (metrics segments, rebuild
        counters) so JIT-compile time stays out of the measured series.
        ``pad_to`` overrides the padded batch shape (the sidecar's
        latency-budget chunking: the scan length — and so the per-batch
        device latency — is the PADDED size, not the real pod count;
        each distinct pad size is its own compiled executable)."""
        self._warming = warming
        pad = pad_to or self.max_batch
        seq_before = self.sched.cache.mutation_seq
        # why this solve cannot stay incremental, once known
        reason = None
        # mirror catch-up: a journaled mutation window since the last
        # validated seq is scattered into the resident planes, making
        # the incremental gate below pass — external churn stops
        # forcing rebuilds. Timed here, booked into the devprof cycle
        # once it opens (the scatter belongs to THIS solve's cycle).
        scatter_stash = None
        if (
            self._mirror is not None and self._state is not None
            and not self._poisoned
            and 0 <= self._last_seq != seq_before
            and self._node_epoch == self.sched.cache.node_set_seq
        ):
            events0 = self._mirror.events_applied
            spread0 = self._mirror.spread_pods
            with self._span("solve.scatter") as sp:
                t_sc = time.monotonic()
                applied = self._mirror.catch_up(self._last_seq, seq_before)
                sc_s = time.monotonic() - t_sc
                sp.set(events=self._mirror.events_applied - events0,
                       spread=self._mirror.spread_pods - spread0,
                       bytes=applied or 0)
            if applied is not None:
                scatter_stash = (sc_s, applied)
                self._last_seq = seq_before
                self._note_rebuild("scatter")
            else:
                reason = self._mirror.last_reason
        if self._state is not None and seq_before == self._last_seq \
                and self._node_epoch == self.sched.cache.node_set_seq:
            dp = get_devprof()
            rec = dp.begin_cycle(
                cycle=self.trace_cycle, pad=pad, real=len(pods),
                warming=warming) if dp.enabled else None
            if not warming:
                self._note_staleness(rec, dp)
            if scatter_stash is not None:
                sc_s, sc_bytes = scatter_stash
                dp.phase("scatter", sc_s)
                if sc_bytes:
                    # the only remaining per-event h2d: index/value
                    # triples. Counted in solver_transfer_bytes_total
                    # (h2d) plus the scatter attribution ledger; never
                    # in the donated ledger.
                    dp.add_bytes("h2d", sc_bytes)
                    dp.add_bytes("scatter", sc_bytes)
                self._observe("scatter", sc_s)
            try:
                t0 = time.monotonic()
                with self._span("solve.encode"):
                    pb = self._encoder.encode_pods_only(pods, pad)
                if pb is not None and pb.requests.shape[1] == \
                        self._cluster.allocatable.shape[1]:
                    self.last_profile_idx = pb.profile_idx
                    self.last_inexpressible = pb.inexpressible
                    t_pack = time.monotonic()
                    with self._span("solve.pack"):
                        ints, floats = pack_podin(pb)
                    t_done = time.monotonic()
                    self._observe("encode", t_pack - t0)
                    self._observe("pack", t_done - t_pack)
                    # devprof attribution: the pod-row delta encode is
                    # the drained pods' h2d prep — inherent per-batch
                    # work, booked under pack. The "encode" phase (and
                    # so encode_share) is reserved for cluster-plane
                    # builds, the stage the device mirror eliminates.
                    dp.phase("pack", t_done - t0)
                    dp.add_bytes("h2d", ints.nbytes + floats.nbytes)
                    t0 = time.monotonic()
                    with self._span("solve.device"):
                        handle, self._state = self._active.solve_lazy(
                            self.params, self._static, self._state,
                            ints, floats
                        )
                    staging = self._take_staging_s()
                    dp.phase("dispatch",
                             max(0.0, time.monotonic() - t0 - staging))
                    if staging:
                        # synchronous host↔device plane staging (the
                        # un-donated arm): the device sat fed-or-idle on
                        # this copy — device wait, not dispatch work
                        dp.phase("block", staging)
                    self._flush_backend_bytes(dp)
                    t_b = time.monotonic()
                    with self._span("solve.block"):
                        out = self._active.materialize(handle)
                    dp.phase("block", time.monotonic() - t_b)
                    dp.add_bytes("d2h", int(getattr(out, "nbytes", 0)))
                    self._observe("device", time.monotonic() - t0)
                    dp.end_cycle(rec)
                    if not self._warming:
                        self.incremental_hits += 1
                    return out, self._cluster, seq_before
                # incremental encode fell through (epoch shape drift):
                # the record describes no solve — drop it rather than
                # pollute the cycle stream with an empty row
                dp.abort(rec)
                reason = "batch_shape"
            except BaseException:
                # encode/solve raised (the sidecar falls back to the
                # serial path): the record describes no completed solve,
                # and leaving it thread-local-active would misattribute
                # later compile events to a dead cycle
                dp.abort(rec)
                raise
        return self._rebuild_and_solve(
            pods, seq_before, pad, reason or self._rebuild_reason())

    def _rebuild_reason(self) -> str:
        """Why a solve that tried no catch-up has to rebuild: the
        reasons that never reach ``DeviceClusterMirror.catch_up``."""
        if self._state is None:
            return "cold"
        if self._poisoned:
            return "poisoned"
        if self._node_epoch != self.sched.cache.node_set_seq:
            return "node_epoch"
        # cache mutations the sidecar did not account for, and no
        # mirror to scatter them (KTPU_MIRROR=off)
        return "no_mirror"

    def _note_rebuild(self, kind: str) -> None:
        """Raise ``cycle_rebuild`` to ``kind`` (warm-ups excluded)."""
        if not self._warming and REBUILD_KINDS.index(kind) > \
                REBUILD_KINDS.index(self.cycle_rebuild):
            self.cycle_rebuild = kind

    def _span(self, name: str, **attrs):
        """A ``solve.*`` span carrying this solve's cycle id; none while
        warming, so compile time stays out of the phase series."""
        if self._warming:
            return NOOP_SPAN
        return get_tracer().span(name, cycle=self.trace_cycle, **attrs)

    def _note_staleness(self, rec, dp) -> None:
        """Snapshot-staleness SLI, once per solve cycle: age of the
        newest watch event reflected in the cache this encoding solves
        against (``SchedulerCache.last_event_ts``, stamped at store
        commit). Sampled only for cycles whose snapshot ADVANCED since
        the previous sample — a backoff-retry cycle over an unchanged
        snapshot (no events exist to reflect) is solving CURRENT truth,
        and counting its ever-growing event age would false-flip the
        staleness SLO during any event lull. Lands in the devprof cycle
        record (→ the bench row's ``freshness`` sub-object), the
        ``snapshot_staleness_seconds`` histogram (→ the SLO engine),
        and a cycle-correlated tracer instant so staleness is
        attributable per pod through the flight recorder."""
        try:
            ts = getattr(self.sched.cache, "last_event_ts", 0.0)
            if not ts or ts == self._staleness_anchor:
                return
            self._staleness_anchor = ts
            stale = max(0.0, time.time() - ts)
            dp.note_staleness(rec, stale)
            from kubernetes_tpu.metrics.freshness_metrics import (
                freshness_metrics,
            )

            fm = freshness_metrics()
            if fm.enabled:
                fm.snapshot_staleness_seconds.observe(stale)
                tracer = get_tracer()
                if tracer.enabled:
                    tracer.event("solve.staleness",
                                 cycle=self.trace_cycle,
                                 staleness_ms=round(stale * 1000, 2))
        except Exception:  # noqa: BLE001 — SLIs must never break solves
            pass

    def _flush_backend_bytes(self, dp, backend=None) -> None:
        """Book a self-accounting backend's pending transfer ledgers
        (real uploads/readbacks as h2d/d2h, donated resident planes in
        the excluded ``donated`` ledger) into the open devprof cycle.
        Called only AFTER a successful solve — the same
        charge-only-after-success rule the generic ``_tree_nbytes``
        accounting follows."""
        take = getattr(backend or self._active, "take_transfer_bytes",
                       None)
        if take is None:
            return
        try:
            for direction, n in take().items():
                if n:
                    dp.add_bytes(direction, int(n))
        except Exception:  # noqa: BLE001 — accounting must never break
            pass

    def _take_staging_s(self, backend=None) -> float:
        """Consume a backend's synchronous host↔device staging seconds
        for the last solve (0.0 for backends without staging — only the
        un-donated sharded arm stages). Defaults to the ACTIVE backend;
        the rebuild passes its routed backend explicitly (``_active``
        is only re-pointed after success). The caller subtracts this
        from its dispatch timing and books it as block: time spent
        feeding the device is device wait."""
        take = getattr(backend or self._active, "take_staging_s", None)
        if take is None:
            return 0.0
        try:
            return float(take())
        except Exception:  # noqa: BLE001 — accounting must never break
            return 0.0

    # inputs whose equality makes the packed STATIC planes bit-identical
    _STATIC_FP_CLUSTER = ("allocatable", "max_pods", "topo_codes")
    _STATIC_FP_BATCH = (
        "static_masks", "static_scores", "sc_key_idx", "sc_max_skew",
        "sc_hard", "sc_domain", "term_key_idx",
    )

    def _static_fingerprint(self, cluster, batch):
        # sv_keys: the shared-volume slot mapping is part of the static
        # identity — a changed slot order re-keys every pod_sv index
        sv_keys = cluster.sv_keys if cluster.sv_keys is not None \
            else np.empty(0, dtype=np.int64)
        return (
            [np.asarray(getattr(cluster, k))
             for k in self._STATIC_FP_CLUSTER]
            + [np.asarray(getattr(batch, k))
               for k in self._STATIC_FP_BATCH]
            + [sv_keys],
            (cluster.resource_names, batch.num_values,
             cluster.num_real_nodes),
        )

    @staticmethod
    def _fingerprints_equal(a, b) -> bool:
        if a is None or b is None or a[1] != b[1]:
            return False
        return all(
            x.shape == y.shape and np.array_equal(x, y)
            for x, y in zip(a[0], b[0])
        )

    def _rebuild_and_solve(self, pods: List, seq_before: int,
                           pad: Optional[int], reason: str):
        if not self._warming:
            self.rebuilds += 1
        self._poisoned = False
        dp = get_devprof()
        rec = dp.begin_cycle(
            cycle=self.trace_cycle, pad=pad or self.max_batch,
            real=len(pods), warming=self._warming,
            rebuild="full") if dp.enabled else None
        if not self._warming:
            self._note_staleness(rec, dp)
        try:
            return self._rebuild_and_solve_inner(
                pods, seq_before, pad, dp, rec, reason)
        except BaseException:
            # the backend raised (or a keyboard interrupt): the record
            # describes no completed solve
            dp.abort(rec)
            raise

    def _rebuild_and_solve_inner(self, pods: List, seq_before: int,
                                 pad: Optional[int], dp, rec, reason: str):
        # for the mirror's reseed accounting: a rebuild with resident
        # state is a re-seed (the mirror failed to keep up); the cold
        # start is just the seed
        mirror_cold = self._state is None
        # the host part of the rebuild (snapshot, encode, pack, plane
        # upload) is one span, its stages nested inside it
        with self._span("solve.rebuild", reason=reason) as rebuild:
            t0 = time.monotonic()
            # captured BEFORE the snapshot refresh: a node-set change
            # that races the rebuild bumps mutation_seq too, so the next
            # solve re-validates either way
            self._node_epoch = self.sched.cache.node_set_seq
            with self._span("solve.snapshot"):
                self.sched.algorithm.update_snapshot()
            with self._span("solve.encode") as enc_span:
                self._encoder = BatchEncoder(
                    self.sched.algorithm.snapshot,
                    pad_nodes=self.pad_nodes,
                    client=getattr(self.sched, "client", None),
                    # sharded encode: split the node-column fill by the
                    # SAME shard boundaries the mesh solve uses, so a
                    # 50k-node plane never serializes on one host thread
                    # before upload
                    node_shards=getattr(self.backend, "encode_shards", 1),
                )
                cluster, batch = self._encoder.encode(
                    pods, pad_pods=pad or self.max_batch
                )
                enc_span.set(count_pods=self._encoder.count_pods,
                             count_sigs=self._encoder.count_sigs)
            self._cluster = cluster
            self._static_masks_host = batch.static_masks
            self.last_profile_idx = batch.profile_idx
            self.last_inexpressible = batch.inexpressible
            t_pack = time.monotonic()
            with self._span("solve.pack"):
                ints, floats = pack_podin(batch)
            t_done = time.monotonic()
            self._observe("encode", t_pack - t0)
            self._observe("pack", t_done - t_pack)
            dp.phase("encode", t_pack - t0)
            dp.phase("pack", t_done - t_pack)
            dp.add_bytes("h2d", ints.nbytes + floats.nbytes)

            # state-only rebuild: when the mutation that invalidated the
            # mirror touched only DYNAMIC state (mass preemption's
            # victim deletions, serial binds), the packed static planes
            # are bit-identical to the resident ones — re-upload just
            # the state planes and keep the device-resident static
            # (halves the per-round host→device traffic on the
            # rebuild-heavy paths)
            fp = self._static_fingerprint(cluster, batch)
            backend = self._route(cluster, batch)
            state_only = (
                self._static is not None
                and self._active is backend
                and hasattr(backend, "prepare_state_only")
                and self._fingerprints_equal(fp, self._static_fp)
            )
            kind = "state_only" if state_only else "full"
            rebuild.set(kind=kind)
            self._note_rebuild(kind)
            t0 = time.monotonic()
            with self._span("solve.prepare"):
                if state_only:
                    if rec is not None:
                        rec["rebuild"] = "state_only"
                    state = backend.prepare_state_only(cluster, batch)
                else:
                    self._static_fp = None
                    self._static, state = backend.prepare(cluster, batch)
        t_disp = time.monotonic()
        with self._span("solve.device"):
            handle, self._state = backend.solve_lazy(
                self.params, self._static, state, ints, floats
            )
        t_block = time.monotonic()
        with self._span("solve.block"):
            out = backend.materialize(handle)
        t_end = time.monotonic()
        staging = self._take_staging_s(backend)
        dp.phase("dispatch", max(0.0, t_block - t_disp - staging))
        dp.phase("block", t_end - t_block + staging)
        # a self-accounting backend (sharded tier) reports its real
        # uploads via the pending-ledger hand-over — _tree_nbytes would
        # count donated device-resident buffers as shipped
        if getattr(backend, "self_accounting", False):
            self._flush_backend_bytes(dp, backend)
        else:
            dp.add_bytes("h2d", _tree_nbytes(state) + (
                0 if state_only else _tree_nbytes(self._static)))
        dp.add_bytes("d2h", int(getattr(out, "nbytes", 0)))
        self._active = backend
        self._static_fp = fp
        if state_only and not self._warming:
            self.state_only_rebuilds += 1
        self._observe("device", t_end - t0)
        dp.end_cycle(rec)
        # valid-until-next-mutation; the sidecar's note_committed refines
        self._last_seq = seq_before
        if self._mirror is not None:
            self._mirror.note_seeded(mirror_cold, self._warming, reason)
        return out, cluster, seq_before

    @property
    def active_backend(self) -> str:
        """Name of the backend that solved the current epoch."""
        return self._active.name

    def _route(self, cluster, batch):
        """Structural routing, decided from the epoch's shapes before
        any solve:

        - more tracked terms than padded nodes overflow the planes
          layout's one-plane totals: the legacy scan;
        - shared-volume epochs need the sv planes: the preferred backend
          when it carries them, else the planes scan;
        - the pallas kernel takes what ``_pallas_fits``; the rest goes
          to the planes scan.
        """
        name = self.backend.name
        if batch.term_counts.shape[0] > cluster.allocatable.shape[0]:
            return self._legacy
        if cluster.sv_attached is not None:
            return self.backend if name in _SV_BACKENDS else self._planes
        if name == "pallas" and not _pallas_fits(batch, cluster,
                                                 self.backend):
            return self._planes
        return self.backend

    @property
    def static_masks_host(self):
        """Host copy of the current epoch's [U, N] static predicate
        masks (None before the first rebuild)."""
        return self._static_masks_host

    def static_mask_for(self, batch_index: int):
        """Host-side static predicate mask ([num_real_nodes] bool) for the
        given pod of the LAST solved batch, or None when unavailable.
        False = the node fails a node-static predicate (selector/affinity,
        nodeName, taints, unschedulable) — UnschedulableAndUnresolvable in
        reference terms; True = only dynamic predicates failed."""
        if (
            self._static_masks_host is None
            or self.last_profile_idx is None
            or self._cluster is None
            or batch_index >= len(self.last_profile_idx)
        ):
            return None
        u = self.last_profile_idx[batch_index]
        return self._static_masks_host[u][: self._cluster.num_real_nodes]

    def _observe(self, segment: str, seconds: float) -> None:
        """One solve phase into ``batch_solve_duration{segment}`` (the
        ``solve.*`` spans carry the same phases on the tracer)."""
        if self._warming:
            return
        try:
            self.sched.metrics.batch_solve_duration.observe(seconds, segment)
        except Exception:  # pragma: no cover — metrics must never break solves
            pass
