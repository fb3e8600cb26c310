"""Device-resident cluster mirror: scatter watch deltas into the planes.

PR 10 made solver state live on device (donated buffers) and PR 12 made
encode a delta pass, but every solve cycle still paid a host-side plane
build plus h2d of node columns whenever anything beyond the sidecar's
own commits touched the cache. This module finishes the thought: the
pod×node planes become a persistent device-resident mirror of the
cluster, and watch-event deltas — pod bind/delete/update, node
capacity changes — are applied by jitted row/column scatter kernels
chained onto the donated state carry instead of re-encoding.

The contract has three parts:

- ``DeltaJournal``: the :class:`SchedulerCache` notes one compact
  ``DeltaRecord`` per ``mutation_seq`` bump (under the cache lock).
  The journal is a bounded ring; a window that is no longer
  contiguous (evicted, or a bump site that predates the journal)
  reads as a gap and forces a reseed — safety never depends on the
  journal being complete.
- ``DeviceClusterMirror.catch_up(lo, hi)``: translates the journaled
  window into exact int32 scatter entries against the RESIDENT
  encoding space (the one the last full encode retained), then
  dispatches them through the active backend's scatter hooks
  (``scatter_state_add`` / ``scatter_static_set``, donated in-place
  updates). Translation is transactional: every record is translated
  host-side first, and ANY record the space cannot express
  bit-exactly returns None — the caller falls back to the full host
  encode + re-seed, which is exactly the ``KTPU_MIRROR=off`` path.
- Expressibility is conservative and arithmetic-exact. A pod delta is
  only scattered when the result is bit-identical to a rebuild:
  the node is in the resident index, the pod matches no tracked
  (anti-)affinity term and owns none, it has no volumes while CSI
  attach columns exist, and its memory/ephemeral requests are
  KiB-aligned (``_kib`` is a ceiling division applied to SUMS at
  rebuild — per-pod deltas are exact only on aligned values).
  Spread constraints are no obstacle: they are tracked from the pods
  being scheduled, so a running pod that owns one registers nothing,
  and a pod a tracked constraint counts (namespace and selector match,
  not terminating) is ±1 on its node's topology domain — an exact add
  on every column of that domain in the constraint's ``sc_counts``
  row. A node without the key counts nowhere, as at rebuild.
  A node update scatters only when old and new differ in nothing but
  ``status.allocatable`` (labels, taints, unschedulable, images all
  equal — anything else touches static masks/scores/topology codes).

Scatter bytes are the only per-event h2d left (indices + values, a
few KiB) and are booked into ``solver_transfer_bytes_total`` as h2d
plus the separate ``scatter`` attribution ledger; they never enter
the donated ledger.
"""

from __future__ import annotations

import logging
import os
import threading
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from kubernetes_tpu.ops.encode import _kib, _resource_row
from kubernetes_tpu.ops.pallas_solver import _state_planes, _static_planes
from kubernetes_tpu.scheduler.types import NodeInfo, PodInfo

_logger = logging.getLogger(__name__)

# journal ring capacity: ~8k mutations of headroom between two solves.
# An event storm that overflows it reads as a gap → one reseed — the
# exact behavior a lost watch connection has always had.
JOURNAL_CAP = 8192


def mirror_enabled() -> bool:
    """KTPU_MIRROR kill switch — default ON; ``off``/``0``/``false``
    selects the PR 12 delta-encode path (the differential reference)."""
    return os.environ.get("KTPU_MIRROR", "on").strip().lower() not in (
        "off", "0", "false",
    )


class DeltaRecord(NamedTuple):
    """One cache mutation, journaled at its ``mutation_seq``."""

    seq: int
    kind: str
    a: object = None
    b: object = None


class DeltaJournal:
    """Bounded ring of cache mutations, written under the cache lock."""

    def __init__(self, cap: int = JOURNAL_CAP):
        self._recs: deque = deque(maxlen=cap)
        self._lock = threading.Lock()

    def note(self, seq: int, kind: str, a=None, b=None) -> None:
        with self._lock:
            self._recs.append(DeltaRecord(seq, kind, a, b))

    def window(self, lo: int, hi: int) -> Optional[List[DeltaRecord]]:
        """Records with lo < seq ≤ hi, or None when the ring no longer
        covers that range contiguously (evicted entries, or a mutation
        bumped by a site the journal does not instrument — both must
        read as 'mirror diverged', never as 'nothing happened')."""
        if hi <= lo:
            return []
        with self._lock:
            recs = [r for r in self._recs if lo < r.seq <= hi]
        if len(recs) != hi - lo or recs[0].seq != lo + 1:
            return None
        return recs


def _pad_pow2(m: int) -> int:
    """Scatter-entry padding bucket (pow2, min 1024): bounds the number
    of distinct compiled scatter shapes, each of which the sidecar's
    warm-up compiles (``warm_scatter``); a padded small catch-up ships
    12 KiB."""
    p = 1024
    while p < m:
        p *= 2
    return p


class DeviceClusterMirror:
    """Owns the catch-up path for one :class:`SolverSession`: journal
    window → exact scatter entries → donated device update."""

    def __init__(self, session, journal: DeltaJournal):
        self._session = session
        self._journal = journal
        # telemetry (the mirror[] diag segment reads these)
        self.events_applied = 0
        # pod deltas expressed through the sc_counts rows
        self.spread_pods = 0
        self.catch_ups = 0
        self.scatter_bytes_total = 0
        self.reseeds = 0   # full rebuilds AFTER the first seed
        self.seeds = 0
        # rebuilds (seed and reseeds) by the reason the session gave
        self.reseed_reasons: Dict[str, int] = {}
        # why the last catch_up returned None (see catch_up), else None
        self.last_reason: Optional[str] = None
        self._node_map: Optional[Dict[str, int]] = None
        # [SC, N] topology code of each plane column per tracked spread
        # constraint (V = the node lacks the key); None with none tracked
        self._sc_codes: Optional[np.ndarray] = None

    # ------------------------------------------------------------------
    def note_seeded(self, cold: bool, warming: bool, reason: str) -> None:
        """Called by the session after every full/state-only rebuild:
        the device planes were just re-seeded from a host encode, so
        the cached node index is stale and — unless this was the cold
        start or a warm-up — the rebuild counts as a mirror reseed.
        ``reason`` is counted in ``reseed_reasons``."""
        self._node_map = None
        self._sc_codes = None
        if warming:
            return
        if cold:
            self.seeds += 1
        else:
            self.reseeds += 1
        self.reseed_reasons[reason] = self.reseed_reasons.get(reason, 0) + 1

    def info(self) -> dict:
        return {
            "events": self.events_applied,
            "spread_pods": self.spread_pods,
            "catch_ups": self.catch_ups,
            "scatter_mb": round(self.scatter_bytes_total / 1e6, 3),
            "reseeds": self.reseeds,
            "reseed_reasons": dict(self.reseed_reasons),
        }

    def warm_scatter(self) -> int:
        """Compile the state add-scatter for every entry bucket
        (``_pad_pow2``) a catch-up against the resident encoding can
        reach, with entries that add nothing, so that no catch-up
        compiles inside a measured run. A window's entries, one per
        target after the host-side combine, are at most the pod rows of
        every node (resource columns, nonzero, pod count) plus every
        column of each tracked spread constraint's row. Returns the
        buckets dispatched (0 without resident state)."""
        sess = self._session
        backend = sess._active
        if sess._state is None or sess._cluster is None or \
                sess._encoder is None or \
                not hasattr(backend, "scatter_state_add"):
            return 0
        enc = sess._encoder
        cluster = sess._cluster
        # a window's pod records (two per update) touch at most this
        # many node columns
        cols = min(2 * JOURNAL_CAP, cluster.num_real_nodes)
        most = cols * (len(enc._resource_names) + 3) \
            + len(enc._constraints or ()) * cluster.allocatable.shape[0]
        buckets = 0
        pad = _pad_pow2(1)
        while pad <= _pad_pow2(most):
            zeros = np.zeros(pad, dtype=np.int32)
            sess._state, _ = backend.scatter_state_add(
                sess._state, zeros, zeros, zeros)
            buckets += 1
            pad *= 2
        return buckets

    def _refuse(self, reason: str) -> None:
        """Record why the window falls back to a full reseed."""
        self.last_reason = reason
        return None

    # ------------------------------------------------------------------
    def catch_up(self, from_seq: int, to_seq: int) -> Optional[int]:
        """Scatter the journaled (from_seq, to_seq] window into the
        resident device planes. Returns the scatter h2d bytes on
        success (0 = the window nets out to nothing), or None when the
        window is inexpressible/gapped — the caller reseeds via the
        full host encode, which is the mirror-off behavior. On None,
        ``last_reason`` names the exit: ``no_scatter``, ``cold``,
        ``journal_gap``, ``translate_error``, ``dispatch_error``, or a
        record's own (``pod_affinity_terms``, ``pod_volumes``,
        ``pod_unaligned``, ``node_unknown``, ``node_set_change``,
        ``node_static_change``, ``external``)."""
        sess = self._session
        backend = sess._active
        self.last_reason = None
        if not hasattr(backend, "scatter_state_add"):
            return self._refuse("no_scatter")
        if (
            sess._state is None
            or sess._static is None
            or sess._cluster is None
            or sess._encoder is None
        ):
            return self._refuse("cold")
        recs = self._journal.window(from_seq, to_seq)
        if recs is None:
            return self._refuse("journal_gap")
        try:
            plan = self._translate(recs)
        except Exception:  # noqa: BLE001 — any doubt → full rebuild
            _logger.exception("mirror delta translation failed; reseed")
            return self._refuse("translate_error")
        if plan is None:
            return None
        adds, spread, spread_pods, sets = plan
        try:
            nbytes = self._dispatch(backend, adds, spread, sets)
        except Exception:  # noqa: BLE001
            # the device update may have half-applied: poison the
            # session AND drop the static fingerprint so the rebuild
            # re-uploads everything
            _logger.exception("mirror scatter dispatch failed; reseed")
            sess.invalidate()
            sess._static_fp = None
            return self._refuse("dispatch_error")
        self.catch_ups += 1
        self.events_applied += len(recs)
        self.spread_pods += spread_pods
        self.scatter_bytes_total += nbytes
        return nbytes

    # ------------------------------------------------------------------
    def _node_index(self) -> Dict[str, int]:
        """name → flat plane column of the RESIDENT encoding (column i
        of every plane is ``cluster.node_names[i]``; the planes-layout
        [C, NB, 128] reshape is row-major, so the flat index is the
        same). Rebuilds invalidate via ``note_seeded``."""
        if self._node_map is None:
            self._node_map = {
                name: i
                for i, name in enumerate(self._session._cluster.node_names)
            }
        return self._node_map

    def _spread_codes(self) -> Optional[np.ndarray]:
        """[SC, N] topology code of every plane column for each tracked
        spread constraint of the RESIDENT encoding, with the sentinel V
        for a node that lacks the key — the ``sc_codes`` the state
        planes were built from. None when no constraint is tracked."""
        enc = self._session._encoder
        if not enc._constraints:
            return None
        if self._sc_codes is None:
            keys = [con.key_idx for con in enc._constraints]
            self._sc_codes = np.minimum(
                self._session._cluster.topo_codes[:, keys].T,
                enc._num_values).astype(np.int32)
        return self._sc_codes

    def _translate(
        self, recs: List[DeltaRecord],
    ) -> Optional[Tuple[list, Optional[np.ndarray], int, dict]]:
        """Journal window → (state add-entries, spread add-entries as an
        [M, 3] array or None, pods those express, static set-entries).
        None = some record cannot be expressed bit-exactly against the
        resident encoding space (``last_reason`` says which)."""
        sess = self._session
        static = sess._static
        do, _ = _state_planes(static.r, static.sc, static.t, static.sv)
        so, _ = _static_planes(static.r, static.sc, static.t, static.u)
        names = sess._encoder._resource_names
        nmap = self._node_index()
        adds: list = []
        sets: dict = {}
        spread = _SpreadDelta(self._spread_codes(),
                              sess._encoder._num_values)
        for rec in recs:
            k = rec.kind
            if k == "assume_bulk":
                # bulk-committed batch pods: the solve already applied
                # them to the device carry — scattering again would
                # double-count
                continue
            if k in ("assume", "pod_add"):
                why = self._pod_delta(adds, spread, rec.a, +1, do, names,
                                      nmap)
            elif k == "pod_del":
                why = self._pod_delta(adds, spread, rec.a, -1, do, names,
                                      nmap)
            elif k in ("pod_update", "pod_move"):
                why = self._pod_delta(adds, spread, rec.a, -1, do, names,
                                      nmap) \
                    or self._pod_delta(adds, spread, rec.b, +1, do, names,
                                       nmap)
            elif k == "node_update":
                why = self._node_set(sets, rec.a, rec.b, so, names, nmap)
            elif k in ("node_add", "node_del"):
                why = "node_set_change"
            else:
                # "external" and unknown kinds: arbitrary host state
                # changed
                why = "external"
            if why is not None:
                return self._refuse(why)
        return adds, spread.entries(do), spread.pods, sets

    def _pod_delta(self, out: list, spread: "_SpreadDelta", pod,
                   sign: int, do, names, nmap) -> Optional[str]:
        """Append (plane row, node col, value) add-entries for one
        pod's contribution to the dynamic planes, and add its sign to
        the domains of the spread constraints that count it. Returns
        None, or the reason the pod forces a reseed."""
        node_name = getattr(pod.spec, "node_name", "") or ""
        if not node_name:
            return None   # unbound pod: no node-plane impact
        col = nmap.get(node_name)
        if col is None:
            return "node_unknown"
        enc = self._session._encoder
        # volumes consume CSI attach-column / shared-volume budget —
        # per-claim set semantics the additive model cannot replay
        if enc._attach_col and getattr(pod.spec, "volumes", None):
            return "pod_volumes"
        # pods owning affinity terms contribute to tracked-term
        # registration and anti-term owner counts
        aff = getattr(pod.spec, "affinity", None)
        if aff is not None and (
            getattr(aff, "pod_affinity", None) is not None
            or getattr(aff, "pod_anti_affinity", None) is not None
        ):
            return "pod_affinity_terms"
        # pods MATCHED by a tracked term land in term_counts and the
        # per-term totals
        for term in (enc._terms or []):
            if term.matches(pod):
                return "pod_affinity_terms"
        pi = PodInfo.of(pod)
        req = pi.resource_request
        nz = pi.non_zero_request
        # _kib is ceil-division applied to SUMS at rebuild; per-pod
        # deltas are exact only on KiB-aligned values
        if req.memory % 1024 or req.ephemeral_storage % 1024 \
                or nz.memory % 1024:
            return "pod_unaligned"
        # scalar resources outside the tracked column set contribute
        # nothing to the planes at rebuild either — no check needed
        row_vals = _resource_row(req, names)
        for j, val in enumerate(row_vals):
            if val:
                out.append((do["requested"] + j, col, sign * val))
        if nz.milli_cpu:
            out.append((do["nonzero"], col, sign * nz.milli_cpu))
        if nz.memory:
            out.append((do["nonzero"] + 1, col, sign * _kib(nz.memory)))
        out.append((do["pod_count"], col, sign))
        # a terminating pod counts toward no spread constraint
        if spread.codes is not None and \
                pod.metadata.deletion_timestamp is None:
            spread.add(enc.matched_constraints(pod), col, sign)
        return None

    def _node_set(self, sets: dict, old, new, so, names,
                  nmap) -> Optional[str]:
        """SET-entries for a node whose old→new change is confined to
        ``status.allocatable`` (the capacity-churn fast path); anything
        touching static masks/scores/topology reseeds. Returns None, or
        the reason for the reseed."""
        if old is None or new is None or old.name != new.name:
            return "node_set_change"
        col = nmap.get(new.name)
        if col is None:
            return "node_unknown"
        # attach-limit columns are derived from CSINode state per
        # driver — a capacity scatter would zero them
        if self._session._encoder._attach_col:
            return "node_static_change"
        if (getattr(old.metadata, "labels", None) or {}) != \
                (getattr(new.metadata, "labels", None) or {}):
            return "node_static_change"
        if bool(getattr(old.spec, "unschedulable", False)) != \
                bool(getattr(new.spec, "unschedulable", False)):
            return "node_static_change"
        if not _seq_equal(getattr(old.spec, "taints", None),
                          getattr(new.spec, "taints", None)):
            return "node_static_change"
        if not _seq_equal(getattr(old.status, "images", None),
                          getattr(new.status, "images", None)):
            return "node_static_change"
        ni = NodeInfo()
        ni.set_node(new)
        for j, val in enumerate(_resource_row(ni.allocatable, names)):
            sets[(so["alloc"] + j, col)] = val
        sets[(so["max_pods"], col)] = \
            ni.allocatable.allowed_pod_number or 1_000_000
        return None

    # ------------------------------------------------------------------
    def _dispatch(self, backend, adds: list, spread: Optional[np.ndarray],
                  sets: dict) -> int:
        """Ship the translated entries through the backend's donated
        scatter hooks; returns the h2d bytes that actually crossed."""
        sess = self._session
        total = 0
        if adds or spread is not None:
            # combine duplicate (row, col) targets host-side (one entry
            # per target keeps the padded bucket small; .at[].add would
            # accumulate duplicates anyway). The spread entries are one
            # per target already, on rows no pod-row entry touches.
            acc: Dict[tuple, int] = {}
            for row, col, val in adds:
                acc[(row, col)] = acc.get((row, col), 0) + val
            items = np.asarray(
                [(rc[0], rc[1], v) for rc, v in acc.items() if v],
                dtype=np.int32).reshape(-1, 3)
            if spread is not None:
                items = np.concatenate([items, spread])
            if len(items):
                rows, cols, vals = _pack_entries(items, pad_with_zero=True)
                sess._state, nb = backend.scatter_state_add(
                    sess._state, rows, cols, vals)
                total += nb
        if sets:
            # last-write-wins dedup already happened (dict); pad by
            # repeating the final entry — a duplicate same-value set is
            # deterministic
            items = [(rc[0], rc[1], v) for rc, v in sets.items()]
            rows, cols, vals = _pack_entries(items, pad_with_zero=False)
            sess._static, nb = backend.scatter_static_set(
                sess._static, rows, cols, vals)
            # the resident static no longer matches the retained
            # fingerprint; the next rebuild must not take the
            # state-only path against a stale identity
            sess._static_fp = None
            total += nb
        return total


class _SpreadDelta:
    """One window's spread count changes: ``domains[ci, code]`` sums
    the signs of the pods constraint ``ci`` counts on nodes of topology
    domain ``code``. ``codes`` is the resident [SC, N] column coding
    (None when no constraint is tracked); the sentinel column V, a node
    without the key, is never written."""

    def __init__(self, codes: Optional[np.ndarray], num_values: int):
        self.codes = codes
        self.sentinel = num_values
        self.domains = None if codes is None else np.zeros(
            (codes.shape[0], num_values + 1), dtype=np.int32)
        self.pods = 0

    def add(self, matched: List[int], col: int, sign: int) -> None:
        if not matched:
            return
        self.pods += 1
        for ci in matched:
            code = self.codes[ci, col]
            if code != self.sentinel:
                self.domains[ci, code] += sign

    def entries(self, do: dict) -> Optional[np.ndarray]:
        """[M, 3] int32 (row, col, val) add-entries: each domain's net
        change on every column of the domain, in its constraint's
        ``sc_counts`` row. None when nothing changed."""
        if self.domains is None or not self.domains.any():
            return None
        per_col = np.take_along_axis(self.domains, self.codes, axis=1)
        ci, col = np.nonzero(per_col)
        return np.stack([do["sc_counts"] + ci, col, per_col[ci, col]],
                        axis=1).astype(np.int32)


def _seq_equal(a, b) -> bool:
    """Structural equality for api-object lists (taints, images):
    dataclass ``__eq__`` compares by value; fall back to repr so an
    identity-only type degrades to 'changed' (reseed), never 'equal'."""
    a = list(a or [])
    b = list(b or [])
    if len(a) != len(b):
        return False
    try:
        if a == b:
            return True
    except Exception:  # noqa: BLE001
        pass
    return repr(a) == repr(b)


def _pack_entries(items, pad_with_zero: bool):
    """(row, col, val) triples, a list or an [M, 3] array → padded
    int32 arrays. Add-scatters pad with (0, 0, 0) (adds nothing);
    set-scatters repeat the last real entry (same-value duplicate set
    is deterministic)."""
    arr = np.asarray(items, dtype=np.int32).reshape(-1, 3)
    m = len(arr)
    fill = np.zeros(3, dtype=np.int32) if pad_with_zero else arr[-1]
    arr = np.concatenate(
        [arr, np.broadcast_to(fill, (_pad_pow2(m) - m, 3))])
    return (np.ascontiguousarray(arr[:, 0]),
            np.ascontiguousarray(arr[:, 1]),
            np.ascontiguousarray(arr[:, 2]))
