"""Device assignment solvers.

``solve_scan`` is the serial-equivalent batch solver: one ``lax.scan`` step
per pod (in queue priority order), each step evaluating EVERY node with
dense vector ops — feasibility (capacity fit, pod-count cap, topology-skew,
(anti-)affinity domain counts, static predicate masks) and scores
(balanced/least allocation, spread, preferred affinity, static) — then
committing the argmax and updating capacity/count state with one-hot adds.

This replaces the reference's hot path 1:1: a scan step IS one
``scheduleOne`` cycle (SURVEY.md section 3.2), except the per-node work the
reference fans out over 16 goroutines with adaptive sampling
(``generic_scheduler.go:179-199``) runs as full-width vector ops — all
nodes, no sampling. Intra-batch interactions (pod A consuming capacity,
shifting topology counts for pod B) are exact by construction, which is the
"hard part (2)" called out in SURVEY.md section 7.

Everything is static-shaped (pods and nodes padded to buckets), int32/f32,
with no data-dependent Python control flow — one XLA compilation per
(bucket-shape) signature, reused across batches.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.ops.encode import EncodedBatch, EncodedCluster

NEG_INF = -1e30
BIG = np.int32(2**30)


@dataclass(frozen=True)
class SolverParams:
    """Score weights mirroring the default provider's plugin weights
    (provider.py): balanced 1, least-allocated 1, topology-spread 2,
    inter-pod affinity 1. Spread/affinity device scores are rank-equivalent
    to the host's min-max normalized forms (monotone in the same counts)."""

    balanced_weight: float = 1.0
    least_weight: float = 1.0
    spread_weight: float = 2.0
    affinity_weight: float = 1.0
    static_weight: float = 1.0


class _State(NamedTuple):
    requested: jnp.ndarray          # [N, R] int32
    nonzero_requested: jnp.ndarray  # [N, 2] int32
    pod_count: jnp.ndarray          # [N] int32
    sc_counts: jnp.ndarray          # [SC, V+1] int32
    term_counts: jnp.ndarray        # [T, V+1] int32
    term_owners: jnp.ndarray        # [T, V+1] int32


class _PodIn(NamedTuple):
    request: jnp.ndarray        # [R]
    nonzero_request: jnp.ndarray  # [2]
    profile: jnp.ndarray        # scalar int32
    valid: jnp.ndarray          # scalar bool (real & expressible)
    pod_sc: jnp.ndarray         # [SC] bool
    pod_sc_match: jnp.ndarray   # [SC] bool
    match_by: jnp.ndarray       # [T] bool
    own_aff: jnp.ndarray        # [T] bool
    own_anti: jnp.ndarray       # [T] bool
    pref_weight: jnp.ndarray    # [T] f32


class _Static(NamedTuple):
    allocatable: jnp.ndarray     # [N, R]
    max_pods: jnp.ndarray        # [N]
    static_masks: jnp.ndarray    # [U, N] bool
    static_scores: jnp.ndarray   # [U, N] f32
    sc_codes: jnp.ndarray        # [SC, N] int32 (V = missing)
    sc_max_skew: jnp.ndarray     # [SC]
    sc_hard: jnp.ndarray         # [SC] bool
    sc_domain: jnp.ndarray       # [U, SC, V+1] bool
    term_codes: jnp.ndarray      # [T, N] int32
    node_valid: jnp.ndarray      # [N] bool


def _step(static: _Static, params: SolverParams, state: _State, pod: _PodIn):
    n = static.allocatable.shape[0]
    v = state.sc_counts.shape[1] - 1

    # ---- feasibility --------------------------------------------------
    fit = jnp.all(
        state.requested + pod.request[None, :] <= static.allocatable, axis=1
    )
    fit &= state.pod_count < static.max_pods
    static_ok = static.static_masks[pod.profile]

    # topology spread (hard constraints)
    counts_at = jnp.take_along_axis(state.sc_counts, static.sc_codes, axis=1)  # [SC, N]
    domain = static.sc_domain[pod.profile]                                     # [SC, V+1]
    min_c = jnp.min(
        jnp.where(domain[:, :v], state.sc_counts[:, :v], BIG), axis=1
    )
    min_c = jnp.where(jnp.any(domain[:, :v], axis=1), min_c, 0)
    skew = counts_at + pod.pod_sc_match[:, None].astype(jnp.int32) - min_c[:, None]
    missing = static.sc_codes >= v
    active_hard = pod.pod_sc & static.sc_hard
    spread_violation = jnp.any(
        active_hard[:, None] & ((skew > static.sc_max_skew[:, None]) | missing),
        axis=0,
    )

    # inter-pod affinity
    tcounts_at = jnp.take_along_axis(state.term_counts, static.term_codes, axis=1)  # [T, N]
    towners_at = jnp.take_along_axis(state.term_owners, static.term_codes, axis=1)
    t_missing = static.term_codes >= v
    existing_anti_block = jnp.any(
        pod.match_by[:, None] & (towners_at > 0), axis=0
    )
    own_anti_block = jnp.any(pod.own_anti[:, None] & (tcounts_at > 0), axis=0)
    aff_here = (tcounts_at > 0) & ~t_missing
    aff_sat = jnp.all(~pod.own_aff[:, None] | aff_here, axis=0)
    # first-pod-of-group special case (filtering.go): no matches anywhere
    # for ANY of its terms and the pod matches its own terms
    totals = jnp.sum(state.term_counts[:, :v], axis=1)
    no_any = jnp.all(~pod.own_aff | (totals == 0))
    self_all = jnp.all(~pod.own_aff | pod.match_by)
    has_aff = jnp.any(pod.own_aff)
    aff_ok = jnp.where(has_aff, aff_sat | (no_any & self_all), True)

    feasible = (
        static.node_valid
        & static_ok
        & fit
        & ~spread_violation
        & ~existing_anti_block
        & ~own_anti_block
        & aff_ok
        & pod.valid
    )

    # ---- scores -------------------------------------------------------
    alloc_cpu = jnp.maximum(static.allocatable[:, 0], 1).astype(jnp.float32)
    alloc_mem = jnp.maximum(static.allocatable[:, 1], 1).astype(jnp.float32)
    cpu_frac = (
        state.nonzero_requested[:, 0] + pod.nonzero_request[0]
    ).astype(jnp.float32) / alloc_cpu
    mem_frac = (
        state.nonzero_requested[:, 1] + pod.nonzero_request[1]
    ).astype(jnp.float32) / alloc_mem
    over = (cpu_frac >= 1.0) | (mem_frac >= 1.0)
    balanced = jnp.where(over, 0.0, (1.0 - jnp.abs(cpu_frac - mem_frac)) * 100.0)
    least = (
        jnp.clip(1.0 - cpu_frac, 0.0, 1.0) + jnp.clip(1.0 - mem_frac, 0.0, 1.0)
    ) * 50.0

    active_soft = pod.pod_sc & ~static.sc_hard
    soft_counts = jnp.sum(
        jnp.where(active_soft[:, None], counts_at, 0), axis=0
    ).astype(jnp.float32)
    spread_score = 100.0 / (1.0 + soft_counts)
    has_soft = jnp.any(active_soft)
    spread_score = jnp.where(has_soft, spread_score, 0.0)

    pref_score = jnp.sum(
        pod.pref_weight[:, None] * tcounts_at.astype(jnp.float32), axis=0
    )

    score = (
        params.balanced_weight * balanced
        + params.least_weight * least
        + params.spread_weight * spread_score
        + params.affinity_weight * pref_score
        + params.static_weight * static.static_scores[pod.profile]
    )
    score = jnp.where(feasible, score, NEG_INF)

    best = jnp.argmax(score)
    found = jnp.any(feasible)
    chosen = jnp.where(found, best, -1)
    valid = found & pod.valid

    # ---- commit (one-hot updates) ------------------------------------
    onehot = (jnp.arange(n) == chosen) & valid
    inc = onehot.astype(jnp.int32)
    new_state = _State(
        requested=state.requested + inc[:, None] * pod.request[None, :],
        nonzero_requested=state.nonzero_requested
        + inc[:, None] * pod.nonzero_request[None, :],
        pod_count=state.pod_count + inc,
        sc_counts=state.sc_counts.at[
            jnp.arange(state.sc_counts.shape[0]),
            static.sc_codes[:, jnp.maximum(chosen, 0)],
        ].add((pod.pod_sc_match & valid).astype(jnp.int32)),
        term_counts=state.term_counts.at[
            jnp.arange(state.term_counts.shape[0]),
            static.term_codes[:, jnp.maximum(chosen, 0)],
        ].add((pod.match_by & valid).astype(jnp.int32)),
        term_owners=state.term_owners.at[
            jnp.arange(state.term_owners.shape[0]),
            static.term_codes[:, jnp.maximum(chosen, 0)],
        ].add((pod.own_anti & valid).astype(jnp.int32)),
    )
    return new_state, chosen


def build_static(cluster: EncodedCluster, batch: EncodedBatch,
                 device: bool = False) -> _Static:
    """Assemble the solve-invariant arrays (static across batches of one
    session). With ``device=True`` they are committed to the default
    device immediately so later jit calls skip the host→device transfer."""
    n = cluster.allocatable.shape[0]
    v = batch.num_values
    sc_codes = np.minimum(
        cluster.topo_codes[:, batch.sc_key_idx].T, v
    ).astype(np.int32)
    term_codes = np.minimum(
        cluster.topo_codes[:, batch.term_key_idx].T, v
    ).astype(np.int32)
    node_valid = np.zeros(n, dtype=bool)
    node_valid[: cluster.num_real_nodes] = True
    static = _Static(
        allocatable=cluster.allocatable,
        max_pods=cluster.max_pods,
        static_masks=batch.static_masks,
        static_scores=batch.static_scores,
        sc_codes=sc_codes,
        sc_max_skew=batch.sc_max_skew,
        sc_hard=batch.sc_hard,
        sc_domain=batch.sc_domain,
        term_codes=term_codes,
        node_valid=node_valid,
    )
    # one batched transfer (see pack_podin on per-call latency)
    return jax.device_put(static) if device else \
        jax.tree.map(jnp.asarray, static)


def build_state(cluster: EncodedCluster, batch: EncodedBatch,
                device: bool = False) -> _State:
    state = _State(
        requested=cluster.requested,
        nonzero_requested=cluster.nonzero_requested,
        pod_count=cluster.pod_count,
        sc_counts=batch.sc_counts,
        term_counts=batch.term_counts,
        term_owners=batch.term_owners,
    )
    return jax.device_put(state) if device else \
        jax.tree.map(jnp.asarray, state)


def pack_podin(batch) -> Tuple[np.ndarray, np.ndarray]:
    """Pack the pod stream into TWO host arrays (one int32, one f32).
    Every device buffer upload pays a host↔device round trip, so
    shipping ten small arrays costs more than two packed buffers.
    Unpacked on device by ``_unpack_podin`` (slicing fuses for free).
    Timed by the CALLER (SolverSession observes the ``pack`` phase):
    warming solves must stay out of the measured series, and only the
    session knows whether a solve is warming."""
    b = batch.requests.shape[0]
    valid = np.zeros(b, dtype=bool)
    valid[: batch.num_real_pods] = True
    valid &= ~batch.inexpressible
    cols = [
        batch.requests,
        batch.nonzero_requests,
        batch.profile_idx.reshape(b, 1),
        valid.reshape(b, 1).astype(np.int32),
        batch.pod_sc.astype(np.int32),
        batch.pod_sc_match.astype(np.int32),
        batch.match_by.astype(np.int32),
        batch.own_aff.astype(np.int32),
        batch.own_anti.astype(np.int32),
    ]
    pod_sv = getattr(batch, "pod_sv", None)
    if pod_sv is not None:
        # shared-volume epochs append (slot, attach column) — absent
        # otherwise, so non-sv workloads keep their compiled shapes
        cols.append(pod_sv)
    ints = np.concatenate(cols, axis=1, dtype=np.int32)
    return ints, np.asarray(batch.pref_weight, dtype=np.float32)


def place_podin(ints: np.ndarray, floats: np.ndarray, sharding=None):
    """Commit the packed pod stream to device. With ``sharding`` (the
    mesh tier passes its replicated NamedSharding) the two buffers are
    PLACED in one step, so the jitted shard_map solve reads them where
    they landed instead of resharding from the default device at every
    dispatch — the pod-stream half of the NamedSharding-placed-uploads
    contract (the plane half lives in ``parallel/sharded.py``)."""
    if sharding is None:
        return jnp.asarray(ints), jnp.asarray(floats)
    import jax as _jax

    return (_jax.device_put(np.asarray(ints), sharding),
            _jax.device_put(np.asarray(floats), sharding))


def _unpack_podin(ints: jnp.ndarray, floats: jnp.ndarray,
                  r: int, sc: int, t: int) -> _PodIn:
    """Device-side inverse of ``pack_podin`` (column widths are static,
    derived from the static arrays' shapes)."""
    # slice clamping would silently misalign fields on a width mismatch;
    # keep the loud failure the per-array path used to give
    if ints.shape[1] != r + 4 + 2 * sc + 3 * t:
        raise ValueError(
            f"packed pod stream width {ints.shape[1]} does not match the "
            f"static constraint space (r={r}, sc={sc}, t={t})"
        )
    o = 0
    request = ints[:, o:o + r]; o += r
    nonzero = ints[:, o:o + 2]; o += 2
    profile = ints[:, o]; o += 1
    valid = ints[:, o] != 0; o += 1
    pod_sc = ints[:, o:o + sc] != 0; o += sc
    pod_sc_match = ints[:, o:o + sc] != 0; o += sc
    match_by = ints[:, o:o + t] != 0; o += t
    own_aff = ints[:, o:o + t] != 0; o += t
    own_anti = ints[:, o:o + t] != 0; o += t
    return _PodIn(
        request=request,
        nonzero_request=nonzero,
        profile=profile,
        valid=valid,
        pod_sc=pod_sc,
        pod_sc_match=pod_sc_match,
        match_by=match_by,
        own_aff=own_aff,
        own_anti=own_anti,
        pref_weight=floats,
    )


@partial(jax.jit, static_argnames=("params",))
def _solve_packed(static: _Static, state: _State, pod_ints, pod_floats,
                  params: SolverParams):
    pods = _unpack_podin(
        pod_ints, pod_floats,
        static.allocatable.shape[1],
        static.sc_codes.shape[0],
        static.term_codes.shape[0],
    )
    final_state, assignments = jax.lax.scan(
        partial(_step, static, params), state, pods
    )
    return final_state, assignments


def solve_scan(
    cluster: EncodedCluster, batch: EncodedBatch,
    params: SolverParams = SolverParams(),
):
    """Run the scan solver. Returns (assignments [B] int32 node indices,
    -1 = unschedulable/fallback)."""
    static = build_static(cluster, batch)
    state = build_state(cluster, batch)
    ints, floats = pack_podin(batch)
    _, assignments = _solve_packed(static, state, ints, floats, params)
    return np.asarray(assignments)


# ---------------------------------------------------------------------------
# what-if solves (the cluster autoscaler's virtual-column hook)

# keeps a column feasible but strictly below every real-node score: the
# scan only spills onto a penalized column when NO unpenalized node is
# feasible — exactly the "would a new node help" question. Real scores
# are O(hundreds) (balanced/least ≤ 200, spread ≤ 100, static small),
# so one tier of 1e6 cleanly separates real < upcoming < virtual.
VIRTUAL_NODE_PENALTY = np.float32(1.0e6)


def solve_whatif(
    cluster: EncodedCluster, batch: EncodedBatch,
    params: SolverParams = SolverParams(),
    deprioritized_cols=(),
    disabled_cols=(),
):
    """Scan solve with per-column overrides, for autoscaler what-ifs:

    - ``deprioritized_cols``: node columns (e.g. the K appended virtual
      template nodes, or still-booting "upcoming" nodes) whose static
      score is pushed down by ``VIRTUAL_NODE_PENALTY`` — a mapping
      ``{col: penalty}`` applies per-column tiers (upcoming nodes get a
      smaller penalty than hypothetical ones, so pods prefer capacity
      that is already paid for);
    - ``disabled_cols``: node columns removed from the solve entirely
      (the scale-down "do its pods fit elsewhere" question).

    Returns ``(assignments [num_real_pods], per-node assigned counts
    [N])``. The batch-wide scan IS the estimator: one solve answers the
    question for every pending pod at once, replacing the reference
    cluster-autoscaler's one-pod-at-a-time scheduler simulation.
    """
    static = build_static(cluster, batch)
    n = cluster.allocatable.shape[0]
    if len(deprioritized_cols):
        scores = np.array(batch.static_scores, dtype=np.float32, copy=True)
        if hasattr(deprioritized_cols, "items"):
            for col, penalty in deprioritized_cols.items():
                scores[:, int(col)] -= np.float32(penalty)
        else:
            cols = np.asarray(list(deprioritized_cols), dtype=np.int64)
            scores[:, cols] -= VIRTUAL_NODE_PENALTY
        static = static._replace(static_scores=jnp.asarray(scores))
    if len(disabled_cols):
        node_valid = np.zeros(n, dtype=bool)
        node_valid[: cluster.num_real_nodes] = True
        node_valid[np.asarray(list(disabled_cols), dtype=np.int64)] = False
        static = static._replace(node_valid=jnp.asarray(node_valid))
    state = build_state(cluster, batch)
    ints, floats = pack_podin(batch)
    _, assignments = _solve_packed(static, state, ints, floats, params)
    a = np.asarray(assignments)[: batch.num_real_pods]
    counts = np.bincount(a[a >= 0], minlength=n)
    return a, counts
