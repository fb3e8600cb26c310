"""Multi-chip sharded solver: the node axis distributed over a device mesh.

When 5k nodes x 30k pods exceeds one chip (or one chip's HBM bandwidth
budget), the node axis of every per-node plane shards across devices over
ICI (the moral analog of tensor parallelism; SURVEY.md section 5
"long-context" mapping). Uses the same gather-free per-node planes
representation as the single-chip backends (``ops.pallas_solver``):

- per device: feasibility + scores for the local node shard (dense
  vector ops, no gathers);
- ONE fused ``all_gather`` per pod carries each shard's local best
  (score, lowest candidate global index) together with that candidate's
  topology codes — every shard then resolves the global argmax, the
  lowest-index tie-break (matching ``jnp.argmax``), and the winner-code
  broadcast locally from the gathered [shards, 2+SC+T] row block. This
  replaces the naive pmax(score) + pmin(index) + 2x psum(codes) chain:
  collectives are latency-bound on ICI (the payload is tiny), so the
  sequential-dependency DEPTH per scan step, not bytes, is what the
  mesh pays for;
- per-constraint domain minima via local min + ``pmin`` — emitted only
  when the batch actually carries a hard topology-spread constraint
  (a static property of the encoded batch, so it is a compile-time
  branch): the common no-hard-spread batch runs ONE collective per pod.

A separate 2D phase (``batch`` x ``nodes``) computes the batched static
feasibility counts — the data-parallel analog — before the sequential
commit; both run under one ``shard_map`` jit so XLA schedules ICI
collectives, not host transfers.

``ShardedBackend`` packages all of this behind the ``SolverSession``
backend contract (prepare / solve_lazy / materialize), so the full
workload path — sidecar drain, pipelined commit, mirror-validity
accounting — can run on a device mesh. The jitted solve is cached per
(mesh, params, shape) signature: session rebuilds reuse the compiled
executable as long as the constraint space doesn't change shape.
"""

from __future__ import annotations

import os
import time
from functools import lru_cache, partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from kubernetes_tpu.ops.encode import EncodedBatch, EncodedCluster
from kubernetes_tpu.ops.pallas_solver import (
    LANES,
    _state_planes,
    _static_planes,
    prepare,
)
from kubernetes_tpu.ops.solver import (
    BIG,
    NEG_INF,
    SolverParams,
    pack_podin,
    place_podin,
)


def make_mesh(n_devices: Optional[int] = None, batch_axis: int = 1) -> Mesh:
    """Build a (batch, nodes) mesh. The node axis carries the sharded
    solve; the batch axis parallelizes batched precomputation."""
    devices = jax.devices()
    n = n_devices or len(devices)
    devices = np.asarray(devices[:n]).reshape(batch_axis, n // batch_axis)
    return Mesh(devices, axis_names=("batch", "nodes"))


class SStatic(NamedTuple):
    """Solve-invariant arrays in the sharded planes layout."""

    sc_meta: jnp.ndarray     # [2, SC] int32
    ints: jnp.ndarray        # [C_s, N] int32 — static planes, node-sharded
    f32s: jnp.ndarray        # [U, N] float32
    has_dom: jnp.ndarray     # [U, SC] bool — static domain existence
    # static dims (part of the compile key)
    r: int
    sc: int
    t: int
    u: int
    v: int
    n: int
    # shared-volume attach plane count (0 = none)
    sv: int = 0
    # True iff any encoded spread constraint is hard (DoNotSchedule):
    # compile-time branch — soft-only batches skip the per-pod domain-min
    # pmin collective entirely
    any_hard: bool = True


class SState(NamedTuple):
    """Dynamic state carried across batches: node-sharded planes plus the
    small replicated per-term totals (a node shard can't see the global
    term columns, so totals ride outside the planes)."""

    planes: jnp.ndarray      # [C_d, N] int32
    totals: jnp.ndarray      # [T] int32


def _step(params, dims, so, do, cols, sc_meta, static_l, f32_l, has_dom_r,
          carry, pod):
    """One pod of the sequential commit scan, on this device's node
    shard. Differentially exact vs the single-chip solvers.

    ``dims`` carries three static solve-shape flags beyond the sizes:
    ``shards`` (mesh width), ``any_hard`` (whether the domain-min pmin
    exists at all), and ``collectives`` (False = the timing-ablation
    build: every cross-shard op replaced by a local stand-in of the same
    arithmetic shape, so full-minus-ablated wall time isolates pure
    collective cost — results are garbage, never use for scheduling)."""
    r, sc, t, u, v, shards, any_hard, collectives, sv = dims
    c_req, c_nonzero, c_profile, c_valid, c_pod_sc, c_sc_match, \
        c_match_by, c_own_aff, c_own_anti, c_sv = cols
    state, totals = carry
    row, pref_w = pod
    n_local = static_l.shape[1]
    shard_ix = jax.lax.axis_index("nodes")
    gidx = shard_ix * n_local + jnp.arange(n_local, dtype=jnp.int32)

    node_valid = static_l[so["node_valid"]] > 0
    alloc = static_l[so["alloc"]:so["alloc"] + r]
    sc_codes = static_l[so["sc_codes"]:so["sc_codes"] + sc]
    term_codes = static_l[so["term_codes"]:so["term_codes"] + t]
    sc_missing = sc_codes >= v
    t_missing = term_codes >= v
    max_skew = sc_meta[0]
    hard = sc_meta[1] > 0

    pod_valid = row[c_valid] > 0
    profile = row[c_profile]
    req = row[c_req:c_req + r]
    pod_sc = row[c_pod_sc:c_pod_sc + sc] > 0
    sc_match = row[c_sc_match:c_sc_match + sc] > 0
    match_by = row[c_match_by:c_match_by + t] > 0
    own_aff = row[c_own_aff:c_own_aff + t] > 0
    own_anti = row[c_own_anti:c_own_anti + t] > 0

    requested = state[do["requested"]:do["requested"] + r]
    fit = jnp.all(requested + req[:, None] <= alloc, axis=0)
    fit &= state[do["pod_count"]] < static_l[so["max_pods"]]
    if sv:
        # shared-volume attach demand is CONDITIONAL per node (1 only
        # where this pod's volume isn't attached yet) — entirely LOCAL:
        # the sv planes shard over nodes like every other plane, and
        # the winner update below touches only the chosen node's shard
        sv_planes = state[do["sv_attached"]:do["sv_attached"] + sv]
        sv_slot = row[c_sv]
        sv_col = row[c_sv + 1]
        sv_is_shared = sv_slot < sv
        slot_c = jnp.minimum(sv_slot, sv - 1)
        att = jnp.take(sv_planes, slot_c, axis=0)         # [n_local]
        sv_demand = jnp.where(sv_is_shared, 1 - att, 0)
        col_alloc = jnp.take(alloc, sv_col, axis=0)
        col_req = jnp.take(requested, sv_col, axis=0)
        col_pod = jnp.take(req, sv_col)
        fit &= col_req + col_pod + sv_demand <= col_alloc
    static_ok = static_l[so["masks"] + profile] > 0

    counts = state[do["sc_counts"]:do["sc_counts"] + sc]
    if any_hard:
        # hard-spread feasibility needs the GLOBAL per-domain count
        # minimum; soft-only batches never read it, so the pmin exists
        # only in builds whose batch has a DoNotSchedule constraint
        dom = jax.lax.dynamic_slice_in_dim(
            static_l, so["sc_domain"] + profile * sc, sc, axis=0
        ) > 0
        lmin = jnp.min(jnp.where(dom, counts, BIG), axis=1)
        gmin = jax.lax.pmin(lmin, "nodes") if collectives else lmin
        min_c = jnp.where(has_dom_r[profile], gmin, 0)
        skew = counts + sc_match[:, None].astype(jnp.int32) - min_c[:, None]
        active_hard = pod_sc & hard
        spread_violation = jnp.any(
            active_hard[:, None]
            & ((skew > max_skew[:, None]) | sc_missing),
            axis=0,
        )
    else:
        spread_violation = jnp.zeros(static_l.shape[1], dtype=bool)

    tcounts = state[do["term_counts"]:do["term_counts"] + t]
    towners = state[do["term_owners"]:do["term_owners"] + t]
    existing_anti = jnp.any(match_by[:, None] & (towners > 0), axis=0)
    own_anti_block = jnp.any(own_anti[:, None] & (tcounts > 0), axis=0)
    aff_here = (tcounts > 0) & ~t_missing
    aff_sat = jnp.all(~own_aff[:, None] | aff_here, axis=0)
    no_any = jnp.all(~own_aff | (totals == 0))
    self_all = jnp.all(~own_aff | match_by)
    has_aff = jnp.any(own_aff)
    aff_ok = ~has_aff | aff_sat | (no_any & self_all)

    feasible = (
        node_valid & static_ok & fit & ~spread_violation
        & ~existing_anti & ~own_anti_block & aff_ok & pod_valid
    )

    alloc_cpu = jnp.maximum(alloc[0], 1).astype(jnp.float32)
    alloc_mem = jnp.maximum(alloc[1], 1).astype(jnp.float32)
    nz = state[do["nonzero"]:do["nonzero"] + 2]
    cpu_frac = (nz[0] + row[c_nonzero]).astype(jnp.float32) / alloc_cpu
    mem_frac = (nz[1] + row[c_nonzero + 1]).astype(
        jnp.float32
    ) / alloc_mem
    over = (cpu_frac >= 1.0) | (mem_frac >= 1.0)
    balanced = jnp.where(
        over, 0.0, (1.0 - jnp.abs(cpu_frac - mem_frac)) * 100.0
    )
    least = (
        jnp.clip(1.0 - cpu_frac, 0.0, 1.0)
        + jnp.clip(1.0 - mem_frac, 0.0, 1.0)
    ) * 50.0
    active_soft = pod_sc & ~hard
    soft_counts = jnp.sum(
        jnp.where(active_soft[:, None], counts, 0), axis=0
    ).astype(jnp.float32)
    spread_score = jnp.where(
        jnp.any(active_soft), 100.0 / (1.0 + soft_counts), 0.0
    )
    pref_score = jnp.sum(
        pref_w[:, None] * tcounts.astype(jnp.float32), axis=0
    )
    score = (
        params.balanced_weight * balanced
        + params.least_weight * least
        + params.spread_weight * spread_score
        + params.affinity_weight * pref_score
        + params.static_weight * f32_l[profile]
    )
    score = jnp.where(feasible, score, NEG_INF)

    # fused winner selection: each shard's local best score, its lowest
    # candidate global index at that score, and THAT candidate's
    # topology codes ride one all_gather; the global argmax, the
    # lowest-index tie-break, and the winner-code broadcast then resolve
    # locally on every shard from the [shards, 2+SC+T] block. One
    # latency-bound collective where the naive chain pays four.
    lmax = jnp.max(score)
    lcand = jnp.min(jnp.where(feasible & (score >= lmax), gidx, BIG))
    lone = gidx == lcand
    l_sc = jnp.sum(jnp.where(lone[None], sc_codes, 0), axis=1)
    l_t = jnp.sum(jnp.where(lone[None], term_codes, 0), axis=1)
    # f32 payload is exact: node indices < 2^24, topology codes <= V
    payload = jnp.concatenate([
        jnp.stack([lmax, lcand.astype(jnp.float32)]),
        l_sc.astype(jnp.float32),
        l_t.astype(jnp.float32),
    ])
    if collectives:
        gathered = jax.lax.all_gather(payload, "nodes")  # [S, 2+SC+T]
    else:
        gathered = jnp.tile(payload[None], (shards, 1))
    scores_g = gathered[:, 0]
    gmx = jnp.max(scores_g)
    found = gmx > NEG_INF / 2
    # shards' gidx ranges are disjoint and ordered, so the min over
    # tying shards' candidates IS the global lowest-index winner
    cand_sel = jnp.where(scores_g >= gmx, gathered[:, 1],
                         jnp.float32(BIG))
    wshard = jnp.argmin(cand_sel)
    chosen = cand_sel[wshard].astype(jnp.int32)
    valid = found & pod_valid
    assignment = jnp.where(found, chosen, -1)

    onehot = (gidx == chosen) & valid
    inc = onehot.astype(jnp.int32)
    valid_i = valid.astype(jnp.int32)
    wrow = gathered[wshard]
    sc_code_j = wrow[2:2 + sc].astype(jnp.int32)
    t_code_j = wrow[2 + sc:2 + sc + t].astype(jnp.int32)
    sc_inc = (sc_codes == sc_code_j[:, None]).astype(jnp.int32) \
        * (sc_match.astype(jnp.int32) * valid_i)[:, None]
    t_same = (term_codes == t_code_j[:, None]).astype(jnp.int32)
    t_inc = t_same * (match_by.astype(jnp.int32) * valid_i)[:, None]
    o_inc = t_same * (own_anti.astype(jnp.int32) * valid_i)[:, None]

    new_requested = requested + inc[None] * req[:, None]
    pieces = [
        new_requested,
        nz + inc[None] * row[c_nonzero:c_nonzero + 2][:, None],
        (state[do["pod_count"]] + inc)[None],
        counts + sc_inc,
        tcounts + t_inc,
        towners + o_inc,
    ]
    if sv:
        sv_add = inc * sv_demand
        pieces[0] = new_requested.at[sv_col].add(sv_add)
        shared_i = jnp.where(sv_is_shared, 1, 0)
        pieces.append(sv_planes.at[slot_c].max(inc * shared_i))
    pieces.append(state[do["totals"]][None])
    new_state = jnp.concatenate(pieces)
    new_totals = totals + (
        match_by.astype(jnp.int32) * valid_i * (t_code_j < v)
    )
    return (new_state, new_totals), assignment


def _batched_static_feasibility(so, r, u, c_req, c_profile, static_l,
                                pods_ints_l):
    """2D-parallel precompute: static-mask x fit counts for this
    device's (batch, nodes) tile — the data-parallel analog phase.
    Returns per-pod statically-feasible-node counts (psum over the
    node axis), an unschedulability early-signal."""
    alloc = static_l[so["alloc"]:so["alloc"] + r]       # [R, n_local]
    node_ok = static_l[so["node_valid"]] > 0
    reqs = pods_ints_l[:, c_req:c_req + r]              # [B_local, R]
    fit = jnp.all(
        reqs[:, :, None] <= alloc[None, :, :], axis=1
    )                                                   # [B_local, n_local]
    profiles = pods_ints_l[:, c_profile]
    masks = (
        static_l[so["masks"]:so["masks"] + u] > 0
    )[profiles]                                         # [B_local, n_local]
    both = fit & masks & node_ok[None, :]
    return jax.lax.psum(
        jnp.sum(both.astype(jnp.int32), axis=1), "nodes"
    )


@lru_cache(maxsize=32)
def _build_solve(mesh: Mesh, params: SolverParams, r: int, sc: int, t: int,
                 u: int, v: int, with_counts: bool = True,
                 any_hard: bool = True, collectives: bool = True,
                 sv: int = 0, donate: bool = False):
    """Build (and cache) the jitted shard_map solve for one
    (mesh, params, shape) signature. Session rebuilds within the same
    constraint space reuse the compiled executable. ``with_counts=False``
    drops the batched static-feasibility phase — the session hot path
    doesn't consume it, so it shouldn't pay the [B x n_local] matrix and
    its psum every batch. ``any_hard=False`` (no DoNotSchedule spread
    constraint in the batch) compiles out the per-pod domain-min pmin.
    ``collectives=False`` builds the timing-ablation variant (local
    stand-ins for every cross-shard op; results are garbage).
    ``donate=True`` donates the state planes + totals inputs to XLA
    (aliased into the same-sharded outputs): the carried state lives in
    ONE device buffer per shard across the whole session instead of a
    fresh allocation per cycle — callers must treat the passed-in state
    as consumed (the session replaces its mirror with the returned
    state every solve, so the contract holds by construction; warm
    solves clone first, see ``ShardedBackend.warm_state``)."""
    so, _ = _static_planes(r, sc, t, u)
    do, _ = _state_planes(r, sc, t, sv)
    c_req, c_nonzero, c_profile, c_valid = 0, r, r + 2, r + 3
    c_pod_sc, c_sc_match = r + 4, r + 4 + sc
    c_match_by = r + 4 + 2 * sc
    c_own_aff = r + 4 + 2 * sc + t
    c_own_anti = r + 4 + 2 * sc + 2 * t
    c_sv = r + 4 + 2 * sc + 3 * t
    cols = (c_req, c_nonzero, c_profile, c_valid, c_pod_sc, c_sc_match,
            c_match_by, c_own_aff, c_own_anti, c_sv)
    dims = (r, sc, t, u, v, mesh.shape["nodes"], any_hard, collectives,
            sv)

    node_sharded = P(None, "nodes")

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P(),                 # sc_meta (replicated)
            node_sharded,        # static planes
            node_sharded,        # static f32 planes
            node_sharded,        # state planes
            P(),                 # totals (replicated)
            P(),                 # pod ints (scan stream, replicated)
            P(),                 # pod floats
            # batch-parallel phase input: replicated when the phase is
            # disabled, so the session path carries no batch-axis
            # divisibility constraint on the pad size
            P("batch", None) if with_counts else P(),
            P(),                 # has_dom [U, SC] (replicated)
        ),
        out_specs=(P(), P("batch") if with_counts else P(), node_sharded,
                   P()),
        check_vma=False,
    )
    def run(sc_meta, static_l, f32_l, planes_l, totals_r, ints_r,
            floats_r, pods_batch_i, has_dom_r):
        if with_counts:
            feasible_counts = _batched_static_feasibility(
                so, r, u, c_req, c_profile, static_l, pods_batch_i
            )
        else:
            feasible_counts = jnp.zeros(
                pods_batch_i.shape[0], dtype=jnp.int32
            )
        (new_planes, new_totals), assignments = jax.lax.scan(
            partial(_step, params, dims, so, do, cols, sc_meta, static_l,
                    f32_l, has_dom_r),
            (planes_l, totals_r),
            (ints_r, floats_r),
        )
        return assignments, feasible_counts, new_planes, new_totals

    if donate:
        # planes_l (arg 3) and totals_r (arg 4) alias into new_planes /
        # new_totals: identical shape, dtype and sharding spec, so XLA
        # reuses the input buffers in place
        return jax.jit(run, donate_argnums=(3, 4))
    return jax.jit(run)


def _host_state_planes(cluster: EncodedCluster, batch: EncodedBatch,
                       t: int, sv: int):
    """Host-side [C_d, N] state planes + [T] totals (flat layout)."""
    from kubernetes_tpu.ops.pallas_solver import prepare_state

    pstate = prepare_state(cluster, batch, device=False)
    cd = pstate.planes.shape[0]
    n = pstate.planes.shape[1] * LANES
    do, _ = _state_planes(
        cluster.allocatable.shape[1], batch.sc_counts.shape[0], t, sv)
    planes2 = np.asarray(pstate.planes).reshape(cd, n)
    totals0 = planes2[do["totals"]][:t].copy()  # encoder pads t >= 1
    return planes2, totals0


def _prepare_sharded(cluster: EncodedCluster, batch: EncodedBatch,
                     mesh: Mesh):
    """Pack encoder output into the sharded planes layout, committed
    with NamedSharding placement: node-sharded planes land directly on
    their shard (no reshard at first dispatch), small meta arrays
    replicated."""
    pstatic, pstate = prepare(cluster, batch, device=False)
    r, sc, t, u, v = pstatic.r, pstatic.sc, pstatic.t, pstatic.u, pstatic.v
    n = pstatic.nb * LANES
    shards = mesh.shape["nodes"]
    if n % shards != 0:
        raise ValueError(
            f"padded node count {n} not divisible by mesh nodes axis "
            f"{shards}"
        )
    sv = pstatic.sv
    _, cs = _static_planes(r, sc, t, u)
    do, cd = _state_planes(r, sc, t, sv)
    static2 = np.asarray(pstatic.ints).reshape(cs, n)
    f32s2 = np.asarray(pstatic.f32s).reshape(u, n)
    planes2 = np.asarray(pstate.planes).reshape(cd, n)
    totals0 = planes2[do["totals"]][:t].copy()  # encoder pads t >= 1
    # static per-(profile, constraint) domain existence: hoisted out of
    # the scan so each step needs no pmax collective for it
    has_dom = batch.sc_domain[:, :, :v].any(axis=2)     # [U, SC]
    node_sh = NamedSharding(mesh, P(None, "nodes"))
    rep = NamedSharding(mesh, P())
    put_n = partial(jax.device_put, device=node_sh)
    put_r = partial(jax.device_put, device=rep)
    sstatic = SStatic(
        sc_meta=put_r(np.asarray(pstatic.sc_meta)),
        ints=put_n(static2),
        f32s=put_n(f32s2),
        has_dom=put_r(np.ascontiguousarray(has_dom)),
        r=r, sc=sc, t=t, u=u, v=v, n=n, sv=sv,
        any_hard=bool(np.asarray(batch.sc_hard).any()),
    )
    sstate = SState(planes=put_n(planes2), totals=put_r(totals0))
    return sstatic, sstate


def _tree_nbytes(tree) -> int:
    return sum(int(getattr(leaf, "nbytes", 0))
               for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "nbytes"))


@lru_cache(maxsize=None)
def _scatter_jit(mesh: Mesh, op: str):
    """Jitted donated row/column scatter into node-sharded [C, N]
    planes (the device-resident cluster mirror's update kernel). The
    input plane stack is DONATED — the update is in-place on device —
    and ``out_shardings`` pins the result to the same node-sharded
    placement, so GSPMD routes each (row, col) entry to the shard
    owning that node column; the host ships only the index/value
    triples. Cached per (mesh, op); jit caches per entry-count bucket."""
    sharding = NamedSharding(mesh, P(None, "nodes"))

    def run(planes, rows, cols, vals):
        if op == "add":
            return planes.at[rows, cols].add(vals)
        return planes.at[rows, cols].set(vals)

    return jax.jit(run, donate_argnums=(0,), out_shardings=sharding)


class ShardedBackend:
    """SolverSession backend running the planes scan over a device mesh
    (drop-in next to PallasBackend / XlaPlanesBackend / CppBackend): the
    node axis of every plane is sharded over the mesh's ``nodes`` axis,
    the batched static-feasibility phase over its ``batch`` axis. State
    carries across batches exactly like the single-chip backends — the
    scan's final carry is the next batch's initial state.

    Default-path contract (the sharded-by-default tier of
    ``ops.session.default_backend``):

    - uploads are **NamedSharding-placed**: every static/state plane is
      committed shard-by-shard onto the mesh at prepare time, so the
      jitted solve never pays a reshard at dispatch;
    - the jitted solve **donates** the state planes + totals
      (``donate_argnums``), so the carried state occupies one device
      buffer per shard for the whole session and per-cycle h↔d copies
      of reusable planes disappear. ``donate=False`` (or env
      ``KTPU_SHARDED_DONATE=0``) selects the staging reference arm the
      devscale bench A/Bs against: no device-persistent planes — state
      rides host↔device every cycle (readback + re-upload), the
      conservative no-aliasing pattern whose copy cost donation
      eliminates;
    - the backend **self-accounts** its plane transfer bytes into the
      open devprof cycle (``self_accounting``): real uploads/readbacks
      count as h2d/d2h, while donated device-resident planes count into
      the separate ``donated`` ledger — excluded from
      ``solver_transfer_bytes_total`` so the proof metric never counts
      bytes that never crossed the link."""

    name = "sharded"
    # the session must not _tree_nbytes-charge this backend's prepared
    # pytrees as h2d: the backend accounts its own plane transfers
    # (donated device-resident buffers are NOT uploads). Bytes are
    # handed over via take_transfer_bytes AFTER a successful solve —
    # the session's charge-only-after-success rule.
    self_accounting = True

    def __init__(self, mesh: Optional[Mesh] = None,
                 donate: Optional[bool] = None):
        self.mesh = mesh or make_mesh()
        if donate is None:
            donate = os.environ.get("KTPU_SHARDED_DONATE", "1") != "0"
        self.donate = bool(donate)
        # the encode stage splits its node-column fill by the same
        # shard boundaries the mesh uses (ops.encode node_shards)
        self.encode_shards = int(self.mesh.shape["nodes"])
        # synchronous host↔device staging seconds of the last solve
        # (the donate=False arm): the session re-attributes this from
        # its dispatch timing into the block phase — time the pipeline
        # spent feeding the device is device wait, not dispatch work
        self._staging_s = 0.0
        # transfer ledgers pending hand-over to the session:
        # epoch-level (prepare's plane uploads — overwritten by the
        # next prepare, so a failed solve can't leak them into a later
        # cycle) and per-cycle (solve_lazy's donated/staging bytes —
        # reset at the top of every solve)
        self._epoch_bytes: dict = {}
        self._cycle_bytes: dict = {}

    def _node_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(None, "nodes"))

    def _replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def take_transfer_bytes(self) -> dict:
        """Consume the pending transfer ledgers (direction → bytes).
        The session calls this after a SUCCESSFUL solve and books the
        result into the open devprof cycle; on failure nothing is
        taken and the next prepare/solve resets the ledgers."""
        out: dict = dict(self._epoch_bytes)
        for k, v in self._cycle_bytes.items():
            out[k] = out.get(k, 0) + v
        self._epoch_bytes = {}
        self._cycle_bytes = {}
        return out

    def prepare(self, cluster, batch):
        sstatic, sstate = _prepare_sharded(cluster, batch, self.mesh)
        # NamedSharding-placed uploads are REAL transfers; pending
        # until the solve succeeds (overwrite: one prepare per epoch)
        self._epoch_bytes = {
            "h2d": _tree_nbytes(sstatic) + _tree_nbytes(sstate)}
        return sstatic, sstate

    def prepare_state_only(self, cluster, batch):
        """State-only rebuild (static planes bit-identical to the
        resident ones): re-upload just the dynamic planes, NamedSharding
        placed like the full prepare."""
        # shapes must match the resident static or the session's
        # fingerprint check would not have routed here
        t = batch.term_counts.shape[0]
        sv = 0 if cluster.sv_attached is None else \
            cluster.sv_attached.shape[0]
        planes2, totals0 = _host_state_planes(cluster, batch, t, sv)
        if planes2.shape[1] % self.mesh.shape["nodes"] != 0:
            raise ValueError("padded node count not divisible by mesh")
        state = SState(
            planes=jax.device_put(planes2, self._node_sharding()),
            totals=jax.device_put(totals0, self._replicated()),
        )
        self._epoch_bytes = {"h2d": _tree_nbytes(state)}
        return state

    def warm_state(self, sstate: SState) -> SState:
        """The carried state as a solve may take it while ``sstate``
        stays valid (warm solves, the session's kept carry): the donated
        executable CONSUMES its state inputs, so it gets a device-side
        copy, on the same shardings and dispatched without a host sync.
        The staging arm consumes nothing: the state itself."""
        if not self.donate:
            return sstate
        return SState(planes=sstate.planes.copy(),
                      totals=sstate.totals.copy())

    def take_staging_s(self) -> float:
        """Consume the synchronous staging seconds of the last solve
        (0.0 on the donated path). The session moves this out of its
        dispatch measurement into the block phase."""
        s, self._staging_s = self._staging_s, 0.0
        return s

    # -------- device-resident mirror scatter hooks (ops.mirror)
    def scatter_state_add(self, sstate: SState, rows, cols, vals):
        """Add (row, col, val) deltas into the donated dynamic planes;
        returns (new state, h2d bytes). Only the index/value triples
        cross the link — the planes stay resident."""
        fn = _scatter_jit(self.mesh, "add")
        with self.mesh:
            planes = fn(sstate.planes, rows, cols, vals)
        return (SState(planes=planes, totals=sstate.totals),
                int(rows.nbytes + cols.nbytes + vals.nbytes))

    def scatter_static_set(self, sstatic: SStatic, rows, cols, vals):
        """Set absolute values (node capacity updates) into the donated
        static int planes; returns (new static, h2d bytes)."""
        fn = _scatter_jit(self.mesh, "set")
        with self.mesh:
            ints = fn(sstatic.ints, rows, cols, vals)
        return (sstatic._replace(ints=ints),
                int(rows.nbytes + cols.nbytes + vals.nbytes))

    def solve_lazy(self, params, sstatic, sstate, pod_ints, pod_floats,
                   donate: Optional[bool] = None):
        donate = self.donate if donate is None else donate
        run = _build_solve(self.mesh, params, sstatic.r, sstatic.sc,
                           sstatic.t, sstatic.u, sstatic.v,
                           with_counts=False, any_hard=sstatic.any_hard,
                           sv=sstatic.sv, donate=donate)
        rep = self._replicated()
        ints, floats = place_podin(pod_ints, pod_floats, sharding=rep)
        # per-cycle ledgers start fresh: a FAILED earlier solve (a warm
        # abort) must not leak its staging seconds or byte counts into
        # this cycle's attribution
        self._cycle_bytes = {}
        self._staging_s = 0.0
        planes, totals = sstate.planes, sstate.totals
        plane_bytes = int(planes.nbytes) + int(totals.nbytes)
        if donate:
            # device-persistent donated planes: nothing crosses the
            # link this cycle — record what WOULD have shipped in the
            # separate donated ledger (excluded from transfer totals)
            self._cycle_bytes["donated"] = plane_bytes
        else:
            # staging arm ("before" reference): no device-persistent
            # state — read the carried planes back and re-upload them,
            # the per-cycle h↔d copy of reusable planes that donation
            # removes. Synchronous feed time is device wait, so it is
            # handed to the session via take_staging_s for the block
            # phase. (The readback copies a long-finished buffer — the
            # previous cycle's solve completed before its commit — so
            # this does not serialize the pipeline.)
            t0 = time.monotonic()
            planes_host = np.asarray(planes)
            totals_host = np.asarray(totals)
            planes = jax.device_put(planes_host, self._node_sharding())
            totals = jax.device_put(totals_host, rep)
            jax.block_until_ready((planes, totals))
            self._staging_s += time.monotonic() - t0
            self._cycle_bytes["d2h"] = plane_bytes
            self._cycle_bytes["h2d"] = plane_bytes
        with self.mesh:
            assignments, _counts, new_planes, new_totals = run(
                sstatic.sc_meta, sstatic.ints, sstatic.f32s, planes,
                totals, ints, floats, ints, sstatic.has_dom,
            )
        return assignments, SState(planes=new_planes, totals=new_totals)

    @staticmethod
    def materialize(handle):
        return np.asarray(handle)

    def solve(self, params, sstatic, sstate, pod_ints, pod_floats):
        h, state = self.solve_lazy(params, sstatic, sstate, pod_ints,
                                   pod_floats)
        return self.materialize(h), state


def solve_scan_sharded(
    cluster: EncodedCluster,
    batch: EncodedBatch,
    mesh: Mesh,
    params: SolverParams = SolverParams(),
):
    """Sharded solve over `mesh` (axes ("batch","nodes")). Returns
    (assignments [B] int32 global node indices, feasible_counts [B]).
    Matches the single-chip solvers exactly (differential tests)."""
    sstatic, sstate = _prepare_sharded(cluster, batch, mesh)
    run = _build_solve(mesh, params, sstatic.r, sstatic.sc, sstatic.t,
                       sstatic.u, sstatic.v, any_hard=sstatic.any_hard,
                       sv=sstatic.sv)
    pod_ints, pod_floats = pack_podin(batch)
    b_axis = mesh.shape["batch"]
    if pod_ints.shape[0] % b_axis != 0:
        raise ValueError(
            f"padded batch size {pod_ints.shape[0]} not divisible by mesh "
            f"batch axis {b_axis}"
        )
    ints = jnp.asarray(pod_ints)
    floats = jnp.asarray(pod_floats)
    with mesh:
        assignments, feasible_counts, _, _ = run(
            sstatic.sc_meta, sstatic.ints, sstatic.f32s, sstate.planes,
            sstate.totals, ints, floats, ints, sstatic.has_dom,
        )
    return np.asarray(assignments), np.asarray(feasible_counts)
