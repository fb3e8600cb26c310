"""Pallas TPU kernel for the batch scheduling solve.

The XLA ``lax.scan`` path (``ops.solver``) pays per-step dispatch and
HBM round-trips for every pod: each scan iteration re-reads and
re-writes the full cluster state from HBM. This kernel runs the WHOLE
pod loop inside one ``pallas_call`` with the cluster state resident in
VMEM, so a pod step touches on-chip memory only (~100KB of state), and
the per-pod cost drops from ~100µs to single-digit µs.

Key design points (see ``/opt/skills/guides/pallas_guide.md``):

- **Node-axis layout**: every per-node array is shaped ``[.., NB, 128]``
  (``NB = N/128`` sublane groups × 128 lanes) so elementwise work runs
  full-width on the VPU.
- **No gathers**: the scan path's ``take_along_axis`` (counts per
  topology value, indexed by each node's value code) is a gather — slow
  or unsupported in Mosaic. Instead the kernel keeps topology counts
  PER NODE (``counts_node[sc, n]`` = matching pods in node *n*'s domain
  value). A commit to node *j* updates all nodes in *j*'s domain with
  one vector compare (``codes[sc] == code_j``), which is exactly the
  domain-value increment of the reference semantics
  (``podtopologyspread/filtering.go:313-324``) — duplicated per member
  node, which min/compare reductions are insensitive to.
- **State carry**: dynamic state buffers are aliased input→output
  (``input_output_aliases``), so the session keeps them on device
  between batches, like the XLA path's carried ``_State``.
- Semantics mirror ``ops.solver._step`` one-to-one; the differential
  tests assert equal assignments against ``solve_scan``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.ops.encode import EncodedBatch, EncodedCluster
from kubernetes_tpu.ops.solver import NEG_INF, SolverParams

LANES = 128
POD_SUB = 8   # pods per grid step (SMEM sublane tiling)
BIG_I32 = np.int32(2**30)


class PStatic(NamedTuple):
    """Solve-invariant arrays in kernel layout (device-resident)."""

    ints: jnp.ndarray        # [C_s, NB, 128] int32 — stacked int planes
    f32s: jnp.ndarray        # [U, NB, 128] float32 — static scores
    sc_meta: jnp.ndarray     # [2, SC] int32 (SMEM): max_skew row, hard row
    # static dims (Python ints — part of the compile key)
    r: int
    sc: int
    t: int
    u: int
    v: int
    nb: int
    sv: int = 0   # shared-volume attach plane count (0 = no sv planes)


class PState(NamedTuple):
    """Dynamic state in kernel layout: ONE stacked int32 array so the
    carry is a single device buffer. Plane order:
    requested[R] | nonzero[2] | pod_count | sc_counts[SC] |
    term_counts[T] | term_owners[T] | term_totals (lane t = total)."""

    planes: jnp.ndarray      # [C_d, NB, 128] int32


def _static_planes(r: int, sc: int, t: int, u: int):
    """Plane offsets inside PStatic.ints."""
    o = {}
    i = 0
    o["alloc"] = i; i += r
    o["max_pods"] = i; i += 1
    o["masks"] = i; i += u          # static predicate masks (0/1)
    o["sc_codes"] = i; i += sc
    o["sc_domain"] = i; i += u * sc  # per-profile eligible-domain masks
    o["term_codes"] = i; i += t
    o["node_valid"] = i; i += 1
    return o, i


def _state_planes(r: int, sc: int, t: int, sv: int = 0):
    o = {}
    i = 0
    o["requested"] = i; i += r
    o["nonzero"] = i; i += 2
    o["pod_count"] = i; i += 1
    o["sc_counts"] = i; i += sc
    o["term_counts"] = i; i += t
    o["term_owners"] = i; i += t
    # shared-volume attach planes (0/1 per node), sv = 0 for epochs
    # without shared CSI volumes — the layout is then bit-identical to
    # the pre-sv contract and no executable recompiles
    o["sv_attached"] = i; i += sv
    o["totals"] = i; i += 1          # lane t holds term t's real-column total
    return o, i


def _to_planes(arr: np.ndarray, nb: int) -> np.ndarray:
    """[K, N] -> [K, NB, 128]."""
    k = arr.shape[0]
    return np.ascontiguousarray(arr.reshape(k, nb, LANES))


def prepare(cluster: EncodedCluster, batch: EncodedBatch,
            device: bool = True) -> Tuple[PStatic, PState]:
    """Host-side packing of the encoder output into kernel layout.
    ``device=False`` keeps the planes as host numpy arrays (the native
    C++ backend mutates them in place through ctypes)."""
    n = cluster.allocatable.shape[0]
    if n % LANES != 0:
        raise ValueError(f"padded node count {n} not a multiple of {LANES}")
    nb = n // LANES
    r = cluster.allocatable.shape[1]
    scn = batch.sc_counts.shape[0]
    tn = batch.term_counts.shape[0]
    u = batch.static_masks.shape[0]
    v = batch.num_values

    sc_codes = np.minimum(
        cluster.topo_codes[:, batch.sc_key_idx].T, v
    ).astype(np.int32)                                        # [SC, N]
    term_codes = np.minimum(
        cluster.topo_codes[:, batch.term_key_idx].T, v
    ).astype(np.int32)                                        # [T, N]
    node_valid = np.zeros(n, dtype=np.int32)
    node_valid[: cluster.num_real_nodes] = 1

    # per-node eligible-domain masks: domain_node[u, sc, n] =
    # sc_domain[u, sc, code(sc, n)]  (sentinel column V is always False)
    dom_node = np.take_along_axis(
        batch.sc_domain.astype(np.int32),                     # [U, SC, V+1]
        sc_codes[None, :, :],                                 # [1, SC, N]
        axis=2,
    )                                                         # [U, SC, N]

    so, cs = _static_planes(r, scn, tn, u)
    ints = np.zeros((cs, n), dtype=np.int32)
    ints[so["alloc"]:so["alloc"] + r] = cluster.allocatable.T
    ints[so["max_pods"]] = cluster.max_pods
    ints[so["masks"]:so["masks"] + u] = batch.static_masks.astype(np.int32)
    ints[so["sc_codes"]:so["sc_codes"] + scn] = sc_codes
    ints[so["sc_domain"]:so["sc_domain"] + u * scn] = dom_node.reshape(
        u * scn, n
    )
    ints[so["term_codes"]:so["term_codes"] + tn] = term_codes
    ints[so["node_valid"]] = node_valid

    sc_meta = np.stack(
        [batch.sc_max_skew.astype(np.int32),
         batch.sc_hard.astype(np.int32)]
    )                                                         # [2, SC]

    put = jax.device_put if device else (lambda a: a)
    svn = 0 if cluster.sv_attached is None else cluster.sv_attached.shape[0]
    pstatic = PStatic(
        ints=put(_to_planes(ints, nb)),
        f32s=put(_to_planes(batch.static_scores.astype(np.float32), nb)),
        sc_meta=put(sc_meta),
        r=r, sc=scn, t=tn, u=u, v=v, nb=nb, sv=svn,
    )
    pstate = prepare_state(cluster, batch, device=device)
    return pstatic, pstate


def prepare_state(cluster: EncodedCluster, batch: EncodedBatch,
                  device: bool = True) -> PState:
    """The DYNAMIC half of ``prepare`` alone: per-node requested /
    nonzero / pod-count planes plus the topology/affinity count planes.
    Used by the session's state-only rebuild — after self-inflicted
    cache mutations whose static planes are bit-identical (e.g. mass
    preemption: victims change only the dynamic state), re-uploading
    just these planes skips the static upload and its host packing."""
    n = cluster.allocatable.shape[0]
    nb = n // LANES
    r = cluster.allocatable.shape[1]
    scn = batch.sc_counts.shape[0]
    tn = batch.term_counts.shape[0]
    v = batch.num_values
    sc_codes = np.minimum(
        cluster.topo_codes[:, batch.sc_key_idx].T, v
    ).astype(np.int32)
    term_codes = np.minimum(
        cluster.topo_codes[:, batch.term_key_idx].T, v
    ).astype(np.int32)

    svn = 0 if cluster.sv_attached is None else cluster.sv_attached.shape[0]
    # dynamic state: counts translated to the per-node representation
    do, cd = _state_planes(r, scn, tn, svn)
    planes = np.zeros((cd, n), dtype=np.int32)
    planes[do["requested"]:do["requested"] + r] = cluster.requested.T
    planes[do["nonzero"]:do["nonzero"] + 2] = cluster.nonzero_requested.T
    planes[do["pod_count"]] = cluster.pod_count
    planes[do["sc_counts"]:do["sc_counts"] + scn] = np.take_along_axis(
        batch.sc_counts, sc_codes, axis=1
    )
    planes[do["term_counts"]:do["term_counts"] + tn] = np.take_along_axis(
        batch.term_counts, term_codes, axis=1
    )
    planes[do["term_owners"]:do["term_owners"] + tn] = np.take_along_axis(
        batch.term_owners, term_codes, axis=1
    )
    if svn:
        planes[do["sv_attached"]:do["sv_attached"] + svn] = \
            cluster.sv_attached
    if tn > n:
        raise ValueError(
            f"planes layout holds per-term totals in one node-sized plane "
            f"({n}); {tn} tracked terms exceed it — use the legacy backend"
        )
    totals = np.zeros(n, dtype=np.int32)
    totals[:tn] = batch.term_counts[:, :v].sum(axis=1)
    planes[do["totals"]] = totals

    put = jax.device_put if device else (lambda a: a)
    return PState(planes=put(_to_planes(planes, nb)))


# ----------------------------------------------------------------------
def _kernel(params: SolverParams, r: int, scn: int, tn: int, u: int,
            v: int, nb: int, b: int,
            # inputs (state_in_ref is the alias source — outputs are used)
            sc_meta_ref, ints_ref, floats_ref, static_ref, scores_ref,
            state_in_ref,
            # outputs (state_ref aliases state_in_ref's buffer)
            assign_ref, state_ref,
            # scratch: per-term real-column totals (scalars must live in
            # SMEM — Mosaic cannot store scalars to VMEM)
            totals_ref):
    from jax.experimental import pallas as pl

    so, _ = _static_planes(r, scn, tn, u)
    do, _ = _state_planes(r, scn, tn)
    step = pl.program_id(0)

    # static per-node planes (VMEM reads, hoisted by Mosaic where possible)
    node_valid = static_ref[so["node_valid"]] > 0
    flat_idx = (
        jax.lax.broadcasted_iota(jnp.int32, (nb, LANES), 0) * LANES
        + jax.lax.broadcasted_iota(jnp.int32, (nb, LANES), 1)
    )
    lane_row = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    # totals plane -> SMEM scalars on the first step (the running totals
    # live in SMEM scratch, which persists across the sequential grid;
    # Mosaic cannot store scalars to VMEM)
    @pl.when(step == 0)
    def _init_totals():
        totals_plane = state_ref[do["totals"]][0:1, :]
        for _ti in range(tn):
            totals_ref[_ti] = jnp.sum(
                jnp.where(lane_row == _ti, totals_plane, 0)
            )

    # packed pod-stream column offsets (pack_podin layout)
    c_req = 0
    c_nonzero = r
    c_profile = r + 2
    c_valid = r + 3
    c_pod_sc = r + 4
    c_sc_match = r + 4 + scn
    c_match_by = r + 4 + 2 * scn
    c_own_aff = r + 4 + 2 * scn + tn
    c_own_anti = r + 4 + 2 * scn + 2 * tn

    for sub in range(POD_SUB):  # 8 pods per grid step (SMEM tiling rule)
        pod_valid = ints_ref[sub, c_valid] > 0
        profile = ints_ref[sub, c_profile]

        # ---- feasibility ------------------------------------------------
        fit = node_valid & (
            state_ref[do["pod_count"]] < static_ref[so["max_pods"]]
        )
        for ri in range(r):
            req_r = ints_ref[sub, c_req + ri]
            fit &= (
                state_ref[do["requested"] + ri] + req_r
                <= static_ref[so["alloc"] + ri]
            )
        static_ok = static_ref[so["masks"] + profile] > 0
        feasible = fit & static_ok & pod_valid

        # topology spread (hard)
        for sci in range(scn):
            pod_has = ints_ref[sub, c_pod_sc + sci] > 0
            hard = sc_meta_ref[1, sci] > 0
            active = pod_has & hard
            self_match = ints_ref[sub, c_sc_match + sci]
            counts = state_ref[do["sc_counts"] + sci]
            codes = static_ref[so["sc_codes"] + sci]
            missing = codes >= v
            dom = static_ref[so["sc_domain"] + profile * scn + sci] > 0
            min_c = jnp.min(jnp.where(dom, counts, BIG_I32))
            min_c = jnp.where(jnp.any(dom), min_c, 0)
            skew = counts + self_match - min_c
            ok = ~(missing | (skew > sc_meta_ref[0, sci]))
            # select on i1 vectors does not lower in Mosaic; use logic
            feasible &= ~active | ok

        # inter-pod affinity
        has_aff = False
        aff_sat = jnp.ones((nb, LANES), dtype=jnp.bool_)
        no_any = True
        self_all = True
        for ti in range(tn):
            codes = static_ref[so["term_codes"] + ti]
            t_missing = codes >= v
            tcounts = state_ref[do["term_counts"] + ti]
            towners = state_ref[do["term_owners"] + ti]
            matched = ints_ref[sub, c_match_by + ti] > 0
            own_aff = ints_ref[sub, c_own_aff + ti] > 0
            own_anti = ints_ref[sub, c_own_anti + ti] > 0
            feasible &= ~(matched & (towners > 0))        # existing anti
            feasible &= ~(own_anti & (tcounts > 0))       # own anti
            aff_here = (tcounts > 0) & ~t_missing
            aff_sat &= ~own_aff | aff_here
            total_t = totals_ref[ti]
            no_any &= ~own_aff | (total_t == 0)
            self_all &= ~own_aff | matched
            has_aff |= own_aff
        aff_ok = ~has_aff | aff_sat | (no_any & self_all)
        feasible &= aff_ok

        # ---- scores -----------------------------------------------------
        alloc_cpu = jnp.maximum(static_ref[so["alloc"]], 1).astype(jnp.float32)
        alloc_mem = jnp.maximum(
            static_ref[so["alloc"] + 1], 1
        ).astype(jnp.float32)
        nz_cpu = ints_ref[sub, c_nonzero]
        nz_mem = ints_ref[sub, c_nonzero + 1]
        cpu_frac = (
            state_ref[do["nonzero"]] + nz_cpu
        ).astype(jnp.float32) / alloc_cpu
        mem_frac = (
            state_ref[do["nonzero"] + 1] + nz_mem
        ).astype(jnp.float32) / alloc_mem
        over = (cpu_frac >= 1.0) | (mem_frac >= 1.0)
        balanced = jnp.where(
            over, 0.0, (1.0 - jnp.abs(cpu_frac - mem_frac)) * 100.0
        )
        least = (
            jnp.clip(1.0 - cpu_frac, 0.0, 1.0)
            + jnp.clip(1.0 - mem_frac, 0.0, 1.0)
        ) * 50.0

        soft_counts = jnp.zeros((nb, LANES), dtype=jnp.float32)
        any_soft = False
        for sci in range(scn):
            pod_has = ints_ref[sub, c_pod_sc + sci] > 0
            soft = ~(sc_meta_ref[1, sci] > 0) & pod_has
            soft_counts += jnp.where(
                soft, state_ref[do["sc_counts"] + sci], 0
            ).astype(jnp.float32)
            any_soft |= soft
        spread_score = jnp.where(any_soft, 100.0 / (1.0 + soft_counts), 0.0)

        pref_score = jnp.zeros((nb, LANES), dtype=jnp.float32)
        for ti in range(tn):
            w = floats_ref[sub, ti]
            pref_score += w * state_ref[do["term_counts"] + ti].astype(
                jnp.float32
            )

        score = (
            params.balanced_weight * balanced
            + params.least_weight * least
            + params.spread_weight * spread_score
            + params.affinity_weight * pref_score
            + params.static_weight * scores_ref[profile]
        )
        score = jnp.where(feasible, score, NEG_INF)

        # ---- argmax (lowest index wins ties) ---------------------------
        mx = jnp.max(score)
        found = mx > NEG_INF / 2
        cand = jnp.where(feasible & (score >= mx), flat_idx, BIG_I32)
        chosen = jnp.min(cand)
        valid = found & pod_valid
        assign_ref[sub, 0] = jnp.where(found, chosen, -1)

        # ---- commit -----------------------------------------------------
        onehot = (flat_idx == chosen) & valid
        inc = onehot.astype(jnp.int32)
        for ri in range(r):
            state_ref[do["requested"] + ri] += inc * ints_ref[sub, c_req + ri]
        state_ref[do["nonzero"]] += inc * nz_cpu
        state_ref[do["nonzero"] + 1] += inc * nz_mem
        state_ref[do["pod_count"]] += inc

        valid_i = valid.astype(jnp.int32)
        for sci in range(scn):
            codes = static_ref[so["sc_codes"] + sci]
            code_j = jnp.sum(jnp.where(onehot, codes, 0))
            self_match = ints_ref[sub, c_sc_match + sci] * valid_i
            state_ref[do["sc_counts"] + sci] += (
                (codes == code_j).astype(jnp.int32) * self_match
            )
        for ti in range(tn):
            codes = static_ref[so["term_codes"] + ti]
            code_j = jnp.sum(jnp.where(onehot, codes, 0))
            same = (codes == code_j).astype(jnp.int32)
            matched = ints_ref[sub, c_match_by + ti] * valid_i
            own_anti = ints_ref[sub, c_own_anti + ti] * valid_i
            state_ref[do["term_counts"] + ti] += same * matched
            state_ref[do["term_owners"] + ti] += same * own_anti
            # real-column total: only counted when the chosen node's
            # domain value is real (code_j < v), matching the scan path's
            # exclusion of the sentinel column
            real = (code_j < v).astype(jnp.int32)
            totals_ref[ti] = totals_ref[ti] + matched * real

    # SMEM totals -> state plane on the last step (vector store), so the
    # carried state round-trips through the aliased output buffer
    @pl.when(step == (b // POD_SUB) - 1)
    def _flush_totals():
        row0 = jax.lax.broadcasted_iota(jnp.int32, (nb, LANES), 0) == 0
        lane2d = jax.lax.broadcasted_iota(jnp.int32, (nb, LANES), 1)
        plane = jnp.zeros((nb, LANES), dtype=jnp.int32)
        for _ti in range(tn):
            plane += jnp.where(
                row0 & (lane2d == _ti), totals_ref[_ti], 0
            )
        state_ref[do["totals"]] = plane


def vmem_limit_bytes(capacity: int) -> int:
    """Scoped VMEM limit handed to the compiler: three quarters of the
    core's physical VMEM (the rest stays for Mosaic's internal scratch).
    The compiler's default scoped limit (16 MiB on v5e) refuses the
    50k-node kernel at the SC/T/U caps."""
    return capacity * 3 // 4


def kernel_fits_vmem(r: int, sc: int, t: int, u: int, nb: int,
                     limit: int) -> bool:
    """Shape decision: the planes the kernel keeps resident in VMEM for
    the whole pod loop (static ints, static scores, state in and out;
    no shared-volume planes), twice over for the pipeline's buffers,
    fit under ``limit``. Beyond it the session routes the epoch to the
    planes scan, which keeps its state in HBM."""
    planes = (_static_planes(r, sc, t, u)[1] + u
              + 2 * _state_planes(r, sc, t)[1])
    return 2 * 4 * planes * nb * LANES <= limit


@functools.lru_cache(maxsize=64)
def _get_call(params: SolverParams, r: int, sc: int, t: int, u: int,
              v: int, nb: int, b: int, c_cols: int, t_cols: int,
              cd: int, interpret: bool, vmem_limit: int = 0):
    """Build and jit-wrap the pallas_call for one shape signature.
    Without the jit wrapper every invocation re-traces and re-lowers the
    kernel (≈1.6s per call); cached, the steady-state call is a single
    executable launch. ``vmem_limit`` (bytes, 0 = the compiler's
    default) raises the kernel's scoped VMEM limit. Kernel and module
    carry the stable name ``ktpu_solve``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(_kernel, params, r, sc, t, u, v, nb, b)
    planes_shape = (cd, nb, LANES)
    ints_shape = (_static_planes(r, sc, t, u)[1], nb, LANES)
    f32s_shape = (u, nb, LANES)
    # Eight pods per grid step: the TPU grid is a sequential loop, so state
    # mutation across steps is ordered. The pod stream is block-mapped 8
    # ROWS per step into SMEM (scalar memory — the kernel consumes pod
    # fields as scalars with static offsets); the big per-node planes use
    # constant index maps so they stay resident in VMEM for the whole run.
    if b % POD_SUB != 0:
        raise ValueError(f"batch {b} not a multiple of {POD_SUB}")
    call = pl.pallas_call(
        kernel,
        grid=(b // POD_SUB,),
        in_specs=[
            pl.BlockSpec((2, sc), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),            # sc_meta
            pl.BlockSpec((POD_SUB, c_cols), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),            # pod ints rows
            pl.BlockSpec((POD_SUB, t_cols), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),            # pod floats rows
            pl.BlockSpec(ints_shape, lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),            # static ints
            pl.BlockSpec(f32s_shape, lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),            # static scores
            pl.BlockSpec(planes_shape, lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),            # state (in)
        ],
        out_specs=(
            pl.BlockSpec((POD_SUB, 1), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),            # assignments
            pl.BlockSpec(planes_shape, lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),            # state (out)
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct(planes_shape, jnp.int32),
        ),
        input_output_aliases={5: 1},   # state planes in -> out
        scratch_shapes=[pltpu.SMEM((max(t, 1),), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)
        if vmem_limit and not interpret else None,
        name="ktpu_solve",
    )

    def ktpu_solve(*args):
        return call(*args)

    return jax.jit(ktpu_solve)


def _run(params: SolverParams, pstatic: PStatic, pstate: PState,
         pod_ints, pod_floats, interpret: bool, vmem_limit: int = 0):
    b = pod_ints.shape[0]
    if b % POD_SUB != 0:
        raise ValueError(f"batch {b} not a multiple of {POD_SUB}")
    call = _get_call(
        params, pstatic.r, pstatic.sc, pstatic.t, pstatic.u, pstatic.v,
        pstatic.nb, b, pod_ints.shape[1], pod_floats.shape[1],
        pstate.planes.shape[0], interpret, vmem_limit,
    )
    assignments, new_planes = call(
        pstatic.sc_meta, pod_ints, pod_floats, pstatic.ints, pstatic.f32s,
        pstate.planes,
    )
    return assignments, PState(planes=new_planes)


# ----------------------------------------------------------------------
# Gather-free XLA scan over the SAME planes layout. The legacy scan
# (ops.solver._step) indexes per-value count tables with
# take_along_axis — a [T, N] gather per step that collapses at
# hostname-keyed terms (V≈N): ~18ms/step at T=100, V=5000. This variant
# keeps counts per node (like the kernel) so every op is a dense
# vector compare/add that XLA fuses, and it is vectorized over the
# SC/T axes — no Python unrolling — so it covers the wide constraint
# spaces the pallas kernel cannot.

@functools.partial(
    jax.jit, static_argnames=("params", "r", "sc", "t", "u", "v", "sv")
)
def _xla_planes_solve(params: SolverParams, r: int, sc: int, t: int,
                      u: int, v: int, sc_meta, static_ints, static_f32s,
                      planes, pod_ints, pod_floats, sv: int = 0):
    so, _ = _static_planes(r, sc, t, u)
    do, cd = _state_planes(r, sc, t, sv)
    nb, lanes = planes.shape[1], planes.shape[2]

    node_valid = static_ints[so["node_valid"]] > 0
    alloc = static_ints[so["alloc"]:so["alloc"] + r]
    max_pods = static_ints[so["max_pods"]]
    masks = static_ints[so["masks"]:so["masks"] + u]
    sc_codes = static_ints[so["sc_codes"]:so["sc_codes"] + sc]
    dom_all = static_ints[so["sc_domain"]:so["sc_domain"] + u * sc].reshape(
        u, sc, nb, lanes
    )
    term_codes = static_ints[so["term_codes"]:so["term_codes"] + t]
    sc_missing = sc_codes >= v
    t_missing = term_codes >= v
    flat_idx = (
        jax.lax.broadcasted_iota(jnp.int32, (nb, lanes), 0) * lanes
        + jax.lax.broadcasted_iota(jnp.int32, (nb, lanes), 1)
    )
    max_skew = sc_meta[0]
    hard = sc_meta[1] > 0

    # pod-stream column offsets (pack_podin layout)
    c_req, c_nonzero, c_profile, c_valid = 0, r, r + 2, r + 3
    c_pod_sc, c_sc_match = r + 4, r + 4 + sc
    c_match_by, c_own_aff, c_own_anti = (
        r + 4 + 2 * sc, r + 4 + 2 * sc + t, r + 4 + 2 * sc + 2 * t,
    )
    c_sv = r + 4 + 2 * sc + 3 * t   # (slot, attach col), sv epochs only

    def step(carry, pod):
        state, totals = carry
        row, pref_w = pod
        pod_valid = row[c_valid] > 0
        profile = row[c_profile]
        req = row[c_req:c_req + r]
        pod_sc = row[c_pod_sc:c_pod_sc + sc] > 0
        sc_match = row[c_sc_match:c_sc_match + sc] > 0
        match_by = row[c_match_by:c_match_by + t] > 0
        own_aff = row[c_own_aff:c_own_aff + t] > 0
        own_anti = row[c_own_anti:c_own_anti + t] > 0

        requested = state[do["requested"]:do["requested"] + r]
        fit = jnp.all(requested + req[:, None, None] <= alloc, axis=0)
        fit &= state[do["pod_count"]] < max_pods
        if sv:
            # shared-volume attach: demand is CONDITIONAL per node —
            # 1 only where this pod's shared volume isn't attached yet
            # (csi.go len(in_use | wanted) set semantics)
            sv_planes = state[do["sv_attached"]:do["sv_attached"] + sv]
            sv_slot = row[c_sv]
            sv_col = row[c_sv + 1]
            sv_is_shared = sv_slot < sv
            slot_c = jnp.minimum(sv_slot, sv - 1)
            att = jnp.take(sv_planes, slot_c, axis=0)      # [nb, lanes]
            sv_demand = jnp.where(sv_is_shared, 1 - att, 0)
            col_alloc = jnp.take(alloc, sv_col, axis=0)
            col_req = jnp.take(requested, sv_col, axis=0)
            col_pod = jnp.take(req, sv_col)
            fit &= col_req + col_pod + sv_demand <= col_alloc
        static_ok = masks[profile] > 0

        counts = state[do["sc_counts"]:do["sc_counts"] + sc]
        dom = dom_all[profile] > 0
        min_c = jnp.min(jnp.where(dom, counts, BIG_I32), axis=(1, 2))
        min_c = jnp.where(jnp.any(dom, axis=(1, 2)), min_c, 0)
        skew = counts + sc_match[:, None, None] - min_c[:, None, None]
        active_hard = pod_sc & hard
        spread_violation = jnp.any(
            active_hard[:, None, None]
            & ((skew > max_skew[:, None, None]) | sc_missing),
            axis=0,
        )

        tcounts = state[do["term_counts"]:do["term_counts"] + t]
        towners = state[do["term_owners"]:do["term_owners"] + t]
        existing_anti = jnp.any(
            match_by[:, None, None] & (towners > 0), axis=0
        )
        own_anti_block = jnp.any(
            own_anti[:, None, None] & (tcounts > 0), axis=0
        )
        aff_here = (tcounts > 0) & ~t_missing
        aff_sat = jnp.all(~own_aff[:, None, None] | aff_here, axis=0)
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
            jnp.where(active_soft[:, None, None], counts, 0), axis=0
        ).astype(jnp.float32)
        spread_score = jnp.where(
            jnp.any(active_soft), 100.0 / (1.0 + soft_counts), 0.0
        )
        pref_score = jnp.sum(
            pref_w[:, None, None] * tcounts.astype(jnp.float32), axis=0
        )
        score = (
            params.balanced_weight * balanced
            + params.least_weight * least
            + params.spread_weight * spread_score
            + params.affinity_weight * pref_score
            + params.static_weight * static_f32s[profile]
        )
        score = jnp.where(feasible, score, NEG_INF)

        mx = jnp.max(score)
        found = mx > NEG_INF / 2
        cand = jnp.where(feasible & (score >= mx), flat_idx, BIG_I32)
        chosen = jnp.min(cand)
        valid = found & pod_valid
        assignment = jnp.where(found, chosen, -1)

        onehot = (flat_idx == chosen) & valid
        inc = onehot.astype(jnp.int32)
        valid_i = valid.astype(jnp.int32)
        sc_code_j = jnp.sum(
            jnp.where(onehot[None], sc_codes, 0), axis=(1, 2)
        )
        t_code_j = jnp.sum(
            jnp.where(onehot[None], term_codes, 0), axis=(1, 2)
        )
        sc_inc = (sc_codes == sc_code_j[:, None, None]).astype(jnp.int32) \
            * (sc_match.astype(jnp.int32) * valid_i)[:, None, None]
        t_same = (term_codes == t_code_j[:, None, None]).astype(jnp.int32)
        t_inc = t_same * (match_by.astype(jnp.int32) * valid_i)[:, None, None]
        o_inc = t_same * (own_anti.astype(jnp.int32) * valid_i)[:, None, None]

        new_requested = requested + inc[None] * req[:, None, None]
        pieces = [
            new_requested,
            nz + inc[None] * row[c_nonzero:c_nonzero + 2][:, None, None],
            (state[do["pod_count"]] + inc)[None],
            counts + sc_inc,
            tcounts + t_inc,
            towners + o_inc,
        ]
        if sv:
            # consume the attach slot only where it wasn't already
            # attached, and mark the volume attached on the chosen node
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

    totals0 = planes[do["totals"]].reshape(-1)[:t]
    (final_planes, final_totals), assignments = jax.lax.scan(
        step, (planes, totals0), (pod_ints, pod_floats)
    )
    # totals back into their plane (row 0, lane t) for the carry contract
    flat = jnp.zeros(nb * lanes, dtype=jnp.int32).at[:t].set(final_totals)
    final_planes = final_planes.at[do["totals"]].set(
        flat.reshape(nb, lanes)
    )
    return final_planes, assignments


# ----------------------------------------------------------------------
# Sparse term-slot variant: a pod references only the handful of terms
# its own (anti-)affinity names or is matched by (config-4-style
# workloads: 1 term per pod out of 100+ tracked). The dense scan does
# O(T·N) vector work per pod regardless; this variant carries the SAME
# [T]-plane state but gathers just the K referenced planes per pod and
# scatter-adds the commit back, so per-pod cost is O(K·N). The pod
# stream also shrinks from [B, 3T] term columns to [B, 4K] slots —
# ~20x less host->device upload at T≈100.

SPARSE_K = 8          # max term references per pod on the sparse path
SPARSE_MIN_T = 12     # below this the dense scan is already fine


def pack_sparse_slots(ints: np.ndarray, floats: np.ndarray, r: int,
                      sc: int, t: int):
    """Derive per-pod term slots from the packed dense pod stream.
    Returns (base_ints, slot_idx, slot_flags, slot_w) — or None when any
    pod references more than SPARSE_K terms (caller stays dense).
    slot_flags packs (matched, own_aff, own_anti) as bits 0/1/2."""
    c_match_by = r + 4 + 2 * sc
    mb = ints[:, c_match_by:c_match_by + t] != 0
    oa = ints[:, c_match_by + t:c_match_by + 2 * t] != 0
    oan = ints[:, c_match_by + 2 * t:c_match_by + 3 * t] != 0
    w = floats[:, :t]
    ref = mb | oa | oan | (w != 0.0)
    nref = ref.sum(axis=1)
    if nref.max(initial=0) > SPARSE_K:
        return None
    # stable argsort puts referenced term indices first, in term order
    order = np.argsort(~ref, axis=1, kind="stable")[:, :SPARSE_K]
    active = np.take_along_axis(ref, order, axis=1)
    slot_idx = np.where(active, order, 0).astype(np.int32)
    flags = (
        np.take_along_axis(mb, order, axis=1).astype(np.int32)
        | (np.take_along_axis(oa, order, axis=1).astype(np.int32) << 1)
        | (np.take_along_axis(oan, order, axis=1).astype(np.int32) << 2)
    )
    flags = np.where(active, flags, 0)
    slot_w = np.where(
        active, np.take_along_axis(w, order, axis=1), 0.0
    ).astype(np.float32)
    base = np.ascontiguousarray(ints[:, :c_match_by])
    return base, slot_idx, flags, slot_w


@functools.partial(
    jax.jit, static_argnames=("params", "r", "sc", "t", "u", "v")
)
def _xla_planes_solve_sparse(params: SolverParams, r: int, sc: int, t: int,
                             u: int, v: int, sc_meta, static_ints,
                             static_f32s, planes, base_ints, slot_idx,
                             slot_flags, slot_w):
    so, _ = _static_planes(r, sc, t, u)
    do, cd = _state_planes(r, sc, t)
    nb, lanes = planes.shape[1], planes.shape[2]

    node_valid = static_ints[so["node_valid"]] > 0
    alloc = static_ints[so["alloc"]:so["alloc"] + r]
    max_pods = static_ints[so["max_pods"]]
    masks = static_ints[so["masks"]:so["masks"] + u]
    sc_codes = static_ints[so["sc_codes"]:so["sc_codes"] + sc]
    dom_all = static_ints[so["sc_domain"]:so["sc_domain"] + u * sc].reshape(
        u, sc, nb, lanes
    )
    term_codes = static_ints[so["term_codes"]:so["term_codes"] + t]
    sc_missing = sc_codes >= v
    flat_idx = (
        jax.lax.broadcasted_iota(jnp.int32, (nb, lanes), 0) * lanes
        + jax.lax.broadcasted_iota(jnp.int32, (nb, lanes), 1)
    )
    max_skew = sc_meta[0]
    hard = sc_meta[1] > 0

    c_req, c_nonzero, c_profile, c_valid = 0, r, r + 2, r + 3
    c_pod_sc, c_sc_match = r + 4, r + 4 + sc

    def step(carry, pod):
        tcounts_all, towners_all, totals, rest = carry
        row, idxs, flags, pref_w = pod
        pod_valid = row[c_valid] > 0
        profile = row[c_profile]
        req = row[c_req:c_req + r]
        pod_sc = row[c_pod_sc:c_pod_sc + sc] > 0
        sc_match = row[c_sc_match:c_sc_match + sc] > 0
        matched = (flags & 1) > 0            # [K]
        own_aff = (flags & 2) > 0
        own_anti = (flags & 4) > 0

        requested = rest[do["requested"]:do["requested"] + r]
        fit = jnp.all(requested + req[:, None, None] <= alloc, axis=0)
        fit &= rest[do["pod_count"]] < max_pods
        static_ok = masks[profile] > 0

        counts = rest[do["sc_counts"]:do["sc_counts"] + sc]
        dom = dom_all[profile] > 0
        min_c = jnp.min(jnp.where(dom, counts, BIG_I32), axis=(1, 2))
        min_c = jnp.where(jnp.any(dom, axis=(1, 2)), min_c, 0)
        skew = counts + sc_match[:, None, None] - min_c[:, None, None]
        active_hard = pod_sc & hard
        spread_violation = jnp.any(
            active_hard[:, None, None]
            & ((skew > max_skew[:, None, None]) | sc_missing),
            axis=0,
        )

        # gather the K referenced term planes (clip-mode gathers are
        # harmless: inactive slots carry zero flags/weights)
        tc_k = jnp.take(tcounts_all, idxs, axis=0)          # [K, NB, L]
        to_k = jnp.take(towners_all, idxs, axis=0)
        codes_k = jnp.take(term_codes, idxs, axis=0)
        tmiss_k = codes_k >= v
        totals_k = jnp.take(totals, idxs)

        existing_anti = jnp.any(matched[:, None, None] & (to_k > 0), axis=0)
        own_anti_block = jnp.any(
            own_anti[:, None, None] & (tc_k > 0), axis=0
        )
        aff_here = (tc_k > 0) & ~tmiss_k
        aff_sat = jnp.all(~own_aff[:, None, None] | aff_here, axis=0)
        no_any = jnp.all(~own_aff | (totals_k == 0))
        self_all = jnp.all(~own_aff | matched)
        has_aff = jnp.any(own_aff)
        aff_ok = ~has_aff | aff_sat | (no_any & self_all)

        feasible = (
            node_valid & static_ok & fit & ~spread_violation
            & ~existing_anti & ~own_anti_block & aff_ok & pod_valid
        )

        alloc_cpu = jnp.maximum(alloc[0], 1).astype(jnp.float32)
        alloc_mem = jnp.maximum(alloc[1], 1).astype(jnp.float32)
        nz = rest[do["nonzero"]:do["nonzero"] + 2]
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
            jnp.where(active_soft[:, None, None], counts, 0), axis=0
        ).astype(jnp.float32)
        spread_score = jnp.where(
            jnp.any(active_soft), 100.0 / (1.0 + soft_counts), 0.0
        )
        pref_score = jnp.sum(
            pref_w[:, None, None] * tc_k.astype(jnp.float32), axis=0
        )
        score = (
            params.balanced_weight * balanced
            + params.least_weight * least
            + params.spread_weight * spread_score
            + params.affinity_weight * pref_score
            + params.static_weight * static_f32s[profile]
        )
        score = jnp.where(feasible, score, NEG_INF)

        mx = jnp.max(score)
        found = mx > NEG_INF / 2
        cand = jnp.where(feasible & (score >= mx), flat_idx, BIG_I32)
        chosen = jnp.min(cand)
        valid = found & pod_valid
        assignment = jnp.where(found, chosen, -1)

        onehot = (flat_idx == chosen) & valid
        inc = onehot.astype(jnp.int32)
        valid_i = valid.astype(jnp.int32)
        sc_code_j = jnp.sum(
            jnp.where(onehot[None], sc_codes, 0), axis=(1, 2)
        )
        sc_inc = (sc_codes == sc_code_j[:, None, None]).astype(jnp.int32) \
            * (sc_match.astype(jnp.int32) * valid_i)[:, None, None]

        # per-slot commit, scatter-added back into the [T] planes
        t_code_j = jnp.sum(
            jnp.where(onehot[None], codes_k, 0), axis=(1, 2)
        )                                                     # [K]
        t_same = (codes_k == t_code_j[:, None, None]).astype(jnp.int32)
        m_i = matched.astype(jnp.int32) * valid_i
        a_i = own_anti.astype(jnp.int32) * valid_i
        new_tcounts = tcounts_all.at[idxs].add(
            t_same * m_i[:, None, None]
        )
        new_towners = towners_all.at[idxs].add(
            t_same * a_i[:, None, None]
        )
        new_totals = totals.at[idxs].add(m_i * (t_code_j < v))

        new_rest = jnp.concatenate([
            requested + inc[None] * req[:, None, None],
            nz + inc[None] * row[c_nonzero:c_nonzero + 2][:, None, None],
            (rest[do["pod_count"]] + inc)[None],
            counts + sc_inc,
        ])
        return (new_tcounts, new_towners, new_totals, new_rest), assignment

    # split the carry so the hot [T] planes scatter in place
    tcounts0 = planes[do["term_counts"]:do["term_counts"] + t]
    towners0 = planes[do["term_owners"]:do["term_owners"] + t]
    totals0 = planes[do["totals"]].reshape(-1)[:t]
    rest0 = planes[:do["term_counts"]]
    (tcounts_f, towners_f, totals_f, rest_f), assignments = jax.lax.scan(
        step, (tcounts0, towners0, totals0, rest0),
        (base_ints, slot_idx, slot_flags, slot_w),
    )
    flat = jnp.zeros(nb * lanes, dtype=jnp.int32).at[:t].set(totals_f)
    final_planes = jnp.concatenate([
        rest_f, tcounts_f, towners_f, flat.reshape(1, nb, lanes)
    ])
    return final_planes, assignments


def _scatter_flat_add(planes, rows, cols, vals):
    """Donated scatter-add into [C, NB, 128] planes; ``cols`` are flat
    node indices (the [C, N] view — the reshape is row-major, so flat
    col == node index)."""
    c, nb, lanes = planes.shape
    flat = planes.reshape(c, nb * lanes)
    return flat.at[rows, cols].add(vals).reshape(c, nb, lanes)


def _scatter_flat_set(planes, rows, cols, vals):
    c, nb, lanes = planes.shape
    flat = planes.reshape(c, nb * lanes)
    return flat.at[rows, cols].set(vals).reshape(c, nb, lanes)


# device-resident mirror update kernels (ops.mirror): the plane stack
# is donated so the update happens in place on device and only the
# index/value triples cross the link
_scatter_flat_add_jit = jax.jit(_scatter_flat_add, donate_argnums=(0,))
_scatter_flat_set_jit = jax.jit(_scatter_flat_set, donate_argnums=(0,))


class _PlanesScatterHooks:
    """Mirror scatter hooks shared by the device planes backends
    (XlaPlanes + Pallas — both carry PState/PStatic device arrays)."""

    def scatter_state_add(self, pstate, rows, cols, vals):
        planes = _scatter_flat_add_jit(pstate.planes, rows, cols, vals)
        return (PState(planes=planes),
                int(rows.nbytes + cols.nbytes + vals.nbytes))

    def scatter_static_set(self, pstatic, rows, cols, vals):
        ints = _scatter_flat_set_jit(pstatic.ints, rows, cols, vals)
        return (pstatic._replace(ints=ints),
                int(rows.nbytes + cols.nbytes + vals.nbytes))


class XlaPlanesBackend(_PlanesScatterHooks):
    """Gather-free scan backend on the planes layout — the fallback for
    constraint spaces too wide for the unrolled pallas kernel. Wide term
    axes (T ≥ SPARSE_MIN_T) with few per-pod references ride the sparse
    term-slot scan: O(K·N) per pod instead of O(T·N)."""

    name = "xla-planes"

    def prepare(self, cluster, batch):
        return prepare(cluster, batch)

    def prepare_state_only(self, cluster, batch):
        return prepare_state(cluster, batch)

    def solve_lazy(self, params, pstatic, pstate, pod_ints, pod_floats):
        """Dispatch the solve; the returned assignments handle is a
        device array the caller materializes later (jax dispatch is
        async, so host work can overlap the device solve)."""
        t = pstatic.t
        if t >= SPARSE_MIN_T and pstatic.sv == 0:
            # the sparse term-slot variant predates the sv planes; sv
            # epochs take the dense scan (wide-term + shared-volume
            # workloads are not a measured combination)
            sparse = pack_sparse_slots(
                np.asarray(pod_ints), np.asarray(pod_floats),
                pstatic.r, pstatic.sc, t,
            )
            if sparse is not None:
                base, slot_idx, slot_flags, slot_w = sparse
                new_planes, assignments = _xla_planes_solve_sparse(
                    params, pstatic.r, pstatic.sc, t, pstatic.u,
                    pstatic.v, pstatic.sc_meta, pstatic.ints,
                    pstatic.f32s, pstate.planes, jnp.asarray(base),
                    jnp.asarray(slot_idx), jnp.asarray(slot_flags),
                    jnp.asarray(slot_w),
                )
                return assignments, PState(planes=new_planes)
        new_planes, assignments = _xla_planes_solve(
            params, pstatic.r, pstatic.sc, pstatic.t, pstatic.u,
            pstatic.v, pstatic.sc_meta, pstatic.ints, pstatic.f32s,
            pstate.planes, jnp.asarray(pod_ints), jnp.asarray(pod_floats),
            sv=pstatic.sv,
        )
        return assignments, PState(planes=new_planes)

    @staticmethod
    def materialize(handle):
        return np.asarray(handle)

    def solve(self, params, pstatic, pstate, pod_ints, pod_floats):
        h, state = self.solve_lazy(params, pstatic, pstate, pod_ints,
                                   pod_floats)
        return self.materialize(h), state


class PallasBackend(_PlanesScatterHooks):
    """Drop-in solve backend for SolverSession (see session.py). On a
    TPU the kernel compiles for the chip with a scoped VMEM limit taken
    from the core's capacity; on the CPU (tests) it runs in Pallas'
    interpret mode. The platform decides, never a caller."""

    name = "pallas"

    def __init__(self):
        from jax.experimental.pallas import tpu as pltpu

        self.interpret = jax.default_backend() == "cpu"
        self.vmem_limit = 0 if self.interpret else vmem_limit_bytes(
            pltpu.get_tpu_info().vmem_capacity_bytes)

    def fits(self, batch, n_nodes: int) -> bool:
        """Whether this epoch's planes fit the kernel's VMEM limit
        (always true in interpret mode, which has no VMEM)."""
        if self.interpret:
            return True
        return kernel_fits_vmem(
            batch.requests.shape[1], batch.sc_counts.shape[0],
            batch.term_counts.shape[0], batch.static_masks.shape[0],
            n_nodes // LANES, self.vmem_limit)

    def prepare(self, cluster, batch):
        if cluster.sv_attached is not None:
            # the unrolled kernel has no sv planes; the chain falls to
            # the planes scan for shared-volume epochs
            raise ValueError(
                "pallas kernel does not carry shared-volume planes")
        return prepare(cluster, batch)

    def prepare_state_only(self, cluster, batch):
        return prepare_state(cluster, batch)

    def solve_lazy(self, params, pstatic, pstate, pod_ints, pod_floats):
        """Async-dispatched solve; materialize the handle later."""
        assignments, new_state = _run(
            params, pstatic, pstate,
            jnp.asarray(pod_ints), jnp.asarray(pod_floats),
            self.interpret, self.vmem_limit,
        )
        return assignments, new_state

    @staticmethod
    def materialize(handle):
        return np.asarray(handle)[:, 0]

    def solve(self, params, pstatic, pstate, pod_ints, pod_floats):
        h, state = self.solve_lazy(params, pstatic, pstate, pod_ints,
                                   pod_floats)
        return self.materialize(h), state
