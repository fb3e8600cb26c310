"""Pallas kernel differential tests (interpret mode — CPU-safe).

The kernel must produce EXACTLY the scan solver's assignments (same
serial-equivalent semantics) on mixed workloads: resource fit, hard
topology spread, and (anti-)affinity, including intra-batch interaction.
"""

import dataclasses

import numpy as np
import pytest

from kubernetes_tpu.ops import pallas_solver as ps
from kubernetes_tpu.ops.encode import BatchEncoder
from kubernetes_tpu.ops.solver import SolverParams, pack_podin, solve_scan
from kubernetes_tpu.scheduler.snapshot import new_snapshot
from kubernetes_tpu.testing import MakeNode, MakePod


def _problem(n_nodes=12, n_pods=16, mixed=True):
    nodes = [
        MakeNode().name(f"n{i}")
        .label("topology.kubernetes.io/zone", f"z{i % 3}")
        .capacity({"cpu": "8", "memory": "16Gi"}).obj()
        for i in range(n_nodes)
    ]
    pods = []
    for i in range(n_pods):
        w = MakePod().name(f"p{i}").uid(f"pu{i}").label("app", "w").req(
            {"cpu": "500m", "memory": "256Mi"})
        if mixed and i % 3 == 0:
            w.spread_constraint(2, "topology.kubernetes.io/zone",
                                "DoNotSchedule", {"app": "w"})
        elif mixed and i % 3 == 1:
            w.pod_anti_affinity("app", ["w"], "kubernetes.io/hostname")
        pods.append(w.obj())
    snap = new_snapshot([], nodes)
    enc = BatchEncoder(snap, pad_nodes=128)
    return enc.encode(pods, pad_pods=32)


@pytest.mark.parametrize("mixed", [False, True])
def test_kernel_matches_scan(mixed):
    cluster, batch = _problem(mixed=mixed)
    ref = solve_scan(cluster, batch, SolverParams())
    pstatic, pstate = ps.prepare(cluster, batch)
    ints, floats = pack_podin(batch)
    backend = ps.PallasBackend()
    got, _ = backend.solve(SolverParams(), pstatic, pstate, ints, floats)
    np.testing.assert_array_equal(ref, got)


def test_kernel_state_carry_across_batches():
    """Two sequential 8-pod batches through the kernel must equal one
    16-pod scan: the carried PState (capacity + topology counts) is the
    cross-batch contract."""
    cluster, batch = _problem(n_pods=16)
    ref = solve_scan(cluster, batch, SolverParams())

    pstatic, pstate = ps.prepare(cluster, batch)
    backend = ps.PallasBackend()
    outs = []
    pods_all = batch.pods
    for half in (slice(0, 8), slice(8, 16)):
        sub = dataclasses.replace(
            batch,
            pods=pods_all[half],
            num_real_pods=8,
            requests=np.vstack([batch.requests[half],
                                np.zeros((8, batch.requests.shape[1]),
                                         np.int32)]),
            nonzero_requests=np.vstack([batch.nonzero_requests[half],
                                        np.zeros((8, 2), np.int32)]),
            profile_idx=np.concatenate([batch.profile_idx[half],
                                        np.zeros(8, np.int32)]),
            inexpressible=np.concatenate([batch.inexpressible[half],
                                          np.zeros(8, bool)]),
            pod_sc=np.vstack([batch.pod_sc[half],
                              np.zeros((8, batch.pod_sc.shape[1]), bool)]),
            pod_sc_match=np.vstack(
                [batch.pod_sc_match[half],
                 np.zeros((8, batch.pod_sc_match.shape[1]), bool)]),
            match_by=np.vstack([batch.match_by[half],
                                np.zeros((8, batch.match_by.shape[1]),
                                         bool)]),
            own_aff=np.vstack([batch.own_aff[half],
                               np.zeros((8, batch.own_aff.shape[1]), bool)]),
            own_anti=np.vstack([batch.own_anti[half],
                                np.zeros((8, batch.own_anti.shape[1]),
                                         bool)]),
            pref_weight=np.vstack(
                [batch.pref_weight[half],
                 np.zeros((8, batch.pref_weight.shape[1]), np.float32)]),
        )
        ints, floats = pack_podin(sub)
        got, pstate = backend.solve(SolverParams(), pstatic, pstate,
                                    ints, floats)
        outs.extend(got[:8].tolist())
    np.testing.assert_array_equal(ref[:16], outs)


@pytest.mark.parametrize("mixed", [False, True])
def test_xla_planes_backend_matches_scan(mixed):
    """The gather-free planes scan (wide-constraint fallback) must also
    match the legacy scan exactly."""
    cluster, batch = _problem(mixed=mixed)
    ref = solve_scan(cluster, batch, SolverParams())
    backend = ps.XlaPlanesBackend()
    pstatic, pstate = backend.prepare(cluster, batch)
    ints, floats = pack_podin(batch)
    got, _ = backend.solve(SolverParams(), pstatic, pstate, ints, floats)
    np.testing.assert_array_equal(ref, got)


def _wide_term_problem(n_nodes=16, n_pods=48, groups=20, preferred=False):
    """Config-4-shaped workload: many anti-affinity groups (T >=
    SPARSE_MIN_T tracked terms), each pod referencing exactly one."""
    nodes = [
        MakeNode().name(f"n{i}")
        .label("topology.kubernetes.io/zone", f"z{i % 4}")
        .capacity({"cpu": "64", "memory": "64Gi"}).obj()
        for i in range(n_nodes)
    ]
    pods = []
    for i in range(n_pods):
        g = f"g{i % groups}"
        w = (MakePod().name(f"p{i}").uid(f"pu{i}").label("grp", g)
             .req({"cpu": "100m", "memory": "64Mi"}))
        if preferred and i % 2 == 0:
            w.preferred_pod_affinity(3, "grp", [g],
                                     "topology.kubernetes.io/zone")
        else:
            w.pod_anti_affinity("grp", [g], "kubernetes.io/hostname")
        pods.append(w.obj())
    snap = new_snapshot([], nodes)
    enc = BatchEncoder(snap, pad_nodes=128)
    return enc.encode(pods, pad_pods=64)


@pytest.mark.parametrize("preferred", [False, True])
def test_sparse_term_slots_match_scan(preferred):
    """The sparse term-slot scan (wide-T fast path) must match the
    legacy scan exactly — same assignments, same carried state."""
    cluster, batch = _wide_term_problem(preferred=preferred)
    t = batch.term_counts.shape[0]
    assert t >= ps.SPARSE_MIN_T, f"problem too narrow (T={t}) to hit sparse"
    ref = solve_scan(cluster, batch, SolverParams())
    backend = ps.XlaPlanesBackend()
    pstatic, pstate = backend.prepare(cluster, batch)
    ints, floats = pack_podin(batch)
    # make sure the sparse packer actually applies to this problem
    assert ps.pack_sparse_slots(ints, floats, pstatic.r, pstatic.sc,
                                t) is not None
    got, state = backend.solve(SolverParams(), pstatic, pstate, ints, floats)
    np.testing.assert_array_equal(ref, got)
    # carried state must equal the dense path's carried state
    pstatic2, pstate2 = backend.prepare(cluster, batch)
    dense_planes, _ = ps._xla_planes_solve(
        SolverParams(), pstatic2.r, pstatic2.sc, pstatic2.t, pstatic2.u,
        pstatic2.v, pstatic2.sc_meta, pstatic2.ints, pstatic2.f32s,
        pstate2.planes, ints, floats,
    )
    np.testing.assert_array_equal(np.asarray(dense_planes),
                                  np.asarray(state.planes))


def test_sparse_overflow_falls_back_dense():
    """A batch containing a pod that references more than SPARSE_K terms
    must solve END-TO-END on the dense path (pack_sparse_slots declines,
    solve_lazy falls through) and still match the legacy scan."""
    nodes = [
        MakeNode().name(f"n{i}")
        .capacity({"cpu": "64", "memory": "64Gi"}).obj()
        for i in range(8)
    ]
    pods = []
    for i in range(24):
        w = (MakePod().name(f"p{i}").uid(f"pu{i}")
             .label("grp", f"g{i % 16}").req({"cpu": "100m"}))
        if i == 0:
            # one pod owning SPARSE_K+1 distinct anti-affinity terms
            for j in range(ps.SPARSE_K + 1):
                w.label(f"multi{j}", "x")
                w.pod_anti_affinity(f"multi{j}", ["x"],
                                    "kubernetes.io/hostname")
        else:
            w.pod_anti_affinity("grp", [f"g{i % 16}"],
                                "kubernetes.io/hostname")
        pods.append(w.obj())
    snap = new_snapshot([], nodes)
    enc = BatchEncoder(snap, pad_nodes=128)
    cluster, batch = enc.encode(pods, pad_pods=32)
    t = batch.term_counts.shape[0]
    assert t >= ps.SPARSE_MIN_T
    ints, floats = pack_podin(batch)
    r, sc = cluster.allocatable.shape[1], batch.sc_counts.shape[0]
    assert ps.pack_sparse_slots(ints, floats, r, sc, t) is None
    ref = solve_scan(cluster, batch, SolverParams())
    backend = ps.XlaPlanesBackend()
    pstatic, pstate = backend.prepare(cluster, batch)
    got, _ = backend.solve(SolverParams(), pstatic, pstate, ints, floats)
    np.testing.assert_array_equal(ref, got)
