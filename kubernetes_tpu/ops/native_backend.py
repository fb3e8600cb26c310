"""ctypes bridge to the native C++ planes solver (kubernetes_tpu.native).

Same backend interface and planes layout as the JAX backends; state is
host numpy mutated in place by the library, so the cross-batch carry is
free. Serves as the CPU-native solve path and as an independent
implementation for differential testing of the device kernels.
"""

from __future__ import annotations

import ctypes

import numpy as np

from kubernetes_tpu import native
from kubernetes_tpu.ops.pallas_solver import (
    PState,
    _state_planes,
    prepare,
)
from kubernetes_tpu.ops.solver import SolverParams

_I32P = ctypes.POINTER(ctypes.c_int32)
_F32P = ctypes.POINTER(ctypes.c_float)


def available() -> bool:
    return native.load() is not None


class CppBackend:
    """Native solve backend (see session.py for the chain)."""

    name = "cpp"

    def __init__(self):
        self._lib = native.load()
        if self._lib is None:
            raise RuntimeError("native solver library unavailable")

    def prepare(self, cluster, batch):
        return prepare(cluster, batch, device=False)

    def solve_lazy(self, params, pstatic, pstate, pod_ints, pod_floats):
        """The native solver is synchronous; lazy == eager here."""
        return self.solve(params, pstatic, pstate, pod_ints, pod_floats)

    @staticmethod
    def materialize(handle):
        return handle

    @staticmethod
    def warm_state(pstate):
        """The state as a solve may take it while ``pstate`` stays
        valid: the solve mutates its planes in place, so a copy."""
        return PState(planes=pstate.planes.copy())

    # -------- device-resident mirror scatter hooks (ops.mirror): the
    # planes are host numpy mutated in place, so a "scatter" is a fancy
    # index update and zero bytes cross any link
    @staticmethod
    def scatter_state_add(pstate, rows, cols, vals):
        planes = pstate.planes
        flat = planes.reshape(planes.shape[0], -1)
        np.add.at(flat, (rows, cols), vals)
        return pstate, 0

    @staticmethod
    def scatter_static_set(pstatic, rows, cols, vals):
        flat = pstatic.ints.reshape(pstatic.ints.shape[0], -1)
        flat[rows, cols] = vals
        return pstatic, 0

    def solve(self, params: SolverParams, pstatic, pstate, pod_ints,
              pod_floats):
        planes = pstate.planes  # [CD, NB, 128] int32, C-contiguous
        n = planes.shape[1] * planes.shape[2]
        sv = pstatic.sv
        do, _ = _state_planes(pstatic.r, pstatic.sc, pstatic.t, sv)
        b, c_cols = pod_ints.shape
        expected = pstatic.r + 4 + 2 * pstatic.sc + 3 * pstatic.t \
            + (2 if sv else 0)
        if c_cols != expected:
            # mirror _unpack_podin's loud failure: misaligned columns
            # would silently corrupt every assignment
            raise ValueError(
                f"packed pod stream width {c_cols} does not match the "
                f"static constraint space (expected {expected})"
            )
        assignments = np.empty(b, dtype=np.int32)
        weights = np.array(
            [params.balanced_weight, params.least_weight,
             params.spread_weight, params.affinity_weight,
             params.static_weight],
            dtype=np.float32,
        )
        pod_ints = np.ascontiguousarray(pod_ints, dtype=np.int32)
        pod_floats = np.ascontiguousarray(pod_floats, dtype=np.float32)
        totals = planes[do["totals"]].reshape(-1)  # flat [:t] slots
        rc = self._lib.ktpu_solve(
            pstatic.ints.ctypes.data_as(_I32P),
            pstatic.f32s.ctypes.data_as(_F32P),
            np.ascontiguousarray(
                pstatic.sc_meta, dtype=np.int32
            ).ctypes.data_as(_I32P),
            planes.ctypes.data_as(_I32P),
            totals.ctypes.data_as(_I32P),
            pod_ints.ctypes.data_as(_I32P),
            pod_floats.ctypes.data_as(_F32P),
            assignments.ctypes.data_as(_I32P),
            weights.ctypes.data_as(_F32P),
            pstatic.r, pstatic.sc, pstatic.t, pstatic.u, pstatic.v,
            n, b, c_cols, sv,
        )
        if rc != 0:
            raise RuntimeError(f"ktpu_solve failed (rc={rc})")
        return assignments, PState(planes=planes)
