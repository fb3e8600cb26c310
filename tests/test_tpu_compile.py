"""The main path's solvers compiled for a described TPU v5e.

Nothing runs: the TPU compiler, installed here, compiles for a chip that
is described and not attached, and refuses what the chip would refuse
(a kernel over its VMEM, a program over its memory). Every compile here
is what ``chip_smoke.py`` and the bench run on the chip.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers all import this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from kubernetes_tpu.ops import pallas_solver as ps
from kubernetes_tpu.ops.solver import SolverParams

V5E_VMEM = 128 << 20   # per core: pltpu.get_tpu_info() on the chip
PODS = 4096            # the sidecar's batch (max_batch)
R = 3                  # cpu, memory, pods


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        from jax.experimental import topologies

        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        compilation_cache.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _dims(n_nodes, sc, t, u):
    nb = -(-n_nodes // ps.LANES)
    return dict(r=R, sc=sc, t=t, u=u, nb=nb,
                cs=ps._static_planes(R, sc, t, u)[1],
                cd=ps._state_planes(R, sc, t)[1],
                c_cols=R + 4 + 2 * sc + 3 * t, t_cols=t)


def _compile_kernel(one_chip, n_nodes, sc, t, u):
    d = _dims(n_nodes, sc, t, u)
    call = ps._get_call(
        SolverParams(), d["r"], sc, t, u, n_nodes, d["nb"], PODS,
        d["c_cols"], d["t_cols"], d["cd"], False,
        ps.vmem_limit_bytes(V5E_VMEM))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return call.lower(
        s((2, sc), jnp.int32), s((PODS, d["c_cols"]), jnp.int32),
        s((PODS, d["t_cols"]), jnp.float32),
        s((d["cs"], d["nb"], ps.LANES), jnp.int32),
        s((u, d["nb"], ps.LANES), jnp.float32),
        s((d["cd"], d["nb"], ps.LANES), jnp.int32),
    ).compile()


@pytest.mark.parametrize("sc_t_u", [1, 8], ids=["sc-t-u-1", "caps"])
def test_pallas_kernel_compiles_at_5k_nodes(one_chip, sc_t_u):
    compiled = _compile_kernel(one_chip, 5000, sc_t_u, sc_t_u, sc_t_u)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the stable names a profiler trace shows: module and kernel op
    assert text.startswith("HloModule jit_ktpu_solve")
    assert "%ktpu_solve" in text


def test_50k_caps_kernel_compiles_under_the_raised_vmem_limit(one_chip):
    """The 50k-node kernel at the SC/T/U caps is over the compiler's
    default 16 MiB scoped VMEM limit; under the raised limit it fits
    and compiles, so ``_pallas_fits`` keeps it on the kernel."""
    d = _dims(50000, 8, 8, 8)
    limit = ps.vmem_limit_bytes(V5E_VMEM)
    assert not ps.kernel_fits_vmem(R, 8, 8, 8, d["nb"], 16 << 20)
    assert ps.kernel_fits_vmem(R, 8, 8, 8, d["nb"], limit)
    compiled = _compile_kernel(one_chip, 50000, 8, 8, 8)
    assert "tpu_custom_call" in compiled.as_text()


def test_planes_beyond_vmem_route_to_the_scan():
    """A cluster whose planes outgrow v5e's VMEM is a shape the kernel
    declines before any compile."""
    nb = -(-200000 // ps.LANES)
    assert not ps.kernel_fits_vmem(R, 8, 8, 8, nb,
                                   ps.vmem_limit_bytes(V5E_VMEM))


def test_planes_scan_compiles_at_5k_nodes(one_chip):
    d = _dims(5000, 8, 8, 8)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = ps._xla_planes_solve.lower(
        SolverParams(), R, 8, 8, 8, 5000,
        s((2, 8), jnp.int32), s((d["cs"], d["nb"], ps.LANES), jnp.int32),
        s((8, d["nb"], ps.LANES), jnp.float32),
        s((d["cd"], d["nb"], ps.LANES), jnp.int32),
        s((PODS, d["c_cols"]), jnp.int32), s((PODS, 8), jnp.float32),
    ).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_sharded_solve_compiles_on_a_4_chip_mesh(topo):
    """The mesh tier's solve over the four described chips: node planes
    sharded on the ``nodes`` axis, with each device's memory counted."""
    from kubernetes_tpu.parallel.sharded import _build_solve

    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4),
                axis_names=("batch", "nodes"))
    sc = t = u = 1
    n = 50048   # 50k nodes padded to a multiple of 4 x 128
    cs = ps._static_planes(R, sc, t, u)[1]
    cd = ps._state_planes(R, sc, t)[1]
    c_cols = R + 4 + 2 * sc + 3 * t
    run = _build_solve(mesh, SolverParams(), R, sc, t, u, 10,
                       with_counts=False, any_hard=True, donate=True)
    nodes = NamedSharding(mesh, P(None, "nodes"))
    rep = NamedSharding(mesh, P())

    def s(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    ints = s((PODS, c_cols), jnp.int32, rep)
    compiled = run.lower(
        s((2, sc), jnp.int32, rep), s((cs, n), jnp.int32, nodes),
        s((u, n), jnp.float32, nodes), s((cd, n), jnp.int32, nodes),
        s((t,), jnp.int32, rep), ints, s((PODS, t), jnp.float32, rep),
        ints, s((u, sc), jnp.bool_, rep),
    ).compile()
    mem = compiled.memory_analysis()
    # per device: a quarter of the node planes, well inside 16 GB of HBM
    plane_bytes = 4 * (cs + u + cd) * n
    assert mem.argument_size_in_bytes < plane_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    hlo = compiled.as_text()
    assert "all-gather" in hlo or "all-reduce" in hlo
