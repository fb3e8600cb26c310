"""Devices×throughput scaling bench — the ``devscale`` row — plus the
legacy multi-chip scaling-shape bench, behind ONE virtual-device
bootstrap.

The sharded-by-default solve (``ops.session.default_backend`` mesh
tier) claims the hardware, not the host, is the ceiling; this harness
is its proof surface. Because a JAX process fixes its device count at
backend init, every arm runs in a SPAWNED child whose environment
forces the device count (``XLA_FLAGS=--xla_force_host_platform_
device_count=N``) before the interpreter touches JAX — the same
mechanism tests/conftest.py uses, now living in exactly one place
(``ensure_virtual_devices``; ``bench_sharded.py`` is a thin shim over
it, so the committed ``sharded_scaling.log`` workflow keeps working
without a second diverging copy of the bootstrap).

Each child runs the workload END-TO-END through the sidecar with the
DEFAULT backend selection (``KTPU_SOLVER=auto`` → the mesh tier
whenever >1 device is visible; the 1-device reference arm pins
``KTPU_SOLVER=xla``, the same planes scan the mesh distributes — a
1-device "auto" child would pick the native C++ solver where it
builds, and the row would measure backend choice, not sharding) and
reports:

- ``pods_per_second`` — end-to-end, Amdahl-bounded by the host-side
  encode/commit pipeline (reported for honesty, not the scaling
  claim);
- ``solve_pods_per_sec`` — measured pods over the device solve phase,
  the devices×throughput number the row's ``value`` carries;
- per-arm devprof telemetry — ``device_wait_share``, per-cycle
  h2d/d2h/donated bytes — so the donation A/B (``KTPU_SHARDED_DONATE``
  on vs off at one mesh width) shows transfer bytes and device-wait
  share strictly lower with donation on.

Run via ``python bench.py --config devscale`` (or directly:
``python -m kubernetes_tpu.harness.devscale``). Absolute CPU rates say
nothing about TPU rates; the SHAPE — solve throughput vs device count
at fixed problem size — is the evidence that node-axis sharding pays
before multi-chip hardware exists.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

# default scales: the 50k-node tier (the plane size PR 9's partitioned
# fabric already drives) — big enough that per-pod compute dominates
# the per-pod collective (latency-bound, shard-count-dependent), the
# regime real multi-chip clusters live in. On shared-silicon virtual
# devices the 1-device baseline is itself intra-op multithreaded, so
# measured efficiency UNDERSTATES what real ICI meshes get; the shape
# (solve throughput growing with mesh width), not the efficiency, is
# the claim a virtual-device row can make.
FULL_NODES, FULL_PODS, FULL_BATCH = 51_200, 8_192, 2_048
QUICK_NODES, QUICK_PODS, QUICK_BATCH = 1_024, 2_048, 1_024

_FLAG = "xla_force_host_platform_device_count"

# the package ships without an installer: children spawned with
# ``-m kubernetes_tpu.harness.devscale`` can only import it with the
# repo root on their path, wherever the PARENT was invoked from
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def child_env(devices: int) -> Dict[str, str]:
    """A child-process environment with the virtual-device bootstrap
    applied AND the repo root importable (PYTHONPATH) — the parent may
    have been launched from any cwd."""
    env = ensure_virtual_devices(devices, dict(os.environ))
    env["PYTHONPATH"] = _REPO_ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def ensure_virtual_devices(n: int,
                           env: Optional[Dict[str, str]] = None,
                           ) -> Dict[str, str]:
    """THE spawn-with-XLA_FLAGS bootstrap: force an ``n``-device CPU
    host platform. With ``env=None`` mutates ``os.environ`` — which
    only works BEFORE any JAX backend initializes in this interpreter
    (the bench_sharded.py / conftest.py pattern); pass a copied env to
    prepare a child process instead."""
    target = os.environ if env is None else env
    flags = target.get("XLA_FLAGS", "")
    if _FLAG in flags:
        flags = re.sub(rf"--{_FLAG}=\d+", f"--{_FLAG}={n}", flags)
    else:
        flags = (flags + f" --{_FLAG}={n}").strip()
    target["XLA_FLAGS"] = flags
    return target


def force_cpu_platform() -> None:
    """Pin this process to the CPU for a virtual-device mesh: set via
    jax.config AFTER import, BEFORE first backend use, so it wins over
    a JAX_PLATFORMS the caller's environment already set."""
    import jax

    jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# child side: one measured arm, end-to-end through the sidecar


def run_devscale_arm(workload: str, nodes: int, pods: int,
                     max_batch: int, donate: bool,
                     wait_timeout: float = 3600.0) -> dict:
    """One end-to-end arm on THIS interpreter's device count. The
    backend comes from the session's default tier (KTPU_SOLVER in the
    environment), so the arm measures the actual default path."""
    force_cpu_platform()
    import jax

    devices = len(jax.devices())
    from kubernetes_tpu.harness import make_workload
    from kubernetes_tpu.harness.perf import run_workload

    seg: dict = {}
    mesh_info: dict = {}

    def hook(sched, bs):
        series = sched.metrics.batch_solve_duration._series
        for key, (_counts, total, count) in series.items():
            seg[key[0]] = (total, count)
        if bs is not None:
            mi = bs.mesh_info()
            if mi:
                mesh_info.update(mi)

    ops = make_workload(workload, nodes=nodes, init_pods=0,
                        measure_pods=pods)
    t0 = time.time()
    # adaptive_chunk=False: every arm must solve the IDENTICAL batch
    # partition, or the latency tuner shrinks the slow arms' chunks and
    # the comparison measures the tuner, not the sharding
    r = run_workload(
        f"{workload}/devscale-{devices}dev"
        + ("" if donate else "-nodonate"),
        ops, use_batch=True, max_batch=max_batch,
        wait_timeout=wait_timeout, progress=log, result_hook=hook,
        adaptive_chunk=False,
    )
    _, dev_batches = seg.get("device", (0.0, 0))
    tel = r.telemetry or {}
    cycles = max(int(tel.get("cycles", 0)), 1)
    # solve time from the devprof dispatch+block split, NOT the session
    # "device" histogram segment: with lazy pipelined solves the
    # histogram measures dispatch only (the block lands cycles later in
    # the commit pipeline), while devprof attributes the measured block
    # wait back to the cycle that dispatched it — the same number on
    # every arm, whichever side of the pipeline the wait surfaces on
    dev_total = float(tel.get("dispatch_s", 0.0)) \
        + float(tel.get("block_s", 0.0))
    return {
        "devices": devices,
        "donated": bool(donate),
        "pods_per_second": round(r.pods_per_second, 1),
        "p99_latency_ms": round(r.metrics.get("Perc99", 0)),
        "device_solve_s": round(dev_total, 3),
        "solve_batches": dev_batches,
        "solve_pods_per_sec": round(pods / dev_total, 1)
        if dev_total > 0 else 0.0,
        "device_wait_share": tel.get("device_wait_share", 0.0),
        "h2d_bytes_per_cycle": int(tel.get("h2d_bytes", 0) / cycles),
        "d2h_bytes_per_cycle": int(tel.get("d2h_bytes", 0) / cycles),
        "donated_bytes_per_cycle": int(
            tel.get("donated_bytes", 0) / cycles),
        "telemetry": tel,
        "mesh": mesh_info
        or {"devices": devices, "shards": 1, "donated": False},
        "wall_s": round(time.time() - t0, 1),
    }


# ---------------------------------------------------------------------------
# parent side: spawn one child per arm, assemble the row


def _spawn_arm(devices: int, workload: str, nodes: int, pods: int,
               max_batch: int, donate: bool,
               timeout: float = 3600.0) -> dict:
    env = child_env(devices)
    # the sharded-by-default tier under test: auto → mesh whenever >1
    # device; the 1-device reference pins the planes scan the mesh
    # distributes (see module docstring)
    env["KTPU_SOLVER"] = "auto" if devices > 1 else "xla"
    env["KTPU_SHARDED_DONATE"] = "1" if donate else "0"
    cmd = [
        sys.executable, "-m", "kubernetes_tpu.harness.devscale",
        "--child", "--workload", workload, "--nodes", str(nodes),
        "--pods", str(pods), "--max-batch", str(max_batch),
    ]
    if not donate:
        cmd.append("--no-donate")
    proc = subprocess.run(cmd, env=env, stdout=subprocess.PIPE,
                          text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"devscale child (devices={devices}, donate={donate}) "
            f"exited {proc.returncode}")
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError("devscale child produced no row JSON")


def _ab_view(arm: dict) -> dict:
    """The donation-A/B slice of one arm: exactly the fields the
    acceptance bar names, per cycle."""
    return {
        "device_wait_share": arm["device_wait_share"],
        "h2d_bytes_per_cycle": arm["h2d_bytes_per_cycle"],
        "d2h_bytes_per_cycle": arm["d2h_bytes_per_cycle"],
        "donated_bytes_per_cycle": arm["donated_bytes_per_cycle"],
        "solve_pods_per_sec": arm["solve_pods_per_sec"],
        "pods_per_second": arm["pods_per_second"],
    }


def run_devscale_row(nodes: int = FULL_NODES, pods: int = FULL_PODS,
                     max_batch: int = FULL_BATCH,
                     device_counts: Sequence[int] = (1, 2, 4, 8),
                     donation_ab_devices: int = 4,
                     workload: str = "SchedulingBasic",
                     timeout: float = 3600.0,
                     progress=log) -> dict:
    """The devices×throughput row: one spawned child per device count
    (donation on), plus one donation-off child at ``donation_ab_devices``
    for the before/after telemetry A/B. ``value`` is the solve
    throughput at the A/B mesh width — the number the scaling claim is
    about; end-to-end pods/s rides each arm for honesty."""
    arms: List[dict] = []
    for d in device_counts:
        progress(f"--- devscale: {d} device(s), donation on ---")
        arms.append(_spawn_arm(d, workload, nodes, pods, max_batch,
                               donate=True, timeout=timeout))
    base = next((a for a in arms if a["devices"] == 1), None)
    for a in arms:
        if base and a["device_solve_s"] > 0 \
                and base["device_solve_s"] > 0:
            a["solve_speedup_vs_1dev"] = round(
                base["device_solve_s"] / a["device_solve_s"], 2)
    ab = None
    if donation_ab_devices in [a["devices"] for a in arms]:
        progress(f"--- devscale: {donation_ab_devices} device(s), "
                 f"donation OFF (A/B arm) ---")
        off = _spawn_arm(donation_ab_devices, workload, nodes, pods,
                         max_batch, donate=False, timeout=timeout)
        on = next(a for a in arms
                  if a["devices"] == donation_ab_devices)
        ab = {
            "devices": donation_ab_devices,
            "on": _ab_view(on),
            "off": _ab_view(off),
            # the acceptance bar: per-cycle transfer bytes (BOTH
            # directions — solver_transfer_bytes_total counts h2d and
            # d2h) AND device wait share strictly lower with donation on
            "donation_pays": (
                on["h2d_bytes_per_cycle"] < off["h2d_bytes_per_cycle"]
                and on["d2h_bytes_per_cycle"]
                < off["d2h_bytes_per_cycle"]
                and on["device_wait_share"] < off["device_wait_share"]
            ),
        }
    anchor = next((a for a in arms if a["devices"] == 4), arms[-1])
    row = {
        "metric": f"solve_throughput_devscale[{workload} {nodes}nodes/"
                  f"{pods}pods]",
        "value": anchor["solve_pods_per_sec"],
        "unit": "pods/s",
        "devices": anchor["devices"],
        # this harness always forces shared-silicon virtual devices:
        # the 1-device baseline is itself intra-op multithreaded, so
        # efficiency understates real meshes — perf_report's 0.6
        # efficiency gate applies to real-hardware rows only (the
        # ≥1.5× speedup bar and the donation A/B apply everywhere)
        "virtual_devices": True,
        "arms": arms,
        "solve_speedup_vs_1dev": {
            str(a["devices"]): a.get("solve_speedup_vs_1dev", 1.0)
            for a in arms
        },
    }
    four = next((a for a in arms if a["devices"] == 4), None)
    if four is not None and "solve_speedup_vs_1dev" in four:
        row["scaling_efficiency_4dev"] = round(
            four["solve_speedup_vs_1dev"] / 4.0, 3)
    if ab is not None:
        row["donation_ab"] = ab
    return row


# ---------------------------------------------------------------------------
# REST row on the sharded default: the deployable-fabric A/B


def run_rest_arm(nodes: int, pods: int, qps: Optional[float],
                 max_batch: int, wait_timeout: float = 1800.0) -> dict:
    """One REST-fabric arm on THIS interpreter's device count: the
    headline workload with every byte over HTTP (apiserver child, WAL,
    watch-fed scheduler), the scheduler's solve backend coming from
    the DEFAULT tier — so a multi-device interpreter runs the REST row
    on the sharded-by-default solve."""
    force_cpu_platform()
    import jax

    devices = len(jax.devices())
    from kubernetes_tpu.harness.rest_perf import run_workload_rest

    mesh_info: dict = {}

    def hook(sched, bs):
        if bs is not None:
            mi = bs.mesh_info()
            if mi:
                mesh_info.update(mi)

    t0 = time.time()
    r = run_workload_rest(
        "SchedulingBasic", nodes=nodes, measure_pods=pods,
        max_batch=max_batch, qps=qps, wait_timeout=wait_timeout,
        progress=log, result_hook=hook,
    )
    tel = r.telemetry or {}
    return {
        "devices": devices,
        "pods_per_second": round(r.pods_per_second, 1),
        "p99_latency_ms": round(r.metrics.get("Perc99", 0)),
        "server_pods_bound": r.metrics.get("server_pods_bound"),
        "device_wait_share": tel.get("device_wait_share", 0.0),
        "solve_s": round(float(tel.get("dispatch_s", 0.0))
                         + float(tel.get("block_s", 0.0)), 3),
        "mesh": mesh_info
        or {"devices": devices, "shards": 1, "donated": False},
        "wall_s": round(time.time() - t0, 1),
    }


def run_rest_sharded_ab(nodes: int, pods: int,
                        qps: Optional[float] = 5000.0,
                        max_batch: int = 2048, devices: int = 4,
                        timeout: float = 3600.0,
                        progress=log) -> dict:
    """The REST row A/B'd over the sharded default: one child with the
    mesh tier (``devices`` virtual devices, KTPU_SOLVER=auto), one on
    the single-device planes scan — the deployable-system view of the
    sharded-by-default solve. On real multi-chip hardware the sharded
    arm is the one that makes the hardware, not the fabric, the
    ceiling; on shared-silicon virtual devices the arm documents the
    PATH (mesh solve under the full REST pipeline), not a CPU win."""

    def spawn(dev_count: int) -> dict:
        env = child_env(dev_count)
        env["KTPU_SOLVER"] = "auto" if dev_count > 1 else "xla"
        env["KTPU_SHARDED_DONATE"] = "1"
        cmd = [
            sys.executable, "-m", "kubernetes_tpu.harness.devscale",
            "--child-rest", "--nodes", str(nodes), "--pods", str(pods),
            "--max-batch", str(max_batch),
            "--qps", str(qps if qps else 0),
        ]
        proc = subprocess.run(cmd, env=env, stdout=subprocess.PIPE,
                              text=True, timeout=timeout)
        if proc.returncode != 0:
            raise RuntimeError(
                f"rest-ab child (devices={dev_count}) exited "
                f"{proc.returncode}")
        for line in reversed(proc.stdout.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
        raise RuntimeError("rest-ab child produced no row JSON")

    progress(f"--- rest-ab: sharded default, {devices} device(s) ---")
    sharded = spawn(devices)
    progress("--- rest-ab: single-device reference ---")
    single = spawn(1)
    return {
        "sharded": sharded,
        "single_device": single,
        "sharded_vs_single": round(
            sharded["pods_per_second"]
            / max(single["pods_per_second"], 1e-9), 3),
    }


# ---------------------------------------------------------------------------
# legacy multi-chip scaling-shape bench (folded in from bench_sharded.py
# — the committed sharded_scaling.log workflow)


def _measure_sharded_cpu(name: str, nodes: int, pods: int, devices: int,
                         init_pods: int = 0) -> dict:
    """One end-to-end run; returns the JSON row. devices=1 uses the
    single-device planes scan, >1 the mesh-sharded backend."""
    from kubernetes_tpu.harness import make_workload, run_workload

    if devices == 1:
        def backend_factory():
            from kubernetes_tpu.ops.pallas_solver import XlaPlanesBackend

            return XlaPlanesBackend()
    else:
        def backend_factory():
            from kubernetes_tpu.parallel import ShardedBackend, make_mesh

            return ShardedBackend(make_mesh(devices, batch_axis=1))

    seg = {}
    mem = {}

    def _shard_bytes(x) -> int:
        """Bytes ONE device holds for array x (sharded arrays report a
        single shard; replicated/host arrays their full size)."""
        try:
            return x.addressable_shards[0].data.nbytes
        except Exception:  # noqa: BLE001 — numpy / non-jax fields
            return int(getattr(x, "nbytes", 0))

    def hook(sched, bs):
        series = sched.metrics.batch_solve_duration._series
        for key, (_counts, total, count) in series.items():
            seg[key[0]] = (total, count)
        # per-device footprint of the resident mirror (static planes +
        # carried state): the multi-chip memory story — per-device bytes
        # shrink ~1/N with the node axis sharded, so clusters larger
        # than one chip's HBM fit the mesh
        import dataclasses

        total_b = 0
        for obj in (bs.session._static, bs.session._state):
            if obj is None:
                continue
            if dataclasses.is_dataclass(obj):
                for f in dataclasses.fields(obj):
                    v = getattr(obj, f.name)
                    if hasattr(v, "nbytes") or hasattr(
                            v, "addressable_shards"):
                        total_b += _shard_bytes(v)
            elif isinstance(obj, (tuple, list)):
                for v in obj:
                    total_b += _shard_bytes(v)
        mem["per_device_bytes"] = total_b

    ops = make_workload(name, nodes=nodes, init_pods=init_pods,
                        measure_pods=pods)
    t0 = time.time()
    # adaptive_chunk=False: every mesh size must solve the IDENTICAL
    # batch partition (the latency tuner would shrink slow
    # configurations' chunks and inflate their batch counts — round-3's
    # 13-vs-29 artifact measured the tuner, not the sharding)
    r = run_workload(
        f"{name}/sharded-{devices}dev", ops, use_batch=True,
        max_batch=4096, wait_timeout=3600, progress=log,
        backend_factory=backend_factory, result_hook=hook,
        adaptive_chunk=False,
    )
    dev_total, dev_batches = seg.get("device", (0.0, 0))
    return {
        "metric": f"sharded_cpu[{name} {nodes}nodes/{pods}pods]",
        "devices": devices,
        "pods_per_second": round(r.pods_per_second, 1),
        "device_solve_s": round(dev_total, 3),
        "solve_batches": dev_batches,
        "mirror_bytes_per_device": mem.get("per_device_bytes", 0),
        "wall_s": round(time.time() - t0, 1),
    }


def _breakdown(n_nodes: int, batch_pods: int, device_counts) -> list:
    """Per-batch compute-vs-collective split on one representative
    solve batch. The ablated build (``collectives=False``) replaces
    every cross-shard op with a local stand-in of identical arithmetic
    shape, so full-minus-ablated wall time isolates pure collective
    cost — the quantity shared-silicon virtual devices inflate (every
    shard's collective work serializes onto the same cores) and real
    ICI does not."""
    import jax

    from kubernetes_tpu.ops import BatchEncoder
    from kubernetes_tpu.ops.pallas_solver import XlaPlanesBackend
    from kubernetes_tpu.ops.solver import SolverParams, pack_podin
    from kubernetes_tpu.parallel.sharded import (
        _build_solve,
        _prepare_sharded,
        make_mesh,
    )
    from kubernetes_tpu.scheduler.snapshot import new_snapshot
    from kubernetes_tpu.testing import MakeNode, MakePod

    nodes = [
        MakeNode().name(f"n{i}")
        .capacity({"cpu": "32", "memory": "64Gi", "pods": "110"}).obj()
        for i in range(n_nodes)
    ]
    pods = [
        MakePod().name(f"p{i}").uid(f"u{i}")
        .req({"cpu": "100m", "memory": "200Mi"}).obj()
        for i in range(batch_pods)
    ]
    snap = new_snapshot([], nodes)
    cluster, batch = BatchEncoder(snap, pad_nodes=128).encode(
        pods, pad_pods=batch_pods
    )
    params = SolverParams()
    ints, floats = pack_podin(batch)

    def timed(fn, reps: int = 3) -> float:
        fn()  # warm (compile)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    rows = []
    # single-device reference: the same planes scan the sharded build
    # distributes
    be = XlaPlanesBackend()
    static1, state1 = be.prepare(cluster, batch)
    base_s = timed(
        lambda: be.solve(params, static1, state1, ints, floats)[0]
    )
    rows.append({
        "metric": f"sharded_breakdown[{n_nodes}nodes/{batch_pods}pod-batch]",
        "devices": 1, "batch_solve_s": round(base_s, 3),
        "compute_s": round(base_s, 3), "collective_s": 0.0,
        "collective_frac": 0.0,
    })
    # 1-shard control: the SAME shard_map build on a 1-device mesh —
    # collectives are no-ops, so (control - planes-scan baseline)
    # isolates the shard_map machinery's constant overhead from
    # anything that scales with shard count
    for d in [1] + list(device_counts):
        mesh = make_mesh(d, batch_axis=1)
        sstatic, sstate = _prepare_sharded(cluster, batch, mesh)
        args = (sstatic.sc_meta, sstatic.ints, sstatic.f32s,
                sstate.planes, sstate.totals, ints, floats, ints,
                sstatic.has_dom)
        times = {}
        for collectives in (True, False):
            run = _build_solve(
                mesh, params, sstatic.r, sstatic.sc, sstatic.t,
                sstatic.u, sstatic.v, with_counts=False,
                any_hard=sstatic.any_hard, collectives=collectives,
            )
            with mesh:
                times[collectives] = timed(lambda: run(*args)[0])
        coll = max(times[True] - times[False], 0.0)
        rows.append({
            "metric":
                f"sharded_breakdown[{n_nodes}nodes/{batch_pods}pod-batch]"
                + ("(1-shard shard_map control)" if d == 1 else ""),
            "devices": d,
            "batch_solve_s": round(times[True], 3),
            "compute_s": round(times[False], 3),
            "collective_s": round(coll, 3),
            "collective_frac": round(coll / max(times[True], 1e-9), 3),
        })
    return rows


def run_sharded_cpu(quick: bool = False,
                    breakdown_only: bool = False) -> None:
    """The legacy scaling-shape flow (sharded_scaling.log): end-to-end
    rows per mesh size, the preemption family, and the per-batch
    compute/collective breakdown. Must own the interpreter's JAX
    platform — call ``ensure_virtual_devices(8)`` before any backend
    initializes (the bench_sharded.py shim does)."""
    force_cpu_platform()
    import jax

    n_dev = len(jax.devices())
    if n_dev < 8:
        log(f"WARNING: only {n_dev} CPU devices (wanted 8); "
            "XLA_FLAGS was set too late for this interpreter — run "
            "bench_sharded.py (or -m kubernetes_tpu.harness.devscale "
            "--sharded-cpu) directly")
    name = "SchedulingBasic"
    nodes, pods = (512, 4096) if quick else (5000, 30000)
    rows = []
    for devices in (1, 2, 4, 8):
        if devices > n_dev or breakdown_only:
            continue
        log(f"--- {devices} device(s) ---")
        rows.append(_measure_sharded_cpu(name, nodes, pods, devices))
    # preemption-heavy scaling row (VERDICT r4 next #4): the mass-
    # decline -> vectorized screen -> victim-planner flow on the mesh
    # path; fillers exactly fill the cluster so every measured pod
    # preempts
    p_nodes, p_pods = (256, 256) if quick else (1000, 1000)
    for devices in (1, 8):
        if devices > n_dev or breakdown_only:
            continue
        log(f"--- Preemption, {devices} device(s) ---")
        row = _measure_sharded_cpu("Preemption", p_nodes, p_pods,
                                   devices, init_pods=p_nodes)
        print(json.dumps(row), flush=True)
    base = next((r for r in rows if r["devices"] == 1), None)
    for r in rows:
        if base and r["device_solve_s"] > 0:
            r["solve_speedup_vs_1dev"] = round(
                base["device_solve_s"] / r["device_solve_s"], 2
            )
        print(json.dumps(r), flush=True)
    log("--- per-batch compute/collective breakdown ---")
    bd_nodes, bd_pods = (512, 1024) if quick else (5000, 4096)
    for row in _breakdown(bd_nodes, bd_pods,
                          [d for d in (2, 4, 8) if d <= n_dev]):
        print(json.dumps(row), flush=True)


# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true",
                    help="run ONE spawned arm on this interpreter's "
                         "forced device count")
    ap.add_argument("--child-rest", action="store_true",
                    help="run ONE spawned REST-fabric arm")
    ap.add_argument("--rest-ab", action="store_true",
                    help="REST row A/B: sharded default vs "
                         "single-device")
    ap.add_argument("--qps", type=float, default=5000.0)
    ap.add_argument("--sharded-cpu", action="store_true",
                    help="legacy scaling-shape flow "
                         "(sharded_scaling.log)")
    ap.add_argument("--workload", default="SchedulingBasic")
    ap.add_argument("--nodes", type=int, default=FULL_NODES)
    ap.add_argument("--pods", type=int, default=FULL_PODS)
    ap.add_argument("--max-batch", type=int, default=FULL_BATCH)
    ap.add_argument("--no-donate", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--breakdown-only", action="store_true")
    args = ap.parse_args(argv)

    if args.sharded_cpu:
        # the bootstrap must land before ANY jax import resolves a
        # backend — module import above is jax-free, and
        # run_sharded_cpu only imports jax inside, so this is in time
        # whether we were spawned by bench.py or invoked directly
        ensure_virtual_devices(8)
        run_sharded_cpu(quick=args.quick,
                        breakdown_only=args.breakdown_only)
        return
    if args.child:
        os.environ["KTPU_SHARDED_DONATE"] = \
            "0" if args.no_donate else "1"
        os.environ.setdefault("KTPU_SOLVER", "auto")
        row = run_devscale_arm(args.workload, args.nodes, args.pods,
                               args.max_batch,
                               donate=not args.no_donate)
        print(json.dumps(row), flush=True)
        return
    if args.child_rest:
        os.environ.setdefault("KTPU_SOLVER", "auto")
        row = run_rest_arm(args.nodes, args.pods,
                           qps=args.qps or None,
                           max_batch=args.max_batch)
        print(json.dumps(row), flush=True)
        return
    if args.rest_ab:
        nodes, pods = (1024, 4096) if args.quick else (5000, 30000)
        ab = run_rest_sharded_ab(nodes, pods, qps=args.qps or None,
                                 max_batch=args.max_batch)
        print(json.dumps({
            "metric": f"rest_sharded_ab[SchedulingBasic {nodes}nodes/"
                      f"{pods}pods]", **ab}), flush=True)
        return
    if args.quick:
        row = run_devscale_row(
            nodes=QUICK_NODES, pods=QUICK_PODS, max_batch=QUICK_BATCH,
            device_counts=(1, 2), donation_ab_devices=2)
    else:
        row = run_devscale_row()
    print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
