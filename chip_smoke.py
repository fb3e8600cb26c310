"""Chip smoke run: the batched scheduler's main path, once, on a TPU.

This is a smoke run, not a benchmark: every time it prints includes
compilation and is there to find where a run got stuck.

One chip (the default):

1. device: the TPU JAX reports; any other platform fails at once;
2. kernel: one seeded 4,096-pod batch (plain, hard topology-spread and
   pod anti-affinity pods) on a seeded 5,000-node cluster, solved by the
   backend ``default_backend()`` picks (the Pallas kernel) and by the
   scan solver on the host CPU; the assignments must be equal;
3. end to end: ``harness.perf.run_workload`` with the batch path, as
   ``bench.py --config headline`` calls it, on SchedulingBasic (5,000
   nodes, 30,000 pods) and TopologySpreading (5,000 nodes, 5,000 pods).
   Every pod must be bound, no pod may fall back to the serial path,
   and the Pallas backend must have served the batches.

``--chips 4`` runs only the mesh path: the sharded backend over four
chips against the host reference at 5,000 and 50,000 nodes, with the
node-column shards on four distinct devices, and SchedulingBasic at
5,000 nodes end to end.

The last line of stdout is the result, printed only when every phase
passed: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

KERNEL_NODES = 5000
KERNEL_PODS = 4096
E2E_CELLS = (("SchedulingBasic", 5000, 30000),
             ("TopologySpreading", 5000, 5000))
MESH_NODES = (5000, 50000)
MESH_E2E = ("SchedulingBasic", 5000, 30000)


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_phase(chips: int) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    log(f"jax {jax.__version__} devices={devices}")
    log(f"device platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    check(info["platform"] == "tpu",
          f"no TPU found: JAX reports platform {info['platform']!r}")
    check(info["count"] == chips,
          f"this run needs exactly {chips} TPU device(s); JAX shows "
          f"{info['count']} (use --chips {info['count']})")
    return info


def mixed_batch(n_nodes: int, n_pods: int, seed: int):
    """Seeded cluster with zone labels and one batch mixing plain, hard
    topology-spread and pod anti-affinity pods, encoded as the session
    encodes it."""
    import numpy as np

    from kubernetes_tpu.api.types import Node, Pod
    from kubernetes_tpu.harness.workloads import (
        _affinity, _spread, basic_pod, node_template)
    from kubernetes_tpu.ops.encode import BatchEncoder
    from kubernetes_tpu.scheduler.snapshot import new_snapshot

    rng = np.random.default_rng(seed)
    node_cpu = rng.choice(["16", "32", "64"], size=n_nodes)
    nodes = [Node.from_dict(node_template(i, cpu=str(node_cpu[i])))
             for i in range(n_nodes)]
    kinds = rng.integers(0, 3, size=n_pods)
    cpus = rng.choice(["100m", "250m", "500m", "1"], size=n_pods)
    pods = []
    for i in range(n_pods):
        if kinds[i] == 1:
            d = basic_pod(i, cpu=str(cpus[i]), labels={"app": "spread"},
                          extra_spec=_spread(
                              1, "topology.kubernetes.io/zone",
                              "DoNotSchedule", {"app": "spread"}))
        elif kinds[i] == 2:
            color = f"c{i % 8}"
            d = basic_pod(i, cpu=str(cpus[i]), labels={"color": color},
                          extra_spec=_affinity(
                              "podAntiAffinity", "color", [color],
                              "kubernetes.io/hostname"))
        else:
            d = basic_pod(i, cpu=str(cpus[i]))
        d["metadata"]["uid"] = f"smoke-{i}"
        pods.append(Pod.from_dict(d))
    enc = BatchEncoder(new_snapshot([], nodes), pad_nodes=128)
    return enc.encode(pods, pad_pods=n_pods)


def host_reference(cluster, batch):
    """``solve_scan`` placed on the host CPU device: the reference the
    kernel tests hold every device backend to."""
    import jax

    from kubernetes_tpu.ops.solver import solve_scan

    with jax.default_device(jax.devices("cpu")[0]):
        return solve_scan(cluster, batch)


def compare(name: str, got, ref, n_real: int) -> None:
    import numpy as np

    got = np.asarray(got)[:n_real]
    ref = np.asarray(ref)[:n_real]
    diff = int((got != ref).sum())
    log(f"{name}: {n_real - diff}/{n_real} assignments equal to the host "
        f"reference ({int((ref >= 0).sum())} placed, "
        f"{int((ref < 0).sum())} unplaced)")
    check(diff == 0, f"{name}: {diff} assignments differ from the host "
          f"reference; first at batch index "
          f"{int(np.flatnonzero(got != ref)[0]) if diff else -1}")


def kernel_phase(seed: int) -> None:
    from kubernetes_tpu.ops.session import default_backend
    from kubernetes_tpu.ops.solver import SolverParams, pack_podin

    t0 = time.monotonic()
    cluster, batch = mixed_batch(KERNEL_NODES, KERNEL_PODS, seed)
    log(f"kernel: encoded {KERNEL_NODES} nodes x {KERNEL_PODS} pods "
        f"(SC={batch.sc_counts.shape[0]} T={batch.term_counts.shape[0]} "
        f"U={batch.static_masks.shape[0]}) in {time.monotonic() - t0:.3f}s")
    backend = default_backend()
    log(f"kernel: default_backend() = {backend.name}")
    check(backend.name == "pallas",
          f"one TPU must pick the pallas backend, got {backend.name}")
    t0 = time.monotonic()
    pstatic, pstate = backend.prepare(cluster, batch)
    ints, floats = pack_podin(batch)
    got, _ = backend.solve(SolverParams(), pstatic, pstate, ints, floats)
    log(f"kernel: pallas solve (compile included) "
        f"{time.monotonic() - t0:.3f}s")
    t0 = time.monotonic()
    ref = host_reference(cluster, batch)
    log(f"kernel: host solve_scan {time.monotonic() - t0:.3f}s")
    compare("kernel", got, ref, batch.num_real_pods)


def e2e_phase(name: str, nodes: int, pods: int, expect_backend: str
              ) -> None:
    from kubernetes_tpu.harness import make_workload
    from kubernetes_tpu.harness.perf import run_workload

    seen: dict = {}

    def hook(sched, bs):
        seen["bound"] = sum(1 for p in sched.client.list_pods()
                            if p.spec.node_name)
        seen["batch"] = bs.batch_committed_pods
        seen["fallback"] = bs.serial_fallback_pods
        seen["backend"] = bs.session.active_backend

    t0 = time.monotonic()
    res = run_workload(f"{name}/batch",
                       make_workload(name, nodes=nodes, init_pods=0,
                                     measure_pods=pods),
                       use_batch=True, max_batch=min(pods, 4096),
                       wait_timeout=600, result_hook=hook)
    wall = time.monotonic() - t0
    tel = res.telemetry
    log(f"e2e {name} {nodes} nodes: bound {seen['bound']}/{pods}, "
        f"by the batch path {seen['batch']}, serial fallback "
        f"{seen['fallback']}, backend {seen['backend']}")
    log(f"e2e {name}: compiles in the measured window "
        f"{tel.get('compiles')} (unexpected {tel.get('unexpected_compiles')}"
        f", detector {tel.get('compile_detector')}), measured "
        f"{res.duration_seconds:.3f}s, phase wall {wall:.3f}s "
        f"(smoke timing, not a benchmark)")
    check(seen["bound"] == pods, f"{name}: {seen['bound']}/{pods} bound")
    check(seen["fallback"] == 0,
          f"{name}: {seen['fallback']} pods fell back to the serial path")
    check(seen["backend"] == expect_backend,
          f"{name}: served by {seen['backend']}, expected {expect_backend}")


def mesh_phase(seed: int) -> None:
    from kubernetes_tpu.ops.session import default_backend
    from kubernetes_tpu.ops.solver import SolverParams, pack_podin

    backend = default_backend()
    log(f"mesh: default_backend() = {backend.name}")
    check(backend.name == "sharded",
          f"four TPUs must pick the sharded backend, got {backend.name}")
    log(f"mesh: {dict(backend.mesh.shape)}")
    for n_nodes in MESH_NODES:
        t0 = time.monotonic()
        cluster, batch = mixed_batch(n_nodes, KERNEL_PODS, seed)
        log(f"mesh {n_nodes}: encoded in {time.monotonic() - t0:.3f}s")
        t0 = time.monotonic()
        sstatic, sstate = backend.prepare(cluster, batch)
        for what, arr in (("static", sstatic.ints),
                          ("state", sstate.planes)):
            devs = {s.device for s in arr.addressable_shards}
            log(f"mesh {n_nodes}: {what} planes {arr.shape} on "
                f"{len(arr.sharding.device_set)} devices, shards on "
                f"{sorted(d.id for d in devs)}")
            check(len(arr.sharding.device_set) == 4 and len(devs) == 4,
                  f"mesh {n_nodes}: {what} planes not on 4 distinct "
                  f"devices")
        ints, floats = pack_podin(batch)
        got, _ = backend.solve(SolverParams(), sstatic, sstate, ints,
                               floats)
        log(f"mesh {n_nodes}: sharded solve (compile included) "
            f"{time.monotonic() - t0:.3f}s")
        t0 = time.monotonic()
        ref = host_reference(cluster, batch)
        log(f"mesh {n_nodes}: host solve_scan {time.monotonic() - t0:.3f}s")
        compare(f"mesh {n_nodes}", got, ref, batch.num_real_pods)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    log("smoke run, not a benchmark: times include compilation")
    t_all = time.monotonic()
    try:
        t0 = time.monotonic()
        info = device_phase(args.chips)
        log(f"phase device {time.monotonic() - t0:.3f}s")
        if args.chips == 1:
            phases = [("kernel", lambda: kernel_phase(args.seed))] + [
                (f"e2e {name}", lambda c=(name, n, p): e2e_phase(
                    *c, expect_backend="pallas"))
                for name, n, p in E2E_CELLS]
        else:
            phases = [("mesh", lambda: mesh_phase(args.seed)),
                      (f"e2e {MESH_E2E[0]}", lambda: e2e_phase(
                          *MESH_E2E, expect_backend="sharded"))]
        for name, run in phases:
            t0 = time.monotonic()
            run()
            log(f"phase {name} {time.monotonic() - t0:.3f}s")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log(f"all phases passed in {time.monotonic() - t_all:.3f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
