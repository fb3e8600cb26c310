"""Sharded-by-default solve: the devscale harness, the mesh diag
segment, donation accounting through the live session, the sharded
encode stage, and THE differential guarantee — the sharded-default
backend must produce bit-identical assignments (same argmax
tie-breaks) to the single-device backend on identical encoded batches,
across mesh sizes, via subprocesses that force the device count with
XLA_FLAGS before JAX imports (the only way a test controls
``jax.device_count()``; in-process the conftest already pinned 8).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from kubernetes_tpu.harness import diagfmt
from kubernetes_tpu.harness.devscale import ensure_virtual_devices
from tools.perf_report import devscale_flags

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the subprocess body: builds 3 seeded problems (heavy score ties so
# the lowest-index argmax tie-break is genuinely exercised), solves
# each on the DEFAULT backend for this interpreter's device count
# (KTPU_SOLVER=auto → mesh tier when >1 device), asserts equality with
# the serial-equivalent reference scan, and prints the assignments so
# the parent can cross-check bit-identity ACROSS mesh sizes.
_CHILD = r"""
import json
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from kubernetes_tpu.ops import BatchEncoder
from kubernetes_tpu.ops.session import default_backend
from kubernetes_tpu.ops.solver import SolverParams, pack_podin, solve_scan
from kubernetes_tpu.scheduler.snapshot import new_snapshot
from kubernetes_tpu.testing import MakeNode, MakePod


def problem(seed):
    rng = np.random.default_rng(seed)
    nodes = []
    for i in range(40):
        # half the nodes identical -> massive score ties -> the
        # lowest-index tie-break decides
        cpu = 8 if i % 2 == 0 else int(rng.integers(4, 9))
        nodes.append(
            MakeNode().name(f"n{i:03d}")
            .label("topology.kubernetes.io/zone", f"z{i % 4}")
            .capacity({"cpu": str(cpu), "memory": "16Gi"}).obj())
    pods = []
    for i in range(60):
        w = (MakePod().name(f"p{i:03d}").uid(f"u{seed}-{i}")
             .label("app", f"g{i % 3}")
             .req({"cpu": f"{int(rng.integers(1, 4)) * 100}m"}))
        if i % 5 == 0:
            w.spread_constraint(1, "topology.kubernetes.io/zone",
                                "DoNotSchedule", {"app": f"g{i % 3}"})
        if i % 7 == 0:
            w.pod_anti_affinity("app", [f"g{(i + 1) % 3}"],
                                "kubernetes.io/hostname")
        pods.append(w.obj())
    return nodes, pods


be = default_backend()
out = {"devices": jax.device_count(), "backend": be.name,
       "assignments": {}}
params = SolverParams()
for seed in (0, 1, 2):
    nodes, pods = problem(seed)
    snap = new_snapshot([], nodes)
    enc = BatchEncoder(snap, pad_nodes=128,
                       node_shards=getattr(be, "encode_shards", 1))
    cluster, batch = enc.encode(pods, pad_pods=64)
    ref = solve_scan(cluster, batch)[: len(pods)]
    static, state = be.prepare(cluster, batch)
    ints, floats = pack_podin(batch)
    got, _ = be.solve(params, static, state, ints, floats)
    got = np.asarray(got)[: len(pods)]
    assert np.array_equal(ref, got), (
        f"seed {seed}: default backend {be.name} diverged from the "
        f"reference scan: {ref.tolist()} vs {got.tolist()}")
    out["assignments"][str(seed)] = got.tolist()
print(json.dumps(out))
"""


def _run_child(devices: int) -> dict:
    env = ensure_virtual_devices(devices, dict(os.environ))
    env["KTPU_SOLVER"] = "auto"
    env.pop("KTPU_SHARDED_DONATE", None)
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH",
                                                          "")
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True,
                          timeout=240)
    assert proc.returncode == 0, (
        f"differential child (devices={devices}) failed:\n"
        f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.splitlines()[-1])


class TestShardedDefaultDifferential:
    """Mesh sizes {1, 2, 4} × 3 seeds: the sharded-default tier is
    bit-identical to the single-device backend."""

    def test_assignments_identical_across_mesh_sizes(self):
        results = {d: _run_child(d) for d in (1, 2, 4)}
        # devices=1 must NOT be the mesh tier; >1 must be
        assert results[1]["backend"] != "sharded"
        assert results[2]["backend"] == "sharded"
        assert results[4]["backend"] == "sharded"
        base = results[1]["assignments"]
        for d in (2, 4):
            assert results[d]["assignments"] == base, (
                f"mesh size {d} diverged from the single-device "
                f"backend")


class TestShardedSessionAccounting:
    """The live session on the mesh tier: donated planes ride the
    donated ledger (never h2d), the staging arm pays the per-cycle
    h↔d copies donation removes, and warming never corrupts the
    resident mirror."""

    def _bind_all(self, donate: bool, prof):
        import time

        from kubernetes_tpu.apiserver.store import ClusterStore
        from kubernetes_tpu.config.feature_gates import FeatureGates
        from kubernetes_tpu.parallel import ShardedBackend, make_mesh
        from kubernetes_tpu.scheduler.scheduler import Scheduler
        from kubernetes_tpu.sidecar import attach_batch_scheduler
        from kubernetes_tpu.testing import MakeNode, MakePod

        store = ClusterStore()
        for i in range(16):
            store.add_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "16", "memory": "32Gi"}).obj())
        sched = Scheduler.create(
            store,
            feature_gates=FeatureGates({"TPUBatchScheduler": True}))
        bs = attach_batch_scheduler(
            sched, max_batch=64,
            backend=ShardedBackend(make_mesh(4, batch_axis=1),
                                   donate=donate))
        sched.start()
        try:
            for i in range(96):
                store.create_pod(
                    MakePod().name(f"p{i}").uid(f"u{i}")
                    .req({"cpu": "1"}).obj())
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                sched.queue.flush_backoff_completed()
                if bs.run_batch(pop_timeout=0.0):
                    continue
                if sched.queue.num_active() == 0 \
                        and sched.queue.num_backoff() == 0:
                    break
                time.sleep(0.02)
            assert sched.wait_for_inflight_bindings()
            bound = sum(1 for p in store.list_pods()
                        if p.spec.node_name)
            info = bs.mesh_info()
        finally:
            sched.stop()
        assert bound == 96
        assert bs.session._active.name == "sharded"
        return prof.summary(), info

    def test_donation_ledgers_and_staging_ab(self):
        from kubernetes_tpu.observability.devprof import (
            DevProfiler,
            get_devprof,
            set_devprof,
        )

        prev = get_devprof()
        try:
            prof_on = DevProfiler(enabled=True, use_listener=False)
            set_devprof(prof_on)
            on, info_on = self._bind_all(donate=True, prof=prof_on)
            prof_off = DevProfiler(enabled=True, use_listener=False)
            set_devprof(prof_off)
            off, info_off = self._bind_all(donate=False, prof=prof_off)
        finally:
            set_devprof(prev)
        # donation on: resident planes ride the donated ledger only
        assert on["donated_bytes"] > 0
        assert info_on == {"devices": 4, "shards": 4, "donated": True}
        assert info_off["donated"] is False
        # the per-cycle h↔d copies of reusable planes exist exactly on
        # the staging arm — transfer totals strictly lower with
        # donation on (the tentpole's acceptance metric)
        assert off["h2d_bytes"] > on["h2d_bytes"]
        assert off["d2h_bytes"] > on["d2h_bytes"]
        assert off["donated_bytes"] == 0

    def test_warm_pad_preserves_resident_mirror_under_donation(self):
        from kubernetes_tpu.apiserver.store import ClusterStore
        from kubernetes_tpu.config.feature_gates import FeatureGates
        from kubernetes_tpu.parallel import ShardedBackend, make_mesh
        from kubernetes_tpu.scheduler.scheduler import Scheduler
        from kubernetes_tpu.sidecar import attach_batch_scheduler
        from kubernetes_tpu.testing import MakeNode, MakePod

        store = ClusterStore()
        for i in range(8):
            store.add_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "64", "memory": "64Gi"}).obj())
        sched = Scheduler.create(
            store,
            feature_gates=FeatureGates({"TPUBatchScheduler": True}))
        bs = attach_batch_scheduler(
            sched, max_batch=32,
            backend=ShardedBackend(make_mesh(4, batch_axis=1),
                                   donate=True))
        sess = bs.session
        pods = [MakePod().name(f"p{i}").uid(f"u{i}")
                .req({"cpu": "1"}).obj() for i in range(8)]
        sess.solve(pods, warming=True)
        before = np.asarray(sess._state.planes).copy()
        # the donated executable consumes its state inputs: without the
        # warm_state clone this would delete the resident buffer
        assert sess.warm_pad(pods[:2], 16) is not None
        after = np.asarray(sess._state.planes)  # still alive
        assert np.array_equal(before, after)


class TestShardedEncode:
    def test_node_sharded_encode_is_bit_identical(self):
        """The sharded encode stage emits per-shard node columns into
        the same arrays the serial fill produces — every plane must be
        bit-identical (the solve's differential guarantee starts
        here)."""
        from kubernetes_tpu.ops import BatchEncoder
        from kubernetes_tpu.scheduler.snapshot import new_snapshot
        from kubernetes_tpu.testing import MakeNode, MakePod

        nodes = [
            MakeNode().name(f"n{i:04d}")
            .label("topology.kubernetes.io/zone", f"z{i % 5}")
            .capacity({"cpu": str(4 + i % 5), "memory": "16Gi"}).obj()
            for i in range(600)   # above ENCODE_SHARD_MIN_NODES
        ]
        pods = []
        for i in range(32):
            w = (MakePod().name(f"p{i}").uid(f"u{i}")
                 .label("app", f"g{i % 2}").req({"cpu": "200m"}))
            if i % 4 == 0:
                w.spread_constraint(
                    2, "topology.kubernetes.io/zone", "DoNotSchedule",
                    {"app": f"g{i % 2}"})
            if i % 2 == 0:
                w.node_selector({"topology.kubernetes.io/zone": "z1"})
            pods.append(w.obj())
        snap = new_snapshot([], nodes)
        c1, b1 = BatchEncoder(snap, pad_nodes=128,
                              node_shards=1).encode(pods, pad_pods=32)
        c8, b8 = BatchEncoder(snap, pad_nodes=128,
                              node_shards=8).encode(pods, pad_pods=32)
        np.testing.assert_array_equal(c1.allocatable, c8.allocatable)
        np.testing.assert_array_equal(c1.requested, c8.requested)
        np.testing.assert_array_equal(c1.pod_count, c8.pod_count)
        np.testing.assert_array_equal(c1.max_pods, c8.max_pods)
        np.testing.assert_array_equal(c1.topo_codes, c8.topo_codes)
        np.testing.assert_array_equal(b1.static_masks, b8.static_masks)
        np.testing.assert_array_equal(b1.affinity_masks,
                                      b8.affinity_masks)
        np.testing.assert_array_equal(b1.static_scores,
                                      b8.static_scores)
        np.testing.assert_array_equal(b1.sc_domain, b8.sc_domain)

    def test_small_clusters_stay_serial(self):
        from kubernetes_tpu.ops.encode import BatchEncoder
        from kubernetes_tpu.scheduler.snapshot import new_snapshot
        from kubernetes_tpu.testing import MakeNode

        snap = new_snapshot([], [
            MakeNode().name("n0").capacity({"cpu": "4"}).obj()])
        enc = BatchEncoder(snap, node_shards=8)
        assert not enc._sharding_active()


class TestMeshDiagSegment:
    def test_round_trip(self):
        seg = diagfmt.format_mesh(
            {"devices": 8, "shards": 8, "donated": True})
        assert seg == "mesh[devices=8 shards=8 donated=1]"
        line = diagfmt.format_diag(
            ["solve.commit=1.00s/2~p99 10ms", seg])
        parsed = diagfmt.parse_diag(line)
        assert parsed["mesh"] == {"devices": 8, "shards": 8,
                                  "donated": 1}

    def test_empty_info_prints_nothing(self):
        assert diagfmt.format_mesh(None) == ""
        assert diagfmt.format_mesh({}) == ""

    def test_devprof_segment_carries_donated_mb(self):
        summary = {
            "cycles": 3, "compiles": 0, "unexpected_compiles": 0,
            "warm_compiles": 0, "device_wait_share": 0.5,
            "pad_waste_pct": 0.0, "h2d_bytes": 1_000_000,
            "d2h_bytes": 1_000, "donated_bytes": 5_000_000,
            "compile_detector": "listener",
        }
        seg = diagfmt.format_devprof(summary)
        assert "donated_mb=5.0" in seg
        parsed = diagfmt.parse_diag("    diag: " + seg)
        assert parsed["devprof"]["donated_mb"] == 5.0


class TestDevscaleFlags:
    """tools/perf_report.py learns the devscale family: scaling bar,
    efficiency gate, and the donation A/B verdict."""

    @staticmethod
    def _round(row):
        return [{"round": 7, "rows": [row]}]

    @staticmethod
    def _row(**over):
        row = {
            "metric": "solve_throughput_devscale[SchedulingBasic "
                      "51200nodes/8192pods]",
            "value": 16000.0, "unit": "pods/s",
            "solve_speedup_vs_1dev": {"1": 1.0, "2": 1.4, "4": 2.5},
            "scaling_efficiency_4dev": 0.63,
            "donation_ab": {
                "devices": 4,
                "on": {"h2d_bytes_per_cycle": 100,
                       "device_wait_share": 0.4},
                "off": {"h2d_bytes_per_cycle": 900,
                        "device_wait_share": 0.6},
                "donation_pays": True,
            },
        }
        row.update(over)
        return row

    def test_healthy_row_has_no_flags(self):
        assert devscale_flags(self._round(self._row())) == []

    def test_flags_speedup_below_bar(self):
        row = self._row(solve_speedup_vs_1dev={"1": 1.0, "4": 1.2})
        (flag,) = devscale_flags(self._round(row))
        assert "speedup 1.2 < 1.5x" in flag["problems"][0]

    def test_flags_efficiency_below_point_six_on_real_hardware(self):
        row = self._row(scaling_efficiency_4dev=0.51)
        (flag,) = devscale_flags(self._round(row))
        assert "efficiency 0.51 < 0.6" in flag["problems"][0]

    def test_virtual_device_rows_exempt_from_efficiency_gate(self):
        """Forced shared-silicon virtual devices understate mesh
        efficiency by construction (the 1-device baseline is intra-op
        multithreaded) — the 0.6 gate polices real meshes only; the
        ≥1.5× speedup bar still applies."""
        row = self._row(scaling_efficiency_4dev=0.47,
                        virtual_devices=True)
        assert devscale_flags(self._round(row)) == []
        row = self._row(scaling_efficiency_4dev=0.47,
                        virtual_devices=True,
                        solve_speedup_vs_1dev={"1": 1.0, "4": 1.2})
        (flag,) = devscale_flags(self._round(row))
        assert "speedup 1.2 < 1.5x" in flag["problems"][0]

    def test_flags_donation_not_paying(self):
        ab = self._row()["donation_ab"]
        ab["donation_pays"] = False
        row = self._row(donation_ab=ab)
        (flag,) = devscale_flags(self._round(row))
        assert "donation A/B not paying" in flag["problems"][0]

    def test_non_devscale_rows_ignored(self):
        row = {"metric": "pods_scheduled_per_sec[x]", "value": 1.0}
        assert devscale_flags(self._round(row)) == []


class TestVirtualDeviceBootstrap:
    def test_sets_and_replaces_flag(self):
        env = ensure_virtual_devices(8, {})
        assert env["XLA_FLAGS"] == \
            "--xla_force_host_platform_device_count=8"
        env = ensure_virtual_devices(4, env)
        assert env["XLA_FLAGS"] == \
            "--xla_force_host_platform_device_count=4"

    def test_preserves_other_flags(self):
        env = ensure_virtual_devices(
            2, {"XLA_FLAGS": "--xla_foo=bar"})
        assert "--xla_foo=bar" in env["XLA_FLAGS"]
        assert "--xla_force_host_platform_device_count=2" \
            in env["XLA_FLAGS"]


@pytest.mark.slow
class TestDevscaleRowSlow:
    def test_quick_row_schema_and_donation_ab(self):
        """The full spawned row at quick scale: arms, speedups, and
        the donation A/B with its acceptance verdict."""
        from kubernetes_tpu.harness.devscale import run_devscale_row

        row = run_devscale_row(nodes=1024, pods=2048, max_batch=1024,
                               device_counts=(1, 2),
                               donation_ab_devices=2)
        assert row["unit"] == "pods/s"
        assert [a["devices"] for a in row["arms"]] == [1, 2]
        assert row["arms"][1]["mesh"]["shards"] == 2
        ab = row["donation_ab"]
        assert ab["on"]["h2d_bytes_per_cycle"] \
            < ab["off"]["h2d_bytes_per_cycle"]
        assert ab["donation_pays"] in (True, False)
