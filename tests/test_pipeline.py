"""The batch scheduler's one loop: each ``run_batch`` call drains,
solves and commits its batch before it returns.

The differential guard drives identical seeded event sequences —
including a gang workload and a mid-run node-death drift — twice
through the loop and requires a BIT-IDENTICAL bound set (same pods →
same nodes) with the sequence's invariants. Both runs use
``adaptive_chunk=False`` and the same ``max_batch`` so the drains
partition identically; everything else (incremental mirror, state
carry, tie-breaks) must line up by construction. The backlog tests
churn bound pods between calls, as the benchmark's client does, and
require every solved pod to be committed in the call that solved it.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from kubernetes_tpu.apiserver.store import ClusterStore
from kubernetes_tpu.config.feature_gates import FeatureGates
from kubernetes_tpu.scheduler.scheduler import Scheduler
from kubernetes_tpu.sidecar import attach_batch_scheduler
from kubernetes_tpu.testing import MakeNode, MakePod


def _make_sched(store, max_batch=32, backend=None):
    sched = Scheduler.create(
        store, feature_gates=FeatureGates({"TPUBatchScheduler": True}),
        provider="GangSchedulingProvider")
    bs = attach_batch_scheduler(sched, max_batch=max_batch,
                                adaptive_chunk=False, backend=backend)
    sched.start()
    return sched, bs


def _pump(sched, bs, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        sched.queue.flush_backoff_completed()
        if bs.run_batch(pop_timeout=0.0):
            continue
        if sched.queue.pending_active_count() == 0:
            break
        time.sleep(0.01)
    assert sched.wait_for_inflight_bindings()


def _bound_set(store):
    return sorted((p.metadata.name, p.spec.node_name)
                  for p in store.list_pods())


def _run_event_sequence(waves, nodes=12, node_cpu="8",
                        kill_node_after=None, max_batch=32):
    """Drive the loop through a seeded event sequence: each wave is a
    list of pod builders, pumped to quiescence before the next;
    ``kill_node_after`` deletes that node name after the given wave
    index (the mid-run drift, at a quiesce point)."""
    store = ClusterStore()
    for i in range(nodes):
        store.add_node(MakeNode().name(f"n{i}")
                       .capacity({"cpu": node_cpu, "memory": "16Gi"})
                       .obj())
    sched, bs = _make_sched(store, max_batch=max_batch)
    try:
        for wi, wave in enumerate(waves):
            store.create_pods([mk() for mk in wave])
            _pump(sched, bs)
            if kill_node_after is not None and \
                    wi == kill_node_after[0]:
                store.delete_node(kill_node_after[1])
        return _bound_set(store)
    finally:
        sched.stop()
        import gc

        gc.collect()   # don't leave a deferred-GC pause for later tests

def _plain_waves(n_waves=3, per_wave=40, cpu="1", offset=0):
    return [
        [
            (lambda w=w, i=i: MakePod().name(f"w{w}-p{i}")
             .uid(f"u{w}-{i}").req({"cpu": cpu}).obj())
            for i in range(per_wave)
        ]
        for w in range(offset, offset + n_waves)
    ]


def _gang_wave(w, gangs=3, size=4, cpu="2"):
    out = []
    for g in range(gangs):
        for m in range(size):
            out.append(
                lambda w=w, g=g, m=m: MakePod()
                .name(f"w{w}-g{g}-m{m}").uid(f"gu{w}-{g}-{m}")
                .priority(10).req({"cpu": cpu})
                .label("pod-group.scheduling.k8s.io/name", f"gang-{w}-{g}")
                .label("pod-group.scheduling.k8s.io/min-available",
                       str(size))
                .obj())
    return out


class TestDifferentialGuard:
    def test_contended_waves_bit_identical(self):
        """Capacity-contended waves (more pods than fit): two runs must
        agree on exactly WHICH pods bound and WHERE."""
        waves = _plain_waves(3, 40)   # 120 x 1cpu vs 96 cores
        a = _run_event_sequence(waves)
        b = _run_event_sequence(waves)
        assert a == b
        assert sum(1 for _, n in a if n) == 96   # capacity exactly

    def test_gang_workload_bit_identical(self):
        """Gangs (Permit-parked, async binding cycles) interleaved
        with plain pods — two runs must still agree pod-for-pod."""
        waves = [
            _plain_waves(1, 20)[0],
            _gang_wave(1, gangs=3, size=4),
            _plain_waves(1, 10, offset=2)[0]
            + _gang_wave(2, gangs=2, size=4),
        ]
        a = _run_event_sequence(waves)
        b = _run_event_sequence(waves)
        assert a == b
        # the gangs landed atomically
        for w, g, size in ((1, 0, 4), (1, 1, 4), (1, 2, 4),
                           (2, 0, 4), (2, 1, 4)):
            members = [n for (name, n) in a
                       if name.startswith(f"w{w}-g{g}-") and n]
            assert len(members) in (0, size), (w, g, members)

    def test_mid_run_node_death_bit_identical(self):
        """A node deleted mid-sequence (after wave 0's quiesce): the
        node-SET epoch bump forces the drift re-encode, and the
        remaining waves must land identically in both runs — with
        nothing placed on the dead node."""
        waves = _plain_waves(3, 30)
        a = _run_event_sequence(waves, kill_node_after=(0, "n3"))
        b = _run_event_sequence(waves, kill_node_after=(0, "n3"))
        assert a == b
        # post-death waves never bound onto the deleted node
        for name, node in a:
            if node == "n3":
                assert name.startswith("w0-"), \
                    f"{name} bound to the dead node after its deletion"

    def test_mid_flight_node_death_loses_nothing(self):
        """A node dies between two ``run_batch`` calls under a backlog:
        one call commits a batch, the node is deleted with 32 pods
        still queued, and pumping on binds every pod, none of the
        later ones onto the dead node."""
        store = ClusterStore()
        for i in range(8):
            store.add_node(MakeNode().name(f"n{i}")
                           .capacity({"cpu": "8", "memory": "16Gi"})
                           .obj())
        sched, bs = _make_sched(store, max_batch=16)
        try:
            store.create_pods([
                MakePod().name(f"p{i}").uid(f"u{i}")
                .req({"cpu": "1"}).obj()
                for i in range(48)
            ])
            assert bs.run_batch(pop_timeout=0.1) == 16
            assert sched.wait_for_inflight_bindings()
            first = {p.metadata.name for p in store.list_pods()
                     if p.spec.node_name}
            assert len(first) == 16
            store.delete_node("n2")   # drift with a backlog queued
            _pump(sched, bs)
            pods = store.list_pods()
            assert all(p.spec.node_name for p in pods)
            assert len(pods) == 48
            assert not any(p.spec.node_name == "n2" for p in pods
                           if p.metadata.name not in first)
        finally:
            sched.stop()


class TestKillSwitch:
    def test_serialized_arm_never_holds_a_batch(self):
        """``run_batch`` never holds a batch: every pod a call drains
        is bound when the call returns (its binds waited out)."""
        store = ClusterStore()
        for i in range(4):
            store.add_node(MakeNode().name(f"n{i}")
                           .capacity({"cpu": "8", "memory": "16Gi"})
                           .obj())
        sched, bs = _make_sched(store, max_batch=8)
        try:
            store.create_pods([
                MakePod().name(f"p{i}").uid(f"u{i}")
                .req({"cpu": "500m"}).obj()
                for i in range(30)
            ])
            drained = 0
            while True:
                n = bs.run_batch(pop_timeout=0.0)
                if not n:
                    break
                drained += n
                assert sched.wait_for_inflight_bindings()
                bound = sum(1 for p in store.list_pods()
                            if p.spec.node_name)
                assert bound == drained
            assert drained == 30
        finally:
            sched.stop()


def _spread_pod(name, node=""):
    w = (MakePod().name(name).uid(f"uid-{name}").label("foo", "bar")
         .req({"cpu": "100m", "memory": "500Mi"})
         .spread_constraint(5, "kubernetes.io/hostname", "DoNotSchedule",
                            {"foo": "bar"})
         .spread_constraint(5, "topology.kubernetes.io/zone",
                            "DoNotSchedule", {"foo": "bar"}))
    if node:
        w = w.node(node)
    return w.obj()


def _plain_pod(name, node=""):
    w = MakePod().name(name).uid(f"uid-{name}").req(
        {"cpu": "100m", "memory": "500Mi"})
    if node:
        w = w.node(node)
    return w.obj()


class TestOneLoop:
    """The benchmark's backlog shape at a test's size: a standing
    backlog topped up before every call, and for each pod bound the
    oldest running pod deleted between calls."""

    @pytest.mark.parametrize("make", [_spread_pod, _plain_pod],
                             ids=["spread", "plain"])
    def test_backlog_commits_every_solve_in_its_call(self, make):
        store = ClusterStore()
        nodes = 12
        for i in range(nodes):
            store.add_node(MakeNode().name(f"n{i}")
                           .label("topology.kubernetes.io/zone",
                                  f"z{i % 3}")
                           .capacity({"cpu": "4", "memory": "32Gi"})
                           .obj())
        running = [f"run-{j}" for j in range(2 * nodes)]
        store.create_pods([make(nm, f"n{j % nodes}")
                           for j, nm in enumerate(running)])
        sched, bs = _make_sched(store, max_batch=16)
        try:
            created = 0

            def top_up(want):
                nonlocal created
                store.create_pods([make(f"p-{created + k}")
                                   for k in range(want)])
                created += want

            top_up(40)
            bound_before = 0
            for _ in range(6):
                committed0 = bs.batch_committed_pods
                n = bs.run_batch(pop_timeout=0.1)
                assert n == 16
                # every pod drained this call was solved and committed
                # in it: nothing waits for the next call
                assert bs.batch_committed_pods - committed0 == n
                assert sched.wait_for_inflight_bindings()
                bound = sum(1 for p in store.list_pods()
                            if p.spec.node_name
                            and p.metadata.name.startswith("p-"))
                assert bound == bound_before + n
                bound_before = bound
                # churn: one running pod out per pod bound, the
                # backlog back up
                for nm in running[:n]:
                    store.delete_pod("default", nm)
                del running[:n]
                top_up(n)
        finally:
            sched.stop()

    def test_run_batch_leaves_no_carry_clone(self):
        """A solve takes the resident state as its input and keeps only
        the state it returns: the native backend, which updates its
        state in place, is never asked for a clone of it."""
        from kubernetes_tpu.ops import native_backend

        backend = native_backend.CppBackend()
        clones = []
        orig = backend.warm_state

        def counting(state):
            clones.append(1)
            return orig(state)

        backend.warm_state = counting
        store = ClusterStore()
        for i in range(6):
            store.add_node(MakeNode().name(f"n{i}")
                           .capacity({"cpu": "16", "memory": "32Gi"})
                           .obj())
        sched, bs = _make_sched(store, max_batch=8, backend=backend)
        try:
            store.create_pods([MakePod().name(f"p{i}").uid(f"u{i}")
                               .req({"cpu": "100m"}).obj()
                               for i in range(40)])
            _pump(sched, bs)
            assert all(p.spec.node_name for p in store.list_pods())
            assert bs.session.incremental_hits >= 3
            assert clones == []
        finally:
            sched.stop()

    def test_solve_result_is_always_materialized(self, monkeypatch):
        """Every solve ``run_batch`` makes hands back host assignments,
        incremental and rebuild solves alike: there is no device handle
        to materialize later."""
        monkeypatch.setenv("KTPU_SOLVER", "xla")
        store = ClusterStore()
        for i in range(6):
            store.add_node(MakeNode().name(f"n{i}")
                           .capacity({"cpu": "16", "memory": "32Gi"})
                           .obj())
        sched, bs = _make_sched(store, max_batch=8)
        results = []
        orig = bs.session.solve

        def recording(*a, **kw):
            out = orig(*a, **kw)
            results.append(out[0])
            return out

        bs.session.solve = recording
        try:
            store.create_pods([MakePod().name(f"p{i}").uid(f"u{i}")
                               .req({"cpu": "100m"}).obj()
                               for i in range(24)])
            _pump(sched, bs)
            assert len(results) >= 3
            assert bs.session.rebuilds >= 1
            assert bs.session.incremental_hits >= 1
            for out in results:
                assert isinstance(out, np.ndarray), type(out)
        finally:
            sched.stop()


class TestSustainedMiniCell:
    """The tier-1 sustained-arrival cell — open-loop arrivals through
    the replay engine at compressed scale, asserting nothing is lost
    and the staleness SLO stays green, inside the fast-suite time
    budget."""

    def test_overlap_occurs_and_staleness_green(self):
        from kubernetes_tpu.harness.sustained import run_sustained_cell

        cell = run_sustained_cell(pods=400, qps=400.0, max_batch=64,
                                  wait_timeout=90.0)
        assert cell["lost"] == 0
        assert cell["ever_bound"] == cell["injected"] == 400
        # the solve never ran against a stale mirror: the snapshot
        # staleness SLO verdict holds under open-loop arrivals
        assert cell["staleness_verdict"] in (None, "ok")
        assert cell["p99_arrival_to_bind_ms"] < 2000


class TestOverlapTelemetry:
    def test_note_block_computes_overlap(self):
        from kubernetes_tpu.observability.devprof import DevProfiler

        p = DevProfiler(enabled=True, use_listener=False)
        rec = p.begin_cycle(cycle=1, pad=64, real=32)
        p.phase("dispatch", 0.01)
        p.end_cycle(rec, pending_block=True)
        t_dispatch_end = rec.dispatch_end
        # host work happens here (the pipeline's overlap window)
        p.note_block(rec, 0.05, 128,
                     start_mono=t_dispatch_end + 0.2)
        assert rec["overlap_s"] == pytest.approx(0.2)
        s = p.summary()
        assert s["overlapped_cycles"] == 1
        assert s["overlap_s"] == pytest.approx(0.2, abs=1e-4)
        assert s["overlap_share"] == pytest.approx(0.2 / 0.25, abs=1e-3)

    def test_eager_cycles_excluded_from_overlap_share(self):
        from kubernetes_tpu.observability.devprof import DevProfiler

        p = DevProfiler(enabled=True, use_listener=False)
        # one eager cycle: block recorded inline, no in-flight window
        rec = p.begin_cycle(cycle=1, pad=64, real=32)
        p.phase("block", 1.0)
        p.end_cycle(rec)
        # one lazy cycle that fully overlapped
        rec2 = p.begin_cycle(cycle=2, pad=64, real=32)
        p.end_cycle(rec2, pending_block=True)
        p.note_block(rec2, 0.0, 0,
                     start_mono=rec2.dispatch_end + 0.5)
        s = p.summary()
        assert s["overlapped_cycles"] == 1
        # the eager cycle's 1.0s block must not dilute the share
        assert s["overlap_share"] == pytest.approx(1.0)

    def test_overlap_rides_jsonl_and_stream_summary(self, tmp_path):
        from kubernetes_tpu.observability.devprof import DevProfiler
        from tools.perf_report import summarize_telemetry

        p = DevProfiler(enabled=True, use_listener=False,
                        telemetry_dir=str(tmp_path))
        rec = p.begin_cycle(cycle=1, pad=64, real=32)
        p.end_cycle(rec, pending_block=True)
        p.note_block(rec, 0.1, 0, start_mono=rec.dispatch_end + 0.3)
        p.close()
        stream = summarize_telemetry(str(tmp_path))
        assert stream["overlapped_cycles"] == 1
        assert stream["overlap_s"] == pytest.approx(0.3, abs=1e-4)
        assert stream["overlap_share"] == pytest.approx(0.75, abs=1e-3)
        live = p.summary()
        assert stream["overlap_share"] == pytest.approx(
            live["overlap_share"], abs=1e-3)
