"""Device-resident cluster-state mirror tests (ISSUE 20): the delta
journal's gap semantics, the expressibility contract's reseed paths,
and the PR's hardest promise — over identical seeded event sequences
(capacity churn, pod churn, node death mid-flight, gang waves) the
mirror-on scatter path and the ``KTPU_MIRROR=off`` delta-encode
reference must produce a BIT-IDENTICAL bound set, across mesh sizes
{1, 2, 4} × 3 seeds on the sharded tier.

Also carries the tier-1 sustained mini-cell for the tentpole's
measurable claim: on an open-loop sustained row the host cluster-plane
encode share collapses to near zero (``encode_share < 0.05``) with
zero lost pods — the per-batch pod-row encode (the drained h2d) is all
that remains.
"""

from __future__ import annotations

import copy
import time

import numpy as np
import pytest

from kubernetes_tpu.api.resource import Quantity
from kubernetes_tpu.apiserver.store import ClusterStore
from kubernetes_tpu.config.feature_gates import FeatureGates
from kubernetes_tpu.ops.mirror import (
    DeltaJournal,
    _pack_entries,
    mirror_enabled,
)
from kubernetes_tpu.scheduler.scheduler import Scheduler
from kubernetes_tpu.sidecar import attach_batch_scheduler
from kubernetes_tpu.testing import MakeNode, MakePod


def _make_sched(store, *, max_batch=32, backend=None):
    sched = Scheduler.create(
        store, feature_gates=FeatureGates({"TPUBatchScheduler": True}),
        provider="GangSchedulingProvider")
    bs = attach_batch_scheduler(sched, max_batch=max_batch,
                                adaptive_chunk=False, backend=backend)
    sched.start()
    return sched, bs


def _pump(sched, bs, timeout=60.0):
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


def _set_node_cpu(store, name: str, cpu: str) -> None:
    """Capacity churn: an allocatable-only node update (the scatter
    fast path — everything else about the node is unchanged)."""
    node = copy.deepcopy(store.get_node(name))
    node.status.allocatable["cpu"] = Quantity(cpu)
    node.status.capacity["cpu"] = Quantity(cpu)
    store.update_node(node)


def _gang(w, gangs=2, size=4, cpu="2"):
    out = []
    for g in range(gangs):
        for m in range(size):
            out.append(
                MakePod().name(f"w{w}-g{g}-m{m}").uid(f"gu{w}-{g}-{m}")
                .priority(10).req({"cpu": cpu})
                .label("pod-group.scheduling.k8s.io/name",
                       f"gang-{w}-{g}")
                .label("pod-group.scheduling.k8s.io/min-available",
                       str(size))
                .obj())
    return out


def _run_scenario(scenario: str, seed: int, mirror_on: bool,
                  monkeypatch, *, devices=None, max_batch=32):
    """One arm of the differential: drive a seeded event sequence and
    return (bound set, mirror info). ``devices`` selects the sharded
    tier at that mesh width; None = the process default backend."""
    monkeypatch.setenv("KTPU_MIRROR", "on" if mirror_on else "off")
    rng = np.random.default_rng(seed)
    store = ClusterStore()
    n_nodes = 10
    for i in range(n_nodes):
        store.add_node(MakeNode().name(f"n{i}")
                       .capacity({"cpu": "8", "memory": "16Gi"}).obj())
    backend = None
    if devices is not None:
        from kubernetes_tpu.parallel import ShardedBackend, make_mesh

        backend = ShardedBackend(make_mesh(devices, batch_axis=1))
    sched, bs = _make_sched(store, max_batch=max_batch, backend=backend)
    try:
        assert (bs.session._mirror is not None) == mirror_on

        def wave(w, count):
            store.create_pods([
                MakePod().name(f"w{w}-p{i}").uid(f"u{w}-{i}")
                .req({"cpu": f"{int(rng.integers(1, 6)) * 100}m"})
                .obj()
                for i in range(count)
            ])
            _pump(sched, bs)

        if scenario == "capacity_churn":
            wave(0, 24)
            # shrink two seeded nodes, grow one — three allocatable-only
            # updates the mirror must scatter bit-exactly
            picks = rng.choice(n_nodes, size=3, replace=False)
            _set_node_cpu(store, f"n{picks[0]}", "4")
            _set_node_cpu(store, f"n{picks[1]}", "5")
            _set_node_cpu(store, f"n{picks[2]}", "12")
            wave(1, 24)
            # pod churn: free seeded capacity, then refill it
            bound = [p for p in store.list_pods() if p.spec.node_name]
            for p in rng.choice(bound, size=6, replace=False):
                store.delete_pod(p.metadata.namespace, p.metadata.name)
            wave(2, 16)
        elif scenario == "node_death":
            wave(0, 24)
            # one cycle commits a batch with the rest of the wave still
            # queued when the node dies — the node-SET epoch bump fires
            # mid-sequence
            store.create_pods([
                MakePod().name(f"w1-p{i}").uid(f"u1-{i}")
                .req({"cpu": "300m"}).obj()
                for i in range(24)
            ])
            bs.run_batch(pop_timeout=0.1)
            store.delete_node(f"n{int(rng.integers(0, n_nodes))}")
            _pump(sched, bs)
            wave(2, 16)
        elif scenario == "gang_waves":
            wave(0, 12)
            store.create_pods(_gang(1, gangs=3, size=4))
            _pump(sched, bs)
            picks = rng.choice(n_nodes, size=2, replace=False)
            _set_node_cpu(store, f"n{picks[0]}", "6")
            _set_node_cpu(store, f"n{picks[1]}", "10")
            store.create_pods(_gang(2, gangs=2, size=4))
            _pump(sched, bs)
        elif scenario == "discard_under_churn":
            wave(0, 24)
            bound = [p for p in store.list_pods() if p.spec.node_name]
            doomed = rng.choice(bound, size=6, replace=False)
            store.create_pods([
                MakePod().name(f"w1-p{i}").uid(f"u1-{i}")
                .req({"cpu": f"{int(rng.integers(1, 6)) * 100}m"}).obj()
                for i in range(max_batch + max_batch // 2)
            ])
            # one batch committed (half a batch more queued) when the
            # deletes land: the next pass catches them up first
            assert bs.run_batch(pop_timeout=0.1) == max_batch
            for p in doomed:
                store.delete_pod(p.metadata.namespace, p.metadata.name)
            _pump(sched, bs)
            wave(2, 16)
        else:  # pragma: no cover - scenario typo guard
            raise AssertionError(scenario)
        info = None
        if bs.session._mirror is not None:
            info = bs.session._mirror.info()
        return _bound_set(store), info
    finally:
        sched.stop()
        import gc

        gc.collect()


class TestDeltaJournal:
    def test_contiguous_window(self):
        j = DeltaJournal()
        for s in range(1, 6):
            j.note(s, "pod_add", f"p{s}")
        recs = j.window(1, 5)
        assert [r.seq for r in recs] == [2, 3, 4, 5]
        assert all(r.kind == "pod_add" for r in recs)

    def test_empty_window(self):
        j = DeltaJournal()
        assert j.window(7, 7) == []
        assert j.window(9, 7) == []

    def test_gap_reads_as_none(self):
        j = DeltaJournal()
        j.note(1, "pod_add")
        j.note(3, "pod_add")   # seq 2 bumped by an uninstrumented site
        assert j.window(0, 3) is None
        # a window starting past the gap is fine
        assert [r.seq for r in j.window(2, 3)] == [3]

    def test_ring_eviction_reads_as_none(self):
        j = DeltaJournal(cap=4)
        for s in range(1, 10):
            j.note(s, "pod_add")
        assert j.window(0, 9) is None          # 1..5 evicted
        assert j.window(5, 9) is not None      # still resident

    def test_window_predating_journal_reads_as_none(self):
        j = DeltaJournal()
        j.note(11, "pod_add")
        assert j.window(9, 11) is None


class TestPackEntries:
    def test_add_padding_is_zero(self):
        rows, cols, vals = _pack_entries([(3, 7, -5)], pad_with_zero=True)
        assert rows.shape == (1024,) and rows.dtype == np.int32
        assert (rows[1:] == 0).all() and (vals[1:] == 0).all()
        assert (rows[0], cols[0], vals[0]) == (3, 7, -5)

    def test_set_padding_repeats_last(self):
        items = [(1, 2, 9), (4, 5, 6)]
        rows, cols, vals = _pack_entries(items, pad_with_zero=False)
        assert rows.shape == (1024,)
        assert (rows[2:] == 4).all() and (vals[2:] == 6).all()

    def test_pow2_buckets(self):
        rows, _, _ = _pack_entries([(0, 0, 1)] * 1024, pad_with_zero=True)
        assert rows.shape == (1024,)
        rows, _, _ = _pack_entries([(0, 0, 1)] * 1025, pad_with_zero=True)
        assert rows.shape == (2048,)


class TestKillSwitch:
    def test_env_parsing(self, monkeypatch):
        for off in ("off", "0", "false", " OFF "):
            monkeypatch.setenv("KTPU_MIRROR", off)
            assert mirror_enabled() is False
        monkeypatch.setenv("KTPU_MIRROR", "on")
        assert mirror_enabled() is True
        monkeypatch.delenv("KTPU_MIRROR")
        assert mirror_enabled() is True

    def test_off_builds_no_mirror(self, monkeypatch):
        monkeypatch.setenv("KTPU_MIRROR", "off")
        store = ClusterStore()
        store.add_node(MakeNode().name("n0")
                       .capacity({"cpu": "8", "memory": "16Gi"}).obj())
        sched, bs = _make_sched(store)
        try:
            assert bs.session._mirror is None
            assert bs.mirror_info() is None
        finally:
            sched.stop()


class TestMirrorDifferential:
    """Mirror-on ≡ mirror-off bound sets over seeded event sequences
    on the process-default backend, 3 seeds per scenario."""

    @pytest.mark.parametrize("seed", [3, 14, 77])
    def test_capacity_and_pod_churn(self, seed, monkeypatch):
        on, ion = _run_scenario("capacity_churn", seed, True, monkeypatch)
        off, _ = _run_scenario("capacity_churn", seed, False, monkeypatch)
        assert on == off
        # the churn was genuinely scattered, not reseeded around:
        # allocatable updates and pod deletes ride catch_up
        assert ion["events"] > 0
        assert ion["catch_ups"] > 0

    @pytest.mark.parametrize("seed", [3, 14, 77])
    def test_node_death_mid_flight(self, seed, monkeypatch):
        on, _ = _run_scenario("node_death", seed, True, monkeypatch)
        off, _ = _run_scenario("node_death", seed, False, monkeypatch)
        assert on == off
        # nothing was lost: every injected pod is in the store (bound
        # or pending), and the arms agree pod-for-pod
        assert len(on) == 64

    @pytest.mark.parametrize("seed", [3, 14, 77])
    def test_gang_waves(self, seed, monkeypatch):
        on, _ = _run_scenario("gang_waves", seed, True, monkeypatch)
        off, _ = _run_scenario("gang_waves", seed, False, monkeypatch)
        assert on == off
        # gangs landed atomically in both arms
        for w, g, size in ((1, 0, 4), (1, 1, 4), (1, 2, 4),
                           (2, 0, 4), (2, 1, 4)):
            members = [n for (name, n) in on
                       if name.startswith(f"w{w}-g{g}-") and n]
            assert len(members) in (0, size), (w, g, members)


class TestDiscardUnderChurn:
    """Deletes that land between two calls, with a backlog queued, are
    seen by the next solve: mirror on (deletes scattered) and mirror
    off (rebuild) bind alike."""

    @pytest.mark.parametrize("seed", [3, 14, 77])
    def test_bound_sets_match(self, seed, monkeypatch):
        on, ion = _run_scenario("discard_under_churn", seed, True,
                                monkeypatch)
        off, _ = _run_scenario("discard_under_churn", seed, False,
                               monkeypatch)
        assert on == off
        assert ion["catch_ups"] > 0
        assert ion["reseeds"] == 0


def _state_rows(state) -> np.ndarray:
    """Every plane of a backend's state, one row per plane, as numpy
    (``PState`` [C, NB, 128]; ``SState`` [C, N] plus its totals)."""
    return np.concatenate([np.asarray(x).reshape(-1) for x in state])


class TestCarryRestore:
    """Commit a batch, delete bound pods behind it, catch up: with the
    deletes scattered, the resident state equals a fresh state-only
    rebuild on the same cache, bit for bit, on every backend the
    mirror serves."""

    def _check(self, backend=None):
        from kubernetes_tpu.ops.encode import BatchEncoder
        from kubernetes_tpu.ops.pallas_solver import _state_planes

        rng = np.random.default_rng(5)
        store = ClusterStore()
        for i in range(10):
            store.add_node(MakeNode().name(f"n{i}")
                           .capacity({"cpu": "8", "memory": "16Gi"}).obj())
        sched, bs = _make_sched(store, max_batch=16, backend=backend)
        try:
            def pods(w, count):
                return [MakePod().name(f"w{w}-p{i}").uid(f"u{w}-{i}")
                        .req({"cpu": f"{int(rng.integers(1, 6)) * 100}m",
                              "memory": "256Mi"}).obj()
                        for i in range(count)]

            store.create_pods(pods(0, 24))
            _pump(sched, bs)
            store.create_pods(pods(1, 24))
            assert bs.run_batch(pop_timeout=0.1) == 16
            assert sched.wait_for_inflight_bindings()
            sess = bs.session
            assert sess.mirror_current()
            static = sess._static
            do, _ = _state_planes(static.r, static.sc, static.t, static.sv)
            n = sess._cluster.allocatable.shape[0]

            def pod_count(state):
                planes = np.asarray(state.planes)
                return planes.reshape(planes.shape[0], n)[do["pod_count"]]

            # the carry holds every committed pod, and nothing else
            before = pod_count(sess._state).sum()
            assert before == 24 + 16
            bound = [p for p in store.list_pods()
                     if p.spec.node_name and p.metadata.name.startswith("w0")]
            for p in bound[:5]:
                store.delete_pod(p.metadata.namespace, p.metadata.name)
            assert not sess.mirror_current()
            applied = sess._mirror.catch_up(sess._last_seq,
                                            sched.cache.mutation_seq)
            assert applied is not None
            got = _state_rows(sess._state)
            after = pod_count(sess._state).sum()

            sched.algorithm.update_snapshot()
            cluster, batch = BatchEncoder(
                sched.algorithm.snapshot, pad_nodes=sess.pad_nodes,
            ).encode(sched.queue.pending_pods()[:16], pad_pods=16)
            be = sess._active
            if hasattr(be, "prepare_state_only"):
                fresh = be.prepare_state_only(cluster, batch)
            else:
                _static, fresh = be.prepare(cluster, batch)
            assert np.array_equal(got, _state_rows(fresh))
            assert after == before - 5
            assert after == len([p for p in store.list_pods()
                                 if p.spec.node_name])
        finally:
            sched.stop()

    def test_pallas(self, monkeypatch):
        monkeypatch.setenv("KTPU_SOLVER", "pallas")
        self._check()

    def test_native(self):
        from kubernetes_tpu.ops import native_backend

        self._check(native_backend.CppBackend())

    @pytest.mark.parametrize("devices", [1, 2, 4])
    def test_sharded_donated(self, devices):
        import jax

        from kubernetes_tpu.parallel import ShardedBackend, make_mesh

        if len(jax.devices()) < devices:
            pytest.skip(f"needs {devices} devices")
        self._check(ShardedBackend(make_mesh(devices, batch_axis=1),
                                   donate=True))


ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"


def _spread_pod(name, node="", ns="default", owner=True, app="bar",
                cpu="200m"):
    """A pod labelled ``app=<app>``; an owner also carries hostname and
    zone ``DoNotSchedule`` constraints that count ``app=bar`` pods."""
    w = (MakePod().name(name).namespace(ns).uid(f"u-{ns}-{name}")
         .label("app", app).req({"cpu": cpu, "memory": "256Mi"}))
    if owner:
        w = (w.spread_constraint(10, HOST, "DoNotSchedule", {"app": "bar"})
             .spread_constraint(10, ZONE, "DoNotSchedule", {"app": "bar"}))
    if node:
        w = w.node(node)
    return w.obj()


class TestSpreadCatchUp:
    """The spread twin of ``TestCarryRestore``: running spread owners,
    pods only matched by the tracked hostname and zone constraints, a
    pod in another namespace, a terminating pod, a relabelled pod and a
    node without the zone label all change under the resident
    encoding. The catch-up scatters every one of them into the
    ``sc_counts`` rows, and the state equals a fresh state-only
    rebuild, bit for bit, on every backend the mirror serves."""

    def _seeded(self, backend=None, warm=False):
        """A scheduler whose mirror is current after 24 + 16 committed
        spread pods, with the running pods of every kind bound."""
        store = ClusterStore()
        for i in range(10):
            w = MakeNode().name(f"n{i}").capacity(
                {"cpu": "8", "memory": "16Gi"})
            if i < 9:   # n9 has no zone label
                w = w.label(ZONE, f"z{i % 3}")
            store.add_node(w.obj())
        sched, bs = _make_sched(store, max_batch=16, backend=backend)
        if warm:
            bs.warmup(sample_pods=[_spread_pod("warm-0")])
        running = [_spread_pod(f"own-{i}", f"n{i % 10}") for i in range(10)]
        running += [_spread_pod(f"match-{i}", f"n{(3 * i) % 10}",
                                owner=False) for i in range(8)]
        running += [_spread_pod("far-0", "n1", ns="other"),
                    _spread_pod("plain-0", "n2", owner=False, app="baz")]
        store.create_pods(running)
        store.create_pods([_spread_pod(f"w0-{i}") for i in range(24)])
        _pump(sched, bs)
        store.create_pods([_spread_pod(f"w1-{i}") for i in range(24)])
        assert bs.run_batch(pop_timeout=0.1) == 16
        assert sched.wait_for_inflight_bindings()
        assert bs.session.mirror_current()
        return store, sched, bs

    def _check(self, backend=None):
        from kubernetes_tpu.ops.encode import BatchEncoder
        from kubernetes_tpu.ops.pallas_solver import _state_planes

        store, sched, bs = self._seeded(backend)
        try:
            sess = bs.session
            mirror = sess._mirror
            static = sess._static
            assert static.sc == 2
            do, _ = _state_planes(static.r, static.sc, static.t, static.sv)
            n = sess._cluster.allocatable.shape[0]

            def sc_rows(state):
                planes = np.asarray(state.planes)
                rows = planes.reshape(planes.shape[0], n)
                return rows[do["sc_counts"]:do["sc_counts"] + 2].copy()

            sc_before = sc_rows(sess._state)
            w0 = [p for p in store.list_pods()
                  if p.metadata.name.startswith("w0") and p.spec.node_name]
            store.delete_pods(
                [("default", p.metadata.name) for p in w0[:6]]
                + [("default", f"own-{i}") for i in (0, 4, 9)]
                + [("default", f"match-{i}") for i in (0, 3, 5)]
                + [("other", "far-0"), ("default", "plain-0")])
            # a new revision of match-1 (on n3) that no constraint counts
            store.update_pod(_spread_pod("match-1", "n3", owner=False,
                                         app="baz"))
            store.create_pods([
                _spread_pod("match-9", "n9", owner=False),
                _spread_pod("own-10", "n3"),
                _spread_pod("far-1", "n2", ns="other", owner=False),
                MakePod().name("gone-0").uid("u-gone-0").label("app", "bar")
                .req({"cpu": "100m", "memory": "256Mi"}).node("n5")
                .terminating().obj(),
            ])
            assert not sess.mirror_current()
            spread0 = mirror.info()["spread_pods"]
            applied = mirror.catch_up(sess._last_seq,
                                      sched.cache.mutation_seq)
            assert applied is not None and mirror.last_reason is None
            # deleted owners and matched pods, the relabel's matched old
            # side, the two added; not the other namespace, the
            # unmatched pods or the terminating one
            assert mirror.info()["spread_pods"] - spread0 == 6 + 3 + 3 + 1 + 2
            got = _state_rows(sess._state)
            assert not np.array_equal(sc_rows(sess._state), sc_before)

            sched.algorithm.update_snapshot()
            cluster, batch = BatchEncoder(
                sched.algorithm.snapshot, pad_nodes=sess.pad_nodes,
            ).encode(sched.queue.pending_pods()[:16], pad_pods=16)
            be = sess._active
            if hasattr(be, "prepare_state_only"):
                fresh = be.prepare_state_only(cluster, batch)
            else:
                _static, fresh = be.prepare(cluster, batch)
            assert np.array_equal(got, _state_rows(fresh))
        finally:
            sched.stop()

    def test_pallas(self, monkeypatch):
        monkeypatch.setenv("KTPU_SOLVER", "pallas")
        self._check()

    def test_native(self):
        from kubernetes_tpu.ops import native_backend

        self._check(native_backend.CppBackend())

    @pytest.mark.parametrize("devices", [1, 2, 4])
    def test_sharded_donated(self, devices):
        import jax

        from kubernetes_tpu.parallel import ShardedBackend, make_mesh

        if len(jax.devices()) < devices:
            pytest.skip(f"needs {devices} devices")
        self._check(ShardedBackend(make_mesh(devices, batch_axis=1),
                                   donate=True))

    def test_warmup_compiles_every_scatter_bucket(self, monkeypatch):
        """After the sidecar's warm-up no catch-up compiles a scatter:
        every entry bucket was dispatched against the same shapes."""
        from kubernetes_tpu.ops.pallas_solver import _scatter_flat_add_jit

        monkeypatch.setenv("KTPU_SOLVER", "pallas")
        _scatter_flat_add_jit.clear_cache()
        store, sched, bs = self._seeded(warm=True)
        try:
            sess = bs.session
            warmed = _scatter_flat_add_jit._cache_size()
            assert warmed >= 1
            for k in range(1, 40, 6):
                store.delete_pods([("default", f"w0-{i}")
                                   for i in range(k - 6, k) if i >= 0])
                store.create_pods([_spread_pod(f"add-{k}-{i}", f"n{i}")
                                   for i in range(k % 10)])
                assert sess._mirror.catch_up(
                    sess._last_seq, sched.cache.mutation_seq) is not None
                sess._last_seq = sched.cache.mutation_seq
            assert sess._mirror.info()["spread_pods"] > 0
            assert _scatter_flat_add_jit._cache_size() == warmed
        finally:
            sched.stop()

    def test_anti_affinity_owner_refuses(self, monkeypatch):
        """An anti-affinity owner registers a term: still a reseed."""
        monkeypatch.setenv("KTPU_SOLVER", "pallas")
        store, sched, bs = self._seeded()
        try:
            sess = bs.session
            store.create_pods([
                _spread_pod("match-9", "n4", owner=False),
                MakePod().name("anti-0").uid("u-anti-0").label("app", "bar")
                .req({"cpu": "100m", "memory": "256Mi"}).node("n6")
                .pod_anti_affinity("app", ["bar"], HOST).obj(),
            ])
            assert sess._mirror.catch_up(
                sess._last_seq, sched.cache.mutation_seq) is None
            assert sess._mirror.last_reason == "pod_affinity_terms"
        finally:
            sched.stop()


class TestMeshDifferential:
    """The sharded tier: mirror-on ≡ mirror-off across mesh {1, 2, 4}
    × 3 seeds (the scatter routes through GSPMD to the shard owning
    each node column — out_shardings pins the planes layout)."""

    @pytest.mark.parametrize("devices", [1, 2, 4])
    @pytest.mark.parametrize("seed", [3, 14, 77])
    def test_capacity_churn_bit_identical(self, devices, seed,
                                          monkeypatch):
        import jax

        if len(jax.devices()) < devices:
            pytest.skip(f"needs {devices} devices")
        on, ion = _run_scenario("capacity_churn", seed, True,
                                monkeypatch, devices=devices)
        off, _ = _run_scenario("capacity_churn", seed, False,
                               monkeypatch, devices=devices)
        assert on == off
        assert ion["events"] > 0


class TestSustainedMirrorCell:
    """Tier-1 sustained mini-cell: the tentpole's measurable claim at
    compressed scale — host cluster-plane encode share near zero with
    zero lost pods on an open-loop arrival row."""

    def test_encode_share_near_zero_zero_lost(self):
        from kubernetes_tpu.harness.sustained import run_sustained_cell

        cell = run_sustained_cell(pods=600, qps=400.0, max_batch=64,
                                  wait_timeout=120.0)
        assert cell["lost"] == 0
        assert cell["ever_bound"] == cell["injected"] == 600
        # the mirror rode the row (default-on) ...
        assert cell["mirror"] is not None
        # ... and the encode stage is gone from the sustained path:
        # what remains under "encode" is cluster-plane builds (cold
        # seed + rare reseeds), amortized to noise over the row
        assert cell["encode_share"] < 0.05
        assert cell["staleness_verdict"] in (None, "ok")

    def test_mirror_off_reference_still_clean(self, monkeypatch):
        """The differential reference arm stays healthy: KTPU_MIRROR=off
        must not regress the zero-lost invariant (it is the committed
        fallback, not a dead code path)."""
        monkeypatch.setenv("KTPU_MIRROR", "off")
        from kubernetes_tpu.harness.sustained import run_sustained_cell

        cell = run_sustained_cell(pods=300, qps=400.0, max_batch=64,
                                  wait_timeout=120.0)
        assert cell["lost"] == 0
        assert cell["mirror"] is None
