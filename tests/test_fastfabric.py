"""Fast REST fabric: bulk hot-path verbs, pipelined watch delivery, and
codec/connection overhead elimination (ISSUE 5).

Covers the contracts the perf work must not bend:

- bulk-verb round-trips over the binary codec (create/bind/status as
  ``{Kind}List`` requests) cross-checked against store truth;
- coalesced-watch framing: batched event chunks decode, a frame split
  mid-event is detected as torn (relist), cached event bytes are shared;
- the per-object and bulk paths produce IDENTICAL store mutation
  sequences (events, order, resource versions);
- token-bucket rate equivalence: a bulk request of N objects charges
  the same budget as N singles (the documented RestClusterClient
  contract), so the perf win cannot come from laundering client QPS;
- bench emission order: the REST row prints immediately before the
  headline (the driver tail-captures stdout) and parses with the
  fabric-overhead ratio;
- gang batches no longer churn the solver session (WAIT-parked pods
  count through the commit mutation ledger).
"""

from __future__ import annotations

import io
import json
import time

import pytest

from kubernetes_tpu.api.resource import parse_quantity
from kubernetes_tpu.api.types import (
    Container,
    ObjectMeta,
    Pod,
    PodSpec,
    ResourceRequirements,
)
from kubernetes_tpu.apiserver import codec
from kubernetes_tpu.apiserver.rest import APIServer
from kubernetes_tpu.apiserver.store import ClusterStore, Event
from kubernetes_tpu.client.restcluster import RestClusterClient
from kubernetes_tpu.testing import MakeNode, MakePod


def _pod(name: str, uid: str = "") -> Pod:
    return Pod(
        metadata=ObjectMeta(name=name, namespace="default",
                            uid=uid or f"uid-{name}"),
        spec=PodSpec(containers=[Container(
            name="c",
            resources=ResourceRequirements(
                requests={"cpu": parse_quantity("100m")}),
        )]),
    )


def _serve(store_factory=ClusterStore):
    server = APIServer(store=store_factory()).start()
    return server.store, server


# ---------------------------------------------------------------------------
# bulk-verb round-trip over the binary codec


class TestBulkVerbRoundTrip:
    def test_create_bind_status_bulk_binary_cross_checked(self):
        store, server = _serve()
        client = RestClusterClient(server.url, binary=True)
        try:
            node = MakeNode().name("n1").capacity(
                {"cpu": "64", "memory": "256Gi", "pods": "500"}).obj()
            code, resp = client._request(
                "POST", "/api/v1/nodes",
                {"kind": "NodeList", "items": [node]}, charge=1)
            assert code == 201 and not resp.get("failures")

            pods = [_pod(f"p{i}") for i in range(40)]
            code, resp = client._request(
                "POST", "/api/v1/namespaces/default/pods",
                {"kind": "PodList", "items": pods}, charge=len(pods))
            assert code == 201
            assert resp.get("created") == 40 and not resp.get("failures")

            errors = client.bind_many([
                ("default", p.metadata.name, p.metadata.uid, "n1")
                for p in pods
            ])
            assert errors == [None] * 40

            updates = [{"namespace": "default", "name": p.metadata.name,
                        "status": {"phase": "Running",
                                   "podIP": f"10.0.0.{i}"}}
                       for i, p in enumerate(pods)]
            errs = client.write_pod_statuses(updates)
            assert errs == [None] * 40

            # store truth: every pod bound to n1, Running, IP stamped,
            # resourceVersions strictly increasing across the flow
            live = {p.metadata.name: p for p in store.list_pods()}
            assert len(live) == 40
            for i, p in enumerate(pods):
                got = live[p.metadata.name]
                assert got.spec.node_name == "n1"
                assert got.status.phase == "Running"
                assert got.status.pod_ip == f"10.0.0.{i}"
        finally:
            client._drop_conn()
            server.shutdown_server()

    def test_bulk_status_reports_positional_failures(self):
        store, server = _serve()
        client = RestClusterClient(server.url, binary=True)
        try:
            store.create_pod(_pod("exists"))
            errs = client.write_pod_statuses([
                {"namespace": "default", "name": "exists",
                 "status": {"phase": "Running"}},
                {"namespace": "default", "name": "ghost",
                 "status": {"phase": "Running"}},
            ])
            # 404s are None (pod deleted under us — single-PUT no-op
            # semantics); the live pod applied
            assert errs == [None, None]
            assert store.get_pod("default", "exists").status.phase \
                == "Running"
            assert store.get_pod("default", "ghost") is None
        finally:
            client._drop_conn()
            server.shutdown_server()

    def test_bulk_status_conditions_and_nomination(self):
        store, server = _serve()
        client = RestClusterClient(server.url, binary=True)
        try:
            store.create_pod(_pod("p1"))
            errs = client.write_pod_statuses([
                {"namespace": "default", "name": "p1", "status": {
                    "conditions": [{"type": "PodScheduled",
                                    "status": "False",
                                    "reason": "Unschedulable",
                                    "message": "no fit"}],
                    "nominatedNodeName": "n9",
                }},
            ])
            assert errs == [None]
            pod = store.get_pod("default", "p1")
            conds = {c.type: c for c in pod.status.conditions}
            assert conds["PodScheduled"].reason == "Unschedulable"
            assert pod.status.nominated_node_name == "n9"
        finally:
            client._drop_conn()
            server.shutdown_server()


# ---------------------------------------------------------------------------
# coalesced watch framing


class TestCoalescedWatchFraming:
    def test_batched_chunks_decode_and_carry_old(self):
        store, server = _serve()
        client = RestClusterClient(server.url, binary=True,
                                   watch_kinds=("Pod",))
        batches = []
        try:
            handle = client.watch(lambda e: None,
                                  batch_fn=batches.append)
            time.sleep(0.3)   # initial list + stream up
            pods = [_pod(f"w{i}") for i in range(64)]
            store.create_pods(pods)
            store.bind_many([
                ("default", p.metadata.name, p.metadata.uid, "n1")
                for p in pods
            ])
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                seen = [e for b in batches for e in b]
                if len(seen) >= 128:
                    break
                time.sleep(0.05)
            seen = [e for b in batches for e in b]
            adds = [e for e in seen if e.type == "ADDED"]
            mods = [e for e in seen if e.type == "MODIFIED"]
            assert len(adds) == 64 and len(mods) == 64
            # coalescing actually happened: fewer chunks than events
            assert len(batches) < len(seen)
            # old_obj rides along (bind-transition detection keys on it)
            assert all(m.old_obj is not None
                       and not m.old_obj.spec.node_name for m in mods)
            handle.stop()
        finally:
            client._drop_conn()
            server.shutdown_server()

    def test_event_bytes_cached_across_watchers(self):
        from kubernetes_tpu.apiserver.rest import _cached_event_bytes

        pod = _pod("c1")
        event = Event("ADDED", "Pod", pod)
        b1 = _cached_event_bytes(event)
        b2 = _cached_event_bytes(event)
        assert b1 is b2   # second watcher reuses the first encode
        t, obj, old, ts = codec.decode(b1)
        assert t == "ADDED" and obj.metadata.name == "c1" and old is None
        # the commit stamp rides the cached encoding (freshness SLI);
        # an un-dispatched event carries the 0.0 sentinel
        assert ts == 0.0

    def test_frame_split_mid_event_reads_as_torn(self):
        events = [codec.encode(("ADDED", _pod(f"t{i}"), None))
                  for i in range(8)]
        wire = codec.frame(events)
        # a complete frame decodes whole
        batch = codec.read_frame(io.BytesIO(wire))
        assert [codec.decode(b)[1].metadata.name for b in batch] \
            == [f"t{i}" for i in range(8)]
        # cut mid-event (inside the pickled body): torn -> None, the
        # client's relist trigger — no partial batch is ever delivered
        for cut in (2, codec.FRAME_LEN_BYTES + 10, len(wire) - 3):
            assert codec.read_frame(io.BytesIO(wire[:cut])) is None

    def test_json_watchers_coalesce_but_still_parse_by_line(self):
        store, server = _serve()
        from kubernetes_tpu.apiserver.rest import RestClient

        client = RestClient(server.url)
        got = []
        try:
            handle = client.watch("Pod", 0, lambda t, o: got.append((t, o)))
            time.sleep(0.3)
            store.create_pods([_pod(f"j{i}") for i in range(16)])
            deadline = time.monotonic() + 5.0
            while len(got) < 16 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert len(got) == 16
            assert {o.metadata.name for _, o in got} \
                == {f"j{i}" for i in range(16)}
            handle.stop()
        finally:
            server.shutdown_server()


class TestListCache:
    def test_cached_list_refreshes_when_rv_compacts_out(self):
        """A quiet kind's cached list rv must not outlive the watch
        log: serving it after compaction would strand the reflector in
        a relist→410 loop (its watch from the stale rv can never
        attach)."""
        from kubernetes_tpu.apiserver.watchcache import (
            TooOldResourceVersion,
        )

        store, server = _serve()
        try:
            store.add_node(MakeNode().name("n1").capacity(
                {"cpu": "4", "memory": "8Gi"}).obj())
            body1 = server.cached_list_binary("Node", None)
            rv1 = codec.decode(body1)["resourceVersion"]
            # hit while valid: byte-identical cached body
            assert server.cached_list_binary("Node", None) is body1
            # other-kind churn advances the log, then compaction drops
            # everything at or below the Node list's rv
            store.create_pods([_pod(f"churn{i}") for i in range(50)])
            server.watch_cache.compact(keep_last=10)
            assert server.watch_cache.oldest_rv() > rv1
            body2 = server.cached_list_binary("Node", None)
            rv2 = codec.decode(body2)["resourceVersion"]
            assert rv2 > rv1
            # the refreshed rv can open a watch; the stale one cannot
            h = server.watch_cache.watch_from(rv2, lambda rv, e: None)
            h.stop()
            with pytest.raises(TooOldResourceVersion):
                server.watch_cache.watch_from(rv1, lambda rv, e: None)
        finally:
            server.shutdown_server()


# ---------------------------------------------------------------------------
# the fastfabric matrix over the PARTITIONED store (differential guard
# satellite of the sharded-control-plane PR): with partitions=1 the
# sharded store must be behaviorally identical to a bare ClusterStore,
# and with partitions>1 every bulk-verb/watch/list-cache semantic above
# must hold unchanged through the same REST surface.


class TestPartitionedFabricMatrix:
    @pytest.mark.parametrize("parts", [1, 3])
    def test_fabric_matrix_over_partitioned_store(self, parts,
                                                  monkeypatch):
        import sys as _sys

        from kubernetes_tpu.apiserver.partition import PartitionedStore

        mod = _sys.modules[__name__]
        monkeypatch.setattr(
            mod, "_serve",
            lambda: _orig_serve(lambda: PartitionedStore(parts)))
        TestBulkVerbRoundTrip(
        ).test_create_bind_status_bulk_binary_cross_checked()
        TestBulkVerbRoundTrip(
        ).test_bulk_status_reports_positional_failures()
        TestCoalescedWatchFraming(
        ).test_batched_chunks_decode_and_carry_old()
        TestListCache().test_cached_list_refreshes_when_rv_compacts_out()


_orig_serve = _serve


# ---------------------------------------------------------------------------
# per-object vs bulk: identical store mutation sequences


class TestMutationSequenceInvariant:
    @staticmethod
    def _record(store):
        log = []
        store.watch(lambda e: log.append(
            (e.type, e.kind, e.obj.metadata.name,
             e.obj.metadata.resource_version)))
        return log

    def test_create_and_bind_sequences_match(self):
        single, bulk = ClusterStore(), ClusterStore()
        log_s, log_b = self._record(single), self._record(bulk)

        pods_s = [_pod(f"p{i}") for i in range(12)]
        pods_b = [_pod(f"p{i}") for i in range(12)]
        for p in pods_s:
            single.create_pod(p)
        bulk.create_pods(pods_b)
        for p in pods_s:
            single.bind("default", p.metadata.name, p.metadata.uid, "n1")
        bulk.bind_many([
            ("default", p.metadata.name, p.metadata.uid, "n1")
            for p in pods_b
        ])
        assert log_s == log_b

    def test_status_sequences_match_over_rest(self):
        # two servers: one takes per-object PUTs, one the bulk verb —
        # watchers must observe identical event sequences and the
        # stores identical final state
        store_s, server_s = _serve()
        store_b, server_b = _serve()
        log_s, log_b = self._record(store_s), self._record(store_b)
        cs = RestClusterClient(server_s.url, binary=True)
        cb = RestClusterClient(server_b.url, binary=True)
        try:
            for store in (store_s, store_b):
                store.create_pods([_pod(f"p{i}") for i in range(6)])
            for i in range(6):
                cs._put_status("default", f"p{i}",
                               {"phase": "Running",
                                "nominatedNodeName": "n3"})
            cb.write_pod_statuses([
                {"namespace": "default", "name": f"p{i}",
                 "status": {"phase": "Running",
                            "nominatedNodeName": "n3"}}
                for i in range(6)
            ])
            assert log_s == log_b
            for i in range(6):
                ps = store_s.get_pod("default", f"p{i}")
                pb = store_b.get_pod("default", f"p{i}")
                assert ps.status.phase == pb.status.phase == "Running"
                assert ps.status.nominated_node_name \
                    == pb.status.nominated_node_name == "n3"
        finally:
            cs._drop_conn()
            cb._drop_conn()
            server_s.shutdown_server()
            server_b.shutdown_server()


# ---------------------------------------------------------------------------
# token-bucket rate equivalence


class _RecordingLimiter:
    def __init__(self):
        self.charges = []

    def charge(self, n: float = 1.0) -> None:
        self.charges.append(n)


class TestRateEquivalence:
    def test_bulk_verbs_charge_per_object(self):
        store, server = _serve()
        client = RestClusterClient(server.url, binary=True)
        limiter = _RecordingLimiter()
        client.limiter = limiter
        try:
            node = MakeNode().name("n1").capacity(
                {"cpu": "64", "memory": "256Gi", "pods": "500"}).obj()
            store.add_node(node)
            pods = [_pod(f"r{i}") for i in range(17)]
            client.create_objects_bulk("Pod", pods)
            client.bind_many([
                ("default", p.metadata.name, p.metadata.uid, "n1")
                for p in pods
            ])
            client.write_pod_statuses([
                {"namespace": "default", "name": p.metadata.name,
                 "status": {"phase": "Running"}} for p in pods
            ])
            # 3 bulk requests, each charging exactly N — the budget N
            # singles would pay (the documented contract; batching must
            # never launder rate)
            assert limiter.charges == [17.0, 17.0, 17.0] \
                or limiter.charges == [17, 17, 17]
        finally:
            client._drop_conn()
            server.shutdown_server()

    def test_batched_status_scope_charges_per_item(self):
        store, server = _serve()
        client = RestClusterClient(server.url, binary=True)
        limiter = _RecordingLimiter()
        client.limiter = limiter
        try:
            store.create_pods([_pod(f"s{i}") for i in range(9)])
            with client.batched_status_writes():
                for i in range(9):
                    client.set_nominated_node_name("default", f"s{i}",
                                                   "n1")
            assert limiter.charges == [9.0] or limiter.charges == [9]
            for i in range(9):
                assert store.get_pod(
                    "default", f"s{i}").status.nominated_node_name == "n1"
        finally:
            client._drop_conn()
            server.shutdown_server()

    def test_token_bucket_blocks_same_for_bulk_and_singles(self):
        from kubernetes_tpu.client.restcluster import TokenBucket

        # deterministic accounting check on the bucket itself: after
        # any charge pattern totalling N from a full bucket, the token
        # deficit is identical
        b1 = TokenBucket(qps=1000.0, burst=50.0)
        b2 = TokenBucket(qps=1000.0, burst=50.0)
        b1.charge(30)
        for _ in range(30):
            b2.charge(1)
        assert b1._tokens == pytest.approx(b2._tokens, abs=1.5)


# ---------------------------------------------------------------------------
# bench emission order + REST-row parse smoke (tier-1 regression guard)


class TestBenchRowOrder:
    def test_rest_row_prints_immediately_before_headline(self, capsys,
                                                         monkeypatch):
        import bench

        def fake_run_one(key, name, nodes, init_pods, measure_pods,
                         serial_rate, repeat=1):
            return {"metric": f"pods_scheduled_per_sec[{name} {key}]",
                    "value": 1000.0, "unit": "pods/s",
                    "vs_baseline": 10.0}

        def fake_run_rest_one(nodes, measure_pods, serial_rate, qps,
                              repeat=1):
            return {"metric":
                    "pods_scheduled_per_sec[SchedulingBasic REST fabric]",
                    "value": 4500.0, "unit": "pods/s",
                    "vs_baseline": 70.0, "p99_latency_ms": 900,
                    "store_direct_pods_per_sec": 7500.0,
                    "fabric_overhead_ratio": 0.6}

        def fake_run_qos_one(nodes, measure_pods, serial_rate, qps,
                             tenants=3, solo_baseline=None):
            # the default matrix hands the REST row's numbers over as
            # the solo baseline (same configuration, no third run)
            assert solo_baseline is not None
            assert solo_baseline["pods_per_sec"] == 4500.0
            return {"metric": "noisy_tenant_qos[SchedulingBasic]",
                    "value": 3000.0, "unit": "pods/s",
                    "vs_baseline": 48.0, "p99_ratio_vs_solo": 1.3,
                    "qos_ok": True}

        def fake_run_scale10x_one(serial_rate, qps, quick=False):
            return {"metric": "pods_scheduled_per_sec[Scale10x "
                              "400nodes/2000pods, partitioned fabric "
                              "2p x 2r]",
                    "value": 2000.0, "unit": "pods/s",
                    "vs_baseline": 32.0,
                    "ab": {"partitioned_pods_per_sec": 2000.0,
                           "single_partition_pods_per_sec": 1500.0,
                           "speedup": 1.33, "sharding_pays": True},
                    "invariants": {"lost_pods": 0, "double_binds": 0},
                    "conflict_cell": {"conflicts_total": 9, "ok": True}}

        monkeypatch.setattr(bench, "run_one", fake_run_one)
        monkeypatch.setattr(bench, "run_rest_one", fake_run_rest_one)
        monkeypatch.setattr(bench, "run_qos_one", fake_run_qos_one)
        monkeypatch.setattr(bench, "run_scale10x_one",
                            fake_run_scale10x_one)
        monkeypatch.setattr(bench.sys, "argv",
                            ["bench.py", "--skip-serial"])
        bench.main()
        lines = [ln for ln in capsys.readouterr().out.splitlines()
                 if ln.strip().startswith("{")]
        rows = [json.loads(ln) for ln in lines]
        idx_rest = next(i for i, r in enumerate(rows)
                        if "REST fabric" in r["metric"])
        idx_headline = len(rows) - 1
        # the driver tail-captures stdout: the REST row must be the
        # second-to-last JSON line, right before the headline — and the
        # noisy-tenant QoS row rides right before the REST row
        assert idx_rest == idx_headline - 1
        assert "REST fabric" not in rows[idx_headline]["metric"]
        idx_qos = next(i for i, r in enumerate(rows)
                       if "noisy_tenant_qos" in r["metric"])
        assert idx_qos == idx_rest - 1
        assert rows[idx_qos]["qos_ok"] is True
        # the 10×-tier partitioned-control-plane row rides right
        # before the QoS/REST/headline tail with its A/B intact
        idx_scale = next(i for i, r in enumerate(rows)
                         if "Scale10x" in r["metric"])
        assert idx_scale == idx_qos - 1
        assert rows[idx_scale]["ab"]["sharding_pays"] is True
        assert rows[idx_scale]["conflict_cell"]["ok"] is True
        # smoke: the REST row parses with its required fields
        rest = rows[idx_rest]
        assert rest["value"] > 0 and rest["unit"] == "pods/s"
        assert rest["fabric_overhead_ratio"] > 0
        assert rest["store_direct_pods_per_sec"] > 0

    def test_matrix_row_order_contract(self):
        import bench

        order = bench.matrix_row_order()
        assert order[-1] == "headline"
        assert order[-2] == "rest"
        assert order[-3] == "qos"
        assert order[-4] == "scale10x"
        order_all = bench.matrix_row_order(include_extra=True)
        assert order_all[-4:] == ["scale10x", "qos", "rest", "headline"]
        assert set(bench.EXTRA_MATRIX) < set(order_all)


# ---------------------------------------------------------------------------
# gang batches must not churn the solver session


class TestGangSessionStability:
    def test_wait_parked_gang_pods_keep_session_valid(self):
        """A batch whose gang members park at Permit (WAIT) assumes
        them without committing them; the commit mutation ledger must
        count those assumes or every gang batch reads as mirror drift
        (the r5 state-only-rebuild-per-batch churn)."""
        from kubernetes_tpu.config.feature_gates import FeatureGates
        from kubernetes_tpu.scheduler.framework.plugins.coscheduling import (  # noqa: E501
            GROUP_NAME_LABEL,
            MIN_AVAILABLE_LABEL,
        )
        from kubernetes_tpu.scheduler.scheduler import Scheduler
        from kubernetes_tpu.sidecar import attach_batch_scheduler

        store = ClusterStore()
        for i in range(8):
            store.add_node(
                MakeNode().name(f"n{i}")
                .capacity({"cpu": "16", "memory": "64Gi", "pods": "110"})
                .obj())
        sched = Scheduler.create(
            store, feature_gates=FeatureGates({"TPUBatchScheduler": True}),
            provider="GangSchedulingProvider")
        bs = attach_batch_scheduler(sched, max_batch=64)
        sched.start()
        try:
            # two full gangs — every member fits; members park at
            # Permit until their gang completes within the same batch
            pods = []
            for g in range(2):
                for m in range(10):
                    pods.append(
                        MakePod().name(f"g{g}-m{m}").uid(f"g{g}-m{m}")
                        .req({"cpu": "100m"})
                        .labels({GROUP_NAME_LABEL: f"gang-{g}",
                                 MIN_AVAILABLE_LABEL: "10"})
                        .obj())
            store.create_pods(pods)
            deadline = time.monotonic() + 30.0
            bound = 0
            while time.monotonic() < deadline and bound < 20:
                bs.run_batch(pop_timeout=0.05)
                bound = sum(1 for p in store.list_pods()
                            if p.spec.node_name)
            assert bound == 20
            sched.wait_for_inflight_bindings(timeout=10.0)
            # the solve that placed the gangs must not have poisoned
            # the session: WAIT-parked assumes are sanctioned mutations
            assert bs.session.mirror_current(), (
                "gang WAIT assumes invalidated the session "
                f"(rebuilds={bs.session.rebuilds}, "
                f"state_only={bs.session.state_only_rebuilds})")
        finally:
            sched.stop()
