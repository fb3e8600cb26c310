"""Fleet-wide distributed tracing: wire context propagation, the trace
federation's skew-corrected merge, and critical-path attribution.

The tier-1 mini-cell here is the PR's acceptance path: two in-proc
partition apiservers (each with its OWN tracer ring, modeling separate
processes) plus this process as the scheduler replica, all traffic over
real REST. A sampled pod's trace must stitch across the processes with
zero orphan spans, every imported span must carry the half-RTT skew
bound, and the ``KTPU_TRACE=off`` arm must shed the layer entirely —
no ``X-Ktpu-Trace`` header on the wire at all."""

import json
import time
import urllib.request

import pytest

from kubernetes_tpu.apiserver.rest import APIServer
from kubernetes_tpu.apiserver.store import ClusterStore
from kubernetes_tpu.metrics.registry import MetricsRegistry
from kubernetes_tpu.observability import get_tracer
from kubernetes_tpu.observability.fleettrace import (
    TraceFederation,
    collect_fleet_trace,
    critical_path,
    phase_of,
)
from kubernetes_tpu.observability.tracer import (
    TRACE_HEADER,
    Tracer,
    format_trace_header,
    parse_trace_header,
)
from kubernetes_tpu.testing import MakeNode, MakePod


@pytest.fixture
def global_tracer():
    t = get_tracer()
    saved = (t.enabled, t.sample_rate, t.seed, t.retain_s)
    t.clear()
    t.configure(enabled=True, sample_rate=1.0)
    yield t
    (t.enabled, t.sample_rate, t.seed, t.retain_s) = saved
    t.clear()


def _pod(name, ns="default", uid=None):
    p = MakePod().name(name).uid(uid or f"u-{ns}-{name}").req(
        {"cpu": "100m", "memory": "50Mi"}).obj()
    p.metadata.namespace = ns
    return p


def _node(name):
    return MakeNode().name(name).capacity(
        {"cpu": "8", "memory": "16Gi", "pods": "110"}).obj()


# ---------------------------------------------------------------------------
# wire format + sampling override (satellite a)


class TestTraceContextWire:
    def test_header_round_trip(self):
        hdr = format_trace_header("pod-uid-1", 42, True)
        ctx = parse_trace_header(hdr)
        assert ctx.trace == "pod-uid-1"
        assert ctx.parent == 42
        assert ctx.sampled is True
        assert parse_trace_header(ctx.header_value()) == ctx
        off = parse_trace_header(format_trace_header("t", 0, False))
        assert off.sampled is False

    def test_malformed_header_is_none_never_raises(self):
        for bad in ("", "justatrace", "t;notanint;1", "t;1;2;3;4",
                    ";;;", None):
            assert parse_trace_header(bad) is None

    def test_inbound_decision_overrides_local_sampling(self):
        # a tracer that would NEVER sample locally must honor an
        # explicit inbound sampled=1 ...
        never = Tracer(component="t", sample_rate=0.0,
                       registry=MetricsRegistry())
        assert never.sampled("uid-x", inbound=True)
        assert not never.sampled("uid-x", inbound=False)
        assert not never.sampled("uid-x")
        # ... and one that ALWAYS would must honor inbound sampled=0
        always = Tracer(component="t", sample_rate=1.0,
                        registry=MetricsRegistry())
        assert not always.sampled("uid-x", inbound=False)
        assert always.sampled("uid-x", inbound=True)
        assert always.sampled("uid-x")
        # the enabled check still wins over everything
        off = Tracer(component="t", enabled=False,
                     registry=MetricsRegistry())
        assert not off.sampled("uid-x", inbound=True)

    def test_bulk_elects_one_context_with_uid_list_attribute(
            self, global_tracer):
        from kubernetes_tpu.client.restcluster import RestClusterClient

        uids = ["bulk-u1", "bulk-u2", "bulk-u3"]
        hdr = RestClusterClient._trace_ctx_for(uids)
        ctx = parse_trace_header(hdr)
        # ONE context for the whole batch, elected deterministically
        assert ctx.trace == "bulk-u1" and ctx.sampled is True
        # no open span -> the sampled-uid list rides a client.batch
        # instant event
        batch = [r for r in global_tracer._ring
                 if r[0] == "client.batch"]
        assert batch and batch[-1][8]["uids"] == uids
        # with an open span, the list annotates THAT span instead
        with global_tracer.span("cycle") as sp:
            RestClusterClient._trace_ctx_for(uids)
            assert sp.attrs.get("trace_uids") == uids
        # nothing sampled -> no header at all
        global_tracer.configure(sample_rate=0.0)
        assert RestClusterClient._trace_ctx_for(uids) is None


# ---------------------------------------------------------------------------
# critical-path analysis (pure, no servers)


class TestCriticalPath:
    def test_phase_classification(self):
        assert phase_of("rest.ingest") == "rest"
        assert phase_of("rest.POST") == "rest"
        assert phase_of("queue.wait") == "queue"
        assert phase_of("solve.encode") == "encode"
        assert phase_of("solve.device") == "solve"
        assert phase_of("solve.commit") == "commit"
        assert phase_of("sched.bind") == "bind"
        assert phase_of("watch.deliver") == "watch"
        assert phase_of("reshard.freeze") == "seam"
        assert phase_of("upgrade.roll") == "seam"
        assert phase_of("unrelated") is None
        # the scheduling cycle's host stages: rebuild time is encode,
        # never device solve
        for name in ("solve.rebuild", "solve.snapshot", "solve.prepare",
                     "solve.scatter", "solve.pack"):
            assert phase_of(name) == "encode", name
        assert phase_of("solve.block") == "solve"
        assert phase_of("sidecar.drain") == "queue"
        assert phase_of("sidecar.serial") == "solve"
        assert phase_of("bind.bulk") == "bind"

    def test_priority_sweep_and_unattributed(self):
        t = Tracer(component="t", sample_rate=1.0,
                   registry=MetricsRegistry())
        now = time.monotonic()
        # 1.0s window: queue covers all of it, commit overlays the last
        # 0.4s (commit outranks queue), and a 0.1s head gap is left raw
        t.record("rest.ingest", now - 1.0, now - 0.998, trace="p1")
        t.record("queue.wait", now - 0.9, now - 0.4, trace="p1")
        t.record("solve.commit", now - 0.6, now - 0.2)  # batch-level
        t.record("sched.bind", now - 0.2, now, trace="p1")
        fed = TraceFederation()
        fed.absorb_local(t, "solo")
        cp = critical_path(fed.merged())
        assert cp["pods"] == 1
        pod = cp["per_pod"][0]
        # commit owns [−0.6,−0.4] even though queue.wait covers it too
        assert pod["phases_ms"]["commit"] == pytest.approx(400, abs=20)
        assert pod["phases_ms"]["queue"] == pytest.approx(300, abs=20)
        assert pod["phases_ms"]["bind"] == pytest.approx(200, abs=20)
        # [−0.998,−0.9] has no covering span: ~10% unattributed
        assert 0.05 < cp["unattributed_share"] < 0.15
        assert cp["top"] == "commit"

    def test_seam_spans_attribute_overlapping_stalls(self):
        t = Tracer(component="t", sample_rate=1.0,
                   registry=MetricsRegistry())
        now = time.monotonic()
        t.record("rest.ingest", now - 1.0, now - 0.99, trace="p1")
        t.record("sched.bind", now - 0.1, now, trace="p1")
        # a reshard freeze explains the dead middle of the window
        t.record("reshard.freeze", now - 0.8, now - 0.3, trace="seam:4")
        cp = critical_path(_merged_of(t))
        assert cp["seam_windows"] == 1
        assert cp["per_pod"][0]["phases_ms"]["seam"] == pytest.approx(
            500, abs=25)


def _merged_of(tracer):
    fed = TraceFederation()
    fed.absorb_local(tracer, "solo")
    return fed.merged()


# ---------------------------------------------------------------------------
# the tier-1 mini-cell: 2 partitions + 1 scheduler replica over REST


class TestFleetMiniCell:
    def _spin_up(self, parts=2):
        servers = []
        for i in range(parts):
            s = APIServer(store=ClusterStore(),
                          partition=(i, parts)).start()
            # each server gets its OWN ring: in-proc stand-in for a
            # separate process's flight recorder (rest.py reads
            # server.tracer everywhere)
            s.tracer = Tracer(component=f"partition-{i}",
                              sample_rate=1.0,
                              registry=MetricsRegistry())
            servers.append(s)
        return servers, [s.url for s in servers]

    def test_sampled_trace_stitches_across_processes(self, global_tracer):
        from kubernetes_tpu.client.restcluster import RestClusterClient

        servers, urls = self._spin_up(2)
        client = RestClusterClient(urls[0], partition_urls=urls,
                                   watch_kinds=("Pod",))
        delivered = []
        try:
            client.watch(lambda e: delivered.append(e),
                         batch_fn=lambda evs: delivered.extend(evs))
            time.sleep(0.3)
            # namespaces spread over both partitions so every process
            # participates in the merged timeline
            pods = [_pod(f"ft{i}", ns=f"ns{i}") for i in range(8)]
            assert client.create_objects_bulk("Pod", pods) == 8
            client.create_objects_bulk(
                "Node", [_node(f"ftn{i}") for i in range(2)])
            errs = client.bind_many([
                (p.metadata.namespace, p.metadata.name,
                 p.metadata.uid, "ftn0") for p in pods])
            assert errs == [None] * 8
            # watch.deliver spans land on the scheduler ring once the
            # origin-stamped events arrive
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if any(r[0] == "watch.deliver"
                       for r in global_tracer._ring):
                    break
                time.sleep(0.05)
            else:
                pytest.fail("no watch.deliver span ever recorded "
                            "(origin context lost on the watch path)")
            # both servers saw propagated contexts on the wire
            assert all(s.trace_headers_seen > 0 for s in servers)

            doc, cp = collect_fleet_trace(
                remote=[(f"partition-{i}", u)
                        for i, u in enumerate(urls)],
                local=[("scheduler", global_tracer)])
            instances = doc["otherData"]["instances"]
            assert set(instances) == {"partition-0", "partition-1",
                                      "scheduler"}
            assert doc["otherData"]["scrape_errors"] == []
            events = [e for e in doc["traceEvents"]
                      if e["ph"] in ("X", "i")]
            by_instance = {}
            for e in events:
                by_instance.setdefault(
                    e["args"]["instance"], []).append(e)
            # every process contributed spans to the merged timeline
            assert set(by_instance) == set(instances)

            # skew correction applied: scraped rings carry the half-RTT
            # bound on EVERY imported span; the local ring is exact
            for i in range(2):
                inst = f"partition-{i}"
                bound = instances[inst]["skew_ms"]
                assert bound > 0.0
                assert all(e["args"]["skew_ms"] == bound
                           for e in by_instance[inst])
            assert all(e["args"]["skew_ms"] == 0.0
                       for e in by_instance["scheduler"])

            # the elected bulk trace stitches scheduler -> its
            # partition server -> back to the scheduler (watch hop)
            stitched = [e for e in events
                        if str(e["args"].get("trace", ""))
                        .startswith("u-ns")]
            traces = {}
            for e in stitched:
                traces.setdefault(e["args"]["trace"], set()).add(
                    e["args"]["instance"])
            cross = {t: insts for t, insts in traces.items()
                     if len(insts) >= 2}
            assert cross, f"no trace crossed a process: {traces}"
            best = max(cross.values(), key=len)
            assert "scheduler" in best
            assert any(i.startswith("partition-") for i in best)
            names_of_best = {
                e["name"] for e in stitched
                if e["args"]["trace"] == max(cross, key=lambda t: len(
                    cross[t]))}
            assert "rest.ingest" in names_of_best
            assert "watch.deliver" in names_of_best

            # zero orphan spans: within each instance every nonzero
            # parent id resolves to a span id of the same instance
            ids = {}
            for e in events:
                ids.setdefault(e["args"]["instance"], set()).add(
                    e["args"]["id"])
            orphans = [e for e in events if e["args"]["parent"]
                       and e["args"]["parent"]
                       not in ids[e["args"]["instance"]]]
            assert orphans == [], orphans

            # the aggregate the bench row would carry
            assert cp["pods"] >= 1
            assert cp["max_skew_ms"] > 0.0
            assert cp["max_skew_ms"] <= cp["skew_bound_ms"]
        finally:
            client._stop_watches()
            client._drop_conn()
            for s in servers:
                s.shutdown_server()

    def test_trace_off_arm_sheds_header_on_wire(self, global_tracer):
        from kubernetes_tpu.client.restcluster import RestClusterClient

        global_tracer.configure(enabled=False)
        servers, urls = self._spin_up(2)
        client = RestClusterClient(urls[0], partition_urls=urls,
                                   watch_kinds=("Pod",))
        try:
            client.watch(lambda e: None, batch_fn=lambda evs: None)
            time.sleep(0.3)
            pods = [_pod(f"off{i}", ns=f"ns{i}") for i in range(4)]
            assert client.create_objects_bulk("Pod", pods) == 4
            client.create_objects_bulk("Node", [_node("offn0")])
            client.bind_many([
                (p.metadata.namespace, p.metadata.name,
                 p.metadata.uid, "offn0") for p in pods])
            # the layer is SHED, not just quiet: no request — bulk,
            # bind, or the watch handoff itself — carried the header
            assert all(s.trace_headers_seen == 0 for s in servers), \
                [s.trace_headers_seen for s in servers]
        finally:
            client._stop_watches()
            client._drop_conn()
            for s in servers:
                s.shutdown_server()

    def test_scrape_survives_dead_instance(self, global_tracer):
        fed = TraceFederation()
        ok = fed.scrape("http://127.0.0.1:9", "dead")
        assert ok is False
        assert fed.scrape_errors and "dead" in fed.scrape_errors[0]
        # the merge still renders from whatever WAS imported
        fed.absorb_local(global_tracer, "scheduler")
        doc = fed.merged()
        assert doc["otherData"]["scrape_errors"]
        assert "scheduler" in doc["otherData"]["instances"]


# ---------------------------------------------------------------------------
# /debug/trace clock-offset handshake


class TestClockOffsetEcho:
    def test_server_echoes_monotonic_stamp(self, global_tracer):
        server = APIServer(store=ClusterStore()).start()
        try:
            global_tracer.event("probe")
            t0 = time.monotonic()
            with urllib.request.urlopen(
                    f"{server.url}/debug/trace?echo_mono={t0!r}",
                    timeout=10) as resp:
                doc = json.loads(resp.read())
            other = doc["otherData"]
            assert other["echo_mono"] == pytest.approx(t0)
            # same process here, so the server's monotonic stamp sits
            # between send and now
            assert t0 <= other["server_mono"] <= time.monotonic()
        finally:
            server.shutdown_server()

    def test_federation_offset_near_zero_for_same_host(
            self, global_tracer):
        server = APIServer(store=ClusterStore()).start()
        try:
            global_tracer.event("probe")
            fed = TraceFederation()
            assert fed.scrape(server.url, "api")
            # same clock: the half-RTT estimate must be tiny, and the
            # recorded bound must cover the true offset (zero)
            assert abs(fed._offsets["api"]) <= max(
                0.05, fed._skew_ms["api"] / 1000.0)
            assert fed._skew_ms["api"] > 0.0
        finally:
            server.shutdown_server()
