"""Observability layer: span recorder, flight recorder, Perfetto export,
admin endpoints, causal-trace stitching, and the trace_report tool."""

import json
import time
import urllib.request

import pytest

from kubernetes_tpu.metrics.registry import MetricsRegistry
from kubernetes_tpu.observability import Tracer, get_tracer


@pytest.fixture
def global_tracer():
    """The process-wide tracer, reset around each test that touches it."""
    t = get_tracer()
    saved = (t.enabled, t.sample_rate, t.seed, t.retain_s, t._dump_dir)
    t.clear()
    t._last_dump_mono.clear()
    t.last_dump_path = None
    t.configure(enabled=True, sample_rate=1.0)
    yield t
    (t.enabled, t.sample_rate, t.seed, t.retain_s, t._dump_dir) = saved
    t.clear()


def _http(url, method="GET", body=None):
    req = urllib.request.Request(url, method=method,
                                 data=json.dumps(body).encode()
                                 if body is not None else None)
    if body is not None:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


class TestSpanRecorder:
    def test_span_nesting_and_ordering(self):
        t = Tracer(component="test", sample_rate=1.0,
                   registry=MetricsRegistry())
        with t.span("outer", trace="pod-1", kind="cycle") as outer:
            with t.span("inner") as inner:
                time.sleep(0.001)
            assert inner.parent_id == outer.span_id
            # children inherit the trace id from the enclosing span
            assert inner.trace == "pod-1"
        recs = list(t._ring)
        # inner closes (and records) before outer
        names = [r[0] for r in recs]
        assert names == ["inner", "outer"]
        inner_rec = recs[0]
        outer_rec = recs[1]
        assert inner_rec[6] == outer_rec[5]       # parent linkage
        assert inner_rec[3] <= outer_rec[3]       # nested duration
        assert outer_rec[8] == {"kind": "cycle"}  # attrs carried

    def test_explicit_record_and_event(self):
        t = Tracer(component="test", sample_rate=1.0,
                   registry=MetricsRegistry())
        now = time.monotonic()
        t.record("queue.wait", now - 0.25, now, trace="pod-2", attempts=1)
        t.event("rest.ingest", trace="pod-2")
        spans = [r for r in t._ring if r[1] == "X"]
        assert len(spans) == 1
        assert abs(spans[0][3] - 0.25) < 0.01
        events = [r for r in t._ring if r[1] == "i"]
        assert events[0][0] == "rest.ingest"

    def test_ring_eviction_under_overflow(self):
        t = Tracer(component="test", sample_rate=1.0, max_events=10,
                   registry=MetricsRegistry())
        for i in range(25):
            t.event(f"e{i}")
        assert len(t) == 10
        names = [r[0] for r in t._ring]
        assert names == [f"e{i}" for i in range(15, 25)]  # oldest evicted

    def test_sampling_deterministic_with_fixed_seed(self):
        uids = [f"uid-{i}" for i in range(500)]
        a = Tracer(component="a", sample_rate=0.25, seed=7,
                   registry=MetricsRegistry())
        b = Tracer(component="b", sample_rate=0.25, seed=7,
                   registry=MetricsRegistry())
        decisions_a = [a.sampled(u) for u in uids]
        decisions_b = [b.sampled(u) for u in uids]
        assert decisions_a == decisions_b     # no shared state needed
        frac = sum(decisions_a) / len(uids)
        assert 0.15 < frac < 0.35             # roughly the configured rate
        c = Tracer(component="c", sample_rate=0.25, seed=8,
                   registry=MetricsRegistry())
        assert [c.sampled(u) for u in uids] != decisions_a
        # edge rates
        assert Tracer(sample_rate=1.0,
                      registry=MetricsRegistry()).sampled("x")
        assert not Tracer(sample_rate=0.0,
                          registry=MetricsRegistry()).sampled("x")

    def test_disabled_tracer_records_nothing(self):
        t = Tracer(component="test", enabled=False,
                   registry=MetricsRegistry())
        t.event("e")
        t.record("s", time.monotonic() - 0.1)
        with t.span("x"):
            pass
        assert len(t) == 0
        assert not t.sampled("uid")

    def test_phase_stats_from_ring(self):
        t = Tracer(component="test", sample_rate=1.0,
                   registry=MetricsRegistry())
        now = time.monotonic()
        for dur in (0.010, 0.020, 0.030):
            t.record("solve.device", now - dur, now)
        stats = t.phase_stats()
        assert stats["solve.device"]["count"] == 3
        assert abs(stats["solve.device"]["total_s"] - 0.060) < 0.005
        assert abs(stats["solve.device"]["p50_s"] - 0.020) < 0.005

    def test_phase_histogram_exported_via_registry(self):
        reg = MetricsRegistry()
        t = Tracer(component="test", sample_rate=1.0, registry=reg)
        now = time.monotonic()
        t.record("solve.encode", now - 0.05, now)
        text = reg.expose()
        assert "schedtrace_phase_duration_seconds" in text
        assert 'phase="solve.encode"' in text


class TestPerfettoExport:
    def test_schema_validity(self):
        t = Tracer(component="test", sample_rate=1.0,
                   registry=MetricsRegistry())
        with t.span("cycle", trace="pod-3"):
            t.event("mark", trace="pod-3")
        doc = json.loads(json.dumps(t.export_perfetto()))
        events = doc["traceEvents"]
        assert events, "export produced no events"
        for ev in events:
            for field in ("ph", "ts", "pid", "tid"):
                assert field in ev, f"missing {field} in {ev}"
        xs = [e for e in events if e["ph"] == "X"]
        assert xs and all("dur" in e and e["dur"] >= 0 for e in xs)
        metas = [e for e in events if e["ph"] == "M"]
        assert any(e["name"] == "process_name" for e in metas)
        assert doc["otherData"]["component"] == "test"

    def test_retention_window_filters_old_spans(self):
        t = Tracer(component="test", sample_rate=1.0,
                   registry=MetricsRegistry())
        now = time.monotonic()
        t.record("old", now - 100.0, now - 99.0)
        t.record("new", now - 0.1, now)
        names = [e["name"] for e in
                 t.export_perfetto(window_s=60.0)["traceEvents"]]
        assert "new" in names and "old" not in names
        # explicit wide window keeps everything
        names = [e["name"] for e in
                 t.export_perfetto(window_s=1000.0)["traceEvents"]]
        assert "old" in names

    def test_dump_writes_valid_json(self, tmp_path):
        t = Tracer(component="test", sample_rate=1.0,
                   registry=MetricsRegistry(), dump_dir=str(tmp_path))
        t.event("e")
        path = t.dump(reason="unit")
        assert path is not None and path.startswith(str(tmp_path))
        with open(path) as f:
            doc = json.load(f)
        assert doc["otherData"]["reason"] == "unit"
        assert t.last_dump_path == path


class TestTraceCompatShim:
    def test_out_of_order_steps_get_chronological_deltas(self, caplog,
                                                         global_tracer):
        from kubernetes_tpu.utils.trace import Trace

        tr = Trace("Reorder", pod="default/p")
        # helper code stamped its step BEFORE the caller stamped an
        # earlier moment: append order is not chronological
        tr.steps.append((tr.start + 0.050, "late step"))
        tr.steps.append((tr.start + 0.010, "early step"))
        with caplog.at_level("INFO", logger="kubernetes_tpu.trace"):
            tr.log_if_long(0.0)
        text = caplog.text
        assert text.index("early step") < text.index("late step")
        assert "+-" not in text            # no negative deltas
        # the shim folded the trace onto the flight recorder
        assert any(r[0] == "trace.Reorder" for r in global_tracer._ring)

    def test_under_threshold_does_not_log_but_records(self, caplog,
                                                      global_tracer):
        from kubernetes_tpu.utils.trace import Trace

        with caplog.at_level("INFO", logger="kubernetes_tpu.trace"):
            with Trace("Quiet") as tr:
                tr.step("s")
                tr.log_if_long(10.0)
        assert "Quiet" not in caplog.text
        assert any(r[0] == "trace.Quiet" for r in global_tracer._ring)


class TestAdminEndpoints:
    def test_both_admin_routes_exempt_from_lanes_and_faults(
            self, global_tracer):
        from kubernetes_tpu.apiserver.rest import APIServer
        from kubernetes_tpu.apiserver.store import ClusterStore

        # legacy lane path: exhausting the semaphores directly is the
        # cheapest way to prove lane exemption (APF-path exemption has
        # its own saturation test in test_flowcontrol.py)
        server = APIServer(store=ClusterStore(),
                           max_readonly_inflight=1,
                           max_mutating_inflight=1,
                           flow_control=None).start()
        try:
            url = server.url
            # exhaust both lanes: ordinary traffic now answers 429 ...
            assert server.readonly_lane.acquire(blocking=False)
            assert server.mutating_lane.acquire(blocking=False)
            code, _ = _http(f"{url}/api/v1/pods")
            assert code == 429
            # ... while BOTH admin routes bypass the lanes
            code, _ = _http(f"{url}/debug/faults")
            assert code == 200
            code, doc = _http(f"{url}/debug/trace")
            assert code == 200 and "traceEvents" in doc
            code, _ = _http(f"{url}/debug/faults", method="POST",
                            body={"seed": 1, "rules": [
                                {"fault": "error", "verb": "GET",
                                 "resource": "*", "probability": 1.0,
                                 "code": 503}]})
            assert code == 200
            server.readonly_lane.release()
            server.mutating_lane.release()
            # fault armed: ordinary GETs now eat injected 503s ...
            code, _ = _http(f"{url}/api/v1/pods")
            assert code == 503
            # ... while BOTH admin routes stay fault-exempt
            code, _ = _http(f"{url}/debug/faults")
            assert code == 200
            code, _ = _http(f"{url}/debug/trace")
            assert code == 200
            # clear via DELETE still reachable under the armed gate
            code, _ = _http(f"{url}/debug/faults", method="DELETE")
            assert code == 200
        finally:
            server.shutdown_server()

    def test_trace_endpoint_dump_and_clear(self, global_tracer):
        from kubernetes_tpu.apiserver.rest import APIServer
        from kubernetes_tpu.apiserver.store import ClusterStore

        server = APIServer(store=ClusterStore()).start()
        try:
            global_tracer.event("probe-event", trace="u1")
            code, doc = _http(f"{server.url}/debug/trace")
            assert code == 200
            names = [e["name"] for e in doc["traceEvents"]]
            assert "probe-event" in names
            code, _ = _http(f"{server.url}/debug/trace", method="DELETE")
            assert code == 200
            # cleared — only the DELETE request's own span may remain
            # (it closes, and records, after the handler ran)
            assert not any(r[0] == "probe-event"
                           for r in global_tracer._ring)
            code, _ = _http(f"{server.url}/debug/trace?window=bogus")
            assert code == 400
            # PATCH routes through the admin registry: 405, not a 404
            # from resource routing
            code, _ = _http(f"{server.url}/debug/trace", method="PATCH",
                            body={})
            assert code == 405
            code, _ = _http(f"{server.url}/debug/faults", method="PATCH",
                            body={})
            assert code == 405
            # disabled tracer: an explicit 404, never a 200 empty dump
            global_tracer.configure(enabled=False)
            code, _ = _http(f"{server.url}/debug/trace")
            assert code == 404
            global_tracer.configure(enabled=True)
        finally:
            server.shutdown_server()


class TestCausalStitching:
    def test_rest_queue_solve_bind_stitch_over_debug_trace(
            self, global_tracer):
        """The acceptance path: a pod created over REST, scheduled by
        the batch path, must show up in /debug/trace with spans that
        stitch REST ingest → queue wait → solve → bind by pod uid."""
        from kubernetes_tpu.apiserver.rest import APIServer, RestClient
        from kubernetes_tpu.apiserver.store import ClusterStore
        from kubernetes_tpu.config.feature_gates import FeatureGates
        from kubernetes_tpu.scheduler.scheduler import Scheduler
        from kubernetes_tpu.sidecar import attach_batch_scheduler
        from kubernetes_tpu.testing import MakeNode, MakePod

        store = ClusterStore()
        server = APIServer(store=store).start()
        sched = Scheduler.create(
            store, feature_gates=FeatureGates({"TPUBatchScheduler": True}))
        bs = attach_batch_scheduler(sched, max_batch=64)
        sched.start()
        try:
            client = RestClient(server.url)
            client.create(MakeNode().name("n1")
                          .capacity({"cpu": "8", "memory": "16Gi"}).obj())
            pod = MakePod().name("traced").uid("traced-uid") \
                .req({"cpu": "1"}).obj()
            client.create(pod)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                bs.run_batch(pop_timeout=0.05)
                live = store.get_pod("default", "traced")
                if live is not None and live.spec.node_name:
                    break
            else:
                pytest.fail("pod never bound")
            code, doc = _http(f"{server.url}/debug/trace")
            assert code == 200
            events = doc["traceEvents"]
            for ev in events:
                for field in ("ph", "ts", "pid", "tid"):
                    assert field in ev
            mine = [e for e in events
                    if (e.get("args") or {}).get("trace") == "traced-uid"]
            names = {e["name"] for e in mine}
            assert "rest.ingest" in names     # REST ingestion
            assert "queue.wait" in names      # queueing
            assert "sched.bind" in names      # commit/bind e2e
            all_names = {e["name"] for e in events}
            # per-cycle solver phase spans from the same recorder
            assert any(n.startswith("solve.") for n in all_names), all_names
            # and the span-derived histogram reached /metrics
            with urllib.request.urlopen(f"{server.url}/metrics",
                                        timeout=10) as resp:
                metrics_text = resp.read().decode()
            assert "schedtrace_phase_duration_seconds" in metrics_text
        finally:
            sched.stop()
            server.shutdown_server()


@pytest.mark.chaos
class TestDegradedModeDump:
    def test_flight_recorder_dump_on_degraded_entry(self, tmp_path,
                                                    global_tracer):
        from kubernetes_tpu.apiserver.store import ClusterStore
        from kubernetes_tpu.scheduler.scheduler import Scheduler

        global_tracer._dump_dir = str(tmp_path)
        global_tracer.event("pre-outage-span", trace="u1")
        sched = Scheduler.create(ClusterStore())
        try:
            # the circuit breaker's listener path: an injected outage
            sched.set_degraded(True)
            path = global_tracer.last_dump_path
            assert path is not None and path.startswith(str(tmp_path))
            with open(path) as f:
                doc = json.load(f)
            assert doc["otherData"]["reason"] == "degraded"
            names = [e["name"] for e in doc["traceEvents"]]
            assert "pre-outage-span" in names
            sched.set_degraded(False)
        finally:
            sched.stop()


class TestTraceReportTool:
    def test_report_on_synthetic_dump(self, tmp_path):
        import sys
        sys.path.insert(0, "tools")
        try:
            import trace_report
        finally:
            sys.path.pop(0)
        t = Tracer(component="test", sample_rate=1.0,
                   registry=MetricsRegistry(), dump_dir=str(tmp_path))
        now = time.monotonic()
        t.event("rest.ingest", trace="pod-slow")
        t.record("queue.wait", now - 0.5, now - 0.1, trace="pod-slow")
        t.record("sched.bind", now - 0.1, now, trace="pod-slow",
                 node="n1", pod="default/slow")
        t.record("queue.wait", now - 0.05, now - 0.04, trace="pod-fast")
        t.record("solve.device", now - 0.2, now - 0.15)
        path = t.dump(reason="unit")
        out = trace_report.report(path)
        assert "per-phase latency breakdown" in out
        assert "queue.wait" in out and "solve.device" in out
        # slowest pod first, with its span tree and node
        slow_idx = out.index("pod-slow")
        fast_idx = out.index("pod-fast")
        assert slow_idx < fast_idx
        assert "n1" in out
        # malformed dumps fail loudly (the smoke check's purpose)
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"traceEvents": [{"name": "x"}]}))
        with pytest.raises(ValueError):
            trace_report.report(str(bad))

    @pytest.mark.slow
    def test_smoke_on_bench_path_dump(self, tmp_path, global_tracer):
        """The slow-marker bench path: run a small batch workload, dump
        the flight recorder, and push the dump through trace_report —
        a dump-format regression fails here, not in a postmortem."""
        import subprocess
        import sys

        from kubernetes_tpu.harness import make_workload, run_workload

        ops = make_workload("SchedulingBasic", nodes=20, init_pods=0,
                            measure_pods=40)
        result = run_workload("SchedulingBasic/trace-smoke", ops,
                              use_batch=True, wait_timeout=120)
        assert result.pods_per_second > 0
        path = global_tracer.dump(
            path=str(tmp_path / "bench-dump.json"), reason="bench-smoke")
        assert path is not None
        proc = subprocess.run(
            [sys.executable, "tools/trace_report.py", path, "--top", "3"],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        assert "per-phase latency breakdown" in proc.stdout
        assert "solve." in proc.stdout
        assert "slowest pods" in proc.stdout
