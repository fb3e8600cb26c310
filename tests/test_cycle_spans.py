"""The scheduling cycle's stages as tracer spans, on the profiler's clock.

A 48-node cluster with zone and hostname spread pods, driven one
``run_batch`` pass at a time: the span tree of a pass, the scatter a
churn delete between passes leaves in place of a rebuild, the disabled
tracer's shared no-op, and the spans' ``TraceAnnotation`` twins in a
CPU profiler trace, read back by the benchmark's own trace reduction.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from kubernetes_tpu.apiserver.store import ClusterStore
from kubernetes_tpu.config.feature_gates import FeatureGates
from kubernetes_tpu.observability import get_tracer
from kubernetes_tpu.observability.tracer import NOOP_SPAN, Tracer
from kubernetes_tpu.scheduler.scheduler import Scheduler
from kubernetes_tpu.sidecar import attach_batch_scheduler
from kubernetes_tpu.testing import MakeNode, MakePod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NODES = 48
ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"


@pytest.fixture
def tracer():
    """The process-wide tracer, enabled and emptied around the test."""
    t = get_tracer()
    saved = (t.enabled, t.sample_rate)
    t.clear()
    t.configure(enabled=True, sample_rate=1.0)
    yield t
    t.enabled, t.sample_rate = saved
    t.clear()


def _pod(name: str, node: str = ""):
    w = (MakePod().name(name).uid(f"uid-{name}").label("foo", "bar")
         .req({"cpu": "100m", "memory": "500Mi"})
         .spread_constraint(5, HOST, "DoNotSchedule", {"foo": "bar"})
         .spread_constraint(5, ZONE, "DoNotSchedule", {"foo": "bar"}))
    if node:
        w = w.node(node)
    return w.obj()


@pytest.fixture
def cluster(monkeypatch):
    """48 zoned nodes with two running spread pods each, and a batch
    scheduler warmed on the spread pod's shapes."""
    monkeypatch.setenv("KTPU_SOLVER", "xla")
    store = ClusterStore()
    for i in range(NODES):
        store.add_node(MakeNode().name(f"n{i}").label(ZONE, f"z{i % 3}")
                       .capacity({"cpu": "4", "memory": "32Gi"}).obj())
    store.create_pods([_pod(f"run-{j}", f"n{j % NODES}")
                       for j in range(2 * NODES)])
    sched = Scheduler.create(
        store, feature_gates=FeatureGates({"TPUBatchScheduler": True}))
    bs = attach_batch_scheduler(sched, max_batch=16, adaptive_chunk=False)
    sched.start()
    bs.warmup(sample_pods=[_pod("warm")])
    try:
        yield store, bs
    finally:
        sched.stop()


def _records(tracer, name=None):
    return [r for r in tracer._ring
            if r[1] == "X" and (name is None or r[0] == name)]


def _ancestors(rec, by_id):
    out = []
    while rec[6] in by_id:
        rec = by_id[rec[6]]
        out.append(rec[0])
    return out


def test_pass_span_tree(cluster, tracer):
    store, bs = cluster
    store.create_pods([_pod(f"p-{k}") for k in range(8)])
    assert bs.run_batch(pop_timeout=0.0) == 8
    recs = _records(tracer)
    by_id = {r[5]: r for r in recs}
    [cycle] = _records(tracer, "queue.cycle")
    assert cycle[8]["pods"] == 8 and cycle[8]["committed"] == 8
    # the warm-up left the session invalidated: a full rebuild
    assert cycle[8]["rebuild"] in ("full", "state_only")
    names = {r[0] for r in recs}
    for name in ("sidecar.drain", "solve.rebuild", "solve.snapshot",
                 "solve.encode", "solve.pack", "solve.prepare",
                 "solve.device", "solve.block", "solve.commit",
                 "bind.bulk"):
        assert name in names, name
        for rec in _records(tracer, name):
            assert "queue.cycle" in _ancestors(rec, by_id), name
    for name in ("solve.snapshot", "solve.encode", "solve.prepare"):
        for rec in _records(tracer, name):
            assert _ancestors(rec, by_id)[0] == "solve.rebuild", name
    [rebuild] = _records(tracer, "solve.rebuild")
    assert rebuild[8]["reason"] == "poisoned"
    assert rebuild[8]["kind"] in ("full", "state_only")
    # the device stages and the commit sit beside the rebuild, the
    # bind inside the commit
    for name in ("solve.device", "solve.block", "solve.commit"):
        for rec in _records(tracer, name):
            assert "solve.rebuild" not in _ancestors(rec, by_id), name
    for rec in _records(tracer, "bind.bulk"):
        assert _ancestors(rec, by_id)[0] == "solve.commit"
    [commit] = _records(tracer, "solve.commit")
    assert commit[8]["committed"] == 8 and commit[8]["pods"] == 8


def test_encode_span_counts_signatures(cluster, tracer):
    """A spread rebuild's encode reads the nodes' label-signature
    counts: all running pods, one signature. The incremental path's
    pod-row encode reads none and carries neither arg."""
    store, bs = cluster
    store.create_pods([_pod(f"p-{k}") for k in range(8)])
    assert bs.run_batch(pop_timeout=0.0) == 8
    store.create_pods([_pod(f"q-{k}") for k in range(8)])
    assert bs.run_batch(pop_timeout=0.0) == 8
    by_id = {r[5]: r for r in _records(tracer)}
    encodes = _records(tracer, "solve.encode")
    rebuilt = [r for r in encodes
               if _ancestors(r, by_id)[0] == "solve.rebuild"]
    incremental = [r for r in encodes if r not in rebuilt]
    assert rebuilt and incremental
    first = rebuilt[0][8]
    # the running pods, and nothing the batch brought
    assert first["count_pods"] == 2 * NODES
    assert first["count_sigs"] == 1
    for rec in incremental:
        assert "count_pods" not in rec[8] and "count_sigs" not in rec[8]


def test_idle_pass_leaves_no_record(cluster, tracer):
    _store, bs = cluster
    assert bs.run_batch(pop_timeout=0.0) == 0
    assert _records(tracer) == []


def test_discard_after_churn_delete(cluster, tracer):
    """A spread pod deleted between two passes, with a backlog queued:
    nothing is discarded, and the next pass scatters the delete into
    the resident spread counts, with no rebuild and no reseed, solves
    its own drain and commits all of it."""
    store, bs = cluster
    mirror = bs.session._mirror
    assert mirror is not None
    store.create_pods([_pod(f"p-{k}") for k in range(24)])
    # 16 pods solved and committed, 8 more queued
    assert bs.run_batch(pop_timeout=0.0) == 16
    before = dict(mirror.info()["reseed_reasons"])
    rebuilds = len(_records(tracer, "solve.rebuild"))
    store.delete_pod("default", "run-0")
    assert bs.run_batch(pop_timeout=0.0) == 8
    cycles = _records(tracer, "queue.cycle")
    assert [(c[8]["pods"], c[8]["committed"]) for c in cycles] == \
        [(16, 16), (8, 8)]
    for c in cycles:
        assert "discarded" not in c[8]
    assert _records(tracer, "sidecar.discard") == []
    assert cycles[-1][8]["rebuild"] == "scatter"
    assert len(_records(tracer, "solve.rebuild")) == rebuilds
    assert mirror.info()["reseed_reasons"] == before
    assert mirror.last_reason is None
    # the catch-up is on the tracer, the delete counted as a spread pod
    scatter = _records(tracer, "solve.scatter")[-1]
    assert scatter[8]["events"] > 0 and scatter[8]["spread"] > 0
    assert bs.run_batch(pop_timeout=0.0) == 0
    assert all(p.spec.node_name for p in store.list_pods())


def test_disabled_tracer_hands_out_the_shared_noop(cluster, tracer):
    store, bs = cluster
    tracer.configure(enabled=False)
    span = tracer.span("queue.cycle", pods=1)
    assert span is NOOP_SPAN
    with span as sp:
        sp.set(committed=1)
        sp.drop()
        assert sp.span_id == 0
        assert tracer.current_span_id() == 0
    store.create_pods([_pod(f"p-{k}") for k in range(4)])
    assert bs.run_batch(pop_timeout=0.0) == 4
    assert len(tracer) == 0
    assert Tracer(enabled=False).span("x") is NOOP_SPAN


def test_spans_reach_the_profiler_trace(cluster, tracer, tmp_path):
    """Under a CPU profiler trace the spans are TraceAnnotations on the
    scheduling thread, nested under the harness's ``bench.*``
    annotation; the benchmark's reduction names a gap by them."""
    import jax

    from benchmark.lib import xplane

    store, bs = cluster
    store.create_pods([_pod(f"p-{k}") for k in range(8)])
    # the benchmark's options: host events, no Python call tracing
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.run_batch"):
            assert bs.run_batch(pop_timeout=0.0) == 8
    finally:
        jax.profiler.stop_trace()
    path = xplane.find_xplane(tmp_path)
    assert path is not None
    device_ops, host = xplane.load_events(path)
    assert device_ops == {}   # a CPU trace has no TPU plane
    depth = {}
    for name, s, e, d in host:
        depth.setdefault(name, (d, s, e))
    assert depth["bench.run_batch"][0] == 0
    assert depth["queue.cycle"][0] == 1
    assert depth["solve.rebuild"][0] == 2
    assert depth["solve.snapshot"][0] == 3
    assert depth["solve.commit"][0] == 2
    # a device gap spanning the snapshot refresh is named by it
    _d, s, e = depth["solve.snapshot"]
    ops = {"/device:TPU:0": [("%a = s32[8] fusion()", s - 1e-3, s),
                             ("%b = s32[8] fusion()", e, e + 1e-3)]}
    out = xplane.reduce_events(ops, host, window_s=1.0)
    assert out["idle_gaps"][0][0] == "solve.snapshot"


def test_span_in_a_process_without_jax_imports_none():
    code = ("import sys\n"
            "from kubernetes_tpu.observability.tracer import Tracer\n"
            "t = Tracer(enabled=True)\n"
            "with t.span('queue.cycle') as sp:\n"
            "    sp.set(pods=1)\n"
            "print(len(t), 'jax' in sys.modules)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env={**os.environ, "PYTHONPATH": REPO},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == ["1", "False"]
