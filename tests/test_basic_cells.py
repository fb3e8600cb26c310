"""The ``basic-5k`` deployment's backlog and open-loop cells at a CPU
test's size: the benchmark's own harness builds the deployment from the
repository's files (``benchmark.tests.tiny``), drives the default path
(the sidecar's one loop, mirror on, the Pallas kernel in interpret
mode) and
replays every bind through the plain reference. Each run has to read
``correct`` true with every bind on a best-scoring node.
"""

import gc

import pytest


@pytest.fixture
def tree(tmp_path, monkeypatch):
    from benchmark.tests import tiny

    monkeypatch.setenv("KTPU_SOLVER", "pallas")
    monkeypatch.delenv("KTPU_MIRROR", raising=False)
    # the harness tunes the collector for its window; put it back for
    # the tests that share this process
    threshold = gc.get_threshold()
    yield tiny, tiny.build_tree(tmp_path / "tree")
    gc.unfreeze()
    gc.set_threshold(*threshold)


@pytest.mark.parametrize("cell", ["basic-5k.backlog", "basic-5k.steady"])
def test_basic_cell_correct(tree, cell):
    tiny, root = tree
    out = tiny.run(root, cell, seed=2**31 + 11, seconds=1.5)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["score_gap_max"]["value"] == 0.0
    assert out["checks"]["infeasible_binds"]["value"] == 0
    assert out["device"]["platform"] == "cpu"


def test_basic_backlog_restores_every_discard(tree):
    """The traced backlog run: the client's churn deletes between calls
    discard nothing; each cycle's solve catches them up (no cycle in
    the window rebuilds) and commits every pod it drained."""
    from kubernetes_tpu.observability import get_tracer

    tiny, root = tree
    keep = {}
    t = get_tracer()

    def spans(sched, bs):
        # the window opens next: keep its spans only
        keep["bs"] = bs
        keep["catch_ups"] = bs.mirror_info()["catch_ups"]
        t.clear()

    saved = (t.enabled, t.sample_rate)
    t.clear()
    t.configure(enabled=True, sample_rate=1.0)
    try:
        out = tiny.run(root, "basic-5k.backlog", seed=2**31 + 12,
                       seconds=1.5, trace=True, after_setup=spans)
        recs = [r for r in t._ring if r[1] == "X"]
    finally:
        t.enabled, t.sample_rate = saved
        t.clear()
    assert out["correct"] is True, out["checks"]
    assert "scatter_ms_per_cycle.backlog" in out["metrics"]
    assert out["metrics"]["discard_ms_per_cycle.backlog"]["value"] == 0.0
    assert "discarded_pods_per_cycle.backlog" not in out["metrics"]
    assert not [r for r in recs if r[0] == "sidecar.discard"]
    cycles = [r[8] for r in recs if r[0] == "queue.cycle"]
    assert cycles
    for c in cycles:
        assert c["committed"] == c["pods"], c
    assert {c["rebuild"] for c in cycles} <= {"none", "scatter"}
    assert "scatter" in {c["rebuild"] for c in cycles}
    info = keep["bs"].mirror_info()
    assert info["catch_ups"] > keep["catch_ups"]
    assert "carry_restores" not in info
