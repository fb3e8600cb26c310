"""The reader of the scheduler cache's pod-removal spans, on a synthetic
run: what it reads from a program that emits them, and that it reads
nothing from one that does not."""

import pytest

from benchmark.lib.harness import RunData, Window
from benchmark.lib.metrics import load_reader
from benchmark.tests import tiny

NAME = "pod_delete_ms_per_cycle.backlog"


def _span(name, dur, **args):
    return {"name": name, "start": 0.0, "dur": dur, "args": args}


def _run(spans):
    return RunData(window=Window(0.0, 10.0, 0.0, 10.0), spans=spans)


def test_reads_the_removal_spans_per_cycle():
    # a pair of backlog cycles, each after the client's bulk delete
    spans = [_span("cache.remove_pods", 0.021, pods=4096),
             _span("queue.cycle", 0.4, pods=4096, committed=0),
             _span("cache.remove_pods", 0.019, pods=4096),
             _span("queue.cycle", 0.3, pods=4096, committed=4096)]
    assert load_reader(tiny.REPO, NAME).read(_run(spans)) == \
        pytest.approx((0.021 + 0.019) / 2 * 1e3)


@pytest.mark.parametrize("spans", [
    [],
    [_span("queue.cycle", 0.4, pods=4096, committed=4096)],
    [_span("cache.remove_pods", 0.02, pods=4096)],
], ids=["empty", "no_removal_span", "no_cycle"])
def test_program_without_the_spans_reads_nothing(spans):
    assert load_reader(tiny.REPO, NAME).read(_run(spans)) is None


def test_declares_what_the_benchmark_states():
    r = load_reader(tiny.REPO, NAME)
    assert (r.LAYER, r.UNIT, r.SOURCE, r.MOVES) == (
        "apiserver store", "ms/cycle", "program_span", "pods_per_s")
