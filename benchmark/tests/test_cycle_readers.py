"""The readers of the scheduling cycle's stage spans and discard counter,
on a synthetic run: what they read from a program that emits them, and
that they read nothing from one that does not."""

import pytest

from benchmark.lib.harness import RunData, Window
from benchmark.lib.metrics import load_reader
from benchmark.tests import tiny


def _span(name, dur, **args):
    return {"name": name, "start": 0.0, "dur": dur, "args": args}


def _run(spans):
    return RunData(window=Window(0.0, 10.0, 0.0, 10.0), spans=spans)


# two cycles of a backlog pipeline: one discards a 4,096-pod batch and
# rebuilds, the next commits the re-solved batch on the resident state
CYCLES = [
    _span("queue.cycle", 1.3, pods=4096, committed=0, discarded=4096,
          rebuild="full"),
    _span("sidecar.discard", 0.01, pods=4096),
    _span("solve.rebuild", 0.9, kind="full", reason="pod_spread_terms"),
    _span("solve.snapshot", 0.25),
    _span("queue.cycle", 0.14, pods=4096, committed=4096, discarded=0,
          rebuild="none"),
]


@pytest.mark.parametrize("name,want", [
    ("rebuild_ms_per_cycle.steady", 0.9 / 2 * 1e3),
    ("snapshot_ms_per_cycle.backlog", 0.25 / 2 * 1e3),
    ("discarded_pods_per_cycle.backlog", 4096 / 2),
])
def test_reads_the_cycle_spans(name, want):
    assert load_reader(tiny.REPO, name).read(_run(CYCLES)) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", ["rebuild_ms_per_cycle.steady",
                                  "snapshot_ms_per_cycle.backlog",
                                  "discarded_pods_per_cycle.backlog"])
def test_program_without_the_spans_reads_nothing(name):
    """Cycles as a program without the stage spans records them: no
    ``rebuild`` or ``discarded`` argument, no rebuild spans."""
    old = [_span("queue.cycle", 1.3, pods=4096, committed=0),
           _span("solve.encode", 0.6)]
    reader = load_reader(tiny.REPO, name)
    assert reader.read(_run(old)) is None
    assert reader.read(_run([])) is None


def test_cycles_without_rebuilds_read_zero():
    calm = [_span("queue.cycle", 0.1, pods=8, committed=8, discarded=0,
                  rebuild="none")] * 3
    for name in ("rebuild_ms_per_cycle.steady",
                 "snapshot_ms_per_cycle.backlog",
                 "discarded_pods_per_cycle.backlog"):
        assert load_reader(tiny.REPO, name).read(_run(calm)) == 0
