"""Pods of solved batches thrown away per scheduler cycle, from the discarded argument of queue.cycle spans (backlog cells).

A program whose ``queue.cycle`` spans carry no ``discarded`` argument
does not count discards: the metric is then left out."""

LAYER = "sidecar"
UNIT = "pods/cycle"
SOURCE = "program_counter"
MOVES = "pods_per_s"


def read(run):
    cycles = [s for s in run.spans if s["name"] == "queue.cycle"]
    if not any("discarded" in s["args"] for s in cycles):
        return None
    return sum(s["args"].get("discarded", 0) for s in cycles) / len(cycles)
