"""Host time of the snapshot refresh per scheduler cycle, from solve.snapshot spans (backlog cells).

A program whose ``queue.cycle`` spans carry no ``rebuild`` argument has
no ``solve.snapshot`` spans either: the metric is then left out."""

LAYER = "encode"
UNIT = "ms/cycle"
SOURCE = "program_span"
MOVES = "pods_per_s"


def read(run):
    cycles = [s for s in run.spans if s["name"] == "queue.cycle"]
    if not any("rebuild" in s["args"] for s in cycles):
        return None
    total = sum(s["dur"] for s in run.spans if s["name"] == "solve.snapshot")
    return total / len(cycles) * 1e3
