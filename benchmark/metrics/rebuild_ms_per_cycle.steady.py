"""Host time of the full rebuilds per scheduler cycle, from solve.rebuild spans (open-loop cells).

A program whose ``queue.cycle`` spans carry no ``rebuild`` argument has
no ``solve.rebuild`` spans either: the metric is then left out."""

LAYER = "encode"
UNIT = "ms/cycle"
SOURCE = "program_span"
MOVES = "bind_p99_ms"


def read(run):
    cycles = [s for s in run.spans if s["name"] == "queue.cycle"]
    if not any("rebuild" in s["args"] for s in cycles):
        return None
    total = sum(s["dur"] for s in run.spans if s["name"] == "solve.rebuild")
    return total / len(cycles) * 1e3
