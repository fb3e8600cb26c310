"""Host time of the mirror catch-up per scheduler cycle, from solve.scatter spans (backlog cells).

A run with no ``solve.scatter`` span in its window scattered nothing
(or its program has no such span): the metric is then left out."""

LAYER = "encode"
UNIT = "ms/cycle"
SOURCE = "program_span"
MOVES = "pods_per_s"


def read(run):
    cycles = [s for s in run.spans if s["name"] == "queue.cycle"]
    scatters = [s for s in run.spans if s["name"] == "solve.scatter"]
    if not cycles or not scatters:
        return None
    return sum(s["dur"] for s in scatters) / len(cycles) * 1e3
