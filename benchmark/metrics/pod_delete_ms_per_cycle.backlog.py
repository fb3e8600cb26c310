"""Host time of taking deleted pods out of the scheduler cache per scheduler cycle, from cache.remove_pods spans (backlog cells).

The spans run inside the store's synchronous watch, under the client's
bulk delete. A run with no ``cache.remove_pods`` span in its window (or
a program without the span) leaves the metric out."""

LAYER = "apiserver store"
UNIT = "ms/cycle"
SOURCE = "program_span"
MOVES = "pods_per_s"


def read(run):
    cycles = [s for s in run.spans if s["name"] == "queue.cycle"]
    removals = [s for s in run.spans if s["name"] == "cache.remove_pods"]
    if not cycles or not removals:
        return None
    return sum(s["dur"] for s in removals) / len(cycles) * 1e3
