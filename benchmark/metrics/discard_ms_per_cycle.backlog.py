"""Host time of throwing solved batches away per scheduler cycle, from sidecar.discard spans: the re-fetch of the batch's pods and the restore of the device carry (backlog cells)."""

LAYER = "sidecar"
UNIT = "ms/cycle"
SOURCE = "program_span"
MOVES = "pods_per_s"


def read(run):
    cycles = [s for s in run.spans if s["name"] == "queue.cycle"]
    if not cycles:
        return None
    total = sum(s["dur"] for s in run.spans if s["name"] == "sidecar.discard")
    return total / len(cycles) * 1e3
