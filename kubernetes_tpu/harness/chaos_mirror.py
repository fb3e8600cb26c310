"""Seeded chaos cells for the device-resident cluster mirror
(``tools/chaos_matrix.py --suite mirror``).

Every cell runs the SAME seeded event sequence twice — mirror on
(scatter path) and ``KTPU_MIRROR=off`` (the PR 12 delta-encode
reference) — and passes only when the two arms land a BIT-IDENTICAL
placement set with zero lost pods. The scenarios aim the faults at the
mirror's seams:

- ``node_kill`` — a node dies right after a cycle that scattered
  churned deltas and committed part of a wave, with the rest of the
  wave still queued, so the node-set epoch bump crosses the resident
  planes mid-sequence.
- ``mesh_resize`` — the sharded backend is torn down and re-attached
  at a different mesh width with pods still queued: the new session must
  cold-seed the mirror from store truth and keep the differential.
- ``event_storm`` — a mutation storm overflows the delta journal ring
  between two solves: the window reads as a gap, which MUST surface as
  a reseed (full host encode + mirror re-seed), never as silently
  missing deltas. The cell fails if the storm did not force a reseed —
  a quiet cell proves nothing.
"""

from __future__ import annotations

import copy
import gc
import os
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

MIRROR_SCENARIOS = ("node_kill", "mesh_resize", "event_storm")

# ring capacity the event-storm cell shrinks the LIVE journal to: small
# enough that the storm below overflows it between two solves, large
# enough that the quiet phases of the cell never gap
STORM_RING_CAP = 96
STORM_UPDATES = 3 * STORM_RING_CAP


def _pump(sched, bs, timeout: float = 60.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        sched.queue.flush_backoff_completed()
        if bs.run_batch(pop_timeout=0.0):
            continue
        if sched.queue.pending_active_count() == 0:
            break
        time.sleep(0.01)
    sched.wait_for_inflight_bindings()


def _bound_set(store) -> List[Tuple[str, Optional[str]]]:
    return sorted((p.metadata.name, p.spec.node_name)
                  for p in store.list_pods())


def _set_node_cpu(store, name: str, cpu: str) -> None:
    from kubernetes_tpu.api.resource import Quantity

    node = copy.deepcopy(store.get_node(name))
    node.status.allocatable["cpu"] = Quantity(cpu)
    node.status.capacity["cpu"] = Quantity(cpu)
    store.update_node(node)


def _make_sched(store, *, max_batch=64, backend=None):
    from kubernetes_tpu.config.feature_gates import FeatureGates
    from kubernetes_tpu.scheduler.scheduler import Scheduler
    from kubernetes_tpu.sidecar import attach_batch_scheduler

    sched = Scheduler.create(
        store, feature_gates=FeatureGates({"TPUBatchScheduler": True}),
        provider="GangSchedulingProvider")
    bs = attach_batch_scheduler(sched, max_batch=max_batch,
                                adaptive_chunk=False, backend=backend)
    sched.start()
    return sched, bs


def _drive(scenario: str, seed: int, mirror_on: bool, *,
           nodes: int, pods: int, wait_timeout: float,
           progress: Optional[Callable[[str], None]] = None) -> Dict:
    """One arm of a cell: drive the seeded sequence and return the
    final placement set plus the mirror counters (None on the off
    arm)."""
    from kubernetes_tpu.apiserver.store import ClusterStore
    from kubernetes_tpu.testing import MakeNode, MakePod

    prev = os.environ.get("KTPU_MIRROR")
    os.environ["KTPU_MIRROR"] = "on" if mirror_on else "off"
    scheds = []
    try:
        rng = np.random.default_rng(seed)
        store = ClusterStore()
        for i in range(nodes):
            store.add_node(MakeNode().name(f"n{i}")
                           .capacity({"cpu": "8",
                                      "memory": "16Gi"}).obj())
        w0 = max(8, int(pods * 0.4))
        w1 = max(8, int(pods * 0.35))
        w2 = max(4, pods - w0 - w1)
        created = 0
        deleted = 0

        def make_wave(w: int, count: int):
            nonlocal created
            created += count
            return [
                MakePod().name(f"w{w}-p{i}").uid(f"u{w}-{i}")
                .req({"cpu": f"{int(rng.integers(1, 5)) * 100}m"})
                .obj()
                for i in range(count)
            ]

        def churn() -> None:
            # scatterable deltas: allocatable-only node updates plus
            # bound-pod deletes — the fault must cross a mirror that
            # has actually scattered, not a freshly-seeded one
            nonlocal deleted
            picks = rng.choice(nodes, size=2, replace=False)
            _set_node_cpu(store, f"n{picks[0]}", "6")
            _set_node_cpu(store, f"n{picks[1]}", "10")
            bound = [p for p in store.list_pods() if p.spec.node_name]
            if len(bound) >= 4:
                for p in rng.choice(bound, size=4, replace=False):
                    store.delete_pod(p.metadata.namespace,
                                     p.metadata.name)
                    deleted += 1

        backend = None
        widths = (None, None)
        if scenario == "mesh_resize":
            import jax

            from kubernetes_tpu.parallel import ShardedBackend, make_mesh

            avail = len(jax.devices())
            widths = (2, 4) if avail >= 4 else (1, max(1, avail))
            backend = ShardedBackend(make_mesh(widths[0], batch_axis=1))
        sched, bs = _make_sched(store, backend=backend)
        scheds.append(sched)

        store.create_pods(make_wave(0, w0))
        _pump(sched, bs, timeout=wait_timeout)

        if scenario == "node_kill":
            # churned deltas scatter on the next cycle; the node dies
            # right after it, with the rest of the wave still queued
            churn()
            store.create_pods(make_wave(1, w1))
            bs.run_batch(pop_timeout=0.1)
            store.delete_node(f"n{int(rng.integers(0, nodes))}")
            _pump(sched, bs, timeout=wait_timeout)
        elif scenario == "event_storm":
            churn()
            store.create_pods(make_wave(1, w1))
            _pump(sched, bs, timeout=wait_timeout)
            journal = getattr(bs.session, "_journal", None)
            if journal is not None:
                with journal._lock:
                    journal._recs = deque(journal._recs,
                                          maxlen=STORM_RING_CAP)
            # the storm: allocatable churn far past the ring capacity
            # between two solves — the next catch-up window MUST read
            # as a gap, never as "nothing happened"
            for _ in range(STORM_UPDATES):
                pick = int(rng.integers(0, nodes))
                cpu = str(int(rng.choice([6, 8, 10, 12])))
                _set_node_cpu(store, f"n{pick}", cpu)
        elif scenario == "mesh_resize":
            from kubernetes_tpu.parallel import ShardedBackend, make_mesh

            # pods queued across the resize: one cycle commits, then
            # the backend is torn down and re-attached one width up; more
            # churn lands on the re-seeded mirror afterwards
            churn()
            store.create_pods(make_wave(1, w1))
            bs.run_batch(pop_timeout=0.1)
            sched.stop()
            backend = ShardedBackend(make_mesh(widths[1], batch_axis=1))
            sched, bs = _make_sched(store, backend=backend)
            scheds.append(sched)
            _pump(sched, bs, timeout=wait_timeout)
            # a small wave guarantees a post-resize solve (the mirror
            # seeds on its first solve), so the churn below scatters
            # instead of folding into the cold seed
            store.create_pods(make_wave(3, 8))
            _pump(sched, bs, timeout=wait_timeout)
            churn()
        else:
            raise ValueError(f"unknown mirror scenario {scenario!r}")

        store.create_pods(make_wave(2, w2))
        _pump(sched, bs, timeout=wait_timeout)

        info = None
        if getattr(bs.session, "_mirror", None) is not None:
            info = bs.session._mirror.info()
        if progress:
            arm = "on" if mirror_on else "off"
            progress(f"[mirror/{scenario}] arm={arm} created={created} "
                     f"mirror={info}")
        return {"bound": _bound_set(store), "mirror": info,
                "created": created, "deleted": deleted}
    finally:
        for s in scheds:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 — teardown must not mask
                pass
        if prev is None:
            os.environ.pop("KTPU_MIRROR", None)
        else:
            os.environ["KTPU_MIRROR"] = prev
        gc.collect()


def run_chaos_mirror(
    seed: int,
    *,
    scenario: str,
    nodes: int = 20,
    pods: int = 120,
    wait_timeout: float = 120.0,
    progress: Optional[Callable[[str], None]] = None,
) -> Dict:
    """One (scenario × seed) cell: both arms, differential verdict."""
    if scenario not in MIRROR_SCENARIOS:
        raise ValueError(f"unknown mirror scenario {scenario!r} "
                         f"(have: {', '.join(MIRROR_SCENARIOS)})")
    on = _drive(scenario, seed, True, nodes=nodes, pods=pods,
                wait_timeout=wait_timeout, progress=progress)
    off = _drive(scenario, seed, False, nodes=nodes, pods=pods,
                 wait_timeout=wait_timeout, progress=progress)
    match = on["bound"] == off["bound"]
    lost = ((on["created"] - on["deleted"] - len(on["bound"]))
            + (off["created"] - off["deleted"] - len(off["bound"])))
    info = on["mirror"] or {}
    problems = []
    if on["mirror"] is None:
        problems.append("mirror-on arm built no mirror")
    if not match:
        problems.append("differential mismatch: mirror-on placements "
                        "diverged from the delta-encode reference")
    if lost:
        problems.append(f"lost_pods={lost}")
    if on["mirror"] is not None and not info.get("events"):
        problems.append("no deltas were ever scattered (the fault "
                        "crossed a mirror the cell never exercised)")
    if scenario == "event_storm" and not info.get("reseeds"):
        problems.append("storm never forced a reseed (the journal-gap "
                        "path went untested — a quiet cell proves "
                        "nothing)")
    return {
        "seed": seed,
        "profile": scenario,
        "ok": not problems,
        "failure": "; ".join(problems),
        "differential_match": match,
        "lost_pods": lost,
        "stats": {
            "faults_injected": (STORM_UPDATES
                                if scenario == "event_storm" else 1),
            "events": info.get("events"),
            "catch_ups": info.get("catch_ups"),
            "reseeds": info.get("reseeds"),
        },
    }
