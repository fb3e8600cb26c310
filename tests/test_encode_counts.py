"""Differential test of the encoder's cluster-side counts.

``BatchEncoder`` reads the existing pods each tracked spread constraint
and (anti-)affinity term counts per topology domain from the nodes'
label-signature counts. The oracle below is the straightforward per-pod
count: every node, every pod, one selector match each. Seeded random
clusters with several namespaces and label sets, terminating pods, and
pods removed and re-added through the cache must give element-for-element
equal ``sc_counts`` and ``term_counts``.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from kubernetes_tpu.api.labels import (
    DOES_NOT_EXIST,
    EXISTS,
    IN,
    NOT_IN,
    LabelSelector,
    Requirement,
)
from kubernetes_tpu.api.types import (
    Affinity,
    PodAffinity,
    PodAffinityTerm,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
)
from kubernetes_tpu.ops.encode import BatchEncoder
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.snapshot import Snapshot
from kubernetes_tpu.testing import MakeNode, MakePod

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
RACK = "example.com/rack"
NAMESPACES = ("default", "team-a", "team-b")
LABEL_SETS = (
    {"app": "web"},
    {"app": "web", "tier": "front"},
    {"app": "db", "tier": "back"},
    {"app": "cache"},
    {"tier": "front"},
    {},
)


def oracle_counts(enc: BatchEncoder, cluster, batch):
    """The per-pod count: each node's pods, one match per pod. A spread
    constraint skips terminating pods; a term counts them."""
    v = batch.num_values
    codes = cluster.topo_codes
    sc_counts = np.zeros_like(batch.sc_counts)
    term_counts = np.zeros_like(batch.term_counts)
    for ci, con in enumerate(enc._constraints):
        for i, ni in enumerate(enc.node_infos):
            code = codes[i, con.key_idx]
            if code >= v:
                continue
            sc_counts[ci, code] += sum(
                1 for pi in ni.pods
                if pi.pod.metadata.deletion_timestamp is None
                and con.matches(pi.pod))
    for ti, term in enumerate(enc._terms):
        for i, ni in enumerate(enc.node_infos):
            code = codes[i, term.key_idx]
            if code >= v:
                continue
            term_counts[ti, code] += sum(
                1 for pi in ni.pods if term.matches(pi.pod))
    return sc_counts, term_counts


def _selector(rng: random.Random) -> LabelSelector:
    shape = rng.randrange(4)
    if shape == 0:
        return LabelSelector(match_labels=dict(rng.choice(LABEL_SETS[:5])))
    if shape == 1:
        return LabelSelector(match_expressions=[
            Requirement("app", IN, tuple(rng.sample(["web", "db", "cache"],
                                                    2)))])
    if shape == 2:
        return LabelSelector(
            match_labels={"tier": rng.choice(["front", "back"])},
            match_expressions=[Requirement("app", NOT_IN, ("cache",))])
    return LabelSelector(match_expressions=[
        Requirement("tier", rng.choice([EXISTS, DOES_NOT_EXIST]))])


def _running_pod(rng: random.Random, uid: str, node: str,
                 terminating: bool = False):
    w = (MakePod().name(uid).uid(uid).namespace(rng.choice(NAMESPACES))
         .labels(rng.choice(LABEL_SETS)).req({"cpu": "10m"}).node(node))
    if rng.random() < 0.15:
        w = w.pod_anti_affinity("app", ["db"], ZONE)
    if terminating:
        w = w.terminating()
    return w.obj()


def _batch_pod(rng: random.Random, k: int):
    pod = (MakePod().name(f"b{k}").uid(f"b{k}")
           .namespace(rng.choice(NAMESPACES))
           .labels(rng.choice(LABEL_SETS)).req({"cpu": "10m"}).obj())
    for key in rng.sample([ZONE, HOST, RACK], rng.randrange(1, 3)):
        pod.spec.topology_spread_constraints.append(TopologySpreadConstraint(
            max_skew=rng.randrange(1, 4), topology_key=key,
            when_unsatisfiable=rng.choice(["DoNotSchedule",
                                           "ScheduleAnyway"]),
            label_selector=_selector(rng)))
    if rng.random() < 0.5:
        term = PodAffinityTerm(
            label_selector=_selector(rng),
            namespaces=rng.sample(NAMESPACES, rng.randrange(0, 3)),
            topology_key=rng.choice([ZONE, HOST]))
        aff = PodAffinity()
        if rng.random() < 0.5:
            aff.preferred_during_scheduling_ignored_during_execution.append(
                WeightedPodAffinityTerm(rng.randrange(1, 100), term))
            pod.spec.affinity = Affinity(pod_affinity=aff)
        else:
            aff.required_during_scheduling_ignored_during_execution.append(
                term)
            pod.spec.affinity = Affinity(pod_anti_affinity=aff)
    return pod


def _churn(rng: random.Random, cache: SchedulerCache, live: dict,
           removed: list, nodes: list, next_uid: list) -> None:
    """Adds, removes, re-adds (the same pod object) and updates to
    terminating, through the cache as the informer would."""
    for _ in range(rng.randrange(10, 40)):
        op = rng.random()
        if op < 0.35 or not live:
            uid = f"r{next_uid[0]}"
            next_uid[0] += 1
            pod = _running_pod(rng, uid, rng.choice(nodes),
                               terminating=rng.random() < 0.1)
            cache.add_pod(pod)
            live[uid] = pod
        elif op < 0.6:
            uid = rng.choice(sorted(live))
            cache.remove_pod(live.pop(uid))
            removed.append(uid)
        elif op < 0.75 and removed:
            uid = removed.pop(rng.randrange(len(removed)))
            pod = _running_pod(rng, uid, rng.choice(nodes))
            cache.add_pod(pod)
            live[uid] = pod
        else:
            uid = rng.choice(sorted(live))
            old = live[uid]
            if old.metadata.deletion_timestamp is not None:
                continue
            new = (MakePod().name(uid).uid(uid)
                   .namespace(old.namespace)
                   .labels(dict(old.metadata.labels)).req({"cpu": "10m"})
                   .node(old.spec.node_name).terminating().obj())
            cache.update_pod(old, new)
            live[uid] = new


@pytest.mark.parametrize("seed", range(8))
def test_counts_match_the_per_pod_oracle(seed):
    rng = random.Random(seed)
    cache = SchedulerCache()
    nodes = []
    for i in range(rng.randrange(6, 18)):
        w = MakeNode().name(f"n{i}").capacity({"cpu": "64",
                                                "memory": "64Gi"})
        if rng.random() < 0.85:   # some nodes carry no zone: sentinel
            w = w.label(ZONE, f"z{rng.randrange(3)}")
        if rng.random() < 0.5:
            w = w.label(RACK, f"rack{rng.randrange(2)}")
        cache.add_node(w.obj())
        nodes.append(f"n{i}")
    live, removed, next_uid = {}, [], [0]
    snap = Snapshot()
    rounds = 0
    for _ in range(4):
        _churn(rng, cache, live, removed, nodes, next_uid)
        cache.update_snapshot(snap)
        pods = [_batch_pod(rng, k) for k in range(rng.randrange(2, 8))]
        enc = BatchEncoder(snap, pad_nodes=8)
        cluster, batch = enc.encode(pods, pad_pods=8)
        sc_want, term_want = oracle_counts(enc, cluster, batch)
        np.testing.assert_array_equal(batch.sc_counts, sc_want)
        np.testing.assert_array_equal(batch.term_counts, term_want)
        assert batch.sc_counts.dtype == sc_want.dtype == np.int32
        assert enc.count_pods == sum(len(ni.pods) for ni in snap.list())
        assert enc.count_sigs == len({pi.label_sig for ni in snap.list()
                                      for pi in ni.pods})
        rounds += bool(enc._constraints) and bool(live)
    assert rounds


def test_no_tracked_constraint_reads_nothing():
    cache = SchedulerCache()
    cache.add_node(MakeNode().name("n0").capacity({"cpu": "4"}).obj())
    cache.add_pod(MakePod().name("r").uid("r").label("app", "web")
                  .node("n0").obj())
    snap = Snapshot()
    cache.update_snapshot(snap)
    enc = BatchEncoder(snap, pad_nodes=8)
    _cluster, batch = enc.encode([MakePod().name("p").uid("p").obj()],
                                 pad_pods=8)
    assert (enc.count_pods, enc.count_sigs) == (0, 0)
    assert not batch.sc_counts.any() and not batch.term_counts.any()


def test_one_signature_per_label_set():
    """Many pods with one label set: one match stands for all of them."""
    cache = SchedulerCache()
    for i in range(4):
        cache.add_node(MakeNode().name(f"n{i}").label(ZONE, f"z{i % 2}")
                       .capacity({"cpu": "64"}).obj())
    for j in range(40):
        cache.add_pod(MakePod().name(f"r{j}").uid(f"r{j}")
                      .label("foo", "bar").node(f"n{j % 4}").obj())
    snap = Snapshot()
    cache.update_snapshot(snap)
    pod = (MakePod().name("p").uid("p").label("foo", "bar")
           .spread_constraint(1, ZONE, "DoNotSchedule", {"foo": "bar"})
           .spread_constraint(1, HOST, "DoNotSchedule", {"foo": "bar"})
           .obj())
    enc = BatchEncoder(snap, pad_nodes=8)
    cluster, batch = enc.encode([pod], pad_pods=8)
    assert (enc.count_pods, enc.count_sigs) == (40, 1)
    zone = enc._key_index[ZONE]
    zcodes = cluster.topo_codes[:4, zone]
    ci = [i for i, c in enumerate(enc._constraints) if c.key_idx == zone][0]
    for code in set(zcodes.tolist()):
        assert batch.sc_counts[ci, code] == 20
