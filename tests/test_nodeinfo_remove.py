"""Pod removal from NodeInfo and SchedulerCache: a removal finds the pod by
uid and takes away only that pod's ports, affinity entries and counts, so
after any add/remove sequence a NodeInfo equals one built fresh from the
pods that are left."""

import random

import pytest

from kubernetes_tpu.ops.mirror import DeltaJournal
from kubernetes_tpu.scheduler import types as sched_types
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.types import NodeInfo, PodInfo
from kubernetes_tpu.testing import MakeNode, MakePod


def _node(name="n1"):
    return MakeNode().name(name).capacity(
        {"cpu": "32", "memory": "64Gi", "pods": "110"}).obj()


def _pod(rng, uid, node="n1"):
    """A pod of one of the shapes removal has to undo: plain, with host
    ports (few port numbers, so keys repeat across pods), with required
    anti-affinity, or with preferred affinity only."""
    w = MakePod().name(uid).uid(uid).node(node).req(
        {"cpu": f"{rng.choice([100, 250, 500])}m",
         "memory": f"{rng.choice([128, 500])}Mi"}).labels(
        rng.choice([{"app": "a"}, {"app": "b"}, {}]))
    kind = rng.choice(["plain", "ports", "anti", "preferred"])
    if kind == "ports":
        w = w.host_port(rng.choice([80, 443]),
                        host_ip=rng.choice(["", "10.0.0.1"]))
        if rng.random() < 0.3:
            w = w.host_port(8080, protocol="UDP")
    elif kind == "anti":
        w = w.pod_anti_affinity("app", ["a"], "kubernetes.io/hostname")
    elif kind == "preferred":
        w = w.preferred_pod_affinity(5, "app", ["b"],
                                     "kubernetes.io/hostname")
    return w.obj()


def _state(ni):
    return {
        "pods": {pi.pod.uid for pi in ni.pods},
        "n_pods": len(ni.pods),
        "requested": ni.requested,
        "non_zero_requested": ni.non_zero_requested,
        "used_ports": dict(ni.used_ports),
        "label_sig_counts": dict(ni.label_sig_counts),
        "pods_with_affinity": sorted(
            pi.pod.uid for pi in ni.pods_with_affinity),
        "pods_with_required_anti_affinity": sorted(
            pi.pod.uid for pi in ni.pods_with_required_anti_affinity),
    }


def _fresh(live):
    ni = NodeInfo(*live.values())
    ni.set_node(_node())
    return ni


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 2147483659])
def test_removal_matches_a_fresh_build(seed):
    rng = random.Random(seed)
    ni = NodeInfo()
    ni.set_node(_node())
    live = {}
    for step in range(160):
        roll = rng.random()
        if roll < 0.08:
            absent = _pod(rng, f"gone{step}")
            assert ni.remove_pod(absent) is False
        elif live and roll < 0.5:
            uid = rng.choice(sorted(live))
            assert ni.remove_pod(live.pop(uid)) is True
        else:
            uid = f"u{step}"
            live[uid] = _pod(rng, uid)
            ni.add_pod(live[uid])
        assert _state(ni) == _state(_fresh(live)), f"step {step}"
        # a pod's order on the node is the order it was added in
        assert [pi.pod.uid for pi in ni.pods] == list(live)


def test_two_pods_sharing_a_port_key():
    a = MakePod().name("a").uid("a").node("n1").host_port(80).obj()
    b = MakePod().name("b").uid("b").node("n1").host_port(80).obj()
    ni = NodeInfo(a, b)
    assert set(ni.used_ports) == {("0.0.0.0", "TCP", 80)}
    assert ni.remove_pod(a)
    wanted = sched_types.pod_host_ports(a)
    assert sched_types.ports_conflict(ni.used_ports, wanted)
    assert ni.remove_pod(b)
    assert not ni.used_ports
    assert not sched_types.ports_conflict(ni.used_ports, wanted)


def test_removal_reads_only_the_leaving_pods_ports(monkeypatch):
    rng = random.Random(7)
    pods = [_pod(rng, f"u{i}") for i in range(40)]
    ni = NodeInfo(*pods)
    seen = []
    real = sched_types.pod_host_ports

    def counting(pod):
        seen.append(pod.uid)
        return real(pod)

    monkeypatch.setattr(sched_types, "pod_host_ports", counting)
    assert ni.remove_pod(pods[17])
    assert seen == ["u17"]
    assert not ni.remove_pod(pods[17])
    assert seen == ["u17"]


def test_removal_does_not_scan_the_nodes_pods(monkeypatch):
    """Finding the pod goes through the uid map: no other pod's uid is read
    and no PodInfo is compared."""
    rng = random.Random(11)
    pods = [_pod(rng, f"u{i}") for i in range(40)]
    leaving = MakePod().name("plain").uid("plain").node("n1").obj()
    ni = NodeInfo(*pods, leaving, *[_pod(rng, f"v{i}") for i in range(8)])
    reads = []
    for p in ni.pods:
        p.pod.metadata = _CountingMeta(p.pod.metadata, reads)
    monkeypatch.setattr(PodInfo, "__eq__", _no_eq, raising=False)
    assert ni.remove_pod(leaving)
    assert reads == ["plain"]


class _CountingMeta:
    def __init__(self, meta, reads):
        self._meta = meta
        self._reads = reads

    def __getattr__(self, name):
        if name == "uid":
            self._reads.append(self._meta.uid)
        return getattr(self._meta, name)


def _no_eq(self, other):
    raise AssertionError("a removal compared PodInfos")


@pytest.mark.parametrize("how", ["clone", "copy_from"])
def test_mutated_copy_leaves_its_source_untouched(how):
    rng = random.Random(5)
    pods = [_pod(rng, f"u{i}") for i in range(30)]
    src = NodeInfo(*pods)
    src.set_node(_node())
    order = [pi.pod.uid for pi in src.pods]   # the list copies share
    before = _state(src)
    if how == "clone":
        copy = src.clone()
    else:
        copy = NodeInfo()
        copy.copy_from(src)
    for p in pods[::3]:
        assert copy.remove_pod(p)
    copy.add_pod(_pod(rng, "new"))
    assert _state(src) == before
    assert [pi.pod.uid for pi in src.pods] == order
    left = {p.uid: p for p in pods if p.uid not in
            {q.uid for q in pods[::3]}}
    left["new"] = copy.pods[-1].pod
    assert _state(copy) == _state(_fresh(left))


def _cache_with_pods(seed):
    rng = random.Random(seed)
    cache = SchedulerCache(now=lambda: 0.0)
    journal = DeltaJournal()
    cache.attach_delta_journal(journal)
    nodes = [_node(f"n{i}") for i in range(4)]
    for n in nodes:
        cache.add_node(n)
    pods = []
    for i in range(48):
        pods.append(_pod(rng, f"u{i}", node=f"n{i % 4}"))
    for p in pods[:40]:
        cache.add_pod(p)
    # assumed pods, one of them never confirmed
    for p in pods[40:]:
        cache.assume_pod(p)
    cache.add_pod(pods[40])
    # delete a shuffled mix: confirmed, assumed, and never cached
    doomed = rng.sample(pods, 30) + [_pod(rng, "absent", node="n0")]
    rng.shuffle(doomed)
    return cache, journal, doomed


def _cache_state(cache):
    dump = cache.dump()
    return (
        {n: _state(ni) for n, ni in dump["nodes"].items()},
        dump["assumed_pods"],
        cache.mutation_seq,
    )


def _records(journal, cache):
    return [(r.seq, r.kind, r.a.name)
            for r in journal.window(0, cache.mutation_seq)]


@pytest.mark.parametrize("seed", [0, 1, 2147483651])
def test_bulk_removal_equals_one_by_one(seed):
    one, one_journal, doomed = _cache_with_pods(seed)
    bulk, bulk_journal, bulk_doomed = _cache_with_pods(seed)
    assert [p.uid for p in doomed] == [p.uid for p in bulk_doomed]
    for p in doomed:
        one.remove_pod(p)
    bulk.remove_pods(bulk_doomed)
    assert _cache_state(bulk) == _cache_state(one)
    assert _records(bulk_journal, bulk) == _records(one_journal, one)
    # the head of the generation list is the node touched last
    assert bulk._head.info.node.name == one._head.info.node.name
