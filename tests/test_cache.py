"""Cache/snapshot/node_tree tests (modeled on the reference's
``internal/cache/cache_test.go`` strategy: direct state transitions +
incremental-snapshot coherence checks)."""

import pytest

from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.node_tree import NodeTree
from kubernetes_tpu.scheduler.snapshot import Snapshot, new_snapshot
from kubernetes_tpu.scheduler.types import NodeInfo, PodInfo
from kubernetes_tpu.testing import MakeNode, MakePod


def make_node(name, zone=None, cpu="4", mem="8Gi"):
    w = MakeNode().name(name).capacity({"cpu": cpu, "memory": mem})
    if zone:
        w.label("topology.kubernetes.io/zone", zone)
    return w.obj()


class TestNodeTree:
    def test_zone_interleave(self):
        t = NodeTree()
        for name, zone in [
            ("a1", "za"), ("a2", "za"), ("a3", "za"),
            ("b1", "zb"), ("c1", "zc"),
        ]:
            t.add_node(make_node(name, zone))
        assert t.list() == ["a1", "b1", "c1", "a2", "a3"]
        assert t.num_nodes == 5

    def test_remove(self):
        t = NodeTree()
        n = make_node("x", "z1")
        t.add_node(n)
        assert t.remove_node(n)
        assert t.num_nodes == 0
        assert t.list() == []


class TestCache:
    def test_assume_confirm_lifecycle(self):
        c = SchedulerCache()
        c.add_node(make_node("n1"))
        pod = MakePod().name("p").uid("u1").req({"cpu": "1"}).node("n1").obj()
        c.assume_pod(pod)
        assert c.is_assumed_pod(pod)
        assert c.pod_count() == 1
        c.add_pod(pod)  # informer confirms
        assert not c.is_assumed_pod(pod)
        assert c.pod_count() == 1
        c.remove_pod(pod)
        assert c.pod_count() == 0

    def test_forget(self):
        c = SchedulerCache()
        c.add_node(make_node("n1"))
        pod = MakePod().name("p").uid("u1").node("n1").obj()
        c.assume_pod(pod)
        c.forget_pod(pod)
        assert c.pod_count() == 0
        with pytest.raises(ValueError):
            c.forget_pod(pod)

    def test_assumed_expiry(self):
        now = [100.0]
        c = SchedulerCache(ttl=30.0, now=lambda: now[0])
        c.add_node(make_node("n1"))
        pod = MakePod().name("p").uid("u1").node("n1").obj()
        c.assume_pod(pod)
        c.finish_binding(pod)
        c.cleanup_expired_assumed_pods(now=105.0)
        assert c.pod_count() == 1  # not yet expired
        c.cleanup_expired_assumed_pods(now=131.0)
        assert c.pod_count() == 0  # expired: assume undone

    def test_expiry_only_after_binding_finished(self):
        c = SchedulerCache(ttl=30.0, now=lambda: 0.0)
        c.add_node(make_node("n1"))
        pod = MakePod().name("p").uid("u1").node("n1").obj()
        c.assume_pod(pod)
        c.cleanup_expired_assumed_pods(now=10_000.0)
        assert c.pod_count() == 1  # no FinishBinding -> never expires

    def test_incremental_snapshot(self):
        c = SchedulerCache()
        snap = Snapshot()
        for i in range(3):
            c.add_node(make_node(f"n{i}"))
        c.update_snapshot(snap)
        assert snap.num_nodes() == 3
        gen1 = snap.generation

        pod = MakePod().name("p").uid("u1").req({"cpu": "500m"}).node("n1").obj()
        c.add_pod(pod)
        c.update_snapshot(snap)
        assert snap.generation > gen1
        assert snap.get("n1").requested.milli_cpu == 500
        # unchanged nodes keep identity (no gratuitous clone churn check:
        # at least the data stays correct)
        assert snap.get("n0").requested.milli_cpu == 0

        c.remove_node(make_node("n2"))
        c.update_snapshot(snap)
        assert snap.num_nodes() == 2
        assert snap.get("n2") is None

    def test_snapshot_affinity_lists(self):
        c = SchedulerCache()
        snap = Snapshot()
        c.add_node(make_node("n1"))
        c.update_snapshot(snap)
        assert snap.have_pods_with_affinity_list() == []
        pod = (
            MakePod().name("p").uid("u1").node("n1")
            .pod_anti_affinity("app", ["web"], "zone").obj()
        )
        c.add_pod(pod)
        c.update_snapshot(snap)
        assert len(snap.have_pods_with_affinity_list()) == 1
        assert len(snap.have_pods_with_required_anti_affinity_list()) == 1

    def test_update_pod(self):
        c = SchedulerCache()
        c.add_node(make_node("n1"))
        old = MakePod().name("p").uid("u1").req({"cpu": "1"}).node("n1").obj()
        c.add_pod(old)
        new = MakePod().name("p").uid("u1").req({"cpu": "2"}).node("n1").obj()
        c.update_pod(old, new)
        snap = Snapshot()
        c.update_snapshot(snap)
        assert snap.get("n1").requested.milli_cpu == 2000

    def test_image_states(self):
        c = SchedulerCache()
        c.add_node(MakeNode().name("n1").image("img:v1", 1000).obj())
        c.add_node(MakeNode().name("n2").image("img:v1", 1000).obj())
        snap = Snapshot()
        c.update_snapshot(snap)
        assert snap.get("n1").image_states["img:v1"].num_nodes == 2


class TestNewSnapshot:
    def test_direct_construction(self):
        nodes = [make_node("n1"), make_node("n2")]
        pods = [MakePod().name("p1").uid("u1").req({"cpu": "1"}).node("n1").obj()]
        s = new_snapshot(pods, nodes)
        assert s.num_nodes() == 2
        assert s.get("n1").requested.milli_cpu == 1000
        assert len(s.pods()) == 1


def _recount(ni):
    counts = {}
    for pi in ni.pods:
        counts[pi.label_sig] = counts.get(pi.label_sig, 0) + 1
    return counts


class TestLabelSigCounts:
    """NodeInfo's per-node counts of (namespace, labels, terminating)
    signatures, which the encoder reads instead of the pods."""

    def _pod(self, uid, ns="default", labels=None, terminating=False):
        w = MakePod().name(uid).uid(uid).namespace(ns).labels(
            labels or {}).node("n1")
        if terminating:
            w = w.terminating()
        return w.obj()

    @pytest.mark.parametrize("seed", range(4))
    def test_table_equals_a_recount_after_adds_and_removes(self, seed):
        import random

        rng = random.Random(seed)
        ni = NodeInfo()
        ni.set_node(make_node("n1"))
        live = {}
        for step in range(200):
            if live and rng.random() < 0.45:
                uid = rng.choice(sorted(live))
                assert ni.remove_pod(live.pop(uid))
            else:
                uid = f"u{step}"
                live[uid] = self._pod(
                    uid, rng.choice(["default", "other"]),
                    rng.choice([{"a": "1"}, {"a": "2"}, {}]),
                    terminating=rng.random() < 0.2)
                ni.add_pod(live[uid])
            assert ni.label_sig_counts == _recount(ni)
        assert all(ni.label_sig_counts.values())

    def test_signature_fields(self):
        pi = PodInfo.of(self._pod("u", "ns1", {"a": "1"}, terminating=True))
        assert pi.label_sig == ("ns1", frozenset({("a", "1")}), True)

    def test_removing_an_unknown_pod_leaves_it_unchanged(self):
        ni = NodeInfo(self._pod("u1", labels={"a": "1"}))
        before = dict(ni.label_sig_counts)
        assert not ni.remove_pod(self._pod("missing", labels={"a": "1"}))
        assert ni.label_sig_counts == before

    def test_remove_uses_the_stored_pod(self):
        """The argument pod may be a later revision: the stored one's
        signature is the one taken away."""
        ni = NodeInfo(self._pod("u1", labels={"a": "1"}))
        assert ni.remove_pod(self._pod("u1", labels={"a": "2"}))
        assert ni.label_sig_counts == {}

    def test_zero_count_key_is_dropped(self):
        a, b = self._pod("u1", labels={"a": "1"}), self._pod("u2")
        ni = NodeInfo(a, b, self._pod("u3", labels={"a": "1"}))
        assert ni.label_sig_counts[PodInfo.of(a).label_sig] == 2
        ni.remove_pod(b)
        assert PodInfo.of(b).label_sig not in ni.label_sig_counts
        assert list(ni.label_sig_counts.values()) == [2]

    def test_copies_are_independent(self):
        src = NodeInfo(self._pod("u1", labels={"a": "1"}))
        clone = src.clone()
        dst = NodeInfo()
        dst.copy_from(src)
        src.add_pod(self._pod("u2", labels={"a": "1"}))
        clone.add_pod(self._pod("u3", labels={"b": "1"}))
        assert dst.label_sig_counts == {
            PodInfo.of(self._pod("x", labels={"a": "1"})).label_sig: 1}
        for ni in (src, clone, dst):
            assert ni.label_sig_counts == _recount(ni)

    def test_assumed_copy_shares_the_signature(self):
        pod = self._pod("u1", labels={"a": "1"})
        assumed = MakePod().name("u1").uid("u1").labels({"a": "1"}).obj()
        assert PodInfo.derived(assumed, PodInfo.of(pod)).label_sig == \
            PodInfo.of(pod).label_sig

    def test_snapshot_follows_cache_churn(self):
        c = SchedulerCache()
        c.add_node(make_node("n1"))
        snap = Snapshot()
        pods = [self._pod(f"u{i}", labels={"a": str(i % 2)})
                for i in range(6)]
        for p in pods:
            c.add_pod(p)
        c.update_snapshot(snap)
        c.remove_pod(pods[0])
        c.remove_pod(pods[2])
        c.update_pod(pods[1], self._pod("u1", labels={"a": "1"},
                                        terminating=True))
        c.update_snapshot(snap)
        ni = snap.get("n1")
        assert ni.label_sig_counts == _recount(ni)
        assert sum(ni.label_sig_counts.values()) == 4
