"""Scheduler-internal types: Resource vectors, PodInfo, NodeInfo.

Behavioral equivalent of the reference's ``pkg/scheduler/framework/types.go``
(NodeInfo :230-271, Resource :324, PodInfo/AffinityTerm :72-93, QueuedPodInfo
:45, nextGeneration :282, Add/RemovePod :524-633). These structs are the
de-facto feature vectors of the system: per-node aggregates as int64
milli-CPU / bytes plus scalar-resource maps, used-port counts, image states,
and affinity-specialized pod sublists. The TPU encoder
(``kubernetes_tpu.ops.encode``) flattens exactly these aggregates into dense
device arrays, so keeping them columnar-friendly here is deliberate.
"""

from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from kubernetes_tpu.api import labels as labelslib
from kubernetes_tpu.api.types import (
    CPU,
    DEFAULT_MILLI_CPU_REQUEST,
    DEFAULT_MEMORY_REQUEST,
    EPHEMERAL_STORAGE,
    MEMORY,
    PODS,
    Node,
    Pod,
    PodAffinityTerm,
)

# Monotonic generation counter shared by all NodeInfos (reference
# types.go:282 nextGeneration / generation package var).
_generation = itertools.count(1)
_generation_lock = threading.Lock()


def next_generation() -> int:
    with _generation_lock:
        return next(_generation)


@dataclass
class Resource:
    """Aggregate resource vector (reference Resource, types.go:324)."""

    milli_cpu: int = 0
    memory: int = 0
    ephemeral_storage: int = 0
    allowed_pod_number: int = 0
    scalar_resources: Dict[str, int] = field(default_factory=dict)

    @classmethod
    def from_resource_list(cls, rl: Dict) -> "Resource":
        r = cls()
        for name, q in (rl or {}).items():
            if name == CPU:
                r.milli_cpu = q.milli_value()
            elif name == MEMORY:
                r.memory = q.value()
            elif name == EPHEMERAL_STORAGE:
                r.ephemeral_storage = q.value()
            elif name == PODS:
                r.allowed_pod_number = q.value()
            else:
                # scalar resources (extended, hugepages) count whole units
                r.scalar_resources[name] = q.value()
        return r

    def add(self, other: "Resource") -> None:
        self.milli_cpu += other.milli_cpu
        self.memory += other.memory
        self.ephemeral_storage += other.ephemeral_storage
        for k, v in other.scalar_resources.items():
            self.scalar_resources[k] = self.scalar_resources.get(k, 0) + v

    def sub(self, other: "Resource") -> None:
        self.milli_cpu -= other.milli_cpu
        self.memory -= other.memory
        self.ephemeral_storage -= other.ephemeral_storage
        for k, v in other.scalar_resources.items():
            self.scalar_resources[k] = self.scalar_resources.get(k, 0) - v

    def set_max(self, other: "Resource") -> None:
        self.milli_cpu = max(self.milli_cpu, other.milli_cpu)
        self.memory = max(self.memory, other.memory)
        self.ephemeral_storage = max(self.ephemeral_storage, other.ephemeral_storage)
        for k, v in other.scalar_resources.items():
            self.scalar_resources[k] = max(self.scalar_resources.get(k, 0), v)

    def clone(self) -> "Resource":
        return Resource(
            self.milli_cpu,
            self.memory,
            self.ephemeral_storage,
            self.allowed_pod_number,
            dict(self.scalar_resources),
        )


def is_extended(name: str) -> bool:
    return "/" in name


def is_hugepage(name: str) -> bool:
    return name.startswith("hugepages-")


def compute_pod_resource_request(pod: Pod, non_zero: bool = False) -> Resource:
    """max(sum(app containers), max(init containers)) + overhead
    (reference fit.go:148-165 computePodResourceRequest; non_zero variant
    applies the 100m/200Mi defaults from schedutil GetNonzeroRequests)."""
    spec = pod.spec
    if len(spec.containers) == 1 and not spec.init_containers \
            and not spec.overhead:
        # single plain container — the overwhelmingly common shape; skip
        # the aggregate scaffolding (this runs twice per pod on the
        # queue-admission hot path)
        result = _container_request(spec.containers[0], non_zero)
    else:
        result = Resource()
        for c in spec.containers:
            result.add(_container_request(c, non_zero))
        init_max = Resource()
        for c in spec.init_containers:
            init_max.set_max(_container_request(c, non_zero))
        result.set_max(init_max)
        if spec.overhead:
            result.add(Resource.from_resource_list(spec.overhead))
    # a pod request never carries allowed_pod_number (single enforcement
    # site for both paths)
    result.allowed_pod_number = 0
    return result


def _container_request(container, non_zero: bool) -> Resource:
    r = Resource.from_resource_list(container.resources.requests)
    if non_zero:
        if CPU not in container.resources.requests:
            r.milli_cpu = DEFAULT_MILLI_CPU_REQUEST
        if MEMORY not in container.resources.requests:
            r.memory = DEFAULT_MEMORY_REQUEST
    return r


@dataclass(frozen=True)
class AffinityTerm:
    """Pre-parsed (anti-)affinity term (reference types.go:72-82)."""

    namespaces: frozenset
    selector: labelslib.Selector
    topology_key: str

    def matches(self, pod: Pod) -> bool:
        return pod.namespace in self.namespaces and self.selector.matches(
            pod.metadata.labels
        )


@dataclass(frozen=True)
class WeightedAffinityTerm:
    term: AffinityTerm
    weight: int


def _make_term(pod: Pod, term: PodAffinityTerm) -> AffinityTerm:
    namespaces = set(term.namespaces) if term.namespaces else {pod.namespace}
    return AffinityTerm(
        namespaces=frozenset(namespaces),
        selector=labelslib.selector_from_label_selector(term.label_selector),
        topology_key=term.topology_key,
    )


class PodInfo:
    """Pod plus pre-parsed affinity terms (reference types.go:83-93) and the
    precomputed resource requests the hot path reads repeatedly."""

    __slots__ = (
        "pod",
        "required_affinity_terms",
        "required_anti_affinity_terms",
        "preferred_affinity_terms",
        "preferred_anti_affinity_terms",
        "resource_request",
        "non_zero_request",
        "label_sig",
    )

    @classmethod
    def of(cls, pod: Pod) -> "PodInfo":
        """Memoized constructor: parsing terms and summing resource vectors
        dominates the hot commit path when the same Pod object flows
        through queue → cache → encoder, so cache the PodInfo on the pod.
        The identity check guards against ``copy.copy`` propagating the
        memo to a new pod revision (the copied ``__dict__`` aliases it):
        a hit requires the cached parse to belong to THIS object.

        CONTRACT: Pod objects are immutable once stored — every revision
        is a fresh object (the store's copy-on-write updates, matching
        the reference's serialize-over-the-wire boundary). A caller that
        mutates a stored Pod's labels/containers in place would read a
        stale parse here; don't."""
        pi = pod.__dict__.get("_pod_info")
        if pi is None or pi.pod is not pod:
            pi = cls(pod)
            pod.__dict__["_pod_info"] = pi
        return pi

    @classmethod
    def derived(cls, pod: Pod, base: "PodInfo") -> "PodInfo":
        """A PodInfo for a shallow variant of ``base.pod`` (the assumed
        copy, which differs only in spec.nodeName): share the parsed
        terms and resource vectors instead of re-parsing. The caller
        guarantees containers/affinity/labels are unchanged."""
        pi = cls.__new__(cls)
        pi.pod = pod
        pi.required_affinity_terms = base.required_affinity_terms
        pi.required_anti_affinity_terms = base.required_anti_affinity_terms
        pi.preferred_affinity_terms = base.preferred_affinity_terms
        pi.preferred_anti_affinity_terms = base.preferred_anti_affinity_terms
        pi.resource_request = base.resource_request
        pi.non_zero_request = base.non_zero_request
        pi.label_sig = base.label_sig
        pod.__dict__["_pod_info"] = pi
        return pi

    def __init__(self, pod: Pod):
        self.pod = pod
        self.required_affinity_terms: List[AffinityTerm] = []
        self.required_anti_affinity_terms: List[AffinityTerm] = []
        self.preferred_affinity_terms: List[WeightedAffinityTerm] = []
        self.preferred_anti_affinity_terms: List[WeightedAffinityTerm] = []
        aff = pod.spec.affinity
        if aff is not None:
            if aff.pod_affinity is not None:
                for t in aff.pod_affinity.required_during_scheduling_ignored_during_execution:
                    self.required_affinity_terms.append(_make_term(pod, t))
                for wt in aff.pod_affinity.preferred_during_scheduling_ignored_during_execution:
                    self.preferred_affinity_terms.append(
                        WeightedAffinityTerm(_make_term(pod, wt.pod_affinity_term), wt.weight)
                    )
            if aff.pod_anti_affinity is not None:
                for t in aff.pod_anti_affinity.required_during_scheduling_ignored_during_execution:
                    self.required_anti_affinity_terms.append(_make_term(pod, t))
                for wt in aff.pod_anti_affinity.preferred_during_scheduling_ignored_during_execution:
                    self.preferred_anti_affinity_terms.append(
                        WeightedAffinityTerm(_make_term(pod, wt.pod_affinity_term), wt.weight)
                    )
        self.resource_request = compute_pod_resource_request(pod)
        self.non_zero_request = compute_pod_resource_request(pod, non_zero=True)
        # what a spread constraint or an affinity term can ask of the
        # pod: pods with equal signatures match the same selectors, so
        # NodeInfo's per-node signature counts stand in for its pods
        meta = pod.metadata
        self.label_sig = (meta.namespace,
                          frozenset((meta.labels or {}).items()),
                          meta.deletion_timestamp is not None)


class QueuedPodInfo:
    """Queue bookkeeping around a PodInfo (reference types.go:45)."""

    __slots__ = ("pod_info", "timestamp", "attempts", "initial_attempt_timestamp")

    def __init__(self, pod: Pod, timestamp: Optional[float] = None, attempts: int = 0):
        now = time.monotonic() if timestamp is None else timestamp
        self.pod_info = PodInfo.of(pod)
        self.timestamp = now
        self.attempts = attempts
        self.initial_attempt_timestamp = now

    @property
    def pod(self) -> Pod:
        return self.pod_info.pod


@dataclass
class ImageStateSummary:
    size: int = 0
    num_nodes: int = 0


# used-ports key: (hostIP, protocol, hostPort) — reference HostPortInfo.
PortKey = Tuple[str, str, int]


def pod_host_ports(pod: Pod) -> List[PortKey]:
    out = []
    for c in pod.spec.containers:
        for p in c.ports:
            if p.host_port > 0:
                out.append((p.host_ip or "0.0.0.0", p.protocol or "TCP", p.host_port))
    return out


def ports_conflict(used: Iterable[PortKey], wanted: List[PortKey]) -> bool:
    """HostPortInfo.CheckConflict: 0.0.0.0 conflicts with any IP on the
    same (protocol, port). ``used`` is NodeInfo.used_ports, whose keys
    are the node's ports."""
    if not wanted or not used:
        return False
    for ip, proto, port in wanted:
        for uip, uproto, uport in used:
            if proto == uproto and port == uport:
                if ip == "0.0.0.0" or uip == "0.0.0.0" or ip == uip:
                    return True
    return False


class NodeInfo:
    """Aggregated per-node scheduling state (reference types.go:230-271)."""

    __slots__ = (
        "node",
        "_pods",
        "_pod_list",
        "pods_with_affinity",
        "pods_with_required_anti_affinity",
        "used_ports",
        "requested",
        "non_zero_requested",
        "allocatable",
        "image_states",
        "generation",
        "label_sig_counts",
    )

    def __init__(self, *pods: Pod):
        self.node: Optional[Node] = None
        # pod uid -> PodInfo, in the order the pods were added: a removal
        # finds its pod without scanning the node's pods
        self._pods: Dict[str, PodInfo] = {}
        # ``pods`` as a list, built on the first read after a change and
        # never mutated, so copies may share it
        self._pod_list: Optional[List[PodInfo]] = []
        self.pods_with_affinity: List[PodInfo] = []
        self.pods_with_required_anti_affinity: List[PodInfo] = []
        # host port -> number of this node's pods declaring it, so a
        # removal takes away only the leaving pod's own ports
        self.used_ports: Dict[PortKey, int] = {}
        self.requested = Resource()
        self.non_zero_requested = Resource()
        self.allocatable = Resource()
        self.image_states: Dict[str, ImageStateSummary] = {}
        self.generation = next_generation()
        # PodInfo.label_sig -> number of this node's pods carrying it
        self.label_sig_counts: Dict[tuple, int] = {}
        for p in pods:
            self.add_pod(p)

    @property
    def pods(self) -> List[PodInfo]:
        """The node's pods in the order they were added. Read-only: the
        list is shared with copies of this NodeInfo."""
        pods = self._pod_list
        if pods is None:
            pods = self._pod_list = list(self._pods.values())
        return pods

    def set_node(self, node: Node) -> None:
        self.node = node
        self.allocatable = Resource.from_resource_list(node.status.allocatable)
        self.generation = next_generation()

    def remove_node(self) -> None:
        self.node = None
        self.generation = next_generation()

    def add_pod(self, pod: Pod) -> None:
        self.add_pod_info(PodInfo.of(pod))

    def add_pod_info(self, pi: PodInfo) -> None:
        self._pods[pi.pod.uid] = pi
        self._pod_list = None
        if _pod_with_affinity(pi):
            self.pods_with_affinity.append(pi)
        if pi.required_anti_affinity_terms:
            self.pods_with_required_anti_affinity.append(pi)
        self.requested.add(pi.resource_request)
        self.requested.allowed_pod_number = 0  # not meaningful on requested
        self.non_zero_requested.add(pi.non_zero_request)
        ports = self.used_ports
        for key in pod_host_ports(pi.pod):
            ports[key] = ports.get(key, 0) + 1
        sigs = self.label_sig_counts
        sigs[pi.label_sig] = sigs.get(pi.label_sig, 0) + 1
        self.generation = next_generation()

    def remove_pod(self, pod: Pod) -> bool:
        """Take away the stored pod with ``pod``'s uid (node_info.go
        RemovePod); False when the node holds no such pod."""
        uid = pod.uid
        pi = self._pods.pop(uid, None)
        if pi is None:
            return False
        self._pod_list = None
        if _pod_with_affinity(pi):
            self.pods_with_affinity = [
                x for x in self.pods_with_affinity if x.pod.uid != uid
            ]
        if pi.required_anti_affinity_terms:
            self.pods_with_required_anti_affinity = [
                x for x in self.pods_with_required_anti_affinity
                if x.pod.uid != uid
            ]
        self.requested.sub(pi.resource_request)
        self.non_zero_requested.sub(pi.non_zero_request)
        ports = self.used_ports
        for key in pod_host_ports(pi.pod):
            left = ports[key] - 1
            if left:
                ports[key] = left
            else:
                del ports[key]
        left = self.label_sig_counts[pi.label_sig] - 1
        if left:
            self.label_sig_counts[pi.label_sig] = left
        else:
            del self.label_sig_counts[pi.label_sig]
        self.generation = next_generation()
        return True

    def clone(self) -> "NodeInfo":
        c = NodeInfo()
        c.copy_from(self)
        return c

    def copy_from(self, other: "NodeInfo") -> None:
        """Overwrite this NodeInfo in place. The snapshot's map and ordered
        lists share NodeInfo identity (like the reference's shared
        pointers, snapshot.go), so incremental updates must mutate the
        existing object rather than replace it."""
        self.node = other.node
        self._pods = dict(other._pods)
        self._pod_list = other._pod_list
        self.pods_with_affinity = list(other.pods_with_affinity)
        self.pods_with_required_anti_affinity = list(
            other.pods_with_required_anti_affinity
        )
        self.used_ports = dict(other.used_ports)
        self.requested = other.requested.clone()
        self.non_zero_requested = other.non_zero_requested.clone()
        self.allocatable = other.allocatable.clone()
        self.image_states = dict(other.image_states)
        self.label_sig_counts = dict(other.label_sig_counts)
        self.generation = other.generation


def _pod_with_affinity(pi: PodInfo) -> bool:
    return bool(
        pi.required_affinity_terms
        or pi.required_anti_affinity_terms
        or pi.preferred_affinity_terms
        or pi.preferred_anti_affinity_terms
    )


def get_pod_key(pod: Pod) -> str:
    return pod.uid
