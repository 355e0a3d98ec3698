"""In-memory cluster model — the framework's stand-in for the K8s API server.

Every reference component talks exclusively through the API server (CRDs
+ watches, SURVEY.md §1); this object is that hub for the TPU framework:
intake (podgrouper) writes PodGroups into it, the scheduler snapshots it,
the binder commits bindings back, controllers (queue/podgroup status)
derive status from it.  In a real deployment this is replaced by a thin
client layer; the scheduling semantics live entirely above it.

It deliberately mirrors the fake-cluster model the reference uses for its
action integration tests (``pkg/scheduler/test_utils/test_utils.go``) —
the same object doubles as the test harness, per SURVEY.md §4 tier 2.
"""
from __future__ import annotations

import dataclasses

from ..apis import types as apis
from ..intake import gate as _gate
from .store import Collection

#: the collections of the store that mark the journal as they are
#: written (``runtime/store.py``)
STORE_COLLECTIONS = ("nodes", "pod_groups", "pods", "bind_requests")


@dataclasses.dataclass
class Cluster:
    """Mutable cluster document store, keyed by object name."""

    nodes: dict[str, apis.Node] = dataclasses.field(default_factory=dict)
    queues: dict[str, apis.Queue] = dataclasses.field(default_factory=dict)
    pod_groups: dict[str, apis.PodGroup] = dataclasses.field(default_factory=dict)
    pods: dict[str, apis.Pod] = dataclasses.field(default_factory=dict)
    topology: apis.Topology | None = None
    bind_requests: dict[str, apis.BindRequest] = dataclasses.field(default_factory=dict)
    #: DRA objects (ref populateDRAGPUs + SharedDRAManager state)
    resource_claims: dict[str, apis.ResourceClaim] = dataclasses.field(
        default_factory=dict)
    device_classes: dict[str, apis.DeviceClass] = dataclasses.field(
        default_factory=dict)
    #: storage objects (ref storage{class,claim} info structs)
    volume_claims: dict[str, apis.PersistentVolumeClaim] = dataclasses.field(
        default_factory=dict)
    storage_classes: dict[str, apis.StorageClass] = dataclasses.field(
        default_factory=dict)
    #: shared-device reservation registry (ref the reservation pods in
    #: kai-resource-reservation; see runtime/reservation.py)
    reservations: "object" = None
    #: mutation journal — every state change records dirty keys so the
    #: incremental snapshotter (state/incremental.py) refreshes
    #: proportional to churn instead of rebuilding per cycle (the
    #: API-watch role of the reference's cache layer, SURVEY §2.6).
    #: The four ``STORE_COLLECTIONS`` mark it themselves as they are
    #: written, and so do the watched fields of what they hold
    journal: "object" = None

    def __post_init__(self):
        if self.reservations is None:
            from .reservation import ReservationRegistry
            self.reservations = ReservationRegistry()
        if self.journal is None:
            from ..state.incremental import MutationJournal
            self.journal = MutationJournal()

    def __setattr__(self, name, value):
        if name in STORE_COLLECTIONS:
            old = self.__dict__.get(name)
            if value is old:  # ``cluster.pods |= ...`` sets it back
                return
            journal = self.__dict__.get("journal")
            # filled unmarked: a new collection rebuilds as a whole
            new = Collection(name, None, value)
            new.journal = journal
            object.__setattr__(self, name, new)
            if old is not None:
                # a collection replaced wholesale: nothing of the old one
                # is worth patching from
                old.detach()
                if journal is not None:
                    _gate.structural(journal, f"{name}-replaced")
            return
        object.__setattr__(self, name, value)
        if name == "journal":
            for coll in STORE_COLLECTIONS:
                store = self.__dict__.get(coll)
                if store is not None:
                    store.journal = value

    #: monotonic clock advanced by the simulation driver
    now: float = 0.0
    #: evicted pods whose workload controller will recreate them (the
    #: consolidation-move path) — on the next tick they return to PENDING
    #: instead of vanishing
    restarting: set[str] = dataclasses.field(default_factory=set)
    #: kai-twin recorder hook (``twin/stream.StreamRecorder``): when
    #: set, the shared intake applier mirrors every successfully
    #: applied event into the recorder's stream.  Deepcopied clusters
    #: drop the hook (the recorder's ``__deepcopy__`` returns None) so
    #: a profiling/differential twin never re-records its own replay.
    twin_recorder: "object" = None

    # -- intake -----------------------------------------------------------

    @classmethod
    def from_objects(cls, nodes, queues, pod_groups, pods, topology=None) -> "Cluster":
        c = cls(topology=topology)
        for n in nodes:
            c.nodes[n.name] = n
        for q in queues:
            c.queues[q.name] = q
        for g in pod_groups:
            c.pod_groups[g.name] = g
        for p in pods:
            c.pods[p.name] = p
        return c

    def submit(self, group: apis.PodGroup, pods: list[apis.Pod]) -> None:
        """Add a workload (PodGroup + its pods) — podgrouper output."""
        group.creation_timestamp = group.creation_timestamp or self.now
        # storing marks a new object, or another one under the name; the
        # one already there may have been changed before it came back
        if group.name in self.pod_groups:
            _gate.gang_touched(self.journal, group.name)
        self.pod_groups[group.name] = group
        for p in pods:
            p.creation_timestamp = p.creation_timestamp or self.now
            if p.name in self.pods:
                _gate.pod_touched(self.journal, p.name)
            self.pods[p.name] = p

    # -- views ------------------------------------------------------------

    def snapshot_lists(self):
        """Stable-ordered object lists for ``build_snapshot``.

        Pods with an in-flight (Pending) BindRequest are presented as
        BOUND on their selected node — the reference's snapshot does the
        same (``cache/cluster_info/cluster_info.go:323`` snapshotBindRequests)
        so the scheduler neither double-allocates their capacity nor
        re-schedules them while the binder retries.
        """
        pods: list[apis.Pod] = []
        for p in self.pods.values():
            br = self.bind_requests.get(p.name)
            if (p.status == apis.PodStatus.PENDING and br is not None
                    and br.phase == "Pending"):
                pods.append(dataclasses.replace(
                    p, status=apis.PodStatus.BOUND, node=br.selected_node))
            elif (p.status == apis.PodStatus.RELEASING and br is not None
                    and br.phase == "Pending"):
                # consolidation move in flight: the pod still occupies its
                # old node (releasing) AND holds a verified claim on the
                # rebind target — present both, so a cycle run before the
                # restart tick cannot steal the earmarked capacity.
                pods.append(p)
                pods.append(dataclasses.replace(
                    p, status=apis.PodStatus.BOUND, node=br.selected_node,
                    accel_devices=[]))
            else:
                pods.append(p)
        return (
            list(self.nodes.values()),
            list(self.queues.values()),
            list(self.pod_groups.values()),
            pods,
            self.topology,
        )

    def pods_of_group(self, group: str) -> list[apis.Pod]:
        return [p for p in self.pods.values() if p.group == group]

    def group_running_count(self, group: str) -> int:
        return sum(p.status in (apis.PodStatus.BOUND, apis.PodStatus.RUNNING)
                   for p in self.pods_of_group(group))

    # -- commit side (binder / evictor write-backs) -----------------------

    def create_bind_request(self, br: apis.BindRequest) -> None:
        # a Pending BindRequest changes the pod's snapshot presentation:
        # the store marks the pod
        self.bind_requests[br.pod_name] = br

    def node_device_free(self, node_name: str) -> list[float]:
        """Free share per accel device on a node, from pods' recorded
        devices — the runtime equivalent of the reservation-pod device
        bookkeeping (``binder/binding/resourcereservation``)."""
        node = self.nodes[node_name]
        free = [1.0] * int(round(node.allocatable.accel))
        # devices held through allocated DRA claims are not free either
        for claim in self.resource_claims.values():
            if claim.node == node_name:
                for d in claim.devices:
                    if d < len(free):
                        free[d] = 0.0
        for pod in self.pods.values():
            if pod.node != node_name or pod.status not in (
                    apis.PodStatus.BOUND, apis.PodStatus.RUNNING,
                    apis.PodStatus.RELEASING):
                continue
            if pod.accel_portion > 0 or pod.accel_memory_gib > 0:
                share = (pod.accel_portion if pod.accel_portion > 0
                         else pod.accel_memory_gib
                         / max(node.accel_memory_gib, 1e-6))
                for d in pod.accel_devices[:1]:
                    if d < len(free):
                        free[d] = max(0.0, free[d] - share)
            else:
                for d in pod.accel_devices:
                    if d < len(free):
                        free[d] = 0.0
        return free

    def bind_pod(self, pod_name: str, node_name: str,
                 devices: list[int] | None = None) -> None:
        """pods/binding subresource equivalent; assigns concrete accel
        devices (the reference resolves these through the reservation
        pod's NVML-discovered UUID — here device indices are first-class).
        """
        pod = self.pods[pod_name]
        if node_name not in self.nodes:
            raise KeyError(f"node {node_name} not found")
        free = self.node_device_free(node_name)
        if pod.accel_portion > 0 or pod.accel_memory_gib > 0:
            node = self.nodes[node_name]
            share = (pod.accel_portion if pod.accel_portion > 0
                     else pod.accel_memory_gib
                     / max(node.accel_memory_gib, 1e-6))
            if devices:
                pod.accel_devices = devices[:1]
            else:  # first fitting device, matching the snapshot builder
                fits = [d for d, f in enumerate(free) if f >= share - 1e-6]
                pod.accel_devices = fits[:1]
            if not pod.accel_devices:
                raise RuntimeError(
                    f"no device on {node_name} fits share {share} for "
                    f"{pod_name}")  # binder rolls back + backs off
        else:
            k = int(round(pod.resources.accel))
            if k > 0 and not pod.accel_devices:
                fully = [d for d, f in enumerate(free) if f >= 1.0 - 1e-6]
                if len(fully) < k:
                    raise RuntimeError(
                        f"only {len(fully)} fully-free devices on "
                        f"{node_name}, {pod_name} needs {k}")
                pod.accel_devices = fully[:k]
        pod.node = node_name
        pod.status = apis.PodStatus.BOUND
        # the devices are not a watched field
        _gate.pod_touched(self.journal, pod_name)
        group = self.pod_groups.get(pod.group)
        if group is not None and group.last_start_timestamp is None:
            group.last_start_timestamp = self.now

    def evict_pod(self, pod_name: str, restart: bool = False) -> None:
        """Eviction = delete pod; its resources become releasing until the
        next tick reaps it (matching the reference's deletion grace window).

        ``restart=True`` models the workload controller recreating the pod
        (consolidation moves): after release it returns to PENDING so a
        pipelined rebind can land it on its planned node.
        """
        pod = self.pods.get(pod_name)
        if pod is not None:
            pod.status = apis.PodStatus.RELEASING
            if restart:
                self.restarting.add(pod_name)

    def tick(self, seconds: float = 1.0) -> None:
        """Advance time: bound pods start running, releasing pods vanish
        (or restart as pending, if their controller recreates them)."""
        self.now += seconds
        _gate.time_advanced(self.journal)
        for name in list(self.pods):
            pod = self.pods[name]
            if pod.status == apis.PodStatus.RELEASING:
                # the pod's DRA claims deallocate with it (ref claim
                # deallocation on pod deletion) ...
                for claim in self.resource_claims.values():
                    if claim.owner_pod == name:
                        claim.node = None
                        claim.devices = []
                        claim.owner_pod = None
                # ... and its device reservations drop this sharer (the
                # reservation pod is deleted with the last one)
                self.reservations.release(name)
                if name in self.restarting:
                    self.restarting.discard(name)
                    pod.status = apis.PodStatus.PENDING
                    pod.node = None
                    pod.accel_devices = []
                    _gate.pod_touched(self.journal, name)
                else:
                    del self.pods[name]
            elif pod.status == apis.PodStatus.BOUND:
                pod.status = apis.PodStatus.RUNNING
