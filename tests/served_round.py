"""One iteration of a closed-loop client against a served scheduler, as
the benchmark's harness posts it: the finished gang's pods and group
deleted through ``POST /cluster/delta``, two new gangs through
``POST /intake``, then ``POST /cycle/stored`` and, the moment the reply
is there, ``GET /healthz``.  At 64 nodes half full; every round after
the first patches its snapshot.  Shared by the tests of the request
traces (``docs/TRACING.md``)."""
import json
import time
import urllib.request

from kai_scheduler_tpu.framework.server import SchedulerServer
from kai_scheduler_tpu.runtime.cluster import Cluster
from kai_scheduler_tpu.state import make_cluster

PATHS = ("/cluster/delta", "/intake", "/cycle/stored")


def churn_cluster() -> Cluster:
    nodes, queues, groups, pods, topo = make_cluster(
        num_nodes=64, node_accel=8.0, num_gangs=32, tasks_per_gang=8,
        running_fraction=1.0)
    return Cluster.from_objects(nodes, queues, groups, pods, topo)


def start_server(cluster: Cluster | None = None):
    server = SchedulerServer(cluster or churn_cluster()).start()
    return server, f"http://127.0.0.1:{server.port}"


def post(base: str, path: str, body: bytes, ctype: str) -> bytes:
    req = urllib.request.Request(base + path, data=body,
                                 headers={"Content-Type": ctype})
    return urllib.request.urlopen(req, timeout=300).read()


def post_round(base: str, cyc: int, framing: str = "json") -> dict:
    """Round ``cyc`` in ``framing`` (``json`` or ``protobuf``; the
    intake speaks JSON alone) → ``last_cycle`` as a client that GETs
    ``/healthz`` directly after the cycle's reply reads it."""
    gone = [f"gang-{cyc}-pod-{t}" for t in range(8)]
    names = [f"job-{cyc}-{i}" for i in range(2)]
    intake = {
        "pod_groups_upsert": [
            {"name": n, "queue": "queue-0-0", "min_member": 8}
            for n in names],
        "pods_upsert": [{"name": f"{n}-{t}", "group": n,
                         "resources": {"accel": 1.0, "cpu": 1.0,
                                       "memory": 4.0}}
                        for n in names for t in range(8)]}
    if framing == "json":
        ctype = "application/json"
        delta = json.dumps({"now": float(cyc + 1), "pods_delete": gone,
                            "pod_groups_delete": [f"gang-{cyc}"]}).encode()
        cycle_body = b"{}"
    else:
        from kai_scheduler_tpu.wire import sidecar_pb2 as pb
        ctype = "application/x-protobuf"
        delta = pb.ClusterDelta(
            now=float(cyc + 1), pods_delete=gone,
            pod_groups_delete=[f"gang-{cyc}"]).SerializeToString()
        cycle_body = b""
    post(base, "/cluster/delta", delta, ctype)
    post(base, "/intake", json.dumps(intake).encode(), "application/json")
    post(base, "/cycle/stored", cycle_body, ctype)
    return json.load(urllib.request.urlopen(f"{base}/healthz"))["last_cycle"]


def closed_requests(server, n: int, timeout: float = 10.0) -> list:
    """The last ``n`` closed request traces, once that many have
    closed: a handler closes its request after the client has the
    reply, so a test that reads the ring (or forces a collection
    "between requests") waits for it here."""
    tracer = server.scheduler.tracer
    deadline = time.monotonic() + timeout
    while True:
        ring = tracer.last_requests(max(n, 1))
        if len(ring) >= n or time.monotonic() > deadline:
            return ring
        time.sleep(0.005)
