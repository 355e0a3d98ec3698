"""From the profiler's trace to numbers: the benchmark's own reduction.

Two steps, so the second can be checked on a small recorded trace
(``tests/data/trace_small.json``):

``load_xplane``  ``.xplane.pb`` -> ``{"devices": {plane: [[name, start_ns,
                 dur_ns], ...]}, "host": [[name, start_ns, dur_ns], ...]}``.
                 Device events are the ``XLA Ops`` line of each
                 ``/device:`` plane.  Host events are the harness's own
                 ``TraceAnnotation`` spans, by name.
``reduce``       that -> busy and window seconds, the operations that
                 took most device time (self time: a ``while`` does not
                 count its body twice), and the longest idle gaps with
                 the host span each fell in.

Busy is the union of the intervals in which an operation ran on the
device, clipped to the traced window and averaged over the devices.  The
window runs from the start of the first host span to the end of the last.
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
BETWEEN = "between_posts"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, span_names: tuple[str, ...],
                rehearsal: bool = False) -> dict:
    """``rehearsal``: with no ``/device:`` plane (the CPU backend), take
    the host events that carry an ``hlo_op`` as the device's, so that the
    path can be walked without a chip.  Never used on one."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    cpu_ops: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            # "%fusion.8 = f32[1024,...] fusion(...)": the name is enough
            ops = [[ev.name.split(" = ", 1)[0], float(ev.start_ns),
                    float(ev.duration_ns)]
                   for ln in lines for ev in ln.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    row = [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    if ev.name in span_names:
                        host.append(row)
                    elif rehearsal and any(k == "hlo_op" for k, _v in ev.stats):
                        cpu_ops.append(row)
    if rehearsal and not devices and cpu_ops:
        devices["cpu-rehearsal"] = cpu_ops
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end] pairs."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(ops: list) -> dict:
    """Seconds per operation name, each instant given to the innermost
    operation that covers it."""
    total: dict = {}
    stack: list = []  # [name, end, child_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, child, start = stack.pop()
            total[name] = total.get(name, 0.0) + (end - start - child)
            if stack:
                stack[-1][2] += end - start

    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        end = start + dur
        if stack:
            end = min(end, stack[-1][1])
        stack.append([name, end, 0.0, start])
    close(float("inf"))
    return {k: v / 1e9 for k, v in total.items()}


def reduce(raw: dict, top: int = 10) -> dict | None:
    """``None`` where there is nothing to read: no host span or no
    device operation inside the window."""
    host, devices = raw["host"], raw["devices"]
    if not host or not devices:
        return None
    t0 = min(s for _n, s, _d in host)
    t1 = max(s + d for _n, s, d in host)
    busy, gaps, selfs = [], [], {}
    for ops in devices.values():
        inside = [[n, max(s, t0), min(s + d, t1) - max(s, t0)]
                  for n, s, d in ops if s < t1 and s + d > t0]
        if not inside:
            continue
        merged = _union([[s, s + d] for _n, s, d in inside])
        busy.append(sum(e - s for s, e in merged))
        edges = [t0] + [x for pair in merged for x in pair] + [t1]
        gaps += [[edges[i], edges[i + 1]]
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, secs in _self_times(inside).items():
            selfs[name] = selfs.get(name, 0.0) + secs
    if not busy:
        return None

    def host_span_at(t: float) -> str:
        for name, s, d in host:
            if s <= t < s + d:
                return name
        return BETWEEN

    # a gap is cut where the host passes from one span to the next, so
    # each piece has one answer to "what was the host doing"
    cuts = sorted({t for _n, s, d in host for t in (s, s + d)})
    pieces = []
    for s, e in gaps:
        edges = [s] + [t for t in cuts if s < t < e] + [e]
        pieces += [[host_span_at((a + b) / 2), (b - a) / 1e9]
                   for a, b in zip(edges, edges[1:])]
    pieces.sort(key=lambda g: -g[1])
    idle_by_span: dict = {}
    for name, secs in pieces:
        idle_by_span[name] = idle_by_span.get(name, 0.0) + secs / len(busy)
    n_dev = len(busy)
    return {
        "busy_s": sum(busy) / n_dev / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "cycles": sum(1 for n, _s, _d in host if n == host[-1][0]),
        "device_ops": [[n, s / n_dev] for n, s in sorted(
            selfs.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": pieces[:top],
        "idle_by_host_span": idle_by_span,
    }
