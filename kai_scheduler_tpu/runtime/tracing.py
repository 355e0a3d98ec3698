"""kai-trace — the cycle flight recorder.

The reference treats observability as a first-class layer (per-action /
per-plugin latency metrics, pod events explaining unschedulability,
continuous profiles).  This module is the span half of that story for
the TPU rebuild: a thread-safe recorder of *phase-attributed spans*
over the scheduling cycle, kept in a bounded ring of recent cycle
traces and exportable as Chrome-trace ("Trace Event Format") JSON —
loadable in ``chrome://tracing`` / Perfetto — via ``GET /debug/trace``
on the :class:`~..framework.server.SchedulerServer`.

Why spans and not three wall timers: kernels dispatch *async*, so a
naive per-step timer smears device execution, transfer wait, and host
decode into whichever step first blocks (historically all of it landed
in ``commit_seconds``).  The cycle driver therefore records explicit
**device-sync markers** (``device_sync=True`` spans) around the first
blocking transfer, splitting the old commit wall into
``device_wait`` / ``host_decode`` / ``commit`` — the attribution any
attack on host↔device transfer cost needs first.

Concurrency model: span recording is **thread-local** — each thread
owns the trace of the cycle it is running (an open trace is reachable
only through ``threading.local``, so no other thread can observe a
half-built span tree).  A trace enters the shared ring only once the
cycle closes, and ring entries are never mutated afterwards; ring
append/read is serialized under ``_lock`` (discipline declared in
``analysis/guarded_by.json``, checked by kai-race).  Exports therefore
can never serve a torn document.

Tracer calls are HOST-side by construction: kai-lint rule ``KAI061``
forbids them inside the jit-traced region (a span body executes at
trace time, not at kernel run time — it would record compilation, not
execution, and its timestamps would be garbage).

One clock with the device: every cycle and span also enters a
``jax.profiler.TraceAnnotation`` named ``"kai:" + name``, so that a
profiler capture around the served process holds the program's spans on
the device's own timeline (with no capture on, the annotation is a flag
check).  ``docs/TRACING.md`` lists every span by name.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import threading
import time

from jax import profiler as _profiler

__all__ = ["Span", "CycleTrace", "CycleTracer", "GcWatch",
           "SpanSections", "span_of", "add_span_to_open_cycle"]

#: prefix of every annotation this module writes into a profiler
#: capture; a harness that filters host events by its own names never
#: matches one
ANNOTATION_PREFIX = "kai:"

#: attr value types exported verbatim; anything else is stringified
_JSONABLE = (str, int, float, bool, type(None))


@dataclasses.dataclass
class Span:
    """One timed region of a cycle.

    ``start``/``end`` are ``time.perf_counter`` seconds (monotonic);
    ``children`` are strictly nested inside ``[start, end]`` by
    construction (context-manager discipline).
    """

    name: str
    start: float
    end: float = 0.0
    attrs: dict = dataclasses.field(default_factory=dict)
    children: list = dataclasses.field(default_factory=list)
    #: an explicit device-sync marker: this span brackets a blocking
    #: device→host (or host→device) boundary, so its duration is link +
    #: device time, not host work
    device_sync: bool = False

    @property
    def seconds(self) -> float:
        return max(0.0, self.end - self.start)

    def self_seconds(self) -> float:
        """This span's duration minus the part of it that its children
        cover (their union, clipped to the span: a retroactive child
        may overlap a sibling)."""
        covered, upto = 0.0, self.start
        for child in sorted(self.children, key=lambda c: c.start):
            lo, hi = max(child.start, upto), min(child.end, self.end)
            if hi > lo:
                covered += hi - lo
                upto = hi
        return max(0.0, self.seconds - covered)


class GcWatch:
    """Times every garbage collection of the process.

    ``install`` appends one hook to ``gc.callbacks`` (process-wide by
    nature: a collection stops every thread through the GIL, whichever
    thread's allocation set it off).  The hook reads the clock twice and
    adds to three per-generation accumulators; full (generation-2)
    collections also leave their interval in a small fixed ring, which
    :class:`CycleTracer` turns into ``gc.pause`` spans.  The interpreter
    runs one collection at a time and no collection while a callback
    runs, so the hook needs no lock; a reader may see the counts of a
    collection one step ahead of its seconds, never a torn value.
    """

    def __init__(self, keep_full: int = 8):
        self.collections = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        #: the last full collections as ``(start, end, collected)`` in
        #: ``perf_counter`` seconds; a fixed-size list written by slot,
        #: so a reader can walk it while the hook replaces an entry
        self.recent_full: list = [None] * max(1, keep_full)
        self._full_seq = 0
        self._t0 = 0.0
        self._installed = False

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        end = time.perf_counter()
        gen = info["generation"]
        self.collections[gen] += 1
        self.seconds[gen] += end - self._t0
        if gen == 2:
            ring = self.recent_full
            ring[self._full_seq % len(ring)] = (
                self._t0, end, info["collected"])
            self._full_seq += 1

    def install(self) -> "GcWatch":
        if not self._installed:
            gc.callbacks.append(self._on_gc)
            self._installed = True
        return self

    def uninstall(self) -> None:
        if self._installed:
            gc.callbacks.remove(self._on_gc)
            self._installed = False

    def read(self) -> tuple:
        """``(collections by generation, seconds by generation)`` since
        the watch was made, as two tuples."""
        return tuple(self.collections), tuple(self.seconds)


@dataclasses.dataclass
class CycleTrace:
    """One completed cycle's span tree — immutable once ringed."""

    cycle_id: int
    #: unix epoch at cycle start — anchors perf_counter offsets so
    #: multiple cycles export onto one consistent timeline
    wall_start: float
    #: the root "cycle" span; the phase spans are its children
    root: Span
    #: the same instant in whole nanoseconds (``time.time_ns``): what a
    #: profiler capture's timestamps are counted in
    wall_start_ns: int = 0
    #: garbage collections that ended inside the cycle, by generation
    #: (zeros where the tracer has no :class:`GcWatch`)
    gc: dict = dataclasses.field(default_factory=lambda: {
        "collections": [0, 0, 0], "pause_seconds": [0.0, 0.0, 0.0]})
    #: ``(name, {series: value})`` samples appended before the cycle
    #: closes — exported as Chrome "C" (counter) events at the cycle's
    #: start timestamp, so per-cycle scalars (kai-wire bytes-on-wire,
    #: device-resident bytes) render as step charts aligned with the
    #: phase lanes
    counters: list = dataclasses.field(default_factory=list)

    def phase_seconds(self) -> dict[str, float]:
        """Top-level (phase) span durations by name.

        Direct children named ``upload`` are promoted to their own
        phase and subtracted from their parent — matching the cycle
        driver's ``CycleResult.phase_seconds`` convention, where the
        snapshotter's transfer-dispatch section is carved out of the
        ``snapshot`` phase.  Without the promotion the trace's
        ``snapshot`` number would disagree with the metric/healthz/
        bench surfaces by exactly the upload duration.
        """
        out: dict[str, float] = {}
        for sp in self.root.children:
            secs = sp.seconds
            up = sum(c.seconds for c in sp.children
                     if c.name == "upload")
            if up:
                out["upload"] = out.get("upload", 0.0) + up
                secs = max(0.0, secs - up)
            out[sp.name] = out.get(sp.name, 0.0) + secs
        return out

    def self_seconds(self) -> dict[str, float]:
        """Self time of every span by its path from the root: the
        ancestors' names and its own joined by ``/`` (span names carry
        dots), the root being ``"cycle"``.  Repeats of one path add up.
        Each instant of the cycle is given to the innermost span that
        covers it, so the values sum to the root's duration."""
        out: dict[str, float] = {}

        def walk(sp: Span, path: str) -> None:
            out[path] = out.get(path, 0.0) + sp.self_seconds()
            for child in sp.children:
                walk(child, f"{path}/{child.name}")

        walk(self.root, self.root.name)
        return out


def _clean_attrs(attrs: dict, extra: dict | None = None) -> dict:
    out = {}
    for k, v in attrs.items():
        out[str(k)] = v if isinstance(v, _JSONABLE) else str(v)
    if extra:
        out.update(extra)
    return out


def _emit_span(events: list, sp: Span, origin_us: float, root_start: float,
               tid: int) -> None:
    """Append one span (and, recursively, its children) as a Chrome
    "X" (complete) event.  ``origin_us`` maps this trace's
    ``perf_counter`` timeline onto the shared wall-anchored export
    timeline."""
    extra = {"device_sync": True} if sp.device_sync else None
    events.append({
        "name": sp.name, "ph": "X", "pid": 0, "tid": tid,
        "ts": round(origin_us + (sp.start - root_start) * 1e6, 3),
        "dur": round(sp.seconds * 1e6, 3),
        "args": _clean_attrs(sp.attrs, extra),
    })
    for child in sp.children:
        _emit_span(events, child, origin_us, root_start, tid)


#: the tracer whose cycle is open on this thread, for code that times
#: work on the cycle's path but is handed no tracer (the compile
#: watcher's process-wide wrapper)
_OPEN = threading.local()


def add_span_to_open_cycle(name: str, start: float, end: float,
                           **attrs) -> None:
    """:meth:`CycleTracer.add_span` on whichever tracer has a cycle
    open on the calling thread; nothing where none has."""
    tracer = getattr(_OPEN, "tracer", None)
    if tracer is not None:
        tracer.add_span(name, start, end, **attrs)


def _attach_pause(host: Span, start: float, end: float,
                  attrs: dict) -> None:
    """Put the interval ``[start, end]`` (inside ``host``) into the
    tree as ``gc.pause`` spans: each part of it goes to the innermost
    span that covers that part, so a pause that straddles a span
    boundary (a collection another thread set off) is cut there and
    self times still partition the cycle."""
    at = start
    for child in sorted(host.children, key=lambda c: c.start):
        lo, hi = max(child.start, start), min(child.end, end)
        if hi <= lo:
            continue
        if lo > at:
            host.children.append(Span("gc.pause", at, lo, dict(attrs)))
        _attach_pause(child, lo, hi, attrs)
        at = hi
    if end > at:
        host.children.append(Span("gc.pause", at, end, dict(attrs)))
    host.children.sort(key=lambda c: c.start)


def _close_gc(trace: CycleTrace, watch: GcWatch, opened: tuple) -> None:
    """Book on a closing cycle what the collector did since it opened:
    counts and seconds by generation, and a ``gc.pause`` span for each
    full collection that overlaps it."""
    root = trace.root
    (n0, s0), (n1, s1) = opened, watch.read()
    trace.gc = {"collections": [b - a for a, b in zip(n0, n1)],
                "pause_seconds": [b - a for a, b in zip(s0, s1)]}
    if n1[2] == n0[2]:
        return
    for full in watch.recent_full:
        if full is None:
            continue
        lo, hi = max(full[0], root.start), min(full[1], root.end)
        if hi > lo:
            _attach_pause(root, lo, hi, {"collected": full[2]})


def span_of(tracer: "CycleTracer | None", name: str, **attrs):
    """``tracer.span(name, ...)`` for code that may have been handed no
    tracer: with ``None`` a context that records nothing."""
    if tracer is None:
        return contextlib.nullcontext(Span(name, 0.0))
    return tracer.span(name, **attrs)


class SpanSections:
    """Consecutive child spans of the open span, for a long
    straight-line function whose sections follow one another: calling
    the object with a name ends the section before and starts the
    next, ``close()`` ends the last.  The caller closes in a
    ``finally``, so an exception leaves the span stack as it found
    it."""

    def __init__(self, tracer: "CycleTracer | None"):
        self._tracer = tracer
        self._open = None

    def __call__(self, name: str) -> None:
        self.close()
        self._open = span_of(self._tracer, name)
        self._open.__enter__()

    def close(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


class CycleTracer:
    """Thread-safe cycle span recorder with a bounded trace ring.

    Recording API (all host-side; never call from jit-traced code —
    KAI061)::

        with tracer.cycle() as trace:            # one scheduling cycle
            with tracer.span("snapshot") as sp:  # a phase
                ...
                sp.attrs["mode"] = "patched"
            with tracer.span("device_wait", device_sync=True):
                host = gather()                  # the blocking transfer
        trace.phase_seconds()                    # {"snapshot": ..., ...}

    ``span`` outside an open cycle records nothing (it yields a
    detached dummy span), so instrumented helpers — e.g. the
    incremental snapshotter's upload section — stay callable from
    benches and CLIs that never open a cycle.
    """

    def __init__(self, retain_cycles: int = 16,
                 gc_watch: GcWatch | None = None):
        #: the process's collection timer, or None: whoever installs
        #: one (``SchedulerServer.start``) hands it over here; a cycle
        #: takes the binding once, as it opens
        self.gc_watch = gc_watch  # kai-race: guarded-by=atomic-swap
        self._lock = threading.Lock()
        self._ring: list[CycleTrace] = []  # kai-race: guarded-by=_lock
        self._cycle_seq = 0  # kai-race: guarded-by=_lock
        #: ring bound — immutable after construction
        self._retain = max(1, int(retain_cycles))
        #: per-thread open-span stack (an open trace is visible only to
        #: the thread recording it; read-only binding after init)
        self._local = threading.local()

    # -- recording --------------------------------------------------------

    @contextlib.contextmanager
    def cycle(self, **attrs):
        """Record one cycle; the trace enters the ring when the block
        exits (never before, so readers cannot observe a live tree)."""
        with self._lock:
            cid = self._cycle_seq
            self._cycle_seq += 1
        watch = self.gc_watch
        gc_open = watch.read() if watch is not None else None
        wall_ns = time.time_ns()
        root = Span(name="cycle", start=time.perf_counter(),
                    attrs=_clean_attrs(attrs))
        trace = CycleTrace(cycle_id=cid, wall_start=wall_ns / 1e9,
                           root=root, wall_start_ns=wall_ns)
        prev = getattr(self._local, "stack", None)
        prev_open = getattr(_OPEN, "tracer", None)
        self._local.stack = [root]
        _OPEN.tracer = self
        try:
            with _profiler.TraceAnnotation(ANNOTATION_PREFIX + root.name):
                yield trace
        finally:
            root.end = time.perf_counter()
            self._local.stack = prev
            _OPEN.tracer = prev_open
            if gc_open is not None:
                _close_gc(trace, watch, gc_open)
            with self._lock:
                self._ring.append(trace)
                del self._ring[:-self._retain]

    @contextlib.contextmanager
    def span(self, name: str, *, device_sync: bool = False, **attrs):
        stack = getattr(self._local, "stack", None)
        if not stack:
            # no open cycle on this thread: detached spans record
            # nothing (the dummy keeps `sp.attrs[...] = ...` callers
            # working unconditionally)
            yield Span(name=name, start=0.0, attrs=_clean_attrs(attrs),
                       device_sync=device_sync)
            return
        sp = Span(name=name, start=time.perf_counter(),
                  attrs=_clean_attrs(attrs), device_sync=device_sync)
        stack[-1].children.append(sp)
        stack.append(sp)
        try:
            with _profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
                yield sp
        finally:
            sp.end = time.perf_counter()
            stack.pop()

    def add_span(self, name: str, start: float, end: float,
                 *, device_sync: bool = False, **attrs) -> None:
        """Attach an already-timed span (``perf_counter`` seconds) as a
        child of the currently open span — for sections timed inside
        helpers that cannot hold a context manager open (e.g. the
        snapshotter's upload loop).  No-op without an open cycle."""
        stack = getattr(self._local, "stack", None)
        if not stack:
            return
        stack[-1].children.append(Span(
            name=name, start=start, end=end, attrs=_clean_attrs(attrs),
            device_sync=device_sync))

    # -- reading ----------------------------------------------------------

    def last(self, n: int = 1) -> list[CycleTrace]:
        """The most recent ``n`` completed cycle traces, oldest first."""
        with self._lock:
            return list(self._ring[-max(1, n):])

    def export_chrome(self, cycles: int | None = None) -> dict:
        """The retained ring (or the last ``cycles``) as a Chrome-trace
        JSON document: ``{"traceEvents": [...]}`` with "X" complete
        events, one ``tid`` lane per cycle so concurrent recorders can
        never interleave into a partially-overlapping (non-nested)
        lane."""
        with self._lock:
            traces = list(self._ring if cycles is None
                          else self._ring[-max(1, cycles):])
        events: list[dict] = [{
            "ph": "M", "name": "process_name", "pid": 0, "tid": 0,
            "args": {"name": "kai-scheduler"},
        }]
        if traces:
            epoch = min(t.wall_start for t in traces)
            for t in traces:
                tid = t.cycle_id
                events.append({
                    "ph": "M", "name": "thread_name", "pid": 0,
                    "tid": tid, "args": {"name": f"cycle-{t.cycle_id}"},
                })
                origin_us = (t.wall_start - epoch) * 1e6
                _emit_span(events, t.root, origin_us, t.root.start, tid)
                for cname, values in t.counters:
                    events.append({
                        "ph": "C", "name": str(cname), "pid": 0,
                        "tid": tid, "ts": round(origin_us, 3),
                        "args": _clean_attrs(dict(values)),
                    })
        return {"traceEvents": events, "displayTimeUnit": "ms"}
