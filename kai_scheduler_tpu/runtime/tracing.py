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

Requests: the server opens a root span ``request`` around every
``POST`` (:meth:`CycleTracer.request`) on the handler's thread, on the
same thread-local stack, and a cycle opened inside it hangs its root
under it while still closing into the cycle ring as the
:class:`CycleTrace` it always was.  Closed requests have a ring of their
own; :meth:`CycleTracer.close_iteration` sums them by path, with what
the collector did inside and between them, for the cycle's ``/healthz``
document.

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

__all__ = ["Span", "CycleTrace", "RequestTrace", "CycleTracer", "GcWatch",
           "SpanSections", "span_of", "add_span_to_open_cycle",
           "annotation"]

#: prefix of every annotation this module writes into a profiler
#: capture; a harness that filters host events by its own names never
#: matches one
ANNOTATION_PREFIX = "kai:"

#: first ``tid`` of the request lanes in :meth:`CycleTracer.export_chrome`
_REQUEST_TID = 1 << 30

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
        if not self.children:
            return self.seconds
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


#: a :meth:`GcWatch.read` of a process that never collected
_NO_READING = ((0, 0, 0), (0.0, 0.0, 0.0))


def _no_gc() -> dict:
    return {"collections": [0, 0, 0], "pause_seconds": [0.0, 0.0, 0.0]}


def _self_seconds(root: Span, whole: tuple = ()) -> dict[str, float]:
    """Self time of every span under ``root`` by its path (names joined
    by ``/``; repeats of one path add up).  A span named in ``whole``
    counts with everything under it and is not walked into."""
    out: dict[str, float] = {}

    def walk(sp: Span, path: str) -> None:
        if sp.name in whole:
            out[path] = out.get(path, 0.0) + sp.seconds
            return
        out[path] = out.get(path, 0.0) + sp.self_seconds()
        for child in sp.children:
            walk(child, f"{path}/{child.name}")

    walk(root, root.name)
    return out


@dataclasses.dataclass
class CycleTrace:
    """One completed cycle's span tree — immutable once ringed."""

    cycle_id: int
    #: the root "cycle" span; the phase spans are its children
    root: Span
    #: unix epoch at cycle start in whole nanoseconds
    #: (``time.time_ns``): anchors perf_counter offsets so several
    #: traces export onto one timeline, and is what a profiler
    #: capture's timestamps are counted in
    wall_start_ns: int = 0
    #: garbage collections that ended inside the cycle, by generation
    #: (zeros where the tracer has no :class:`GcWatch`)
    gc: dict = dataclasses.field(default_factory=_no_gc)
    #: ``(name, {series: value})`` samples appended before the cycle
    #: closes — exported as Chrome "C" (counter) events at the cycle's
    #: start timestamp, so per-cycle scalars (kai-wire bytes-on-wire,
    #: device-resident bytes) render as step charts aligned with the
    #: phase lanes.  For an operator at ``GET /debug/trace`` alone
    #: (README "kai-wire"); no metric reads them
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
        return _self_seconds(self.root)


@dataclasses.dataclass
class RequestTrace:
    """One ``POST`` as the handler's thread served it: the root span
    ``request`` and what lies under it — immutable once ringed."""

    request_id: int
    #: the route, one of the server's own (a key of ``last_cycle.requests``)
    path: str
    root: Span
    wall_start_ns: int = 0
    #: collections that ended inside the request, the nested cycle's
    #: among them
    gc: dict = dataclasses.field(default_factory=_no_gc)
    #: ``(perf_counter seconds, GcWatch reading)`` of the moment
    #: :meth:`CycleTracer.close_iteration` booked this request as it
    #: stood: what follows (the reply's write) belongs to the next
    #: iteration.  None for a request that published nothing
    published: tuple | None = None
    #: the :meth:`GcWatch.read` the request opened at
    gc_opened: tuple = dataclasses.field(default=_NO_READING, repr=False)

    def self_seconds(self) -> dict[str, float]:
        """As :meth:`CycleTrace.self_seconds`, paths from ``request``.
        A nested cycle counts whole, under ``request/cycle``: its own
        trace has its inside."""
        return _self_seconds(self.root, whole=("cycle",))

    def sums(self) -> dict:
        """What the request adds to its path's entry of
        ``last_cycle.requests`` (:meth:`CycleTracer.close_iteration`)."""
        attrs = self.root.attrs
        return {"count": 1, "total_seconds": self.root.seconds,
                "span_self_seconds": self.self_seconds(),
                "gc": {key: list(by_gen) for key, by_gen in self.gc.items()},
                "bytes_in": int(attrs.get("bytes_in", 0)),
                "bytes_out": int(attrs.get("bytes_out", 0))}


def _clean_attrs(attrs: dict, extra: dict | None = None) -> dict:
    out = {}
    for k, v in attrs.items():
        out[str(k)] = v if isinstance(v, _JSONABLE) else str(v)
    if extra:
        out.update(extra)
    return out


def _emit_span(events: list, sp: Span, origin_us: float, root_start: float,
               tid: int, whole: tuple = ()) -> None:
    """Append one span (and, recursively, its children) as a Chrome
    "X" (complete) event.  ``origin_us`` maps this trace's
    ``perf_counter`` timeline onto the shared wall-anchored export
    timeline.  A span named in ``whole`` goes in without its children
    (a request's lane shows its cycle as one box: the cycle has a lane
    of its own)."""
    extra = {"device_sync": True} if sp.device_sync else None
    events.append({
        "name": sp.name, "ph": "X", "pid": 0, "tid": tid,
        "ts": round(origin_us + (sp.start - root_start) * 1e6, 3),
        "dur": round(sp.seconds * 1e6, 3),
        "args": _clean_attrs(sp.attrs, extra),
    })
    if sp.name in whole:
        return
    for child in sp.children:
        _emit_span(events, child, origin_us, root_start, tid, whole)


#: the tracer whose cycle or request is open on this thread, for code
#: that times work on the cycle's path but is handed no tracer (the
#: compile watcher's process-wide wrapper)
_OPEN = threading.local()


def annotation(name: str):
    """A bare profiler annotation ``kai:<name>``, for work on a thread
    that owns no trace (a lane worker's admission): a capture shows it
    on that thread's line; with no capture on it is a flag check."""
    return _profiler.TraceAnnotation(ANNOTATION_PREFIX + name)


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
    self times still partition the trace.  A part that a ``gc.pause``
    already covers stays as it is: a request closes over the cycle
    that booked its own pauses, and over what it booked itself when
    its iteration closed."""
    at = start
    for child in sorted(host.children, key=lambda c: c.start):
        lo, hi = max(child.start, start), min(child.end, end)
        if hi <= lo:
            continue
        if lo > at:
            host.children.append(Span("gc.pause", at, lo, dict(attrs)))
        if child.name != "gc.pause":
            _attach_pause(child, lo, hi, attrs)
        at = max(at, hi)
    if end > at:
        host.children.append(Span("gc.pause", at, end, dict(attrs)))
    host.children.sort(key=lambda c: c.start)


def _gc_between(opened: tuple, closed: tuple) -> dict:
    """What the collector did between two readings of
    :meth:`GcWatch.read`."""
    (n0, s0), (n1, s1) = opened, closed
    return {"collections": [b - a for a, b in zip(n0, n1)],
            "pause_seconds": [b - a for a, b in zip(s0, s1)]}


def _gc_minus(a: dict, b: dict, sign: int = -1) -> dict:
    """``a - b`` (``a + b`` with ``sign`` 1) of two ``gc`` documents."""
    return {key: [x + sign * y for x, y in zip(a[key], b[key])]
            for key in a}


def _close_gc(trace, watch: GcWatch, opened: tuple,
              closed: tuple | None = None) -> None:
    """Book on a closing cycle or request what the collector did since
    it opened (until the reading ``closed``, else until now): counts
    and seconds by generation, and a ``gc.pause`` span for each full
    collection that overlaps the root."""
    root = trace.root
    closed = watch.read() if closed is None else closed
    trace.gc = _gc_between(opened, closed)
    if closed[0][2] == opened[0][2]:
        return
    for full in watch.recent_full:
        if full is None:
            continue
        lo, hi = max(full[0], root.start), min(full[1], root.end)
        if hi > lo:
            _attach_pause(root, lo, hi, {"collected": full[2]})


def _accumulate(into: dict, entry: dict) -> None:
    """``into[k] += entry[k]``, through nested dicts and lists of
    numbers: how requests of one path sum."""
    for key, value in entry.items():
        if isinstance(value, dict):
            _accumulate(into.setdefault(key, {}), value)
        elif isinstance(value, list):
            have = into.setdefault(key, [0] * len(value))
            into[key] = [a + b for a, b in zip(have, value)]
        else:
            into[key] = into.get(key, 0) + value


def span_of(tracer: "CycleTracer | None", name: str, **attrs):
    """``tracer.span(name, ...)`` for code that may have been handed no
    tracer: with ``None`` a context that records nothing."""
    if tracer is None:
        return contextlib.nullcontext(Span(name, 0.0))
    return tracer.span(name, **attrs)


class _OpenSpan:
    """What :meth:`CycleTracer.span` returns.  A class and not a
    generator behind ``contextlib.contextmanager``: an iteration opens
    about a hundred, and this is what each costs."""

    __slots__ = ("_stack", "_span", "_annotation")

    def __init__(self, stack: list | None, span: Span):
        self._stack = stack
        self._span = span

    def __enter__(self) -> Span:
        sp, stack = self._span, self._stack
        if stack:
            sp.start = time.perf_counter()
            stack[-1].children.append(sp)
            stack.append(sp)
            self._annotation = _profiler.TraceAnnotation(
                ANNOTATION_PREFIX + sp.name)
            self._annotation.__enter__()
        return sp

    def __exit__(self, exc_type, exc, tb) -> bool:
        stack = self._stack
        if stack:
            self._annotation.__exit__(exc_type, exc, tb)
            self._span.end = time.perf_counter()
            stack.pop()
        return False


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

    ``span`` outside an open cycle or request records nothing (it
    yields a detached dummy span), so instrumented helpers — e.g. the
    incremental snapshotter's upload section — stay callable from
    benches and CLIs that never open a cycle.

    A server's handler wraps the same in a request (``docs/TRACING.md``
    has the tree)::

        with tracer.request("/cycle/stored", start=handed_over) as req:
            with tracer.span("coalesce"): ...
            with tracer.cycle() as trace: ...     # request/cycle
            with tracer.span("record"):
                doc = tracer.close_iteration(req, trace.gc)
    """

    def __init__(self, retain_cycles: int = 16,
                 gc_watch: GcWatch | None = None):
        #: the process's collection timer, or None: whoever installs
        #: one (``SchedulerServer.start``) hands it over here; a cycle
        #: or request takes the binding once, as it opens
        self.gc_watch = gc_watch  # kai-race: guarded-by=atomic-swap
        self._lock = threading.Lock()
        self._ring: list[CycleTrace] = []  # kai-race: guarded-by=_lock
        self._cycle_seq = 0  # kai-race: guarded-by=_lock
        #: ring bound — immutable after construction
        self._retain = max(1, int(retain_cycles))
        #: closed requests, as many as the cycle ring's iterations made
        #: (the two churn POSTs and the cycle's own)
        self._requests: list[RequestTrace] = []  # kai-race: guarded-by=_lock
        #: (the three below: handler threads alone, under ``_lock`` too)
        self._request_seq = 0
        #: ``{path: sums}`` of the requests closed since the last
        #: :meth:`close_iteration`, and the collector's reading there
        self._since: dict = {}
        self._gc_published = _NO_READING
        #: per-thread open-span stack (an open trace is visible only to
        #: the thread recording it; read-only binding after init)
        self._local = threading.local()

    # -- recording --------------------------------------------------------

    @contextlib.contextmanager
    def cycle(self, **attrs):
        """Record one cycle; the trace enters the ring when the block
        exits (never before, so readers cannot observe a live tree).
        Inside an open :meth:`request` the cycle's root also hangs
        under the request's open span."""
        with self._lock:
            cid = self._cycle_seq
            self._cycle_seq += 1
        watch = self.gc_watch
        gc_open = watch.read() if watch is not None else None
        wall_ns = time.time_ns()
        root = Span(name="cycle", start=time.perf_counter(),
                    attrs=_clean_attrs(attrs))
        trace = CycleTrace(cycle_id=cid, root=root, wall_start_ns=wall_ns)
        prev = getattr(self._local, "stack", None)
        prev_open = getattr(_OPEN, "tracer", None)
        if prev:
            prev[-1].children.append(root)
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
    def request(self, path: str, *, start: float | None = None, **attrs):
        """Record one request on the calling (handler) thread: a root
        span ``request`` with the attribute ``path``, under which
        :meth:`span` and :meth:`cycle` nest until the block exits.
        ``start`` (``perf_counter`` seconds) dates the root back to
        when the request was handed to this thread; the time until now
        becomes the child ``accept_wait``.  The closed trace enters the
        request ring and the sums that :meth:`close_iteration` takes."""
        with self._lock:
            rid = self._request_seq
            self._request_seq += 1
        watch = self.gc_watch
        gc_open = watch.read() if watch is not None else _NO_READING
        now = time.perf_counter()
        start = now if start is None else min(start, now)
        root = Span(name="request", start=start,
                    attrs=_clean_attrs(attrs, {"path": path}))
        if start < now:
            root.children.append(Span("accept_wait", start, now))
        trace = RequestTrace(
            request_id=rid, path=path, root=root,
            wall_start_ns=time.time_ns() - int((now - start) * 1e9),
            gc_opened=gc_open)
        prev = getattr(self._local, "stack", None)
        prev_open = getattr(_OPEN, "tracer", None)
        self._local.stack = [root]
        _OPEN.tracer = self
        try:
            with _profiler.TraceAnnotation(
                    f"{ANNOTATION_PREFIX}request:{path}"):
                yield trace
        finally:
            root.end = time.perf_counter()
            self._local.stack = prev
            _OPEN.tracer = prev_open
            closed = watch.read() if watch is not None else _NO_READING
            if watch is not None:
                _close_gc(trace, watch, gc_open, closed)
            if trace.published is None:
                entry = trace.sums()
            else:
                # booked as it stood when its iteration closed: the
                # rest is the next iteration's, under its own key
                at, reading = trace.published
                entry = {"previous_reply_write_seconds": root.end - at,
                         "gc": _gc_between(reading, closed)}
            with self._lock:
                self._requests.append(trace)
                del self._requests[:-3 * self._retain]
                # a closed loop has one request a path between two
                # iterations: its entry is the sum
                if path in self._since:
                    _accumulate(self._since[path], entry)
                else:
                    self._since[path] = entry

    def close_iteration(self, request: RequestTrace,
                        cycle_gc: dict | None = None) -> dict:
        """What the requests did since the last call, for the cycle's
        ``/healthz`` document.  Called on the thread that has
        ``request`` open, once its reply is encoded and before it is
        written: the document has to be whole before the reply leaves.

        ``requests``: ``{path: sums}`` of the requests that closed since
        the last call and of ``request`` as it stands now (``count``,
        ``total_seconds``, ``span_self_seconds`` from ``request/``,
        ``gc``, ``bytes_in``, ``bytes_out``); what ``request`` still
        does after this is booked when it closes, in the next call's
        sums, as ``previous_reply_write_seconds``.

        ``gc_iteration``: what the collector did since the last call
        (the :class:`GcWatch` totals' difference), in three disjoint
        parts: ``in_cycle`` (``cycle_gc``, the nested cycle's own ``gc``;
        nothing where the cycle left no trace),
        ``in_requests`` (inside those requests, outside the cycle) and
        ``between_requests`` (the rest: no request was open).  Requests
        that overlap on two threads each book a collection that falls
        in both, and ``between_requests`` then comes out short by it.
        """
        stack = self._local.stack
        watch = self.gc_watch
        cycle_gc = cycle_gc or _no_gc()
        # as it stands: the open spans (the root, and the span this is
        # called under) end now; closing them for good overwrites it
        now = time.perf_counter()
        for sp in stack:
            sp.end = now
        reading = watch.read() if watch is not None else _NO_READING
        if watch is not None:
            _close_gc(request, watch, request.gc_opened, reading)
        entry = request.sums()
        with self._lock:
            sums, self._since = self._since, {}
            last, self._gc_published = self._gc_published, reading
        mine = sums.setdefault(request.path, entry)
        if mine is not entry:
            _accumulate(mine, entry)
        inside = _no_gc()
        for path_sums in sums.values():
            inside = _gc_minus(inside, path_sums["gc"], sign=1)
        gc_iteration = {
            "in_cycle": cycle_gc,
            "in_requests": _gc_minus(inside, cycle_gc),
            "between_requests": _gc_minus(_gc_between(last, reading),
                                          inside)}
        # the sums above are the innermost open span's own work: booked
        # here, or the document would leave out what making it cost
        done = time.perf_counter()
        request.published = (done, reading)
        innermost = "/".join(sp.name for sp in stack)
        selfs = mine["span_self_seconds"]
        selfs[innermost] = selfs.get(innermost, 0.0) + done - now
        mine["total_seconds"] += done - now
        return {"requests": sums, "gc_iteration": gc_iteration}

    def span(self, name: str, *, device_sync: bool = False, **attrs):
        """A context manager that records ``name`` as a child of the
        open span and yields its :class:`Span`.  With no open cycle or
        request on this thread it records nothing (the yielded dummy
        keeps ``sp.attrs[...] = ...`` callers working
        unconditionally)."""
        return _OpenSpan(getattr(self._local, "stack", None),
                         Span(name=name, start=0.0,
                              attrs=_clean_attrs(attrs),
                              device_sync=device_sync))

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

    def last_requests(self, n: int = 1) -> list[RequestTrace]:
        """The most recent ``n`` closed requests, oldest first."""
        with self._lock:
            return list(self._requests[-max(1, n):])

    def export_chrome(self, cycles: int | None = None) -> dict:
        """The retained rings (or the last ``cycles`` cycles and three
        requests for each) as a Chrome-trace JSON document:
        ``{"traceEvents": [...]}`` with "X" complete events, one
        ``tid`` lane per cycle and per request so concurrent recorders
        can never interleave into a partially-overlapping (non-nested)
        lane."""
        with self._lock:
            traces = list(self._ring if cycles is None
                          else self._ring[-max(1, cycles):])
            requests = list(self._requests if cycles is None
                            else self._requests[-3 * max(1, cycles):])
        events: list[dict] = [{
            "ph": "M", "name": "process_name", "pid": 0, "tid": 0,
            "args": {"name": "kai-scheduler"},
        }]
        if not (traces or requests):
            return {"traceEvents": events, "displayTimeUnit": "ms"}
        epoch_ns = min(t.wall_start_ns for t in traces + requests)

        def lane(tid: int, name: str, t, whole: tuple = ()) -> float:
            events.append({
                "ph": "M", "name": "thread_name", "pid": 0,
                "tid": tid, "args": {"name": name}})
            origin_us = (t.wall_start_ns - epoch_ns) / 1e3
            _emit_span(events, t.root, origin_us, t.root.start, tid, whole)
            return origin_us

        for t in traces:
            origin_us = lane(t.cycle_id, f"cycle-{t.cycle_id}", t)
            for cname, values in t.counters:
                events.append({
                    "ph": "C", "name": str(cname), "pid": 0,
                    "tid": t.cycle_id, "ts": round(origin_us, 3),
                    "args": _clean_attrs(dict(values)),
                })
        for r in requests:
            # lanes of their own, clear of every cycle id
            lane(_REQUEST_TID + r.request_id,
                 f"request-{r.request_id} {r.path}", r, whole=("cycle",))
        return {"traceEvents": events, "displayTimeUnit": "ms"}
