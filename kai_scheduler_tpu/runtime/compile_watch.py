"""kai-wire's compile half — jit cache-miss attribution.

Recompiles are the other way the host↔device link silently eats a
cycle: a drifting abstract signature (a padded dim that crossed a
bucket, an unstable static config) turns "one dispatch per cycle" into
seconds of XLA compile, and nothing in the repo could say *which entry*
recompiled or *why*.  The jaxpr probe (``analysis/trace_probe.py``)
asserts two equivalent builds share one compile at canonical shapes —
a CI property; this module is the production counterpart: a
:class:`CompileWatcher` wrapping the package's jit entry points (the
same entries the analysis call graph enumerates) that attributes every
cache miss to its ``(entry, abstract-shape-signature)`` pair, times it,
and raises a **recompile-storm alarm** when one entry misses repeatedly
inside a sliding window (the padded-capacity-oscillation failure mode:
a cluster whose entity counts straddle a bucket boundary recompiles
every other cycle).

Mechanics: the watcher models jax's cache key — the pytree structure of
``(args, kwargs)`` with array leaves abstracted to ``(shape, dtype)``
and non-array leaves (static configs) to their ``repr`` — and treats
the first call per unseen signature as the compile.  The fused
five-action entry (``fused_pipeline``) is the one the steady-state
cycle lives on: the snapshot's padded axes are kept across rebuilds
precisely so this watcher sees ONE signature per capacity — a storm
there means the padding broke, and the alarm below is the tripwire.
The model is checked against jax itself where possible: wrappers
forward the underlying ``_cache_size`` probe, which the trace probe's
compile-once assertion continues to consume.

The wrapper is HOST-side and adds ~tens of microseconds per call
(one ``tree_flatten`` + tuple build) — never traced, zero new
primitives in any jit region (the jaxpr probe baseline is unchanged).

A miss inside an open cycle is also a ``compile:<entry>`` span of that
cycle's trace (``runtime/tracing.py``), so a recompiling cycle shows
where.  ``listen()`` adds JAX's own account of a compile, by stage
(trace, lower, backend compile, cache load), from ``jax.monitoring``'s
duration events: ``stage_seconds()``, served under ``/healthz``
``last_cycle.startup``.

Surfaces: ``kai_compile_*`` registry metrics, the ``compile`` section
of ``GET /debug/wire``, and per-event docs in a bounded ring.
Concurrency: all watcher state is accessed under ``_lock`` (declared
in ``analysis/guarded_by.json``); events ring as immutable dicts.
"""
from __future__ import annotations

import functools
import threading
import time
import zlib

import jax
import jax.monitoring

from . import tracing

__all__ = ["CompileWatcher", "WATCHER", "watch"]

#: ``jax.monitoring`` duration event -> key of ``stage_seconds()``.
#: JAX books the load of a cached executable under the backend-compile
#: event too, so ``cache_load_s`` is a part of ``backend_compile_s``.
_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}


def _signature(args, kwargs) -> tuple:
    """The abstract signature jax's jit cache keys on, modeled: tree
    structure + per-leaf ``(shape, dtype)`` for arrays, ``repr`` for
    static leaves (configs, ints, strings)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        (args, dict(sorted(kwargs.items()))))
    parts = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(("a", tuple(shape), str(dtype)))
        else:
            parts.append(("s", repr(leaf)))
    return (str(treedef), tuple(parts))


def _render_signature(sig: tuple) -> str:
    """Compact human-readable form: digest + the dominant array shapes
    (full signatures are hundreds of tokens; the doc needs a label)."""
    digest = f"{zlib.crc32(repr(sig).encode()):08x}"
    counts: dict[str, int] = {}
    for part in sig[1]:
        if part[0] == "a":
            key = f"{part[2]}[{','.join(str(d) for d in part[1])}]"
            counts[key] = counts.get(key, 0) + 1
    top = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))[:3]
    shapes = ", ".join(f"{k}×{n}" if n > 1 else k for k, n in top)
    return f"sig-{digest}" + (f" ({shapes}, …)" if shapes else "")


class CompileWatcher:
    """Attributes jit cache misses to ``(entry, signature)`` pairs."""

    def __init__(self, retain_events: int = 256,
                 storm_threshold: int = 3,
                 storm_window_s: float = 300.0):
        self._lock = threading.Lock()
        #: entry -> set of seen signatures
        self._seen: dict[str, set] = {}
        #: entry -> {"misses": n, "seconds": s, "calls": n}
        self._stats: dict[str, dict] = {}
        #: bounded ring of immutable miss-event docs, oldest first
        self._events: list[dict] = []
        #: entry -> recent miss monotonic stamps (storm detection)
        self._miss_times: dict[str, list] = {}
        self._alarms = 0
        #: seconds by compile stage since ``listen()``
        self._stage_s = dict.fromkeys(_STAGE_EVENTS.values(), 0.0)
        self._listening = False
        #: bounds — immutable after construction
        self._retain = max(1, int(retain_events))
        self.storm_threshold = max(2, int(storm_threshold))
        self.storm_window_s = float(storm_window_s)

    # -- wrapping ----------------------------------------------------------

    def wrap(self, entry: str, fn):
        """Wrap a jitted callable; every call classifies its abstract
        signature, and a first-seen signature is recorded as the
        entry's compile (timed around the dispatch, which on a miss is
        dominated by trace + XLA compile).  ``_cache_size`` and
        ``__wrapped__`` forward to the underlying jit object / raw
        function so the trace probe's compile-once assertion keeps
        working through the wrapper."""
        with self._lock:
            self._seen.setdefault(entry, set())
            self._stats.setdefault(
                entry, {"misses": 0, "seconds": 0.0, "calls": 0})

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            sig = _signature(args, kwargs)
            if not self._observe_call(entry, sig):
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            t1 = time.perf_counter()
            self._observe_miss(entry, sig, t1 - t0)
            tracing.add_span_to_open_cycle(
                f"compile:{entry}", t0, t1,
                signature=_render_signature(sig))
            return out

        # the raw python function, one hop past the jit object (jax's
        # own functools.wraps chain) — what make_jaxpr consumers want
        wrapped.__wrapped__ = getattr(fn, "__wrapped__", fn)
        cache_probe = getattr(fn, "_cache_size", None)
        if cache_probe is not None:
            wrapped._cache_size = cache_probe
        wrapped.__kai_entry__ = entry
        wrapped.__kai_jit__ = fn
        return wrapped

    def _observe_call(self, entry: str, sig: tuple) -> bool:
        """Register the call; True when the signature is new (a
        presumed cache miss — the caller times the dispatch)."""
        with self._lock:
            self._stats[entry]["calls"] += 1
            seen = self._seen[entry]
            if sig in seen:
                return False
            seen.add(sig)
            return True

    def _observe_miss(self, entry: str, sig: tuple,
                      seconds: float) -> None:
        now = time.monotonic()
        with self._lock:
            stamps = self._miss_times.setdefault(entry, [])
            stamps.append(now)
            cutoff = now - self.storm_window_s
            while stamps and stamps[0] < cutoff:
                stamps.pop(0)
            storm = len(stamps) >= self.storm_threshold
            if storm:
                self._alarms += 1
            st = self._stats[entry]
            st["misses"] += 1
            st["seconds"] += seconds
            self._events.append({
                "entry": entry,
                "signature": _render_signature(sig),
                "seconds": round(seconds, 6),
                "storm": storm,
                "wall": time.time(),
            })
            del self._events[:-self._retain]
        self._export_metrics(entry, seconds, storm)

    def _export_metrics(self, entry, seconds, storm) -> None:
        try:
            # package-relative cycle-breaker (see runtime/profiling.py):
            # ops/framework modules wrap their entries at import time,
            # so the registry import must stay lazy
            from ..framework import metrics
        except Exception:  # noqa: BLE001 — a metrics mirror must never
            return         # fail a dispatch (the watcher ring stands)
        metrics.compile_cache_misses.inc(entry)
        metrics.compile_seconds.inc(entry, by=float(seconds))
        if storm:
            metrics.compile_storm_alarms.inc(entry)

    # -- JAX's own stage clock ---------------------------------------------

    def listen(self) -> None:
        """Start summing JAX's compile-stage durations.  Once per
        watcher: ``jax.monitoring`` keeps a listener for the life of the
        process."""
        with self._lock:
            if self._listening:
                return
            self._listening = True
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_duration(self, name: str, secs: float, **_) -> None:
        key = _STAGE_EVENTS.get(name)
        if key is not None:
            with self._lock:
                self._stage_s[key] += secs

    def stage_seconds(self) -> dict[str, float]:
        """Cumulative seconds by compile stage since ``listen()``."""
        with self._lock:
            return dict(self._stage_s)

    # -- reading -----------------------------------------------------------

    def entries(self) -> list[str]:
        with self._lock:
            return sorted(self._seen)

    def events(self, n: int | None = None) -> list[dict]:
        """Recent miss events, oldest first (immutable docs)."""
        with self._lock:
            evs = self._events if n is None else self._events[-max(1, n):]
            return [dict(e) for e in evs]

    def report(self) -> dict:
        """The ``compile`` section of ``GET /debug/wire``."""
        with self._lock:
            entries = {
                name: {"signatures": len(self._seen[name]),
                       "misses": st["misses"], "calls": st["calls"],
                       "seconds": round(st["seconds"], 6)}
                for name, st in sorted(self._stats.items())}
            events = [dict(e) for e in self._events]
            alarms = self._alarms
        return {"entries": entries, "events": events, "alarms": alarms,
                "storm_threshold": self.storm_threshold,
                "storm_window_s": self.storm_window_s}


#: the process-global watcher the package's jit entry points wrap with
WATCHER = CompileWatcher()


def watch(entry: str, fn):
    """Hook one jit entry point into the global watcher — the one-line
    idiom the entry-point modules use at module scope::

        allocate_jit = compile_watch.watch("allocate", allocate_jit)
    """
    return WATCHER.wrap(entry, fn)
