"""kai-wire — the host↔device transfer ledger.

A steady cycle should move only what changed between host and device;
the acceptance bar is "a multi-cycle soak that never re-uploads an
unchanged leaf" — a claim
the phase tracer (``runtime/tracing.py``) cannot adjudicate: it times
the ``upload`` phase but cannot say *which leaves, how many bytes, or
why*.  This module is the evidence layer: a :class:`TransferLedger`
that is the package's single **mandatory choke point** for every
``jax.device_put`` (kai-lint rule ``KAI071`` forbids the raw call
anywhere else), recording per-cycle, per-leaf upload events — leaf
name, nbytes, dtype/shape, content fingerprint, and a *reason*:

* ``full-build``     — ``build_snapshot``'s one-shot snapshot transfer;
* ``journal-patch``  — the incremental snapshotter's changed-leaves
  ship (``state/incremental.py``), batched into ONE dispatch;
* ``fallback``       — the incremental engine rebuilt in full (cold
  start, structural change, feature pods, dirty-threshold, ...);
* ``verify``         — the patched==fresh verifier's reference rebuild;
* ``mesh-shard``     — ``parallel/mesh.shard_state`` mesh placement.

Three derived surfaces ride the ledger:

* a **redundancy detector**: every upload is fingerprinted (full-buffer
  ``zlib.crc32`` + nbytes/dtype/shape) against the last upload of the
  same ``(site, leaf)`` key, and re-uploaded-*identical* bytes are
  counted per reason — the invariant the patch path holds at zero;
* a **device-residency gauge**: the ledger-known resident set (last
  upload per leaf key) as live buffer count / bytes plus a per-cycle
  peak watermark;
* per-cycle summaries in a bounded ring (``GET /debug/wire``, the
  ``/healthz`` wire slice, ``CycleResult.wire``, Chrome-trace counter
  lanes) and cumulative ``kai_wire_*`` registry metrics.

Accounting honesty: the ledger sees *dispatches*, not the allocator —
"resident" means "the latest buffer uploaded through the ledger for
this leaf key", which matches reality as long as snapshots rebind their
leaves (they do: the snapshotter swaps whole pytrees).  Leaves that are
not host ``numpy`` arrays (e.g. already-on-device arrays headed to a
mesh layout) are counted by size but not fingerprinted — hashing them
would itself force a device→host transfer; ``unfingerprinted_bytes``
reports the blind spot instead of pretending.

Concurrency model (disciplines declared in ``analysis/guarded_by.json``,
checked by kai-race): event recording happens on whichever thread
dispatches the transfer (cycle thread, HTTP cycle handlers), cycle
roll-over on the cycle thread, and readers (``/debug/wire`` handler
threads) take consistent copies — every access to ledger state holds
``_lock``, ring entries are immutable once rolled, and the
``jax.device_put`` dispatch itself runs *outside* the lock so a slow
transfer never stalls a concurrent scrape.
"""
from __future__ import annotations

import contextlib
import threading
import time
import zlib

import jax
import numpy as np

__all__ = [
    "TransferLedger", "LEDGER", "REASON_FULL_BUILD",
    "REASON_JOURNAL_PATCH", "REASON_FALLBACK",
    "REASON_VERIFY", "REASON_MESH_SHARD",
]

REASON_FULL_BUILD = "full-build"
REASON_JOURNAL_PATCH = "journal-patch"
REASON_FALLBACK = "fallback"
REASON_VERIFY = "verify"
REASON_MESH_SHARD = "mesh-shard"

#: leaves larger than this are size-counted but not fingerprinted —
#: crc32 runs ~0.5 GB/s, and the ledger must never turn a huge upload
#: into a hashing stall.  Far above every leaf of the 10k×50k headline
#: snapshot, so in practice everything is fingerprinted exactly.
_FINGERPRINT_LIMIT_BYTES = 64 * 1024 * 1024

_TOTAL_FIELDS = ("leaves", "bytes", "redundant_leaves",
                 "redundant_bytes", "dispatches",
                 "unfingerprinted_bytes")


def _fingerprint(leaf, limit: int) -> tuple | None:
    """Content fingerprint of a host array: full-buffer crc32 qualified
    by nbytes/dtype/shape (a crc collision alone cannot fake identity
    across different geometry).  None for non-numpy leaves and
    over-limit buffers — those are never counted redundant."""
    if not isinstance(leaf, np.ndarray) or leaf.nbytes > limit:
        return None
    arr = np.ascontiguousarray(leaf)
    if arr.nbytes == 0:
        crc = 0
    else:
        try:
            crc = zlib.crc32(memoryview(arr).cast("B"))
        except (TypeError, ValueError):
            # 0-d and zero-stride views refuse the flat cast
            crc = zlib.crc32(arr.tobytes())
    return (crc, int(arr.nbytes), str(arr.dtype), tuple(arr.shape))


def _leaf_doc(name: str, leaf, reason: str, site: str,
              redundant: bool) -> dict:
    shape = getattr(leaf, "shape", None)
    return {
        "leaf": name,
        "nbytes": int(getattr(leaf, "nbytes", 0)),
        "dtype": str(getattr(leaf, "dtype", type(leaf).__name__)),
        "shape": list(shape) if shape is not None else [],
        "reason": reason,
        "site": site,
        "redundant": bool(redundant),
    }


class TransferLedger:
    """Per-cycle, per-leaf host→device upload accounting.

    One process-global instance (:data:`LEDGER`) serves the whole
    package: the ledger is a property of the *wire*, not of any one
    scheduler, so every dispatch in the process is on the books
    (including ``profile_cycle``'s synthetic cycles — exactly like the
    metrics registry).  Uploads between cycle rolls accumulate in an
    open window; :meth:`roll_cycle` closes the window into an immutable
    ring entry and returns the cycle summary.
    """

    def __init__(self, retain_cycles: int = 32,
                 max_events_per_cycle: int = 512,
                 fingerprint_limit_bytes: int = _FINGERPRINT_LIMIT_BYTES):
        self._lock = threading.Lock()
        #: immutable per-cycle documents, oldest first
        self._ring: list[dict] = []
        #: open-window bounded event docs (the per-cycle detail)
        self._window_events: list[dict] = []
        self._window_dropped = 0
        #: open-window aggregates by reason — kept separately from the
        #: bounded event list so dropped events still count their bytes
        self._window_totals: dict[str, dict] = {}
        self._window_peak = 0
        #: (site, leaf) -> (fingerprint, nbytes): the ledger-known
        #: device-resident set (last upload per leaf key)
        self._resident: dict[tuple[str, str], tuple] = {}
        self._resident_bytes = 0
        #: resident keys (re)uploaded in the open window — at roll
        #: time, resident bytes NOT in this set were *reused* on device
        #: without touching the wire
        self._window_uploaded_keys: set[tuple[str, str]] = set()
        #: cumulative per-reason aggregates since process start
        self._totals: dict[str, dict] = {}
        #: ring/event bounds + fingerprint limit — immutable after init
        self._retain = max(1, int(retain_cycles))
        self.max_events_per_cycle = max(1, int(max_events_per_cycle))
        self.fingerprint_limit_bytes = int(fingerprint_limit_bytes)
        #: per-thread reason override (see :meth:`override_reason`);
        #: read-only binding after init
        self._local = threading.local()

    # -- recording ---------------------------------------------------------

    @contextlib.contextmanager
    def override_reason(self, reason: str):
        """Re-label transfers dispatched inside the block — the
        incremental snapshotter wraps ``build_snapshot`` with this so a
        full rebuild it *fell back* to is distinguishable from a
        deliberate one (and the verifier's reference rebuild from
        both)."""
        prev = getattr(self._local, "reason", None)
        self._local.reason = reason
        try:
            yield
        finally:
            self._local.reason = prev

    def device_put(self, tree, sharding=None, *, reason: str,
                   site: str = "snapshot", replace_site: bool = False,
                   leaf_names: list[str] | None = None):
        """THE package choke point for ``jax.device_put`` (KAI071).

        Dispatches the whole ``tree`` in ONE ``jax.device_put`` call
        (per-leaf transfers cost a dispatch each — see
        ``cluster_state.py``) and records one event per leaf.
        ``sharding`` passes through untouched.  ``replace_site=True``
        declares the upload supersedes the site's entire resident set
        (a full snapshot rebuild drops the previous snapshot's
        buffers); the default accumulates (a patch replaces only the
        leaves it ships).  ``leaf_names`` overrides the derived
        ``jax.tree_util.keystr`` names — the batched patch path ships a
        ``{keystr: leaf}`` dict and passes the original names so
        redundancy tracking keys identically across full builds and
        patches.  Names must follow the tree's FLATTEN order (jax
        flattens dict keys SORTED, not in insertion order).
        """
        override = getattr(self._local, "reason", None)
        if override is not None:
            reason = override
        leaves_p, _ = jax.tree_util.tree_flatten_with_path(tree)
        if not leaves_p:
            return tree
        t0 = time.perf_counter()
        out = (jax.device_put(tree) if sharding is None
               else jax.device_put(tree, sharding))
        dispatch_s = time.perf_counter() - t0
        if leaf_names is not None and len(leaf_names) != len(leaves_p):
            raise ValueError(
                f"leaf_names has {len(leaf_names)} entries for "
                f"{len(leaves_p)} leaves")
        limit = self.fingerprint_limit_bytes
        staged = []  # (name, leaf, nbytes, fingerprint)
        for i, (path, leaf) in enumerate(leaves_p):
            name = (leaf_names[i] if leaf_names is not None
                    else jax.tree_util.keystr(path) or f"[{i}]")
            staged.append((name, leaf, int(getattr(leaf, "nbytes", 0)),
                           _fingerprint(leaf, limit)))
        agg = dict.fromkeys(_TOTAL_FIELDS, 0)
        agg["dispatches"] = 1
        with self._lock:
            # replace_site: leaves of this site NOT re-uploaded by this
            # dispatch are superseded and leave the resident set — but
            # only AFTER the per-leaf compares, so a full rebuild that
            # re-ships identical bytes is still caught red-handed
            stale = ({k for k in self._resident if k[0] == site}
                     if replace_site else None)
            for name, leaf, nbytes, fp in staged:
                key = (site, name)
                if stale is not None:
                    stale.discard(key)
                prev = self._resident.get(key)
                redundant = (fp is not None and prev is not None
                             and prev[0] == fp)
                self._resident_bytes += nbytes - (
                    prev[1] if prev is not None else 0)
                self._resident[key] = (fp, nbytes)
                self._window_uploaded_keys.add(key)
                agg["leaves"] += 1
                agg["bytes"] += nbytes
                if redundant:
                    agg["redundant_leaves"] += 1
                    agg["redundant_bytes"] += nbytes
                if fp is None:
                    agg["unfingerprinted_bytes"] += nbytes
                if len(self._window_events) < self.max_events_per_cycle:
                    self._window_events.append(
                        _leaf_doc(name, leaf, reason, site, redundant))
                else:
                    self._window_dropped += 1
            for key in sorted(stale or ()):
                self._resident_bytes -= self._resident.pop(key)[1]
                self._window_uploaded_keys.discard(key)
            self._window_peak = max(self._window_peak,
                                    self._resident_bytes)
            for dst in (self._window_totals.setdefault(
                            reason, dict.fromkeys(_TOTAL_FIELDS, 0)),
                        self._totals.setdefault(
                            reason, dict.fromkeys(_TOTAL_FIELDS, 0))):
                for field in _TOTAL_FIELDS:
                    dst[field] += agg[field]
            resident_bytes = self._resident_bytes
            resident_buffers = len(self._resident)
        self._export_metrics(reason, agg, resident_bytes,
                             resident_buffers, dispatch_s)
        return out

    def _export_metrics(self, reason, agg, resident_bytes,
                        resident_buffers, dispatch_s) -> None:
        """Mirror one dispatch into the ``kai_wire_*`` registry metrics
        (outside ``_lock``; each metric takes its own)."""
        try:
            # package-relative cycle-breaker: framework pulls this
            # module through state/cluster_state at import time, so the
            # registry import must stay lazy (same idiom as
            # runtime/profiling.py)
            from ..framework import metrics
        except Exception:  # noqa: BLE001 — a metrics mirror must never
            return         # fail a transfer (the ledger itself stands)
        metrics.wire_uploaded_bytes.inc(reason, by=float(agg["bytes"]))
        metrics.wire_uploaded_leaves.inc(reason, by=float(agg["leaves"]))
        metrics.wire_dispatches.inc(reason, by=float(agg["dispatches"]))
        metrics.wire_redundant_bytes.inc(
            reason, by=float(agg["redundant_bytes"]))
        metrics.wire_dispatch_seconds.inc(reason, by=float(dispatch_s))
        metrics.wire_resident_bytes.set(value=float(resident_bytes))
        metrics.wire_resident_buffers.set(value=float(resident_buffers))

    def roll_cycle(self, cycle_id: int) -> dict:
        """Close the open window into an immutable ring entry and
        return the cycle summary (``CycleResult.wire``).  Called by the
        cycle driver at the end of every ``run_once``; uploads from
        harnesses that never roll (bench refreshes, CLIs) simply land
        in the next rolled window."""
        with self._lock:
            by_reason = {r: dict(t)
                         for r, t in sorted(self._window_totals.items())}
            events = tuple(self._window_events)
            dropped = self._window_dropped
            peak = max(self._window_peak, self._resident_bytes)
            # resident bytes that stayed on device this cycle without
            # touching the wire, vs bytes actually uploaded: a patched
            # cycle reads reused ≈ snapshot size less the changed leaves
            reused = sum(
                ent[1] for key, ent in self._resident.items()
                if key not in self._window_uploaded_keys)
            self._window_events = []
            self._window_dropped = 0
            self._window_totals = {}
            self._window_uploaded_keys = set()
            self._window_peak = self._resident_bytes
            resident_bytes = self._resident_bytes
            resident_buffers = len(self._resident)
            summary = {
                "cycle": int(cycle_id),
                "by_reason": by_reason,
                "dropped": dropped,
                "resident_bytes": resident_bytes,
                "resident_buffers": resident_buffers,
                "peak_resident_bytes": peak,
                "resident_reused_bytes": reused,
            }
            for field in _TOTAL_FIELDS:
                summary[field] = sum(t[field] for t in by_reason.values())
            summary["resident_uploaded_bytes"] = summary["bytes"]
            entry = dict(summary)
            entry["events"] = events
            self._ring.append(entry)
            del self._ring[:-self._retain]
        self._export_cycle_metrics(summary)
        return summary

    def _export_cycle_metrics(self, summary) -> None:
        try:
            from ..framework import metrics  # package-relative, lazy
        except Exception:  # noqa: BLE001
            return
        metrics.wire_cycle_uploaded_bytes.observe(
            value=float(summary["bytes"]))
        # reused-on-device vs uploaded bytes per cycle
        metrics.wire_resident_reused_bytes.set(
            value=float(summary["resident_reused_bytes"]))
        metrics.wire_resident_uploaded_bytes.set(
            value=float(summary["resident_uploaded_bytes"]))

    # -- reading -----------------------------------------------------------

    def totals(self) -> dict:
        """Cumulative per-reason aggregates since process start — the
        bench's wire-bytes-per-cycle columns are deltas of this."""
        with self._lock:
            return {"by_reason": {r: dict(t) for r, t
                                  in sorted(self._totals.items())},
                    "resident_bytes": self._resident_bytes,
                    "resident_buffers": len(self._resident)}

    def residency(self) -> dict:
        with self._lock:
            return {"buffers": len(self._resident),
                    "bytes": self._resident_bytes,
                    "peak_bytes": max(self._window_peak,
                                      self._resident_bytes)}

    def last(self, n: int = 1) -> list[dict]:
        """The most recent ``n`` rolled cycle documents, oldest first
        (immutable — events are tuples of per-leaf docs)."""
        with self._lock:
            return list(self._ring[-max(1, n):])

    def wire_doc(self, cycles: int | None = None) -> dict:
        """The ``GET /debug/wire`` document: rolled cycle ring (bounded
        by ``?cycles=``), the open window's partial aggregates, the
        residency gauge, and cumulative totals.  Ring entries are
        immutable once rolled, so the document can never tear."""
        with self._lock:
            ring = list(self._ring if cycles is None
                        else self._ring[-max(1, cycles):])
            window = {
                "by_reason": {r: dict(t) for r, t
                              in sorted(self._window_totals.items())},
                "events": len(self._window_events),
                "dropped": self._window_dropped,
            }
            residency = {"buffers": len(self._resident),
                         "bytes": self._resident_bytes,
                         "peak_bytes": max(self._window_peak,
                                           self._resident_bytes)}
            totals = {r: dict(t) for r, t in sorted(self._totals.items())}
        return {
            "cycles": [dict(c, events=list(c["events"])) for c in ring],
            "window": window,
            "residency": residency,
            "totals": {"by_reason": totals},
        }


#: the process-global ledger every package ``device_put`` flows through
LEDGER = TransferLedger()
