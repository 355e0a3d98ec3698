"""What the harness reads that is not a span: JAX's own compile events,
and the HTTP client the one closed-loop client speaks through."""
from __future__ import annotations

import json
import urllib.error
import urllib.request


class CompileMeter:
    """Counts persistent-cache requests and hits and sums backend compile
    seconds, from the events JAX records itself.  On a warm cache JAX
    reports the load of an entry under the same duration event."""

    def __init__(self):
        import jax.monitoring as mon
        self.requests = self.hits = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def read(self) -> dict:
        return {"compile_requests": self.requests, "cache_hits": self.hits,
                "backend_compile_s": self.compile_s}


class Client:
    """One HTTP client on 127.0.0.1.  ``get`` returns the decoded JSON
    body and ``post`` the body's bytes; another status than 200 raises
    ``urllib.error.HTTPError``."""

    def __init__(self, port: int, timeout_s: float = 1100.0):
        self.base = f"http://127.0.0.1:{port}"
        self.timeout_s = timeout_s

    def _call(self, path: str, body: bytes | None) -> bytes:
        req = urllib.request.Request(
            self.base + path, data=body,
            method="GET" if body is None else "POST")
        with urllib.request.urlopen(req, timeout=self.timeout_s) as resp:
            return resp.read()

    def get(self, path: str):
        return json.loads(self._call(path, None))

    def post(self, path: str, body: bytes) -> bytes:
        return self._call(path, body)

    def jit_misses(self) -> int:
        wire = self.get("/debug/wire?cycles=1")
        return sum(e["misses"] for e in wire["compile"]["entries"].values())


def memory_held(stats: dict) -> tuple[int, int]:
    """(peak bytes in use, peak bytes reserved) of one device's
    ``memory_stats()``.  In use: live arrays and loaded executables.
    Reserved: the temporaries of the programs that ran, which the TPU
    runtime keeps apart from "in use" (read on a v5e: a program whose
    compiled temporaries are 11.42 GB left 0.36 GB in use, 11.37 GB
    reserved and a largest free block of the limit less both)."""
    return (int(stats.get("peak_bytes_in_use", 0)),
            int(stats.get("peak_bytes_reserved", 0)))
