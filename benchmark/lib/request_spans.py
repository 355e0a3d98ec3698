"""What the program says of an iteration's requests, as ``/healthz``
serves it after each cycle of a traced run: ``last_cycle.requests``
(by path, the requests that closed since the cycle before was
published: ``total_seconds``, ``span_self_seconds`` by path from
``request/``, ``gc``), ``gc_iteration`` and ``lanes``.  A program that
serves no ``requests`` (one from before they were added) gives every
reader here ``None``."""
from __future__ import annotations

from .spans import healths, mean_ms


def mean_request_ms(run, spans: dict) -> float | None:
    """Mean per window cycle, in ms, of the spans named in ``spans``
    (``{request path: span names}``), each with everything under it."""
    def of(health: dict) -> float:
        return sum(
            secs for route, names in spans.items()
            for path, secs in health["requests"].get(route, {}).get(
                "span_self_seconds", {}).items()
            if set(names) & set(path.split("/")))

    return mean_ms([of(h) for h in healths(run, "requests")])


def outside_cycle(health: dict, key: str, generations=(0, 1, 2)) -> float:
    """``gc_iteration``'s two parts outside the cycle's root, summed
    over ``generations``: ``key`` is ``collections`` or
    ``pause_seconds``."""
    parts = health["gc_iteration"]
    return sum(parts[part][key][g] for g in generations
               for part in ("in_requests", "between_requests"))
