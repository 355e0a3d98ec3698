"""Warm-up of a mix that sends nothing is over once ``min_cycles`` have
run, the last cycle had no jit miss and no compile request, and the last
two commits held no bind and no eviction: what the first cycles placed
or evicted has drained, and every later cycle patches an unchanged
state."""


def done(log: list, rule: dict) -> bool:
    last = log[-1]
    return (len(log) >= max(2, rule["min_cycles"])
            and not last["jit_misses"] and not last["compile_requests"]
            and not any(c["binds"] or c["evictions"] for c in log[-2:]))
