"""Warm-up is over once a cycle has passed with no jit miss and no
compile request, and at least ``min_cycles`` have run.  A mix names its
rule (``warmup.until``); the harness finds this file by that name and
gives ``done`` the log of the warm-up cycles so far (``cycle``,
``wall_s``, ``jit_misses``, ``compile_requests``, ``binds``,
``evictions`` each) and the mix's ``warmup`` object."""


def done(log: list, rule: dict) -> bool:
    last = log[-1]
    return (len(log) >= rule["min_cycles"] and not last["jit_misses"]
            and not last["compile_requests"])
