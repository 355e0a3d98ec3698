"""Test configuration: everything runs on 8 virtual CPU devices.

The sandbox has no accelerator; sharding is validated on the virtual
CPU mesh, and ``tests/test_chip_compile.py`` compiles the main path for
a described (not attached) v5e.  ``XLA_FLAGS`` must be in the
environment before the CPU backend first initialises.
"""
import gc
import os

os.environ.setdefault("JAX_ENABLE_X64", "0")
os.environ["JAX_PLATFORMS"] = "cpu"

# the single source of the virtual-device count (shared with
# __graft_entry__'s dryrun and the kai-comms lowering stage); importing
# the mesh module does NOT initialise a jax backend
from kai_scheduler_tpu.parallel.mesh import (  # noqa: E402
    VIRTUAL_DEVICE_COUNT, ensure_virtual_cpu_devices)

ensure_virtual_cpu_devices()

import jax  # noqa: E402
import pytest  # noqa: E402

# Persistent XLA compilation cache: the suite's cost is dominated by jit
# compiles of the solver kernels (heavy nested control flow), most of
# which recur across tests, xdist workers, and runs.  Placed by the
# same rule as every entry point (runtime/compile_cache.py):
# JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache.
from kai_scheduler_tpu.runtime import compile_cache  # noqa: E402

compile_cache.enable()
# the seed's threshold: small programs are cheaper to compile than to
# queue for the cache lock
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

# Shape-unify the test snapshots: pad every snapshot axis to multiples
# of 32 (instead of the production default 8), so the dozens of small
# synthetic clusters across the suite collapse onto a handful of padded
# tensor shapes and REUSE each other's compiled kernels — the single
# biggest lever on cold-suite wall time (each distinct (shape, config)
# pair is a fresh XLA compile of the solver pipeline).  Semantics are
# unchanged: padding rows are invalid/masked by construction.
import functools  # noqa: E402

import kai_scheduler_tpu.framework.session as _session_mod  # noqa: E402
import kai_scheduler_tpu.state as _state_pkg  # noqa: E402
import kai_scheduler_tpu.state.cluster_state as _cs  # noqa: E402

_orig_build_snapshot = _cs.build_snapshot


@functools.wraps(_orig_build_snapshot)
def _padded_build_snapshot(*args, **kwargs):
    kwargs.setdefault("pad", 32)
    return _orig_build_snapshot(*args, **kwargs)


_cs.build_snapshot = _padded_build_snapshot
_state_pkg.build_snapshot = _padded_build_snapshot
_session_mod.build_snapshot = _padded_build_snapshot

# The suite is COMPILE-bound: the fused 5-action pipeline is a huge XLA
# program and every (shape, config) variant costs 1-6 min of CPU
# compile at full optimization, while the test shapes execute in
# milliseconds either way.  Compile at -O0 for tests.
jax.config.update("jax_disable_most_optimizations", True)


def _memory_map_limit() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 0


_MAP_LIMIT = _memory_map_limit()


@pytest.fixture(autouse=True)
def _bound_memory_maps():
    """Drop jit caches before the worker runs out of memory mappings.

    Every XLA:CPU executable holds ~19 mappings until its jit cache
    entry dies, a worker lives for many test files, and the kernel caps
    a process at ``vm.max_map_count`` (65 530 here): workers hold 30 000
    after three minutes, and at the cap they died in native code —
    SIGSEGV or SIGABRT inside compile, serialize or deserialize, 4–5
    workers a run, at the seed commit too.  Past 60 % of the limit the caches are cleared
    (the persistent cache on disk stays, so reloading is cheap)."""
    yield
    if not _MAP_LIMIT:
        return
    with open("/proc/self/maps", "rb") as f:
        mapped = f.read().count(b"\n")
    if mapped > 0.6 * _MAP_LIMIT:
        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def virtual_devices():
    """The VIRTUAL_DEVICE_COUNT CPU devices every multi-device test
    shares.  Skips (rather than fails) if the backend initialised
    before the XLA flag landed — a harness problem, not a product one."""
    devs = jax.devices("cpu")
    if len(devs) < VIRTUAL_DEVICE_COUNT:
        pytest.skip(f"need {VIRTUAL_DEVICE_COUNT} virtual CPU devices, "
                    f"got {len(devs)} (backend initialised too early)")
    return devs[:VIRTUAL_DEVICE_COUNT]
