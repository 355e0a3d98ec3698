"""Where the persistent XLA compile cache lives — decided outside code.

Every entry point (``python -m kai_scheduler_tpu``, ``SchedulerServer.
start``, ``bench.py``, ``chip_smoke.py``, ``snapshot_tool.py`` and the
test suite) calls :func:`enable` before its first jit.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing here
  names a directory.
* unset — ``<checkout>/.jax_cache``, resolved from this file's own
  location and normalised.  The path is part of the cache key, so it is
  never a temporary name, a pid or a time.

The fused five-action program takes minutes to compile at 10k nodes; a
process that starts with a warm cache starts scheduling in seconds.

Several processes share one directory (the suite's workers, a server
beside a CLI tool), and JAX writes an entry with a plain ``write_bytes``
under no lock: a reader that meets a half-written entry loads garbage
and the process dies in native code, at once or at its next compile
(seen as worker segfaults in the suite).  Giving the cache a size bound
— generous enough never to evict in practice — is what makes JAX take
its file lock around every read and write.
"""
from __future__ import annotations

import os

import jax

#: <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


#: the bound that switches JAX's cache lock on (see above); the fused
#: program is ~300 MB per entry on the chip, the suite writes ~1 GB
MAX_BYTES = 64 << 30


def enable() -> str:
    """Turn the persistent compile cache on; returns the directory in
    use.  Call before the first jit of the process: JAX binds the cache
    at its first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_max_size", MAX_BYTES)
    return jax.config.jax_compilation_cache_dir
