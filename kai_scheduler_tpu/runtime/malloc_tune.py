"""Fixed glibc malloc thresholds for a serving process.

A patched cycle allocates the snapshot's large host arrays anew
(``state/incremental.py:_assemble``, tens of MB) and frees the previous
cycle's.  glibc moves its ``mmap`` threshold with the sizes it sees
freed, so whether those arrays come from a heap that is already mapped
or from a fresh ``mmap`` whose pages fault in one by one is decided by
the order of a process's earlier frees: whole runs of one tree sat on
one of two levels of ``patch.assemble`` (71 or 95 ms on a cluster of
80 000 pods), and a process could change level in the middle of a run
(PERF.md, PR 25 / PR 26 / PR 30).  Setting the thresholds by hand
switches the moving off: every array below 32 MiB comes from the heap,
and the heap keeps up to 1 GiB of freed memory mapped instead of giving
it back and faulting it in again.
"""
from __future__ import annotations

import ctypes

# <malloc.h>
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_THRESHOLD = -1, -2, -3

#: 32 MiB is the largest mmap threshold glibc accepts (half a heap)
SETTINGS = ((_M_MMAP_THRESHOLD, 32 << 20),
            (_M_TRIM_THRESHOLD, 1 << 30),
            (_M_TOP_PAD, 64 << 20))


def fix_thresholds() -> bool:
    """Set them; False where the C library has no ``mallopt`` or refuses
    a value (another allocator preloaded, another libc): the process
    then runs as it did."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    return all(mallopt(param, value) == 1 for param, value in SETTINGS)
