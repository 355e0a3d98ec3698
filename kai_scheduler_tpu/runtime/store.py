"""The cluster store's collections: dicts that mark the journal as they
are written.

``Cluster.pods``, ``pod_groups``, ``nodes`` and ``bind_requests`` are
each a :class:`Collection`.  Every mutator of ``dict`` marks the
cluster's :class:`~..state.incremental.MutationJournal` through the
intake gate after the write (``gate.store_mark``), and a stored object
tells its collection of writes to its watched fields
(``apis.types.WATCHED_FIELDS``).  So the incremental snapshot learns of
every write the snapshot depends on as it happens, and never has to
compare the cluster with its ledger to find one.

An object held by two stores marks both; a copy of it, held by none,
marks nothing.  A write that goes round the collection's methods
(``dict.__setitem__(store, ...)``) marks nothing either: the snapshotter
counts the collections against its ledger, and a count that differs
rebuilds.
"""
from __future__ import annotations

import itertools
import weakref

from ..apis.types import HELD_BY, STORES
from ..intake import gate as _gate

#: what happened at a key, as ``gate.STORE_MARKS`` orders it
_NEW, _OVER, _GONE, _WRITTEN = range(4)

_ABSENT = object()
_SERIALS = itertools.count(1)


def _holds(serial: int, key, obj) -> bool:
    ref = STORES.get(serial)
    store = ref() if ref is not None else None
    return store is not None and dict.get(store, key) is obj


def _hold(obj, serial: int, key) -> None:
    held = getattr(obj, HELD_BY, None)
    if held:
        # what a copy inherited, or a write round the methods left, goes
        held = tuple(x for i in range(0, len(held), 2)
                     if _holds(held[i], held[i + 1], obj)
                     for x in held[i:i + 2])
    try:
        object.__setattr__(obj, HELD_BY, (*held, serial, key)
                           if held else (serial, key))
    except (AttributeError, TypeError):
        pass  # an object with no ``__dict__`` has no watched field


def _release(obj, serial: int, key) -> None:
    held = getattr(obj, HELD_BY, None)
    if held == (serial, key):  # the one store that held it
        object.__setattr__(obj, HELD_BY, ())
    elif held:
        object.__setattr__(obj, HELD_BY, tuple(
            x for i in range(0, len(held), 2)
            if held[i] != serial or held[i + 1] != key
            for x in held[i:i + 2]))


class Collection(dict):
    """One collection of the store, named ``coll`` as the attribute of
    ``Cluster`` it is; ``journal`` None marks nothing (a cluster under
    construction, or one whose journal was taken away)."""

    __slots__ = ("coll", "journal", "serial", "__weakref__")

    def __init__(self, coll: str, journal, items=()):
        super().__init__()
        self.coll = coll
        self.journal = journal
        self.serial = serial = next(_SERIALS)
        STORES[serial] = weakref.ref(
            self, lambda _ref, serial=serial: STORES.pop(serial, None))
        for key, obj in dict(items).items():
            self[key] = obj

    def __reduce__(self):
        # a copy is another store, with a serial of its own
        return Collection, (self.coll, self.journal, dict(self))

    def written(self, key) -> None:
        """A watched field of the object at ``key`` changed."""
        if self.journal is not None:
            _gate.store_mark(self.journal, self.coll, _WRITTEN, key)

    def detach(self) -> None:
        """This collection stops being part of a store: its objects stop
        telling it of their writes."""
        for key, obj in self.items():
            _release(obj, self.serial, key)
        self.journal = None

    # -- the mutators of dict --------------------------------------------

    def __setitem__(self, key, obj) -> None:
        old = dict.get(self, key, _ABSENT)
        dict.__setitem__(self, key, obj)
        if old is obj:
            return
        if old is not _ABSENT:
            _release(old, self.serial, key)
        _hold(obj, self.serial, key)
        if self.journal is not None:
            _gate.store_mark(self.journal, self.coll,
                             _NEW if old is _ABSENT else _OVER, key)

    def _removed(self, key, obj) -> None:
        _release(obj, self.serial, key)
        if self.journal is not None:
            _gate.store_mark(self.journal, self.coll, _GONE, key)

    def __delitem__(self, key) -> None:
        self._removed(key, dict.pop(self, key))

    def pop(self, key, *default):
        obj = dict.pop(self, key, _ABSENT)
        if obj is _ABSENT:
            if default:
                return default[0]
            raise KeyError(key)
        self._removed(key, obj)
        return obj

    def popitem(self):
        key, obj = dict.popitem(self)
        self._removed(key, obj)
        return key, obj

    def setdefault(self, key, default=None):
        obj = dict.get(self, key, _ABSENT)
        if obj is _ABSENT:
            self[key] = obj = default
        return obj

    def update(self, *args, **kw) -> None:
        for key, obj in dict(*args, **kw).items():
            self[key] = obj

    def __ior__(self, other):
        self.update(other)
        return self

    def clear(self) -> None:
        for key in list(self):
            del self[key]
