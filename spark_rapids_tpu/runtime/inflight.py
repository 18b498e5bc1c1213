"""Whose books a thread writes into.

Every query in flight owns a tracer (runtime/trace.py), a stats
collector (runtime/stats.py) and a flight recorder
(runtime/attribution.py).  Ownership is by the query's thread of
execution, not by the process: the thread that opens a query installs
the query's objects in its own slots, so two queries served at once on
two worker threads each keep books of their own, and a span opened on
one thread can only land in that thread's tracer.

* A nested execution (a sub-query planned on a thread whose slot is
  taken) rides its owner: ``install`` returns None and the owner's
  object stays.
* A helper thread that works for one query (a pump task, a file
  reader) starts with empty slots; the query hands it its books with
  ``carry(fn)`` (or ``bind(held())``), and gets the thread's previous
  books back when the call returns, so pool threads are safe to reuse.
* A thread nobody bound has no books: ``trace.span()`` there is the
  null span, ``stats.current()`` and ``attribution.current()`` None.

This is the one mechanism the three modules share; each keeps only its
``current`` / ``start_query`` / ``end_query`` over its own slot.  It
imports nothing of the package.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Callable, Optional, Tuple

TRACER, COLLECTOR, RECORDER = "tracer", "collector", "recorder"


class _Books(threading.local):
    """The calling thread's slots.  Class-level defaults make a read on
    a thread that never installed anything one attribute load."""
    tracer = None
    collector = None
    recorder = None


BOOKS = _Books()

_LOCK = threading.Lock()
_in_flight = 0   # queries that own a tracer now, over all threads
_peak = 0


def install(slot: str, make: Callable[[], object]) -> Optional[object]:
    """``make()`` installed as the calling thread's ``slot``; None,
    with nothing made, where the thread already has one (the caller is
    a nested execution and rides its owner)."""
    global _in_flight, _peak
    if getattr(BOOKS, slot) is not None:
        return None
    obj = make()
    setattr(BOOKS, slot, obj)
    if slot == TRACER:
        with _LOCK:
            _in_flight += 1
            _peak = max(_peak, _in_flight)
    return obj


def remove(slot: str, obj) -> None:
    """Empty the calling thread's ``slot`` if ``obj`` is what it holds
    (``None``, the nested execution's handle, removes nothing)."""
    global _in_flight
    if obj is None or getattr(BOOKS, slot) is not obj:
        return
    setattr(BOOKS, slot, None)
    if slot == TRACER:
        with _LOCK:
            _in_flight -= 1


def in_flight_peak() -> int:
    """The most queries that owned a tracer at one time."""
    return _peak


def held() -> Tuple[object, object, object]:
    """The calling thread's books, to hand to a helper thread."""
    return BOOKS.tracer, BOOKS.collector, BOOKS.recorder


@contextlib.contextmanager
def bind(books: Tuple[object, object, object]):
    """Run a block on this thread under another thread's ``held()``
    books; the thread's own come back on exit."""
    prev = held()
    BOOKS.tracer, BOOKS.collector, BOOKS.recorder = books
    try:
        yield
    finally:
        BOOKS.tracer, BOOKS.collector, BOOKS.recorder = prev


def carry(fn: Callable) -> Callable:
    """``fn`` as the calling thread's query runs it, on whatever thread
    it is called: the books are captured now, bound around each call."""
    books = held()

    @functools.wraps(fn)
    def carried(*args, **kw):
        with bind(books):
            return fn(*args, **kw)
    return carried
