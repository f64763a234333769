"""Program spans and counters (DESIGN.md §16).

``span(name, **args)`` times one step of host code twice over:

* it enters ``jax.profiler.TraceAnnotation("repro." + name, **args)``, so
  inside a profile the step sits on the profiler's clock beside the
  device ops it dispatched or waited for (outside a profile the
  annotation costs under a microsecond and records nothing);
* it appends one ``Record(id, parent_id, name, t0_ns, t1_ns, args)`` to a
  bounded in-memory ring, timed by ``time.perf_counter_ns()``. The parent
  is the span open on the same thread when this one began, so every span
  of one generation or one call descends from that step's root span.

``span`` yields its ``args`` dict: what the body adds to it lands in the
ring (the annotation took its arguments on entry). A body that raises is
still recorded, with ``args["error"]`` the exception's type name.

``count(name, n)`` adds to process totals. Each garbage collection is
recorded as a ``python.gc`` span (args ``generation``, ``collected``).

Reading: ``records()`` (oldest first), ``counters()``, ``reset()``. The
ring keeps the newest ``CAPACITY`` records; once it is full, a reader
that needs every record since time t checks that the oldest record kept
ended before t.

Spans belong on host code only: inside a function being traced by
``jax.jit`` one would fire once, at trace time, and measure nothing.
"""
from __future__ import annotations

import collections
import gc
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

PREFIX = "repro."
# newest records kept: on one TPU v5e a 48 s window of the search
# benchmark made 5.3k-7.1k (9 spans a 60 ms generation, and the
# collections), of the robustness benchmark 2.3k-2.5k
CAPACITY = 65536


class Record(NamedTuple):
    id: int
    parent_id: Optional[int]
    name: str
    t0_ns: int
    t1_ns: int
    args: Dict[str, Any]


# plain tuples: a Record is built only when read (costs 0.7 us a span)
_ring: collections.deque = collections.deque(maxlen=CAPACITY)
_counters: Dict[str, float] = {}
_counters_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> List[int]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class span:
    """Context manager: one ``repro.<name>`` annotation and one record."""

    __slots__ = ("name", "args", "_id", "_parent", "_t0", "_ann")

    def __init__(self, name: str, **args):
        self.name, self.args = name, args

    def __enter__(self) -> Dict[str, Any]:
        st = _stack()
        self._parent = st[-1] if st else None
        self._id = next(_ids)
        st.append(self._id)
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                 **self.args)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self.args

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(exc_type, exc, tb)
        _stack().pop()
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        _ring.append((self._id, self._parent, self.name, self._t0, t1,
                      self.args))
        return False


def count(name: str, n: float = 1) -> None:
    with _counters_lock:
        _counters[name] = _counters.get(name, 0) + n


def records() -> List[Record]:
    return [Record._make(r) for r in list(_ring)]


def counters() -> Dict[str, float]:
    with _counters_lock:
        return dict(_counters)


def reset() -> None:
    _ring.clear()
    with _counters_lock:
        _counters.clear()


# ------------------------------------------------------ garbage collections
_gc_open: List[Any] = []          # (t0_ns, annotation) of the collection


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    if phase == "start":
        ann = jax.profiler.TraceAnnotation(PREFIX + "python.gc")
        ann.__enter__()
        _gc_open[:] = [time.perf_counter_ns(), ann]
        return
    if not _gc_open:
        return
    t0, ann = _gc_open
    _gc_open.clear()
    t1 = time.perf_counter_ns()
    ann.__exit__(None, None, None)
    st = _stack()
    _ring.append((next(_ids), st[-1] if st else None, "python.gc", t0, t1,
                  {"generation": info["generation"],
                   "collected": info["collected"]}))


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)
