"""Spans of the task runtime: one bounded ring in memory, and the profiler's
timeline (DESIGN.md §9).

``span(name, **counts)`` marks one layer boundary of a drain::

    with span("utp.plan") as sp:
        plan = plan_schedule(waves, dag)
        sp.counts["groups"] = plan.n_groups

It opens a ``jax.profiler.TraceAnnotation``, so a profiler trace shows the
span on the same timeline as the device ops, and on exit appends one record
``(id, parent_id, drain_id, name, t0_ns, t1_ns, counts)`` on
``time.perf_counter_ns`` to the ring.  ``parent_id`` is the span open around
it on the same thread, ``drain_id`` the innermost open ``utp.drain`` span
(its own id for a drain); both are 0 where there is none.  ``records()``
returns the ring, oldest first, and ``clear()`` empties it; nothing else
reads or writes it.

JAX's compile-path durations are counted where they happen: a
``jax.monitoring`` listener adds the jaxpr trace, MLIR lowering and backend
compile (or persistent-cache load) seconds to the counts of the innermost
open span of the thread, as ``trace_s``, ``lower_s`` and ``compile_s``.
JAX reports nested events (a function traced while its caller is lowered),
each as it ends; a second is counted once, under the innermost event that
covers it.

Spans are coarse: a drain replayed from the memo opens a fixed handful, and
no span is opened per task or per group.  There is no switch; the ring's
cost is a few microseconds a span.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Deque, Dict, List, Optional, Tuple

import jax

RING = 4096
DRAIN = "utp.drain"
# jax.monitoring duration events -> the count they add to the open span
EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}

Record = Tuple[int, int, int, str, int, int, Dict[str, float]]

_ring: Deque[Record] = collections.deque(maxlen=RING)
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> List["span"]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class span:
    """Context manager for one span; ``counts`` may be filled while open."""

    __slots__ = ("name", "counts", "id", "parent", "drain", "t0", "_ann", "_events")

    def __init__(self, name: str, **counts: float):
        self.name = name
        self.counts: Dict[str, float] = counts
        # disjoint (t0, t1) host intervals of the events counted so far
        self._events: List[Tuple[float, float]] = []

    def __enter__(self) -> "span":
        st = _stack()
        outer: Optional[span] = st[-1] if st else None
        self.id = next(_ids)
        self.parent = outer.id if outer is not None else 0
        if self.name == DRAIN:
            self.drain = self.id
        else:
            self.drain = outer.drain if outer is not None else 0
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        st.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        _stack().pop()
        self._ann.__exit__(None, None, None)
        _ring.append(
            (self.id, self.parent, self.drain, self.name, self.t0, t1, self.counts)
        )


def records() -> List[Record]:
    """The ring's records, oldest first."""
    return list(_ring)


def clear() -> None:
    _ring.clear()


def _on_event(event: str, secs: float, **_) -> None:
    key = EVENTS.get(event)
    st = getattr(_local, "stack", None)
    if key is None or not st:
        return
    sp = st[-1]
    t1 = time.perf_counter()
    t0 = t1 - secs
    # events end innermost first: those this one covers were counted already
    inner = [(a, b) for a, b in sp._events if b > t0 and a < t1]
    own = secs - sum(min(b, t1) - max(a, t0) for a, b in inner)
    merged = (min([t0] + [a for a, _ in inner]), max([t1] + [b for _, b in inner]))
    sp._events = [e for e in sp._events if e not in inner] + [merged]
    sp.counts[key] = sp.counts.get(key, 0.0) + max(own, 0.0)


jax.monitoring.register_event_duration_secs_listener(_on_event)
