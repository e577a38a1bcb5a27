"""Host spans on the ``perf_counter_ns`` clock: the program's own tracer.

A span marks one step of one layer (``<layer>.<step>``: ``engine.fetch``,
``store.read``, ``service.batch``) from its start to its end::

    from repro.obs import trace

    with trace.span("engine.fetch", parent=cause, start=s) as sp:
        ...
    stats["fetch_s"] += sp.seconds      # the counter and the span share
                                        # the same two clock reads

* **Causes.**  A span's parent is the innermost span open on the same
  thread.  Work handed to another thread carries its cause explicitly:
  capture :func:`current` where the work is submitted and pass it as
  ``parent=``.
* **Identity.**  A span inherits its parent's attributes and adds its own,
  so every span of one macro batch carries the job and batch the service
  opened it with.
* **Storage.**  Closed spans go into one bounded ring per process
  (:func:`spans`, :func:`dropped`, :func:`clear`).  A full ring drops its
  oldest span and counts it.
* **The profiler.**  Each recorded span also enters
  ``jax.profiler.TraceAnnotation``, so a profile taken with host tracing on
  shows the spans in the host plane, on the device trace's clock.

Recording is on by default; :func:`enable` ``(False)`` stops it (spans still
time themselves, so counters keep working).  Spans belong at segment and
site granularity: a few tens per macro batch.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple, Optional, Union

import jax

#: spans kept per process: thousands of macro batches of a short chain,
#: the newest two or three of an 8,176-site one (three store spans a site)
CAPACITY = 1 << 16


class Record(NamedTuple):
    """One closed span, as kept in the ring."""
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: Optional[int]
    attrs: dict


class Span:
    """An open (or, after the ``with`` block, closed) span."""
    __slots__ = ("name", "span_id", "parent_id", "attrs", "start_ns",
                 "end_ns", "_rec", "_ann")

    def __init__(self, rec: "Recorder", name: str, parent, attrs: dict):
        self.name = name
        self._rec = rec
        self._ann = None
        self.span_id = self.parent_id = None
        self.end_ns = None
        if not rec.enabled:
            self.attrs = attrs
            return
        if parent is None:
            parent = rec.current()
        if isinstance(parent, Span):
            self.parent_id = parent.span_id
            attrs = {**parent.attrs, **attrs}
        else:
            self.parent_id = parent
        self.attrs = attrs
        self.span_id = next(rec._ids)

    @property
    def seconds(self) -> float:
        """Duration in seconds, once the span has closed."""
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self) -> "Span":
        if self.span_id is not None:
            self._rec._stack().append(self)
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        if self.span_id is None:
            return
        self._ann.__exit__(*exc)
        stack = self._rec._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        self._rec._keep(Record(self.name, self.start_ns, self.end_ns,
                               self.span_id, self.parent_id, self.attrs))


class Recorder:
    """A bounded ring of closed spans plus each thread's open-span stack."""

    def __init__(self, capacity: int = CAPACITY):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._dropped = 0
        self._ids = itertools.count(1)
        self._local = threading.local()
        self.enabled = True

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _keep(self, rec: Record) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self._dropped += 1
            self._ring.append(rec)

    def span(self, name: str, *, parent: Union[Span, int, None] = None,
             **attrs) -> Span:
        return Span(self, name, parent, attrs)

    def current(self) -> Optional[Span]:
        """The innermost span open on this thread, or None."""
        st = getattr(self._local, "stack", None)
        return st[-1] if st else None

    def spans(self) -> list[Record]:
        """A snapshot of the ring, oldest first."""
        with self._lock:
            return list(self._ring)

    def dropped(self) -> int:
        """Spans the full ring has dropped since the last :meth:`clear`."""
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._dropped = 0

    def enable(self, on: bool = True) -> None:
        self.enabled = bool(on)


_default = Recorder()


def span(name: str, *, parent: Union[Span, int, None] = None,
         **attrs) -> Span:
    """A span on the process's recorder; use it as a context manager.
    ``parent`` (a :class:`Span` or its id) overrides the thread's innermost
    open span as the cause."""
    return _default.span(name, parent=parent, **attrs)


def current() -> Optional[Span]:
    return _default.current()


def spans() -> list[Record]:
    return _default.spans()


def dropped() -> int:
    return _default.dropped()


def clear() -> None:
    _default.clear()


def enable(on: bool = True) -> None:
    """Turn recording on or off for the whole process (for operators and
    for measuring the recorder's own cost)."""
    _default.enable(on)
