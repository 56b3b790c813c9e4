"""In-process spans and counters: where the host time of a pass goes.

Every span and counter lands in one bounded ring of records (the last
``CAPACITY``), always on::

    with trace.span("engine.scan", groups=len(groups)) as sp:
        ...
        sp.set(bytes=n)              # attributes known only at the end
    trace.count("engine.h2d_bytes", v.nbytes)
    trace.records(t0, t1)            # the records that start in [t0, t1]

A span records its name, ``t0``/``t1`` from ``time.perf_counter()``, the
thread it ran on, its parent (the innermost open span of that thread, or
the ``parent`` given, for work handed to another thread) and its
attributes. A counter is a record with ``t0 == t1`` and a ``value``, its
parent the innermost open span. Each span is also entered as a
``jax.profiler.TraceAnnotation`` of the same name, so inside a profiler
session it sits on the host plane, on the device trace's clock; with no
session running that costs one TraceMe check.

``spanned(name)`` makes every call of a function one span.
``table(records)`` totals the spans by name; it is the one exporter
(``python -m repro.launch.serve --task detect`` prints it at exit).
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

#: Records the process keeps (the oldest fall out first).
CAPACITY = 65_536


class Record:
    """One span, or one counter event (``value`` not None, ``t0 == t1``)."""

    __slots__ = ("name", "t0", "t1", "thread", "id", "parent", "attrs",
                 "value", "_recorder", "_ann")

    def __init__(self, name, parent, attrs, value=None, recorder=None):
        self.name = name
        self.parent = parent          # id of the parent span, or None
        self.attrs = attrs
        self.value = value
        self.thread = threading.get_ident()
        self.id = next(_ids)
        self.t0 = self.t1 = None
        self._recorder = recorder
        self._ann = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def set(self, **attrs) -> None:
        """Add attributes to the span (before it ends)."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    def __enter__(self) -> "Record":
        rec = self._recorder
        stack = rec._stack()
        if self.parent is None and stack:
            self.parent = stack[-1].id
        stack.append(self)
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._ann = None
        rec = self._recorder
        rec._stack().pop()
        rec._add(self)

    def __repr__(self) -> str:
        kind = "span" if self.value is None else f"count={self.value}"
        return f"Record({self.name!r}, {kind}, t0={self.t0}, t1={self.t1})"


_ids = itertools.count(1)


class Recorder:
    """A bounded, thread-safe ring of span and counter records."""

    def __init__(self, capacity: int = CAPACITY):
        self._ring: deque = deque(maxlen=int(capacity))
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _add(self, record: Record) -> None:
        with self._lock:
            self._ring.append(record)

    def span(self, name: str, *, parent: "Record | None" = None,
             **attrs) -> Record:
        """A span around a ``with`` body; ``parent`` links work handed to
        another thread to the span that handed it over."""
        return Record(name, None if parent is None else parent.id, attrs,
                      recorder=self)

    def count(self, name: str, n, **attrs) -> None:
        """One counter event of ``n``, under the innermost open span."""
        top = self.current()
        r = Record(name, None if top is None else top.id, attrs, value=n,
                   recorder=self)
        r.t0 = r.t1 = time.perf_counter()
        self._add(r)

    def current(self) -> "Record | None":
        """The innermost open span of the calling thread."""
        stack = self._stack()
        return stack[-1] if stack else None

    def records(self, t0: float = float("-inf"),
                t1: float = float("inf")) -> list:
        """The records that start in ``[t0, t1]``, oldest first."""
        with self._lock:
            snap = list(self._ring)
        return [r for r in snap if t0 <= r.t0 <= t1]


def table(records) -> str:
    """Per-name totals of spans (calls, seconds, mean ms) and counters
    (events, sum), largest total first."""
    spans, counts = {}, {}
    for r in records:
        if r.value is None:
            n, s = spans.get(r.name, (0, 0.0))
            spans[r.name] = (n + 1, s + r.seconds)
        else:
            n, s = counts.get(r.name, (0, 0))
            counts[r.name] = (n + 1, s + r.value)
    width = max((len(k) for k in [*spans, *counts]), default=4)
    lines = [f"{'span':<{width}} {'calls':>7} {'total s':>10} {'mean ms':>10}"]
    for name, (n, s) in sorted(spans.items(), key=lambda kv: -kv[1][1]):
        lines.append(f"{name:<{width}} {n:>7} {s:>10.3f} {1e3 * s / n:>10.3f}")
    for name, (n, s) in sorted(counts.items()):
        lines.append(f"{name:<{width}} {n:>7} {'sum':>10} {s:>10}")
    return "\n".join(lines)


#: The process's recorder; the functions below use it.
RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
current = RECORDER.current
records = RECORDER.records


def spanned(name: str):
    """Decorator: each call of the function is one span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with RECORDER.span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def annotate(**attrs) -> None:
    """Add attributes to the calling thread's innermost open span."""
    sp = RECORDER.current()
    if sp is not None:
        sp.set(**attrs)


__all__ = ["CAPACITY", "Record", "Recorder", "RECORDER", "span", "count",
           "current", "records", "spanned", "annotate", "table"]
