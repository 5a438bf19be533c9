"""Process-wide span recorder: host spans where the program does its work,
on ``time.perf_counter`` and on the profiler's clock.

    with trace.span("serve/prefill", req_id=7, prompt_len=900):
        ...

Each span always opens a ``jax.profiler.TraceAnnotation`` (next to free
when no profiler runs; under one it lands on the ``/host:`` plane, on
the same clock as the device planes) and, on exit, appends
``Span(name, start, end, parent, attrs)`` to a buffer that keeps the last
``KEEP`` records of each name.  ``parent`` is the name of the span that
was open around it on the same thread.

Two process counters are kept beside the spans, each installed once at
import: XLA backend compiles (count and seconds, from JAX's monitoring
events) and Python GC collections by generation; a generation-2
collection is also recorded (and annotated) as a ``python/gc`` span, so a
long stall shows which span it fell in.
"""
from __future__ import annotations

import bisect
import collections
import functools
import gc
import threading
import time
from typing import NamedTuple

import jax

KEEP = 1 << 16                       # records kept per span name
GC_SPAN = "python/gc"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

__all__ = ["Span", "span", "records", "self_times", "counters", "summary",
           "KEEP", "GC_SPAN"]


class Span(NamedTuple):
    name: str
    start: float
    end: float
    parent: str | None
    attrs: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


_records: dict[str, collections.deque] = collections.defaultdict(
    lambda: collections.deque(maxlen=KEEP))
_local = threading.local()
_compiles = [0, 0.0]
_compile_lock = threading.Lock()
_gc_counts = [0, 0, 0]


def _stack() -> list:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


class span:
    """Context manager: one span called ``name`` with ``attrs``."""

    __slots__ = ("name", "attrs", "parent", "start", "_ann")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self._ann.__exit__(*exc)
        _stack().pop()
        _records[self.name].append(
            Span(self.name, self.start, end, self.parent, self.attrs))
        return False

    def __call__(self, fn):
        """As a decorator: every call of ``fn`` is one span."""
        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with span(self.name, **self.attrs):
                return fn(*args, **kwargs)
        return spanned


# ------------------------------------------------------------- reading
def records(name: str) -> list[Span]:
    """The kept records of span ``name``, oldest first."""
    return list(_records.get(name, ()))


def self_times(name: str, children=None) -> list[float]:
    """Per record of ``name``: its seconds less those of the child spans
    (named in ``children``; every name recorded with ``name`` as its parent
    when ``None``) that lie inside it."""
    if children is None:
        children = [n for n, rs in list(_records.items())
                    if any(r.parent == name for r in rs)]
    kids = sorted((r.start, r.end) for c in children
                  for r in records(c) if r.parent == name)
    starts = [a for a, _ in kids]
    out = []
    for r in records(name):
        inner = sum(b - a for a, b in kids[bisect.bisect_left(
            starts, r.start):bisect.bisect_right(starts, r.end)]
            if b <= r.end)
        out.append(r.seconds - inner)
    return out


def counters() -> dict:
    """XLA backend compiles (count, seconds) and Python GC collections by
    generation, since this module was imported."""
    return {"compiles": _compiles[0], "compile_s": _compiles[1],
            "gc": {g: n for g, n in enumerate(_gc_counts)}}


def summary() -> dict:
    """Per span name: count, p50 and max seconds, p50 self seconds."""
    def p50(xs):
        return sorted(xs)[(len(xs) - 1) // 2]
    out = {}
    for name in sorted(_records):
        secs = [r.seconds for r in records(name)]
        if secs:
            out[name] = {"count": len(secs), "p50_s": p50(secs),
                         "max_s": max(secs), "self_p50_s": p50(
                             self_times(name))}
    return out


# ------------------------------------------------------------ counters
def _on_compile(event: str, secs: float, **_) -> None:
    if event == _COMPILE_EVENT:
        with _compile_lock:              # compiles may run on any thread
            _compiles[0] += 1
            _compiles[1] += secs


def _on_gc(phase: str, info: dict) -> None:
    gen = info["generation"]
    if phase == "start":
        if gen == 2:
            stack = _stack()
            ann = jax.profiler.TraceAnnotation(GC_SPAN)
            ann.__enter__()
            _local.gc = (time.perf_counter(), stack[-1] if stack else None,
                         ann)
        return
    _gc_counts[gen] += 1
    open_ = getattr(_local, "gc", None)
    if gen == 2 and open_ is not None:
        _local.gc = None
        start, parent, ann = open_
        end = time.perf_counter()
        ann.__exit__(None, None, None)
        _records[GC_SPAN].append(Span(GC_SPAN, start, end, parent, {
            "generation": 2, "collected": info.get("collected", 0)}))


jax.monitoring.register_event_duration_secs_listener(_on_compile)
gc.callbacks.append(_on_gc)
