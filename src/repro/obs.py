"""Spans and counters inside the engine: the operator's view of where a
scenario's wall time goes.

    from repro import obs
    with obs.recording() as rec:
        run_scenario("nezha-vectorized", "intra-zone", tier="jit")
    rec["spans"]      # name -> [(t0, t1)], time.time() seconds
    rec["counters"]   # name -> int
    rec["events"]     # [(name, time.time(), {field: value})]

Recording is off by default.  Off, `span` returns one shared no-op object
(no clock read, no allocation) and `count`/`event` test one flag, so the
instrumented code pays a few attribute lookups per span and nothing per
request.  On, each span also opens a `jax.profiler.TraceAnnotation` of its
name, so a profiled run carries the spans on the trace's host plane, on
the trace's own clock, beside the device operations.

The names recorded by the Nezha engine are listed in README.md
("Where the time goes").  Recording adds no device->host transfer: spans
read the host clock only.
"""
from __future__ import annotations

import contextlib
import time

_record = None          # the dict being recorded into, or None when off
_annotation = None      # jax.profiler.TraceAnnotation, bound by recording()


class _Off:
    """The shared span of a process that is not recording."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "t0", "ann", "spans")

    def __init__(self, name: str, spans: dict):
        self.name = name
        self.spans = spans

    def __enter__(self):
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        self.t0 = time.time()
        return None

    def __exit__(self, *exc):
        t1 = time.time()
        self.ann.__exit__(None, None, None)
        self.spans.setdefault(self.name, []).append((self.t0, t1))
        return False


def span(name: str):
    """A context manager timing the work inside it under ``name``."""
    if _record is None:
        return _OFF
    return _Span(name, _record["spans"])


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    if _record is not None:
        c = _record["counters"]
        c[name] = c.get(name, 0) + n


def event(name: str, **fields) -> None:
    """Record one timestamped event with its fields."""
    if _record is not None:
        _record["events"].append((name, time.time(), fields))


@contextlib.contextmanager
def recording():
    """Turn recording on for the block; yields the record (a plain dict,
    see the module docstring).  Nested blocks record into their own dict
    and hand back to the outer one on exit."""
    global _record, _annotation
    from jax.profiler import TraceAnnotation

    outer = _record
    _annotation = TraceAnnotation
    _record = {"spans": {}, "counters": {}, "events": []}
    try:
        yield _record
    finally:
        _record = outer
