"""Structured tracing spans and events (the ``obs`` ring buffer).

One process-wide tracer, off by default. When enabled (``obs.enable()``,
the ``obs.tracing()`` context manager, or the ``REPRO_OBS_TRACE``
environment variable), instrumented code records *spans* — named,
attributed durations from ``with obs.trace(name, **attrs):`` — and
instantaneous *events* (``obs.event(name, **attrs)``) into a bounded
in-memory ring buffer.

Independently of the ring, while a ``jax.profiler`` session records
(``jax.profiler.trace(dir)``, ``start_trace``), every span and event also
enters a ``jax.profiler.TraceAnnotation`` of the same name and attributes,
so the engine's host spans land in the profiler's XSpace beside the device
ops, on its clock. With the ring off and no profiler recording,
``trace()`` returns a shared no-op span and ``event()`` returns at once:
the hot path (``InteractionPlan.execute``) pays one predicate per dispatch
(:func:`active`) and records nothing — the zero-overhead contract
``tests/test_obs.py`` asserts.

Clock: the profiler's, wall-clock nanoseconds (``time.time_ns()``; the
profiler stamps its events from the same clock). Ring records hold seconds
since the ring's origin, and ``origin_ns`` (in :func:`stats` and in both
exports) is that origin in absolute nanoseconds: ``origin_ns + ts * 1e9``
is the record's place on an XSpace's timeline.

Exports: :func:`export_jsonl` (one JSON object per record) and
:func:`export_chrome_trace` (Chrome ``trace_event`` JSON — load it at
``chrome://tracing`` or https://ui.perfetto.dev). ``tools/trace_view.py``
converts and summarizes the JSONL form offline.

Record schema (the JSONL form)::

    {"name": "plan.execute", "ph": "X",     # "X" span | "i" instant
     "ts": 0.0123,                          # seconds since the origin
     "dur": 0.0004,                         # seconds (spans only)
     "tid": 140023, "attrs": {...},
     "origin_ns": 1792306374538984122}      # the origin (exports only)

The buffer is a ``collections.deque(maxlen=capacity)``: a long run keeps
the newest ``capacity`` records and counts what it dropped
(:func:`stats`), so tracing can stay on for a whole benchmark without
unbounded memory.
"""

from __future__ import annotations

import collections
import functools
import json
import os
import pathlib
import threading
import time
from typing import Deque, Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation as _Annotation

__all__ = ["trace", "event", "device_scope", "enable", "disable", "tracing",
           "tracing_enabled", "active", "spans", "clear", "stats",
           "export_jsonl", "export_chrome_trace", "DEFAULT_CAPACITY"]

DEFAULT_CAPACITY = 65536

_enabled = False
_buf: Deque[dict] = collections.deque(maxlen=DEFAULT_CAPACITY)
_t0 = 0                    # the ring's origin, time.time_ns(); 0 = unset
_total = 0                 # records ever offered (drops = _total - len(_buf))


def tracing_enabled() -> bool:
    """True while the ring buffer records."""
    return _enabled


def active() -> bool:
    """True while spans go anywhere: the ring records or a profiler
    session does (the one predicate hot paths pay)."""
    return _enabled or _Annotation.is_enabled()


def enable(capacity: Optional[int] = None) -> None:
    """Turn tracing on. ``capacity`` resizes the ring buffer (existing
    records are kept up to the new bound); the time origin is set on the
    first enable only, so re-enabling composes with earlier records."""
    global _enabled, _buf, _t0
    if capacity is not None and capacity != _buf.maxlen:
        _buf = collections.deque(_buf, maxlen=int(capacity))
    if not _enabled and _t0 == 0:
        _t0 = time.time_ns()
    _enabled = True


def disable() -> None:
    """Turn tracing off (records are kept; ``clear()`` drops them)."""
    global _enabled
    _enabled = False


def clear() -> None:
    """Drop every recorded span/event and reset the drop accounting."""
    global _total, _t0
    _buf.clear()
    _total = 0
    _t0 = time.time_ns() if _enabled else 0


def spans() -> List[dict]:
    """The recorded span/event dicts, oldest first (a copy)."""
    return list(_buf)


def stats() -> Dict[str, int]:
    """Ring-buffer accounting: recorded / capacity / dropped, and the
    origin of ``ts`` in absolute nanoseconds (``origin_ns``)."""
    return {"recorded": len(_buf), "capacity": int(_buf.maxlen or 0),
            "dropped": _total - len(_buf), "enabled": int(_enabled),
            "origin_ns": _t0}


class tracing:
    """Context manager: tracing on inside, restored outside.

    >>> with obs.tracing():
    ...     plan.execute(state)
    ... obs.export_chrome_trace("trace.json")
    """

    def __init__(self, capacity: Optional[int] = None):
        self._capacity = capacity
        self._was = False

    def __enter__(self):
        self._was = _enabled
        enable(self._capacity)
        return self

    def __exit__(self, exc_type, exc, tb):
        if not self._was:
            disable()
        return False


def _record(rec: dict) -> None:
    global _total
    _total += 1
    _buf.append(rec)


class _Span:
    """A live span: ``with obs.trace(name, **attrs) as sp: sp.set(...)``.
    Enters a profiler annotation when a profiler session records (with the
    attributes given at creation; ``set`` reaches the ring record only),
    and is recorded in the ring at exit when ``ring``; an exception inside
    marks ``attrs["error"]``."""

    __slots__ = ("name", "attrs", "_start", "_ring", "_annotation")

    def __init__(self, name: str, attrs: dict, ring: bool):
        self.name = name
        self.attrs = attrs
        self._start = 0
        self._ring = ring
        self._annotation = None

    def set(self, **attrs) -> "_Span":
        """Annotate the span mid-flight (no-op on the disabled tracer)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "_Span":
        if _Annotation.is_enabled():
            self._annotation = _Annotation(self.name, **self.attrs)
            self._annotation.__enter__()
        self._start = time.time_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        end = time.time_ns()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        if not self._ring:
            return False
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        _record({"name": self.name, "ph": "X",
                 "ts": (self._start - _t0) * 1e-9,
                 "dur": (end - self._start) * 1e-9,
                 "tid": threading.get_ident(), "attrs": self.attrs})
        return False


class _NullSpan:
    """The shared disabled-tracer span: every operation is a no-op."""

    __slots__ = ()

    def set(self, **attrs) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL = _NullSpan()


def trace(name: str, **attrs):
    """A span context manager around a named operation, recorded in the
    ring when it is on and in the profiler's trace while one records.

    Cheap by construction: when neither records this returns one shared
    no-op object — no allocation, no clock read, nothing recorded.
    Attribute values should be JSON-able scalars (str/int/float/bool)."""
    if _enabled:
        return _Span(name, attrs, ring=True)
    if _Annotation.is_enabled():
        return _Span(name, attrs, ring=False)
    return _NULL


def event(name: str, **attrs) -> None:
    """Record one instantaneous event (Chrome ``ph: "i"``); a profiler
    session sees it as a span of no length."""
    if _Annotation.is_enabled():
        with _Annotation(name, **attrs):
            pass
    if not _enabled:
        return
    _record({"name": name, "ph": "i", "ts": (time.time_ns() - _t0) * 1e-9,
             "tid": threading.get_ident(), "attrs": attrs})


def device_scope(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``, so
    the device ops it issues carry ``name`` in their ``op_name`` (the
    engine's layer names, ARCHITECTURE.md "Device scopes"). A fresh scope
    per call: one ``named_scope`` used as a decorator is shared by every
    call and holds state between its enter and exit."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# --------------------------------------------------------------------------
# export
# --------------------------------------------------------------------------

def export_jsonl(path) -> int:
    """Write the buffer as JSON Lines (one record per line, each with the
    ring's ``origin_ns``). -> count."""
    p = pathlib.Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    recs = spans()
    with open(p, "w") as f:
        for rec in recs:
            f.write(json.dumps(dict(rec, origin_ns=_t0), default=str) + "\n")
    return len(recs)


def chrome_events(records: Optional[List[dict]] = None) -> List[dict]:
    """The buffer (or ``records`` in the JSONL schema) as Chrome
    ``trace_event`` dicts — ``ts``/``dur`` in microseconds, span records
    as complete ("X") events, instants as "i" (thread scope)."""
    pid = os.getpid()
    out = []
    for rec in (spans() if records is None else records):
        ev = {"name": rec["name"], "ph": rec["ph"],
              "ts": rec["ts"] * 1e6, "pid": pid, "tid": rec["tid"],
              "args": rec.get("attrs", {})}
        if rec["ph"] == "X":
            ev["dur"] = rec.get("dur", 0.0) * 1e6
        else:
            ev["s"] = "t"
        out.append(ev)
    return out


def export_chrome_trace(path, records: Optional[List[dict]] = None) -> int:
    """Write the buffer (or ``records``) as a Chrome ``trace_event`` file
    (``{"traceEvents": [...]}``) viewable at ``chrome://tracing`` or
    https://ui.perfetto.dev; ``otherData.origin_ns`` is the origin of
    ``ts``. -> event count."""
    p = pathlib.Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    evs = chrome_events(records)
    origin = _t0 if records is None else (
        records[0].get("origin_ns") if records else None)
    with open(p, "w") as f:
        json.dump({"traceEvents": evs, "displayTimeUnit": "ms",
                   "otherData": {"origin_ns": origin}}, f, default=str)
    return len(evs)


if os.environ.get("REPRO_OBS_TRACE", "").strip() not in ("", "0"):
    enable()
