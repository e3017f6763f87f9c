"""Zero-dependency tracing: context-manager spans with a strict no-op off path.

A span has two sinks.  The JSONL recording (``enable``/``disable``, one
module-level flag) keeps ``(name, ts, dur, tid, depth, attrs)`` against a
process start reference; events accumulate in memory, and ``flush`` writes
them as JSONL (one event per line, first line a ``meta`` header).  And
whenever a JAX profiler session is collecting, a span also enters a
``jax.profiler.TraceAnnotation`` of its name, with its scalar attrs as
metadata, so the profiler's ``.xplane.pb`` holds the program's spans on its
own clock, next to the device operations.  The profiler is looked up in
``sys.modules``, never imported: this package runs without jax.

With both sinks off (the default), ``span`` returns a shared singleton whose
``__enter__``/``__exit__`` do nothing: no event dict is built, no string is
formatted, no timestamp is read, so instrumented hot paths cost one flag
test and the profiler's own ``is_enabled`` check.  Call sites that build an
attrs dict gate it on ``active()``, which is true when either sink is on.

Event schema (``TRACE_SCHEMA`` = 1), one JSON object per line:

* ``{"ev": "meta", "schema": 1, "pid": ..., "unix_time": ...}``
* ``{"ev": "span", "name": ..., "ts": us, "dur": us, "tid": ..., "depth": ...,
  "attrs": {...}}``
* ``{"ev": "log", "level": ..., "name": ..., "msg": ..., "ts": us}``
* ``{"ev": "counter" | "gauge", "name": ..., "value": ..., "ts": us}``
* ``{"ev": "hist", "name": ..., "count": ..., "sum": ..., "min": ..., "max":
  ..., "p50": ..., "p99": ..., "ts": us}``

Capture without touching code: ``REPRO_TRACE=out.jsonl`` — the launchers call
``configure_from_env()``, which enables recording and registers an atexit
flush to that path.
"""
from __future__ import annotations

import atexit
import json
import os
import pathlib
import sys
import threading
import time
from typing import Any, Dict, List, Optional

TRACE_SCHEMA = 1

# ------------------------------------------------------------- process state
# Mutated only through enable()/disable(); read on every instrumented call.
_enabled = False
_events: List[Dict[str, Any]] = []
_sink_path: Optional[pathlib.Path] = None
_t0 = time.perf_counter()
_t0_unix = time.time()
_tls = threading.local()
_atexit_registered = False


def enabled() -> bool:
    """True when the JSONL recording is on (gates counters, histograms and
    JSONL-only work)."""
    return _enabled


_annotation_cls = None   # jax.profiler.TraceAnnotation, once jax is loaded


def _annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session collects,
    else None.  Found in ``sys.modules``: a process without jax has no
    profiler to annotate."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        mod = sys.modules.get("jax.profiler")
        if mod is None:
            return None
        cls = _annotation_cls = mod.TraceAnnotation
    return cls if cls.is_enabled() else None


def active() -> bool:
    """True when a span goes anywhere: the JSONL recording is on or a JAX
    profiler session is collecting.  Gate attrs dicts on this."""
    return _enabled or _annotation() is not None


# attr values a profiler annotation can carry as metadata
_SCALAR = (str, int, float)


def _scalars(attrs: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    if not attrs:
        return {}
    return {k: v for k, v in attrs.items() if isinstance(v, _SCALAR)}


def _now_us() -> float:
    return (time.perf_counter() - _t0) * 1e6


def _depth() -> int:
    return getattr(_tls, "depth", 0)


class _NullSpan:
    """The disabled path: a shared, attribute-less, allocation-free span."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, key: str, value) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


class Span:
    """A live span: times the ``with`` body and records one JSONL event on
    exit if ``record``; inside a profiler session it also holds a
    ``TraceAnnotation`` open over the body."""

    __slots__ = ("name", "attrs", "_record", "_start", "_ann")

    def __init__(self, name: str, attrs: Optional[Dict[str, Any]],
                 record: bool = True):
        self.name = name
        self.attrs = attrs
        self._record = record
        self._ann = None

    def set(self, key: str, value) -> "Span":
        """Attach/overwrite one attribute (usable before or inside the body)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value
        if self._ann is not None and isinstance(value, _SCALAR):
            self._ann.set_metadata(**{key: value})
        return self

    def __enter__(self) -> "Span":
        cls = _annotation()
        if cls is not None:
            self._ann = cls(self.name, **_scalars(self.attrs))
            self._ann.__enter__()
        if self._record:
            _tls.depth = _depth() + 1
            self._start = _now_us()
        return self

    def __exit__(self, *exc) -> bool:
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        if not self._record:
            return False
        end = _now_us()
        _tls.depth = _depth() - 1
        if _enabled:   # disable() between enter/exit drops the event
            _events.append({
                "ev": "span", "name": self.name, "ts": self._start,
                "dur": end - self._start, "tid": threading.get_ident(),
                "depth": _depth(),
                "attrs": self.attrs if self.attrs is not None else {}})
        return False


def span(name: str, attrs: Optional[Dict[str, Any]] = None):
    """Open a span; ``attrs`` is an optional plain dict of attributes.

    With the recording and the profiler both off this returns the shared
    ``NULL_SPAN`` — callers building an attrs dict on a hot path gate it on
    ``active()`` first.
    """
    if _enabled:
        return Span(name, attrs)
    if _annotation() is not None:
        return Span(name, attrs, record=False)
    return NULL_SPAN


def record_span(name: str, start_us: float,
                attrs: Optional[Dict[str, Any]] = None,
                end_us: Optional[float] = None) -> None:
    """Record an already-timed interval (for paths that cannot use ``with``).

    ``start_us``/``end_us`` are ``now_us()`` values; ``end_us`` defaults to
    the current time.  JSONL only (the profiler sees nothing); no-op when
    the recording is off.
    """
    if not _enabled:
        return
    _events.append({
        "ev": "span", "name": name, "ts": start_us,
        "dur": (_now_us() if end_us is None else end_us) - start_us,
        "tid": threading.get_ident(), "depth": _depth(),
        "attrs": attrs if attrs is not None else {}})


def now_us() -> float:
    """Microseconds since the trace clock epoch (pairs with record_span)."""
    return _now_us()


def record_event(event: Dict[str, Any]) -> None:
    """Append a pre-built non-span event (log/counter lines).  No-op off."""
    if not _enabled:
        return
    event.setdefault("ts", _now_us())
    _events.append(event)


# ------------------------------------------------------------ lifecycle / IO
def enable(trace_path: Optional[str | os.PathLike] = None) -> None:
    """Start recording; with ``trace_path`` also flush there at process exit."""
    global _enabled, _sink_path, _atexit_registered
    _enabled = True
    if trace_path is not None:
        _sink_path = pathlib.Path(trace_path)
        if not _atexit_registered:
            atexit.register(_atexit_flush)
            _atexit_registered = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    """Drop all recorded events and metrics; disable tracing (test hook)."""
    global _enabled, _sink_path
    from . import metrics
    _enabled = False
    _sink_path = None
    _events.clear()
    for store in metrics.registry():
        store.clear()


def events() -> List[Dict[str, Any]]:
    """The in-memory event list (live reference; treat as read-only)."""
    return _events


def configure_from_env() -> None:
    """Honour ``REPRO_TRACE=<path>`` (enable + atexit flush) if set."""
    path = os.environ.get("REPRO_TRACE")
    if path:
        enable(path)


def _meta_event() -> Dict[str, Any]:
    return {"ev": "meta", "schema": TRACE_SCHEMA, "pid": os.getpid(),
            "unix_time": _t0_unix}


def flush(path: Optional[str | os.PathLike] = None) -> pathlib.Path:
    """Write meta + all events + a final metrics snapshot as JSONL."""
    from . import metrics
    p = pathlib.Path(path) if path is not None else _sink_path
    if p is None:
        raise ValueError("no trace path: pass one or enable(trace_path=...)")
    p.parent.mkdir(parents=True, exist_ok=True)
    lines = [json.dumps(_meta_event())]
    lines += [json.dumps(e) for e in _events]
    lines += [json.dumps(e) for e in metrics.snapshot_events(_now_us())]
    p.write_text("\n".join(lines) + "\n")
    return p


def _atexit_flush() -> None:
    if _enabled and _sink_path is not None:
        flush()


def read_trace(path: str | os.PathLike) -> List[Dict[str, Any]]:
    """Parse a JSONL trace back into its event list (meta line included)."""
    out = []
    for line in pathlib.Path(path).read_text().splitlines():
        line = line.strip()
        if line:
            out.append(json.loads(line))
    return out


def validate_trace(evs: List[Dict[str, Any]]) -> List[str]:
    """Schema errors in a parsed trace ([] == valid).

    Checks: a leading meta line with a known schema, every event carries a
    known ``ev`` kind and its required fields, span timestamps/durations are
    finite and non-negative.
    """
    errors: List[str] = []
    if not evs:
        return ["empty trace"]
    if evs[0].get("ev") != "meta":
        errors.append("first line is not a meta event")
    elif evs[0].get("schema") != TRACE_SCHEMA:
        errors.append(f"unknown trace schema {evs[0].get('schema')}")
    required = {"span": ("name", "ts", "dur", "tid", "depth", "attrs"),
                "log": ("level", "name", "msg", "ts"),
                "counter": ("name", "value", "ts"),
                "gauge": ("name", "value", "ts"),
                "hist": ("name", "count", "sum", "min", "max", "p50",
                         "p99", "ts"),
                "meta": ("schema", "pid")}
    for i, e in enumerate(evs):
        kind = e.get("ev")
        if kind not in required:
            errors.append(f"line {i}: unknown event kind {kind!r}")
            continue
        missing = [k for k in required[kind] if k not in e]
        if missing:
            errors.append(f"line {i}: {kind} missing {missing}")
        if kind == "span" and not missing:
            if not (e["ts"] >= 0 and e["dur"] >= 0):
                errors.append(f"line {i}: negative ts/dur")
    return errors


# -------------------------------------------------------------------- measure
def measure(fn, *args, **kwargs):
    """``(result, seconds)`` of ``fn(*args, **kwargs)``, async dispatch fenced.

    JAX dispatch is asynchronous: timing ``fn()`` alone measures Python call
    overhead, not the computation.  ``measure`` calls
    ``jax.block_until_ready`` on the result *inside* the timed region, so
    wall-clock covers the device work.  Non-JAX results (plans, numpy) pass
    through untouched; the helper stays usable — and jax stays unimported —
    in pure-python benchmarks.
    """
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    try:
        import jax
        out = jax.block_until_ready(out)
    except ImportError:          # pure-python caller: nothing to fence
        pass
    return out, time.perf_counter() - t0
