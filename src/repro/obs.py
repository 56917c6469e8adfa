"""In-program spans and counters, off by default.

    from repro import obs

    with obs.span("reshard", kind="shrink"):
        with obs.span("reshard.put"):
            ...
        obs.count("reshard.bytes_moved", n)

While tracing is off, ``span`` returns one shared no-op context after a
single flag check, and ``count`` and ``annotate`` return after the same
check: no clock, no profiler annotation, no record, no lock.

``enable()`` turns it on.  Each span then becomes a record
``{id, parent, name, t0, t1, attrs, counters}`` (``t0``/``t1`` from
``time.perf_counter_ns``; ``parent`` is the id of the innermost span open
when it started, or None) and opens ``jax.profiler.TraceAnnotation``
``repro/<name>``, so a profiler trace shows every span on its host plane
next to the device's ops.  ``enable()`` also registers ``jax.monitoring``
listeners that count JAX's compile events into the innermost open span:

- ``compile.trace_s:<fun>``, ``compile.lower_s:<fun>``,
  ``compile.backend_s:<fun>``: seconds of jaxpr tracing, lowering to
  MLIR, and backend compilation (or a load from the persistent cache)
  of the function named ``<fun>``, less the events of the same kind
  nested in it, so the keys of one kind add up to its time;
- ``compile.count``: one per backend compilation;
- ``compile.cache_hits``, ``compile.cache_misses``: persistent-cache
  lookups.

The program counts its own beside them: ``reshard.bytes_moved``,
``reshard.bytes_total`` and ``reshard.gathered_bytes`` in a resize's
reshard, and ``rejit.cache_hits`` or ``rejit.cache_misses`` in each
``rejit`` span, as ``ElasticTrainer`` finds its jitted step for the
mesh stored or builds it.

``disable()`` unregisters them.  ``records()`` and ``reset()`` are the
only readout.  Spans nest on one stack: open and close them from the
thread that runs the loop.
"""
from __future__ import annotations

import contextlib
import time

import jax

_TIMED = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_s",
    "/jax/core/compile/backend_compile_duration": "compile.backend_s",
}
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}

_on = False
_records: list[dict] = []
_open: list[dict] = []
# Per timed event, the (start, end) of those counted so far that no later
# one contains.  JAX reports a nested event (a jnp function traced inside
# the step being traced) before the one around it.
_timed: dict[str, list[tuple[float, float]]] = {}


_NOOP = contextlib.nullcontext()


class _Span:
    __slots__ = ("name", "attrs", "rec", "annotation")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        self.rec = {"id": len(_records), "parent": _open[-1]["id"] if _open else None,
                    "name": self.name, "t0": None, "t1": None,
                    "attrs": self.attrs, "counters": {}}
        _records.append(self.rec)
        _open.append(self.rec)
        self.annotation = jax.profiler.TraceAnnotation(f"repro/{self.name}")
        self.annotation.__enter__()
        self.rec["t0"] = time.perf_counter_ns()

    def __exit__(self, *exc):
        self.rec["t1"] = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        if _open and _open[-1] is self.rec:   # not after a reset()
            _open.pop()
        return False


def span(name: str, **attrs):
    """A context manager around one piece of work; see the module doc."""
    if not _on:
        return _NOOP
    return _Span(name, attrs)


def count(name: str, value: float = 1) -> None:
    """Add ``value`` to counter ``name`` of the innermost open span."""
    if not _on or not _open:
        return
    counters = _open[-1]["counters"]
    counters[name] = counters.get(name, 0) + value


def annotate(**attrs) -> None:
    """Set attributes of the innermost open span, for what is known only
    after it started."""
    if _on and _open:
        _open[-1]["attrs"].update(attrs)


def enabled() -> bool:
    return _on


def _on_time_span(event: str, start_time: float, end_time: float, **kwargs) -> None:
    key = _TIMED.get(event)
    if key is None:
        return
    done = _timed.setdefault(event, [])
    nested = 0.0
    while done and done[-1][0] >= start_time:
        s, e = done.pop()
        nested += e - s
    done.append((start_time, end_time))
    count(f"{key}:{kwargs.get('fun_name', '?')}", end_time - start_time - nested)
    if event == _BACKEND_COMPILE:
        count("compile.count")


def _on_event(event: str, **kwargs) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        count(key)


def enable() -> None:
    """Turn tracing on and register the compile listeners."""
    global _on
    if _on:
        return
    jax.monitoring.register_event_time_span_listener(_on_time_span)
    jax.monitoring.register_event_listener(_on_event)
    _on = True


def disable() -> None:
    """Turn tracing off and unregister the compile listeners; the records
    stay until ``reset``."""
    global _on
    if not _on:
        return
    _on = False
    jax.monitoring.unregister_event_time_span_listener(_on_time_span)
    jax.monitoring.unregister_event_listener(_on_event)


def records() -> list[dict]:
    """Every span recorded since the last ``reset``, in the order they
    opened (``t1`` is None while a span is still open)."""
    return list(_records)


def reset() -> None:
    """Drop every record.  Spans still open keep working but are no
    longer parents of later records."""
    _records.clear()
    _open.clear()
    _timed.clear()
