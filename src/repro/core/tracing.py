"""Spans and counters of the database's own work, kept as aggregates.

``span(name)`` times a block of host work; ``count(name, n)`` adds to an
integer counter. Both land in one process-wide registry keyed by name,
so a long-running server holds bounded memory however long it serves:

* a span keeps its call count, total and self nanoseconds (total less
  the time of the spans opened inside it, on the same thread), the name
  of the span it was first opened in, and the XLA compiles it triggered;
* each thread keeps its own stack of open spans, so background
  maintenance never becomes a child of a request's span.

While a JAX profiler trace is recording, every span also opens a
``jax.profiler.TraceAnnotation("repro:<name>")``: the span then lies on
the trace's host plane, on the same clock as the device's events, and
the timeline of single calls lives in that trace. Spans and counters
recorded during a trace are also booked to a second registry that
restarts with each trace, so ``snapshot()["traced"]`` says what the
database did over exactly the traced interval.

One ``jax.monitoring`` listener, registered on import, books each XLA
backend compile to the innermost open span of the compiling thread
(``compile_s``, ``compiles``) and to the counters ``jax.compiles`` and
``jax.compile_ns``; the persistent compile cache's events go to
``jax.cache_lookups``, ``jax.cache_hits`` and ``jax.cache_writes`` (JAX
reports a write as a miss). A compile inside a steady-state loop thus
names the step that triggered it.

``snapshot()`` is the operator's view; ``reset()`` clears it.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterator, List, Optional

import jax
from jax.profiler import TraceAnnotation

__all__ = ["count", "reset", "snapshot", "span"]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "jax.cache_lookups",
    "/jax/compilation_cache/cache_hits": "jax.cache_hits",
    "/jax/compilation_cache/cache_misses": "jax.cache_writes",
}


class _Stats:
    __slots__ = ("calls", "total_ns", "self_ns", "parent", "compile_ns", "compiles")

    def __init__(self, parent: Optional[str]):
        self.calls = self.total_ns = self.self_ns = 0
        self.compile_ns = self.compiles = 0
        self.parent = parent


class _Registry:
    def __init__(self):
        self.spans: Dict[str, _Stats] = {}
        self.counters: Dict[str, int] = {}

    def stats(self, name: str, parent: Optional[str]) -> _Stats:
        st = self.spans.get(name)
        if st is None:
            st = self.spans[name] = _Stats(parent)
        return st

    def add(self, name: str, n: int) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def view(self) -> dict:
        return {
            "spans": {
                name: {"calls": st.calls, "total_s": st.total_ns * 1e-9,
                       "self_s": st.self_ns * 1e-9, "parent": st.parent,
                       "compile_s": st.compile_ns * 1e-9, "compiles": st.compiles}
                for name, st in self.spans.items()
            },
            "counters": dict(self.counters),
        }


class _Frame:
    __slots__ = ("name", "child_ns")

    def __init__(self, name: str):
        self.name, self.child_ns = name, 0


_lock = threading.Lock()
_local = threading.local()
_all = _Registry()
_traced = _Registry()
_tracing = False  # whether the last booking saw a profiler trace recording


def _stack() -> List[_Frame]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _registries() -> tuple:
    """The registries a booking goes to now; call with ``_lock`` held. The
    first booking of a new trace restarts the traced registry."""
    global _traced, _tracing
    on = TraceAnnotation.is_enabled()
    if on and not _tracing:
        _traced = _Registry()
    _tracing = on
    return (_all, _traced) if on else (_all,)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Time the enclosed block under ``name`` (see the module docstring)."""
    stack = _stack()
    parent = stack[-1].name if stack else None
    frame = _Frame(name)
    stack.append(frame)
    ann = TraceAnnotation(f"repro:{name}") if TraceAnnotation.is_enabled() else None
    if ann is not None:
        ann.__enter__()
    t0 = time.perf_counter_ns()
    try:
        yield
    finally:
        dt = time.perf_counter_ns() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        stack.pop()
        if stack:
            stack[-1].child_ns += dt
        with _lock:
            for reg in _registries():
                st = reg.stats(name, parent)
                st.calls += 1
                st.total_ns += dt
                st.self_ns += dt - frame.child_ns


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the integer counter ``name``."""
    with _lock:
        for reg in _registries():
            reg.add(name, n)


def snapshot() -> dict:
    """``{"spans": {name: {"calls", "total_s", "self_s", "parent",
    "compile_s", "compiles"}}, "counters": {name: int}, "traced": {...}}``;
    ``traced`` holds the same two maps over the latest profiler trace
    (empty if none has recorded a booking)."""
    with _lock:
        _registries()
        out = _all.view()
        out["traced"] = _traced.view()
    return out


def reset() -> None:
    """Clear every span and counter, the traced ones included."""
    global _all, _traced
    with _lock:
        _all, _traced = _Registry(), _Registry()


def _on_duration(event: str, secs: float, **_) -> None:
    if event != _COMPILE_EVENT:
        return
    ns = int(secs * 1e9)
    stack = _stack()
    with _lock:
        for reg in _registries():
            reg.add("jax.compiles", 1)
            reg.add("jax.compile_ns", ns)
            if stack:
                st = reg.stats(stack[-1].name, stack[-2].name if len(stack) > 1 else None)
                st.compile_ns += ns
                st.compiles += 1


def _on_event(event: str, **_) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        count(name)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
