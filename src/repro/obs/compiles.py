"""Counts of the programs JAX traces and builds, from ``jax.monitoring``.

``watch_compiles()`` registers one listener for JAX's compile-duration
events and returns the process-wide :data:`COMPILES` counter set:

  * ``traces`` / ``trace_us`` — jaxpr traces (a jit cache miss traced a
    function) and their microseconds;
  * ``compiles`` / ``compile_us`` — XLA programs built (compiled, or
    loaded from the persistent compilation cache) and their
    microseconds.

The listener fires only when JAX traces or compiles, so a warm request
or training step costs nothing. A steady window should count 0: the
difference of two ``COMPILES.as_dict()`` readings around it is the
number of programs the window built. A ``sink`` counter set passed in
receives the same counts from then on (the front door's telemetry does
this, so its counters report what serving compiled).
"""
from __future__ import annotations

import threading
import weakref
from typing import Optional

from .metrics import CounterSet

__all__ = ["COMPILES", "TRACE_EVENT", "COMPILE_EVENT", "watch_compiles"]

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COUNTERS = {TRACE_EVENT: ("traces", "trace_us"),
             COMPILE_EVENT: ("compiles", "compile_us")}

COMPILES = CounterSet(("traces", "trace_us", "compiles", "compile_us"))
_sinks = weakref.WeakSet()
_lock = threading.Lock()
_installed = False


def _on_duration(event: str, duration_secs: float, **_) -> None:
    names = _COUNTERS.get(event)
    if names is None:
        return
    count, micros = names
    us = int(round(duration_secs * 1e6))
    with _lock:
        targets = (COMPILES, *_sinks)
    for counters in targets:
        counters.bump(count)
        counters.bump(micros, us)


def watch_compiles(sink: Optional[CounterSet] = None) -> CounterSet:
    """Start counting JAX's traces and compiles (idempotent; imports jax
    on the first call) and return :data:`COMPILES`. ``sink``, if given,
    also receives every count from now on."""
    global _installed
    with _lock:
        if not _installed:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _installed = True
        if sink is not None:
            _sinks.add(sink)
    return COMPILES
