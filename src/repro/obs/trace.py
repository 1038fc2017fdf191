"""Spans and trace IDs — the request-scoped side of ``repro.obs``.

A :class:`Tracer` hands out :class:`Span` objects three ways:

  * ``tracer.trace(name)`` — a new **root** span starting a new trace.
    Returned un-entered so it can cross threads (the frontdoor opens a
    request's root on the caller thread and closes it on the batcher
    thread); close it with ``span.end()``. It also works as a context
    manager when the whole trace lives on one thread.
  * ``tracer.span(name)`` — a context-managed **child** of the current
    thread's ambient span (or a fresh root when there is none). This is
    the call sites' default: solver sweeps, stream replay steps, swap
    sections all nest automatically.
  * ``tracer.record_span(name, t0, t1, parent=...)`` — a
    **retroactive** span committed from timestamps measured elsewhere.
    The batcher uses this to attribute queue/batch/dispatch time to
    every request in a coalesced batch without entering live spans per
    request on the hot path.

Sampling is decided once per trace at root creation (head sampling) and
inherited by every child, so a trace is always complete-or-absent.
A disabled tracer returns the shared :data:`NULL_SPAN` from every call
— no allocation, no clock reads, no lock — which is what keeps the
"tracing off" load-bench QPS inside 1% of pre-PR.

When ``device_annotations`` is on and jax is *already imported*
(``repro.obs`` itself never imports jax — ``solver_jax`` dryrun sets
XLA flags before backend init), live spans also enter a
``jax.profiler.TraceAnnotation``, so host spans show up as named
regions inside device profiles captured by ``BenchRun --profile``.

Retroactive spans never enter an annotation, so a profile cannot show
them. :meth:`Tracer.anchor` puts every span on the profile's clock
instead: it reads ``clock.now()`` inside a ``TraceAnnotation`` named
:data:`ANCHOR`, so that reading and the annotation's start in the
profile name one instant, and a span time ``t`` lies at
``anchor_ns + (t - tracer.anchor_t) * 1e9`` on the profile's timeline.
"""
from __future__ import annotations

import sys
import threading
from typing import Dict, List, Optional

from .clock import now, wall

__all__ = ["Span", "Tracer", "NULL_SPAN", "ANCHOR", "get_tracer",
           "set_tracer", "configure"]

ANCHOR = "obs.anchor"   # the profiler annotation Tracer.anchor() enters


class _NullSpan:
    """The do-nothing span a disabled (or down-sampled) tracer returns.

    Supports everything a real span does so call sites never branch on
    tracer state; every method is a constant-time no-op.
    """

    __slots__ = ()
    sampled = False
    trace_id = ""
    span_id = ""
    name = ""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def end(self, **attrs):
        return self

    def set(self, **attrs):
        return self

    def __bool__(self):
        return False

    def __repr__(self):
        return "NULL_SPAN"


NULL_SPAN = _NullSpan()


class Span:
    """One timed, named, attributed section of work inside a trace."""

    __slots__ = ("tracer", "trace_id", "span_id", "parent_id", "name",
                 "t_start", "t_end", "attrs", "thread", "sampled",
                 "_entered", "_annotation")

    def __init__(self, tracer: "Tracer", trace_id: str, span_id: str,
                 parent_id: str, name: str, t_start: float,
                 sampled: bool, attrs: Optional[dict] = None):
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.t_start = t_start
        self.t_end = float("nan")
        self.attrs: Dict[str, object] = dict(attrs) if attrs else {}
        self.thread = threading.current_thread().name
        self.sampled = sampled
        self._entered = False
        self._annotation = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        self._entered = True
        self.tracer._push(self)
        ann = self.tracer._annotation_cls()
        if ann is not None:
            self._annotation = ann(self.name)
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        self.tracer._pop(self)
        self._entered = False
        self.end()
        return False

    def end(self, **attrs) -> "Span":
        """Close the span at ``clock.now()`` and commit it. Idempotent:
        a second ``end`` (e.g. a cache-hit path racing a drain) is a
        no-op."""
        if attrs:
            self.attrs.update(attrs)
        if self.t_end == self.t_end:      # already closed (not NaN)
            return self
        self.t_end = now()
        self.tracer._commit(self)
        return self

    def __repr__(self):
        state = "open" if self.t_end != self.t_end else "closed"
        return (f"Span({self.name!r}, trace={self.trace_id}, "
                f"id={self.span_id}, {state})")


class Tracer:
    """Creates, nests, samples, and collects spans in bounded memory.

    ``sample_rate`` is the fraction of *traces* kept (head sampling with
    a deterministic error-diffusion accumulator — exactly ``rate`` of
    roots sample, no RNG, reproducible run to run). ``max_spans`` caps
    the committed buffer; overflow increments :attr:`dropped` instead of
    growing (export reports the drop count in its header).
    """

    def __init__(self, enabled: bool = True, sample_rate: float = 1.0,
                 max_spans: int = 100_000, device_annotations: bool = True):
        self.enabled = bool(enabled)
        self.sample_rate = float(sample_rate)
        self.max_spans = int(max_spans)
        self.device_annotations = bool(device_annotations)
        self.dropped = 0
        self._spans: List[Span] = []
        self._lock = threading.Lock()
        self._local = threading.local()
        self._n_traces = 0
        self._n_spans = 0
        self._sample_acc = 0.0
        # perf/wall pair anchoring monotonic timestamps to calendar time
        self.perf_t0 = now()
        self.wall_t0 = wall()
        self.anchor_t: Optional[float] = None   # see anchor()

    # -- ambient span stack (per thread) --------------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()
        elif span in st:                  # mis-nested exit: drop through
            st.remove(span)

    def current(self) -> Optional[Span]:
        """The innermost live span on this thread, if any."""
        st = self._stack()
        return st[-1] if st else None

    # -- span creation --------------------------------------------------
    def _ids(self, new_trace: bool):
        with self._lock:
            if new_trace:
                self._n_traces += 1
                sampled = False
                if self.sample_rate > 0:
                    self._sample_acc += min(self.sample_rate, 1.0)
                    if self._sample_acc >= 1.0 - 1e-12:
                        self._sample_acc -= 1.0
                        sampled = True
                trace_id = f"t{self._n_traces:06d}"
            else:
                trace_id, sampled = "", True
            self._n_spans += 1
            return trace_id, f"s{self._n_spans:06d}", sampled

    def trace(self, name: str, **attrs) -> Span:
        """Open a new root span / new trace (un-entered; see module
        docstring). Close with ``span.end()`` or use as a context
        manager."""
        if not self.enabled:
            return NULL_SPAN
        trace_id, span_id, sampled = self._ids(new_trace=True)
        if not sampled:
            return NULL_SPAN
        return Span(self, trace_id, span_id, "", name, now(), True, attrs)

    def span(self, name: str, parent: Optional[Span] = None,
             **attrs) -> Span:
        """A child of ``parent`` (default: this thread's ambient span;
        a fresh root if there is none). Use as a context manager."""
        if not self.enabled:
            return NULL_SPAN
        if parent is None:
            parent = self.current()
        if parent is None:
            return self.trace(name, **attrs)
        if not getattr(parent, "sampled", False):
            return NULL_SPAN
        _, span_id, _ = self._ids(new_trace=False)
        return Span(self, parent.trace_id, span_id, parent.span_id,
                    name, now(), True, attrs)

    def record_span(self, name: str, t_start: float, t_end: float,
                    parent: Optional[Span] = None, **attrs) -> Span:
        """Commit a span from externally measured ``clock.now()``
        timestamps (retroactive, cross-thread safe). Returns the
        committed span so callers can chain it as a parent."""
        if not self.enabled:
            return NULL_SPAN
        if parent is not None and not getattr(parent, "sampled", False):
            return NULL_SPAN
        if parent is None:
            trace_id, span_id, sampled = self._ids(new_trace=True)
            if not sampled:
                return NULL_SPAN
            parent_id = ""
        else:
            trace_id, parent_id = parent.trace_id, parent.span_id
            _, span_id, _ = self._ids(new_trace=False)
        sp = Span(self, trace_id, span_id, parent_id, name,
                  float(t_start), True, attrs)
        sp.t_end = float(t_end)
        self._commit(sp)
        return sp

    def anchor(self) -> float:
        """Read ``clock.now()`` inside a ``jax.profiler.TraceAnnotation``
        named :data:`ANCHOR`; keep the reading as ``self.anchor_t`` and
        return it.

        Call it while a profile is being captured: the annotation's start
        on the profile's timeline and the returned reading are one
        instant (to within the annotation's entry, microseconds), which
        maps every span of this process, retroactive and cross-thread
        ones included, onto the profile. Works whether or not tracing is
        enabled; without jax imported there is no profile to align with
        and only the reading is taken."""
        ann = _trace_annotation()
        if ann is None:
            self.anchor_t = now()
        else:
            with ann(ANCHOR):
                self.anchor_t = now()
        return self.anchor_t

    # -- collection -----------------------------------------------------
    def _commit(self, span: Span) -> None:
        if not span.sampled:
            return
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
            else:
                self._spans.append(span)

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def drain(self) -> List[Span]:
        """Return and clear the committed spans (export calls this)."""
        with self._lock:
            out, self._spans = self._spans, []
            return out

    def clear(self) -> None:
        with self._lock:
            self._spans = []
            self.dropped = 0

    def _annotation_cls(self):
        """jax.profiler.TraceAnnotation when the bridge is on and jax is
        already imported; never triggers a jax import itself."""
        return _trace_annotation() if self.device_annotations else None


def _trace_annotation():
    """jax.profiler.TraceAnnotation if jax is already imported, else
    None; never triggers a jax import itself."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    prof = getattr(jax, "profiler", None)
    return getattr(prof, "TraceAnnotation", None) if prof else None


# -- the ambient, process-wide tracer ------------------------------------
# Disabled by default: importing repro costs nothing until a bench flag,
# example flag, or configure() call turns tracing on. configure() mutates
# THIS object in place, so modules that grabbed get_tracer() at import
# time see the change.
_GLOBAL = Tracer(enabled=False)


def get_tracer() -> Tracer:
    """The process-wide ambient tracer (disabled until configured)."""
    return _GLOBAL


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the global tracer object (tests use this for isolation)."""
    global _GLOBAL
    _GLOBAL = tracer
    return tracer


def configure(enabled: bool = True, sample_rate: Optional[float] = None,
              max_spans: Optional[int] = None,
              device_annotations: Optional[bool] = None) -> Tracer:
    """Reconfigure the global tracer *in place* (bound references stay
    valid) and return it."""
    t = _GLOBAL
    t.enabled = bool(enabled)
    if sample_rate is not None:
        t.sample_rate = float(sample_rate)
    if max_spans is not None:
        t.max_spans = int(max_spans)
    if device_annotations is not None:
        t.device_annotations = bool(device_annotations)
    return t
