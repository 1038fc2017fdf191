"""repro.obs — the one observability layer everything emits through.

Three pieces (ISSUE 10):

  * :mod:`repro.obs.trace` — spans + trace IDs; context-managed live
    spans, retroactive cross-thread spans, head sampling, a no-op
    disabled mode, and a ``jax.profiler.TraceAnnotation`` bridge so
    host spans land inside device profiles.
  * :mod:`repro.obs.metrics` — counters, gauges, bounded-memory
    geometric histograms, and the capped :class:`LatencyRecorder`
    the serve-layer telemetry classes are built on.
  * :mod:`repro.obs.export` / :mod:`repro.obs.report` — schema-versioned
    JSONL trace export and the tree/rollup renderer behind
    ``python -m repro.launch.obs_report``.

``repro.obs.clock.now()`` is the repo-wide monotonic clock; raw
``time.perf_counter()`` latency bookkeeping outside this package is
forbidden by a grep rule in ``tests/test_obs.py``.

Lining spans up with a device profile: call ``tracer.anchor()`` once
while ``jax.profiler`` captures. It reads ``clock.now()`` inside an
``obs.anchor`` TraceAnnotation, and ``export_jsonl`` writes that reading
as the header's ``anchor_ms``; every exported span, retroactive and
cross-thread ones included, then lies at ``anchor_ns + (start_ms -
anchor_ms) * 1e6`` on the profile's timeline, where ``anchor_ns`` is the
annotation's start there. :mod:`repro.obs.scopes` names the model's
``jax.named_scope`` layers (``lookup``, ``propagate``, ``score``,
``topk``, ``loss``, ``optimizer``, ``sample``) that the profile's ops
carry in their ``op_name`` paths, and the rule that attributes an op to
one of them. ``watch_compiles()`` (:mod:`repro.obs.compiles`) counts the
programs JAX traces and builds, so a steady window can show it built
none.

This package never imports jax at module load (the solver's dryrun path
must set XLA flags before any backend initialization).
"""
from .clock import ms_between, now, wall
from .compiles import COMPILES, watch_compiles
from .export import SCHEMA_VERSION, export_jsonl, span_to_dict
from .metrics import (Counter, CounterSet, Gauge, Histogram,
                      LatencyRecorder, MetricsRegistry)
from .trace import (NULL_SPAN, Span, Tracer, configure, get_tracer,
                    set_tracer)

__all__ = [
    "now", "wall", "ms_between",
    "Counter", "CounterSet", "Gauge", "Histogram", "LatencyRecorder",
    "MetricsRegistry",
    "Span", "Tracer", "NULL_SPAN", "get_tracer", "set_tracer", "configure",
    "COMPILES", "watch_compiles",
    "SCHEMA_VERSION", "export_jsonl", "span_to_dict",
]
