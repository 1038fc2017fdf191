"""Serving telemetry: latency percentiles and compile counting.

Every Session and the BatchDispatcher carry a LatencyRecorder; `stats()`
surfaces p50/p99 per-request wall time plus the number of distinct XLA
programs compiled so far — the quantity the bucket ladder exists to
bound (arbitrary traffic must compile at most `len(buckets)` programs).

Streaming deployments additionally carry a ``StreamTelemetry``: hot-swap
latency (a swap happens between requests, so its cost is pure serving
headroom), label churn per refresh, and monotone counters for the
replay loop (appends, cold assigns, refreshes, capacity bumps).

The async front end (``repro.frontdoor``) carries a
``FrontdoorTelemetry``: end-to-end and queue-delay percentiles,
batch-fill ratio and per-bucket occupancy (how well the continuous
batcher packs the ladder), shed/timeout/cache counters, and the
swap-under-load pause (drain wait + device swap — the number PR 5's
idle swap p99 could not measure).

As of the obs layer (ISSUE 10), every measurement primitive here comes
from :mod:`repro.obs.metrics` and is bounded-memory: ``LatencyRecorder``
is a capped ring + geometric histogram (exact percentiles up to its
cap, then histogram estimates — a serving process no longer grows a
float list per request), counters live in a :class:`CounterSet` that
still reads like the plain dict tests pin (``counters["swaps"]``), and
both telemetry classes hang off a :class:`MetricsRegistry` so an obs
export can snapshot everything at once. ``summary()`` keys and rounding
are unchanged.
"""
from __future__ import annotations

from repro.obs.compiles import COMPILES, watch_compiles
from repro.obs.metrics import (CounterSet, LatencyRecorder,
                               MetricsRegistry)

__all__ = ["LatencyRecorder", "StreamTelemetry", "FrontdoorTelemetry",
           "compile_count"]


class StreamTelemetry:
    """Counters for the online co-clustering / hot-swap pipeline.

    One instance is shared between the swap-capable session (which
    records swap latency and capacity bumps) and the stream updater /
    replay loop (which records label churn and event counters) — the
    `summary()` is what launch/stream.py and stream_bench.py report.
    """

    def __init__(self):
        self.registry = MetricsRegistry()
        self.swap = self.registry.latency("swap_ms")  # per session.swap
        self.counters = self.registry.counter_set(
            "stream", ("appends", "new_edges", "cold_users",
                       "cold_items", "refreshes", "capacity_bumps"))
        # per-refresh label churn: running mean + last, not a list
        self._churn_sum = 0.0
        self._churn_n = 0
        self._churn_last = float("nan")

    def bump(self, name: str, n: int = 1) -> None:
        self.counters.bump(name, n)

    def record_churn(self, fraction: float) -> None:
        f = float(fraction)
        self._churn_sum += f
        self._churn_n += 1
        self._churn_last = f

    def summary(self) -> dict:
        out = self.counters.as_dict()
        out["swaps"] = self.swap.count
        out["swap_p50_ms"] = round(self.swap.percentile(50), 3)
        out["swap_p99_ms"] = round(self.swap.percentile(99), 3)
        out["churn_mean"] = (round(self._churn_sum / self._churn_n, 4)
                             if self._churn_n else float("nan"))
        out["churn_last"] = (round(self._churn_last, 4)
                             if self._churn_n else float("nan"))
        return out


class FrontdoorTelemetry:
    """Counters for the async serving front end (one per Frontdoor).

    Latency recorders (all milliseconds):
      e2e         submit -> response (what a caller experiences)
      queue_delay submit -> batch dispatch (time spent waiting to be
                  coalesced; the batcher's flush rule bounds this at
                  low load, the queue bound at overload)
      swap_pause  swap request -> completion under load: drain wait for
                  the in-flight batch PLUS the device swap itself

    ``record_batch`` tracks how well the continuous batcher packs the
    bucket ladder: fill ratio = real ids / padded ids, and per-bucket
    occupancy counts. Counters: requests, responses, batches, coalesced
    (requests that shared a batch with another), shed (admission
    refused), timeouts (expired in queue), cache_hits, swaps, errors;
    and, from ``repro.obs.compiles``, the process's jaxpr traces and XLA
    programs built since this telemetry was made (traces, trace_us,
    compiles, compile_us): a warm front door serves with none.
    """

    def __init__(self):
        self.registry = MetricsRegistry()
        self.e2e = self.registry.latency("e2e_ms")
        self.queue_delay = self.registry.latency("queue_delay_ms")
        self.swap_pause = self.registry.latency("swap_pause_ms")
        self.counters = self.registry.counter_set(
            "frontdoor", ("requests", "responses", "batches", "coalesced",
                          "shed", "timeouts", "cache_hits", "swaps",
                          "errors") + tuple(COMPILES.keys()))
        watch_compiles(sink=self.counters)
        # batch-fill ratio: running mean, not a per-batch list
        self._fill_sum = 0.0
        self._fill_n = 0
        self.bucket_counts: dict = {}

    def bump(self, name: str, n: int = 1) -> None:
        self.counters.bump(name, n)

    def record_batch(self, n_requests: int, n_ids: int, n_padded: int,
                     buckets_used) -> None:
        """One dispatched batch: ``n_requests`` coalesced requests
        totalling ``n_ids`` real rows, padded to ``n_padded`` rows
        across ``buckets_used`` ladder rungs."""
        self.counters.bump("batches")
        if n_requests > 1:
            self.counters.bump("coalesced", n_requests)
        self._fill_sum += n_ids / max(n_padded, 1)
        self._fill_n += 1
        for b in buckets_used:
            self.bucket_counts[int(b)] = self.bucket_counts.get(int(b), 0) + 1

    def summary(self) -> dict:
        out = self.counters.as_dict()
        out["e2e_p50_ms"] = round(self.e2e.percentile(50), 3)
        out["e2e_p99_ms"] = round(self.e2e.percentile(99), 3)
        out["queue_delay_p50_ms"] = round(self.queue_delay.percentile(50), 3)
        out["queue_delay_p99_ms"] = round(self.queue_delay.percentile(99), 3)
        out["batch_fill_mean"] = (round(self._fill_sum / self._fill_n, 4)
                                  if self._fill_n else float("nan"))
        out["bucket_counts"] = dict(sorted(self.bucket_counts.items()))
        out["swap_pause_p50_ms"] = round(self.swap_pause.percentile(50), 3)
        out["swap_pause_p99_ms"] = round(self.swap_pause.percentile(99), 3)
        return out


def compile_count(jitted, seen_shapes) -> int:
    """Distinct compiled programs for one jitted fn. Reads jax's own
    executable cache when the private hook exists; otherwise falls back
    to the set of distinct request shapes the session has dispatched
    (equal under the bucket-padding invariant)."""
    cache_size = getattr(jitted, "_cache_size", None)
    if cache_size is not None:
        try:
            return int(cache_size())
        except Exception:  # pragma: no cover - jax internals moved
            pass
    return len(seen_shapes)
