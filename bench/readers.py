"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Each reader takes a finished ``harness.Run`` and returns a number, or
None where the run holds nothing to read (an untraced run, a device
without a peak, no such span or kernel in the window).
"""
from __future__ import annotations

import numpy as np

from bench import counts
from bench.trace import kernel_ops

__all__ = ["span_ms", "device_idle", "kernel_roofline", "serve_mfu",
           "train_mfu"]


def span_ms(run, name: str, stat: str):
    """p50 ("p50") or mean ("mean") of the program's ``name`` spans in
    the window, in milliseconds."""
    ms = [(t1 - t0) * 1e3 for n, t0, t1 in run.spans if n == name]
    if not ms:
        return None
    return float(np.percentile(ms, 50) if stat == "p50" else np.mean(ms))


def device_idle(run):
    """Percent of the traced window in which no operation ran on the
    device."""
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def kernel_roofline(run, pattern: str, cost):
    """Percent of a kernel's device time that its roofline accounts for:
    Σ least time (``cost(hlo_text)`` -> operations, bytes) over Σ device
    time of its calls in the window."""
    calls = kernel_ops(run.trace, pattern)
    if not calls or run.peak is None:
        return None
    least = sum(counts.roofline_s(*cost(text), run.peak)
                for _, _, text in calls)
    return 100.0 * least / (sum(d for _, d, _ in calls) / 1e9)


def _mfu(run, flops: float):
    if run.peak is None or not run.window_s > 0:
        return None
    return 100.0 * flops / run.window_s / run.peak["flops_per_s"]


def serve_mfu(run):
    """Percent of the chip's peak that the window's serving work needs:
    every dispatch expands and propagates both tables, and each answered
    user is scored against every item."""
    dispatches = run.counters.get("batches", 0)
    if not dispatches:
        return None
    s = run.shapes
    flops = (dispatches * counts.serve_dispatch_flops(s, 0)
             + counts.score_flops(run.work["users"], s["n_items"], s["dim"]))
    return _mfu(run, flops)


def train_mfu(run):
    """Percent of the chip's peak that the window's BPR steps need."""
    steps = run.work.get("steps", 0)
    if not steps:
        return None
    return _mfu(run, steps * counts.train_step_flops(run.shapes,
                                                     run.shapes["batch"]))
