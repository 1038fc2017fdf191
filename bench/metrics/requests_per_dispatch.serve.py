"""Requests answered per dispatch in the window, from the front door's
counters."""


def read(run):
    batches = run.counters.get("batches", 0)
    return run.counters["responses"] / batches if batches else None
