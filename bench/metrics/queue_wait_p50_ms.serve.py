"""Median of the admission queue spans (submit to dispatch) in the
window, in milliseconds."""
from bench import readers


def read(run):
    return readers.span_ms(run, "queue", "p50")
