"""Mean of the front door's dispatch spans in the window (each ends when
the batch's answers are on the host), in milliseconds."""
from bench import readers


def read(run):
    return readers.span_ms(run, "dispatch", "mean")
