"""Share of the codebook-lookup kernel's device time that its roofline
accounts for: operations and bytes from each call's shapes
(bench/counts.py), time from the trace."""
from bench import counts, readers


def read(run):
    return readers.kernel_roofline(run, "codebook_lookup",
                                   counts.lookup_cost)
