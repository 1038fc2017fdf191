"""Programs JAX built (compiled, or loaded from the persistent cache) in
the window, from the front door's ``compiles`` counter
(``repro.obs.compiles``); None where the program has no such counter."""


def read(run):
    return run.counters.get("compiles")
