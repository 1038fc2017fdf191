"""Platform-aware interpret/compile selection for every Pallas kernel.

On TPU the kernels compile through Mosaic; on any other platform (CPU
test runs, GPU) they run in Pallas interpret mode, a correctness path
and not a performance one. An explicit ``interpret=`` kwarg wins over
the platform default; tests use it to pin either mode.
"""
from __future__ import annotations

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(override=None) -> bool:
    """True -> run the kernel interpreted; False -> compile (Mosaic)."""
    if override is not None:
        return bool(override)
    return jax.default_backend() != "tpu"
