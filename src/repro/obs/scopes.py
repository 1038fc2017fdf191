"""The model's named scopes: one fixed set of layer names for device time.

Call sites wrap their part of a jitted program in
``jax.named_scope(NAME)``. That changes HLO metadata only (each op's
``op_name`` path, e.g. ``jit(score_topk)/score/propagate/mul`` or
``jit(chunk)/while/body/transpose(jvp(propagate))/...``), never the ops,
and profiles carry the path beside every device op. ``scope_of`` is the
attribution rule: an op belongs to the innermost of these names on its
path, and to ``OTHER`` when none is there.

This module imports nothing from jax, like the rest of ``repro.obs``.
"""
from __future__ import annotations

import re

__all__ = ["LOOKUP", "PROPAGATE", "SCORE", "TOPK", "LOSS", "OPTIMIZER",
           "SAMPLE", "SCOPES", "OTHER", "scope_of"]

LOOKUP = "lookup"          # codebook expansion (lightgcn._base_embeddings)
PROPAGATE = "propagate"    # the LightGCN layer loop, forward and backward
SCORE = "score"            # user-item scores and the mask, or fused top-k
TOPK = "topk"              # lax.top_k over the dense scores
LOSS = "loss"              # the BPR readout gathers and loss
OPTIMIZER = "optimizer"    # the optimizer's update
SAMPLE = "sample"          # the on-device BPR sampler
SCOPES = (LOOKUP, PROPAGATE, SCORE, TOPK, LOSS, OPTIMIZER, SAMPLE)
OTHER = "other"

_NAMES = frozenset(SCOPES)
_WORD = re.compile(r"\w+")


def scope_of(op_path: str) -> str:
    """The innermost scope name on ``op_path``, else ``OTHER``.

    Path components nest left to right, and so do the transform wrappers
    inside one component (``transpose(jvp(propagate))``), so the last
    whole word that is a scope name wins. ``score_topk`` is not
    ``score``: only whole words count."""
    for word in reversed(_WORD.findall(op_path or "")):
        if word in _NAMES:
            return word
    return OTHER
