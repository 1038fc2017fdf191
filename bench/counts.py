"""Operations and bytes the algorithm needs, from shapes, and the chip
peaks they are held against.

Counts are of the algorithm, not of the compiled program: padding,
recomputation and layout copies do not count. A multiply and an add
are two operations.
"""
from __future__ import annotations

import re

__all__ = ["PEAKS", "peaks", "expansion_flops", "propagation_flops",
           "score_flops", "serve_dispatch_flops", "train_step_flops",
           "lookup_cost", "roofline_s"]

# Published per-chip peaks, keyed by jax's ``device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,          # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def expansion_flops(n_rows: int, n_hot: int, dim: int) -> float:
    """Σ_h Z[idx[i, h]] for every row: (n_hot - 1) adds per element."""
    return float(n_rows) * (n_hot - 1) * dim


def propagation_flops(n_users: int, n_items: int, n_edges: int, dim: int,
                      n_layers: int) -> float:
    """LightGCN propagation and layer mean. Per layer and side, each edge
    scales a row (d multiplies) and adds it into its segment (d adds);
    each layer is added into the running sum, and the sum is divided by
    K + 1 once."""
    rows = float(n_users + n_items) * dim
    return n_layers * (4.0 * n_edges * dim + rows) + rows


def score_flops(n_users: int, n_items: int, dim: int) -> float:
    """Dense scores of ``n_users`` users against every item."""
    return 2.0 * n_users * n_items * dim


def serve_dispatch_flops(shapes: dict, n_users: int) -> float:
    """One serving dispatch that answers ``n_users`` users: both tables
    expanded, propagated, and the users scored against every item (top-k
    selection is comparisons, not counted)."""
    d = shapes["dim"]
    return (expansion_flops(shapes["n_users"], shapes["n_hot_users"], d)
            + expansion_flops(shapes["n_items"], 1, d)
            + propagation_flops(shapes["n_users"], shapes["n_items"],
                                shapes["n_edges"], d, shapes["n_layers"])
            + score_flops(n_users, shapes["n_items"], d))


# per parameter: m (3), v (4), the bias-corrected update (7)
ADAM_FLOPS_PER_PARAM = 14


def train_step_flops(shapes: dict, batch: int) -> float:
    """One BPR step. Forward: both tables expanded and propagated, then
    per sample two d-long dot products (4d), the log-sigmoid of their
    difference (3) and the squared L2 of three ego rows (6d). Backward:
    the adjoint of each linear map costs what the map costs (propagation,
    expansion's scatter back into the codebook), and the readout twice
    its forward. Adam over every codebook entry."""
    d = shapes["dim"]
    expand = (expansion_flops(shapes["n_users"], shapes["n_hot_users"], d)
              + expansion_flops(shapes["n_items"], 1, d))
    prop = propagation_flops(shapes["n_users"], shapes["n_items"],
                             shapes["n_edges"], d, shapes["n_layers"])
    readout = batch * (10.0 * d + 3.0)
    params = (shapes["k_users"] + shapes["k_items"]) * d
    return 2 * expand + 2 * prop + 3 * readout + ADAM_FLOPS_PER_PARAM * params


_LOOKUP = re.compile(r"= f32\[(\d+),(\d+)\]\S* custom-call\(s32\[(\d+)\]")


def lookup_cost(hlo_text: str, itemsize: int = 4):
    """(operations, bytes) of one codebook-lookup kernel call, from the
    shapes in its HLO text: an index of ``n_idx`` entries gathers
    ``n_idx`` codebook rows of ``d`` and writes ``rows`` output rows.
    Bytes: the index, each gathered row once and the output once."""
    m = _LOOKUP.search(hlo_text)
    if m is None:
        raise ValueError(f"not a codebook lookup call: {hlo_text[:120]}")
    rows, d, n_idx = (int(g) for g in m.groups())
    flops = float(n_idx - rows) * d
    nbytes = 4.0 * n_idx + itemsize * float(n_idx) * d + itemsize * rows * d
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
