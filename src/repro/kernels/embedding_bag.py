"""Pallas TPU kernel: fused EmbeddingBag (gather + segment-sum).

The recsys lookup hot path: multi-hot field values gather table rows and
reduce per bag. JAX's composite (take + segment_sum) writes the [nnz, d]
gathered rows to HBM before reducing; this kernel accumulates each bag in
VMEM and writes each output row exactly once.

Pattern: grid walks the sorted nnz values; the OUTPUT BlockSpec is driven
by the prefetched segment id, so consecutive values of one bag revisit the
same VMEM output block (Pallas keeps revisited blocks resident — the
canonical TPU segment-reduce pattern). First visit zero-initializes.

Requires segment_ids sorted ascending and every segment id < num_segments.
Empty bags produce zero rows (out is zero-initialized on first visit of
each block; untouched blocks are zeroed by a final fill pass in ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .codebook_lookup import MAX_PREFETCH
from .platform import resolve_interpret

__all__ = ["embedding_bag_pallas"]


def _kernel(seg_ref, val_ref, row_ref, out_ref):
    i = pl.program_id(0)
    prev = seg_ref[jnp.maximum(i - 1, 0)]
    is_first = jnp.logical_or(i == 0, seg_ref[i] != prev)

    @pl.when(is_first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += row_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def embedding_bag_pallas(table, values, segment_ids, *, num_segments: int,
                         interpret=None):
    """table [N, d], values int32 [nnz], sorted segment_ids int32 [nnz]
    -> [num_segments, d] bag sums.

    Both index arrays are scalar-prefetched into SMEM; more than
    ``MAX_PREFETCH`` of them in all are summed in several calls (a bag
    cut by a call boundary adds up across the calls' outputs).
    """
    interpret = resolve_interpret(interpret)
    nnz = values.shape[0]
    chunk = max(1, MAX_PREFETCH // 2)
    out = None
    for lo in range(0, nnz, chunk):
        part = _bag_call(table, values[lo:lo + chunk],
                         segment_ids[lo:lo + chunk], num_segments, interpret)
        out = part if out is None else out + part
    return out


def _bag_call(table, values, segment_ids, num_segments: int,
              interpret: bool):
    nnz = values.shape[0]
    n, d = table.shape
    # [N, 1, d] views: a (1, d) block spans the array's last two dims,
    # which Mosaic accepts for any N and d
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # (segment_ids, values)
        grid=(nnz,),
        in_specs=[
            pl.BlockSpec((None, 1, d),
                         lambda i, seg_ref, val_ref: (val_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, d), lambda i, seg_ref, val_ref:
                               (seg_ref[i], 0, 0)),
    )
    fn = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_segments, 1, d), table.dtype),
        interpret=interpret,
    )
    out = fn(segment_ids, values, table.reshape(n, 1, d)).reshape(
        num_segments, d)
    # zero rows for segments that never appeared (blocks never visited)
    present = jnp.zeros((num_segments,), jnp.bool_).at[segment_ids].set(True)
    return jnp.where(present[:, None], out, 0)
