"""Pallas TPU kernel: DLRM dot-interaction, fused products + triangle.

Per example: Z = X X^T over the F feature vectors, of which only the
strictly-lower triangle is kept, compacted to F(F-1)/2 values. XLA
materializes the full [B, F, F] interaction tensor in HBM before the
gather; here each batch tile's triangle is computed in VMEM and only the
compacted tile is written back (≈2x HBM write traffic saved for F=27).

Layout: the batch is the lane dimension. The kernel sees x as
[F, d, Bt] and writes [P, Bt]; triangle row i is the d-reduction of
x[:i] * x[i], stored at sublane offset i(i-1)/2 — static slices only, no
in-kernel gather or reshape, so Mosaic lowers it for any F and d. The
wrapper transposes in and out. Bt must be a multiple of 128 or all of B
on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import resolve_interpret

__all__ = ["dot_interaction_pallas"]


def _kernel(x_ref, out_ref, *, f: int):
    off = 0
    for i in range(1, f):                             # row i: pairs (i, j<i)
        xi = x_ref[i].astype(jnp.float32)             # [d, Bt]
        prods = x_ref[:i].astype(jnp.float32) * xi[None]
        out_ref[off:off + i, :] = prods.sum(axis=1).astype(out_ref.dtype)
        off += i


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def dot_interaction_pallas(x, *, block_b: int = 128, interpret=None):
    """x [B, F, d] -> [B, F(F-1)/2] strictly-lower-triangle interactions,
    in ``np.tril_indices(F, k=-1)`` order."""
    b, f, d = x.shape
    bt = min(block_b, b)
    if b % bt:
        raise ValueError(f"batch {b} not divisible by tile {bt}")
    p = f * (f - 1) // 2
    fn = pl.pallas_call(
        functools.partial(_kernel, f=f),
        grid=(b // bt,),
        in_specs=[pl.BlockSpec((f, d, bt), lambda i: (0, 0, i))],
        out_specs=pl.BlockSpec((p, bt), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((p, b), x.dtype),
        interpret=resolve_interpret(interpret),
    )
    return fn(jnp.transpose(x, (1, 2, 0))).T
