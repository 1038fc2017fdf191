"""Pallas TPU kernel: fused multi-hot codebook lookup (the SCU hot path).

Serving/training retrieves  e_i = Σ_h Z[sketch[i, h]]  for a batch of ids
(paper §3.2/§4.5: H=1 plain clusters, H=2 with secondary user clusters).
A naive XLA lowering issues H separate gathers plus an add, touching the
output twice. This kernel uses scalar-prefetched sketch indices to DMA the
H codebook rows for each output tile straight into VMEM and writes the
combined tile once.

Layout: the codebook is passed ONCE and stays in HBM; the grid is
(B/rows_per_step, rows_per_step, H) — per grid step the input BlockSpec
index_map (driven by the prefetched indices) pulls exactly one needed
codebook row, while the OUTPUT block covers ``rows_per_step`` rows and is
revisited for every (row, h) step of its tile (Pallas keeps revisited
blocks resident), so each output tile is written back to HBM exactly once.
The embedding dim is the lane dimension (pad to 128 for peak DMA
efficiency; any d is accepted). The codebook is passed as a [K, 1, d]
view so that a one-row block spans the array's last two dims, and the
sketch indices are prefetched flat (1-D SMEM pads nothing to 128 lanes);
a batch whose index exceeds ``MAX_PREFETCH`` entries is split into
several calls.

``binary=True`` applies the paper's binary-Y rule in-kernel: a duplicate
sketch index (e.g. SCU falling back to the primary cluster) contributes
once, not twice. The duplicate test reads the prefetched scalars, so no
extra tensor input is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import resolve_interpret

__all__ = ["codebook_lookup_pallas", "MAX_PREFETCH"]

# Scalar-prefetched indices live in SMEM (1 MiB on TPU v5e). A 1-D int32
# operand of this many entries takes 512 KiB; a batch whose flattened
# index is longer is split into several kernel calls.
MAX_PREFETCH = 1 << 17


def _kernel(idx_ref, row_ref, out_ref, *, n_hot: int, rows_per_step: int,
            binary: bool):
    i = pl.program_id(0)
    r = pl.program_id(1)
    h = pl.program_id(2)
    row = i * rows_per_step + r

    @pl.when(h == 0)
    def _():
        out_ref[r, :] = jnp.zeros_like(out_ref[r, :])

    contrib = row_ref[0, :].astype(out_ref.dtype)
    if binary and n_hot > 1:
        cur = idx_ref[row * n_hot + h]
        dup = jnp.zeros((), jnp.bool_)
        for j in range(n_hot - 1):        # j < h <= n_hot-1
            dup = dup | ((j < h) & (idx_ref[row * n_hot + j] == cur))
        contrib = jnp.where(dup, jnp.zeros_like(contrib), contrib)
    out_ref[r, :] += contrib


def codebook_lookup_pallas(codebook, idx, *, binary: bool = False,
                           rows_per_step: int = 8, interpret=None):
    """codebook [K, d], idx int32 [B, H] -> [B, d].

    The H row-blocks of each output row are prefetched via the scalar idx
    so the DMA pipeline overlaps fetch (row i+1, h) with compute of row i;
    rows_per_step output rows share one VMEM-resident output block.

    ``interpret=None`` compiles on TPU and interprets everywhere else
    (``resolve_interpret``); resolution happens outside the jitted impl.
    """
    return _codebook_lookup_jit(codebook, idx, binary=binary,
                                rows_per_step=rows_per_step,
                                interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("binary", "rows_per_step", "interpret"))
def _codebook_lookup_jit(codebook, idx, *, binary: bool,
                         rows_per_step: int, interpret: bool):
    b, h = idx.shape
    r = max(1, min(rows_per_step, b))
    # rows per kernel call: a multiple of r whose flat index fits SMEM
    chunk = max(r, (MAX_PREFETCH // h) // r * r)
    outs = [_lookup_call(codebook, idx[lo:lo + chunk], binary=binary, r=r,
                         interpret=interpret)
            for lo in range(0, b, chunk)]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def _lookup_call(codebook, idx, *, binary: bool, r: int, interpret: bool):
    b, h = idx.shape
    k, d = codebook.shape
    b_pad = -(-b // r) * r
    idx_padded = idx if b_pad == b else jnp.pad(idx, ((0, b_pad - b), (0, 0)))

    # [K, 1, d] view: a (1, d) block then spans the array's last two dims,
    # which Mosaic accepts for any K and d
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b_pad // r, r, h),
        in_specs=[
            pl.BlockSpec((None, 1, d), functools.partial(
                lambda i, rr, hh, idx_ref, r_, h_:
                (idx_ref[(i * r_ + rr) * h_ + hh], 0, 0), r_=r, h_=h)),
        ],
        out_specs=pl.BlockSpec((r, d), lambda i, rr, hh, idx_ref: (i, 0)),
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, n_hot=h, rows_per_step=r, binary=binary),
        grid_spec=grid_spec,
        # f32 accumulation: also keeps the per-row stores 32-bit, which
        # Mosaic can place at any row of the block
        out_shape=jax.ShapeDtypeStruct((b_pad, d), jnp.float32),
        interpret=interpret,
    )
    out = fn(idx_padded.reshape(-1), codebook.reshape(k, 1, d))
    return (out if b_pad == b else out[:b]).astype(codebook.dtype)
