"""Pallas TPU kernel: FlashAttention (blocked online-softmax attention).

The LM-arch hot spot (train + prefill). Grid (batch*heads, q_blocks,
kv_blocks); the kv axis is the innermost (sequential) dimension, with the
running max / denominator / weighted accumulator held in VMEM scratch so
the [S, S] score matrix never exists. Causal blocks above the diagonal
are skipped entirely (@pl.when), halving work for causal attention.

Block sizes default to (128, 128): q/k/v tiles of 128x d with d<=256 keep
VMEM usage ≈ (3*128*d + 128*128 + 128*d)*4B < 1 MB, leaving headroom for
double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import resolve_interpret

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, scale: float, block_q: int, block_k: int,
            n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip blocks strictly above the causal diagonal
    run = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot(p, v,
                                      preferred_element_type=jnp.float32))
        m_ref[...] = m_cur

    @pl.when(ki == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                              "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           interpret=None):
    """q/k/v [B, H, S, d] -> [B, H, S, d]."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, "seq not divisible by block"
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, skv, d)
    vr = v.reshape(b * h, skv, d)
    n_kv = skv // bk
    fn = pl.pallas_call(
        functools.partial(_kernel, causal=causal, scale=1.0 / np.sqrt(d),
                          block_q=bq, block_k=bk, n_kv=n_kv),
        grid=(b * h, sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )
    return fn(qr, kr, vr).reshape(b, h, sq, d)
