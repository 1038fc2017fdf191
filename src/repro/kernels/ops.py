"""Pallas kernels: jit'd public wrappers + the "pallas" lookup backend.

Each wrapper's ``interpret=None`` resolves by platform
(``platform.resolve_interpret``): Mosaic-compiled on TPU, Pallas
interpret mode everywhere else (the CPU test runs).

Importing this module registers the "pallas" backend into the
EmbeddingEngine registry (repro.embedding.engine) — the engine defers
that import until a pallas lookup is first requested, so the embedding
layer never drags Pallas in eagerly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.embedding.engine import (LookupBackend, bag_combine,
                                    register_backend, register_scorer)

from . import ref
from .codebook_lookup import codebook_lookup_pallas
from .embedding_bag import embedding_bag_pallas
from .dot_interaction import dot_interaction_pallas
from .flash_attention import flash_attention_pallas
from .fused_topk import fused_topk_codebook_pallas, fused_topk_pallas
from .platform import resolve_interpret as _interpret

__all__ = ["codebook_lookup", "embedding_bag", "dot_interaction",
           "flash_attention", "fused_topk", "PallasBackend"]


def codebook_lookup(codebook, idx, *, binary=False, rows_per_step=8,
                    interpret=None):
    return codebook_lookup_pallas(codebook, idx, binary=binary,
                                  rows_per_step=rows_per_step,
                                  interpret=interpret)


def embedding_bag(table, values, segment_ids, num_segments, *,
                  interpret=None):
    return embedding_bag_pallas(table, values, segment_ids,
                                num_segments=num_segments,
                                interpret=interpret)


def dot_interaction(x, *, block_b=128, interpret=None):
    return dot_interaction_pallas(x, block_b=block_b, interpret=interpret)


def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128,
                    interpret=None):
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interpret)


def fused_topk(u, items, k, *, sketch=None, scale=None, mask=None,
               exclude=None, block=512, interpret=None):
    """The "pallas" fused scorer (see repro.embedding.fused_topk for the
    dispatching public entry). Serving-forward only — no VJP.

    The exclusion scatter inside the kernel does not lower under Mosaic,
    so exclusions on a compiled platform raise. Serving excludes nothing
    and masks via ``mask``, which compiles; evaluation with exclusions
    uses ``training.eval.topk_streaming``'s "block" backend."""
    interpret = _interpret(interpret)
    has_excl = exclude is not None and len(exclude[0]) > 0
    if has_excl and not interpret:
        raise NotImplementedError(
            "the compiled fused scorer cannot apply exclusion pairs; use "
            "topk_streaming(backend='block') or interpret mode")
    excl = exclude if has_excl else None
    if sketch is not None:
        return fused_topk_codebook_pallas(u, items, sketch, k, scale=scale,
                                          mask=mask, exclude=excl,
                                          block=min(int(block), 512),
                                          interpret=interpret)
    return fused_topk_pallas(u, items, k, scale=scale, mask=mask,
                             exclude=excl, block=block, interpret=interpret)


def _fused_topk_ref(u, items, k, *, sketch=None, scale=None, mask=None,
                    exclude=None, block=None, interpret=None):
    # block/interpret are dispatch-level knobs with no meaning here
    return ref.fused_topk(u, items, k, sketch=sketch, scale=scale,
                          mask=mask, exclude=exclude)


register_scorer("pallas", fused_topk)
register_scorer("ref", _fused_topk_ref)


# ---------------------------------------------------------------------------
# EmbeddingEngine backend registration
# ---------------------------------------------------------------------------
def _codebook_sum_vjp(codebook, flat_idx, keep_flat, binary):
    """Kernel forward + pure-jnp scatter-add backward (pallas_call has no
    autodiff rule; the gradient w.r.t. the codebook is a segment-sum of
    the output cotangent into the looked-up rows, masked by the same
    binary-Y keep mask the kernel applies)."""
    k, d = codebook.shape
    dtype = codebook.dtype

    @jax.custom_vjp
    def fn(cb):
        return codebook_lookup(cb, flat_idx, binary=binary)

    def fwd(cb):
        return fn(cb), None

    def bwd(_, g):                                     # g [B, d]
        gg = jnp.broadcast_to(g[:, None, :], (*flat_idx.shape, d))
        gg = jnp.where(keep_flat[..., None], gg, 0)
        dcb = jax.ops.segment_sum(gg.reshape(-1, d),
                                  flat_idx.reshape(-1), num_segments=k)
        return (dcb.astype(dtype),)

    fn.defvjp(fwd, bwd)
    return fn(codebook)


class PallasBackend(LookupBackend):
    """Fused TPU kernels; interpret mode off-TPU so the parity tests
    (tests/test_engine.py) run on CPU. Forward runs the kernel;
    backward is a pure-jnp scatter-add via custom_vjp, so the backend is
    usable inside jax.grad (training through compressed tables)."""
    name = "pallas"
    supports_bag_weights = False      # no per-value scaling in the kernel
    requires_sorted_bags = True       # first-visit detection via seg[i-1]

    def full(self, table, ids):
        flat = ids.reshape(-1)[:, None]                    # [B, 1]
        keep = jnp.ones(flat.shape, bool)
        out = _codebook_sum_vjp(table, flat, keep, binary=False)
        return out.reshape(*ids.shape, table.shape[-1])

    def codebook_sum(self, codebook, rows_idx, keep):
        # the kernel applies the binary-Y rule itself from the prefetched
        # scalars (same first-occurrence rule as `keep`)
        h = rows_idx.shape[-1]
        out = _codebook_sum_vjp(codebook, rows_idx.reshape(-1, h),
                                keep.reshape(-1, h), binary=True)
        return out.reshape(*rows_idx.shape[:-1], codebook.shape[-1])

    def bag(self, table, values, segment_ids, num_segments, mode="sum",
            weights=None):
        if weights is not None:
            raise NotImplementedError(
                "pallas embedding_bag has no per-value weights; the engine "
                "falls back to the gather backend for weighted bags")
        n, d = table.shape
        dtype = table.dtype

        @jax.custom_vjp
        def fn(t):
            return embedding_bag(t, values, segment_ids, num_segments)

        def fwd(t):
            return fn(t), None

        def bwd(_, g):                                 # g [num_segments, d]
            dt = jax.ops.segment_sum(jnp.take(g, segment_ids, axis=0),
                                     values, num_segments=n)
            return (dt.astype(dtype),)

        fn.defvjp(fwd, bwd)
        out = fn(table)
        return bag_combine(out, values, segment_ids, num_segments, mode)


register_backend(PallasBackend())
