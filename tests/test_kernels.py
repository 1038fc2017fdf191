"""Per-kernel allclose vs pure-jnp oracle, sweeping shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k,d,b,h", [(16, 128, 8, 1), (64, 128, 32, 2),
                                     (128, 64, 16, 2), (256, 256, 4, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_codebook_lookup(k, d, b, h, dtype):
    cb = jnp.asarray(RNG.standard_normal((k, d)), dtype)
    idx = jnp.asarray(RNG.integers(0, k, (b, h)), jnp.int32)
    out = ops.codebook_lookup(cb, idx)
    assert out.shape == (b, d) and out.dtype == dtype
    assert_allclose(np.asarray(out, np.float32),
                    np.asarray(ref.codebook_lookup(cb, idx), np.float32),
                    **_tol(dtype))


@pytest.mark.parametrize("n,d,nnz,nseg", [(50, 128, 64, 12), (10, 64, 5, 3),
                                          (200, 128, 256, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag(n, d, nnz, nseg, dtype):
    table = jnp.asarray(RNG.standard_normal((n, d)), dtype)
    vals = jnp.asarray(RNG.integers(0, n, nnz), jnp.int32)
    segs = jnp.asarray(np.sort(RNG.integers(0, nseg, nnz)), jnp.int32)
    out = ops.embedding_bag(table, vals, segs, nseg)
    assert out.shape == (nseg, d)
    assert_allclose(np.asarray(out, np.float32),
                    np.asarray(ref.embedding_bag(table, vals, segs, nseg),
                               np.float32), **_tol(dtype))


def test_embedding_bag_empty_segments():
    table = jnp.ones((10, 8), jnp.float32)
    vals = jnp.asarray([1, 2, 3], jnp.int32)
    segs = jnp.asarray([0, 0, 4], jnp.int32)  # segments 1-3 empty
    out = ops.embedding_bag(table, vals, segs, 6)
    assert_allclose(np.asarray(out[1:4]), 0.0)
    assert_allclose(np.asarray(out[0]), 2.0)
    assert_allclose(np.asarray(out[4]), 1.0)
    assert_allclose(np.asarray(out[5]), 0.0)


def test_prefetch_split_matches_reference(monkeypatch):
    """Indices longer than MAX_PREFETCH (SMEM) are split over several
    kernel calls; the result is the one-call result."""
    import repro.kernels.codebook_lookup as cl
    import repro.kernels.embedding_bag as eb
    import repro.kernels.fused_topk as ft
    for mod in (cl, eb, ft):
        monkeypatch.setattr(mod, "MAX_PREFETCH", 16)
    cb = jnp.asarray(RNG.standard_normal((20, 8)), jnp.float32)
    idx = RNG.integers(0, 20, (37, 2)).astype(np.int32)
    idx[::3, 1] = idx[::3, 0]                 # binary-Y duplicates
    out = cl.codebook_lookup_pallas(cb, jnp.asarray(idx), binary=True)
    assert_allclose(np.asarray(out),
                    np.asarray(ref.codebook_lookup_dedup(cb, idx)),
                    **_tol(jnp.float32))
    vals = jnp.asarray(RNG.integers(0, 20, 40), jnp.int32)
    segs = jnp.asarray(np.sort(RNG.integers(0, 9, 40)), jnp.int32)
    assert_allclose(np.asarray(eb.embedding_bag_pallas(
        cb, vals, segs, num_segments=9)),
        np.asarray(ref.embedding_bag(cb, vals, segs, 9)),
        **_tol(jnp.float32))
    u = jnp.asarray(RNG.standard_normal((3, 8)), jnp.float32)
    for n in (45, 43):                        # 43: a short tail call
        sk = jnp.asarray(RNG.integers(0, 20, (n, 2)), jnp.int32)
        got = ft.fused_topk_codebook_pallas(u, cb, sk, 5, block=8)
        want = ref.fused_topk(u, cb, 5, sketch=sk)
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
        assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("b,f,d,bt", [(8, 27, 128, 4), (16, 27, 128, 16),
                                      (4, 8, 32, 2), (8, 41, 16, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dot_interaction(b, f, d, bt, dtype):
    x = jnp.asarray(RNG.standard_normal((b, f, d)), dtype)
    out = ops.dot_interaction(x, block_b=bt)
    assert out.shape == (b, f * (f - 1) // 2)
    assert_allclose(np.asarray(out, np.float32),
                    np.asarray(ref.dot_interaction(x), np.float32),
                    rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                    atol=5e-1 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("b,h,s,d,bq,bk", [
    (1, 1, 128, 64, 64, 64), (2, 2, 256, 64, 64, 128),
    (1, 2, 256, 128, 128, 64), (2, 1, 512, 32, 128, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, h, s, d, bq, bk, causal, dtype):
    q = jnp.asarray(RNG.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, h, s, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, h, s, d)), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    r = ref.mha(q, k, v, causal=causal)
    assert_allclose(np.asarray(out, np.float32),
                    np.asarray(r, np.float32), **_tol(dtype))


def test_flash_attention_matches_model_path():
    """Kernel vs the model's chunked_attention (banded path, no window)."""
    from repro.models.transformer import chunked_attention
    q = jnp.asarray(RNG.standard_normal((2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 128, 4, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 128, 4, 32)), jnp.float32)
    model_out = chunked_attention(q, k, v, q_chunk=64)       # [B,S,H,D]
    kern_out = ops.flash_attention(q.transpose(0, 2, 1, 3),
                                   k.transpose(0, 2, 1, 3),
                                   v.transpose(0, 2, 1, 3),
                                   causal=True, block_q=64, block_k=64)
    assert_allclose(np.asarray(kern_out),
                    np.asarray(model_out.transpose(0, 2, 1, 3)),
                    rtol=2e-4, atol=2e-5)
