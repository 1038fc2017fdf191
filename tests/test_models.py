"""Model correctness: LightGCN math, transformer decode==forward, MoE
dispatch equivalence, SchNet invariances."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core.graph import BipartiteGraph
from repro.core.sketch import Sketch
from repro.models import lightgcn as L
from repro.models import schnet as S
from repro.models import transformer as T


# ---------------------------------------------------------------------------
# LightGCN
# ---------------------------------------------------------------------------
def tiny_graph():
    return BipartiteGraph.from_edges(3, 4, [0, 0, 1, 2, 2],
                                     [0, 1, 1, 2, 3])


def test_lightgcn_propagation_matches_dense():
    g = tiny_graph()
    cfg = L.LightGCNConfig(3, 4, dim=8, n_layers=2)
    params = L.init_params(jax.random.PRNGKey(0), cfg)
    statics = L.make_statics(g)
    u, v = L.all_embeddings(params, statics, cfg)
    # dense reference: A_hat propagation, mean over layers
    b = g.biadjacency()
    du = np.maximum(b.sum(1), 1)
    dv = np.maximum(b.sum(0), 1)
    bn = b / np.sqrt(du[:, None] * dv[None, :])
    u0 = np.asarray(params["user_table"])
    v0 = np.asarray(params["item_table"])
    us, vs = [u0], [v0]
    cu, cv = u0, v0
    for _ in range(2):
        cu, cv = bn @ cv, bn.T @ cu
        us.append(cu)
        vs.append(cv)
    assert_allclose(np.asarray(u), np.mean(us, axis=0), rtol=1e-5)
    assert_allclose(np.asarray(v), np.mean(vs, axis=0), rtol=1e-5)


def test_lightgcn_compressed_equals_dense_YZ():
    g = tiny_graph()
    sk = Sketch(np.array([[0, 1], [1, 0], [1, 1]], np.int32),
                np.array([[0], [1], [1], [0]], np.int32), 2, 2)
    cfg = L.from_sketch(g, sk, dim=4, n_layers=0)
    params = L.init_params(jax.random.PRNGKey(1), cfg)
    statics = L.make_statics(g, sk)
    u, v = L.all_embeddings(params, statics, cfg)
    yu = sk.dense_Y_user() @ np.asarray(params["user_table"])
    yv = sk.dense_Y_item() @ np.asarray(params["item_table"])
    assert_allclose(np.asarray(u), yu, rtol=1e-6)
    assert_allclose(np.asarray(v), yv, rtol=1e-6)


def test_bpr_loss_decreases_on_easy_problem():
    g = tiny_graph()
    cfg = L.LightGCNConfig(3, 4, dim=8, n_layers=1)
    params = L.init_params(jax.random.PRNGKey(0), cfg)
    statics = L.make_statics(g)
    batch = {"user": jnp.asarray([0, 1]), "pos": jnp.asarray([0, 1]),
             "neg": jnp.asarray([3, 3])}
    loss = lambda p: L.bpr_loss_fn(p, statics, batch, cfg)
    l0 = float(loss(params))
    for _ in range(50):
        g_ = jax.grad(loss)(params)
        params = jax.tree.map(lambda p, gg: p - 0.1 * gg, params, g_)
    assert float(loss(params)) < l0


B = L.EDGE_BLOCK


def _segment_counts(n_edges, rng):
    """Edges per segment for 9 sorted segments: 0, 4 and 8 empty, segment
    3 holds half the edges (several blocks once n_edges > 4 B)."""
    counts = np.zeros(9, np.int64)
    counts[3] = n_edges // 2
    counts[[1, 2, 5, 6, 7]] = rng.multinomial(n_edges - counts[3],
                                              np.full(5, 0.2))
    return counts


@pytest.mark.parametrize("n_edges", [0, 1, B - 1, B, B + 1, 3 * B + 5,
                                     50_021])
def test_segsum_sorted_matches_float64(n_edges):
    rng = np.random.default_rng(n_edges)
    counts = _segment_counts(n_edges, rng)
    seg = np.repeat(np.arange(9), counts)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    table = rng.standard_normal((64, 8)).astype(np.float32)
    idx = rng.integers(0, 64, n_edges).astype(np.int32)
    w = rng.random(n_edges).astype(np.float32)
    pi, pw = L._pad_edges(jnp.asarray(idx), jnp.asarray(w))
    assert pi.shape[0] % L._edge_block(n_edges) == 0
    got = L._segsum_sorted(jnp.asarray(table)[pi] * pw[:, None],
                           jnp.asarray(indptr))
    ref = np.zeros((9, 8))
    np.add.at(ref, seg, table[idx].astype(np.float64) * w[:, None])
    assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-4)
    assert not np.asarray(got)[[0, 4, 8]].any()


def random_graph(n_users=70, n_items=50, n_edges=4 * B + 9, seed=0):
    rng = np.random.default_rng(seed)
    pairs = rng.choice(n_users * n_items, n_edges, replace=False)
    return BipartiteGraph.from_edges(n_users, n_items, pairs // n_items,
                                     pairs % n_items)


def test_propagation_grad_matches_scatter_fallback():
    g = random_graph()
    assert g.n_edges >= 3 * B
    cfg = L.LightGCNConfig(g.n_users, g.n_items, dim=8, n_layers=3)
    params = L.init_params(jax.random.PRNGKey(0), cfg)
    statics = L.make_statics(g)
    minimal = {k: statics[k] for k in ("edge_u", "edge_v", "edge_norm")}
    rng = np.random.default_rng(1)
    cu = jnp.asarray(rng.standard_normal((g.n_users, 8)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((g.n_items, 8)), jnp.float32)

    def scalar(p, s):
        u, v = L.all_embeddings(p, s, cfg)
        return jnp.sum(u * cu) + jnp.sum(v * cv)

    got = jax.grad(scalar)(params, statics)
    ref = jax.grad(scalar)(params, minimal)
    for name in ("user_table", "item_table"):
        assert_allclose(np.asarray(got[name]), np.asarray(ref[name]),
                        rtol=1e-5, atol=1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_propagation_has_no_interleaved_scan():
    """The forward and the custom-VJP backward hold no interior-padded
    `pad` (the log-depth scan's interleave) and no `concatenate` of
    E + 1 rows (a materialized zero-led prefix)."""
    g = random_graph()
    assert g.n_edges > B
    cfg = L.LightGCNConfig(g.n_users, g.n_items, dim=8, n_layers=2)
    params = L.init_params(jax.random.PRNGKey(0), cfg)
    statics = L.make_statics(g)
    scalar = lambda p: sum(jnp.sum(t) for t in
                           L.all_embeddings(p, statics, cfg))
    padded = -(-g.n_edges // B) * B
    for fn in (scalar, jax.grad(scalar)):
        eqns = list(_eqns(jax.make_jaxpr(fn)(params).jaxpr))
        assert any(e.primitive.name == "dot_general" for e in eqns)
        for e in eqns:
            if e.primitive.name == "pad":
                assert all(i == 0 for _, _, i in e.params["padding_config"])
            if e.primitive.name == "concatenate":
                assert e.outvars[0].aval.shape[0] not in (g.n_edges + 1,
                                                          padded + 1)


# ---------------------------------------------------------------------------
# transformer: decode == full forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pattern,window", [(("global",), 64),
                                            (("local", "global"), 8)])
def test_decode_matches_forward(pattern, window):
    cfg = T.TransformerConfig(
        name="t", n_layers=2 * len(pattern), d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=64, vocab_size=97, block_pattern=pattern,
        window=window, dtype="float32", q_chunk=4, loss_chunk=4,
        remat=False)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    s = 12
    tokens = jnp.asarray(rng.integers(0, 97, (2, s + 1)), jnp.int32)
    # reference: full forward over s+1 tokens, logits at the last position
    positions = jnp.broadcast_to(jnp.arange(s + 1), (2, s + 1))
    h = T._backbone(params, tokens, cfg, positions)
    ref_logits = T._logits(params, h[:, -1:], cfg)[:, 0]
    # prefill s tokens, then decode token s
    _, cache = T.prefill(params, {"tokens": tokens[:, :s]}, cfg,
                         max_seq=s + 4)
    dec_logits, _ = T.decode_step(
        params, cache, {"tokens": tokens[:, s:s + 1],
                        "pos": jnp.int32(s)}, cfg)
    assert_allclose(np.asarray(dec_logits), np.asarray(ref_logits),
                    rtol=2e-4, atol=2e-4)


def test_banded_local_attention_matches_masked_full():
    """chunked banded attention == full attention with a window mask."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 32, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 32, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 32, 2, 16)), jnp.float32)
    banded = T.chunked_attention(q, k, v, window=8, q_chunk=8)
    full = T.chunked_attention(q, k, v, window=8, q_chunk=32)
    assert_allclose(np.asarray(banded), np.asarray(full), rtol=1e-5,
                    atol=1e-6)


def test_moe_local_matches_gspmd_path():
    """shard_map expert-local dispatch == plain dispatch on a 1x1 mesh."""
    cfg = T.TransformerConfig(
        name="m", n_layers=1, d_model=16, n_heads=2, n_kv_heads=2,
        d_ff=32, vocab_size=64, moe=T.MoEConfig(4, 2, capacity_factor=4.0),
        dtype="float32", q_chunk=4, loss_chunk=4, remat=False)
    params = T.init_params(jax.random.PRNGKey(2), cfg)
    batch = {"tokens": jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32),
             "targets": jnp.asarray([[2, 3, 4, 5, 6, 7, 8, 9]], jnp.int32)}
    loss_plain = T.train_loss(params, batch, cfg)          # no mesh
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with mesh:
        loss_local = jax.jit(
            lambda p, b: T.train_loss(p, b, cfg))(params, batch)
    assert_allclose(float(loss_plain), float(loss_local), rtol=1e-5)


def test_kv_cache_dtype_fp8_roundtrip():
    cfg = T.TransformerConfig(
        name="f8", n_layers=1, d_model=16, n_heads=2, n_kv_heads=2,
        d_ff=32, vocab_size=64, dtype="float32",
        kv_cache_dtype="float8_e4m3fn", q_chunk=4, loss_chunk=4,
        remat=False)
    cache = T.init_cache(cfg, batch=1, max_seq=8)
    assert cache["k_global"].dtype == jnp.float8_e4m3fn
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    logits, cache2 = T.decode_step(
        params, cache, {"tokens": jnp.asarray([[5]], jnp.int32),
                        "pos": jnp.int32(0)}, cfg)
    assert bool(jnp.isfinite(logits).all())
    assert cache2["k_global"].dtype == jnp.float8_e4m3fn


def test_param_count_matches_shapes():
    cfg = T.TransformerConfig(name="c", n_layers=2, d_model=16, n_heads=2,
                              n_kv_heads=1, d_ff=32, vocab_size=64,
                              dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    total = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert total == T.count_params(cfg)


# ---------------------------------------------------------------------------
# SchNet invariances
# ---------------------------------------------------------------------------
def test_schnet_edge_permutation_invariant():
    cfg = S.SchNetConfig(n_interactions=2, d_hidden=8, n_rbf=4)
    params = S.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    n, e = 10, 24
    batch = {"z": jnp.asarray(rng.integers(1, 10, n), jnp.int32),
             "edge_src": jnp.asarray(rng.integers(0, n, e), jnp.int32),
             "edge_dst": jnp.asarray(rng.integers(0, n, e), jnp.int32),
             "edge_dist": jnp.asarray(rng.random(e) * 4, jnp.float32),
             "graph_id": jnp.zeros(n, jnp.int32)}
    e1 = S.energy(params, batch, cfg, n_graphs=1)
    perm = rng.permutation(e)
    batch2 = {**batch,
              "edge_src": batch["edge_src"][perm],
              "edge_dst": batch["edge_dst"][perm],
              "edge_dist": batch["edge_dist"][perm]}
    e2 = S.energy(params, batch2, cfg, n_graphs=1)
    assert_allclose(np.asarray(e1), np.asarray(e2), rtol=1e-5)


def test_schnet_cutoff_zeroes_long_edges():
    cfg = S.SchNetConfig(n_interactions=1, d_hidden=8, n_rbf=4, cutoff=2.0)
    params = S.init_params(jax.random.PRNGKey(0), cfg)
    base = {"z": jnp.asarray([1, 2, 3], jnp.int32),
            "edge_src": jnp.asarray([0, 1], jnp.int32),
            "edge_dst": jnp.asarray([1, 2], jnp.int32),
            "graph_id": jnp.zeros(3, jnp.int32)}
    e_short = S.energy(params, {**base, "edge_dist":
                                jnp.asarray([1.0, 1.0], jnp.float32)},
                       cfg, n_graphs=1)
    # edges beyond cutoff contribute nothing == no edges at all
    e_long = S.energy(params, {**base, "edge_dist":
                               jnp.asarray([5.0, 9.0], jnp.float32)},
                      cfg, n_graphs=1)
    e_none = S.energy(params, {**base,
                               "edge_src": jnp.asarray([0, 0], jnp.int32),
                               "edge_dst": jnp.asarray([0, 0], jnp.int32),
                               "edge_dist": jnp.asarray([9.0, 9.0],
                                                        jnp.float32)},
                      cfg, n_graphs=1)
    assert_allclose(np.asarray(e_long), np.asarray(e_none), rtol=1e-5)
    assert not np.allclose(np.asarray(e_short), np.asarray(e_long))
