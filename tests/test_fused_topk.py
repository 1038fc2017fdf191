"""Fused Pallas serving scorer: kernel-vs-oracle parity (exact ids,
ties included), int8 quantization bounds + Recall@20 delta, the scan
rewrite of topk_streaming (bitwise pin vs the hostloop), the session
scorer knob (fused == dense ids, swap adds zero compiles), and the
bench_summary --check regression gate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baco_build
from repro.data import planted_coclusters
from repro.embedding import (dequantize_int8_rows, dequantize_params,
                             fused_topk, quantize_int8_rows,
                             quantize_params)
from repro.kernels import ops, ref
from repro.kernels.fused_topk import select_topk
from repro.kernels.platform import resolve_interpret
from repro.serve import CompressedArtifact
from repro.training import Trainer, TrainConfig
from repro.training.eval import (recall_ndcg_at_k, topk_from_scores,
                                 topk_streaming)


@pytest.fixture(scope="module")
def trained():
    graph, _, _ = planted_coclusters(n_users=150, n_items=110, k_true=6,
                                     avg_deg=8, seed=0)
    sketch = baco_build(graph, d=8, ratio=0.3)
    tr = Trainer(graph, sketch,
                 TrainConfig(dim=8, steps=5, batch_size=64, lr=1e-2))
    tr.run(log_every=0)
    return tr


def _rand(shape, seed=0, dtype=np.float32):
    return np.random.default_rng(seed).standard_normal(shape).astype(dtype)


def _assert_matches_ref(got, want):
    vals, ids = got
    rvals, rids = want
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# select_topk: the in-kernel top-k primitive
# ---------------------------------------------------------------------------
def test_select_topk_matches_lax_topk_with_ties():
    rng = np.random.default_rng(0)
    # quantize scores to few distinct values so ties are everywhere;
    # keep zero out of the palette — select_topk compares with IEEE
    # equality (-0.0 == +0.0) while lax.top_k's total order splits them
    s = np.round(rng.standard_normal((7, 31)) * 2) / 2
    s = jnp.asarray(np.where(s == 0, 5.0, s), jnp.float32)
    ids = jnp.broadcast_to(jnp.arange(31, dtype=jnp.int32)[None, :],
                           s.shape)
    for k in (1, 5, 31):
        vals, got = select_topk(s, ids, k)
        rvals, rids = jax.lax.top_k(s, k)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(rids))
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))


def test_select_topk_all_neg_inf_rows():
    s = jnp.full((3, 6), -jnp.inf, jnp.float32)
    ids = jnp.broadcast_to(jnp.arange(6, dtype=jnp.int32)[None, :], s.shape)
    _, got = select_topk(s, ids, 4)
    _, rids = jax.lax.top_k(s, 4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(rids))


# ---------------------------------------------------------------------------
# fused kernel vs dense oracle (exact ids, ties included)
# ---------------------------------------------------------------------------
def test_fused_dense_parity_with_ties():
    u = jnp.asarray(_rand((9, 16), seed=1))
    v = np.tile(_rand((40, 16), seed=2), (2, 1))   # every row duplicated
    v = jnp.asarray(v)
    for k, block in ((10, 32), (3, 80), (20, 7)):
        _assert_matches_ref(ops.fused_topk(u, v, k, block=block),
                            ref.fused_topk(u, v, k))


def test_fused_mask_and_exclusions_parity():
    rng = np.random.default_rng(3)
    u = jnp.asarray(_rand((6, 8), seed=4))
    v = jnp.asarray(_rand((57, 8), seed=5))
    mask = jnp.where(jnp.asarray(rng.random(57) < 0.2), -jnp.inf, 0.0
                     ).astype(jnp.float32)
    excl = (rng.integers(0, 6, 90).astype(np.int32),
            rng.integers(0, 57, 90).astype(np.int32))
    got = ops.fused_topk(u, v, 12, mask=mask, exclude=excl, block=16)
    want = ref.fused_topk(u, v, 12, mask=mask, exclude=excl)
    _assert_matches_ref(got, want)


def test_fused_int8_parity():
    v = _rand((33, 8), seed=6)
    q, scale = quantize_int8_rows(v)
    u = jnp.asarray(_rand((4, 8), seed=7))
    got = ops.fused_topk(u, jnp.asarray(q), 9, scale=jnp.asarray(scale),
                         block=10)
    want = ref.fused_topk(u, jnp.asarray(q), 9, scale=jnp.asarray(scale))
    _assert_matches_ref(got, want)


def test_fused_codebook_parity():
    rng = np.random.default_rng(8)
    cb = _rand((12, 8), seed=9)
    # duplicate codes inside rows: the binary-Y dedup path must fire
    sk = rng.integers(0, 12, (29, 2)).astype(np.int32)
    sk[::4, 1] = sk[::4, 0]
    u = jnp.asarray(_rand((5, 8), seed=10))
    skj = jnp.asarray(sk)
    got = ops.fused_topk(u, jnp.asarray(cb), 7, sketch=skj, block=8)
    want = ref.fused_topk(u, jnp.asarray(cb), 7, sketch=skj)
    _assert_matches_ref(got, want)
    # int8 codebook through the same expansion
    q, scale = quantize_int8_rows(cb)
    got = ops.fused_topk(u, jnp.asarray(q), 7, sketch=skj,
                         scale=jnp.asarray(scale), block=8)
    want = ref.fused_topk(u, jnp.asarray(q), 7, sketch=skj,
                          scale=jnp.asarray(scale))
    _assert_matches_ref(got, want)


def test_engine_scorer_registry_dispatch():
    from repro.embedding import available_scorers, get_scorer
    assert {"pallas", "ref"} <= set(available_scorers())
    u = jnp.asarray(_rand((3, 4), seed=11))
    v = jnp.asarray(_rand((17, 4), seed=12))
    _assert_matches_ref(fused_topk(u, v, 5, backend="pallas"),
                        fused_topk(u, v, 5, backend="ref"))
    with pytest.raises(KeyError):
        get_scorer("nope")


# ---------------------------------------------------------------------------
# int8 quantization bounds
# ---------------------------------------------------------------------------
def test_int8_roundtrip_error_bound():
    x = _rand((50, 16), seed=13) * np.logspace(-3, 1, 50)[:, None]
    q, scale = quantize_int8_rows(x)
    assert q.dtype == np.int8 and scale.dtype == np.float32
    back = np.asarray(dequantize_int8_rows(jnp.asarray(q),
                                           jnp.asarray(scale)))
    # symmetric rounding: elementwise error is at most half a step
    assert np.all(np.abs(back - x) <= scale[:, None] / 2 + 1e-7)
    # params round-trip: table names re-materialize from _q/_scale pairs
    params = {"user_table": x[:20], "item_table": x[20:]}
    qp = quantize_params(params)
    assert set(qp) == {"user_table_q", "user_table_scale",
                      "item_table_q", "item_table_scale"}
    dq = dequantize_params(qp)
    assert set(dq) == {"user_table", "item_table"}
    np.testing.assert_allclose(np.asarray(dq["item_table"]), x[20:],
                               atol=float(scale.max()) / 2 + 1e-7)


# ---------------------------------------------------------------------------
# topk_streaming backends
# ---------------------------------------------------------------------------
def test_topk_scan_bitwise_matches_hostloop():
    rng = np.random.default_rng(14)
    u = _rand((11, 8), seed=15)
    v = _rand((53, 8), seed=16)
    excl = (rng.integers(0, 11, 40).astype(np.int32),
            rng.integers(0, 53, 40).astype(np.int32))
    for block, ex in ((16, excl), (53, excl), (7, None)):
        np.testing.assert_array_equal(
            topk_streaming(u, v, 6, block=block, exclude=ex,
                           backend="block"),
            topk_streaming(u, v, 6, block=block, exclude=ex,
                           backend="hostloop"))


def test_topk_fused_backend_matches_dense_oracle():
    rng = np.random.default_rng(17)
    u = _rand((9, 8), seed=18)
    v = _rand((61, 8), seed=19)
    excl = (rng.integers(0, 9, 30).astype(np.int32),
            rng.integers(0, 61, 30).astype(np.int32))
    want = topk_from_scores(u @ v.T, 8, exclude=excl)
    np.testing.assert_array_equal(
        topk_streaming(u, v, 8, block=16, exclude=excl, backend="fused"),
        want)
    with pytest.raises(ValueError):
        topk_streaming(u, v, 8, backend="nope")


# ---------------------------------------------------------------------------
# session scorer knob + quantized artifacts
# ---------------------------------------------------------------------------
def test_session_fused_matches_dense_ids(trained):
    art = trained.export(None)
    ids = np.arange(0, 150, 3, dtype=np.int32)
    vd, id_d = art.session(k=20, scorer="dense")(ids)
    vf, id_f = art.session(k=20, scorer="fused")(ids)
    np.testing.assert_array_equal(np.asarray(id_d), np.asarray(id_f))
    np.testing.assert_allclose(np.asarray(vd), np.asarray(vf),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        art.session(k=5, scorer="nope")


def test_quantized_artifact_roundtrip_and_delta(trained, tmp_path):
    art = trained.export(None)
    q = art.quantize()
    assert q.params == {}
    assert set(q.quantized) == {"user_table_q", "user_table_scale",
                               "item_table_q", "item_table_scale"}
    assert q.provenance["quantization"] == "int8_symmetric_rowwise"
    assert q.quantize() is q                     # idempotent
    assert q.serving_nbytes() < art.serving_nbytes()
    q.save(str(tmp_path / "q"))
    q2 = CompressedArtifact.load(str(tmp_path / "q"))
    assert q2.content_id() == q.content_id()
    # a delta can carry an fp32 -> int8 transition
    d = q.delta(art)
    assert art.apply_delta(d).content_id() == q.content_id()
    # and a quantized session still serves
    ids = np.arange(8, dtype=np.int32)
    _, got = q2.session(k=10, scorer="fused")(ids)
    _, want = q2.session(k=10, scorer="dense")(ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_recall_delta_within_half_percent(trained):
    """Acceptance pin: serving the int8 payload costs <= 0.5% absolute
    Recall@20 vs the fp32 tables on the trained toy benchmark."""
    g = trained.graph
    test = (g.edge_u[::5], g.edge_v[(np.arange(g.n_edges)[::5] + 1)
                                    % g.n_edges])
    users = np.unique(test[0])
    art = trained.export(None)

    def recall(artifact, scorer):
        _, topk = artifact.session(k=20, scorer=scorer)(
            users.astype(np.int32))
        return recall_ndcg_at_k(np.asarray(topk), test[0], test[1],
                                users, k=20)["recall"]

    fp32 = recall(art, "dense")
    int8 = recall(art.quantize(), "fused")
    assert abs(fp32 - int8) <= 0.005


def test_swap_under_fused_scorer_adds_zero_compiles(trained):
    art = trained.export(None)
    q = art.quantize()
    session = q.session(k=10, scorer="fused", capacity="auto")
    session.warmup(4)
    session(np.arange(4, dtype=np.int32))
    before = session.compile_count
    swap = session.swap(q)                       # like-for-like int8 swap
    assert not swap["capacity_bumped"]
    _, got = session(np.arange(4, dtype=np.int32))
    assert session.compile_count == before
    assert session.stats()["scorer"] == "fused"
    assert session.stats()["quantized"]
    _, want = q.session(k=10, scorer="fused")(np.arange(4, dtype=np.int32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # fp32 -> int8 changes the served pytree (keys + dtypes), so that
    # swap pays exactly one recompile — not a silent per-request leak
    s2 = art.session(k=10, scorer="fused", capacity="auto")
    s2.warmup(4)
    s2(np.arange(4, dtype=np.int32))
    base = s2.compile_count
    s2.swap(q)
    s2(np.arange(4, dtype=np.int32))
    after_one = s2.compile_count
    assert after_one <= base + 1
    s2(np.arange(4, dtype=np.int32))
    assert s2.compile_count == after_one


# ---------------------------------------------------------------------------
# platform/interpret resolution
# ---------------------------------------------------------------------------
def test_resolve_interpret_env_and_kwarg(monkeypatch):
    """The platform decides by default, an explicit kwarg overrides it,
    and no environment variable can force interpret mode on a TPU."""
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    for value in ("0", "1"):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
        assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


def test_compiled_scorer_refuses_exclusions():
    """On a compiled platform the fused scorer raises on exclusions
    instead of silently running the jnp reference."""
    u = jnp.asarray(_rand((3, 8), seed=13))
    v = jnp.asarray(_rand((40, 8), seed=14))
    excl = (np.array([0, 2], np.int32), np.array([5, 7], np.int32))
    with pytest.raises(NotImplementedError, match="exclusion"):
        ops.fused_topk(u, v, 4, exclude=excl, interpret=False)
    # no exclusions: the compiled path is not refused up front
    empty = (np.zeros(0, np.int32), np.zeros(0, np.int32))
    _assert_matches_ref(ops.fused_topk(u, v, 4, exclude=empty),
                        ref.fused_topk(u, v, 4))


# ---------------------------------------------------------------------------
# bench_summary --check regression gate
# ---------------------------------------------------------------------------
def test_bench_summary_check_flags_regressions(tmp_path):
    import json
    from benchmarks.bench_summary import check
    base = tmp_path / "base"
    cur = tmp_path / "cur"
    base.mkdir(), cur.mkdir()
    rec = {"bench": "stream", "platform": "cpu", "swap_p99_ms": 10.0,
           "recall_stream": 0.40, "compiles": 0}
    (base / "BENCH_stream.json").write_text(json.dumps(rec))
    worse = dict(rec, swap_p99_ms=15.0, recall_stream=0.25, compiles=2)
    (cur / "BENCH_stream.json").write_text(json.dumps(worse))
    warnings = check(str(cur), str(base))
    text = "\n".join(warnings)
    assert "swap_p99_ms" in text
    assert "recall_stream" in text
    assert "compiles" in text                    # 0 -> 2 zero-baseline rule
    # within threshold -> clean
    ok = dict(rec, swap_p99_ms=10.5, recall_stream=0.39)
    (cur / "BENCH_stream.json").write_text(json.dumps(ok))
    assert check(str(cur), str(base)) == []
