"""LightGCN backbone (He et al. 2020) over full or compressed tables.

The paper's evaluation protocol: LightGCN + BPR, where the embedding
tables are either the full |U|x d / |V|x d matrices or codebooks indexed
through a frozen sketch (U = Y_u Z_u, V = Y_v Z_v). Propagation runs over
the *training* interaction graph with symmetric 1/sqrt(d_u d_v) weights;
the final representation is the mean of the K+1 layer outputs.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import BipartiteGraph
from repro.core.sketch import Sketch
from repro.embedding import EmbeddingEngine, EmbeddingSpec, init_codebook
from repro.obs.scopes import LOOKUP, LOSS, PROPAGATE

__all__ = ["LightGCNConfig", "from_sketch", "engines", "make_statics",
           "sorted_edge_statics", "init_params", "all_embeddings",
           "bpr_loss_fn", "score_all_items", "eval_embeddings"]


@dataclasses.dataclass(frozen=True)
class LightGCNConfig:
    n_users: int
    n_items: int
    dim: int = 64
    n_layers: int = 3
    l2: float = 1e-4
    # compression: None -> full tables (identity sketch)
    k_users: Optional[int] = None
    k_items: Optional[int] = None
    n_hot_users: int = 1
    # explicit EmbeddingEngine backend; None -> auto-select by platform
    lookup_backend: Optional[str] = None


def from_sketch(graph: BipartiteGraph, sketch: Optional[Sketch], dim=64,
                n_layers=3, l2=1e-4,
                lookup_backend: Optional[str] = None) -> "LightGCNConfig":
    if sketch is None:
        return LightGCNConfig(graph.n_users, graph.n_items, dim, n_layers, l2,
                              lookup_backend=lookup_backend)
    return LightGCNConfig(graph.n_users, graph.n_items, dim, n_layers, l2,
                          k_users=sketch.k_users, k_items=sketch.k_items,
                          n_hot_users=sketch.user_idx.shape[1],
                          lookup_backend=lookup_backend)


def engines(cfg: LightGCNConfig):
    """(user, item) EmbeddingEngines for this config's tables."""
    u = EmbeddingEngine(EmbeddingSpec(cfg.n_users, cfg.dim,
                                      k_rows=cfg.k_users,
                                      n_hot=cfg.n_hot_users),
                        backend=cfg.lookup_backend)
    v = EmbeddingEngine(EmbeddingSpec(cfg.n_items, cfg.dim,
                                      k_rows=cfg.k_items),
                        backend=cfg.lookup_backend)
    return u, v


def sorted_edge_statics(edge_u, edge_v, edge_norm, n_users: int,
                        n_items: int, perm_by_item=None) -> dict:
    """Scatter-free propagation constants from a (user-sorted) edge list.

    Both segment orientations as SORTED runs: the user side uses the
    edge list as-is (edges arrive sorted by user), the item side a
    stable item-order permutation of it — plus both CSR indptrs. The
    propagation then reduces each side with a blocked prefix sum read
    at the segment boundaries (`_segsum_sorted`), with no scatter.
    """
    edge_u = np.asarray(edge_u)
    edge_v = np.asarray(edge_v)
    edge_norm = np.asarray(edge_norm)
    if edge_u.size and np.any(np.diff(edge_u) < 0):
        raise ValueError("edge_u must be sorted (BipartiteGraph edge "
                         "order); searchsorted indptrs would be garbage")
    # BipartiteGraph already carries this exact stable item-order
    # permutation; only artifact loading (no graph) recomputes it
    perm = (np.asarray(perm_by_item) if perm_by_item is not None
            else np.argsort(edge_v, kind="stable"))
    indptr_u = np.searchsorted(edge_u, np.arange(n_users + 1,
                                                 dtype=np.int64))
    indptr_v = np.searchsorted(edge_v[perm], np.arange(n_items + 1,
                                                       dtype=np.int64))
    return {
        "edge_u": jnp.asarray(edge_u),
        "edge_v": jnp.asarray(edge_v),
        "edge_norm": jnp.asarray(edge_norm),
        "edge_u_byitem": jnp.asarray(edge_u[perm]),
        "edge_norm_byitem": jnp.asarray(edge_norm[perm]),
        "indptr_u": jnp.asarray(indptr_u.astype(np.int32)),
        "indptr_v": jnp.asarray(indptr_v.astype(np.int32)),
    }


def make_statics(graph: BipartiteGraph, sketch: Optional[Sketch] = None):
    """Device-ready constants: normalized edges (both segment
    orientations, for the scatter-free propagation) + sketch arrays."""
    du = np.maximum(graph.user_degrees(), 1).astype(np.float32)
    dv = np.maximum(graph.item_degrees(), 1).astype(np.float32)
    norm = 1.0 / np.sqrt(du[graph.edge_u] * dv[graph.edge_v])
    statics = sorted_edge_statics(graph.edge_u, graph.edge_v, norm,
                                  graph.n_users, graph.n_items,
                                  perm_by_item=graph.perm_by_item)
    if sketch is not None:
        statics["sketch_u"] = jnp.asarray(sketch.user_idx)
        statics["sketch_v"] = jnp.asarray(sketch.item_idx)
    return statics


def init_params(key, cfg: LightGCNConfig, scale: float = 0.1):
    ku, kv = jax.random.split(key)
    nu = cfg.k_users if cfg.k_users is not None else cfg.n_users
    nv = cfg.k_items if cfg.k_items is not None else cfg.n_items
    return {"user_table": init_codebook(ku, nu, cfg.dim, scale),
            "item_table": init_codebook(kv, nv, cfg.dim, scale)}


def _base_embeddings(params, statics, cfg: LightGCNConfig):
    """Materialize E0 = [Y_u Z_u ; Y_v Z_v] (or the full tables)."""
    if cfg.k_users is not None:
        u_eng, v_eng = engines(cfg)
        with jax.named_scope(LOOKUP):
            u = u_eng.codebook_lookup(params["user_table"],
                                      statics["sketch_u"],
                                      jnp.arange(cfg.n_users))
            v = v_eng.codebook_lookup(params["item_table"],
                                      statics["sketch_v"],
                                      jnp.arange(cfg.n_items))
        return u, v
    return params["user_table"], params["item_table"]


# rows of the in-block prefix; one block when the graph has fewer edges
EDGE_BLOCK = 256


def _edge_block(n_edges: int) -> int:
    return max(1, min(EDGE_BLOCK, n_edges))


def _pad_edges(index, weight):
    """Pad an edge orientation's gather index and weight to a whole
    number of prefix blocks: pad edges read row 0 with weight 0 and lie
    past every segment, so only these 1-D vectors are ever padded."""
    n = index.shape[0]
    pad = -n % _edge_block(n)
    return jnp.pad(index, (0, pad)), jnp.pad(weight, (0, pad))


def _block_prefix(x):
    """Inclusive prefix along axis 1 of [n, B, d]: a [B, B] lower
    triangle of ones on the MXU, at full float32 precision (the default
    would round the rows to bfloat16)."""
    b = x.shape[1]
    tri = jnp.tril(jnp.ones((b, b), x.dtype))
    return jnp.einsum("ij,njd->nid", tri, x,
                      precision=jax.lax.Precision.HIGHEST)


def _carry(blocks):
    """[n, d] exclusive prefix of the totals of [n, B, d] in-block
    prefixes: what precedes each block."""
    totals = _prefix(blocks[:, -1], blocks.shape[1])
    return jnp.pad(totals[:-1], ((1, 0), (0, 0)))


def _prefix(x, b: int):
    """Inclusive prefix along axis 0 of [n, d], in blocks of b rows."""
    n, d = x.shape
    if n <= b:
        return _block_prefix(x[None])[0]
    y = _block_prefix(jnp.pad(x, ((0, -n % b), (0, 0))).reshape(-1, b, d))
    return (y + _carry(y)[:, None]).reshape(-1, d)[:n]


def _segsum_sorted(data, indptr):
    """Segment sum of sorted-run rows: blocked prefix + boundary diff.
    data [E, d] grouped into len(indptr)-1 contiguous segments, E a
    whole number of `_edge_block(E)` rows (`_pad_edges`).

    An inclusive prefix inside each block of B rows, plus an exclusive
    prefix of the E/B block totals (the carry), gives the global prefix
    P; segment [s, e) is P[e-1] - P[s-1] (P[-1] = 0), read as the
    in-block difference plus the carry difference, so P itself is never
    written and a segment inside one block sees no carry at all.

    Precision trade: a segment that crosses a block boundary is a
    difference of two global-prefix values, so its absolute error scales
    with the running-sum magnitude (~eps * |prefix|) instead of the
    segment. For zero-mean embedding columns the prefix is a random walk
    (~sqrt(E) * scale), harmless at the repo's dataset scales (pinned vs
    the scatter path in tests); at 1e8+ edges prefer rebasing the carry
    per chunk or an f32->f64 carry."""
    e, d = data.shape
    if e == 0:
        return jnp.zeros((indptr.shape[0] - 1, d), data.dtype)
    b = _edge_block(e)
    if e % b:
        raise ValueError(f"{e} edge rows are not a whole number of "
                         f"{b}-row blocks; pad them with _pad_edges")
    inblock = _block_prefix(data.reshape(e // b, b, d))
    carry = _carry(inblock)
    inblock = inblock.reshape(e, d)

    def prefix_parts(rows):               # P[rows] as (in-block, carry)
        keep = (rows >= 0)[:, None]
        at = jnp.maximum(rows, 0)
        return (jnp.where(keep, inblock[at], 0),
                jnp.where(keep, carry[at // b], 0))

    end_in, end_carry = prefix_parts(indptr[1:] - 1)
    start_in, start_carry = prefix_parts(indptr[:-1] - 1)
    return (end_in - start_in) + (end_carry - start_carry)


def _make_propagate(statics):
    """One scatter-free LightGCN layer (cu, cv) -> (nu, nv).

    Forward aggregates each side over its SORTED edge orientation; the
    custom VJP keeps the backward scatter-free too — the adjoint of
    "sum over edges into user" is "sum over edges into item", which is
    again a sorted segment sum under the opposite orientation (autodiff
    would instead emit the gathers' scatter-add transpose)."""
    ev_u, w_u = _pad_edges(statics["edge_v"], statics["edge_norm"])
    eu_i, w_i = _pad_edges(statics["edge_u_byitem"],
                           statics["edge_norm_byitem"])
    iu, iv = statics["indptr_u"], statics["indptr_v"]

    def impl(cu, cv):
        nu = _segsum_sorted(cv[ev_u] * w_u[:, None], iu)
        nv = _segsum_sorted(cu[eu_i] * w_i[:, None], iv)
        return nu, nv

    prop = jax.custom_vjp(impl)

    def fwd(cu, cv):
        return impl(cu, cv), None

    def bwd(_, g):
        gnu, gnv = g
        # traced apart from the forward: its ops need the scope again
        with jax.named_scope(PROPAGATE):
            d_cv = _segsum_sorted(gnu[eu_i] * w_i[:, None], iv)
            d_cu = _segsum_sorted(gnv[ev_u] * w_u[:, None], iu)
        return d_cu, d_cv

    prop.defvjp(fwd, bwd)
    return prop


def all_embeddings(params, statics, cfg: LightGCNConfig):
    """LightGCN propagation; returns (U [n_users,d], V [n_items,d])."""
    u, v = _base_embeddings(params, statics, cfg)
    if "indptr_u" in statics:
        prop = _make_propagate(statics)
    else:                          # minimal statics: scatter fallback
        eu, ev, w = statics["edge_u"], statics["edge_v"], \
            statics["edge_norm"]
        prop = lambda cu, cv: (
            jax.ops.segment_sum(cv[ev] * w[:, None], eu,
                                num_segments=cfg.n_users),
            jax.ops.segment_sum(cu[eu] * w[:, None], ev,
                                num_segments=cfg.n_items))
    with jax.named_scope(PROPAGATE):
        acc_u, acc_v = u, v
        cu, cv = u, v
        for _ in range(cfg.n_layers):
            cu, cv = prop(cu, cv)
            acc_u = acc_u + cu
            acc_v = acc_v + cv
        k = cfg.n_layers + 1
        return acc_u / k, acc_v / k


def bpr_loss_fn(params, statics, batch, cfg: LightGCNConfig):
    """BPR over (user, pos, neg) with L2 on the *ego* embeddings.

    The propagated and ego tables are concatenated per side so each
    batch index is gathered ONCE (3 gathers instead of 6, and 3 adjoint
    accumulations in the backward) — same values, the gather/transpose
    op count is what dominates small-graph steps on CPU."""
    u_all, v_all = all_embeddings(params, statics, cfg)
    u0, v0 = _base_embeddings(params, statics, cfg)
    d = cfg.dim
    with jax.named_scope(LOSS):
        uu = jnp.concatenate([u_all, u0], axis=1)[batch["user"]]
        pi = jnp.concatenate([v_all, v0], axis=1)[batch["pos"]]
        ni = jnp.concatenate([v_all, v0], axis=1)[batch["neg"]]
        pos = jnp.sum(uu[:, :d] * pi[:, :d], axis=-1)
        neg = jnp.sum(uu[:, :d] * ni[:, :d], axis=-1)
        loss = -jnp.mean(jax.nn.log_sigmoid(pos - neg))
        reg = (jnp.sum(uu[:, d:] ** 2) + jnp.sum(pi[:, d:] ** 2)
               + jnp.sum(ni[:, d:] ** 2)) / batch["user"].shape[0]
        return loss + cfg.l2 * reg


# ---------------------------------------------------------------------------
# frozen seed twins (benchmark reference only — the pre-PR4 train step:
# scatter-add segment sums and one gather per readout term). Kept verbatim
# so BENCH_train.json's "seed host loop" baseline measures the actual seed
# implementation, the same pattern as core.solver_jax.lp_solve_hostloop.
# ---------------------------------------------------------------------------
def all_embeddings_seed(params, statics, cfg: LightGCNConfig):
    """Seed propagation: jax.ops.segment_sum scatter-adds (frozen)."""
    u, v = _base_embeddings(params, statics, cfg)
    eu, ev, w = statics["edge_u"], statics["edge_v"], statics["edge_norm"]
    acc_u, acc_v = u, v
    cu, cv = u, v
    for _ in range(cfg.n_layers):
        nu = jax.ops.segment_sum(cv[ev] * w[:, None], eu,
                                 num_segments=cfg.n_users)
        nv = jax.ops.segment_sum(cu[eu] * w[:, None], ev,
                                 num_segments=cfg.n_items)
        cu, cv = nu, nv
        acc_u = acc_u + cu
        acc_v = acc_v + cv
    k = cfg.n_layers + 1
    return acc_u / k, acc_v / k


def bpr_loss_fn_seed(params, statics, batch, cfg: LightGCNConfig):
    """Seed BPR step (frozen): six separate readout gathers."""
    u_all, v_all = all_embeddings_seed(params, statics, cfg)
    uu = u_all[batch["user"]]
    pi = v_all[batch["pos"]]
    ni = v_all[batch["neg"]]
    pos = jnp.sum(uu * pi, axis=-1)
    neg = jnp.sum(uu * ni, axis=-1)
    loss = -jnp.mean(jax.nn.log_sigmoid(pos - neg))
    u0, v0 = _base_embeddings(params, statics, cfg)
    reg = (jnp.sum(u0[batch["user"]] ** 2) + jnp.sum(v0[batch["pos"]] ** 2)
           + jnp.sum(v0[batch["neg"]] ** 2)) / batch["user"].shape[0]
    return loss + cfg.l2 * reg


def eval_embeddings(params, statics, cfg: LightGCNConfig, user_ids):
    """(U[user_ids] [m,d], V [n_items,d]) propagated embeddings.

    The streaming evaluator scores these in item blocks with an
    on-device running top-k (`training.eval.topk_streaming`) — the
    O(users x items) score matrix of `score_all_items` never
    materializes."""
    u_all, v_all = all_embeddings(params, statics, cfg)
    return u_all[user_ids], v_all


def score_all_items(params, statics, cfg: LightGCNConfig, user_ids):
    """[len(user_ids), n_items] scores (eval-time; dense — prefer
    `eval_embeddings` + streaming top-k for large item sets)."""
    u, v = eval_embeddings(params, statics, cfg, user_ids)
    return u @ v.T
