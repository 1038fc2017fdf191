"""Plain LightGCN + BACO reference (He et al., SIGIR 2020; BACO codebooks).

Straightforward ``jax.numpy`` over the benchmark's own inputs: no kernel,
no cache, no batching, and nothing imported from the program. Tables are
expanded from the codebooks through the sketch (a repeated index counts
once, the paper's binary Y), propagated over the interaction graph with
symmetric 1/sqrt(d_u d_v) weights by scatter segment sums, and averaged
over the K + 1 layers, all at float32's full precision. Scores are the
matmul of the tables at the precision the configuration states:
``jnp``'s default, which on a TPU rounds both inputs to bfloat16 and
accumulates in float32. Training is BPR with L2 on the ego embeddings and
Adam, on the batches the trainer's device sampler draws for each
(seed, step).

``dtype`` is float32 for the reference; the control runs the same code in
bfloat16 (tables, propagation and scores), the step below float32.
(Scoring at full float32 would charge every served value with the
scorer's own bfloat16 rounding, which the configuration states; the
bfloat16 control then reads only about twice the program's gaps, too
close to set a limit between them.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Reference"]


class Reference:
    def __init__(self, cfg: dict, edge_u, edge_v, user_idx, item_idx,
                 dtype=jnp.float32):
        self.n_users = int(cfg["n_users"])
        self.n_items = int(cfg["n_items"])
        self.n_layers = int(cfg["n_layers"])
        self.l2 = float(cfg["l2"])
        self.dtype = dtype
        eu = np.asarray(edge_u, np.int64)
        ev = np.asarray(edge_v, np.int64)
        du = np.maximum(np.bincount(eu, minlength=self.n_users), 1)
        dv = np.maximum(np.bincount(ev, minlength=self.n_items), 1)
        norm = (1.0 / np.sqrt(du[eu].astype(np.float64) * dv[ev]))
        self.edge_u = jnp.asarray(eu, jnp.int32)
        self.edge_v = jnp.asarray(ev, jnp.int32)
        self.norm = jnp.asarray(norm, jnp.float32)
        self.user_idx = jnp.asarray(user_idx, jnp.int32)
        self.item_idx = jnp.asarray(item_idx, jnp.int32)
        self._tables = jax.jit(lambda p: self.propagate(*self.ego(p)))
        self._scores = jax.jit(lambda u, v, users: u[users] @ v.T)

    # -- forward ----------------------------------------------------------
    def _expand(self, codebook, idx):
        out = codebook[idx[:, 0]]
        for h in range(1, idx.shape[1]):
            dup = jnp.zeros(idx.shape[0], bool)
            for j in range(h):
                dup = dup | (idx[:, h] == idx[:, j])
            out = out + jnp.where(dup[:, None], 0, codebook[idx[:, h]])
        return out

    def ego(self, params):
        dt = self.dtype
        return (self._expand(params["user_table"].astype(dt), self.user_idx),
                self._expand(params["item_table"].astype(dt), self.item_idx))

    def propagate(self, u, v):
        w = self.norm.astype(self.dtype)[:, None]
        acc_u, acc_v = u, v
        for _ in range(self.n_layers):
            u, v = (jax.ops.segment_sum(v[self.edge_v] * w, self.edge_u,
                                        num_segments=self.n_users),
                    jax.ops.segment_sum(u[self.edge_u] * w, self.edge_v,
                                        num_segments=self.n_items))
            acc_u = acc_u + u
            acc_v = acc_v + v
        k = self.n_layers + 1
        return acc_u / k, acc_v / k

    def tables(self, params):
        """(U [n_users, d], V [n_items, d]) propagated embeddings."""
        with jax.default_matmul_precision("highest"):
            return self._tables(params)

    def scores(self, U, V, users):
        """[len(users), n_items] scores by the default-precision matmul
        that the configuration states (on a TPU one bfloat16 pass with
        float32 accumulation), in float32 or the control's dtype."""
        return self._scores(U, V, users)

    # -- training ---------------------------------------------------------
    def batch(self, seed: int, step: int, batch_size: int):
        """The (user, pos, neg) batch the device BPR sampler draws at
        ``step``: a uniform edge and a uniform negative other than the
        positive, from fold_in(PRNGKey(seed), step)."""
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        ke, kn = jax.random.split(key)
        e = jax.random.randint(ke, (batch_size,), 0, self.edge_u.shape[0])
        pos = self.edge_v[e]
        r = jax.random.randint(kn, (batch_size,), 0,
                               max(self.n_items - 1, 1))
        return self.edge_u[e], pos, r + (r >= pos).astype(r.dtype)

    def loss(self, params, users, pos, neg):
        u0, v0 = self.ego(params)
        u, v = self.propagate(u0, v0)
        x = (jnp.sum(u[users] * v[pos], axis=-1)
             - jnp.sum(u[users] * v[neg], axis=-1)).astype(jnp.float32)
        bpr = -jnp.mean(jax.nn.log_sigmoid(x))
        reg = (jnp.sum(jnp.square(u0[users].astype(jnp.float32)))
               + jnp.sum(jnp.square(v0[pos].astype(jnp.float32)))
               + jnp.sum(jnp.square(v0[neg].astype(jnp.float32))))
        return bpr + self.l2 * reg / users.shape[0]

    def train(self, params, seed: int, steps: int, batch_size: int,
              lr: float, b1: float = 0.9, b2: float = 0.999,
              eps: float = 1e-8):
        """Adam from ``params`` for ``steps`` steps. Returns (losses
        [steps], params, first moments) as host arrays."""

        def step(carry, t):
            p, m, v = carry
            users, pos, neg = self.batch(seed, t, batch_size)
            loss, g = jax.value_and_grad(self.loss)(p, users, pos, neg)
            n = (t + 1).astype(jnp.float32)
            m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
            v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            p = jax.tree.map(
                lambda w, a, b: w - lr * (a / (1 - b1 ** n))
                / (jnp.sqrt(b / (1 - b2 ** n)) + eps), p, m, v)
            return (p, m, v), loss

        zeros = jax.tree.map(jnp.zeros_like, params)
        run = jax.jit(lambda p: jax.lax.scan(
            step, (p, zeros, zeros), jnp.arange(steps, dtype=jnp.int32)))
        with jax.default_matmul_precision("highest"):
            (p, m, _), losses = run(params)
        to_host = lambda t: jax.tree.map(np.asarray, t)
        return np.asarray(losses), to_host(p), to_host(m)
