"""BPR triplet samplers with deterministic, checkpointable state.

Two registered implementations behind ``make_sampler``:

* ``numpy`` — ``BPRSampler``, the host sampler (seed reference). Each
  batch is drawn from a fresh generator derived from
  ``np.random.SeedSequence([seed, step])`` so distinct ``(seed, step)``
  pairs can never alias (the historical ``(seed << 20) + step`` scheme
  replayed seed+1's stream after 2^20 steps).
* ``device`` — ``DeviceBPRSampler``, the same triplet protocol in
  ``jax.random`` with the batch never leaving the device. Its per-step
  sampling is a pure function of ``(seed, step)``
  (``fold_in(PRNGKey(seed), step)``), which is what lets the fused
  trainer backends scan over steps with zero host copies.

Both samplers checkpoint as the same ``{"seed", "step"}`` state dict —
restoring it resumes the exact mini-batch stream (sampling is keyed by
step, not by mutable generator state), which the fault-tolerance tests
rely on. Negatives are sampled uniformly and rejected against the
positive item only (standard LightGCN protocol); with |V| >> deg this
is unbiased enough and keeps the sampler O(batch).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core.graph import BipartiteGraph
from repro.obs.scopes import SAMPLE

__all__ = ["BPRSampler", "DeviceBPRSampler", "make_sampler",
           "available_samplers", "device_sample_fn"]


class BPRSampler:
    name = "numpy"

    def __init__(self, graph: BipartiteGraph, batch_size: int, seed: int = 0):
        self.n_users = graph.n_users
        self.n_items = graph.n_items
        self.edge_u = graph.edge_u
        self.edge_v = graph.edge_v
        self.batch_size = int(batch_size)
        self.seed = int(seed)
        self.step = 0

    # -- checkpointable state ------------------------------------------------
    def state_dict(self):
        return {"seed": self.seed, "step": self.step}

    def load_state_dict(self, s):
        self.seed = int(s["seed"])
        self.step = int(s["step"])

    # -- sampling --------------------------------------------------------------
    def next_batch(self):
        """(users, pos_items, neg_items) int32[batch] — deterministic in step."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, self.step]))
        self.step += 1
        e = rng.integers(0, self.edge_u.shape[0], size=self.batch_size)
        users = self.edge_u[e]
        pos = self.edge_v[e]
        neg = rng.integers(0, self.n_items, size=self.batch_size)
        # reject collisions with the sampled positive (cheap re-draw)
        bad = neg == pos
        while bad.any():
            neg[bad] = rng.integers(0, self.n_items, size=int(bad.sum()))
            bad = neg == pos
        return (users.astype(np.int32), pos.astype(np.int32),
                neg.astype(np.int32))


def device_sample_fn(edge_u, edge_v, n_items: int, batch_size: int):
    """Pure jittable ``sample(seed, step) -> (users, pos, neg)``.

    The key is ``fold_in(PRNGKey(seed), step)`` so any step is sampled
    without generating its predecessors — the fused trainer scans this
    over a step-index array, and checkpoint resume at an arbitrary step
    replays the identical stream. Negatives draw from [0, n_items-1)
    and shift past the positive (``r + (r >= pos)``): exactly uniform
    over the complement of the positive in ONE draw — the same
    distribution the host sampler's rejection loop converges to,
    without data-dependent control flow in the scan body.
    """
    import jax
    import jax.numpy as jnp

    n_edges = int(edge_u.shape[0])

    def sample(seed, step):
        with jax.named_scope(SAMPLE):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            ke, kn = jax.random.split(key)
            e = jax.random.randint(ke, (batch_size,), 0, n_edges)
            users = edge_u[e]
            pos = edge_v[e]
            r = jax.random.randint(kn, (batch_size,), 0,
                                   max(n_items - 1, 1))
            neg = r + (r >= pos).astype(r.dtype)
            return (users.astype(jnp.int32), pos.astype(jnp.int32),
                    neg.astype(jnp.int32))

    return sample


class DeviceBPRSampler:
    """jax.random BPR sampler; batches are device arrays and never touch
    the host. Same (seed, step) state-dict contract as BPRSampler; the
    fused trainer backends pull ``sample_fn`` directly into their scan
    so a whole chunk of batches is sampled in one compiled program."""

    name = "device"

    def __init__(self, graph: BipartiteGraph, batch_size: int, seed: int = 0):
        import jax
        import jax.numpy as jnp
        self.n_users = graph.n_users
        self.n_items = graph.n_items
        self.edge_u = jnp.asarray(graph.edge_u)
        self.edge_v = jnp.asarray(graph.edge_v)
        self.batch_size = int(batch_size)
        self.seed = int(seed)
        self.step = 0
        self.sample_fn = device_sample_fn(self.edge_u, self.edge_v,
                                          self.n_items, self.batch_size)
        self._jit_sample = jax.jit(self.sample_fn)

    # -- checkpointable state ------------------------------------------------
    def state_dict(self):
        return {"seed": self.seed, "step": self.step}

    def load_state_dict(self, s):
        self.seed = int(s["seed"])
        self.step = int(s["step"])

    # -- sampling --------------------------------------------------------------
    def next_batch(self):
        """(users, pos, neg) int32[batch] device arrays."""
        out = self._jit_sample(self.seed, self.step)
        self.step += 1
        return out


_SAMPLERS = {"numpy": BPRSampler, "device": DeviceBPRSampler}


def available_samplers():
    return tuple(sorted(_SAMPLERS))


def make_sampler(name: Optional[str], graph: BipartiteGraph,
                 batch_size: int, seed: int = 0):
    """Registry constructor; name None -> the host numpy sampler."""
    key = "numpy" if name is None else str(name)
    if key not in _SAMPLERS:
        raise KeyError(f"unknown sampler {name!r}: "
                       f"expected one of {available_samplers()}")
    return _SAMPLERS[key](graph, batch_size, seed=seed)
