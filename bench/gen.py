"""Inputs made from the seed: interaction graphs, BACO-shaped sketches,
codebook weights and serving traffic.

Everything here is the benchmark's own; the program under test receives
only what these functions produce. The graph generator follows the
repository's planted co-cluster generator (``repro.data.synthetic``),
with one change: a user's repeated draws of the same item are drawn
again, so the number of distinct interactions reaches the published
count instead of losing most of it to de-duplication. The arrival and
popularity arithmetic is a copy of ``repro.frontdoor.loadgen``.
"""
from __future__ import annotations

import numpy as np

__all__ = ["USERS", "SAMPLE", "rng_for", "seed32", "interactions", "baco_sketch",
           "codebook_weights", "arrival_times", "zipf_ids"]

# streams of one seed: each input draws from its own generator, so
# adding a draw to one input never moves another
_GRAPH, _SKETCH, USERS, SAMPLE = 1, 2, 3, 4
# re-draws of repeated pairs: from the generator's mixture, then uniform
_ROUNDS, _UNIFORM_ROUNDS = 6, 4


def _entropy(seed: int) -> int:
    return int(seed) % 2**64          # SeedSequence takes no negatives


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([_entropy(seed), stream]))


def seed32(seed: int) -> int:
    """A non-negative int32 drawn from ``seed``: JAX keys and int32
    arguments cannot hold every whole number a run may be given."""
    return int(np.random.SeedSequence([_entropy(seed), 0]).generate_state(
        1)[0] & 0x7FFFFFFF)


def _degrees(rng, n_users, n_items, n_interactions, alpha):
    """Zipf(alpha) user degrees, capped at a quarter of the items, scaled
    so that they add up to ``n_interactions``."""
    raw = rng.zipf(alpha, size=n_users).astype(np.float64)
    raw = np.minimum(raw, n_items // 2 + 1)
    cap = max(4, n_items // 4)
    target = float(n_interactions)
    scale = target / raw.sum()
    for _ in range(8):
        deg = np.clip(np.round(raw * scale), 1, cap).astype(np.int64)
        total = deg.sum()
        if abs(total - target) <= 1e-4 * target:
            break
        scale *= target / total
    return deg


def _draw(rng, users, need, uc, home_items, home_p, pop_p, n_items, noise):
    """``need[i]`` item draws for ``users[i]``: from the user's planted
    home cluster (popularity-weighted) w.p. 1 - noise, else from the
    global popularity."""
    eu, ev = [], []
    order = np.argsort(uc[users], kind="stable")
    users, need = users[order], need[order]
    clusters, starts = np.unique(uc[users], return_index=True)
    bounds = list(starts) + [users.size]
    for c, lo, hi in zip(clusters, bounds[:-1], bounds[1:]):
        us, nd = users[lo:hi], need[lo:hi]
        total = int(nd.sum())
        n_in = int(rng.binomial(total, 1.0 - noise))
        vin = rng.choice(home_items[c], size=n_in, p=home_p[c])
        vout = rng.choice(n_items, size=total - n_in, p=pop_p)
        v = np.concatenate([vin, vout])
        rng.shuffle(v)
        eu.append(np.repeat(us, nd))
        ev.append(v)
    return np.concatenate(eu), np.concatenate(ev)


def interactions(cfg: dict, seed: int):
    """(edge_u, edge_v, user_cluster, item_cluster) for a configuration.

    Distinct (user, item) pairs sorted by user then item. Each user
    draws its Zipf degree from its planted co-cluster (``k_true`` of
    them) as the repository's generator does; pairs drawn twice are
    drawn again, up to ``_ROUNDS`` times from the same mixture and then
    from uniform items, and the few hundred pairs the degrees miss by
    are trimmed or padded so that the count is ``n_interactions``.
    """
    rng = rng_for(seed, _GRAPH)
    nu, nv, k = int(cfg["n_users"]), int(cfg["n_items"]), int(cfg["k_true"])
    noise, alpha = float(cfg["noise"]), float(cfg["degree_alpha"])
    uc = rng.integers(0, k, size=nu)
    ic = rng.integers(0, k, size=nv)
    ic[:k] = np.arange(k)
    deg = _degrees(rng, nu, nv, int(cfg["n_interactions"]), alpha)
    pop = 1.0 / (1.0 + rng.permutation(nv))
    pop_p = pop / pop.sum()
    by_cluster = np.argsort(ic, kind="stable")
    cuts = np.searchsorted(ic[by_cluster], np.arange(k + 1))
    home_items = [by_cluster[cuts[c]:cuts[c + 1]] for c in range(k)]
    home_p = [pop[h] / pop[h].sum() for h in home_items]

    keys = np.empty(0, np.int64)
    users, need = np.arange(nu), deg
    for rnd in range(_ROUNDS + _UNIFORM_ROUNDS):
        if rnd < _ROUNDS:
            eu, ev = _draw(rng, users, need, uc, home_items, home_p, pop_p,
                           nv, noise)
        else:
            eu = np.repeat(users, need)
            ev = rng.integers(0, nv, size=eu.size)
        keys = np.union1d(keys, eu.astype(np.int64) * nv + ev)
        have = np.bincount(keys // nv, minlength=nu)
        short = deg - have
        users = np.flatnonzero(short > 0)
        if users.size == 0:
            break
        need = short[users]
    keys = _exact(rng, keys, int(cfg["n_interactions"]), nu, nv)
    return ((keys // nv).astype(np.int32), (keys % nv).astype(np.int32),
            uc.astype(np.int32), ic.astype(np.int32))


def _exact(rng, keys, target: int, nu: int, nv: int):
    """Trim or pad sorted unique pair keys to exactly ``target``: every
    seed then gives the program the same shapes. Trimming keeps each
    user's first pair; padding adds uniform pairs."""
    if keys.size > target:
        first = np.zeros(keys.size, bool)
        first[np.unique(keys // nv, return_index=True)[1]] = True
        spare = np.flatnonzero(~first)
        drop = rng.choice(spare, size=keys.size - target, replace=False)
        keys = np.delete(keys, drop)
    while keys.size < target:
        need = target - keys.size
        cand = np.unique(rng.integers(0, nu, 2 * need + 16).astype(np.int64)
                         * nv + rng.integers(0, nv, 2 * need + 16))
        cand = cand[~np.isin(cand, keys)]
        keys = np.union1d(keys, rng.permutation(cand)[:need])
    return keys


def baco_sketch(cfg: dict, uc, ic, seed: int):
    """(user_idx int32 [N, 2], item_idx int32 [M, 1], k_users, k_items):
    a sketch of the shape BACO builds with secondary user clusters.

    The row budget is ``ratio * (N + M)`` rows of width ``dim``; the
    secondary user index costs one int per user, so the codebooks get
    ``(budget * dim - N) // dim`` rows (the paper's B'), split between
    users and items in proportion to their counts. Rows are balanced:
    each planted co-cluster owns an equal block of rows on each side,
    and a node takes a uniform row of its cluster's block. A user's
    secondary row lies in another cluster's block.
    """
    rng = rng_for(seed, _SKETCH)
    nu, nv, d = len(uc), len(ic), int(cfg["dim"])
    k = int(cfg["k_true"])
    budget = int(round(float(cfg["ratio"]) * (nu + nv)))
    rows = (budget * d - nu) // d
    k_users = int(round(rows * nu / (nu + nv)))
    k_items = rows - k_users

    def place(labels, k_rows):
        lo = (labels.astype(np.int64) * k_rows) // k
        hi = ((labels.astype(np.int64) + 1) * k_rows) // k
        return (lo + (rng.random(labels.size) * (hi - lo)).astype(np.int64)
                ).astype(np.int32)

    other = (uc + rng.integers(1, k, size=nu)) % k
    user_idx = np.stack([place(uc, k_users), place(other, k_users)], axis=1)
    item_idx = place(ic, k_items)[:, None]
    return user_idx, item_idx, k_users, k_items


def codebook_weights(seed: int, k_users: int, k_items: int, dim: int,
                     scale: float):
    """Both codebooks as float32 device arrays, drawn in one jitted call
    from the seed: N(0, scale^2) entries."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        ku, kv = jax.random.split(key)
        return {"user_table": scale * jax.random.normal(ku, (k_users, dim),
                                                        jnp.float32),
                "item_table": scale * jax.random.normal(kv, (k_items, dim),
                                                        jnp.float32)}

    return draw(jax.random.PRNGKey(seed32(seed)))


# ---------------------------------------------------------------------------
# traffic (copied from repro.frontdoor.loadgen)
# ---------------------------------------------------------------------------
def arrival_times(rate: float, duration_s: float, rng,
                  burst_factor: float = 1.0, burst_frac: float = 0.25,
                  burst_period_s: float = 1.0) -> np.ndarray:
    """Poisson arrival offsets (seconds) in [0, duration_s), thinned or
    boosted into bursty phases when burst_factor > 1. Drawn at the peak
    rate then thinned outside burst windows: exact for a piecewise-
    constant-rate Poisson process. A longer duration extends the same
    schedule: its prefix is unchanged."""
    peak = rate * max(burst_factor, 1.0)
    n = max(1, int(np.ceil(peak * duration_s * 1.5)) + 16)
    t = np.cumsum(rng.exponential(1.0 / peak, size=n))
    t = t[t < duration_s]
    if burst_factor > 1.0:
        phase = np.mod(t, burst_period_s) / burst_period_s
        keep = (phase < burst_frac) | (rng.random(t.size) < 1.0 / burst_factor)
        t = t[keep]
    return t


def zipf_ids(rng, n: int, n_users: int, a: float) -> np.ndarray:
    """``n`` user ids Zipf(a)-distributed over [0, n_users): rank r with
    probability ~ 1/r^a, ranks mapped through a fixed permutation so
    popularity is not id-ordered."""
    ranks = rng.zipf(max(a, 1.0 + 1e-9), size=n)
    ranks = np.minimum(ranks, n_users) - 1
    perm = np.random.default_rng(12345).permutation(n_users)
    return perm[ranks].astype(np.int32)
