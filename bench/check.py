"""Comparisons that decide ``correct``: what the timed path served or
trained, against the plain reference.

Serving, over the answered rows (k ids and values each):

* ``mean_value_gap``: the mean distance between a served value and the
  reference's score of the served item;
* ``mean_topk_gap``: the mean over rows of the margin by which the
  row's worst served item's reference score lies below the reference's
  k-th best score (0 when every served item is in the reference top-k);
* ``bad_rows``: rows with an id out of range or repeated, values that
  rise or are not finite (limit 0).

Means, not maxima: the program's gaps are rare and small (its
propagation differs from the reference's in the last bits, which moves
an occasional bfloat16 rounding of the scorer's inputs), the bfloat16
control's are in every value. The widest gap reads the program's rare
ones, and the control's came out only 2.2-2.7 times above it on the
online cell's 138 rows, too close for a limit between them; the means
read 8 to 375 times apart.

Training, over the steps the reference follows (the set-up chunk and
the window's first chunk): each step's loss (``loss_gap``: widest
relative distance), and after the last of them, by the worst leaf, the
norm of the optimizer's first moment (``grad_gap``, the gradients as
the optimizer got them) and the norm of the parameters' change
(``update_gap``): |norm(program) - norm(reference)| over the
reference's norm of that leaf or of the median leaf, whichever is
larger. Leaves whose reference moment is under a thousandth of the
median leaf's moved by round-off alone and are left out.
"""
from __future__ import annotations

import numpy as np

__all__ = ["topk_gaps", "train_gaps"]


def _bad_rows(ids, vals, n_items: int) -> int:
    srt = np.sort(ids, axis=1)
    bad = ((ids < 0) | (ids >= n_items)).any(axis=1)
    bad |= (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    bad |= ~np.isfinite(vals).all(axis=1)
    bad |= (np.diff(vals, axis=1) > 0).any(axis=1)
    return int(bad.sum())


def topk_gaps(ref, U, V, users, vals, ids, block: int = 512) -> dict:
    """Compare served rows (users [R], vals/ids [R, k]) with the
    reference tables U, V."""
    import jax
    import jax.numpy as jnp

    users = np.asarray(users, np.int32)
    vals = np.asarray(vals, np.float32)
    ids = np.asarray(ids, np.int32)
    n_rows, k = ids.shape
    n_items = int(V.shape[0])
    bad = _bad_rows(ids, vals, n_items)
    safe = np.clip(ids, 0, n_items - 1)

    @jax.jit
    def gaps(scores, vals, ids):
        got = jnp.take_along_axis(scores, ids, axis=1).astype(jnp.float32)
        kth = jax.lax.top_k(scores, k)[0][:, -1].astype(jnp.float32)
        return (jnp.mean(jnp.abs(vals - got), axis=1),
                jnp.maximum(kth - jnp.min(got, axis=1), 0.0))

    value_gaps, topk_gaps_ = [], []
    for lo in range(0, n_rows, block):
        sl = slice(lo, min(lo + block, n_rows))
        pad = block - (sl.stop - sl.start)
        u = np.pad(users[sl], (0, pad))
        v = np.pad(vals[sl], ((0, pad), (0, 0)))
        i = np.pad(safe[sl], ((0, pad), (0, 0)))
        vg, tg = gaps(ref.scores(U, V, jnp.asarray(u)), v, i)
        n = sl.stop - sl.start
        value_gaps.append(np.asarray(vg)[:n])
        topk_gaps_.append(np.asarray(tg)[:n])
    return {"mean_value_gap": float(np.mean(np.concatenate(value_gaps))),
            "mean_topk_gap": float(np.mean(np.concatenate(topk_gaps_))),
            "bad_rows": bad}


def _leaf_gaps(prog: dict, ref: dict, keep) -> float:
    norms_r = {k: float(np.linalg.norm(ref[k])) for k in ref}
    median = float(np.median(list(norms_r.values())))
    worst = 0.0
    for k in ref:
        if not keep(k):
            continue
        gap = abs(float(np.linalg.norm(prog[k])) - norms_r[k])
        worst = max(worst, gap / max(norms_r[k], median))
    return worst


def train_gaps(losses, p0, p, m, ref_losses, ref_p, ref_m) -> dict:
    """Program (losses, params after, first moment) against the
    reference's, from the same starting params ``p0`` (host dicts)."""
    losses = np.asarray(losses, np.float64)
    ref_losses = np.asarray(ref_losses, np.float64)
    loss_gap = float(np.max(np.abs(losses - ref_losses)
                            / np.maximum(np.abs(ref_losses), 1e-30)))
    m_norms = {k: float(np.linalg.norm(ref_m[k])) for k in ref_m}
    floor = 1e-3 * float(np.median(list(m_norms.values())))
    keep = lambda k: m_norms[k] >= floor
    grad_gap = _leaf_gaps(m, ref_m, keep)
    update_gap = _leaf_gaps({k: p[k] - p0[k] for k in p0},
                            {k: ref_p[k] - p0[k] for k in p0}, keep)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap}
