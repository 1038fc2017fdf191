#!/usr/bin/env python3
"""Run the LightGCN+BACO main path once on a TPU: cluster -> train -> serve.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --chips 4         # the multi-chip paths only

One process, data generated from ``--seed``, the library's own entry
points (the calls ``repro.launch.train`` and ``repro.launch.serve``
make), at the width of ``configs/lightgcn_baco.py``'s full config:
amazonbook scale (52,643 users x 91,599 items, ~3 M interactions),
d = 64, 3 LightGCN layers, codebooks at a quarter of the rows.

Phases on one chip:
  device   the first device must be a TPU; otherwise exit non-zero
           before any work.
  data     ``paperlike_dataset("amazonbook")`` (host, numpy).
  cluster  ``ClusterEngine()`` (auto: "jax" on one chip) ``.build``;
           the sketch must stay within its row budget.
  train    BPR steps with the "fused" trainer and a few with the
           default "host" one, default lookup backend (the Pallas
           codebook kernel on TPU, differentiated through its VJP);
           losses finite and falling.
  serve    ``Trainer.export`` -> ``RecsysSession.from_artifact`` ->
           ``BatchDispatcher`` on the 1,8,64,512 ladder, for the dense
           and the fused scorer; compiles <= buckets; every response's
           top-k checked against a float32 numpy reference that shares
           no code with the kernels (see ``check_topk``).

With ``--chips 4`` only the multi-chip paths run, each beside what it is
compared with: the "jax_sharded" cluster build against the one-device
"jax" build (labels equal) and "fused_sharded" training against "fused"
(losses allclose).

Times printed are host-clock times on the device named on the first
line: "setup" includes compilation, "steady" does not. The last line of
stdout is one JSON object: {"ok": true, "device": {...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

DATASET = "amazonbook"
DIM = 64
RATIO = 0.25
BATCH = 2048
LR = 5e-3
CHUNK = 16
FUSED_STEPS = 32
HOST_STEPS = 4
BUCKETS = (1, 8, 64, 512)
K = 20
REQUESTS_PER_BUCKET = 3
# Score tolerance against the f32 reference, per (user, item):
#   SCORE_RTOL * sum_i |u_i v_i| + SCORE_ATOL.
# An f32 matmul on TPU defaults to one bf16 pass: both inputs are rounded
# to 8 significant bits (unit roundoff 2^-8), so each product carries a
# relative error of at most ~2^-7. SCORE_ATOL covers the f32
# reassociation of the propagation (prefix-scan segment sums) upstream.
SCORE_RTOL = 2.0 ** -7
SCORE_ATOL = 1e-5


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def expect(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_device(chips: int) -> dict:
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {platform!r}")
    if len(devices) != chips:
        raise SystemExit(f"chip_smoke: expected {chips} chip(s), JAX found "
                         f"{len(devices)}")
    info = {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    log(f"device: {info['kind']} x{info['count']} ({platform})")
    return info


def phase_data(dataset: str, seed: int):
    from repro.data import paperlike_dataset
    from repro.obs import clock
    t0 = clock.now()
    _, _, _, train, test = paperlike_dataset(dataset, seed=seed)
    log(f"data {dataset}: {train.n_users} users x {train.n_items} items, "
        f"{train.n_edges} train edges (host {clock.now() - t0:.2f} s)")
    return train, test


def phase_cluster(train, *, dim: int, ratio: float):
    from repro.core import ClusterEngine, make_weights
    from repro.obs import clock
    engine = ClusterEngine()
    t0 = clock.now()
    sketch = engine.build(train, d=dim, ratio=ratio)
    t_build = clock.now() - t0
    meta = sketch.meta
    rows = sketch.k_users + sketch.k_items
    expect(rows <= meta["budget"],
           f"sketch has {rows} codebook rows, budget {meta['budget']}")
    # steady: one more solve at the chosen gamma, programs compiled
    wu, wv = make_weights(train, meta["scheme"])
    t0 = clock.now()
    engine.solve(train, wu, wv, meta["gamma"], meta["eff_budget"])
    t_solve = clock.now() - t0
    log(f"cluster: solver={meta['solver']} gamma={meta['gamma']:g} "
        f"rows {sketch.k_users}+{sketch.k_items}={rows} <= budget "
        f"{meta['budget']}; setup (build, incl. compile) {t_build:.3f} s, "
        f"steady one solve {t_solve:.3f} s")
    return sketch


def _trainer(train, sketch, *, backend, dim, batch, seed, lookup_backend,
             n_devices=None, chunk=CHUNK):
    from repro.training import TrainConfig, Trainer
    cfg = TrainConfig(dim=dim, batch_size=batch, lr=LR, seed=seed,
                      backend=backend, chunk_size=chunk,
                      lookup_backend=lookup_backend, n_devices=n_devices)
    return Trainer(train, sketch, cfg)


def _timed_run(tr, first: int, total: int):
    """(losses, setup s, steady s/step): the first ``first`` steps
    include compilation, the rest up to ``total`` are steady."""
    from repro.obs import clock
    t0 = clock.now()
    losses = tr.run(steps=first, log_every=0)
    t_setup = clock.now() - t0
    t0 = clock.now()
    losses += tr.run(steps=total, log_every=0)
    t_steady = (clock.now() - t0) / max(1, total - first)
    return np.asarray(losses, np.float64), t_setup, t_steady


def phase_train(train, sketch, *, dim: int, batch: int, fused_steps: int,
                host_steps: int, seed: int, lookup_backend=None,
                chunk: int = CHUNK):
    fused = _trainer(train, sketch, backend="fused", dim=dim, batch=batch,
                     seed=seed, lookup_backend=lookup_backend, chunk=chunk)
    lf, setup, steady = _timed_run(fused, chunk, fused_steps)
    expect(np.all(np.isfinite(lf)), f"fused losses not finite: {lf}")
    w = max(1, len(lf) // 4)
    expect(lf[-w:].mean() < lf[:w].mean(),
           f"fused losses not falling: {lf}")
    log(f"train[fused]: {len(lf)} steps batch {batch}, loss "
        f"{lf[0]:.5f} -> {lf[-1]:.5f}; setup (first {chunk} steps, incl. "
        f"compile) {setup:.3f} s, steady {steady * 1e3:.3f} ms/step")

    host = _trainer(train, sketch, backend=None, dim=dim, batch=batch,
                    seed=seed, lookup_backend=lookup_backend)
    lh, setup, steady = _timed_run(host, 1, host_steps)
    expect(np.all(np.isfinite(lh)), f"host losses not finite: {lh}")
    expect(lh[-1] < lh[0], f"host losses not falling: {lh}")
    log(f"train[{host.backend.name}]: {len(lh)} steps batch {batch}, loss "
        f"{lh[0]:.5f} -> {lh[-1]:.5f}; setup (first step, incl. compile) "
        f"{setup:.3f} s, steady {steady * 1e3:.3f} ms/step")
    return fused


def phase_serve(trainer, *, buckets, k: int, requests_per_bucket: int,
                seed: int):
    """Serve through both scorers; returns {scorer: [(users, vals, ids)]}
    and the exported artifact."""
    from repro.obs import clock
    from repro.serve import BatchDispatcher, RecsysSession
    art = trainer.export()
    n_users = int(art.model["n_users"])
    responses = {}
    for scorer in ("dense", "fused"):
        rng = np.random.default_rng(seed)
        session = RecsysSession.from_artifact(art, k=k, scorer=scorer)
        disp = BatchDispatcher(session, buckets=buckets)
        t0 = clock.now()
        disp.warmup()
        t_warm = clock.now() - t0
        out, lo = [], 0
        for b in disp.buckets:
            for _ in range(requests_per_bucket):
                users = rng.integers(0, n_users, int(rng.integers(lo + 1, b + 1)))
                vals, ids = disp(users.astype(np.int32))
                out.append((users, np.asarray(vals), np.asarray(ids)))
            lo = b
        st = disp.stats()
        expect(disp.compile_count <= len(disp.buckets),
               f"{scorer}: {disp.compile_count} compiles for "
               f"{len(disp.buckets)} buckets")
        log(f"serve[{scorer}]: setup (warmup, compiles {len(disp.buckets)} "
            f"buckets) {t_warm:.3f} s; steady {st['requests']} requests "
            f"p50 {st['p50_ms']:.3f} ms p99 {st['p99_ms']:.3f} ms; "
            f"compiles {st['compiles']} <= {len(disp.buckets)}")
        responses[scorer] = out
    return art, responses


# ---------------------------------------------------------------------------
# float32 reference: numpy only, no code shared with the kernels
# ---------------------------------------------------------------------------
def _expand(codebook, idx):
    """Σ_h Z[idx[:, h]], a repeated index counted once (binary Y)."""
    out = codebook[idx[:, 0]].copy()
    for h in range(1, idx.shape[1]):
        dup = np.zeros(idx.shape[0], bool)
        for j in range(h):
            dup |= idx[:, h] == idx[:, j]
        out += np.where(dup[:, None], 0.0, codebook[idx[:, h]])
    return out


def _segment_sum(rows, order, seg_sorted, n: int):
    """Sum of ``rows[order]`` per segment id (``seg_sorted`` ascending)."""
    starts = np.searchsorted(seg_sorted, np.arange(n))
    ends = np.searchsorted(seg_sorted, np.arange(n), side="right")
    out = np.zeros((n, rows.shape[1]), np.float32)
    full = starts < ends
    if full.any():
        out[full] = np.add.reduceat(rows[order], starts[full], axis=0)
    return out


def reference_tables(art):
    """(U [n_users, d], V [n_items, d]) LightGCN tables in float32 numpy:
    expand the codebooks through the sketch, propagate with segment sums
    over the symmetric-normalized training edges, mean of the layers."""
    m = art.model
    nu, nv = int(m["n_users"]), int(m["n_items"])
    u = _expand(np.asarray(art.params["user_table"], np.float32),
                np.asarray(art.sketch.user_idx))
    v = _expand(np.asarray(art.params["item_table"], np.float32),
                np.asarray(art.sketch.item_idx))
    eu = np.asarray(art.edges["edge_u"], np.int64)
    ev = np.asarray(art.edges["edge_v"], np.int64)
    du = np.maximum(np.bincount(eu, minlength=nu), 1).astype(np.float32)
    dv = np.maximum(np.bincount(ev, minlength=nv), 1).astype(np.float32)
    w = (1.0 / np.sqrt(du[eu] * dv[ev])).astype(np.float32)[:, None]
    ord_u = np.argsort(eu, kind="stable")
    ord_v = np.argsort(ev, kind="stable")
    acc_u, acc_v = u.copy(), v.copy()
    for _ in range(int(m["n_layers"])):
        u, v = (_segment_sum(v[ev] * w, ord_u, eu[ord_u], nu),
                _segment_sum(u[eu] * w, ord_v, ev[ord_v], nv))
        acc_u += u
        acc_v += v
    layers = int(m["n_layers"]) + 1
    return acc_u / layers, acc_v / layers


def check_topk(users, vals, ids, U, V, k: int):
    """Device top-k against the reference scores U[users] @ V.T.

    Passes when the ids are distinct and in range, the values are
    non-increasing and each within the score tolerance of the reference
    score of its id, and the id set is a top-k of the reference up to
    near-ties at the k-th place: no selected item scores more than
    twice the row's tolerance below the reference k-th value, and no
    unselected item more than that above the weakest selected one.
    Returns (rows checked, fraction of ids equal to the exact
    reference top-k)."""
    uq = U[users]
    s = uq @ V.T
    bound = SCORE_RTOL * (np.abs(uq) @ np.abs(V).T) + SCORE_ATOL
    n = V.shape[0]
    expect(ids.shape == (len(users), k), f"ids shape {ids.shape}")
    expect(np.all((ids >= 0) & (ids < n)), "ids out of range")
    srt = np.sort(ids, axis=1)
    expect(np.all(srt[:, 1:] != srt[:, :-1]), "repeated ids in a row")
    expect(np.all(np.diff(vals, axis=1) <= 0), "values not sorted")
    rows = np.arange(len(users))[:, None]
    got = s[rows, ids]
    expect(np.all(np.abs(vals - got) <= bound[rows, ids]),
           f"values off the reference by up to "
           f"{np.max(np.abs(vals - got)):.3e}")
    slack = 2.0 * bound.max(axis=1)
    kth = np.partition(s, n - k, axis=1)[:, n - k]
    expect(np.all(got >= (kth - slack)[:, None]),
           "a selected item is below the reference k-th score")
    rest = s.copy()
    rest[rows, ids] = -np.inf
    expect(np.all(rest.max(axis=1) <= got.min(axis=1) + slack),
           "an unselected item beats the selection beyond tolerance")
    ref_ids = np.argpartition(-s, k - 1, axis=1)[:, :k]
    exact = np.mean([len(set(a) & set(b)) / k
                     for a, b in zip(ids.tolist(), ref_ids.tolist())])
    return len(users), float(exact)


def phase_check(art, responses, *, k: int):
    from repro.obs import clock
    t0 = clock.now()
    U, V = reference_tables(art)
    log(f"reference: f32 numpy tables {U.shape} + {V.shape} "
        f"(host {clock.now() - t0:.2f} s)")
    for scorer, out in responses.items():
        n_rows, overlaps = 0, []
        for users, vals, ids in out:
            r, exact = check_topk(users, vals, ids, U, V, k)
            n_rows += r
            overlaps.append(exact)
        log(f"top-{k} [{scorer}] agrees with the f32 reference on "
            f"{n_rows} rows in {len(out)} requests (tolerance "
            f"{SCORE_RTOL:g}*sum|u*v| + {SCORE_ATOL:g}); ids equal to the "
            f"exact reference top-{k}: {100 * np.mean(overlaps):.2f}%")


# ---------------------------------------------------------------------------
# four chips: the sharded paths beside their one-device references
# ---------------------------------------------------------------------------
def phase_sharded(train, *, dim: int, ratio: float, batch: int, steps: int,
                  seed: int, n_devices: int, gamma: float = 1.0,
                  lookup_backend=None, chunk: int = 8):
    from repro.core import ClusterEngine
    from repro.obs import clock
    sketches = {}
    for solver in ("jax", "jax_sharded"):
        t0 = clock.now()
        sketches[solver] = ClusterEngine(solver=solver).build(
            train, d=dim, ratio=ratio, gamma=gamma)
        log(f"cluster[{solver}]: build at gamma {gamma:g} (incl. compile) "
            f"{clock.now() - t0:.3f} s")
    a, b = sketches["jax"], sketches["jax_sharded"]
    diff = int(np.sum(a.meta["joint_labels"] != b.meta["joint_labels"]))
    expect(diff == 0, f"jax_sharded labels differ from jax on {diff} nodes")
    expect(np.array_equal(a.user_idx, b.user_idx)
           and np.array_equal(a.item_idx, b.item_idx),
           "jax_sharded sketch differs from jax")
    log(f"cluster: jax_sharded labels equal jax on all {train.n_nodes} "
        f"nodes ({a.k_users}+{a.k_items} rows)")

    losses = {}
    for backend in ("fused", "fused_sharded"):
        tr = _trainer(train, a, backend=backend, dim=dim, batch=batch,
                      seed=seed, lookup_backend=lookup_backend,
                      n_devices=n_devices if backend == "fused_sharded"
                      else None, chunk=chunk)
        lb, setup, steady = _timed_run(tr, chunk, steps)
        expect(np.all(np.isfinite(lb)), f"{backend} losses not finite")
        losses[backend] = lb
        log(f"train[{backend}]: {len(lb)} steps batch {batch}, loss "
            f"{lb[0]:.5f} -> {lb[-1]:.5f}; setup (first {chunk} steps, "
            f"incl. compile) {setup:.3f} s, steady "
            f"{steady * 1e3:.3f} ms/step")
    la, lb = losses["fused"], losses["fused_sharded"]
    err = float(np.max(np.abs(la - lb)))
    expect(np.allclose(la, lb, rtol=1e-5, atol=1e-6),
           f"fused_sharded losses differ from fused by up to {err:.3e}")
    log(f"train: fused_sharded losses allclose to fused over {len(la)} "
        f"steps (max abs diff {err:.3e}, rtol 1e-5, atol 1e-6)")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip paths and what they "
                         "are compared with")
    args = ap.parse_args(argv)
    device = phase_device(args.chips)

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    train, _ = phase_data(DATASET, args.seed)
    if args.chips == 4:
        phase_sharded(train, dim=DIM, ratio=RATIO, batch=BATCH, steps=16,
                      seed=args.seed, n_devices=4)
    else:
        sketch = phase_cluster(train, dim=DIM, ratio=RATIO)
        trainer = phase_train(train, sketch, dim=DIM, batch=BATCH,
                              fused_steps=FUSED_STEPS, host_steps=HOST_STEPS,
                              seed=args.seed)
        art, responses = phase_serve(trainer, buckets=BUCKETS, k=K,
                                     requests_per_bucket=REQUESTS_PER_BUCKET,
                                     seed=args.seed)
        phase_check(art, responses, k=K)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
