"""Streaming launcher: replay an interaction stream through the online
co-clustering + hot-swap serving stack (``repro.stream``).

The loop a live deployment runs, in one process:

    bootstrap   cluster + train the warm prefix, export the artifact,
                open a capacity-padded RecsysSession
    per step    append arriving edges -> cold-assign new users/items
                (one LP half-step over their incident edges) ->
                periodically refresh (budgeted warm re-solve + short
                fine-tune) -> publish a delta -> hot-swap the session
                between requests (zero new XLA compiles under the
                capacity ladder)

The stream is the drifting planted-co-cluster generator
(``repro.data.drifting_coclusters``); ``--artifact DIR`` additionally
publishes the final bundle and the last delta next to it. For the
measured record, run ``benchmarks/stream_bench.py --json``.
"""
from __future__ import annotations

import argparse
import sys

from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-users", type=int, default=1200)
    ap.add_argument("--n-items", type=int, default=960)
    ap.add_argument("--k-true", type=int, default=20)
    ap.add_argument("--avg-deg", type=int, default=10)
    ap.add_argument("--t-steps", type=int, default=4,
                    help="stream steps (arrival waves)")
    ap.add_argument("--drift", type=float, default=0.08,
                    help="fraction of users migrating cluster per step")
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--steps", type=int, default=200,
                    help="BPR steps for the warm bootstrap train")
    ap.add_argument("--tune-steps", type=int, default=40,
                    help="fine-tune steps per refresh")
    ap.add_argument("--refresh-every", type=int, default=2,
                    help="refresh cadence in stream steps (0 disables)")
    ap.add_argument("--requests-per-step", type=int, default=8,
                    help="serving requests issued between event batches")
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cluster-solver", default="auto",
                    help="ClusterEngine solver: auto | jax | jax_sharded "
                         "| numpy")
    ap.add_argument("--artifact", default=None,
                    help="publish the final artifact (and last delta) here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from repro.core import ClusterEngine, normalize_solver
    from repro.data import drifting_coclusters
    from repro.stream import ReplayConfig, StreamUpdater, replay
    from repro.training import Trainer, TrainConfig

    stream = drifting_coclusters(args.n_users, args.n_items, args.k_true,
                                 args.avg_deg, T=args.t_steps,
                                 drift=args.drift, seed=args.seed)
    engine = ClusterEngine(solver=normalize_solver(args.cluster_solver))
    print(f"[stream] warm prefix {stream.n_warm_users}x"
          f"{stream.n_warm_items} ({stream.base.n_edges} edges); "
          f"{args.t_steps} waves to {args.n_users}x{args.n_items}")
    sketch = engine.build(stream.base, d=args.dim, ratio=0.25)
    tr = Trainer(stream.base, sketch,
                 TrainConfig(dim=args.dim, steps=args.steps,
                             batch_size=1024, lr=5e-3, seed=args.seed))
    tr.run(log_every=0)
    art = tr.export()
    print(f"[stream] bootstrap: {sketch.k_users}+{sketch.k_items} codebook "
          f"rows, gamma={sketch.meta['gamma']:.3g}")

    caps = {"n_users": args.n_users, "n_items": args.n_items,
            "k_users": args.n_users // 2, "k_items": args.n_items // 2,
            "n_edges": stream.base.n_edges
            + sum(s.edge_u.size for s in stream.steps)}
    # capacity-padded refresh solves run the jax capped program; a
    # pinned non-jax solver must really be used, so it forgoes them
    solver = normalize_solver(args.cluster_solver)
    updater_caps = caps if solver in (None, "jax") else None
    if updater_caps is None:
        print(f"[stream] note: --cluster-solver={args.cluster_solver} "
              f"pins refresh solves to that solver; capacity-stable "
              f"(compile-once) refresh needs the jax solver")
    updater = StreamUpdater.from_trainer(tr, engine=engine,
                                         capacity=updater_caps)
    session = art.session(k=args.k, capacity=caps)
    session.warmup(8)

    report = replay(updater, stream.steps, session,
                    ReplayConfig(refresh_every=args.refresh_every,
                                 tune_steps=args.tune_steps,
                                 requests_per_step=args.requests_per_step,
                                 request_batch=8, seed=args.seed),
                    log=lambda s: print(f"[stream] {s}"))
    final = report["final_artifact"]
    tele = report["telemetry"]
    print(f"[stream] done: {tele['appends']} appends "
          f"(+{tele['cold_users']} users, +{tele['cold_items']} items, "
          f"+{tele['new_edges']} edges), {tele['refreshes']} refreshes "
          f"(mean churn {tele['churn_mean']}), {tele['swaps']} swaps "
          f"p99={tele['swap_p99_ms']}ms, cold-assign "
          f"first={report['cold_assign_first_ms']}ms (compile) / "
          f"warm p50={report['cold_assign_warm_p50_ms']}ms, "
          f"session compiles="
          f"{session.compile_count}, mean delta "
          f"{report['delta_bytes_mean'] // 1024}KB")
    print(f"[stream] serving telemetry: {session.stats()}")
    if args.artifact:
        path = final.save(args.artifact)
        print(f"[stream] published final artifact to {path} "
              f"(id {final.content_id()})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
