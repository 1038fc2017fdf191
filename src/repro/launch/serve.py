"""Serving launcher: thin CLI over the repro.serve API.

Paper path (default): obtain a CompressedArtifact — loaded from
``--artifact DIR`` when one is published there, otherwise trained on the
spot (and exported to ``--artifact`` if given, so the next run skips the
cluster+train phase entirely) — then serve batched top-k requests
through ``RecsysSession`` + ``BatchDispatcher`` and report p50/p99
latency plus compile-count telemetry.

Every table lookup routes through the EmbeddingEngine; ``--backend``
overrides the lookup backend recorded in the artifact ("gather" |
"onehot" | "pallas"; "auto" keeps the artifact's choice) — see
benchmarks/serve_bench.py --json for the measured sweep. ``--scorer
fused`` swaps the dense score-then-top_k readout for the one-pass
fused Pallas scorer ("auto"/"dense" keep the default dense path).

For the assigned archs, ``--arch <id> --shape serve_p99|decode_32k``
serves the smoke-scale cell through ``ArchSession`` (full configs are
dry-run only); decode shapes donate the KV cache between requests.
"""
from __future__ import annotations

import argparse
import sys

import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def _get_artifact(args):
    from repro.serve import CompressedArtifact
    if args.artifact:
        try:
            art = CompressedArtifact.load(args.artifact)
            print(f"[serve] loaded artifact {args.artifact} "
                  f"(method={art.provenance.get('method', '?')}, "
                  f"{art.n_params()} params)")
            return art
        except FileNotFoundError:
            pass
    from repro.core import ClusterEngine, normalize_solver
    from repro.data import paperlike_dataset
    from repro.embedding import normalize_backend
    from repro.training import Trainer, TrainConfig
    backend = normalize_backend(args.backend)
    _, _, _, train, _ = paperlike_dataset(args.dataset, seed=0)
    engine = ClusterEngine(solver=normalize_solver(args.cluster_solver))
    sketch = engine.build(train, d=args.dim, ratio=0.25)
    tr = Trainer(train, sketch, TrainConfig(dim=args.dim, steps=args.steps,
                                            batch_size=2048, lr=5e-3,
                                            lookup_backend=backend))
    tr.run(log_every=0)
    art = tr.export(args.artifact)
    if args.artifact:
        print(f"[serve] exported artifact to {args.artifact}")
    return art


def paper_serving(args):
    from repro.embedding import normalize_backend
    from repro.serve import BatchDispatcher, RecsysSession
    art = _get_artifact(args)
    # "auto" -> None: keep the backend recorded in the artifact
    session = RecsysSession.from_artifact(
        art, k=args.k, backend=normalize_backend(args.backend),
        scorer=args.scorer)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    disp = BatchDispatcher(session, buckets=buckets)
    disp.warmup()

    rng = np.random.default_rng(0)
    n_users = art.model["n_users"]
    top = disp.buckets[-1]            # dispatcher's sorted ladder
    for _ in range(args.n_requests):
        size = (int(rng.integers(1, top + 1))
                if args.randomize_batches else args.batch)
        disp(rng.integers(0, n_users, size))
    st = disp.stats()
    sk = art.sketch
    compression = (f"codebook {sk.k_users}+{sk.k_items} rows, "
                   f"{sk.compression_ratio(art.model['dim'])*100:.0f}% "
                   f"of full params" if sk is not None else "uncompressed")
    print(f"[serve] {st['requests']} requests "
          f"(batch={'rand' if args.randomize_batches else args.batch}, "
          f"backend={args.backend}): p50={st['p50_ms']:.2f}ms "
          f"p99={st['p99_ms']:.2f}ms compiles={st['compiles']} "
          f"buckets={st['bucket_counts']} ({compression})")
    return 0


def arch_serving(args):
    from repro.serve import ArchSession
    session = ArchSession(args.arch, args.shape, backend=args.backend)
    session.warmup()
    for _ in range(args.n_requests):
        session()
    st = session.stats()
    print(f"[serve] {args.arch}:{args.shape} smoke (backend={args.backend}"
          f"{', cache donated' if st['cache_donated'] else ''}) "
          f"p50={st['p50_ms']:.2f}ms p99={st['p99_ms']:.2f}ms "
          f"compiles={st['compiles']}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default="serve_p99")
    ap.add_argument("--dataset", default="gowalla_s")
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--n-requests", type=int, default=50)
    ap.add_argument("--artifact", default=None,
                    help="artifact dir: load if published, else train "
                         "once and export here (compress-once/serve-many)")
    ap.add_argument("--buckets", default="1,8,64,512",
                    help="BatchDispatcher bucket ladder (comma-separated)")
    ap.add_argument("--randomize-batches", action="store_true",
                    help="draw each request's batch size from [1, top "
                         "bucket] instead of --batch")
    ap.add_argument("--backend", default="auto",
                    help="EmbeddingEngine lookup backend override "
                         "(auto keeps the artifact's choice)")
    ap.add_argument("--scorer", default="auto",
                    help="top-k readout: dense score-then-top_k (auto/"
                         "dense) or the fused Pallas scorer")
    ap.add_argument("--cluster-solver", default="auto",
                    help="ClusterEngine solver for on-the-spot "
                         "compression (auto picks per platform)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    # validate against the live registries, not a hard-coded list: a
    # typo'd name must fail HERE with what actually exists, not after
    # minutes of clustering+training (the build_sketch re-raise pattern)
    from repro.core import normalize_solver
    from repro.embedding import normalize_backend
    from repro.serve.session import normalize_scorer
    for fn, value in ((normalize_backend, args.backend),
                      (normalize_scorer, args.scorer),
                      (normalize_solver, args.cluster_solver)):
        try:
            fn(value)
        except (KeyError, ValueError) as e:
            ap.error(str(e.args[0] if e.args else e))
    if args.arch:
        return arch_serving(args)
    return paper_serving(args)


if __name__ == "__main__":
    sys.exit(main())
