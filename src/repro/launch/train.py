"""Training launcher.

Two modes:
  * paper pipeline (default): BACO-compress a synthetic interaction graph
    and train LightGCN+BPR end-to-end, with checkpoint/resume.
  * --arch <id>: run N smoke-scale train steps of any assigned arch
    (the full configs only lower on the production mesh — see dryrun.py).

Fault-tolerance knobs:
  --resume            resume from the newest checkpoint in --ckpt-dir
  --step-timeout S    straggler mitigation: if a step exceeds S seconds,
                      checkpoint and exit(17) so the cluster runner can
                      relaunch excluding the slow host (on this container
                      it demonstrates the checkpoint/exit path).
  --compress-grads    bf16|int8 DP-gradient compression (training/compress)
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def paper_pipeline(args):
    from repro.core import ClusterEngine, build_sketch, normalize_solver
    from repro.data import paperlike_dataset
    from repro.training import Trainer, TrainConfig

    g, uc, ic, train, test = paperlike_dataset(args.dataset, seed=args.seed)
    print(f"[train] dataset={args.dataset}: {train.n_users} users, "
          f"{train.n_items} items, {train.n_edges} edges")
    if args.method == "full":
        sketch = None
    elif args.method == "baco":
        engine = ClusterEngine(solver=normalize_solver(args.cluster_solver))
        sketch = engine.build(train, d=args.dim, ratio=args.ratio,
                              batched_gamma=args.batched_gamma)
    else:
        sketch = build_sketch(args.method, train,
                              budget=int(args.ratio * train.n_nodes))
    cfg = TrainConfig(dim=args.dim, steps=args.steps,
                      batch_size=args.batch_size, lr=args.lr,
                      ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                      seed=args.seed, backend=args.trainer_backend,
                      chunk_size=args.chunk_size, sampler=args.sampler)
    tr = Trainer(train, sketch, cfg)
    print(f"[train] backend={tr.backend.name} sampler={tr.sampler.name} "
          f"chunk={cfg.chunk_size}")
    if args.resume and tr.maybe_resume():
        print(f"[train] resumed at step {tr.step}")
    t_start = time.time()
    step_t0 = time.time()
    while tr.step < cfg.steps:
        tr.run(steps=min(tr.step + 50, cfg.steps), log_every=0)
        dt = time.time() - step_t0
        if args.step_timeout and dt > args.step_timeout * 50:
            print(f"[train] straggler detected ({dt:.1f}s for 50 steps): "
                  f"checkpointing and exiting for relaunch")
            tr.ckpt.maybe_save(tr.step, tr._state_tree(),
                               extra={"sampler": tr.sampler.state_dict()},
                               force=True)
            return 17
        step_t0 = time.time()
    m = tr.evaluate(test)
    print(f"[train] method={args.method} params={tr.n_params()} "
          f"recall@20={m['recall']:.4f} ndcg@20={m['ndcg']:.4f} "
          f"({time.time()-t_start:.1f}s)")
    return 0


def arch_pipeline(args):
    from repro.launch.steps import build_cell
    cell = build_cell(args.arch, args.shape, mesh=None, smoke=True)
    fn = jax.jit(cell.fn)
    out = fn(*cell.args)
    t0 = time.time()
    arglist = list(cell.args)
    for i in range(args.steps):
        out = fn(*arglist)
        if cell.kind == "train":
            arglist[0], arglist[1] = out[0], out[1]
    dt = time.time() - t0
    loss = out[2] if cell.kind == "train" else None
    print(f"[train] {args.arch}:{args.shape} x{args.steps} smoke steps in "
          f"{dt:.2f}s" + (f" loss={float(loss):.4f}" if loss is not None
                          else ""))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default="train_batch")
    ap.add_argument("--dataset", default="gowalla_s")
    ap.add_argument("--method", default="baco")
    ap.add_argument("--ratio", type=float, default=0.25)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--batch-size", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--step-timeout", type=float, default=0)
    ap.add_argument("--compress-grads", default="none",
                    choices=["none", "bf16", "int8"])
    ap.add_argument("--cluster-solver", default="auto",
                    help="ClusterEngine solver: auto | jax | jax_sharded "
                         "| numpy (auto picks jax_sharded on multi-device "
                         "hosts)")
    ap.add_argument("--trainer-backend", default="auto",
                    help="trainer backend: auto | host (seed reference, "
                         "per-step host sync) | fused (lax.scan chunks, "
                         "device-resident) | fused_sharded (data-parallel "
                         "over the local device mesh)")
    ap.add_argument("--chunk-size", type=int, default=16,
                    help="steps fused per dispatch (fused backends)")
    ap.add_argument("--sampler", default=None,
                    choices=["numpy", "device"],
                    help="BPR sampler (default: the backend's native one)")
    ap.add_argument("--batched-gamma", action="store_true",
                    help="vmap-batched gamma grid search (concurrent "
                         "lanes; identical selection to the sequential "
                         "walk)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.arch:
        if args.arch.startswith(("gemma", "qwen", "kimi", "dbrx")):
            args.shape = ("train_4k" if args.shape == "train_batch"
                          else args.shape)
        return arch_pipeline(args)
    return paper_pipeline(args)


if __name__ == "__main__":
    sys.exit(main())
