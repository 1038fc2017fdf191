#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's compared numbers and
its control's, seed by seed, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed the cell runs as ``bench/run.py`` runs it (set-up, a
window of ``--seconds``, the comparison with the reference), and then
the control is put in the program's place and compared the same way:
the plain reference computed in bfloat16, the precision below the
configuration's float32. Serving: the control's top-k for the same
users. Training: the control's steps from the same weights over the
same batches, as many as the run compares, and the fault of half the
batch left out with the mean over the rest, planted in the reference.
Each reading goes through the same checks, at the configuration's
limits, as a run's own numbers, and carries the ``correct`` they give.
With ``--control-seeds`` only those seeds run the control. Prints one
JSON line per seed. The benchmark's own runs never run a control.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def control_topk(ref_lo, tables_lo, users, k: int, block: int = 512):
    """(values, ids) [R, k]: the control's top-k for ``users``."""
    import jax
    import numpy as np

    vals, ids = [], []
    top = jax.jit(lambda s: jax.lax.top_k(s, k))
    for lo in range(0, len(users), block):
        v, i = top(ref_lo.scores(*tables_lo, users[lo:lo + block]))
        vals.append(np.asarray(v, np.float32))
        ids.append(np.asarray(i))
    return np.concatenate(vals), np.concatenate(ids)


def judged(gaps: dict, limits: dict) -> dict:
    """``gaps`` with the ``correct`` that the configuration's limits give
    them, by the checks that decide a run's ``correct``."""
    from bench.harness import Check
    checks = [Check(name, gaps[name], float(limits[name])) for name in limits]
    return dict(gaps, correct=all(c.ok for c in checks))


def serve_control(cfg, run, seed):
    import jax.numpy as jnp

    from bench import check, model
    c = run.compared
    lo = model.reference(cfg, c["inputs"], dtype=jnp.bfloat16)
    tables_lo = lo.tables(model.weights(cfg, c["inputs"], seed))
    vals, ids = control_topk(lo, tables_lo, c["users"], int(cfg["k"]))
    return {"control": judged(check.topk_gaps(c["ref"], *c["tables"],
                                              c["users"], vals, ids),
                              cfg["limits"]["serve"])}


def train_control(cfg, traffic, run):
    import jax.numpy as jnp

    from bench import check, model
    c = run.compared
    steps, batch = c["steps"], int(traffic["batch_size"])
    lr = float(traffic["lr"])
    out = {}
    lo = model.reference(cfg, c["inputs"], dtype=jnp.bfloat16)
    half = model.reference(cfg, c["inputs"])
    full_batch = half.batch
    half.batch = lambda s, t, b: tuple(x[:b // 2] for x in full_batch(s, t, b))
    for name, ref in (("control", lo), ("half_batch", half)):
        losses, p, m = ref.train(c["p0"], c["seed32"], steps, batch, lr)
        out[name] = judged(check.train_gaps(losses, c["p0"], p, m,
                                            *c["reference"]),
                           cfg["limits"]["train"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=None,
                    help="seeds that also run the control (default: all)")
    args = ap.parse_args(argv)

    from bench import harness as H
    from bench.run import run_cell

    spec = H.load_spec()
    workload = H.find_workload(spec, args.workload)
    config = H.load_config(workload["config"])
    traffic = H.load_traffic(workload["traffic"])
    device = H.require_device(int(workload["chips"]))
    H.enable_compile_cache()
    for seed in args.seeds:
        t0 = time.monotonic()
        result, run = run_cell(spec, workload, config, traffic, seed=seed,
                               seconds=args.seconds, trace=False,
                               device=device, t_start=t0)
        row = {"seed": seed, "correct": result["correct"],
               "program": {c.name: c.value for c in run.checks}}
        if args.control_seeds is None or seed in args.control_seeds:
            row.update(serve_control(config, run, seed)
                       if traffic["driver"] == "serve"
                       else train_control(config, traffic, run))
        row["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
        print(json.dumps(row), flush=True)
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
