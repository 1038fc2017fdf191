#!/usr/bin/env python3
"""Find an open-loop serving cell's knee: one set-up, then one window per
rate of a rising sweep.

    python3 bench/sweep.py --workload <serve cell> --seed <n> --seconds <s> \
        --rates 0.8 1.0 1.2 ...

Each window replays the cell's traffic (its traffic seed, sizes and
users) at the given rate. A rate's backlog grows when the requests due
in the window's last quarter wait, on average, more than one mean
dispatch longer than those due in its first quarter; the knee is the
highest rate before the first that grows. Prints one JSON line per rate
and a last line with the knee. Used once, when the cell's rate is set;
the benchmark's own runs never sweep.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import contextlib

    import numpy as np

    from bench import drive_serve as S
    from bench import harness as H

    spec = H.load_spec()
    workload = H.find_workload(spec, args.workload)
    config = H.load_config(workload["config"])
    traffic = H.load_traffic(workload["traffic"])
    H.require_device(int(workload["chips"]))
    H.enable_compile_cache()
    run = H.Run(config=config, traffic=traffic, workload=workload)
    ctx = types.SimpleNamespace(
        config=config, traffic=traffic, workload=workload, seed=args.seed,
        seconds=args.seconds, trace=True, t_start=T_START, run=run,
        annotate=lambda name: contextlib.nullcontext())
    _, fd, tracer = S.build(ctx)
    knee = None
    for rate in args.rates:
        offsets, requests = S.open_requests(traffic, args.seconds,
                                            int(config["n_users"]),
                                            args.seed, rate=rate)
        tracer.clear()
        t0, t_end, lat, ok, _, late = S.open_loop(fd, ctx, offsets,
                                                   requests)
        spans = [s for s in tracer.spans() if s.name == "dispatch"]
        batches = len(spans)
        dispatch_ms = 1e3 * float(np.mean([s.t_end - s.t_start
                                           for s in spans]))
        q = max(1, len(lat) // 4)
        first, last = float(np.mean(lat[:q])), float(np.mean(lat[-q:]))
        grows = last - first > dispatch_ms
        row = {"rate_per_s": rate, "offered": len(requests),
               "answered": len(ok), "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "first_quarter_mean_ms": first, "last_quarter_mean_ms": last,
               "dispatch_mean_ms": dispatch_ms, "batches": batches,
               "generator_late_ms": late * 1e3, "grows": grows}
        print(json.dumps(row), flush=True)
        if grows:
            break
        knee = rate
    fd.stop()
    print(json.dumps({"knee_rate_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
