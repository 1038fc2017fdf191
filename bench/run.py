#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell's configuration, traffic mix and
per-layer metrics are found by name (see ``bench/harness.py``). Set-up
makes the inputs and weights from ``--seed`` and warms every shape the
cell uses; then the window measures for ``--seconds``; then the outputs
of the window are compared with the configuration's plain reference.
With ``--trace 1`` the window runs under the profiler and the result
line carries the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit
(also printed as the last lines of standard error). Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _window_factory(trace_dir):
    import jax

    @contextlib.contextmanager
    def window():
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
    return window


def _annotate_factory(traced: bool):
    import jax

    def annotate(name: str):
        return (jax.profiler.TraceAnnotation(name) if traced
                else contextlib.nullcontext())
    return annotate


def run_cell(spec: dict, workload: dict, config: dict, traffic: dict, *,
             seed: int, seconds: float, trace: bool, device: dict,
             t_start: float):
    """Drive one cell; returns (result line dict, Run)."""
    from bench import harness as H
    from bench import trace as T
    from bench.counts import peaks

    run = H.Run(config=config, traffic=traffic, workload=workload)
    if device.get("platform") == "tpu":
        run.peak = peaks(device["kind"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    ctx = types.SimpleNamespace(
        config=config, traffic=traffic, workload=workload, seed=int(seed),
        seconds=float(seconds), trace=bool(trace), t_start=t_start, run=run,
        window=_window_factory(trace_dir), annotate=_annotate_factory(trace))
    try:
        H.driver(traffic).run(ctx)
        if trace_dir:
            run.trace = T.reduce_trace(T.find_xplane(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    dev = dict(device)
    dev["memory_peak_bytes"] = run.memory_peak_bytes
    if trace:
        metrics = {}
        for m in H.per_layer_for(spec, workload["name"]):
            value = H.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
    else:
        metrics = {m["name"]: {"value": run.end_to_end[m["name"]],
                               "unit": m["unit"]}
                   for m in H.end_to_end_for(spec, workload["name"])
                   if m["name"] in run.end_to_end}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in run.checks}
    return result, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness as H
    spec = H.load_spec()
    workload = H.find_workload(spec, args.workload)
    config = H.load_config(workload["config"])
    traffic = H.load_traffic(workload["traffic"])
    device = H.require_device(int(workload["chips"]))
    H.log(f"device {device['kind']} x{device['count']}; compile cache "
          f"{H.enable_compile_cache()}")
    result, run = run_cell(spec, workload, config, traffic, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           device=device, t_start=T_START)
    for c in run.checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r})"
              f"{'' if c.ok else '  FAILS'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
