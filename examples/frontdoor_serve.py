"""Frontdoor example: concurrent clients, shared sessions, swap under
load.

PR 2 gave one synchronous caller a bucket-ladder dispatcher; this demo
is the deployment story ABOVE it: many client threads firing
concurrently at an async front end that coalesces their requests into
shared batches, serves three logical tenants from two device-resident
sessions, answers hot users from a response cache, and hot-swaps one
tenant to a fine-tuned artifact version WHILE the others keep hammering
it — all without compiling a single new XLA program once the ladder is
warm.

The assertions at the bottom are the subsystem's contract (CI runs this
file as a smoke test):

  * every response arrives and is identity-correct per request,
  * the mid-load swap takes the in-place (capacity-ladder) path,
  * compile count after warmup stays FLAT through concurrent load,
    the swap included,
  * under --trace-out, the exported JSONL trace holds, for at least one
    request, the full nested span chain (request -> admit/queue/batch ->
    dispatch) under a single trace ID, and obs_report renders
    it — the end-to-end observability contract of ISSUE 10.

Run:  PYTHONPATH=src python examples/frontdoor_serve.py [--steps N]
      [--trace-out traces/frontdoor_trace.jsonl]
"""
import argparse
import threading

import numpy as np

from repro import obs
from repro.core import ClusterEngine
from repro.data import paperlike_dataset
from repro.frontdoor import Frontdoor, FrontdoorConfig
from repro.training import Trainer, TrainConfig


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--steps", type=int, default=40,
                    help="base BPR training steps")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=25,
                    help="requests per client thread")
    ap.add_argument("--trace-out", default=None, metavar="JSONL",
                    help="enable obs tracing and export the trace here")
    args = ap.parse_args(argv)
    if args.trace_out:
        obs.configure(enabled=True, sample_rate=1.0)

    # --- publish two versions from one training run ---------------------
    _, _, _, train, _ = paperlike_dataset("beauty_s", seed=0)
    sketch = ClusterEngine().build(train, d=args.dim, ratio=0.25)
    tr = Trainer(train, sketch,
                 TrainConfig(dim=args.dim, steps=args.steps,
                             batch_size=1024, lr=5e-3))
    tr.run(log_every=0)
    base = tr.export()
    tr.run(steps=tr.step + 16, log_every=0)          # keep fine-tuning
    v2 = base.apply_delta(tr.export().delta(base))   # ship the delta
    print(f"published base {base.content_id()[:12]} and fine-tuned "
          f"v2 {v2.content_id()[:12]} (delta-verified)")

    # --- the front end: 3 tenants, 2 device sessions --------------------
    fd = Frontdoor(FrontdoorConfig(queue_size=256, flush_ms=2.0,
                                   cache_entries=512, k=10,
                                   buckets=(1, 8, 64)))
    fd.attach("web", base, capacity="auto")   # sole owner: swappable
    shared = base.quantize()
    fd.attach("mobile", shared)               # one int8 session,
    fd.attach("beta", shared)                 # two tenants
    compiles_warm = fd.compile_count
    print(f"3 tenants over {fd.registry.n_sessions} sessions, ladder "
          f"warmed: {compiles_warm} compiles")

    # --- concurrent clients + one mid-load swap -------------------------
    n_users = train.n_users
    tenants = ("web", "mobile", "beta")
    errors = []

    def client(cid: int):
        rng = np.random.default_rng(cid)
        try:
            for i in range(args.requests):
                ids = rng.integers(0, n_users, int(rng.choice((1, 2, 4, 8))))
                vals, items = fd(ids, tenant=tenants[cid % len(tenants)])
                assert items.shape[0] == ids.size, \
                    f"client {cid} req {i}: got {items.shape[0]} rows " \
                    f"for {ids.size} users"
        except Exception as e:                     # surface across threads
            errors.append(e)

    with fd:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(args.clients)]
        for t in threads:
            t.start()
        swap = fd.swap("web", v2)                  # under live traffic
        for t in threads:
            t.join()
    if errors:
        raise errors[0]

    st = fd.stats()
    print(f"{st['responses']} responses over {st['batches']} batches "
          f"(coalesced={st['coalesced']}, fill={st['batch_fill_mean']}, "
          f"cache_hits={st['cache_hits']}): e2e p50={st['e2e_p50_ms']}ms "
          f"p99={st['e2e_p99_ms']}ms")
    print(f"mid-load swap: mode={swap['mode']} pause={swap['pause_ms']}ms "
          f"(cache invalidated: {swap.get('cache_invalidated', 0)} rows)")

    # --- the contract ---------------------------------------------------
    assert st["responses"] == args.clients * args.requests, \
        "every submitted request must be answered exactly once"
    assert swap["mode"] == "swapped", \
        f"expected the in-place capacity-ladder swap, got {swap['mode']}"
    assert fd.compile_count == compiles_warm, \
        f"compiles grew under load: {compiles_warm} -> {fd.compile_count}"
    print(f"compiles: {compiles_warm} after warmup -> {fd.compile_count} "
          f"after concurrent load + hot swap — the ladder held")

    # --- the trace contract (ISSUE 10 acceptance) -----------------------
    if args.trace_out:
        from repro.obs.report import read_trace, trace_ids, trace_tree
        n = obs.export_jsonl(obs.get_tracer(), args.trace_out,
                             metrics_snapshot=fd.telemetry.registry
                             .snapshot())
        assert n > 0, "tracing was on but no spans were exported"
        data = read_trace(args.trace_out)     # raises if malformed

        def depth(sp, d=1):
            return max([d] + [depth(c, d + 1) for c in sp["children"]])

        best = 0
        for tid in trace_ids(data["spans"]):
            spans = [s for s in data["spans"] if s["trace"] == tid]
            roots = trace_tree(data["spans"], tid)
            if (len(spans) >= 5 and len(roots) == 1
                    and max(depth(r) for r in roots) >= 3):
                best = max(best, len(spans))
        assert best >= 5, \
            "no request trace carried the full nested span chain " \
            "(>=5 spans, depth >=3, one root) under a shared trace ID"
        print(f"trace: {n} spans -> {args.trace_out}; deepest request "
              f"trace has {best} spans under one trace ID")


if __name__ == "__main__":
    main()
