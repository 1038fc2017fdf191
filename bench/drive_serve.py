"""Serving driver: top-k requests through ``Frontdoor.submit``.

Two loops, chosen by the traffic file's ``loop``:

* ``open``: independent users. Arrival offsets and request sizes are a
  fixed replay drawn from the traffic file's ``traffic_seed`` (Poisson at
  ``rate_per_s``, with bursts where the file sets ``burst_factor``,
  ``burst_frac``, ``burst_period_s``; sizes from ``sizes``); ``--seed``
  draws only the user ids (Zipf ``zipf_a``). One thread submits each
  request when it is due,
  another collects the answers in order. Each request is timed from when
  it was due, so a late generator or a stall counts. Reports
  ``serve_p95_ms`` over every request due in the window.
* ``closed``: ``clients`` threads, each sending ``request_users`` users
  at a time back to back, sweeping a permutation of all users drawn from
  ``--seed``. No request is sent after ``--seconds``; the window ends
  with the last answer. Reports ``serve_users_per_s``: every user
  answered over the whole window.

Set-up warms the session's buckets listed in ``warm_buckets`` (the
shapes the mix can reach) and the front door's path once. After the
window every answered row of the open loop, or ``check_requests``
requests of the closed loop drawn from ``--seed``, is compared with the
reference (``bench/check.py``).
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from bench import check, gen, model
from bench.harness import Check, log

__all__ = ["run"]

RESULT_WAIT_S = 60.0     # how long past the window an answer may come


def open_loop(fd, ctx, offsets, requests):
    n = len(requests)
    done = [None] * n
    answers = [None] * n
    todo = queue.Queue()
    deadline = [None]

    def collect():
        for _ in range(n):
            i, ticket = todo.get()
            wait = max(1.0, deadline[0] - time.perf_counter())
            try:
                answers[i] = ticket.result(timeout=wait)
                done[i] = time.perf_counter()
            except Exception as exc:       # reported as a failed request
                log(f"request {i} failed: {type(exc).__name__}: {exc}")

    collector = threading.Thread(target=collect, name="bench-collect",
                                 daemon=True)
    t0 = time.perf_counter()
    deadline[0] = t0 + ctx.seconds + RESULT_WAIT_S
    collector.start()
    late = 0.0
    for i, ids in enumerate(requests):
        due = t0 + offsets[i]
        with ctx.annotate("bench.wait"):
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        late = max(late, time.perf_counter() - due)
        with ctx.annotate("bench.submit"):
            ticket = fd.submit(ids)
        todo.put((i, ticket))
    with ctx.annotate("bench.drain"):
        collector.join(ctx.seconds + 2 * RESULT_WAIT_S)
    due = t0 + np.asarray(offsets)
    ok = [i for i in range(n) if done[i] is not None]
    lat_ms = np.array([(done[i] - due[i]) * 1e3 if done[i] is not None
                       else np.inf for i in range(n)])
    t_end = max([done[i] for i in ok], default=time.perf_counter())
    return t0, t_end, lat_ms, ok, answers, late


def closed_loop(fd, ctx, block_ids, clients: int):
    lock = threading.Lock()
    state = {"next": 0, "failed": 0}
    records = []                       # (request index, answer, t_done)
    t0 = time.perf_counter()
    stop_at = t0 + ctx.seconds

    def client():
        while time.perf_counter() < stop_at:
            with lock:
                j = state["next"]
                state["next"] += 1
            try:
                with ctx.annotate("bench.submit"):
                    ticket = fd.submit(block_ids(j))
                with ctx.annotate("bench.result"):
                    answer = ticket.result(timeout=ctx.seconds + RESULT_WAIT_S)
            except Exception as exc:       # reported as a failed request
                log(f"request {j} failed: {type(exc).__name__}: {exc}")
                with lock:
                    state["failed"] += 1
                return
            with lock:
                records.append((j, answer, time.perf_counter()))

    threads = [threading.Thread(target=client, name=f"bench-client{c}",
                                daemon=True) for c in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(ctx.seconds + 2 * RESULT_WAIT_S)
    t_end = max([r[2] for r in records], default=time.perf_counter())
    return t0, t_end, sorted(records, key=lambda r: r[0]), state["failed"]


def build(ctx):
    """Set-up: inputs from the seed, the session on the device, the front
    door started with this mix's buckets warm."""
    from repro.frontdoor import Frontdoor, FrontdoorConfig
    from repro.models import lightgcn as L
    from repro.obs.trace import Tracer
    from repro.serve import RecsysSession

    cfg, tr = ctx.config, ctx.traffic
    t = time.perf_counter()
    inputs = model.make_inputs(cfg)
    graph, sketch = model.program_graph(cfg, inputs)
    ctx.run.shapes = inputs.shapes(cfg)
    log(f"graph {graph.n_users} users x {graph.n_items} items, "
        f"{graph.n_edges} interactions; sketch {sketch.k_users}+"
        f"{sketch.k_items} rows (host {time.perf_counter() - t:.2f} s)")
    mcfg = L.from_sketch(graph, sketch, dim=int(cfg["dim"]),
                         n_layers=int(cfg["n_layers"]), l2=float(cfg["l2"]))
    statics = L.make_statics(graph, sketch)
    del graph
    params = model.weights(cfg, inputs, ctx.seed)
    session = RecsysSession(params, statics, mcfg, k=int(cfg["k"]),
                            scorer=cfg["scorer"])
    del params, statics
    tracer = Tracer(enabled=ctx.trace)
    fd = Frontdoor(FrontdoorConfig(
        queue_size=int(tr.get("queue_size", 512)), policy="block",
        cache_entries=int(tr.get("hot_user_cache", 0)), k=int(cfg["k"]),
        buckets=tuple(cfg["buckets"])), tracer=tracer)
    fd.attach_session("default", session, artifact_id="bench",
                      n_users=int(cfg["n_users"]))
    fd.start()
    t = time.perf_counter()
    for b in tr["warm_buckets"]:
        session.warmup(int(b))
    fd.submit(np.zeros(1, np.int32)).result(timeout=600)
    log(f"session on device, buckets {tr['warm_buckets']} warm "
        f"({time.perf_counter() - t:.2f} s)")
    return inputs, fd, tracer


def open_requests(tr: dict, seconds: float, n_users: int, seed: int,
                  rate: float = None):
    """(offsets, [user ids per request]): the fixed replay of arrivals and
    sizes from the mix's traffic seed, users from ``seed``."""
    traffic_rng = np.random.default_rng(int(tr["traffic_seed"]))
    bursts = {k: float(tr[k]) for k in ("burst_factor", "burst_frac",
                                        "burst_period_s") if k in tr}
    offsets = gen.arrival_times(float(rate or tr["rate_per_s"]), seconds,
                                traffic_rng, **bursts)
    sizes = traffic_rng.choice(np.asarray(tr["sizes"], np.int64),
                               size=offsets.size)
    users_rng = gen.rng_for(seed, gen.USERS)
    return offsets, [gen.zipf_ids(users_rng, int(s), n_users,
                                  float(tr["zipf_a"])) for s in sizes]


def run(ctx) -> None:
    cfg, tr, run = ctx.config, ctx.traffic, ctx.run
    inputs, fd, tracer = build(ctx)
    nu = int(cfg["n_users"])
    if tr["loop"] == "open":
        offsets, requests = open_requests(tr, ctx.seconds, nu, ctx.seed)
    else:
        perm = gen.rng_for(ctx.seed, gen.USERS).permutation(nu).astype(
            np.int32)
        width = int(tr["request_users"])

        def block_ids(j):
            lo = (j * width) % nu
            return np.take(perm, np.arange(lo, lo + width), mode="wrap")

    tracer.clear()
    before = fd.telemetry.counters.as_dict()
    run.end_to_end["setup_s"] = time.perf_counter() - ctx.t_start
    log(f"set-up done at {run.end_to_end['setup_s']:.2f} s; window "
        f"{ctx.seconds:g} s")
    with ctx.window():
        if tr["loop"] == "open":
            t0, t_end, lat_ms, ok, answers, late = open_loop(
                fd, ctx, offsets, requests)
        else:
            t0, t_end, records, failed = closed_loop(
                fd, ctx, block_ids, int(tr["clients"]))
    after = fd.telemetry.counters.as_dict()
    run.counters = {k: after[k] - before.get(k, 0) for k in after}
    run.spans = [(s.name, s.t_start, s.t_end) for s in tracer.spans()]
    run.window_s = t_end - t0
    run.memory_peak_bytes = model.memory_peak_bytes()

    if not (ok if tr["loop"] == "open" else records):
        raise RuntimeError("no request was answered in the window")
    if tr["loop"] == "open":
        run.attempted = len(requests)
        run.failed = len(requests) - len(ok)
        run.end_to_end["serve_p95_ms"] = float(np.percentile(lat_ms, 95))
        users = np.concatenate([requests[i] for i in ok])
        vals = np.concatenate([answers[i][0] for i in ok])
        ids = np.concatenate([answers[i][1] for i in ok])
        run.work = {"requests": len(ok), "users": int(users.size)}
        log(f"window closed: {len(ok)}/{len(requests)} answered, p95 "
            f"{run.end_to_end['serve_p95_ms']:.1f} ms, p50 "
            f"{np.percentile(lat_ms, 50):.1f} ms, max {np.max(lat_ms):.1f} "
            f"ms; {run.counters.get('batches')} dispatches; generator late "
            f"up to {late * 1e3:.2f} ms")
    else:
        run.attempted = len(records) + failed
        run.failed = failed
        n_users = sum(len(r[1][0]) for r in records)
        run.end_to_end["serve_users_per_s"] = n_users / run.window_s
        run.work = {"requests": len(records), "users": n_users}
        pick = np.sort(gen.rng_for(ctx.seed, gen.SAMPLE).choice(
            len(records), min(len(records), int(tr["check_requests"])),
            replace=False))
        users = np.concatenate([block_ids(records[i][0]) for i in pick])
        vals = np.concatenate([records[i][1][0] for i in pick])
        ids = np.concatenate([records[i][1][1] for i in pick])
        log(f"window closed: {len(records)} requests, {n_users} users in "
            f"{run.window_s:.3f} s = "
            f"{run.end_to_end['serve_users_per_s']:.2f} users/s; "
            f"{run.counters.get('batches')} dispatches")

    fd.stop()
    del fd
    model.free_device()
    t = time.perf_counter()
    ref = model.reference(cfg, inputs)
    U, V = ref.tables(model.weights(cfg, inputs, ctx.seed))
    gaps = check.topk_gaps(ref, U, V, users, vals, ids)
    run.compared = {"inputs": inputs, "users": users, "ref": ref,
                    "tables": (U, V)}
    limits = cfg["limits"]["serve"]
    run.checks = [Check(name, gaps[name], float(limits[name]))
                  for name in ("mean_value_gap", "mean_topk_gap",
                               "bad_rows")]
    log(f"reference: {users.size} rows compared "
        f"({time.perf_counter() - t:.2f} s)")
