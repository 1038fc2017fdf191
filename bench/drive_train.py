"""Training driver: the ``fused`` trainer's BPR chunks.

Set-up builds one ``Trainer`` (the traffic file's ``backend``,
``batch_size``, ``chunk_size``, ``lr``) on the configuration's graph and
sketch, gives it the benchmark's codebook weights, and drives it through
its first chunk (which compiles). The window then calls the same object
for whole chunks until ``--seconds`` have passed, and reports
``train_samples_per_s``: the BPR samples of every step taken in the
window over the window's length.

What the reference follows: every step from the weights the benchmark
made through the window's first chunk, that is the set-up chunk and one
window call at a nonzero start, with the Adam count, the bias correction
and the sampler's step carried over from set-up. Each of those steps'
losses, and the optimizer's first moment and the parameters after the
window's first chunk (copied on the device as it ends), are compared
afterwards with the plain reference run from the same weights over the
same batches (``bench/check.py``). The window's later chunks run the
same compiled call on the carried state; their losses are checked for
being finite. A fixed number of compared steps keeps the reference's
time (and its readings) the same however many chunks fit the window.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, gen, model
from bench.harness import Check, log

__all__ = ["run"]


def _host(tree):
    return jax.tree.map(lambda x: np.array(x, copy=True), tree)


def run(ctx) -> None:
    from repro.training import TrainConfig, Trainer

    cfg, tr, run = ctx.config, ctx.traffic, ctx.run
    batch, chunk = int(tr["batch_size"]), int(tr["chunk_size"])
    t = time.perf_counter()
    inputs = model.make_inputs(cfg)
    graph, sketch = model.program_graph(cfg, inputs)
    run.shapes = dict(inputs.shapes(cfg), batch=batch)
    log(f"graph {graph.n_users} users x {graph.n_items} items, "
        f"{graph.n_edges} interactions; sketch {sketch.k_users}+"
        f"{sketch.k_items} rows (host {time.perf_counter() - t:.2f} s)")
    seed = gen.seed32(ctx.seed)
    trainer = Trainer(graph, sketch, TrainConfig(
        dim=int(cfg["dim"]), n_layers=int(cfg["n_layers"]),
        l2=float(cfg["l2"]), lr=float(tr["lr"]), batch_size=batch,
        seed=seed, backend=tr["backend"], chunk_size=chunk))
    del graph
    trainer.params = model.weights(cfg, inputs, ctx.seed)
    trainer.opt_state = trainer.optimizer.init(trainer.params)
    p0 = _host(trainer.params)
    # the state after the window's first chunk, copied on the device
    # before the next call donates it
    snapshot = jax.jit(lambda p, o: jax.tree.map(jnp.copy, (p, o["m"])))
    t = time.perf_counter()
    with ctx.annotate("bench.chunk"):
        losses = trainer.run(steps=chunk, log_every=0)
    jax.block_until_ready(snapshot(trainer.params, trainer.opt_state))
    # Warm-up of the trainer's step index from a nonzero start, where
    # ``jnp.arange`` runs two small programs that the chunk at step 0
    # does not. Warming it through the trainer would cost a whole chunk
    # of set-up in every run; the index is built as the trainer builds
    # it (``FusedBackend.run``).
    jax.block_until_ready(jnp.arange(chunk, 2 * chunk, dtype=jnp.int32))
    log(f"first chunk of {chunk} steps done, it compiles "
        f"({time.perf_counter() - t:.2f} s); loss {losses[0]:.6f} -> "
        f"{losses[-1]:.6f}")

    run.end_to_end["setup_s"] = time.perf_counter() - ctx.t_start
    log(f"set-up done at {run.end_to_end['setup_s']:.2f} s; window "
        f"{ctx.seconds:g} s")
    window_losses, state = [], None
    with ctx.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds:
            with ctx.annotate("bench.chunk"):
                window_losses += trainer.run(steps=trainer.step + chunk,
                                             log_every=0)
            if state is None:
                state = snapshot(trainer.params, trainer.opt_state)
        t_end = time.perf_counter()
    steps = len(window_losses)
    run.window_s = t_end - t0
    run.work = {"steps": steps}
    run.attempted = steps
    run.failed = int(np.sum(~np.isfinite(window_losses)))
    run.end_to_end["train_samples_per_s"] = steps * batch / run.window_s
    run.memory_peak_bytes = model.memory_peak_bytes()
    log(f"window closed: {steps} steps in {run.window_s:.3f} s = "
        f"{run.end_to_end['train_samples_per_s']:.3f} samples/s; loss "
        f"{window_losses[-1]:.6f}")

    losses += window_losses[:chunk]
    p, m = _host(state)
    del trainer, state
    model.free_device()
    t = time.perf_counter()
    ref = model.reference(cfg, inputs)
    n = len(losses)
    reference = ref.train(p0, seed, n, batch, float(tr["lr"]))
    gaps = check.train_gaps(losses, p0, p, m, *reference)
    run.compared = {"inputs": inputs, "p0": p0, "seed32": seed, "steps": n,
                    "reference": reference}
    limits = cfg["limits"]["train"]
    run.checks = [Check(name, gaps[name], float(limits[name]))
                  for name in ("loss_gap", "grad_gap", "update_gap")]
    log(f"reference: {n} steps ({time.perf_counter() - t:.2f} s)")
