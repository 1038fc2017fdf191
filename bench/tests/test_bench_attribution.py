"""Attribution of device time to the model's named scopes and of idle
time to the program's spans (``bench/attribution.py``), on CPU profiles
recorded here and on the small TPU trace in ``data/``; and a pin of what
``reduce_trace`` reads from that trace, which the attribution leaves as
it was."""
import glob
import time
from pathlib import Path

import numpy as np
import pytest

from bench import attribution as A
from bench import trace as T
from bench.tests import tiny
from repro.obs.scopes import OTHER, SCOPES

SMALL = str(Path(__file__).parent / "data" / "small_trace.xplane.pb")


def _capture(tmp_path, body):
    """Run ``body()`` under the profiler; the one .xplane.pb written."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    return path


def test_reduce_trace_fields_unchanged_on_the_small_trace():
    s = T.reduce_trace(SMALL)
    assert (s.busy_s, s.window_s, s.n_devices, len(s.ops)) == (
        pytest.approx(0.000365937, abs=1e-12),
        pytest.approx(0.067062767, abs=1e-12), 1, 20)
    assert [n for n, _ in s.device_ops] == [
        "fusion", "copy.2", "broadcast_multiply_fusion", "copy-done",
        "broadcast_add_fusion", "reduce-window.1", "slice_bitcast_fusion",
        "slice.3", "copy-start"]
    assert [v for _, v in s.device_ops] == pytest.approx(
        [2.05096e-4, 6.1761e-5, 4.6512e-5, 4.5289e-5, 6.59e-6, 5.72e-7,
         4.6e-8, 4.4e-8, 2.7e-8], abs=1e-12)
    assert s.idle_gaps == [["python3:bench.host_sleep",
                            pytest.approx(0.06669683, abs=1e-12)]]


def test_tpu_ops_read_their_op_name_path():
    tf_ops, _ = A._metadata(SMALL)
    paths = tf_ops["/device:TPU:0"]
    multiply = next(p for name, p in paths.items()
                    if name.startswith("%broadcast_multiply_fusion"))
    assert multiply.startswith("jit(<lambda>)/mul")
    prof = A.read_profile(SMALL)
    # the same window and busy time as reduce_trace; no scope there
    covered, _ = T.union_ns(prof.busy[0], prof.lo, prof.hi)
    assert covered == 365_937 and prof.hi - prof.lo == 67_062_767
    assert set(A.scope_ns(prof)) == {OTHER}
    assert prof.anchor_ns is None
    with pytest.raises(ValueError, match="obs.anchor"):
        prof.ns(1.0, 0.0)


def test_anchor_puts_retroactive_spans_on_the_profile(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from repro.obs.trace import Tracer
    tracer = Tracer(enabled=True)
    x = jax.numpy.ones(64)
    add = jax.jit(lambda v: v + 1)
    jax.block_until_ready(add(x))

    def body():
        with jax.profiler.TraceAnnotation("t.window"):
            anchor_t = tracer.anchor()
            jax.block_until_ready(add(x))
            time.sleep(0.01)
            with tracer.span("live") as live:       # an annotated span
                time.sleep(0.03)
            # the batcher's kind: committed afterwards, never annotated
            tracer.record_span("retro", live.t_start, live.t_end)
        return anchor_t

    anchor_t = []
    path = _capture(tmp_path, lambda: anchor_t.append(body()))
    (live_ns,) = [(e.start_ns, e.start_ns + e.duration_ns)
                  for p in ProfileData.from_file(path).planes
                  for line in p.lines for e in line.events
                  if e.name == "live"]
    prof = A.read_profile(path, window="t.window")
    assert prof.lo <= prof.anchor_ns <= live_ns[0]
    retro = next(s for s in tracer.spans() if s.name == "retro")
    got = (prof.ns(retro.t_start, anchor_t[0]),
           prof.ns(retro.t_end, anchor_t[0]))
    assert got == pytest.approx(live_ns, abs=1e6)         # within 1 ms


def _tiny_programs():
    """A tiny LightGCN serving session and fused trainer, both warm."""
    from bench import model
    from repro.models import lightgcn as L
    from repro.serve import RecsysSession
    from repro.training import TrainConfig, Trainer

    _, _, cfg, _ = tiny.cell("lgcn-amazonbook.serve-online")
    inputs = model.make_inputs(cfg)
    graph, sketch = model.program_graph(cfg, inputs)
    mcfg = L.from_sketch(graph, sketch, dim=int(cfg["dim"]),
                         n_layers=int(cfg["n_layers"]), l2=float(cfg["l2"]))
    session = RecsysSession(model.weights(cfg, inputs, 7),
                            L.make_statics(graph, sketch), mcfg,
                            k=int(cfg["k"]), scorer=cfg["scorer"])
    session.warmup(8)
    trainer = Trainer(graph, sketch, TrainConfig(
        dim=int(cfg["dim"]), n_layers=int(cfg["n_layers"]), batch_size=64,
        backend="fused", chunk_size=4))
    trainer.run(steps=4, log_every=0)
    return session, trainer


def test_scopes_attribute_serve_and_train_device_time(tmp_path):
    import jax
    session, trainer = _tiny_programs()

    def body():
        with jax.profiler.TraceAnnotation("t.serve"):
            jax.block_until_ready(session(np.arange(8, dtype=np.int32)))
        with jax.profiler.TraceAnnotation("t.train"):
            trainer.run(steps=8, log_every=0)

    path = _capture(tmp_path, body)
    expected = {"t.serve": {"lookup", "propagate", "score", "topk"},
                "t.train": {"lookup", "propagate", "loss", "optimizer",
                            "sample"}}
    for window, scopes in expected.items():
        prof = A.read_profile(path, window=window)
        assert prof.ops[0], window
        assert all(s in SCOPES + (OTHER,) for _, _, s in prof.ops[0])
        ns = A.scope_ns(prof)
        assert scopes <= set(ns), (window, ns)
        assert ns["propagate"] > 0
        named = sum(v for k, v in ns.items() if k != OTHER)
        assert named / sum(ns.values()) > 0.5, (window, ns)


def _synthetic(busy, anchor_ns=1000.0):
    return A.Profile(lo=0.0, hi=1000.0, ops=[[]], busy=[busy],
                     anchor_ns=anchor_ns)


def test_idle_split_on_synthetic_intervals():
    # the anchor reading t = 1.0 s lies at 1000 ns: t -> 1000 + (t-1)*1e9
    ns = lambda x: 1.0 + (x - 1000.0) / 1e9        # noqa: E731
    prof = _synthetic([(100, 200), (300, 350), (600, 700)])
    spans = [
        # one batch of two requests: the same dispatch span twice
        ("dispatch", ns(50), ns(400)), ("dispatch", ns(50), ns(400)),
        ("queue", ns(0), ns(50)), ("queue", ns(20), ns(50)),
        # a request admitted during the dispatch, waiting past its end
        ("queue", ns(380), ns(550)),
        ("dispatch", ns(550), ns(720)),
        ("batch", ns(0), ns(1000)),                  # not read
    ]
    got = A.idle_split(prof, 1.0, spans)
    # idle: [0,100) [200,300) [350,600) [700,1000) = 750 ns
    assert got["idle"] == pytest.approx(750)
    # inside a dispatch: [50,100) [200,300) [350,400) [550,600) [700,720)
    assert got["dispatch"] == pytest.approx(50 + 100 + 50 + 50 + 20)
    # queued, no dispatch: [0,50) and [400,550)
    assert got["queued"] == pytest.approx(50 + 150)
    assert got["rest"] == pytest.approx(750 - 270 - 200)
    assert got["dispatches"] == 2


def test_intersect_merges_overlaps():
    assert A.intersect([(0, 10), (5, 20), (30, 40)],
                       [(8, 32), (35, 36), (36, 50)]) == [
        (8, 20), (30, 32), (35, 40)]
    assert A.intersect([], [(0, 1)]) == []
