"""repro.obs: spans/tracing, bounded-memory metrics, JSONL export +
report, and the repo-wide discipline tests (ISSUE 10 satellites):

  * LatencyRecorder stays exact up to its cap (pinned summaries) and
    bounded at 1M records (the byte-budget regression test);
  * FrontdoorTelemetry.record_batch fill ratio / shed counts pinned
    against deterministic synthetic load;
  * a grep rule forbidding raw ``time.perf_counter()`` latency
    bookkeeping anywhere in src/repro outside repro/obs (benchmarks/
    are exempt: they time their own harness sections);
  * the end-to-end acceptance trace: one frontdoor request produces
    >=5 nested spans under a single trace ID, exported to JSONL and
    rendered by obs_report.
"""
import os
import threading

import numpy as np
import pytest

from repro.launch.obs_report import main as obs_report_main
from repro.obs import clock
from repro.obs.export import export_jsonl
from repro.obs.metrics import (Counter, CounterSet, Gauge, Histogram,
                               LatencyRecorder, MetricsRegistry)
from repro.obs.report import (TraceFileError, read_trace, render_trace,
                              rollup, trace_ids, trace_tree)
from repro.obs.trace import (NULL_SPAN, Tracer, configure, get_tracer,
                             set_tracer)

SRC_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src", "repro")


def _tracer(**kw):
    kw.setdefault("enabled", True)
    kw.setdefault("device_annotations", False)
    return Tracer(**kw)


@pytest.fixture
def global_tracer():
    """Install a fresh enabled tracer as the process-global one and
    restore the previous object afterwards (configure() mutates in
    place, so isolation needs a swap, not a reconfigure)."""
    prev = get_tracer()
    t = set_tracer(_tracer())
    yield t
    set_tracer(prev)


# ---------------------------------------------------------------------------
# tracer: nesting, parentage, cross-thread spans, sampling, caps
# ---------------------------------------------------------------------------
def test_span_nesting_and_parentage():
    tr = _tracer()
    with tr.trace("root", tenant="web") as root:
        with tr.span("child") as child:        # ambient parent = root
            with tr.span("grandchild") as g:
                assert g.trace_id == root.trace_id
                assert g.parent_id == child.span_id
            assert child.parent_id == root.span_id
        with tr.span("sibling", parent=root) as sib:
            assert sib.parent_id == root.span_id
    spans = tr.spans()
    assert [s.name for s in spans] == ["grandchild", "child", "sibling",
                                       "root"]          # commit = close order
    assert len({s.trace_id for s in spans}) == 1
    assert root.attrs["tenant"] == "web"
    assert all(s.t_end >= s.t_start for s in spans)


def test_span_without_ambient_becomes_root():
    tr = _tracer()
    with tr.span("lonely"):
        pass
    (sp,) = tr.spans()
    assert sp.parent_id == "" and sp.trace_id != ""


def test_disabled_tracer_is_null_span_identity():
    tr = _tracer(enabled=False)
    assert tr.trace("a") is NULL_SPAN
    assert tr.span("b") is NULL_SPAN
    assert tr.record_span("c", 0.0, 1.0) is NULL_SPAN
    assert not NULL_SPAN                     # falsy: `if span:` gates work
    with NULL_SPAN as sp:                    # all methods are no-ops
        sp.set(x=1).end(y=2)
    assert tr.spans() == []


def test_sampling_is_deterministic_and_trace_complete_or_absent():
    tr = _tracer(sample_rate=0.25)
    kept = 0
    for _ in range(100):
        root = tr.trace("req")
        with tr.span("child", parent=root):
            pass
        root.end()
        kept += root is not NULL_SPAN
    assert kept == 25                        # error diffusion: exactly rate
    spans = tr.spans()
    assert len(spans) == 50                  # child + root per kept trace
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s.trace_id, []).append(s.name)
    assert all(sorted(v) == ["child", "req"] for v in by_trace.values())


def test_record_span_crosses_threads():
    tr = _tracer()
    root = tr.trace("request")               # opened on this thread
    marks = {}

    def worker():
        t0 = clock.now()
        t1 = clock.now()
        marks["span"] = tr.record_span("device", t0, t1, parent=root,
                                       block=3)

    th = threading.Thread(target=worker, name="batcher-0")
    th.start()
    th.join()
    root.end(outcome="ok")
    sp = marks["span"]
    assert sp.trace_id == root.trace_id
    assert sp.parent_id == root.span_id
    assert sp.thread == "batcher-0"
    assert sp.attrs == {"block": 3}
    assert root.attrs["outcome"] == "ok"


def test_end_is_idempotent():
    tr = _tracer()
    sp = tr.trace("once")
    sp.end()
    t_end = sp.t_end
    sp.end()                                 # second close: no-op
    assert sp.t_end == t_end
    assert len(tr.spans()) == 1


def test_max_spans_cap_counts_drops():
    tr = _tracer(max_spans=5)
    for i in range(9):
        tr.trace(f"s{i}").end()
    assert len(tr.spans()) == 5
    assert tr.dropped == 4


# ---------------------------------------------------------------------------
# metrics: histogram accuracy, bounded recorder, registry
# ---------------------------------------------------------------------------
def test_histogram_percentiles_within_10pct():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=1.0, sigma=1.2, size=100_000)
    h = Histogram()
    h.record_many(vals)
    assert h.count == vals.size
    assert h.mean == pytest.approx(float(vals.mean()))
    for q in (50, 90, 99):
        exact = float(np.percentile(vals, q))
        assert h.percentile(q) == pytest.approx(exact, rel=0.10)
    # estimates clamp into the observed range
    assert h.min <= h.percentile(0) and h.percentile(100) <= h.max


def test_histogram_one_sample_reports_that_sample():
    h = Histogram()
    h.record(3.7)
    assert h.percentile(50) == pytest.approx(3.7)
    assert h.percentile(99) == pytest.approx(3.7)


def test_latency_recorder_exact_up_to_cap():
    rng = np.random.default_rng(1)
    vals = rng.exponential(5.0, size=50)
    rec = LatencyRecorder(cap=64)
    for v in vals:
        rec.record(v)
    for q in (50, 90, 99):                  # ring holds everything: exact
        assert rec.percentile(q) == float(np.percentile(vals, q))
    s = rec.summary()
    assert s == {"requests": 50,
                 "p50_ms": round(float(np.percentile(vals, 50)), 3),
                 "p99_ms": round(float(np.percentile(vals, 99)), 3)}


def test_latency_recorder_1m_records_bounded_memory():
    """The regression the obs layer exists for: a serving process that
    records a latency per request must stay O(1) in request count. 1M
    records must fit a fixed byte budget AND still answer percentiles."""
    rng = np.random.default_rng(2)
    vals = rng.gamma(2.0, 8.0, size=1_000_000)
    rec = LatencyRecorder()
    rec.record_many(vals)
    assert rec.count == 1_000_000
    assert rec.nbytes() < 256 * 1024, \
        f"1M records cost {rec.nbytes()} bytes; budget is 256 KiB " \
        f"(the pre-obs list-of-floats was ~32 MB here)"
    for q in (50, 99):
        assert rec.percentile(q) == pytest.approx(
            float(np.percentile(vals, q)), rel=0.10)
    assert len(rec.values()) == rec.cap     # ring kept only the newest cap


def test_registry_get_or_create_and_kind_conflict():
    reg = MetricsRegistry()
    c = reg.counter("events")
    assert reg.counter("events") is c
    c.inc(3)
    g = reg.gauge("depth")
    g.set(7)
    reg.latency("lat_ms").record(2.0)
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("events")                 # same name, different kind
    snap = reg.snapshot()
    assert snap["events"] == 3
    assert snap["depth"]["value"] == 7 and snap["depth"]["writes"] == 1
    assert snap["lat_ms"]["count"] == 1
    assert reg.nbytes() > 0


def test_counter_set_reads_like_a_dict():
    cs = CounterSet(("a", "b"))
    cs.bump("a")
    cs.bump("c", 2)
    assert cs["a"] == 1 and cs.get("b") == 0 and cs["c"] == 2
    assert list(cs) == ["a", "b", "c"]       # insertion-ordered
    assert dict(cs.items()) == {"a": 1, "b": 0, "c": 2}
    assert len(cs) == 3


# ---------------------------------------------------------------------------
# frontdoor telemetry: pinned fill ratios and shed counts (satellite)
# ---------------------------------------------------------------------------
def test_record_batch_pins_fill_and_coalesced():
    from repro.serve.telemetry import FrontdoorTelemetry
    tel = FrontdoorTelemetry()
    tel.record_batch(2, 7, 8, [8])          # 2 requests, 7 ids padded to 8
    s = tel.summary()
    assert s["batches"] == 1
    assert s["coalesced"] == 2
    assert s["batch_fill_mean"] == 0.875
    assert s["bucket_counts"] == {8: 1}
    tel.record_batch(1, 3, 8, [8])          # solo request: not coalesced
    s = tel.summary()
    assert s["batches"] == 2
    assert s["coalesced"] == 2               # unchanged
    assert s["batch_fill_mean"] == round((0.875 + 0.375) / 2, 4)
    assert s["bucket_counts"] == {8: 2}
    tel.record_batch(3, 65, 72, [64, 8])    # oversize: two ladder rungs
    assert tel.summary()["bucket_counts"] == {8: 3, 64: 1}


def test_shed_counts_pinned_under_deterministic_overflow(monkeypatch):
    """Fill the admission queue with the batcher parked, then submit
    extras: shed policy must reject each one, and the counters must be
    exact — no sleeps, no races."""
    from repro.frontdoor import Frontdoor, FrontdoorConfig, RequestShed
    from tests.test_frontdoor import FakeArtifact, _registry

    fd = Frontdoor(FrontdoorConfig(queue_size=4, policy="shed",
                                   buckets=(1, 8, 64)),
                   registry=_registry())
    fd.attach("web", FakeArtifact(0))
    # park the pipeline: admission is open but nothing drains the queue
    monkeypatch.setattr(type(fd), "running",
                        property(lambda self: True))
    for i in range(4):
        fd.submit([i], tenant="web")        # fills the queue exactly
    for i in range(3):
        with pytest.raises(RequestShed):
            fd.submit([i], tenant="web")
    s = fd.telemetry.summary()
    assert s["requests"] == 7
    assert s["shed"] == 3
    assert s["responses"] == 0
    assert fd.queue_depth() == 4


# ---------------------------------------------------------------------------
# export + report round trip
# ---------------------------------------------------------------------------
def _sample_trace(tr):
    with tr.trace("request", tenant="web") as root:
        with tr.span("admit"):
            pass
        with tr.span("batch", parent=root) as b:
            with tr.span("dispatch") as d:
                tr.record_span("device", d.t_start, clock.now(), parent=d)
            b.set(n_requests=2)
    return root


def test_export_roundtrip_schema_and_tree(tmp_path):
    tr = _tracer()
    _sample_trace(tr)
    path = str(tmp_path / "t.jsonl")
    n = export_jsonl(tr, path, metrics_snapshot={"requests": 1})
    assert n == 5
    data = read_trace(path)
    assert data["header"]["schema"] == 1
    assert data["header"]["n_spans"] == 5
    assert data["header"]["dropped"] == 0
    assert data["metrics"] == {"requests": 1}
    (tid,) = trace_ids(data["spans"])
    roots = trace_tree(data["spans"], tid)
    assert len(roots) == 1 and roots[0]["name"] == "request"
    assert roots[0]["attrs"] == {"tenant": "web"}
    names = {c["name"] for c in roots[0]["children"]}
    assert names == {"admit", "batch"}
    # depth 4: request -> batch -> dispatch -> device
    batch = next(c for c in roots[0]["children"] if c["name"] == "batch")
    assert batch["children"][0]["children"][0]["name"] == "device"
    text = render_trace(data["spans"], tid)
    assert "└─ request" in text and "device" in text
    agg = rollup(data["spans"])
    assert agg["request"]["count"] == 1
    assert agg["device"]["count"] == 1


def test_anchor_reading_kept_and_exported(tmp_path):
    tr = _tracer()
    assert tr.anchor_t is None
    before = clock.now()
    t = tr.anchor()
    assert before <= t <= clock.now() and tr.anchor_t == t
    tr.trace("a").end()
    path = str(tmp_path / "t.jsonl")
    export_jsonl(tr, path)
    header = read_trace(path)["header"]
    assert header["anchor_ms"] == pytest.approx((t - tr.perf_t0) * 1e3,
                                                abs=1e-4)
    export_jsonl(_tracer(), path)
    assert read_trace(path)["header"]["anchor_ms"] is None


@pytest.mark.parametrize("path, scope", [
    ("jit(score_topk)/score/propagate/mul", "propagate"),
    ("jit(score_topk)/score/dot_general", "score"),
    ("jit(score_topk)/topk/top_k", "topk"),
    ("jit(chunk)/while/body/transpose(jvp(propagate))/add", "propagate"),
    ("jit(chunk)/while/body/transpose(jvp(loss))/lookup/x", "lookup"),
    ("jit(chunk)/while/body/optimizer/sqrt", "optimizer"),
    ("jit(chunk)/while/body/closed_call", "other"),
    ("jit(score_topk)/dot_general", "other"),
    ("", "other"),
])
def test_scope_of_takes_the_innermost_name(path, scope):
    from repro.obs.scopes import scope_of
    assert scope_of(path) == scope


def test_watch_compiles_counts_new_shapes_not_warm_calls():
    import jax

    from repro.obs.compiles import COMPILES, watch_compiles

    sink = CounterSet()
    assert watch_compiles(sink) is watch_compiles() is COMPILES
    f = jax.jit(lambda x: x * 3 + 1)
    jax.block_until_ready(f(np.ones(7, np.float32)))
    before = COMPILES.as_dict()
    jax.block_until_ready(f(np.ones(7, np.float32)))   # warm: none built
    assert COMPILES.as_dict() == before
    jax.block_until_ready(f(np.ones(11, np.float32)))  # a new shape
    after = COMPILES.as_dict()
    assert after["compiles"] - before["compiles"] == 1
    assert after["traces"] > before["traces"]
    assert after["compile_us"] > before["compile_us"]
    assert sink["compiles"] >= 1 and sink["traces"] >= 1


def test_frontdoor_telemetry_counts_compiles():
    import jax

    from repro.serve.telemetry import FrontdoorTelemetry

    tel = FrontdoorTelemetry()
    assert tel.counters["compiles"] == 0 == tel.counters["traces"]
    jax.block_until_ready(jax.jit(lambda x: x - 5)(np.ones(13, np.float32)))
    assert tel.summary()["compiles"] == 1


def test_export_drain_clears_buffer(tmp_path):
    tr = _tracer()
    tr.trace("a").end()
    path = str(tmp_path / "t.jsonl")
    assert export_jsonl(tr, path, drain=True) == 1
    assert tr.spans() == []
    assert export_jsonl(tr, str(tmp_path / "t2.jsonl"), drain=True) == 0


def test_read_trace_rejects_malformed(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"kind": "span", "trace": "t1"}\n')   # no header, no name
    with pytest.raises(TraceFileError):
        read_trace(str(p))
    p.write_text('{"kind": "header", "schema": 99}\n')
    with pytest.raises(TraceFileError, match="schema"):
        read_trace(str(p))
    p.write_text("not json\n")
    with pytest.raises(TraceFileError, match="not JSON"):
        read_trace(str(p))


def test_obs_report_cli(tmp_path, capsys):
    tr = _tracer()
    _sample_trace(tr)
    path = str(tmp_path / "t.jsonl")
    export_jsonl(tr, path, metrics_snapshot={"requests": 1})
    assert obs_report_main([path]) == 0
    out = capsys.readouterr().out
    assert "5 spans, 1 traces, schema 1" in out
    assert "└─ request" in out
    assert "metrics snapshot" in out
    assert obs_report_main([path, "--rollup", "--no-metrics"]) == 0
    # missing / empty files are CI failures, not silent skips
    assert obs_report_main([str(tmp_path / "missing.jsonl")]) == 1
    empty = tmp_path / "empty.jsonl"
    export_jsonl(_tracer(), str(empty))
    assert obs_report_main([str(empty)]) == 1
    capsys.readouterr()


# ---------------------------------------------------------------------------
# end-to-end: the frontdoor request trace (acceptance criterion)
# ---------------------------------------------------------------------------
def test_frontdoor_request_trace_end_to_end(tmp_path):
    from repro.frontdoor import Frontdoor, FrontdoorConfig
    from tests.test_frontdoor import FakeArtifact, _check_echo, _registry

    tr = _tracer()
    fd = Frontdoor(FrontdoorConfig(queue_size=64, flush_ms=1.0,
                                   buckets=(1, 8, 64)),
                   registry=_registry(), tracer=tr)
    fd.attach("web", FakeArtifact(0))
    with fd:
        for i in range(4):
            ids = np.arange(i + 1, dtype=np.int32)
            vals, items = fd(ids, tenant="web")
            _check_echo(ids, vals, items)
    path = str(tmp_path / "fd.jsonl")
    n = export_jsonl(tr, path,
                     metrics_snapshot=fd.telemetry.registry.snapshot())
    assert n >= 4 * 5
    data = read_trace(path)

    def depth(sp, d=1):
        return max([d] + [depth(c, d + 1) for c in sp["children"]])

    ok = 0
    for tid in trace_ids(data["spans"]):
        spans = [s for s in data["spans"] if s["trace"] == tid]
        roots = trace_tree(data["spans"], tid)
        if len(roots) != 1 or roots[0]["name"] != "request":
            continue
        assert len(spans) >= 5, \
            f"trace {tid}: only {[s['name'] for s in spans]}"
        assert depth(roots[0]) >= 3      # request->batch->dispatch
        assert roots[0]["attrs"].get("outcome") == "ok"
        names = [s["name"] for s in spans]
        for expected in ("admit", "queue", "batch", "dispatch"):
            assert expected in names
        ok += 1
    assert ok == 4                        # every request traced end to end
    assert data["metrics"]["frontdoor"]["responses"] == 4


def test_cluster_solve_emits_sweep_and_block_spans(global_tracer):
    from repro.core import ClusterEngine, make_weights
    from repro.data import planted_coclusters

    g, _, _ = planted_coclusters(60, 50, k_true=4, avg_deg=6, seed=0)
    wu, wv = make_weights(g, "hws")
    eng = ClusterEngine(solver="jax_streamed", block_edges=200)
    eng.solve(g, wu, wv, 0.7, max_iters=2)
    names = [s.name for s in global_tracer.spans()]
    assert "cluster_solve" in names
    assert "lp_sweep" in names
    assert "edge_block" in names
    solve = next(s for s in global_tracer.spans()
                 if s.name == "cluster_solve")
    assert solve.attrs["solver"] == "jax_streamed"
    assert "iters" in solve.attrs
    # sweeps nest under the solve, blocks under a sweep — one trace
    assert len({s.trace_id for s in global_tracer.spans()}) == 1


def test_fit_gamma_nests_grid_solves(global_tracer):
    from repro.core import ClusterEngine, make_weights
    from repro.data import planted_coclusters

    g, _, _ = planted_coclusters(60, 50, k_true=4, avg_deg=6, seed=0)
    wu, wv = make_weights(g, "hws")
    eng = ClusterEngine()
    gamma, _, _ = eng.fit_gamma(g, wu, wv, budget=30, grid=4, max_iters=2)
    spans = global_tracer.spans()
    fit = [s for s in spans if s.name == "fit_gamma"]
    assert len(fit) == 1
    assert fit[0].attrs["gamma"] == gamma
    solves = [s for s in spans if s.name == "cluster_solve"]
    assert len(solves) >= 4               # grid walk + any x2 probes
    assert all(s.parent_id == fit[0].span_id and
               s.trace_id == fit[0].trace_id for s in solves)


def test_configure_mutates_global_in_place():
    prev = get_tracer()
    try:
        bound = get_tracer()                 # an import-time-bound ref
        configure(enabled=True, sample_rate=0.5, max_spans=10)
        assert bound.enabled and bound.sample_rate == 0.5
        assert bound.max_spans == 10
        configure(enabled=False)
        assert bound is get_tracer() and not bound.enabled
    finally:
        configure(enabled=False, sample_rate=1.0, max_spans=100_000)
        set_tracer(prev)


# ---------------------------------------------------------------------------
# the discipline rule: one clock, owned by repro.obs (satellite)
# ---------------------------------------------------------------------------
def test_no_raw_perf_counter_outside_obs():
    """All latency bookkeeping goes through repro.obs.clock — a single
    monotonic clock source keeps every span/metric timestamp in the
    repo comparable. benchmarks/ are exempt (they time their own
    harness); src/repro is not."""
    offenders = []
    for dirpath, _, files in os.walk(SRC_ROOT):
        rel = os.path.relpath(dirpath, SRC_ROOT)
        if rel == "obs" or rel.startswith("obs" + os.sep):
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as fh:
                for lineno, line in enumerate(fh, 1):
                    if "perf_counter" in line:
                        offenders.append(
                            f"{os.path.relpath(path, SRC_ROOT)}:{lineno}: "
                            f"{line.strip()}")
    assert not offenders, \
        "raw time.perf_counter() outside repro/obs — use " \
        "repro.obs.clock.now() so timestamps stay comparable:\n" \
        + "\n".join(offenders)
