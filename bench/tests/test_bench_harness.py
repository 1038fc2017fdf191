"""The harness finds every piece by name, keeps the online replay fixed
across seeds, and prints the contract's result line."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import drive_serve, harness as H, trace as T
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
SPEC = H.load_spec()


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_pieces_found_by_name(wl):
    cfg = H.load_config(wl["config"])
    tr = H.load_traffic(wl["traffic"])
    assert H.driver(tr).run
    assert (H.BENCH / "configs" / f"{cfg['reference']}.py").exists()
    assert any(c["name"] == wl["config"] and
               c["file"] == f"bench/configs/{wl['config']}.json" and
               c["reduced"] == cfg["reduced"]
               for c in SPEC["configs"])
    e2e = {m["name"] for m in H.end_to_end_for(SPEC, wl["name"])}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert H.per_layer_for(SPEC, wl["name"])


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_found_by_name(metric):
    assert callable(H.metric_reader(metric["name"]))
    for wl in metric["workloads"]:
        reports = {m["name"] for m in H.end_to_end_for(SPEC, wl)}
        assert metric["moves"] in reports


def test_online_replay_fixed_across_seeds():
    tr = H.load_traffic("serve-online")
    a_off, a_req = drive_serve.open_requests(tr, 51, 52643, seed=1)
    b_off, b_req = drive_serve.open_requests(tr, 51, 52643, seed=2**31 + 5)
    np.testing.assert_array_equal(a_off, b_off)
    assert [len(r) for r in a_req] == [len(r) for r in b_req]
    assert any(not np.array_equal(x, y) for x, y in zip(a_req, b_req))
    # a shorter window is a prefix of the same schedule
    c_off, _ = drive_serve.open_requests(tr, 10, 52643, seed=3)
    np.testing.assert_array_equal(c_off, a_off[:c_off.size])


def test_result_line_untraced():
    result, run = tiny.run("lgcn-amazonbook.serve-online")
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_p95_ms", "setup_s"}
    assert set(result["checks"]) == {"mean_value_gap", "mean_topk_gap",
                                     "bad_rows"}
    json.dumps(result)


def test_result_line_traced(monkeypatch):
    small = T.reduce_trace(str(Path(__file__).parent / "data"
                                / "small_trace.xplane.pb"))
    monkeypatch.setattr(T, "reduce_trace", lambda path: small)
    result, run = tiny.run("lgcn-amazonbook.serve-online", trace=True)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "checks"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["device"]["busy_s"] == small.busy_s
    names = {m["name"] for m in H.per_layer_for(SPEC, run.workload["name"])}
    assert set(result["metrics"]) <= names
    assert {"queue_wait_p50_ms.serve", "requests_per_dispatch.serve",
            "dispatch_ms.serve", "device_idle.serve"} <= set(
                result["metrics"])


def test_no_tpu_exits_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lgcn-steam.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""


@pytest.mark.parametrize("seed", [0, -7, 2**31 + 5, 2**70])
def test_any_whole_seed(seed):
    from bench import gen
    assert 0 <= gen.seed32(seed) < 2**31
    assert gen.seed32(seed) == gen.seed32(seed)
    gen.rng_for(seed, gen.USERS).integers(10)


def test_bursts_come_from_the_traffic_file():
    tr = dict(H.load_traffic("serve-online"), burst_factor=4.0,
              burst_frac=0.25, burst_period_s=1.0)
    off, _ = drive_serve.open_requests(tr, 200, 1000, seed=1)
    in_burst = np.mod(off, 1.0) < 0.25
    # a quarter of the time carries 4x the rate: 4/7 of the arrivals
    assert 0.5 < in_burst.mean() < 0.65
