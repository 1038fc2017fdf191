"""A cell at a size a CPU test run holds: every driver path, Pallas-free
(the lookup backend is auto-selected, "gather" off the TPU)."""
import time

from bench import harness as H
from bench.run import run_cell

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# float32 on the CPU reads about 1e-8 to 1e-7 on every number; the
# bfloat16 control reads 1e-6 to 1e-3 at this size
LIMITS = {"serve": {"mean_value_gap": 1e-6, "mean_topk_gap": 1e-6,
                    "bad_rows": 0},
          "train": {"loss_gap": 1e-6, "grad_gap": 1e-5, "update_gap": 1e-5}}


def cell(name: str):
    """(spec, workload, tiny config, tiny traffic) for a workload."""
    spec = H.load_spec()
    wl = H.find_workload(spec, name)
    cfg = dict(H.load_config(wl["config"]), n_users=300, n_items=200,
               n_interactions=3000, dim=16, n_layers=2, k=5,
               buckets=[1, 8, 64], k_true=10, limits=LIMITS)
    tr = H.load_traffic(wl["traffic"])
    if tr["driver"] == "train":
        tr = dict(tr, batch_size=64, chunk_size=4)
    elif tr["loop"] == "open":
        tr = dict(tr, rate_per_s=20.0)
    else:
        tr = dict(tr, request_users=64, warm_buckets=[64])
    return spec, wl, cfg, tr


def run(name: str, seed: int = 2**31 + 11, seconds: float = 1.0,
        trace: bool = False):
    spec, wl, cfg, tr = cell(name)
    return run_cell(spec, wl, cfg, tr, seed=seed, seconds=seconds,
                    trace=trace, device=CPU, t_start=time.monotonic())
