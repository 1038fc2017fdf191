"""The data-driven part of the benchmark: finds a cell's configuration,
traffic mix and per-layer metric readers by name, checks the device,
and assembles the result line.

Layout, all under ``bench/``:

* ``configs/<config>.json``: a configuration's sizes, limits and the name
  of its plain reference (``configs/<reference>.py``);
* ``traffic/<traffic>.json``: a mix's parameters, and the ``driver``
  (``drive_<driver>.py``) that generates and runs it;
* ``metrics/<metric>.py``: one per-layer metric, ``read(run)`` returning
  a number, or None where the run holds nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"

__all__ = ["BENCH", "ROOT", "CACHE_DIR", "Run", "Check", "load_spec",
           "find_workload", "load_config", "load_traffic", "load_module",
           "metric_reader", "per_layer_for", "end_to_end_for",
           "require_device", "enable_compile_cache", "driver", "log"]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Check:
    """One number compared with its limit: passes when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What one run of a cell produced, for the result line and for the
    per-layer readers."""
    config: dict
    traffic: dict
    workload: dict
    shapes: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    checks: List[Check] = dataclasses.field(default_factory=list)
    window_s: float = float("nan")
    work: dict = dataclasses.field(default_factory=dict)
    spans: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    trace: Optional[object] = None       # bench.trace.TraceSummary
    peak: Optional[dict] = None          # bench.counts.PEAKS row
    memory_peak_bytes: Optional[int] = None
    compared: dict = dataclasses.field(default_factory=dict)  # for control

    @property
    def correct(self) -> bool:
        return self.failed == 0 and bool(self.checks) and all(
            c.ok for c in self.checks)


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in spec['workloads']]}")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _json(BENCH / "configs" / f"{name}.json")


def load_traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def load_module(path: Path):
    """Import a file of the benchmark by path (metric names hold dots)."""
    mod_name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py").read


def end_to_end_for(spec: dict, workload: str) -> List[dict]:
    """The end-to-end metrics that ``workload`` reports."""
    return [m for m in spec["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]]


def per_layer_for(spec: dict, workload: str) -> List[dict]:
    """The per-layer metrics read in ``workload``'s traced run: those
    that list it, and those without a list whose end-to-end metric the
    cell reports."""
    e2e = {m["name"] for m in end_to_end_for(spec, workload)}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in e2e)]


def require_device(chips: int) -> dict:
    """The device description, or exit non-zero before any work when JAX
    finds no TPU or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"needs a TPU, JAX found {devices[0].platform!r}")
        raise SystemExit(3)
    if len(devices) < chips:
        log(f"needs {chips} chip(s), JAX found {len(devices)}")
        raise SystemExit(3)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program of the run (the program's own and the
    reference's).

    Not ``repro.launch.compile_cache``, which defers to
    ``JAX_COMPILATION_CACHE_DIR`` where that is set: the benchmark's
    cache belongs to its checkout whatever the environment names, so
    that two checkouts measured side by side share nothing; and it keeps
    programs of any compile time and size (JAX's defaults skip those
    under a second), so that a warm run compiles nothing in its window,
    the small ones included."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(CACHE_DIR)


def driver(traffic: dict):
    return importlib.import_module(f"bench.drive_{traffic['driver']}")
