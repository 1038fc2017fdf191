"""Set-up shared by the drivers: the configuration's inputs made from the
seed and handed to the program, and the plain reference built from the
same inputs."""
from __future__ import annotations

import dataclasses
import gc

import numpy as np

from bench import gen, harness

__all__ = ["Inputs", "make_inputs", "program_graph", "weights", "reference",
           "memory_peak_bytes", "free_device"]


@dataclasses.dataclass
class Inputs:
    edge_u: np.ndarray
    edge_v: np.ndarray
    user_idx: np.ndarray
    item_idx: np.ndarray
    k_users: int
    k_items: int

    def shapes(self, cfg: dict) -> dict:
        return {"n_users": int(cfg["n_users"]), "n_items": int(cfg["n_items"]),
                "n_edges": int(self.edge_u.size), "k_users": self.k_users,
                "k_items": self.k_items,
                "n_hot_users": int(self.user_idx.shape[1]),
                "dim": int(cfg["dim"]), "n_layers": int(cfg["n_layers"])}


def make_inputs(cfg: dict) -> Inputs:
    """The configuration's data set: interactions and sketch drawn from
    its fixed ``graph_seed``, as a published data set is fixed. (The
    trainer closes over the graph, so a graph that moved with the run's
    seed would also compile anew in every run.)"""
    seed = int(cfg["graph_seed"])
    eu, ev, uc, ic = gen.interactions(cfg, seed)
    user_idx, item_idx, ku, kv = gen.baco_sketch(cfg, uc, ic, seed)
    return Inputs(eu, ev, user_idx, item_idx, ku, kv)


def program_graph(cfg: dict, inputs: Inputs):
    """(BipartiteGraph, Sketch) of the program under test."""
    from repro.core.graph import BipartiteGraph
    from repro.core.sketch import Sketch
    graph = BipartiteGraph.from_edges(int(cfg["n_users"]), int(cfg["n_items"]),
                                      inputs.edge_u, inputs.edge_v)
    sketch = Sketch(inputs.user_idx, inputs.item_idx, inputs.k_users,
                    inputs.k_items, method="baco")
    return graph, sketch


def weights(cfg: dict, inputs: Inputs, seed: int):
    return gen.codebook_weights(seed, inputs.k_users, inputs.k_items,
                                int(cfg["dim"]), float(cfg["init_scale"]))


def reference(cfg: dict, inputs: Inputs, dtype=None):
    """The configuration's plain reference over ``inputs``."""
    import jax.numpy as jnp
    mod = harness.load_module(harness.BENCH / "configs"
                              / f"{cfg['reference']}.py")
    return mod.Reference(cfg, inputs.edge_u, inputs.edge_v, inputs.user_idx,
                         inputs.item_idx, dtype=dtype or jnp.float32)


def memory_peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None


def free_device():
    """Drop what the program left on the device before the reference
    runs (callers delete their own references first)."""
    import jax
    gc.collect()
    jax.clear_caches()
