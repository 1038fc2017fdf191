"""Mesh-aware sharding helpers.

Model code calls ``shard(x, *axes)`` with logical axis names per dim;
under a Mesh context this becomes a sharding constraint, otherwise a
no-op — so the same model runs on 1 CPU device (tests) and on the
(pod, data, model) production mesh (dry-run / real launch).

Logical axes under the default "tp" mapping:
  "batch"  -> ("pod", "data") when the pod axis exists, else "data"
  "model"  -> "model"   (TP/EP/vocab-row dim)
  "seq"    -> "model"   only in explicitly sequence-parallel tensors
  None     -> replicated dim

The PHYSICAL mesh is fixed (16x16 / 2x16x16); the LOGICAL mapping is a
perf lever (EXPERIMENTS.md §Perf): ``logical_mapping("dp")`` re-targets
"batch" to every mesh axis and turns "model" constraints off — pure
data parallelism for models whose weights fit per-chip, eliminating the
per-layer TP activation all-reduces.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["shard", "shard_map", "logical_to_spec", "current_mesh",
           "named_sharding", "batch_axes", "logical_mapping",
           "current_mapping", "cluster_mesh", "data_mesh", "edge_partition",
           "pad_to_shards", "edge_partitioned_half_step"]


def shard_map(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (bodies here use
    psum/ppermute explicitly)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_MAPPING = "tp"      # module-level; set during tracing via logical_mapping


@contextlib.contextmanager
def logical_mapping(mode: str):
    """Context manager: 'tp' (default) or 'dp' logical-axis mapping."""
    global _MAPPING
    if mode not in ("tp", "dp"):
        raise ValueError(mode)
    prev = _MAPPING
    _MAPPING = mode
    try:
        yield
    finally:
        _MAPPING = prev


def current_mapping() -> str:
    return _MAPPING


def current_mesh() -> Optional[Mesh]:
    try:
        from jax._src import mesh as mesh_lib
        env = mesh_lib.thread_resources.env
        phys = env.physical_mesh
        if phys is not None and not phys.empty:
            return phys
    except Exception:
        pass
    return None


def batch_axes(mesh: Mesh) -> tuple:
    """Physical axes implementing the logical batch axis."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def logical_to_spec(mesh: Mesh, axes: Sequence[Optional[str]]) -> P:
    dp = _MAPPING == "dp"
    out = []
    for a in axes:
        if a is None:
            out.append(None)
        elif a == "batch":
            ba = batch_axes(mesh)
            if dp and "model" in mesh.axis_names:
                ba = ba + ("model",)
            out.append(ba if len(ba) > 1 else (ba[0] if ba else None))
        elif a in ("model", "seq"):
            if dp:
                out.append(None)          # no tensor parallelism
            else:
                out.append("model" if "model" in mesh.axis_names else None)
        elif a == "data":
            out.append("data" if "data" in mesh.axis_names else None)
        elif a == "vocab":
            # giant embedding tables: row-shard across the whole pod
            # (data x model), replicate across pods (lookups stay on ICI)
            va = tuple(x for x in ("data", "model") if x in mesh.axis_names)
            out.append(va if len(va) > 1 else (va[0] if va else None))
        else:
            raise ValueError(f"unknown logical axis {a!r}")
    return P(*out)


def _in_manual_context() -> bool:
    """True while tracing inside shard_map (Manual mesh axes) — sharding
    constraints are invalid there; the body is already per-device."""
    return any(t == jax.sharding.AxisType.Manual
               for t in jax.sharding.get_abstract_mesh().axis_types)


def shard(x, *axes: Optional[str]):
    """Apply a sharding constraint if a mesh is active; identity otherwise."""
    mesh = current_mesh()
    if mesh is None or len(mesh.axis_names) == 0 or _in_manual_context():
        return x
    spec = logical_to_spec(mesh, axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, *axes: Optional[str]) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(mesh, axes))


# ---------------------------------------------------------------------------
# edge-partitioned co-clustering (ClusterEngine "jax_sharded" solver)
#
# The LP half-step updates one side of the bipartite graph from its
# incident edges. Edges arrive sorted by the updating-side node, so a
# contiguous partition of that side's node range induces a contiguous
# edge partition: each device owns a node range plus exactly the edges
# into it, computes the per-(node, candidate-label) counts with LOCAL
# segment sums, and only the per-label opposite-side weight totals —
# a single f32[n_labels] vector — cross devices, via one psum.
# ---------------------------------------------------------------------------
def cluster_mesh(n_devices: Optional[int] = None, axis: str = "edge") -> Mesh:
    """1-D mesh over the local devices for edge-partitioned clustering."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def data_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D "data" mesh over the local devices — the fused_sharded
    trainer backend splits each BPR batch across it and psums grads."""
    return cluster_mesh(n_devices, axis="data")


def edge_partition(node_of_edge: np.ndarray, opp_of_edge: np.ndarray,
                   n_side: int, n_shards: int, bounds=None):
    """Split edges (sorted by updating-side node) into per-shard blocks.

    Default (bounds=None): nodes are partitioned into ``n_shards``
    contiguous ranges of ``nodes_per_shard``; each shard's edge block is
    the contiguous run of edges into its range, padded to the max block
    length with sentinel edges (local node id == nodes_per_shard,
    dropped by the segment ops). Returns (node_local int32[S*Emax],
    opp int32[S*Emax], nodes_per_shard) — flat, ready for a P("edge")
    in_spec.

    bounds: optional node-aligned EDGE offsets (``node_aligned_bounds``
    / ``graph.edge_block_bounds``) of length ``n_shards + 1`` — the same
    blocking primitive the streamed solver sweeps, composed here into
    the shard layout. Shards then own edge-BALANCED blocks (equal node
    ranges skew per-device edge counts badly on power-law graphs; the
    scale bench records the imbalance factor), node alignment is
    validated, and the return gains each shard's first owned node:
    (node_local, opp, nodes_per_shard, node_starts int64[S + 1]) with
    local ids relative to ``node_starts[s]``.
    """
    if bounds is None:
        nps = max(1, -(-n_side // n_shards))
        bounds = np.searchsorted(
            node_of_edge, np.arange(n_shards + 1, dtype=np.int64) * nps)
        emax = max(1, int(np.max(np.diff(bounds))))
        node_local = np.full((n_shards, emax), nps, dtype=np.int32)
        opp = np.zeros((n_shards, emax), dtype=np.int32)
        for s in range(n_shards):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            node_local[s, :hi - lo] = node_of_edge[lo:hi] - s * nps
            opp[s, :hi - lo] = opp_of_edge[lo:hi]
        return node_local.reshape(-1), opp.reshape(-1), nps
    bounds = np.asarray(bounds, np.int64)
    e = int(node_of_edge.shape[0])
    if bounds.size != n_shards + 1 or bounds[0] != 0 or bounds[-1] != e:
        raise ValueError(f"bounds must be {n_shards + 1} offsets covering "
                         f"[0, {e}], got shape {bounds.shape}")
    cuts = bounds[1:-1]
    inner = cuts[(cuts > 0) & (cuts < e)]
    if inner.size and np.any(node_of_edge[inner - 1] == node_of_edge[inner]):
        raise ValueError("bounds are not node-aligned: a node's edge run "
                         "straddles a shard cut")
    node_starts = np.full(n_shards + 1, n_side, np.int64)
    if e:
        node_starts[:-1] = node_of_edge[np.minimum(bounds[:-1], e - 1)]
    else:
        node_starts[:-1] = 0
    nps = max(1, int(np.max(np.diff(node_starts))))
    emax = max(1, int(np.max(np.diff(bounds))))
    node_local = np.full((n_shards, emax), nps, dtype=np.int32)
    opp = np.zeros((n_shards, emax), dtype=np.int32)
    for s in range(n_shards):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        node_local[s, :hi - lo] = node_of_edge[lo:hi] - node_starts[s]
        opp[s, :hi - lo] = opp_of_edge[lo:hi]
    return node_local.reshape(-1), opp.reshape(-1), nps, node_starts


def pad_to_shards(x: np.ndarray, n_shards: int, per_shard: int,
                  fill=0) -> np.ndarray:
    """Pad a per-node host array to n_shards*per_shard for P(axis) input."""
    out = np.full(n_shards * per_shard, fill, dtype=x.dtype)
    out[:x.shape[0]] = x
    return out


def edge_partitioned_half_step(mesh: Mesh, half_step_fn, n_labels: int,
                               nodes_per_shard: int, axis: str = "edge"):
    """shard_map-wrap one LP half-step over an edge-partitioned mesh axis.

    half_step_fn(node_of_edge, cand_lab_of_edge, w_self,
                 w_other_by_label, own_labels, gamma, n_side, n_labels)
    is the single-device half-step math (core/solver_jax supplies it);
    this wrapper only adds the distribution strategy: per-device edge
    blocks + node ranges, local segment sums, and a psum that combines
    the per-label opposite-side weight totals.

    The returned callable takes GLOBAL (flat-padded) arrays:
      node_local [S*Emax], opp_idx [S*Emax]  — from edge_partition
      own_labels [S*nps], w_self [S*nps]     — updating side, padded
      lab_other  [S*nps_o], w_other [S*nps_o]— opposite side, padded
      lab_other_full [n_other]               — replicated, for the
                                               candidate-label gather
      gamma scalar                           — replicated
    and returns new labels [S*nps] (slice [:n_side] for the real nodes).
    """
    def body(node_local, opp_idx, own_labels, w_self, lab_other, w_other,
             lab_other_full, gamma):
        partial = jax.ops.segment_sum(w_other, lab_other,
                                      num_segments=n_labels)
        w_by_label = jax.lax.psum(partial, axis)
        cand_lab = lab_other_full[opp_idx]
        return half_step_fn(node_local, cand_lab, w_self, w_by_label,
                            own_labels, gamma, nodes_per_shard, n_labels)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axis), P(axis), P(axis), P(axis),
                               P(axis), P(axis), P(), P()),
                     out_specs=P(axis))
