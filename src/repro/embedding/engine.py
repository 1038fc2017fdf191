"""EmbeddingEngine: one dispatch layer for every embedding lookup.

The paper's serving story is the compressed lookup e_i = Σ_h Z[sketch[i,h]]
(§3.2/§4.5); the repo previously had two disconnected implementations of
it (pure-jnp in tables.py and Pallas kernels nothing called). This module
unifies them behind a backend registry so the hot path can be swapped,
benchmarked and sharded without touching call sites.

Three lookup kinds share one `EmbeddingSpec`-driven API:

  * full      e = T[i]                   (uncompressed table)
  * codebook  e = Σ_h Z[sketch[i, h]]    with the BINARY-Y dedup rule:
              a duplicate sketch index (SCU falling back to the primary
              cluster) contributes once, not twice (paper §3.2)
  * bag       e_b = Σ_{i in bag b} T[i]  (EmbeddingBag; multi-hot fields)

Backends (see EXPERIMENTS.md §Lookup-backends):

  * "gather": jnp.take / segment_sum — default; lowers to dynamic-gather.
  * "onehot": one-hot matmul — MXU-friendly for small codebooks, and on
    row-sharded tables it turns the lookup into a local GEMM + psum
    instead of a gather + all-to-all.
  * "pallas": fused TPU kernels (registered by repro.kernels.ops on
    import; interpret mode off-TPU so parity tests run on CPU).

Selection is automatic from (codebook size, H, device platform) and can
be overridden per call site — configs thread a `lookup_backend` field,
`launch/serve.py` exposes `--backend`, benchmarks sweep all of them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

__all__ = ["EmbeddingSpec", "EmbeddingEngine", "LookupBackend",
           "register_backend", "get_backend", "available_backends",
           "normalize_backend", "dedup_keep_mask", "embedding_lookup",
           "register_scorer", "get_scorer", "available_scorers",
           "fused_topk", "ONEHOT_MAX_ROWS"]

# Below this codebook size the one-hot matmul fits comfortably in VMEM and
# trades a gather (slow on the VPU) for an MXU GEMM.
ONEHOT_MAX_ROWS = 512


@dataclasses.dataclass(frozen=True)
class EmbeddingSpec:
    """Static description of one (possibly compressed) table."""
    n_rows: int                     # logical vocabulary size
    dim: int
    k_rows: Optional[int] = None    # codebook rows if compressed
    n_hot: int = 1                  # sketch multiplicity (SCU/double -> 2)
    combine: str = "sum"

    @property
    def compressed(self) -> bool:
        return self.k_rows is not None

    @property
    def table_rows(self) -> int:
        return self.k_rows if self.compressed else self.n_rows


def bag_combine(out, values, segment_ids, num_segments: int, mode: str):
    """Shared sum->mean post-processing for bag backends (empty bags keep
    their zero rows; the count is clamped to 1)."""
    if mode == "mean":
        cnt = jax.ops.segment_sum(jnp.ones_like(values, dtype=out.dtype),
                                  segment_ids, num_segments=num_segments)
        out = out / jnp.maximum(cnt, 1.0)[:, None]
    elif mode != "sum":
        raise ValueError(f"unknown mode {mode!r}")
    return out


def dedup_keep_mask(rows_idx):
    """bool [..., H]: True where an index is the FIRST occurrence in its
    row (the paper's binary Y: duplicates contribute once)."""
    h = rows_idx.shape[-1]
    keep = jnp.ones(rows_idx.shape, bool)
    for i in range(1, h):
        dup = jnp.zeros(rows_idx.shape[:-1], bool)
        for j in range(i):
            dup = dup | (rows_idx[..., i] == rows_idx[..., j])
        keep = keep.at[..., i].set(~dup)
    return keep


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------
class LookupBackend:
    """One strategy for the three lookup kinds. Subclass + register.

    Contract (checked by tests/test_engine.py against kernels/ref.py):
      full(table [N,d], ids [...])                     -> [..., d]
      codebook_sum(codebook [K,d], rows_idx [..., H],
                   keep bool [..., H])                 -> [..., d]
          masked sum: entries with keep=False contribute zero.
      bag(table, values [nnz], segment_ids [nnz], num_segments,
          mode, weights)                               -> [num_segments, d]
    """
    name: str = "?"
    # capability flags consulted by the engine's dispatch
    supports_bag_weights: bool = True     # per-value scaling in bag()
    requires_sorted_bags: bool = False    # bag() correct only for sorted
                                          # ascending segment_ids

    def supports(self, kind: str, spec: Optional[EmbeddingSpec],
                 platform: str) -> bool:
        return True

    def full(self, table, ids):
        raise NotImplementedError

    def codebook_sum(self, codebook, rows_idx, keep):
        raise NotImplementedError

    def bag(self, table, values, segment_ids, num_segments, mode="sum",
            weights=None):
        raise NotImplementedError


_REGISTRY: Dict[str, LookupBackend] = {}


def register_backend(backend: LookupBackend) -> LookupBackend:
    _REGISTRY[backend.name] = backend
    return backend


def _ensure_registered():
    # the pallas backend lives with its kernels; import is deferred so
    # importing repro.embedding never drags Pallas in eagerly
    if "pallas" not in _REGISTRY:
        try:
            import repro.kernels.ops  # noqa: F401  (registers "pallas")
        except ImportError:  # pragma: no cover - kernels always ship
            pass


def get_backend(name: str) -> LookupBackend:
    _ensure_registered()
    if name not in _REGISTRY:
        raise KeyError(f"unknown lookup backend {name!r}; "
                       f"registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available_backends():
    _ensure_registered()
    return tuple(sorted(_REGISTRY))


def normalize_backend(name: Optional[str]) -> Optional[str]:
    """Canonicalize a CLI/config/artifact backend name: "auto"/None mean
    per-platform auto-selection (None); anything else must name a
    registered backend (KeyError otherwise, listing what exists)."""
    if name is None or name == "auto":
        return None
    get_backend(name)           # raises KeyError for unknown names
    return name


# ---------------------------------------------------------------------------
# fused scorer registry (lookup -> score -> top-k in one pass)
# ---------------------------------------------------------------------------
# Scorers live beside the lookup backends because they are the same
# dispatch problem one level up: serving code (repro.serve) reaches ALL
# table-touching compute through this module — the arch tests grep-ban
# direct repro.kernels imports outside the embedding layer. The "pallas"
# scorer is registered by repro.kernels.ops on the same deferred import
# as the "pallas" lookup backend; "ref" is its pure-jnp twin.
_SCORERS: Dict[str, Any] = {}


def register_scorer(name: str, fn) -> None:
    _SCORERS[name] = fn


def get_scorer(name: str):
    _ensure_registered()
    if name not in _SCORERS:
        raise KeyError(f"unknown fused scorer {name!r}; "
                       f"registered: {sorted(_SCORERS)}")
    return _SCORERS[name]


def available_scorers():
    _ensure_registered()
    return tuple(sorted(_SCORERS))


def fused_topk(u, items, k, *, sketch=None, scale=None, mask=None,
               exclude=None, block=512, backend=None, interpret=None):
    """One-pass gather -> score -> top-k over the item axis.

    Returns ``(values [B, k] f32, ids [B, k] int32)`` equal to
    ``lax.top_k(u @ V.T + mask, k)`` where ``V`` is ``items`` [N, d]
    directly, or the codebook expansion ``Σ_h items[sketch[:, h]]``
    (binary-Y dedup) when ``sketch`` [N, H] is given — without ever
    materializing the [B, N] score matrix (backend "pallas", the
    default) . int8 ``items`` rows dequantize in-kernel through the
    per-row fp32 ``scale``. ``exclude`` is a host (rows, cols) pair
    scattered to -inf. Tie-break matches lax.top_k: lowest item id
    among equal values.
    """
    _ensure_registered()
    name = "pallas" if backend in (None, "auto") else str(backend)
    return get_scorer(name)(u, items, k, sketch=sketch, scale=scale,
                            mask=mask, exclude=exclude, block=block,
                            interpret=interpret)


# ---------------------------------------------------------------------------
# pure-jnp backends
# ---------------------------------------------------------------------------
class GatherBackend(LookupBackend):
    """jnp.take / segment_sum — the safe default on every platform."""
    name = "gather"

    def full(self, table, ids):
        return jnp.take(table, ids, axis=0)

    def codebook_sum(self, codebook, rows_idx, keep):
        rows = jnp.take(codebook, rows_idx, axis=0)        # [..., H, d]
        return jnp.where(keep[..., None], rows, 0).sum(axis=-2)

    def bag(self, table, values, segment_ids, num_segments, mode="sum",
            weights=None):
        rows = jnp.take(table, values, axis=0)
        if weights is not None:
            rows = rows * weights[:, None]
        out = jax.ops.segment_sum(rows, segment_ids,
                                  num_segments=num_segments)
        return bag_combine(out, values, segment_ids, num_segments, mode)


class OneHotBackend(LookupBackend):
    """One-hot matmul: GEMM instead of gather (small codebooks / sharded
    tables). No bag support — the [nnz, N] one-hot would dwarf the table."""
    name = "onehot"

    def supports(self, kind, spec, platform):
        return kind != "bag"

    def full(self, table, ids):
        oh = jax.nn.one_hot(ids, table.shape[0], dtype=table.dtype)
        return oh @ table

    def codebook_sum(self, codebook, rows_idx, keep):
        oh = jax.nn.one_hot(rows_idx, codebook.shape[0],
                            dtype=codebook.dtype)
        oh = oh * keep[..., None].astype(codebook.dtype)
        return jnp.einsum("...hk,kd->...d", oh, codebook)


register_backend(GatherBackend())
register_backend(OneHotBackend())


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EmbeddingEngine:
    """Routes lookups for one table through the selected backend.

    Construction is cheap (a frozen dataclass) and trace-safe: backend
    resolution uses only static information (spec sizes, platform), so
    engines can be built inside jitted model functions.

    backend:  explicit override ("gather" | "onehot" | "pallas" | None).
    platform: override for jax.default_backend() (tests force "tpu"/"cpu").
    """
    spec: EmbeddingSpec
    backend: Optional[str] = None
    platform: Optional[str] = None

    def _platform(self) -> str:
        return self.platform or jax.default_backend()

    def resolve(self, kind: str) -> LookupBackend:
        """Pick the backend for one lookup kind (auto unless overridden)."""
        _ensure_registered()
        platform = self._platform()
        if self.backend is not None and self.backend != "auto":
            be = get_backend(self.backend)
            if not be.supports(kind, self.spec, platform):
                raise ValueError(
                    f"backend {be.name!r} does not support {kind!r} lookups")
            return be
        return get_backend(self._auto_select(kind, platform))

    def _auto_select(self, kind: str, platform: str) -> str:
        """Selection rule, on platform and shape only:
        * TPU: fused Pallas kernels for codebook/bag (one HBM write per
          output tile); tiny codebooks go one-hot (K <= ONEHOT_MAX_ROWS);
          full-table lookups stay with XLA's native gather. The Pallas
          lookups compile for every table shape, dtype and batch size
          (batches whose index exceeds the SMEM prefetch are split into
          several kernel calls), so no codebook or bag shape is routed
          to "gather" on TPU (tests/test_tpu_compile.py).
        * CPU/GPU: "gather" everywhere — Pallas runs in interpret mode
          off-TPU (a correctness path, not a perf path), so it is only
          used when explicitly forced.
        """
        if platform == "tpu" and "pallas" in _REGISTRY:
            if kind == "codebook":
                if self.spec.table_rows <= ONEHOT_MAX_ROWS:
                    return "onehot"
                return "pallas"
            if kind == "bag":
                return "pallas"
        return "gather"

    # -- the three lookup kinds --------------------------------------------
    def full_lookup(self, table, ids):
        """table [N, d], ids [...] -> [..., d]."""
        return self.resolve("full").full(table, ids)

    def codebook_lookup(self, codebook, sketch_idx, ids, combine=None):
        """Compressed lookup e = Σ_h Z[sketch[i, h]] (paper §3.2/§4.5).

        codebook [K, d], sketch_idx int32 [N, H] (frozen ETC artifact),
        ids int32 [...] -> [..., d]. Duplicate sketch indices contribute
        once (binary Y), identically on every backend.
        """
        combine = combine or self.spec.combine
        rows_idx = jnp.take(sketch_idx, ids, axis=0)       # [..., H]
        h = rows_idx.shape[-1]
        keep = (dedup_keep_mask(rows_idx) if h > 1
                else jnp.ones(rows_idx.shape, bool))
        out = self.resolve("codebook").codebook_sum(codebook, rows_idx, keep)
        if combine == "sum":
            return out
        if combine == "mean":
            return out / h
        raise ValueError(f"unknown combine {combine!r}")

    def bag_lookup(self, table, values, segment_ids, num_segments: int,
                   mode: str = "sum", weights=None,
                   indices_sorted: bool = False):
        """EmbeddingBag: table [N,d], values [nnz], segment_ids [nnz]
        -> [num_segments, d]. Empty bags produce zero rows.

        indices_sorted: declare segment_ids sorted ascending. Backends
        whose fused kernel is only correct for sorted bags (pallas) are
        auto-selected only under this declaration; an EXPLICIT pallas
        override is honored either way (the caller owns the contract).
        Weighted bags fall back to a backend with per-value scaling.
        """
        be = self.resolve("bag")
        explicit = self.backend not in (None, "auto")
        if (weights is not None and not be.supports_bag_weights) or \
                (be.requires_sorted_bags and not indices_sorted
                 and not explicit):
            be = get_backend("gather")
        return be.bag(table, values, segment_ids, num_segments,
                      mode=mode, weights=weights)

    def lookup(self, table, ids, sketch=None, combine=None):
        """One entry point for call sites: codebook path when a sketch is
        given (or the spec says compressed), full-table path otherwise."""
        if sketch is not None:
            return self.codebook_lookup(table, sketch, ids, combine=combine)
        if self.spec.compressed:
            raise ValueError("spec is compressed but no sketch was given")
        return self.full_lookup(table, ids)


def embedding_lookup(table, ids, *, backend: Optional[str] = None,
                     platform: Optional[str] = None):
    """Convenience full-table lookup for call sites without a persistent
    spec (LM token embeddings, SchNet atom embeddings, ...)."""
    spec = EmbeddingSpec(n_rows=int(table.shape[0]), dim=int(table.shape[-1]))
    return EmbeddingEngine(spec, backend=backend,
                           platform=platform).full_lookup(table, ids)
