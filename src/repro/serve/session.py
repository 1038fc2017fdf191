"""Session: the one serving front door.

A Session owns device-resident state and exactly one jitted request fn;
the protocol is three methods:

    warmup(batch)   compile + touch the path for one request shape
    __call__(...)   serve one request (blocks, records latency)
    stats()         telemetry dict: requests, p50/p99 ms, compile count

Two implementations cover the repo's serving surfaces:

  * RecsysSession — the paper pipeline: batched user ids -> top-k items
    scored over compressed codebooks. Built either from live Trainer
    state or from a CompressedArtifact (the deploy path).
  * ArchSession — the assigned-arch smoke cells (serve/retrieval/decode
    shapes from launch/steps.build_cell); decode cells donate the KV
    cache and the session threads it between requests.

Front a Session with `repro.serve.BatchDispatcher` to serve arbitrary
batch sizes with a bounded number of compiles.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import pad_rung as _cap_rung
from repro.obs import clock
from repro.obs.scopes import SCORE, TOPK
from repro.obs.trace import get_tracer
from repro.embedding import (dequantize_params, fused_topk,
                             normalize_backend, params_quantized)
from repro.serve.telemetry import (LatencyRecorder, StreamTelemetry,
                                   compile_count)

__all__ = ["Session", "RecsysSession", "ArchSession", "capacity_plan",
           "normalize_scorer"]

_SCORER_CHOICES = ("dense", "fused")


def normalize_scorer(name: Optional[str]) -> str:
    """Canonicalize a session scorer name: None/"auto" -> "dense" (the
    classic score-all + lax.top_k path); "fused" -> the one-pass Pallas
    gather->score->top-k kernel (repro.embedding.fused_topk)."""
    if name in (None, "auto"):
        return "dense"
    name = str(name)
    if name not in _SCORER_CHOICES:
        raise ValueError(f"unknown scorer {name!r}; expected "
                         f"{'|'.join(_SCORER_CHOICES)} (or auto)")
    return name


# ---------------------------------------------------------------------------
# capacity ladder: pad device state so hot swaps never change shapes
# ---------------------------------------------------------------------------
_CAP_KEYS = ("n_users", "n_items", "k_users", "k_items", "n_edges")


# _cap_rung (= repro.core.graph.pad_rung) is the capacity ladder rung —
# BatchDispatcher's bucket idea on the MODEL side: any state whose true
# sizes fit under the current rungs compiles zero new XLA programs when
# swapped in. Shared with the padded solver programs so both sides
# agree where the rungs sit.


def capacity_plan(mcfg, statics, **maxima) -> dict:
    """Capacity rungs covering the given state plus caller headroom.

    ``maxima`` may name any of n_users/n_items/k_users/k_items/n_edges
    with the largest value the deployment expects (e.g. the end of a
    replay stream); each capacity is the ladder rung covering
    max(current, requested).
    """
    need = {"n_users": mcfg.n_users, "n_items": mcfg.n_items,
            "k_users": mcfg.k_users or 0, "k_items": mcfg.k_items or 0,
            "n_edges": int(np.asarray(statics["edge_u"]).shape[0])}
    unknown = set(maxima) - set(_CAP_KEYS)
    if unknown:
        raise ValueError(f"unknown capacity keys {sorted(unknown)}; "
                         f"expected {_CAP_KEYS}")
    return {key: _cap_rung(max(need[key], int(maxima.get(key) or 0)))
            for key in _CAP_KEYS}


def _pad_rows(a, rows: int, fill=0):
    a = np.asarray(a)
    if a.shape[0] > rows:
        raise ValueError(f"state of {a.shape[0]} rows exceeds capacity "
                         f"{rows}")
    out = np.full((rows,) + a.shape[1:], fill, dtype=a.dtype)
    out[:a.shape[0]] = a
    return out


def _pad_state(params, statics, mcfg, caps: dict):
    """Pad (params, statics, mcfg) up to the capacity rungs.

    Correctness of the padding, piece by piece:
      * pad codebook/table rows are zero and unreferenced;
      * pad sketch rows point at row 0 — only queried if a caller asks
        for a user id beyond the artifact's true count;
      * pad edges hang off the LAST capacity user/item with edge_norm
        0, appended after the real (sorted) runs — so both sorted
        orientations stay sorted and every segment sum they touch adds
        exactly 0;
      * ``item_mask`` carries -inf for item slots beyond the true item
        count: scores see ``+ mask``, so pad items can never enter a
        top-k (this is data, not shape — it swaps with the state).
    """
    nu, nv = mcfg.n_users, mcfg.n_items
    cu, cv, ce = caps["n_users"], caps["n_items"], caps["n_edges"]
    p = {k: np.asarray(v) for k, v in params.items()}
    s = {k: np.asarray(v) for k, v in statics.items()}
    compressed = mcfg.k_users is not None
    # pad by table-name prefix so int8 payloads ({name}_q int8 rows +
    # {name}_scale fp32 vector) ride the same ladder as fp32 tables; a
    # pad row dequantizes to 0 * 0 and is unreferenced either way
    u_rows = caps["k_users"] if compressed else cu
    v_rows = caps["k_items"] if compressed else cv
    out_p = {}
    for key, arr in p.items():
        if key.startswith("user_table"):
            out_p[key] = _pad_rows(arr, u_rows)
        elif key.startswith("item_table"):
            out_p[key] = _pad_rows(arr, v_rows)
        else:
            raise ValueError(f"unknown param table {key!r}")
    e = int(s["edge_u"].shape[0])
    out_s = {
        "edge_u": _pad_rows(s["edge_u"], ce, cu - 1),
        "edge_v": _pad_rows(s["edge_v"], ce, cv - 1),
        "edge_norm": _pad_rows(s["edge_norm"], ce, 0),
        "edge_u_byitem": _pad_rows(s["edge_u_byitem"], ce, cu - 1),
        "edge_norm_byitem": _pad_rows(s["edge_norm_byitem"], ce, 0),
    }
    for name, n_real, cap in (("indptr_u", nu, cu), ("indptr_v", nv, cv)):
        ip = np.full(cap + 1, e, dtype=s[name].dtype)
        ip[:n_real + 1] = s[name]
        ip[-1] = ce                       # pad edges belong to the last slot
        out_s[name] = ip
    if "sketch_u" in s:
        out_s["sketch_u"] = _pad_rows(s["sketch_u"], cu)
        out_s["sketch_v"] = _pad_rows(s["sketch_v"], cv)
    mask = np.zeros(cv, np.float32)
    mask[nv:] = -np.inf
    out_s["item_mask"] = mask
    mcfg2 = dataclasses.replace(
        mcfg, n_users=cu, n_items=cv,
        k_users=caps["k_users"] if compressed else None,
        k_items=caps["k_items"] if compressed else None)
    return out_p, out_s, mcfg2


class Session:
    """Protocol base: subclasses implement the three methods below."""

    def warmup(self, batch: Optional[int] = None) -> None:
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        raise NotImplementedError

    def stats(self) -> dict:
        raise NotImplementedError

    @property
    def compile_count(self) -> int:
        raise NotImplementedError


class RecsysSession(Session):
    """Top-k scoring over (possibly compressed) LightGCN tables.

    The scoring fn is jitted ONCE; params and statics are device-resident
    for the session's lifetime. Each distinct request batch size is a new
    XLA program — callers with variable traffic should go through
    BatchDispatcher, which pads to a fixed bucket ladder. (The int32
    request ids cannot alias the float top-k outputs, so nothing is
    donated here; the donation win lives in ArchSession's decode path.)

    Streaming deployments construct the session with ``capacity`` — the
    model-side analogue of the dispatcher's bucket ladder: params and
    statics are padded up to power-of-two capacity rungs
    (``capacity_plan``), so ``swap(artifact)`` can atomically switch the
    codebook/sketch/edge device arrays between requests with ZERO new
    XLA compiles as long as the new state fits under the rungs. A swap
    that outgrows a rung bumps the ladder (one recompile, counted in
    telemetry) instead of failing.
    """

    def __init__(self, params, statics, mcfg, k: int = 20,
                 backend: Optional[str] = None, capacity=None,
                 telemetry: Optional[StreamTelemetry] = None,
                 scorer: Optional[str] = None, fused_block: int = 1024):
        if backend is not None:
            mcfg = dataclasses.replace(
                mcfg, lookup_backend=normalize_backend(backend))
        else:
            normalize_backend(mcfg.lookup_backend)   # validate early
        self.k = int(k)
        self.scorer = normalize_scorer(scorer)
        self._fused_block = int(fused_block)
        self._lat = LatencyRecorder()
        self._stream = telemetry or StreamTelemetry()
        self._compiles_base = 0
        self._shapes = set()
        self._fn = None
        self.mcfg = None
        self._caps = None
        # publication identity: bumped on every swap; the content_id of
        # the served artifact when the session came from one (None for
        # live-state sessions). The frontdoor keys tenant sharing and
        # response-cache invalidation on these.
        self.swap_epoch = 0
        self.artifact_id = None
        if capacity is not None:
            if capacity is True or capacity == "auto":
                capacity = {}
            self._caps = capacity_plan(mcfg, statics, **capacity)
            params, statics, mcfg = _pad_state(params, statics, mcfg,
                                               self._caps)
        self._install(params, statics, mcfg)

    def _install(self, params, statics, mcfg) -> None:
        """(Re)build the jitted scorer if the static config changed, and
        put the state on device. The attribute writes at the bottom are
        the swap point: requests issued before them serve the old state,
        requests after serve the new — nothing in between."""
        if self._fn is None or mcfg != self.mcfg:
            if self._fn is not None:   # carry compiled-program count over
                self._compiles_base += compile_count(self._fn, self._shapes)
                self._shapes = set()
            from repro.models import lightgcn as L

            if self.scorer == "fused":
                # one-pass kernel over the propagated item embeddings:
                # the [B, n_items] score matrix never materializes
                def score_topk(params, statics, user_ids):
                    params = dequantize_params(params)
                    u, v = L.eval_embeddings(params, statics, mcfg,
                                             user_ids)
                    with jax.named_scope(SCORE):
                        return fused_topk(u, v, self.k,
                                          mask=statics.get("item_mask"),
                                          block=self._fused_block)
            else:
                def score_topk(params, statics, user_ids):
                    params = dequantize_params(params)
                    # lookup and propagate nest inside: innermost wins
                    with jax.named_scope(SCORE):
                        scores = L.score_all_items(params, statics, mcfg,
                                                   user_ids)
                        mask = statics.get("item_mask")
                        if mask is not None:   # capacity pad items -> -inf
                            scores = scores + mask[None, :]
                    with jax.named_scope(TOPK):
                        return jax.lax.top_k(scores, self.k)

            self._fn = jax.jit(score_topk)
        new_params = jax.device_put(jax.tree.map(jnp.asarray, params))
        new_statics = jax.device_put(jax.tree.map(jnp.asarray, statics))
        jax.block_until_ready((new_params, new_statics))
        self.mcfg = mcfg
        self.params = new_params
        self.statics = new_statics

    @classmethod
    def from_artifact(cls, artifact, k: int = 20,
                      backend: Optional[str] = None, capacity=None,
                      telemetry: Optional[StreamTelemetry] = None,
                      scorer: Optional[str] = None) -> "RecsysSession":
        """The deploy path: rebuild the scoring session from a loaded
        CompressedArtifact. `backend` overrides the backend recorded in
        the artifact meta (None keeps the trained choice); a quantized
        artifact serves its int8 payload (dequant inside the scorer)."""
        session = cls(artifact.serving_params(), artifact.statics(),
                      artifact.mcfg(), k=k, backend=backend,
                      capacity=capacity, telemetry=telemetry, scorer=scorer)
        session.artifact_id = artifact.content_id()
        return session

    # -- hot swap -----------------------------------------------------------
    def swap(self, artifact) -> dict:
        """Atomically switch to a new artifact's state between requests.

        The only sanctioned way to change what a live session serves
        (the arch test greps for out-of-band `.params`/`.statics`
        writes). With a capacity ladder, a swap whose true sizes fit
        under the current rungs reuses every compiled program — the
        zero-new-compiles invariant pinned in tests/test_stream.py. A
        swap that outgrows a rung re-plans the ladder and recompiles
        once (counted as a capacity bump). Returns the swap stats.
        """
        t0 = clock.now()
        with get_tracer().span("session_swap",
                               artifact=artifact.content_id()) as span:
            mcfg = dataclasses.replace(
                artifact.mcfg(), lookup_backend=self.mcfg.lookup_backend)
            params, statics = artifact.serving_params(), artifact.statics()
            bumped = False
            if self._caps is not None:
                try:
                    params, statics, mcfg = _pad_state(params, statics,
                                                       mcfg, self._caps)
                except ValueError:      # outgrew a rung: bump the ladder
                    self._caps = capacity_plan(mcfg, statics, **self._caps)
                    params, statics, mcfg = _pad_state(params, statics,
                                                       mcfg, self._caps)
                    bumped = True
                    self._stream.bump("capacity_bumps")
            self._install(params, statics, mcfg)
            self.swap_epoch += 1
            self.artifact_id = artifact.content_id()
            ms = (clock.now() - t0) * 1e3
            span.set(ms=round(ms, 3), capacity_bumped=bumped)
        self._stream.swap.record(ms)
        return {"ms": round(ms, 3), "capacity_bumped": bumped,
                "capacity": dict(self._caps) if self._caps else None}

    def warmup(self, batch: Optional[int] = None) -> None:
        batch = int(batch or 1)
        self._shapes.add(batch)
        ids = jnp.zeros((batch,), jnp.int32)
        jax.block_until_ready(self._fn(self.params, self.statics, ids))

    def __call__(self, user_ids):
        """user_ids int32 [B] -> (values [B,k], item_ids [B,k])."""
        user_ids = jnp.asarray(user_ids, jnp.int32)
        self._shapes.add(int(user_ids.shape[0]))
        t0 = clock.now()
        out = self._fn(self.params, self.statics, user_ids)
        jax.block_until_ready(out)
        self._lat.record((clock.now() - t0) * 1e3)
        return out

    @property
    def compile_count(self) -> int:
        """Distinct XLA programs over the session's whole life — compiles
        retired by a capacity bump stay counted (the bump paid them)."""
        return self._compiles_base + compile_count(self._fn, self._shapes)

    @property
    def telemetry(self) -> StreamTelemetry:
        return self._stream

    def stats(self) -> dict:
        out = {"kind": "recsys", "k": self.k,
               "backend": self.mcfg.lookup_backend or "auto",
               "scorer": self.scorer,
               "quantized": params_quantized(self.params),
               "compiles": self.compile_count, **self._lat.summary()}
        if self._caps is not None or self._stream.swap.count:
            out["capacity"] = dict(self._caps) if self._caps else None
            out["stream"] = self._stream.summary()
        return out


class ArchSession(Session):
    """Serve/retrieval/decode cells for the assigned archs (smoke scale by
    default; full configs are dry-run only).

    Decode cells donate the KV cache: the session threads the returned
    cache back into the next request's arguments (`Cell.next_args`), so
    steady-state decoding reuses the donated buffers.
    """

    def __init__(self, arch_id: str, shape: str = "serve_p99",
                 backend: Optional[str] = None, mesh=None,
                 smoke: bool = True):
        from repro.launch.steps import build_cell
        self.cell = build_cell(arch_id, shape, mesh=mesh, smoke=smoke,
                               lookup_backend=normalize_backend(backend))
        donate = self.cell.donate if self.cell.kind == "decode" else ()
        self._fn = jax.jit(self.cell.fn, donate_argnums=donate)
        self._args = self.cell.args
        self._lat = LatencyRecorder()
        self._warm = False

    @property
    def donates_cache(self) -> bool:
        return self.cell.kind == "decode" and bool(self.cell.donate)

    def warmup(self, batch: Optional[int] = None) -> None:
        """Compile + run once (untimed); threads the donated cache."""
        out = self._fn(*self._args)
        jax.block_until_ready(out)
        self._args = self.cell.next_args(self._args, out)
        self._warm = True

    def __call__(self):
        if not self._warm:
            self.warmup()
        t0 = clock.now()
        out = self._fn(*self._args)
        jax.block_until_ready(out)
        self._lat.record((clock.now() - t0) * 1e3)
        self._args = self.cell.next_args(self._args, out)
        return out

    @property
    def compile_count(self) -> int:
        return compile_count(self._fn, {0} if self._warm else set())

    def stats(self) -> dict:
        return {"kind": self.cell.kind, "arch": self.cell.arch_id,
                "shape": self.cell.shape_name,
                "cache_donated": self.donates_cache,
                "compiles": self.compile_count, **self._lat.summary()}
