"""Pallas TPU kernel: fused gather -> score -> top-k in one VMEM pass.

ROADMAP item 3: the serving hot path previously ran three programs —
engine lookup, a dense ``u @ V.T`` over every item, and ``lax.top_k``
over the full ``[B, n_items]`` score matrix. This kernel streams the
item side through VMEM in fixed tiles and maintains a running per-user
top-k (values, ids) across tiles, so the ``[B, n_items]`` score matrix
never exists — peak memory is O(B x tile + B x k), independent of the
item count.

Two variants share the merge machinery:

* ``fused_topk_pallas`` — items are an explicit ``[N, d]`` matrix
  (propagated LightGCN embeddings, or a raw table). Grid ``(N/tile,)``;
  per step one item tile is DMA'd to VMEM, scored against the resident
  ``[B, d]`` user block, masked, and merged into the running top-k.
* ``fused_topk_codebook_pallas`` — items are implicit:
  ``v_i = Σ_h Z[sketch[i, h]]`` (binary-Y dedup, paper §3.2). This
  extends the PR 1 ``codebook_lookup`` tiling through the readout: grid
  ``(N/tile, tile, H)``, scalar-prefetched sketch indices drive a
  one-row-per-step DMA into a VMEM ``[tile, d]`` scratch accumulator,
  and the tile's last step scores + merges — expansion, scoring and
  selection in a single kernel, one HBM read per codebook row touched.

Both accept an int8 symmetric per-row quantized table/codebook with an
fp32 scale vector; rows are dequantized in-kernel
(``q.astype(f32) * scale``), so the HBM traffic is the int8 bytes.

Tie-break contract: identical to ``jax.lax.top_k`` — highest value
first, lowest index among equal values. The selection is k unrolled
rounds of masked first-occurrence argmax (Mosaic has no sort/top_k
primitive), and the cross-tile merge concatenates the running carry
BEFORE the new tile so earlier (lower-id) candidates keep winning ties.
One carve-out: equality is IEEE (-0.0 == +0.0), whereas lax.top_k's
total order ranks +0.0 above -0.0 — scores that differ only in zero
sign may order differently. Dot-product scores hit this with measure
zero, and the mask add (+0.0) normalizes -0.0 away on the masked paths.

Exclusion pairs ((row, item) scattered to -inf in-tile) use a jnp
scatter, which Mosaic cannot lower — the exclusion path is
interpret-mode only (serving masks via ``mask``, which compiles);
``kernels/ops.py`` refuses exclusions on a compiled platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .codebook_lookup import MAX_PREFETCH
from .platform import resolve_interpret

__all__ = ["fused_topk_pallas", "fused_topk_codebook_pallas",
           "select_topk", "exclusion_tiles"]

_NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# in-kernel top-k selection + cross-tile merge
# ---------------------------------------------------------------------------
def select_topk(scores, ids, k: int):
    """Row-wise top-k of ``scores`` [B, C] carrying ``ids`` [B, C].

    k unrolled rounds of masked argmax; among equal values the LOWEST
    position wins — bitwise the same (values, ids) as
    ``lax.top_k(scores, k)`` + gather of ``ids``, but built from
    max/min/where reductions only so it lowers under Mosaic. Requires
    C >= k. Rows with fewer than k finite entries fill with the
    lowest-position -inf candidates (exactly like lax.top_k).
    """
    b, c = scores.shape
    if c < k:
        raise ValueError(f"select_topk needs >= k={k} candidates, got {c}")
    pos = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
    taken = jnp.zeros((b, c), jnp.bool_)
    vals, out_ids = [], []
    for _ in range(k):
        live = jnp.where(taken, _NEG_INF, scores)
        m = jnp.max(live, axis=1, keepdims=True)
        # every untaken slot is a hit when the row max is -inf: the
        # first-position rule then picks the earliest leftover candidate
        hit = jnp.logical_and(jnp.logical_or(live == m, m == _NEG_INF),
                              jnp.logical_not(taken))
        first = jnp.min(jnp.where(hit, pos, c), axis=1, keepdims=True)
        sel = pos == first
        vals.append(jnp.max(jnp.where(sel, scores, _NEG_INF), axis=1))
        out_ids.append(jnp.sum(jnp.where(sel, ids, 0), axis=1))
        taken = jnp.logical_or(taken, sel)
    return (jnp.stack(vals, axis=1),
            jnp.stack(out_ids, axis=1).astype(jnp.int32))


def _merge_tile(s, col_ids, vals_ref, ids_ref, k: int, is_first):
    """Fold one tile of scores into the running (vals, ids) outputs.

    The first tile selects from itself alone; later tiles concat the
    carry FIRST so lower-id candidates from earlier tiles win ties —
    together these make the running result bitwise what lax.top_k over
    the full row would return.
    """

    @pl.when(is_first)
    def _():
        v, i = select_topk(s, col_ids, k)
        vals_ref[...] = v
        ids_ref[...] = i

    @pl.when(jnp.logical_not(is_first))
    def _():
        cv = jnp.concatenate([vals_ref[...], s], axis=1)
        ci = jnp.concatenate([ids_ref[...], col_ids], axis=1)
        v, i = select_topk(cv, ci, k)
        vals_ref[...] = v
        ids_ref[...] = i


# ---------------------------------------------------------------------------
# host-side exclusion bucketing (one padded (rows, cols) pair per tile)
# ---------------------------------------------------------------------------
def exclusion_tiles(exclude, nb: int, tile: int, row_sentinel: int):
    """Bucket global (row, item) exclusion pairs per item tile.

    Returns int32 ``(ex_r, ex_c)`` of shape [nb, E] (E = max bucket
    size, >= 1): tile-local column ids, padded with an out-of-range row
    sentinel that a ``mode="drop"`` scatter ignores. Host-only — the
    pairs must be concrete arrays, not tracers.
    """
    rows = np.asarray(exclude[0], dtype=np.int32)
    cols = np.asarray(exclude[1], dtype=np.int32)
    if rows.size == 0:
        return (np.full((nb, 1), row_sentinel, np.int32),
                np.zeros((nb, 1), np.int32))
    order = np.argsort(cols, kind="stable")
    rows, cols = rows[order], cols[order]
    bounds = np.searchsorted(cols, np.arange(nb + 1, dtype=np.int64) * tile)
    emax = max(1, int(np.max(np.diff(bounds))))
    ex_r = np.full((nb, emax), row_sentinel, dtype=np.int32)
    ex_c = np.zeros((nb, emax), dtype=np.int32)
    for b in range(nb):
        lo, hi = int(bounds[b]), int(bounds[b + 1])
        ex_r[b, :hi - lo] = rows[lo:hi]
        ex_c[b, :hi - lo] = cols[lo:hi] - b * tile
    return ex_r, ex_c


def _tile_plan(n: int, k: int, block: int):
    if k > n:
        raise ValueError(f"k={k} exceeds n_items={n}")
    tile = int(min(max(block, k), n))
    nb = -(-n // tile)
    return tile, nb, nb * tile - n


def _full_mask(mask, n: int, pad: int):
    m = (jnp.zeros((n,), jnp.float32) if mask is None
         else jnp.asarray(mask, jnp.float32))
    if pad:
        m = jnp.concatenate([m, jnp.full((pad,), _NEG_INF, jnp.float32)])
    return m.reshape(1, -1)


# ---------------------------------------------------------------------------
# dense variant: explicit [N, d] item matrix
# ---------------------------------------------------------------------------
def _dense_kernel(*refs, k: int, tile: int, b_block: int, quantized: bool,
                  excl: bool):
    it = iter(refs)
    u_ref, v_ref = next(it), next(it)
    scale_ref = next(it) if quantized else None
    mask_ref = next(it)
    exr_ref = next(it) if excl else None
    exc_ref = next(it) if excl else None
    vals_ref, ids_ref = next(it), next(it)

    i = pl.program_id(0)
    t = pl.program_id(1)
    v = v_ref[...]
    if quantized:
        v = v.astype(jnp.float32) * scale_ref[...]
    s = jnp.dot(u_ref[...], v.T, preferred_element_type=jnp.float32)
    s = s + mask_ref[0, :][None, :]
    if excl:
        rows = exr_ref[0] - i * b_block          # block-local; others drop
        rows = jnp.where((rows >= 0) & (rows < b_block), rows, b_block)
        s = s.at[rows, exc_ref[0]].set(_NEG_INF, mode="drop")
    col = t * tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    _merge_tile(s, col, vals_ref, ids_ref, k, t == 0)


# User rows scored per grid step. The k selection rounds keep several
# [rows, tile] temporaries in VMEM; 128 rows x 1024 items stays well
# inside the 16 MiB scoped-VMEM limit of a TPU v5e.
B_BLOCK = 128


def fused_topk_pallas(u, items, k: int, *, scale=None, mask=None,
                      exclude=None, block: int = 512, interpret=None):
    """``lax.top_k(u @ items.T + mask, k)`` without the score matrix.

    u [B, d] f32; items [N, d] f32, or int8 with ``scale`` f32 [N]
    (dequantized in-kernel). ``mask`` f32 [N] is added to every row
    (e.g. the capacity ladder's -inf pad mask); ``exclude`` is a host
    (rows, cols) pair scattered to -inf (interpret-mode only). Returns
    (values [B, k] f32, ids [B, k] int32) with lax.top_k tie-breaking.
    Grid ``(B / B_BLOCK, N / tile)``: user rows beyond ``B_BLOCK`` are
    scored in blocks of that many.
    """
    k = int(k)
    u = jnp.asarray(u, jnp.float32)
    b, d = u.shape
    n = items.shape[0]
    tile, nb, pad = _tile_plan(n, k, int(block))
    m = _full_mask(mask, n, pad)
    v = jnp.asarray(items)
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad, d), v.dtype)])
    bb = min(b, B_BLOCK)
    b_pad = -(-b // bb) * bb
    if b_pad != b:
        u = jnp.concatenate([u, jnp.zeros((b_pad - b, d), u.dtype)])
    quantized = scale is not None
    excl = exclude is not None

    in_specs = [pl.BlockSpec((bb, d), lambda i, t: (i, 0)),
                pl.BlockSpec((tile, d), lambda i, t: (t, 0))]
    args = [u, v]
    if quantized:
        sc = jnp.asarray(scale, jnp.float32).reshape(-1, 1)
        if pad:
            sc = jnp.concatenate([sc, jnp.zeros((pad, 1), jnp.float32)])
        in_specs.append(pl.BlockSpec((tile, 1), lambda i, t: (t, 0)))
        args.append(sc)
    in_specs.append(pl.BlockSpec((1, tile), lambda i, t: (0, t)))
    args.append(m)
    if excl:
        ex_r, ex_c = exclusion_tiles(exclude, nb, tile, row_sentinel=b_pad)
        e = ex_r.shape[1]
        in_specs += [pl.BlockSpec((1, e), lambda i, t: (t, 0)),
                     pl.BlockSpec((1, e), lambda i, t: (t, 0))]
        args += [jnp.asarray(ex_r), jnp.asarray(ex_c)]

    fn = pl.pallas_call(
        functools.partial(_dense_kernel, k=k, tile=tile, b_block=bb,
                          quantized=quantized, excl=excl),
        grid=(b_pad // bb, nb),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bb, k), lambda i, t: (i, 0)),
                   pl.BlockSpec((bb, k), lambda i, t: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b_pad, k), jnp.float32),
                   jax.ShapeDtypeStruct((b_pad, k), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )
    vals, ids = fn(*args)
    return vals[:b], ids[:b]


# ---------------------------------------------------------------------------
# codebook variant: items expanded through the sketch, in-kernel
# ---------------------------------------------------------------------------
def _codebook_kernel(sk_ref, *refs, k: int, tile: int, n_hot: int,
                     b_block: int, n_bblocks: int, quantized: bool,
                     excl: bool):
    it = iter(refs)
    u_ref, row_ref = next(it), next(it)
    scale_ref = next(it) if quantized else None
    mask_ref = next(it)
    exr_ref = next(it) if excl else None
    exc_ref = next(it) if excl else None
    vals_ref, ids_ref, vtile_ref = next(it), next(it), next(it)

    t = pl.program_id(0)
    j = pl.program_id(1)
    hh = pl.program_id(2)

    contrib = row_ref[0, :].astype(jnp.float32)
    if quantized:
        contrib = contrib * scale_ref[0, 0]
    if n_hot > 1:            # binary-Y dedup via the prefetched scalars
        item = t * tile + j
        cur = sk_ref[item * n_hot + hh]
        dup = jnp.zeros((), jnp.bool_)
        for jj in range(n_hot - 1):          # jj < hh <= n_hot-1
            dup = dup | ((jj < hh) & (sk_ref[item * n_hot + jj] == cur))
        contrib = jnp.where(dup, jnp.zeros_like(contrib), contrib)

    @pl.when(hh == 0)
    def _():
        vtile_ref[j, :] = contrib

    @pl.when(hh != 0)
    def _():
        vtile_ref[j, :] = vtile_ref[j, :] + contrib

    # tile fully expanded in VMEM scratch: score + merge, once per tile,
    # B_BLOCK user rows at a time
    @pl.when(jnp.logical_and(j == tile - 1, hh == n_hot - 1))
    def _():
        def score_rows(bi, carry):
            r0 = pl.multiple_of(bi * b_block, b_block)
            rows = pl.ds(r0, b_block)
            s = jnp.dot(u_ref[rows, :], vtile_ref[...].T,
                        preferred_element_type=jnp.float32)
            s = s + mask_ref[0, :][None, :]
            if excl:
                r = exr_ref[0] - r0              # block-local; others drop
                r = jnp.where((r >= 0) & (r < b_block), r, b_block)
                s = s.at[r, exc_ref[0]].set(_NEG_INF, mode="drop")
            col = t * tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            _merge_tile(s, col, vals_ref.at[rows], ids_ref.at[rows], k,
                        t == 0)
            return carry

        jax.lax.fori_loop(0, n_bblocks, score_rows, 0)


def fused_topk_codebook_pallas(u, codebook, sketch, k: int, *, scale=None,
                               mask=None, exclude=None, block: int = 128,
                               interpret=None):
    """Fused codebook expansion -> score -> top-k.

    u [B, d] f32; codebook [K, d] f32 or int8 with ``scale`` f32 [K];
    sketch int32 [N, H]. Item i scores as
    ``u . Σ_h dedup(Z[sketch[i, h]])`` — the expanded [N, d] item table
    never materializes: each tile of ``tile`` item rows is accumulated
    into VMEM scratch one codebook row per grid step (scalar-prefetched
    DMA, exactly the ``codebook_lookup`` pipeline) and scored in place.
    Same mask/exclude/tie-break contract as ``fused_topk_pallas``.

    The flattened sketch is scalar-prefetched into SMEM, so an item
    range whose sketch exceeds ``MAX_PREFETCH`` entries is scored in
    several calls whose per-call top-k are merged with ``lax.top_k``
    (earlier calls hold lower ids, so ties still break to the lower id).
    """
    k = int(k)
    sketch = jnp.asarray(sketch, jnp.int32)
    n, h = sketch.shape
    tile = _tile_plan(n, k, int(block))[0]
    chunk = max(tile, (MAX_PREFETCH // h) // tile * tile)
    cuts = list(range(0, n, chunk)) + [n]
    if len(cuts) > 2 and cuts[-1] - cuts[-2] < k:
        del cuts[-2]             # a tail shorter than k joins the call before
    parts = []
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        excl = None
        if exclude is not None:
            rows = np.asarray(exclude[0], np.int32)
            cols = np.asarray(exclude[1], np.int32)
            sel = (cols >= lo) & (cols < hi)
            excl = (rows[sel], cols[sel] - lo)
        vals, ids = _codebook_call(
            u, codebook, sketch[lo:hi], k, scale=scale,
            mask=None if mask is None else jnp.asarray(mask)[lo:hi],
            exclude=excl, block=block, interpret=interpret)
        parts.append((vals, ids + lo))
    if len(parts) == 1:
        return parts[0]
    vals = jnp.concatenate([p[0] for p in parts], axis=1)
    ids = jnp.concatenate([p[1] for p in parts], axis=1)
    vals, pos = jax.lax.top_k(vals, k)
    return vals, jnp.take_along_axis(ids, pos, axis=1)


def _codebook_call(u, codebook, sketch, k: int, *, scale, mask, exclude,
                   block: int, interpret):
    u = jnp.asarray(u, jnp.float32)
    b, d = u.shape
    bb = min(b, B_BLOCK)
    b_pad = -(-b // bb) * bb
    if b_pad != b:
        u = jnp.concatenate([u, jnp.zeros((b_pad - b, d), u.dtype)])
    n, h = sketch.shape
    tile, nb, pad = _tile_plan(n, k, int(block))
    m = _full_mask(mask, n, pad)
    if pad:                 # pad rows expand row 0 but score -inf via mask
        sketch = jnp.concatenate(
            [sketch, jnp.zeros((pad, h), jnp.int32)])
    quantized = scale is not None
    excl = exclude is not None
    kc = codebook.shape[0]

    def row_map(t, j, hh, sk, tile_=tile, h_=h):
        return (sk[(t * tile_ + j) * h_ + hh], 0, 0)

    # [K, 1, d] views: a (1, d) block spans the array's last two dims,
    # which Mosaic accepts for any K and d
    in_specs = [
        pl.BlockSpec((b_pad, d), lambda t, j, hh, sk: (0, 0)),
        pl.BlockSpec((None, 1, d), row_map),
    ]
    args = [jnp.asarray(codebook).reshape(kc, 1, d)]
    if quantized:
        in_specs.append(pl.BlockSpec((None, 1, 1), row_map))
        args.append(jnp.asarray(scale, jnp.float32).reshape(kc, 1, 1))
    in_specs.append(pl.BlockSpec((1, tile), lambda t, j, hh, sk: (0, t)))
    args.append(m)
    if excl:
        ex_r, ex_c = exclusion_tiles(exclude, nb, tile, row_sentinel=b_pad)
        e = ex_r.shape[1]
        in_specs += [pl.BlockSpec((1, e), lambda t, j, hh, sk: (t, 0)),
                     pl.BlockSpec((1, e), lambda t, j, hh, sk: (t, 0))]
        args += [jnp.asarray(ex_r), jnp.asarray(ex_c)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, tile, h),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((b_pad, k), lambda t, j, hh, sk: (0, 0)),
                   pl.BlockSpec((b_pad, k), lambda t, j, hh, sk: (0, 0))],
        scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_codebook_kernel, k=k, tile=tile, n_hot=h,
                          b_block=bb, n_bblocks=b_pad // bb,
                          quantized=quantized, excl=excl),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b_pad, k), jnp.float32),
                   jax.ShapeDtypeStruct((b_pad, k), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )
    vals, ids = fn(sketch.reshape(-1), u, *args)
    return vals[:b], ids[:b]
