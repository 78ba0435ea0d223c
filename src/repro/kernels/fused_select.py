"""Fused selection megakernel: classify → grouped scatter → select stats.

The paper's query-evaluation hot path answers a φ-constrained heatmap by
(1) classifying every object against the query window and its bx×by bin
grid, (2) scattering per-(tile, bin) ``(count, sum, min, max)``
aggregates, and (3) running suffix scans over the score-sorted
(tiles × bins) width matrix to find the smallest prefix of tiles whose
residual uncertainty meets the per-bin budgets. Composed naively that is
three passes' worth of dispatches; fused, the per-object work is ONE
pass over data the query already streams (the zero-overhead-adaptation
argument: incremental index work must piggyback on the scan).

Three backends, per house style:

- :func:`segment_window_bin_select_np` — f64 host mirror: the grouped
  table is bit-for-bit ``ref.segment_window_bin_agg_np`` (sorted-slice
  pairwise f64 accumulation — the sequential reference), extended with
  the selection-ready suffix widths in the same call.
- :func:`segment_window_bin_select_ref` / the shared jnp primitives
  (:func:`window_bin_ids`, :func:`fused_count_val`,
  :func:`suffix_residual`) — the jit oracle. ``core.distributed``'s
  fused session steps call these SAME primitives, so the SPMD
  classify→scatter→select chain and this oracle are one expression.
- :func:`segment_window_bin_select_pallas` — the TPU megakernel: the
  one grouped kernel of :mod:`repro.kernels.segment_agg` (window mask,
  bin ids, grouped reduction in one kernel body over a 2-D
  ``(cell_groups, row_blocks)`` grid, no host-side partial reduction)
  with the O(S·nb) selection epilogue (suffix widths) as jnp inside the
  same jit, so the whole op is a single dispatch.

Suffix-width contract: given per-segment sound value bounds
``vmin_s/vmax_s`` (the pending intervals of the tiles, in FOLD ORDER),
``w[s, b] = cnt[s, b] · (vmax_s[s] − vmin_s[s])`` is the per-bin CI
width tile s still contributes while unfolded, and
``suffix_w[s] = Σ_{s' ≥ s} w[s']`` (shape ``(S+1, nb)``, last row
exactly zero) is the residual width after folding the first s tiles —
the quantity the refinement driver's stopping rule consumes. Computed
as a reversed cumsum, not total − prefix: the f32/f64 subtraction would
leave ≈+ε at s = S where the exact-method (φ=0) selection must see 0.

The MULTI-window family (``segment_window_bin_select_multi_*``) is the
serving tick's variant: one dispatch where segment s is masked and
binned by its OWN window (one packed pass answers many concurrent
viewports) and the suffix widths are per-QUERY-SPAN
(:func:`segmented_suffix`). Its jnp oracle bins through the
host-precomputed params of ``ref.window_bin_params`` and its Pallas
backend through the compare thresholds of
``ref.window_bin_thresholds``; both make the device counts/extrema
bit-identical to the f64 host mirror, which lets the serving tick leave
the host path without breaking the grouped accumulator's exact count
cross-check.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .segment_agg import DEFAULT_BLOCK_ROWS, grouped_agg_pallas

NEG = -3.4e38
POS = 3.4e38


# --------------------------------------------------------------------- #
# shared jnp selection primitives (the SPMD fused path and the oracle
# are these same expressions — bit-for-bit)
# --------------------------------------------------------------------- #

def window_bin_ids(xs, ys, window, bx: int, by: int):
    """jnp mirror of ``ref.window_bin_ids_np``: ``(in_window_mask,
    bin_id)`` of the bx×by heatmap grid laid over the closed query
    window; bin id = by_row·bx + bx_col, closed-max-edge objects
    clipped into the last bin."""
    qx0, qy0, qx1, qy1 = window[0], window[1], window[2], window[3]
    m = ((xs >= qx0) & (xs <= qx1) & (ys >= qy0) & (ys <= qy1))
    cw = jnp.maximum((qx1 - qx0) / bx, 1e-30)
    ch = jnp.maximum((qy1 - qy0) / by, 1e-30)
    wx = jnp.clip(jnp.floor((xs - qx0) / cw).astype(jnp.int32), 0, bx - 1)
    wy = jnp.clip(jnp.floor((ys - qy0) / ch).astype(jnp.int32), 0, by - 1)
    return m, wy * bx + wx


def window_bin_ids_params(xs, ys, params, bx: int, by: int):
    """Axis-index binning from host-precomputed contract params — the
    device side of ``ref.window_bin_params``.

    ``params`` is the per-object (already gathered) ``(..., 6)`` f32
    row ``(x0, y0, x1, y1, cw, ch)``; the mask and
    ``clip(floor((x − x0) / cw))`` here are plain IEEE f32 ops, so on
    float32 coordinates the result is BIT-IDENTICAL to
    ``ref.window_bin_ids_np`` (see the contract note there: the cell
    sizes must come from the host's f64 derivation, never recomputed
    from f32 window coords in-kernel)."""
    x0, y0 = params[..., 0], params[..., 1]
    x1, y1 = params[..., 2], params[..., 3]
    cw, ch = params[..., 4], params[..., 5]
    m = (xs >= x0) & (xs <= x1) & (ys >= y0) & (ys <= y1)
    wx = jnp.clip(jnp.floor((xs - x0) / cw).astype(jnp.int32), 0, bx - 1)
    wy = jnp.clip(jnp.floor((ys - y0) / ch).astype(jnp.int32), 0, by - 1)
    return m, wy * bx + wx


def fused_count_val(cell, xs, ys, vals, window, cap: int, nb: int,
                    bx: int, by: int, agg: str,
                    neg: float = NEG, pos: float = POS):
    """One-pass classify + per-(tile, bin) grouped scatter — the fused
    data plane of a selection step, pre-merge.

    Window classification, bin assignment, and the masked binned
    scatter keyed by the persistent ``cell`` ids happen in one
    expression over the local objects; returns the flat ``(cap·nb,)``
    count and value (sum / grouped min / grouped max) tables for the
    caller to psum/pmin/pmax across shards. ``nb = bx·by = 1``
    degenerates to the scalar query's per-tile scatter (``key ≡
    cell``), so one primitive serves both session steps. Masked-out
    objects contribute the channel-neutral element (0, or the ±3.4e38
    scatter sentinel for extrema — f32-finite so pmin/pmax stay
    NaN-safe)."""
    assert agg in ("sum", "min", "max"), agg
    inq, wid = window_bin_ids(xs, ys, window, bx, by)
    vf = vals.astype(jnp.float32)
    key = cell * nb + wid
    cnt = jnp.zeros((cap * nb,), jnp.float32).at[key].add(
        jnp.where(inq, 1.0, 0.0))
    if agg == "sum":
        val = jnp.zeros((cap * nb,), jnp.float32).at[key].add(
            jnp.where(inq, vf, 0.0))
    elif agg == "min":
        val = jnp.full((cap * nb,), pos, jnp.float32).at[key].min(
            jnp.where(inq, vf, pos))
    else:
        val = jnp.full((cap * nb,), neg, jnp.float32).at[key].max(
            jnp.where(inq, vf, neg))
    return cnt, val


def suffix_residual(width_sorted, agg: str = "sum"):
    """Selection-ready suffix statistics over a score-sorted width
    matrix ``(T[, nb])``: residual per-bin CI width if the first j rows
    are processed, shape ``(T+1[, nb])`` with row T exactly zero.

    ``agg="sum"`` → reversed cumsum (widths add); min/max → reversed
    running max (an unprocessed tile leaves at most its value-range
    width on every bin it touches). Reversed scan, not total − prefix:
    the f32 subtraction leaves ≈+ε at j = T and φ=0 would then select
    nothing."""
    zrow = jnp.zeros((1,) + width_sorted.shape[1:], width_sorted.dtype)
    if agg == "sum":
        suf = jnp.cumsum(width_sorted[::-1], axis=0)[::-1]
    else:
        suf = jax.lax.cummax(width_sorted, axis=0, reverse=True)
    return jnp.concatenate([suf, zrow])


def segmented_suffix(w, qend):
    """Per-query-span inclusive suffix widths over a packed width matrix
    ``(S[, nb])``: row s is the summed residual width of rows
    ``s .. end(s)−1`` of s's OWN query span, where ``qend[s]`` is the
    (exclusive) end row of the span containing s.

    The multi-query epilogue: a serving tick packs several queries'
    fold-ordered (query, tile) segments into one stream, and each
    query's stopping rule wants the suffix over ITS segments only.
    Computed as one global reversed cumsum minus the gathered span-tail
    (f32 — the device epilogue is allclose to, not bit-equal with, the
    np mirror's per-span reversed cumsum; consumers append the
    exactly-zero terminal row themselves, it is never the result of a
    subtraction)."""
    suf = jnp.cumsum(w[::-1], axis=0)[::-1]
    pad = jnp.concatenate(
        [suf, jnp.zeros((1,) + w.shape[1:], w.dtype)])
    return suf - pad[qend]


# --------------------------------------------------------------------- #
# f64 host mirror (the RefinementDriver's control plane)
# --------------------------------------------------------------------- #

def segment_window_bin_select_np(xs, ys, vals, boundaries, window,
                                 bx: int, by: int, vmin_s, vmax_s):
    """Fused host pass: grouped table + selection suffix widths.

    The table is BIT-FOR-BIT ``ref.segment_window_bin_agg_np`` (the
    sequential per-tile f64 reference the batched rounds must match);
    the suffix widths are derived from its count channel and the
    fold-order pending intervals ``vmin_s/vmax_s`` per the module
    contract. Returns ``(agg (S, bx·by, 4) f64, suffix_w (S+1, bx·by)
    f64)``."""
    agg = ref.segment_window_bin_agg_np(xs, ys, vals, boundaries,
                                        window, bx, by)
    dv = (np.asarray(vmax_s, np.float64)
          - np.asarray(vmin_s, np.float64))[:, None]
    w = agg[:, :, 0] * dv
    suffix_w = np.concatenate(
        [np.cumsum(w[::-1], axis=0)[::-1],
         np.zeros((1, bx * by), np.float64)])
    return agg, suffix_w


def segment_window_bin_select_multi_np(xs, ys, vals, boundaries, windows,
                                       bx: int, by: int, vmin_s, vmax_s,
                                       qbounds=None):
    """Multi-window fused host pass: per-segment OWN-window grouped
    table + per-QUERY-SPAN selection suffix widths.

    The table is ``ref.segment_window_bin_agg_multi_np`` — per segment
    bit-for-bit the single-window sorted-slice f64 reference.
    ``qbounds`` (``(n_q+1,)`` segment offsets, default one span) cuts
    the fold-ordered segments into per-query spans; ``suffix_w`` is
    ``(S, bx·by)`` f64 where row s is the residual width over rows
    ``s..end−1`` of s's own span — each span's rows are BIT-FOR-BIT the
    first L rows a single-query :func:`segment_window_bin_select_np`
    would produce over the same stream (same f64 reversed cumsum over
    the same widths; consumers append the literal zero terminal row).
    Returns ``(agg (S, bx·by, 4) f64, suffix_w (S, bx·by) f64)``."""
    agg = ref.segment_window_bin_agg_multi_np(xs, ys, vals, boundaries,
                                              windows, bx, by)
    n_seg = agg.shape[0]
    dv = (np.asarray(vmax_s, np.float64)
          - np.asarray(vmin_s, np.float64))[:, None]
    w = agg[:, :, 0] * dv
    qb = (np.array([0, n_seg], np.int64) if qbounds is None
          else np.asarray(qbounds, np.int64))
    suffix_w = np.empty_like(w)
    for q in range(len(qb) - 1):
        a, b = int(qb[q]), int(qb[q + 1])
        if b > a:
            suffix_w[a:b] = np.cumsum(w[a:b][::-1], axis=0)[::-1]
    return agg, suffix_w


# --------------------------------------------------------------------- #
# jnp oracle
# --------------------------------------------------------------------- #

def segment_window_bin_select_ref(xs, ys, vals, sids, window, grid,
                                  valid, n_seg, vmin_s, vmax_s):
    """jnp oracle of the fused op: grouped table via the scatter oracle
    + the same suffix-width epilogue in f32. Returns ``(agg (S, k, 4),
    suffix_w (S+1, k))``."""
    agg = ref.segment_window_bin_agg_ref(xs, ys, vals, sids, window,
                                         grid, valid, n_seg)
    w = agg[:, :, 0] * (vmax_s - vmin_s)[:, None]
    return agg, suffix_residual(w, "sum")


def segment_window_bin_select_multi_ref(xs, ys, vals, sids, params, grid,
                                        valid, n_seg, vmin_s, vmax_s,
                                        qend):
    """jnp oracle of the MULTI-window fused op: every segment masked and
    binned by its own window via the gathered contract params
    (``ref.window_bin_params`` rows — NOT the rescaled-float binning of
    ``ref.segment_window_bin_agg_multi_ref``, so counts/extrema are
    bit-identical to the host mirror), plus the per-span suffix-width
    epilogue. ``qend`` is the per-segment exclusive span end (see
    :func:`segmented_suffix`). Returns ``(agg (S, k, 4),
    suffix_w (S, k))`` f32."""
    bx, by = grid
    sid_c, _ = ref._seg_key(sids, 0, n_seg, 1)
    p = params[sid_c]
    m, cid = window_bin_ids_params(xs, ys, p, bx, by)
    if valid is not None:
        m = m & valid
    agg = ref.segment_bin_agg4(sids, cid, vals, m, n_seg, bx * by)
    w = agg[:, :, 0] * (vmax_s - vmin_s)[:, None]
    return agg, segmented_suffix(w, qend)


# --------------------------------------------------------------------- #
# the Pallas megakernel: grouped table + selection epilogue, one jit
# --------------------------------------------------------------------- #

@functools.partial(jax.jit,
                   static_argnames=("n_seg", "bx", "by", "block_rows",
                                    "seg_group", "interpret"))
def segment_window_bin_select_pallas(xs2d, ys2d, vals2d, sid2d, valid2d,
                                     params, vmin_s, vmax_s, *, n_seg,
                                     bx, by,
                                     block_rows=DEFAULT_BLOCK_ROWS,
                                     seg_group=None, interpret=False):
    """Single-dispatch fused select: the per-(segment, window-bin)
    table of ``segment_agg.grouped_agg_pallas`` + the O(S·nb) jnp
    suffix-width epilogue in one jit. ``params`` is the
    ``ref.window_bin_thresholds`` row of the window, one per segment.
    Returns ``(agg (S, bx·by, 4), suffix_w (S+1, bx·by))`` float32."""
    agg = grouped_agg_pallas(params, xs2d, ys2d, vals2d, sid2d, valid2d,
                             n_seg=n_seg, window=True, gx=bx, gy=by,
                             block_rows=block_rows, seg_group=seg_group,
                             interpret=interpret)
    w = agg[:, :, 0] * (vmax_s - vmin_s)[:, None]
    return agg, suffix_residual(w, "sum")


@functools.partial(jax.jit,
                   static_argnames=("n_seg", "bx", "by", "block_rows",
                                    "seg_group", "interpret"))
def segment_window_bin_select_multi_pallas(xs2d, ys2d, vals2d, sid2d,
                                           valid2d, params, vmin_s,
                                           vmax_s, qend, *, n_seg, bx,
                                           by,
                                           block_rows=DEFAULT_BLOCK_ROWS,
                                           seg_group=None,
                                           interpret=False):
    """Single-dispatch multi-window fused select — the serving tick's
    kernel: segment s masked AND binned by its OWN window (row s of
    ``params``, ``ref.window_bin_thresholds``), plus the per-query-span
    :func:`segmented_suffix` epilogue in one jit. Returns
    ``(agg (S, bx·by, 4), suffix_w (S, bx·by))`` float32."""
    agg = grouped_agg_pallas(params, xs2d, ys2d, vals2d, sid2d, valid2d,
                             n_seg=n_seg, window=True, gx=bx, gy=by,
                             block_rows=block_rows, seg_group=seg_group,
                             interpret=interpret)
    w = agg[:, :, 0] * (vmax_s - vmin_s)[:, None]
    return agg, segmented_suffix(w, qend)
