"""Pure-jnp oracles for the Pallas kernels (+ NumPy host mirrors).

These are the semantic ground truth: every Pallas kernel in this package
must match its oracle bit-for-bit (up to float accumulation order) across
the shape/dtype sweeps in ``tests/test_kernels.py``. The ``*_np`` host
mirrors at the bottom serve the index's control plane: float64, segment
slices accumulated with numpy's pairwise summation — bit-for-bit the
arithmetic of the sequential per-tile path the batched pipeline replaces.

Conventions shared with the kernels:

- Object blocks are laid out 2-D ``(rows, 128)`` (TPU lane width). A
  1-D object array of length N is padded to a multiple of ``rows*128``
  and reshaped; padding entries carry ``valid=False``.
- A *window* is ``(x0, y0, x1, y1)`` with half-open semantics on the
  max edge for interior tiles; the caller controls closedness via the
  ``closed_max`` flag folded into the window representation (we use
  closed ``<=`` on both edges, matching the paper's object-selection
  semantics where a query region is a closed rectangle).
- Aggregates are ``(count, sum, min, max)`` stacked on the last axis.
  Empty selections yield ``count=0, sum=0, min=+inf, max=-inf``.

The grouped oracles aggregate via :func:`scatter_agg4` — one shared
grouped-reduction primitive — rather than a per-cell masked-reduction
Python loop. The old loop re-streamed the operands once per cell (S·K
passes: the 0.40 GB/s ``bin_agg_jnp`` row the kernels bench used to
show, and seconds per call at the 4096-cell grouped-table shapes).
``scatter_agg4`` picks its strategy from the STATIC cell count: small
tables use a vectorized ``(cells, n)`` broadcast reduction (XLA:CPU
fuses it into one pass per channel; scatter on XLA:CPU lowers to a
serialized update loop ~30× slower at these sizes), large tables use
true ``.at[key].add/min/max`` scatters — O(n) regardless of cell count,
and the fast path on TPU where scatter is hardware-supported. The
BINNING formulas (clip-binning, edge ownership, window bin ids) are
unchanged — bit-parity with the f64 np mirrors' binning contract is what
the grouped accumulator's exact count bookkeeping rests on; only the
order of float32 sum accumulation differs (counts and extrema are
order-exact under any order).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

AGG_FIELDS = ("count", "sum", "min", "max")

# Strategy crossover for scatter_agg4: below this cell count the
# broadcast reduction (~0.8 ms/cell on XLA:CPU at 256K rows) beats the
# flat ~24 ms serialized XLA:CPU scatter; above it scatter wins (and on
# TPU scatter is fast at every size — the broadcast path is only ever
# a CPU-oracle optimization, never a semantics change).
SCATTER_MIN_CELLS = 32


def scatter_agg4(key, vals, mask, n_cells):
    """Per-cell (count, sum, min, max) grouped reduction.

    ``key`` (int, any shape) assigns each object a cell in [0, n_cells);
    objects with ``mask=False`` contribute the channel-neutral element
    (0 for count/sum, ±inf for min/max) so their landing cell is
    irrelevant — callers still clip ``key`` into range for well-defined
    scatter semantics. ``mask=None`` means every object is live (the
    full-array fast path: skips the mask stream entirely). ``n_cells``
    is static. Returns float32 ``(n_cells, 4)``.
    """
    key = key.ravel()
    vm = vals.astype(jnp.float32).ravel()
    m = None if mask is None else mask.ravel()
    if n_cells <= SCATTER_MIN_CELLS:
        # fold the mask into one int8 class stream (masked-out -> the
        # out-of-range sentinel cell): each per-cell sweep then reads a
        # 1-byte class plane instead of a 4-byte key + bool mask — the
        # sweeps are bandwidth-bound, so the narrower stream is ~30%
        # of the grouped-oracle wall time at 200K rows
        if m is None:
            cls = key.astype(jnp.int8)
        else:
            cls = jnp.where(m, key.astype(jnp.int8), jnp.int8(n_cells))
        mc = cls[None, :] == jnp.arange(n_cells, dtype=jnp.int8)[:, None]
        # count+sum share ONE sweep as a complex64 reduction: complex
        # add is an independent pair of f32 adds, so the real part is
        # exactly the count and the imag part is bit-for-bit the f32
        # sum the two separate reductions would produce
        cs = jnp.sum(jnp.where(
            mc, jax.lax.complex(jnp.float32(1.0), vm)[None, :],
            jnp.complex64(0)), axis=1)
        cnt = jnp.real(cs)
        s = jnp.imag(cs)
        mn = jnp.min(jnp.where(mc, vm[None, :], jnp.inf), axis=1)
        mx = jnp.max(jnp.where(mc, vm[None, :], -jnp.inf), axis=1)
    else:
        w1 = 1.0 if m is None else jnp.where(m, 1.0, 0.0)
        ws = vm if m is None else jnp.where(m, vm, 0.0)
        wlo = vm if m is None else jnp.where(m, vm, jnp.inf)
        whi = vm if m is None else jnp.where(m, vm, -jnp.inf)
        cnt = jnp.zeros((n_cells,), jnp.float32).at[key].add(w1)
        s = jnp.zeros((n_cells,), jnp.float32).at[key].add(ws)
        mn = jnp.full((n_cells,), jnp.inf, jnp.float32).at[key].min(wlo)
        mx = jnp.full((n_cells,), -jnp.inf, jnp.float32).at[key].max(whi)
    return jnp.stack([cnt, s, mn, mx], axis=-1)


def segment_bin_agg4(sids, cid, vals, mask, n_seg, k):
    """Keyed (segment × bin) grouped reduction: float32 ``(n_seg, k, 4)``.

    The flat ``scatter_agg4`` treats ``sid·k + cid`` as an opaque cell
    id, so its broadcast path pays ``n_seg·k`` full-stream sweeps for
    EVERY channel — the 0.09 GB/s ``fused_select_jnp`` row at 16 cells.
    Here the masked-reduction trick is ported to the keyed case, using
    the product structure of the key:

    - **count + sum** contract two small one-hots instead of sweeping
      cells: a ``(n_seg, n)`` segment one-hot against a masked
      ``(2k, n)`` bin stream (bin indicators + bin-masked values), one
      ``(n_seg, n) @ (n, 2k)`` matmul — traffic scales with
      ``n_seg + 2k``, not ``n_seg·k``. Counts stay order-exact (0/1
      products, integer-exact below 2**24); only the f32 sum
      accumulation order changes (GEMM vs sweep — same contract as any
      backend switch). Masked-out values are zeroed BEFORE the product
      so non-finite padding can't leak NaN through ``0·inf``.
    - **min / max** have no linear structure, so they keep the
      ``scatter_agg4`` class-stream sweep (int8 sentinel class plane +
      one masked reduction per channel) over the flat cells.

    Out-of-range segment ids are masked out here (callers pass the raw
    ``sids`` plane, not a pre-clipped key). Above ``SCATTER_MIN_CELLS``
    flat cells the true-scatter path wins and is used unchanged.
    """
    sid_i = sids.astype(jnp.int32).ravel()
    cid = cid.ravel()
    vm = vals.astype(jnp.float32).ravel()
    inrange = (sid_i >= 0) & (sid_i < n_seg)
    m = inrange if mask is None else (mask.ravel() & inrange)
    sid_c = jnp.clip(sid_i, 0, n_seg - 1)
    cells = n_seg * k
    if cells > SCATTER_MIN_CELLS:
        return scatter_agg4(sid_c * k + cid, vm, m, cells).reshape(
            n_seg, k, 4)
    seg_oh = (sid_c[None, :] == jnp.arange(n_seg, dtype=jnp.int32)[:, None]
              ).astype(jnp.float32)
    bin_oh = ((cid[None, :] == jnp.arange(k, dtype=jnp.int32)[:, None])
              & m[None, :]).astype(jnp.float32)
    vz = jnp.where(m, vm, jnp.float32(0))
    stream = jnp.concatenate([bin_oh, bin_oh * vz[None, :]], axis=0)
    cs = seg_oh @ stream.T                       # (n_seg, 2k)
    cnt, s = cs[:, :k], cs[:, k:]
    cls = jnp.where(m, (sid_c * k + cid).astype(jnp.int8), jnp.int8(cells))
    mc = cls[None, :] == jnp.arange(cells, dtype=jnp.int8)[:, None]
    mn = jnp.min(jnp.where(mc, vm[None, :], jnp.inf), axis=1).reshape(
        n_seg, k)
    mx = jnp.max(jnp.where(mc, vm[None, :], -jnp.inf), axis=1).reshape(
        n_seg, k)
    return jnp.stack([cnt, s, mn, mx], axis=-1)


def _seg_key(sids, cid, n_seg, k):
    """Scatter key ``sid·k + cid`` with out-of-range segment ids masked
    out (the loop oracles simply never matched them)."""
    sid_i = sids.astype(jnp.int32)
    inrange = (sid_i >= 0) & (sid_i < n_seg)
    key = jnp.clip(sid_i, 0, n_seg - 1) * k + cid
    return key, inrange


def window_mask(xs, ys, window, valid):
    """Boolean mask of objects inside the closed window (``valid=None``
    means every object is live — skips the validity stream)."""
    x0, y0, x1, y1 = window[0], window[1], window[2], window[3]
    m = (xs >= x0) & (xs <= x1) & (ys >= y0) & (ys <= y1)
    return m if valid is None else m & valid


def window_agg_ref(xs, ys, vals, window, valid):
    """(count, sum, min, max) of ``vals`` over objects inside ``window``.

    Shapes: xs/ys/vals/valid are broadcast-compatible arrays (any shape);
    window is a length-4 vector. Returns a float32 vector of 4 values
    (count is returned as float32 for a homogeneous result layout; it is
    exactly representable for counts < 2**24, and the callers re-derive
    exact integer counts on the host path).
    """
    m = window_mask(xs, ys, window, valid)
    vm = vals.astype(jnp.float32)
    cnt = jnp.sum(m, dtype=jnp.float32)
    s = jnp.sum(jnp.where(m, vm, 0.0), dtype=jnp.float32)
    mn = jnp.min(jnp.where(m, vm, jnp.inf))
    mx = jnp.max(jnp.where(m, vm, -jnp.inf))
    return jnp.stack([cnt, s, mn, mx])


def bin_agg_ref(xs, ys, vals, bbox, grid, valid):
    """Per-cell (count, sum, min, max) over a ``gx × gy`` grid of ``bbox``.

    bbox = (x0, y0, x1, y1); cells are equal-sized. Binning is pure
    clipping — every valid object lands in exactly one cell, including
    objects that sit on (or float-jitter epsilon past) the bbox edges.
    This matches the index's ownership rule: callers pass a tile's owned
    object segment and the split must partition it exactly (an
    inside-test would silently drop edge objects from child metadata
    while the counting sort still assigns them — unsound min/max).
    Returns ``(gx*gy, 4)`` float32; cell id = cy * gx + cx.
    """
    gx, gy = grid
    x0, y0, x1, y1 = bbox[0], bbox[1], bbox[2], bbox[3]
    cw = (x1 - x0) / gx
    ch = (y1 - y0) / gy
    cx = jnp.clip(jnp.floor((xs - x0) / cw).astype(jnp.int32), 0, gx - 1)
    cy = jnp.clip(jnp.floor((ys - y0) / ch).astype(jnp.int32), 0, gy - 1)
    cid = cy * gx + cx
    return scatter_agg4(cid, vals, valid, gx * gy)  # valid=None ok


def segment_window_agg_ref(xs, ys, vals, sids, window, valid, n_seg):
    """Per-segment (count, sum, min, max) inside ``window``.

    ``sids`` assigns each object a segment id in [0, n_seg); n_seg is
    static. Returns float32 ``(n_seg, 4)``.
    """
    m = window_mask(xs, ys, window, valid)
    key, inrange = _seg_key(sids, 0, n_seg, 1)
    return scatter_agg4(key, vals, m & inrange, n_seg)


def segment_window_bin_agg_ref(xs, ys, vals, sids, window, grid, valid,
                               n_seg):
    """Per-segment, per-bin aggregates over the WINDOW's own bx×by grid.

    The heatmap primitive: unlike :func:`segment_bin_agg_ref` (each
    segment binned by its own bbox, every object owned), here every
    segment is binned by ONE shared grid laid over the query window and
    only objects inside the closed window contribute. Returns float32
    ``(n_seg, bx*by, 4)``; bin id = by_row * bx + bx_col.
    """
    bx, by = grid
    m = window_mask(xs, ys, window, valid)
    x0, y0 = window[0], window[1]
    cw = jnp.maximum((window[2] - window[0]) / bx, 1e-30)
    ch = jnp.maximum((window[3] - window[1]) / by, 1e-30)
    cx = jnp.clip(jnp.floor((xs - x0) / cw).astype(jnp.int32), 0, bx - 1)
    cy = jnp.clip(jnp.floor((ys - y0) / ch).astype(jnp.int32), 0, by - 1)
    cid = cy * bx + cx
    return segment_bin_agg4(sids, cid, vals, m, n_seg, bx * by)


def segment_window_agg_multi_ref(xs, ys, vals, sids, windows, valid,
                                 n_seg):
    """Per-segment (count, sum, min, max), each segment under its OWN
    window — the multi-query serving primitive.

    Like :func:`segment_window_agg_ref` but ``windows`` is ``(n_seg, 4)``
    and segment s selects against ``windows[s]``: one packed pass
    answers one (query, tile) stream per segment for MANY concurrent
    queries with different viewports. Returns float32 ``(n_seg, 4)``.
    """
    key, inrange = _seg_key(sids, 0, n_seg, 1)
    w = windows[key]  # per-object gathered window, (..., 4)
    m = window_mask(xs, ys,
                    (w[..., 0], w[..., 1], w[..., 2], w[..., 3]),
                    valid)
    return scatter_agg4(key, vals, m & inrange, n_seg)


def segment_window_bin_agg_multi_ref(xs, ys, vals, sids, windows, grid,
                                     valid, n_seg):
    """Per-segment, per-bin aggregates; segment s binned by the bx×by
    grid of its OWN window ``windows[s]`` — the multi-query heatmap
    serving primitive (one shared (bx, by) per call; the scheduler
    groups same-bin-shape queries into a pass). Returns float32
    ``(n_seg, bx*by, 4)``; bin id = by_row * bx + bx_col.
    """
    bx, by = grid
    k = bx * by
    sid_c, inrange = _seg_key(sids, 0, n_seg, 1)
    w = windows[sid_c]  # per-object gathered window, (..., 4)
    m = window_mask(xs, ys,
                    (w[..., 0], w[..., 1], w[..., 2], w[..., 3]),
                    valid)
    x0, y0 = w[..., 0], w[..., 1]
    cw = jnp.maximum((w[..., 2] - w[..., 0]) / bx, 1e-30)
    ch = jnp.maximum((w[..., 3] - w[..., 1]) / by, 1e-30)
    cx = jnp.clip(jnp.floor((xs - x0) / cw).astype(jnp.int32), 0, bx - 1)
    cy = jnp.clip(jnp.floor((ys - y0) / ch).astype(jnp.int32), 0, by - 1)
    cid = cy * bx + cx
    return segment_bin_agg4(sids, cid, vals, m, n_seg, k)


def segment_bin_agg_edges_ref(xs, ys, vals, sids, x_edges, y_edges, valid,
                              n_seg):
    """Per-segment, per-cell aggregates under per-segment SPLIT EDGES.

    The bin-aligned-split primitive: instead of the even gx×gy grid of
    each segment's bbox (:func:`segment_bin_agg_ref`), segment s is cut
    along its own explicit edge arrays ``x_edges[s]`` (gx+1,) /
    ``y_edges[s]`` (gy+1,) — e.g. snapped to a heatmap bin grid. Cell
    ownership: child cx owns ``[x_edges[s, cx], x_edges[s, cx+1])``,
    objects past the outer edges are clamped into the boundary cells
    (``cx = Σ_i 1[x ≥ x_edges[s, i]]`` over interior edges — every valid
    object lands in exactly one cell). Returns float32
    ``(n_seg, gx*gy, 4)``; cell id = cy*gx + cx.
    """
    gx = x_edges.shape[1] - 1
    gy = y_edges.shape[1] - 1
    k = gx * gy
    sid_c, inrange = _seg_key(sids, 0, n_seg, 1)
    xe = x_edges[sid_c]  # per-object gathered edges, (..., gx+1)
    ye = y_edges[sid_c]
    cx = jnp.zeros(xs.shape, jnp.int32)
    for i in range(1, gx):
        cx = cx + (xs >= xe[..., i]).astype(jnp.int32)
    cy = jnp.zeros(ys.shape, jnp.int32)
    for i in range(1, gy):
        cy = cy + (ys >= ye[..., i]).astype(jnp.int32)
    cid = cy * gx + cx
    return segment_bin_agg4(sids, cid, vals, valid, n_seg, k)


def segment_bin_agg_ref(xs, ys, vals, sids, bboxes, grid, valid, n_seg):
    """Per-segment, per-cell aggregates; segment s binned by bboxes[s].

    Returns float32 ``(n_seg, gx*gy, 4)``; cell id = cy*gx + cx.
    """
    gx, gy = grid
    k = gx * gy
    sid_c, inrange = _seg_key(sids, 0, n_seg, 1)
    bb = bboxes[sid_c]  # per-object gathered bbox, (..., 4)
    x0, y0 = bb[..., 0], bb[..., 1]
    cw = jnp.maximum((bb[..., 2] - bb[..., 0]) / gx, 1e-30)
    ch = jnp.maximum((bb[..., 3] - bb[..., 1]) / gy, 1e-30)
    cx = jnp.clip(jnp.floor((xs - x0) / cw).astype(jnp.int32), 0, gx - 1)
    cy = jnp.clip(jnp.floor((ys - y0) / ch).astype(jnp.int32), 0, gy - 1)
    cid = cy * gx + cx
    return segment_bin_agg4(sids, cid, vals, valid, n_seg, k)


# --------------------------------------------------------------------- #
# NumPy host mirrors (the index's control plane).
#
# Segments are CONTIGUOUS here — described by a boundaries vector rather
# than a sid plane — and sums accumulate in float64 with numpy's pairwise
# algorithm over each segment slice, which makes these mirrors bit-for-bit
# identical to the sequential per-tile host path they replace.
# --------------------------------------------------------------------- #

def segment_window_agg_np(xs, ys, vals, boundaries, window):
    """Per-contiguous-segment (count, sum, min, max) inside ``window``.

    ``boundaries``: int ``(S+1,)``; segment s owns
    ``[boundaries[s], boundaries[s+1])``. Returns float64 ``(S, 4)``;
    empty selection ⇒ (0, 0, +inf, -inf).
    """
    xs, ys = np.asarray(xs), np.asarray(ys)
    vals = np.asarray(vals, np.float32)
    n_seg = len(boundaries) - 1
    x0, y0, x1, y1 = window
    # all-covering window (enrichment stats): segment slices ARE the
    # selection — skip the mask and its boolean-indexing copies
    covers_all = (x0 == -np.inf and y0 == -np.inf
                  and x1 == np.inf and y1 == np.inf)
    if not covers_all:
        m = (xs >= x0) & (xs <= x1) & (ys >= y0) & (ys <= y1)
    out = np.empty((n_seg, 4), np.float64)
    for s in range(n_seg):
        a, b = int(boundaries[s]), int(boundaries[s + 1])
        sel = vals[a:b] if covers_all else vals[a:b][m[a:b]]
        if sel.size:
            out[s] = (sel.size, sel.sum(dtype=np.float64),
                      sel.min(), sel.max())
        else:
            out[s] = (0, 0.0, np.inf, -np.inf)
    return out


def segment_bin_agg_np(xs, ys, vals, boundaries, bboxes, gx, gy):
    """Per-contiguous-segment, per-cell aggregates (float64 ``(S,K,4)``)."""
    xs, ys = np.asarray(xs), np.asarray(ys)
    vals = np.asarray(vals, np.float32)
    bboxes = np.asarray(bboxes, np.float64)
    n_seg = len(boundaries) - 1
    k = gx * gy
    sid = np.repeat(np.arange(n_seg), np.diff(boundaries))
    cw = np.maximum((bboxes[:, 2] - bboxes[:, 0]) / gx, 1e-30)
    ch = np.maximum((bboxes[:, 3] - bboxes[:, 1]) / gy, 1e-30)
    cx = np.clip(np.floor((xs - bboxes[sid, 0]) / cw[sid]).astype(np.int64),
                 0, gx - 1)
    cy = np.clip(np.floor((ys - bboxes[sid, 1]) / ch[sid]).astype(np.int64),
                 0, gy - 1)
    key = sid * k + cy * gx + cx
    order = np.argsort(key, kind="stable")
    vs_sorted = vals[order]
    cell_bounds = np.searchsorted(key[order], np.arange(n_seg * k + 1))
    out = np.empty((n_seg * k, 4), np.float64)
    for c in range(n_seg * k):
        a, b = cell_bounds[c], cell_bounds[c + 1]
        if b > a:
            seg = vs_sorted[a:b]
            out[c] = (b - a, seg.sum(dtype=np.float64), seg.min(), seg.max())
        else:
            out[c] = (0, 0.0, np.inf, -np.inf)
    return out.reshape(n_seg, k, 4)


def edge_cell_ids_np(xs, ys, x_edges, y_edges, sid):
    """THE host ownership rule for explicit (bin-aligned) split edges.

    Child cx of segment s owns ``[x_edges[s, cx], x_edges[s, cx+1])``
    (``cx = Σ_i 1[x ≥ edge_i]`` over interior edges, f64 comparisons);
    points past the outer edges clamp into the boundary cells, so every
    object lands in exactly one cell. This single implementation serves
    both the index's segment reorganization
    (``core.geometry.edge_cell_ids_segmented`` delegates here) and the
    child-metadata mirror below — they MUST agree bit-for-bit or
    reorganized segments desynchronize from their metadata.
    ``x_edges``/``y_edges`` are ``(S, gx+1)`` / ``(S, gy+1)``; ``sid``
    maps each object to its segment row. Returns cell id = cy*gx + cx.
    """
    x_edges = np.asarray(x_edges, np.float64)
    y_edges = np.asarray(y_edges, np.float64)
    gx = x_edges.shape[1] - 1
    gy = y_edges.shape[1] - 1
    cx = (xs[:, None] >= x_edges[sid][:, 1:-1]).sum(axis=1) \
        if gx > 1 else np.zeros(len(xs), np.int64)
    cy = (ys[:, None] >= y_edges[sid][:, 1:-1]).sum(axis=1) \
        if gy > 1 else np.zeros(len(ys), np.int64)
    return cy * gx + cx


def segment_bin_agg_edges_np(xs, ys, vals, boundaries, x_edges, y_edges):
    """Per-contiguous-segment, per-cell aggregates under per-segment
    split edges (f64 ``(S, K, 4)``) — host mirror of
    :func:`segment_bin_agg_edges_ref` in the contiguous layout.

    Cell ids come from :func:`edge_cell_ids_np` — the one host
    ownership rule, shared with the index's segment reorganization —
    and each cell's sum accumulates its own sorted slice in float64, so
    a k-segment call is bit-for-bit the concatenation of k
    single-segment calls (the sequential split path the batched
    multi-tile split replaces).
    """
    xs, ys = np.asarray(xs), np.asarray(ys)
    vals = np.asarray(vals, np.float32)
    x_edges = np.asarray(x_edges, np.float64)
    y_edges = np.asarray(y_edges, np.float64)
    n_seg = len(boundaries) - 1
    gx = x_edges.shape[1] - 1
    gy = y_edges.shape[1] - 1
    k = gx * gy
    sid = np.repeat(np.arange(n_seg), np.diff(boundaries))
    key = sid * k + edge_cell_ids_np(xs, ys, x_edges, y_edges, sid)
    order = np.argsort(key, kind="stable")
    vs_sorted = vals[order]
    cell_bounds = np.searchsorted(key[order], np.arange(n_seg * k + 1))
    out = np.empty((n_seg * k, 4), np.float64)
    for c in range(n_seg * k):
        a, b = cell_bounds[c], cell_bounds[c + 1]
        if b > a:
            seg = vs_sorted[a:b]
            out[c] = (b - a, seg.sum(dtype=np.float64), seg.min(), seg.max())
        else:
            out[c] = (0, 0.0, np.inf, -np.inf)
    return out.reshape(n_seg, k, 4)


def segment_window_agg_multi_np(xs, ys, vals, boundaries, windows):
    """Per-contiguous-segment (count, sum, min, max), each segment under
    its OWN window (f64 ``(S, 4)``).

    Host mirror of :func:`segment_window_agg_multi_ref` in the
    contiguous layout. Delegates each segment's slice to
    :func:`segment_window_agg_np`, so segment s's row is BIT-FOR-BIT
    what a single-window call over the same stream produces — the
    serving scheduler's packed pass answers each query exactly as that
    query's own per-query round would.
    """
    windows = np.asarray(windows, np.float64)
    n_seg = len(boundaries) - 1
    out = np.empty((n_seg, 4), np.float64)
    two = np.array([0, 0], np.int64)
    for s in range(n_seg):
        a, b = int(boundaries[s]), int(boundaries[s + 1])
        two[1] = b - a
        out[s] = segment_window_agg_np(xs[a:b], ys[a:b], vals[a:b],
                                       two, windows[s])[0]
    return out


def segment_window_bin_agg_multi_np(xs, ys, vals, boundaries, windows,
                                    bx, by):
    """Per-contiguous-segment, per-bin aggregates, each segment binned
    by the bx×by grid of its OWN window (f64 ``(S, bx*by, 4)``).

    Host mirror of :func:`segment_window_bin_agg_multi_ref` in the
    contiguous layout; per segment it is bit-for-bit a single-window
    :func:`segment_window_bin_agg_np` call over the same stream (same
    per-cell sorted-slice f64 accumulation), which is what lets the
    serving layer's micro-batched heatmap pass equal the per-query
    reference exactly.
    """
    windows = np.asarray(windows, np.float64)
    n_seg = len(boundaries) - 1
    k = bx * by
    out = np.empty((n_seg, k, 4), np.float64)
    two = np.array([0, 0], np.int64)
    for s in range(n_seg):
        a, b = int(boundaries[s]), int(boundaries[s + 1])
        two[1] = b - a
        out[s] = segment_window_bin_agg_np(xs[a:b], ys[a:b], vals[a:b],
                                           two, windows[s], bx, by)[0]
    return out


def window_bin_ids_np(xs, ys, window, bx, by):
    """Host binning rule of a heatmap window: ``(in_window_mask, bin_id)``.

    The ONE formula both the pending-tile per-bin counts (axis index, no
    file I/O) and the processed per-bin contributions
    (:func:`segment_window_bin_agg_np`) are derived from — they must
    agree bit-for-bit or the grouped accumulator's count cross-check
    fails. Bin id = by_row * bx + bx_col; objects on the closed max edge
    are clipped into the last bin (every selected object lands in
    exactly one bin).
    """
    x0, y0, x1, y1 = (float(window[0]), float(window[1]),
                      float(window[2]), float(window[3]))
    m = (xs >= x0) & (xs <= x1) & (ys >= y0) & (ys <= y1)
    cw = max((x1 - x0) / bx, 1e-30)
    ch = max((y1 - y0) / by, 1e-30)
    cx = np.clip(np.floor((xs - x0) / cw).astype(np.int64), 0, bx - 1)
    cy = np.clip(np.floor((ys - y0) / ch).astype(np.int64), 0, by - 1)
    return m, cy * bx + cx


def window_bin_params(windows, bx, by):
    """Per-window axis-index binning parameters for the DEVICE kernels:
    float32 ``(S, 6)`` rows ``(x0, y0, x1, y1, cw, ch)``.

    THE binning contract. :func:`window_bin_ids_np` runs on float32
    coordinates, so NumPy-2 weak promotion demotes its python-float
    window scalars to f32 at every op — the mask compares and the
    ``floor((x - x0) / cw)`` arithmetic are all f32 — but the cell
    sizes ``cw/ch`` are derived in f64 FIRST and only then rounded.  A
    kernel that recomputes ``(x1 - x0) / bx`` from f32 window coords
    (the rescaled-float binning of the single-window kernels) rounds
    differently and can land edge objects in the neighbouring bin.
    Device kernels must instead take these host-precomputed params and
    bin with ``clip(floor((x - x0) / cw), 0, bx-1)``: IEEE f32
    subtract/divide/floor round identically under numpy and XLA, so the
    device mask and bin ids are BIT-IDENTICAL to the host rule.
    """
    windows = np.asarray(windows, np.float64).reshape(-1, 4)
    out = np.empty((len(windows), 6), np.float32)
    out[:, :4] = windows
    out[:, 4] = np.maximum((windows[:, 2] - windows[:, 0]) / bx, 1e-30)
    out[:, 5] = np.maximum((windows[:, 3] - windows[:, 1]) / by, 1e-30)
    return out


def _f32_key(f):
    """float32 → int64 key, monotone in the float order (±0 → 0)."""
    i = np.asarray(f, np.float32).view(np.int32).astype(np.int64)
    return np.where(i < 0, -(i & 0x7FFFFFFF), i)


def _f32_from_key(k):
    k = np.asarray(k, np.int64)
    bits = np.where(k < 0, (-k) | 0x80000000, k).astype(np.uint32)
    return bits.view(np.float32)


def f32_thresholds(pred, shape):
    """Smallest float32 ``t`` (per element of ``shape``) with
    ``pred(t)`` true, for a predicate monotone in float32 ``x`` —
    bisection over the ordered float32 bit patterns from -inf to +inf
    (33 vectorized evaluations, exact whatever the arithmetic inside
    ``pred``)."""
    lo = np.full(shape, _f32_key(np.float32(-np.inf)) - 1, np.int64)
    hi = np.full(shape, _f32_key(np.float32(np.inf)), np.int64)
    while (hi - lo > 1).any():
        mid = (lo + hi) // 2
        p = np.asarray(pred(_f32_from_key(mid)), bool)
        hi = np.where(p, mid, hi)
        lo = np.where(p, lo, mid)
    return _f32_from_key(hi)


def _axis_thresholds(x0, cw, g, dtype):
    """Interior cell thresholds ``(S, g−1)`` of the rule
    ``clip(floor((x − x0) / cw), 0, g−1)`` evaluated in ``dtype``: cell
    ``cx = Σ_j 1[x ≥ t_j]`` for every float32 ``x``."""
    j = np.arange(1, g, dtype=np.float64)[None, :]
    x0 = np.asarray(x0, dtype)[:, None]
    cw = np.asarray(cw, dtype)[:, None]
    shape = (x0.shape[0], g - 1)

    def pred(t):
        with np.errstate(invalid="ignore", over="ignore"):
            return np.floor((t.astype(dtype) - x0) / cw) >= j
    return f32_thresholds(pred, shape)


def window_bin_thresholds(windows, bx, by):
    """THE device binning contract for heatmap windows: float32
    ``(S, 4 + (bx−1) + (by−1))`` rows ``(x0, y0, x1, y1, tx…, ty…)``.

    The window corners are the float32 values the host mask compares
    against; ``tx_j``/``ty_j`` are the smallest float32 coordinates that
    :func:`window_bin_ids_np` puts in bin column / row ≥ j (its f32
    ``floor((x − x0) / cw)`` with ``cw`` derived in f64 first). A
    kernel that bins by ``Σ_j 1[x ≥ t_j]`` needs no division, so its
    bin ids are BIT-IDENTICAL to the host rule on any hardware — a
    TPU's vector divide is not correctly rounded, an f32 compare is."""
    windows = np.asarray(windows, np.float64).reshape(-1, 4)
    cw = np.maximum((windows[:, 2] - windows[:, 0]) / bx, 1e-30)
    ch = np.maximum((windows[:, 3] - windows[:, 1]) / by, 1e-30)
    return np.concatenate([
        windows.astype(np.float32),
        _axis_thresholds(windows[:, 0], cw, bx, np.float32),
        _axis_thresholds(windows[:, 1], ch, by, np.float32)], axis=1)


def split_thresholds(bboxes, gx, gy):
    """Device form of the even-split ownership rule of
    :func:`segment_bin_agg_np` (f64 ``floor((x − b0) / cw)`` per
    bbox): float32 ``(S, (gx−1) + (gy−1))`` rows ``(tx…, ty…)``."""
    bboxes = np.asarray(bboxes, np.float64).reshape(-1, 4)
    cw = np.maximum((bboxes[:, 2] - bboxes[:, 0]) / gx, 1e-30)
    ch = np.maximum((bboxes[:, 3] - bboxes[:, 1]) / gy, 1e-30)
    return np.concatenate([
        _axis_thresholds(bboxes[:, 0], cw, gx, np.float64),
        _axis_thresholds(bboxes[:, 1], ch, gy, np.float64)], axis=1)


def edge_thresholds(x_edges, y_edges):
    """Device form of :func:`edge_cell_ids_np`: the interior split
    edges rounded UP to float32 (``x ≥ e`` in f64 ⇔ ``x ≥ ceil32(e)``
    for float32 ``x``). Returns ``(S, (gx−1) + (gy−1))`` float32."""
    inner = np.concatenate([np.asarray(x_edges, np.float64)[:, 1:-1],
                            np.asarray(y_edges, np.float64)[:, 1:-1]],
                           axis=1)
    return f32_thresholds(lambda t: t.astype(np.float64) >= inner,
                          inner.shape)


def segment_window_bin_agg_np(xs, ys, vals, boundaries, window, bx, by):
    """Per-contiguous-segment, per-window-bin aggregates (f64 ``(S,K,4)``).

    Host mirror of :func:`segment_window_bin_agg_ref` in the contiguous
    layout. Each (segment, bin) cell's sum accumulates the cell's own
    sorted slice in float64 — per-cell arithmetic is independent of the
    batch composition, so a k-segment call is bit-for-bit the
    concatenation of k single-segment calls (the sequential heatmap
    reference path).
    """
    xs, ys = np.asarray(xs), np.asarray(ys)
    vals = np.asarray(vals, np.float32)
    n_seg = len(boundaries) - 1
    k = bx * by
    m, cid = window_bin_ids_np(xs, ys, window, bx, by)
    sid = np.repeat(np.arange(n_seg), np.diff(boundaries))
    # out-of-window objects go to a sentinel key past every real cell
    key = np.where(m, sid * k + cid, n_seg * k)
    order = np.argsort(key, kind="stable")
    vs_sorted = vals[order]
    cell_bounds = np.searchsorted(key[order], np.arange(n_seg * k + 1))
    out = np.empty((n_seg * k, 4), np.float64)
    for c in range(n_seg * k):
        a, b = cell_bounds[c], cell_bounds[c + 1]
        if b > a:
            seg = vs_sorted[a:b]
            out[c] = (b - a, seg.sum(dtype=np.float64), seg.min(), seg.max())
        else:
            out[c] = (0, 0.0, np.inf, -np.inf)
    return out.reshape(n_seg, k, 4)

