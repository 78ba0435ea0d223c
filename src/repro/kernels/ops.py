"""Public jit'd wrappers around the data-plane kernel.

Backends:
- ``"pallas"`` — the one grouped Pallas kernel
  (:mod:`repro.kernels.segment_agg`). Compiled for the chip on TPU;
  elsewhere it runs in the Pallas interpreter (the kernel body evaluated
  by XLA:CPU) for correctness validation. ``interpret=None`` resolves
  from the platform (:func:`resolve_interpret`), so no caller gets the
  interpreter on a TPU unless it asks for it.
- ``"jnp"`` — the pure-jnp oracle from :mod:`repro.kernels.ref`,
  jit-compiled by XLA.
- ``"np"`` — the f64 NumPy host mirrors: the sequential references the
  batched and device paths are tested against.

``default_backend()`` ("pallas" on TPU, "jnp" elsewhere) is what an op
uses when called without ``backend=``; :func:`index_backend` ("pallas"
on TPU, "np" elsewhere) is what a ``TileIndex`` uses when its
``IndexConfig.backend`` is unset. Every op takes an explicit
``backend=`` override so tests can pin each backend and compare them.

All ops accept flat 1-D object arrays and handle the (rows, 128) padding
layout internally. The Pallas paths take their per-segment scalars from
host-derived tables (``ref.window_bin_thresholds``,
``ref.split_thresholds``, ``ref.edge_thresholds``), so device cell
assignment is bit-identical to the host mirrors' ownership rules.
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from . import fused_select
from . import ref
from .segment_agg import LANES, DEFAULT_BLOCK_ROWS, grouped_agg_pallas
from ..spans import (INIT_BIN, INIT_PERMUTE, INIT_SORT, KERNEL_DISPATCH,
                     KERNEL_PACK, span)

PLANES = 4          # object planes of a packed call: x, y, value, segment id
F32 = 4


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_backend() -> str:
    """Device data plane of a bare op call: "pallas" on TPU, "jnp"
    elsewhere."""
    return "pallas" if on_tpu() else "jnp"


def index_backend() -> str:
    """Data plane of a ``TileIndex`` whose config leaves ``backend``
    unset: the compiled Pallas kernel on TPU, the f64 "np" mirror
    elsewhere (on a CPU host the mirror avoids per-shape XLA recompiles
    of data-dependent segment lengths, and it is the bit-for-bit
    sequential reference)."""
    return "pallas" if on_tpu() else "np"


def resolve_interpret(interpret=None) -> bool:
    """``None`` → compiled on TPU, interpreted elsewhere."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def _window_agg_np(xs, ys, vals, window, n):
    xs, ys = np.asarray(xs)[:n], np.asarray(ys)[:n]
    vals = np.asarray(vals, np.float32)[:n]
    x0, y0, x1, y1 = np.asarray(window, np.float32)
    m = (xs >= x0) & (xs <= x1) & (ys >= y0) & (ys <= y1)
    sel = vals[m]
    if sel.size == 0:
        return np.array([0.0, 0.0, np.inf, -np.inf], np.float32)
    return np.array([m.sum(), sel.sum(dtype=np.float64), sel.min(),
                     sel.max()], np.float32)


def _bin_agg_np(xs, ys, vals, bbox, gx, gy, n):
    xs, ys = np.asarray(xs)[:n], np.asarray(ys)[:n]
    vals = np.asarray(vals, np.float32)[:n]
    x0, y0, x1, y1 = np.asarray(bbox, np.float64)
    # pure clip-binning — see kernels/ref.py: every object must land in
    # exactly one cell or split metadata goes unsound on edge objects
    cw = max((x1 - x0) / gx, 1e-30)
    ch = max((y1 - y0) / gy, 1e-30)
    cx = np.clip(np.floor((xs - x0) / cw).astype(np.int64), 0, gx - 1)
    cy = np.clip(np.floor((ys - y0) / ch).astype(np.int64), 0, gy - 1)
    cid = cy * gx + cx
    k = gx * gy
    cnt = np.bincount(cid, minlength=k + 1)[:k].astype(np.float32)
    s = np.bincount(cid, weights=vals.astype(np.float64),
                    minlength=k + 1)[:k].astype(np.float32)
    mn = np.full(k, np.inf, np.float32)
    mx = np.full(k, -np.inf, np.float32)
    order = np.argsort(cid, kind="stable")
    cs, vs_sorted = cid[order], vals[order]
    bounds = np.searchsorted(cs, np.arange(k + 1))
    for c in range(k):
        a, b = bounds[c], bounds[c + 1]
        if b > a:
            mn[c] = vs_sorted[a:b].min()
            mx[c] = vs_sorted[a:b].max()
    return np.stack([cnt, s, mn, mx], axis=-1)


def _dev(a):
    """Prepare a (possibly large) host array for a jit'd call.

    Passing NumPy float32 directly lets jit's own device_put alias the
    host buffer on CPU (zero copy); an eager ``jnp.asarray`` here costs a
    separate synchronous dispatch per array — measured ~4 ms of the old
    6 ms ``bin_agg`` jnp wall time at 200K rows. Device arrays pass
    through untouched.
    """
    return a if isinstance(a, jax.Array) else np.asarray(a, np.float32)


def _pad_to_blocks(n: int, block_rows: int) -> int:
    per = block_rows * LANES
    return max(per, ((n + per - 1) // per) * per)


def pack2d(*arrays, n=None, block_rows=DEFAULT_BLOCK_ROWS):
    """Pad 1-D arrays to the (rows, 128) kernel layout + validity plane."""
    n = len(arrays[0]) if n is None else n
    padded = _pad_to_blocks(n, block_rows)
    rows = padded // LANES
    outs = []
    for a in arrays:
        buf = jnp.zeros((padded,), jnp.float32).at[:n].set(
            jnp.asarray(a, jnp.float32))
        outs.append(buf.reshape(rows, LANES))
    valid = (jnp.arange(padded) < n).astype(jnp.int8).reshape(rows, LANES)
    return (*outs, valid)


def _packed(xs, ys, vals, sids, n):
    """(traced) pack2d planes with the tail beyond ``n`` masked off."""
    planes = (xs, ys, vals) if sids is None else (xs, ys, vals, sids)
    *p2, valid2 = pack2d(*planes, n=xs.shape[0])
    valid2 = valid2 * (jnp.arange(valid2.size).reshape(valid2.shape) <
                       n).astype(jnp.int8)
    return p2[0], p2[1], p2[2], (None if sids is None else p2[3]), valid2


@functools.partial(jax.jit,
                   static_argnames=("n_seg", "window", "gx", "gy",
                                    "interpret", "seg_group"))
def _pallas_flat(xs, ys, vals, sids, params, n, n_seg, window, gx, gy,
                 interpret, seg_group=None):
    xs2, ys2, vs2, sid2, valid2 = _packed(xs, ys, vals, sids, n)
    return grouped_agg_pallas(params, xs2, ys2, vs2, sid2, valid2,
                              n_seg=n_seg, window=window, gx=gx, gy=gy,
                              seg_group=seg_group, interpret=interpret)


def object_bucket(n: int) -> int:
    """Objects of a packed call, padded: the next power of two, at least
    1024 (one compiled kernel per bucket, not per length)."""
    return max(1024, 1 << (max(int(n), 1) - 1).bit_length())


def segment_bucket(n_seg: int) -> int:
    """Segments of a packed call on the grouped kernel, padded: the next
    power of two."""
    return 1 << (int(n_seg) - 1).bit_length()


def kernel_cols(window: bool, gx: int = 1, gy: int = 1) -> int:
    """Float32 scalars per segment of the grouped kernel: the window's
    corners when a window masks the segment, then ``gx - 1`` x- and
    ``gy - 1`` y-thresholds."""
    return (4 if window else 0) + (gx - 1) + (gy - 1)


def h2d_bytes(backend: str, n: int, n_seg: int = 1, cols: int = 4, *,
              stream: bool = True) -> int:
    """Bytes of host arrays one op call hands a device kernel.

    A packed segment call (``stream``) ships ``PLANES`` float32 object
    planes padded to :func:`object_bucket` and a float32 table of
    ``cols`` scalars per segment, padded to :func:`segment_bucket`: the
    grouped kernel's layout (``kernel_cols``), which the "jnp" backend is
    counted by too. ``window_agg``/``bin_agg`` (``stream=False``) ship
    x, y and value unpadded and one table row. The "np" mirror ships
    nothing."""
    if backend == "np":
        return 0
    if not stream:
        return F32 * (3 * int(n) + cols)
    return F32 * (PLANES * object_bucket(n) + segment_bucket(n_seg) * cols)


def _seg_bucket(n_seg: int, *tables):
    """Zero-pad a packed call's per-segment tables to its
    :func:`segment_bucket`. Padded segments hold no objects, so their
    rows come back empty and are sliced off."""
    pad = segment_bucket(n_seg)
    out = []
    for t in tables:
        t = np.asarray(t)
        out.append(np.concatenate(
            [t, np.zeros((pad - n_seg,) + t.shape[1:], t.dtype)]))
    return pad, out


def _pallas_table(params, n_seg: int):
    """The grouped kernel's per-segment scalar table (``(n_seg, ncol)``
    f32), padded to the segment bucket."""
    params = np.asarray(params, np.float32).reshape(n_seg, -1)
    return _seg_bucket(n_seg, params)[1][0]


def _pallas(xs, ys, vals, sids, table, n, *, n_seg, window, gx=1, gy=1,
            interpret=None, seg_group=None):
    """Dispatch of the grouped kernel on a :func:`_pallas_table`; the
    first ``n_seg`` rows of its output."""
    return _pallas_flat(xs, ys, vals, sids, table, int(n), table.shape[0],
                        window, gx, gy, resolve_interpret(interpret),
                        seg_group)[:n_seg]


def _segment_stream(xs, ys, vals, boundaries):
    """Bucket-padded flat arrays + per-object segment ids of a
    concatenated segment stream: ``(xs, ys, vals, sids, n, n_seg)``."""
    n_seg = len(boundaries) - 1
    n = int(boundaries[-1])
    sids = np.repeat(np.arange(n_seg), np.diff(boundaries))
    xs, ys, vals, sids = _bucket_pad(xs, ys, vals, sids, n=n)
    return xs, ys, vals, sids, n, n_seg


def _window_row(window):
    return np.asarray(window, np.float32).reshape(1, 4)


@functools.partial(jax.jit, static_argnames=("full",))
def _window_agg_jnp(xs, ys, vals, window, n, full=False):
    # full=True: the caller passed n=None (whole array live) — skip the
    # validity stream, the sweeps are bandwidth-bound
    valid = None if full else jnp.arange(xs.shape[0]) < n
    return ref.window_agg_ref(xs, ys, vals, window, valid)


def window_agg(xs, ys, vals, window, *, n=None, backend=None,
               interpret=None):
    """(count, sum, min, max) of ``vals`` for objects in the closed window.

    ``n``: logical length (entries past n are ignored) — lets callers pass
    padded fixed-capacity segments without re-slicing under jit.
    """
    backend = backend or default_backend()
    if backend == "np":
        n = len(xs) if n is None else int(n)
        return _window_agg_np(xs, ys, vals, window, n)
    full = n is None
    with span(KERNEL_PACK):
        xs, ys, vals = _dev(xs), _dev(ys), _dev(vals)
        n = xs.shape[0] if n is None else n
        table = (np.asarray(window, np.float32) if backend == "jnp"
                 else _pallas_table(_window_row(window), 1))
    with span(KERNEL_DISPATCH):
        if backend == "jnp":
            return _window_agg_jnp(xs, ys, vals, table, int(n), full=full)
        return _pallas(xs, ys, vals, None, table, n, n_seg=1, window=True,
                       interpret=interpret).reshape(4)


@functools.partial(jax.jit, static_argnames=("gx", "gy", "full"))
def _bin_agg_jnp(xs, ys, vals, bbox, n, gx, gy, full=False):
    valid = None if full else jnp.arange(xs.shape[0]) < n
    return ref.bin_agg_ref(xs, ys, vals, bbox, (gx, gy), valid)


def bin_agg(xs, ys, vals, bbox, *, gx, gy, n=None, backend=None,
            interpret=None):
    """Per-cell (count, sum, min, max) over a gx×gy split of bbox."""
    backend = backend or default_backend()
    if backend == "np":
        n = len(xs) if n is None else int(n)
        return _bin_agg_np(xs, ys, vals, bbox, gx, gy, n)
    full = n is None
    with span(KERNEL_PACK):
        xs, ys, vals = _dev(xs), _dev(ys), _dev(vals)
        n = xs.shape[0] if n is None else n
        table = (np.asarray(bbox, np.float32) if backend == "jnp" else
                 _pallas_table(ref.split_thresholds(
                     np.asarray(bbox)[None], gx, gy), 1))
    with span(KERNEL_DISPATCH):
        if backend == "jnp":
            return _bin_agg_jnp(xs, ys, vals, table, int(n), gx, gy,
                                full=full)
        return _pallas(xs, ys, vals, None, table, n, n_seg=1,
                       window=False, gx=gx, gy=gy, interpret=interpret)[0]


def _bucket_pad(*arrays, n):
    """Pad flat host arrays to :func:`object_bucket` to bound recompiles."""
    cap = object_bucket(n)
    out = []
    for a in arrays:
        buf = np.zeros(cap, np.float32)
        buf[:n] = np.asarray(a, np.float32)[:n]
        out.append(buf)
    return out


@functools.partial(jax.jit, static_argnames=("n_seg",))
def _segment_window_agg_jnp(xs, ys, vals, sids, window, n, n_seg):
    valid = jnp.arange(xs.shape[0]) < n
    return ref.segment_window_agg_ref(xs, ys, vals, sids, window, valid,
                                      n_seg)


def segment_window_agg(xs, ys, vals, boundaries, window, *, backend=None,
                       interpret=None):
    """Per-segment (count, sum, min, max) inside the closed ``window``.

    The batched-adaptation primitive: ``xs/ys/vals`` are the CONCATENATED
    object segments of one refinement batch; ``boundaries`` (int, (S+1,))
    delimits segment s as ``[boundaries[s], boundaries[s+1])``. One call
    replaces S per-tile ``window_agg`` invocations. An all-covering window
    (±inf edges) yields full-segment aggregates (tile enrichment).

    The "np" backend returns float64 with numpy pairwise summation per
    segment slice — bit-for-bit the sequential host path; "jnp"/"pallas"
    return float32 from one packed device kernel.
    """
    backend = backend or default_backend()
    boundaries = np.asarray(boundaries, np.int64)
    if backend == "np":
        return ref.segment_window_agg_np(xs, ys, vals, boundaries, window)
    with span(KERNEL_PACK):
        xs, ys, vals, sids, n, n_seg = _segment_stream(xs, ys, vals,
                                                       boundaries)
        table = (np.asarray(window, np.float32) if backend == "jnp" else
                 _pallas_table(np.broadcast_to(_window_row(window),
                                               (n_seg, 4)), n_seg))
    with span(KERNEL_DISPATCH):
        if backend == "jnp":
            return _segment_window_agg_jnp(xs, ys, vals, sids, table, n,
                                           n_seg)
        return _pallas(xs, ys, vals, sids, table, n, n_seg=n_seg,
                       window=True, interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("n_seg", "gx", "gy"))
def _segment_bin_agg_jnp(xs, ys, vals, sids, bboxes, n, n_seg, gx, gy):
    valid = jnp.arange(xs.shape[0]) < n
    return ref.segment_bin_agg_ref(xs, ys, vals, sids, bboxes, (gx, gy),
                                   valid, n_seg)


def segment_bin_agg(xs, ys, vals, boundaries, bboxes, *, gx, gy,
                    backend=None, interpret=None):
    """Per-segment, per-cell (count, sum, min, max): one packed call that
    splits every segment s of the concatenated stream by its own
    ``bboxes[s]`` into ``gx × gy`` cells — the multi-tile-split metadata
    kernel. Returns ``(S, gx*gy, 4)``; cell id = cy*gx + cx. Backend
    semantics as in :func:`segment_window_agg`; "pallas" assigns cells
    by the f64 host rule (``ref.split_thresholds``).
    """
    backend = backend or default_backend()
    boundaries = np.asarray(boundaries, np.int64)
    if backend == "np":
        return ref.segment_bin_agg_np(xs, ys, vals, boundaries, bboxes,
                                      gx, gy)
    with span(KERNEL_PACK):
        xs, ys, vals, sids, n, n_seg = _segment_stream(xs, ys, vals,
                                                       boundaries)
        table = (np.asarray(bboxes, np.float32) if backend == "jnp" else
                 _pallas_table(ref.split_thresholds(bboxes, gx, gy), n_seg))
    with span(KERNEL_DISPATCH):
        if backend == "jnp":
            return _segment_bin_agg_jnp(xs, ys, vals, sids, table, n,
                                        n_seg, gx, gy)
        return _pallas(xs, ys, vals, sids, table, n, n_seg=n_seg,
                       window=False, gx=gx, gy=gy, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_seg",))
def _segment_bin_agg_edges_jnp(xs, ys, vals, sids, x_edges, y_edges, n,
                               n_seg):
    valid = jnp.arange(xs.shape[0]) < n
    return ref.segment_bin_agg_edges_ref(xs, ys, vals, sids, x_edges,
                                         y_edges, valid, n_seg)


def segment_bin_agg_edges(xs, ys, vals, boundaries, x_edges, y_edges, *,
                          backend=None, interpret=None):
    """Per-segment, per-cell (count, sum, min, max) under per-segment
    SPLIT EDGES: one packed call that cuts every segment s of the
    concatenated stream along its own ``x_edges[s]`` (gx+1,) /
    ``y_edges[s]`` (gy+1,) — the bin-aligned multi-tile-split metadata
    kernel (split lines snapped to a heatmap grid instead of the even
    gx×gy subdivision). Returns ``(S, gx*gy, 4)``; cell id = cy*gx + cx.
    Backend semantics as in :func:`segment_window_agg` ("np" ⇒ float64
    host mirror whose cell assignment matches
    ``geometry.edge_cell_ids`` bit-for-bit; "pallas" compares against
    the edges rounded up to float32, the same assignment).
    """
    backend = backend or default_backend()
    boundaries = np.asarray(boundaries, np.int64)
    x_edges = np.asarray(x_edges, np.float64)
    y_edges = np.asarray(y_edges, np.float64)
    if backend == "np":
        return ref.segment_bin_agg_edges_np(xs, ys, vals, boundaries,
                                            x_edges, y_edges)
    gx = x_edges.shape[1] - 1
    gy = y_edges.shape[1] - 1
    with span(KERNEL_PACK):
        xs, ys, vals, sids, n, n_seg = _segment_stream(xs, ys, vals,
                                                       boundaries)
        tables = ((np.asarray(x_edges, np.float32),
                   np.asarray(y_edges, np.float32)) if backend == "jnp" else
                  (_pallas_table(ref.edge_thresholds(x_edges, y_edges),
                                 n_seg),))
    with span(KERNEL_DISPATCH):
        if backend == "jnp":
            return _segment_bin_agg_edges_jnp(xs, ys, vals, sids, *tables,
                                              n, n_seg)
        return _pallas(xs, ys, vals, sids, tables[0], n, n_seg=n_seg,
                       window=False, gx=gx, gy=gy, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_seg", "bx", "by"))
def _segment_window_bin_agg_jnp(xs, ys, vals, sids, window, n, n_seg, bx,
                                by):
    valid = jnp.arange(xs.shape[0]) < n
    return ref.segment_window_bin_agg_ref(xs, ys, vals, sids, window,
                                          (bx, by), valid, n_seg)


def segment_window_bin_agg(xs, ys, vals, boundaries, window, *, bx, by,
                           backend=None, interpret=None):
    """Per-segment, per-window-bin (count, sum, min, max) — the heatmap
    primitive: one packed call bins every segment of the concatenated
    stream by the SAME ``bx × by`` grid over the (finite, closed) query
    window, in-window objects only. Returns ``(S, bx*by, 4)``;
    bin id = by_row*bx + bx_col. Backend semantics as in
    :func:`segment_window_agg` ("np" ⇒ float64 host mirror, bit-for-bit
    the sequential per-tile heatmap path).
    """
    backend = backend or default_backend()
    boundaries = np.asarray(boundaries, np.int64)
    if backend == "np":
        return ref.segment_window_bin_agg_np(xs, ys, vals, boundaries,
                                             window, bx, by)
    with span(KERNEL_PACK):
        xs, ys, vals, sids, n, n_seg = _segment_stream(xs, ys, vals,
                                                       boundaries)
        if backend == "jnp":
            table = np.asarray(window, np.float32)
        else:
            row = ref.window_bin_thresholds(np.asarray(window)[None], bx, by)
            table = _pallas_table(
                np.broadcast_to(row, (n_seg, row.shape[1])), n_seg)
    with span(KERNEL_DISPATCH):
        if backend == "jnp":
            return _segment_window_bin_agg_jnp(xs, ys, vals, sids, table, n,
                                               n_seg, bx, by)
        return _pallas(xs, ys, vals, sids, table, n, n_seg=n_seg,
                       window=True, gx=bx, gy=by, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_seg", "bx", "by"))
def _segment_window_bin_select_jnp(xs, ys, vals, sids, window, vmin_s,
                                   vmax_s, n, n_seg, bx, by):
    valid = jnp.arange(xs.shape[0]) < n
    return fused_select.segment_window_bin_select_ref(
        xs, ys, vals, sids, window, (bx, by), valid, n_seg, vmin_s, vmax_s)


@functools.partial(jax.jit,
                   static_argnames=("n_seg", "bx", "by", "interpret",
                                    "seg_group"))
def _segment_window_bin_select_pallas(xs, ys, vals, sids, params, vmin_s,
                                      vmax_s, n, n_seg, bx, by, interpret,
                                      seg_group=None):
    xs2, ys2, vs2, sid2, valid2 = _packed(xs, ys, vals, sids, n)
    return fused_select.segment_window_bin_select_pallas(
        xs2, ys2, vs2, sid2, valid2, params, vmin_s, vmax_s, n_seg=n_seg,
        bx=bx, by=by, seg_group=seg_group, interpret=interpret)


def segment_window_bin_select(xs, ys, vals, boundaries, window, vmin_s,
                              vmax_s, *, bx, by, backend=None,
                              interpret=None, seg_group=None):
    """Fused heatmap-selection primitive: per-segment per-window-bin
    ``(count, sum, min, max)`` PLUS the selection-ready suffix widths, in
    one pass.

    Like :func:`segment_window_bin_agg` with a selection epilogue:
    ``vmin_s/vmax_s`` are the per-segment sound value bounds (fold
    order), and the second return is ``suffix_w`` of shape
    ``(S+1, bx*by)`` — residual per-bin CI width after folding the first
    s segments (row S exactly zero). Returns ``(agg, suffix_w)``.
    Backend semantics as in :func:`segment_window_agg`: "np" is the f64
    host mirror whose ``agg`` is bit-for-bit
    ``segment_window_bin_agg(backend="np")``; "pallas" runs the
    :mod:`repro.kernels.fused_select` megakernel (2-D grid, in-kernel
    accumulation) with the suffix scan fused into the same dispatch.
    ``seg_group`` forces the megakernel's segments-per-program group
    (tests exercise the multi-group outer axis with it).
    """
    backend = backend or default_backend()
    boundaries = np.asarray(boundaries, np.int64)
    if backend == "np":
        return fused_select.segment_window_bin_select_np(
            xs, ys, vals, boundaries, window, bx, by, vmin_s, vmax_s)
    with span(KERNEL_PACK):
        xs, ys, vals, sids, n, n_seg = _segment_stream(xs, ys, vals,
                                                       boundaries)
        vmin_s = np.asarray(vmin_s, np.float32)
        vmax_s = np.asarray(vmax_s, np.float32)
        if backend == "pallas":
            row = ref.window_bin_thresholds(np.asarray(window)[None], bx, by)
            pad, (params, vmin_s, vmax_s) = _seg_bucket(
                n_seg, np.broadcast_to(row, (n_seg, row.shape[1])), vmin_s,
                vmax_s)
    with span(KERNEL_DISPATCH):
        if backend == "jnp":
            return _segment_window_bin_select_jnp(
                xs, ys, vals, sids, np.asarray(window, np.float32), vmin_s,
                vmax_s, n, n_seg, bx, by)
        agg, suffix_w = _segment_window_bin_select_pallas(
            xs, ys, vals, sids, params, vmin_s, vmax_s, n, pad, bx, by,
            resolve_interpret(interpret), seg_group)
        return agg[:n_seg], suffix_w[:n_seg + 1]


@functools.partial(jax.jit, static_argnames=("n_seg",))
def _segment_window_agg_multi_jnp(xs, ys, vals, sids, windows, n, n_seg):
    valid = jnp.arange(xs.shape[0]) < n
    return ref.segment_window_agg_multi_ref(xs, ys, vals, sids, windows,
                                            valid, n_seg)


def segment_window_agg_multi(xs, ys, vals, boundaries, windows, *,
                             backend=None, interpret=None):
    """Per-segment (count, sum, min, max) where segment s is filtered by
    its OWN closed ``windows[s]`` — the multi-query serving primitive:
    the concatenated (query, tile) streams of one serving tick answer N
    different viewports in a single packed kernel pass. ``windows`` is
    ``(S, 4)``. Backend semantics as in :func:`segment_window_agg`
    ("np" ⇒ float64 host mirror that delegates each segment slice to the
    single-window path, bit-for-bit the per-query sequential reference).
    """
    backend = backend or default_backend()
    boundaries = np.asarray(boundaries, np.int64)
    if backend == "np":
        return ref.segment_window_agg_multi_np(xs, ys, vals, boundaries,
                                               windows)
    with span(KERNEL_PACK):
        xs, ys, vals, sids, n, n_seg = _segment_stream(xs, ys, vals,
                                                       boundaries)
        windows = np.asarray(windows, np.float32).reshape(n_seg, 4)
        if backend == "pallas":
            windows = _pallas_table(windows, n_seg)
    with span(KERNEL_DISPATCH):
        if backend == "jnp":
            return _segment_window_agg_multi_jnp(xs, ys, vals, sids,
                                                 windows, n, n_seg)
        return _pallas(xs, ys, vals, sids, windows, n, n_seg=n_seg,
                       window=True, interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("n_seg", "bx", "by"))
def _segment_window_bin_agg_multi_jnp(xs, ys, vals, sids, windows, n,
                                      n_seg, bx, by):
    valid = jnp.arange(xs.shape[0]) < n
    return ref.segment_window_bin_agg_multi_ref(xs, ys, vals, sids,
                                                windows, (bx, by), valid,
                                                n_seg)


def segment_window_bin_agg_multi(xs, ys, vals, boundaries, windows, *, bx,
                                 by, backend=None, interpret=None):
    """Per-segment, per-bin (count, sum, min, max) where segment s is
    binned by the ``bx × by`` grid of its OWN window ``windows[s]`` — the
    multi-query heatmap serving primitive. All queries in the packed tick
    must share a bin resolution (bx, by); windows may differ freely.
    Returns ``(S, bx*by, 4)``. Backend semantics as in
    :func:`segment_window_agg_multi`.
    """
    backend = backend or default_backend()
    boundaries = np.asarray(boundaries, np.int64)
    if backend == "np":
        return ref.segment_window_bin_agg_multi_np(xs, ys, vals, boundaries,
                                                   windows, bx, by)
    with span(KERNEL_PACK):
        xs, ys, vals, sids, n, n_seg = _segment_stream(xs, ys, vals,
                                                       boundaries)
        table = (np.asarray(windows, np.float32) if backend == "jnp" else
                 _pallas_table(ref.window_bin_thresholds(windows, bx, by),
                               n_seg))
    with span(KERNEL_DISPATCH):
        if backend == "jnp":
            return _segment_window_bin_agg_multi_jnp(
                xs, ys, vals, sids, table, n, n_seg, bx, by)
        return _pallas(xs, ys, vals, sids, table, n, n_seg=n_seg,
                       window=True, gx=bx, gy=by, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_seg", "bx", "by"))
def _segment_window_bin_select_multi_jnp(xs, ys, vals, sids, params,
                                         vmin_s, vmax_s, qend, n, n_seg,
                                         bx, by):
    valid = jnp.arange(xs.shape[0]) < n
    return fused_select.segment_window_bin_select_multi_ref(
        xs, ys, vals, sids, params, (bx, by), valid, n_seg, vmin_s, vmax_s,
        qend)


@functools.partial(jax.jit,
                   static_argnames=("n_seg", "bx", "by", "interpret",
                                    "seg_group"))
def _segment_window_bin_select_multi_pallas(xs, ys, vals, sids, params,
                                            vmin_s, vmax_s, qend, n,
                                            n_seg, bx, by, interpret,
                                            seg_group=None):
    xs2, ys2, vs2, sid2, valid2 = _packed(xs, ys, vals, sids, n)
    return fused_select.segment_window_bin_select_multi_pallas(
        xs2, ys2, vs2, sid2, valid2, params, vmin_s, vmax_s, qend,
        n_seg=n_seg, bx=bx, by=by, seg_group=seg_group,
        interpret=interpret)


def segment_window_bin_select_multi(xs, ys, vals, boundaries, windows,
                                    vmin_s, vmax_s, qbounds=None, *, bx,
                                    by, backend=None, interpret=None,
                                    seg_group=None):
    """Multi-window fused heatmap-selection primitive: per-segment
    OWN-window per-bin ``(count, sum, min, max)`` PLUS per-query-span
    selection suffix widths, in one pass — the serving tick's kernel.

    :func:`segment_window_bin_agg_multi` with the selection epilogue of
    :func:`segment_window_bin_select` fused in: ``windows`` is
    ``(S, 4)`` (segment s masked and binned by its own window),
    ``vmin_s/vmax_s`` are the per-segment sound value bounds in fold
    order, and ``qbounds`` (``(n_q+1,)`` segment offsets, default one
    span) cuts the packed segments into per-query spans. The second
    return is ``suffix_w`` of shape ``(S, bx·by)`` — row s is the
    residual per-bin CI width over the remaining UNFOLDED segments of
    s's own span; each consumer appends its span's literal zero
    terminal row (the φ=0 selection must see exact 0, never a
    subtraction residue). Backend semantics as in
    :func:`segment_window_agg_multi`: "np" is the f64 host mirror whose
    ``agg`` is bit-for-bit ``segment_window_bin_agg_multi
    (backend="np")`` and whose span rows match the single-window
    ``segment_window_bin_select(backend="np")``; the device backends
    bin by the host contract (``ref.window_bin_params`` for "jnp",
    ``ref.window_bin_thresholds`` for "pallas") so counts/extrema stay
    bit-identical to the host rule (f32 sums/suffixes allclose).
    ``seg_group`` forces the megakernel's segments-per-program group.
    """
    backend = backend or default_backend()
    boundaries = np.asarray(boundaries, np.int64)
    if backend == "np":
        return fused_select.segment_window_bin_select_multi_np(
            xs, ys, vals, boundaries, windows, bx, by, vmin_s, vmax_s,
            qbounds)
    with span(KERNEL_PACK):
        n_seg = len(boundaries) - 1
        qb = (np.array([0, n_seg], np.int64) if qbounds is None
              else np.asarray(qbounds, np.int64))
        qend = np.repeat(qb[1:], np.diff(qb)).astype(np.int32)
        xs, ys, vals, sids, n, n_seg = _segment_stream(xs, ys, vals,
                                                       boundaries)
        vmin_s = np.asarray(vmin_s, np.float32)
        vmax_s = np.asarray(vmax_s, np.float32)
        if backend == "jnp":
            params = ref.window_bin_params(windows, bx, by)
        else:
            pad, (params, vmin_s, vmax_s) = _seg_bucket(
                n_seg, ref.window_bin_thresholds(windows, bx, by), vmin_s,
                vmax_s)
            qend = np.concatenate([qend, np.full(pad - n_seg, pad,
                                                 np.int32)])
    with span(KERNEL_DISPATCH):
        if backend == "jnp":
            return _segment_window_bin_select_multi_jnp(
                xs, ys, vals, sids, params, vmin_s, vmax_s, qend, n, n_seg,
                bx, by)
        agg, suffix_w = _segment_window_bin_select_multi_pallas(
            xs, ys, vals, sids, params, vmin_s, vmax_s, qend, n, pad, bx,
            by, resolve_interpret(interpret), seg_group)
        return agg[:n_seg], suffix_w[:n_seg]


INIT_CHUNK_ROWS = 1 << 23    # rows per init-order program (32 MB a plane)


@functools.partial(jax.jit, static_argnames=("shift",))
def _init_keys(xs, ys, tx, ty, n, shift):
    """Sort keys ``cell << shift | row`` of one chunk, the cell by
    comparison with the host rule's thresholds; rows at or past ``n``
    (padding) take cell ``g``, after every real cell."""
    gx, gy = tx.shape[0] + 1, ty.shape[0] + 1
    cx = jnp.zeros(xs.shape, jnp.uint32)
    for t in tx:
        cx = cx + (xs >= t)
    cy = jnp.zeros(ys.shape, jnp.uint32)
    for t in ty:
        cy = cy + (ys >= t)
    rows = jax.lax.iota(jnp.uint32, xs.shape[0])
    cell = jnp.where(rows < n, cy * gx + cx, gx * gy)
    return (cell << shift) | rows


@functools.partial(jax.jit, static_argnames=("g", "shift"))
def _init_order(keys, g, shift):
    """One chunk's local rows (int32) in stable cell order, and each
    cell's first position (``g + 1`` starts). Keys are unique, so an
    unstable sort, which compiles several times faster on a TPU than a
    stable one, gives the stable order."""
    keys = jax.lax.sort(keys, is_stable=False)
    rows = (keys & ((1 << shift) - 1)).astype(jnp.int32)
    starts = jnp.searchsorted(
        keys, jnp.arange(g + 1, dtype=jnp.uint32) << shift)
    return rows, starts.astype(jnp.int32)


def init_order(x, y, tx, ty, *, chunk_rows=INIT_CHUNK_ROWS):
    """The init pass's stable cell order of float32 points:
    ``(perm, x_s, y_s, counts)`` as writable host arrays, bit-identical
    to ``argsort(bin_cell_ids(x, y, ...), kind="stable")`` and the axes
    permuted by it, given the rule's thresholds
    (``geometry.bin_thresholds``).

    The device bins and sorts the rows in chunks of at most
    ``chunk_rows`` (the last one padded to :func:`object_bucket`, so
    each size compiles once). With few cells, the global stable order
    is, cell by cell, that cell's run from chunk 0, then chunk 1, ...:
    the host copies each run's row ids into ``perm`` and gathers its
    axes from ``x``, ``y``, in several threads, as soon as the chunk's
    rows are in host memory. Every chunk is dispatched before the first
    wait, so transfers overlap the device's work. (On a TPU v5e,
    gathering the axes on the device took longer than the sort and
    tripled the bytes returned.)"""
    n = len(x)
    gx = len(tx) + 1
    g = gx * (len(ty) + 1)
    shift = 32 - g.bit_length()     # a uint32 key holds cell g and a row
    c = min(int(chunk_rows), 1 << shift)
    tx, ty = np.asarray(tx, np.float32), np.asarray(ty, np.float32)
    with span(INIT_BIN):
        keys = []
        for s in range(0, n, c):
            m = min(c, n - s)
            size = min(c, object_bucket(m))
            xs, ys = (np.asarray(a[s:s + m], np.float32) for a in (x, y))
            if m < size:
                xs, ys = (np.pad(a, (0, size - m)) for a in (xs, ys))
            keys.append(_init_keys(jax.device_put(xs), jax.device_put(ys),
                                   tx, ty, m, shift))
    with span(INIT_SORT):
        outs = [_init_order(k, g, shift) for k in keys]
        for out in outs:
            for a in out:
                a.copy_to_host_async()
        del keys
    with span(INIT_PERMUTE):
        starts = np.array([np.asarray(st) for _, st in outs],
                          np.int64).reshape(-1, g + 1)
        runs = np.diff(starts, axis=1)
        counts = runs.sum(axis=0)
        # first slot of each (chunk, cell) run: cell-major, then chunk
        flat = runs.T.ravel()
        dst = (np.cumsum(flat) - flat).reshape(g, -1).T
        perm = np.empty(n, np.int64)
        x_s, y_s = np.empty(n, np.float32), np.empty(n, np.float32)

        def copy(k, rows, cells):
            for cell in cells:
                i, j = starts[k, cell], starts[k, cell + 1]
                d = dst[k, cell]
                p = perm[d:d + j - i]
                np.add(rows[i:j], np.int64(k * c), out=p)
                # the rows are in range by construction; "raise" would
                # bounds-check them into a buffer and copy it again
                np.take(x, p, out=x_s[d:d + j - i], mode="clip")
                np.take(y, p, out=y_s[d:d + j - i], mode="clip")

        workers = min(8, os.cpu_count() or 1)
        groups = np.array_split(np.arange(g), workers)
        with ThreadPoolExecutor(workers) as pool:
            done = []
            for k, (rows, _) in enumerate(outs):
                rows = np.asarray(rows)
                done += [pool.submit(copy, k, rows, cells)
                         for cells in groups]
            for f in done:
                f.result()
    return perm, x_s, y_s, counts


def window_count(xs, ys, window, *, n=None, backend=None):
    """Count of objects in window (axis attributes only — no file access)."""
    agg = window_agg(xs, ys, jnp.zeros_like(jnp.asarray(xs, jnp.float32)),
                     window, n=n, backend=backend)
    return agg[0]


def window_mask_np(xs, ys, window):
    """NumPy host-side mask (control-plane helper, not a kernel)."""
    x0, y0, x1, y1 = window
    return (xs >= x0) & (xs <= x1) & (ys >= y0) & (ys <= y1)


__all__ = ["window_agg", "bin_agg", "segment_window_agg", "segment_bin_agg",
           "segment_bin_agg_edges", "segment_window_bin_agg",
           "segment_window_bin_select",
           "segment_window_agg_multi", "segment_window_bin_agg_multi",
           "segment_window_bin_select_multi",
           "window_count", "window_mask_np", "pack2d", "default_backend",
           "index_backend", "resolve_interpret", "object_bucket",
           "segment_bucket", "kernel_cols", "h2d_bytes", "init_order"]
