"""Distributed AQP engine: session-stateful partial adaptive indexing
on the production mesh.

Deployment model (DESIGN.md §2): the raw object store is sharded across
every chip (each device owns N/D objects in HBM — the in-situ "file").
What used to be a stateless per-query grid surrogate is now a
**sharded session state** (:class:`ShardedTileState`) that instantiates
the host index architecture on devices and *persists across queries*:

- ``cell`` — one per-object tile id, sharded over the mesh: the cracked
  assignment, the SPMD analog of the host index's object permutation.
  Refine epochs rewrite it in place, so query N+1 starts from query N's
  cracked grid instead of a fresh gx×gy surrogate;
- a replicated capacity-bounded tile table (bbox / active / level /
  count / sound value bounds) — the psum-merged per-tile metadata the
  paper's confidence intervals are built from;
- a per-(tile, bin) exact-state registry (:class:`GroupedCache`): the
  grouped in-window aggregates materialized by past reads under the
  session's current window. A repeated viewport answers previously-read
  tiles from this resident state at ZERO additional read cost — the
  session-amortization claim of the paper, at mesh scale.

One φ-constrained query — scalar (:func:`make_session_query_step`) or
heatmap (:func:`make_session_heatmap_step`) — is a fully-jitted SPMD
program with the same classify → score → fold shape as the host
:class:`~repro.core.refine.RefinementDriver`:

  1. per-device masked binned scatter over local objects keyed by the
     PERSISTENT ``cell`` ids (count/sum or grouped extrema per
     tile ∩ window ∩ bin), merged with ``psum``/``pmin``/``pmax``;
  2. classification of the tile table against the window (conservative,
     like host ``geometry.classify_tiles``); full tiles and tiles whose
     per-(tile, bin) exact state is cached contribute exactly; the rest
     become pending with intervals from the persistent value bounds;
  3. greedy partial processing, vectorized: tiles sorted by the paper's
     score; suffix scans over the sorted (tiles × bins) width matrix
     give every prefix's residual uncertainty at once; the smallest
     prefix whose **per-bin budgets** ``τ_b = max(φ_b·|v_b|, ε_abs)``
     are met is selected (the :class:`~repro.core.bounds.AccuracyPolicy`
     φ_b algebra, via the shared pure-array helpers in
     ``core.bounds``; the uniform policy reproduces the scalar-φ
     selection bit-for-bit);
  4. selected tiles' exact contributions replace their intervals, the
     per-bin bound is re-computed post-read in-SPMD, and the grouped
     exact state of everything read is written back to the cache.

Refinement is a **sharded refine epoch** (:func:`make_refine_epoch`):
up to ``DistConfig.epoch_k`` of the tiles the step just read (already
in HBM — zero extra I/O, mirroring host ``process(t)``'s split
side effect) are split along edges SNAPPED TO THE QUERY'S BIN GRID —
the sharded analog of ``IndexConfig.bin_aligned_splits``, the
``geometry._snap_axis_edges`` edge math as pure jnp — their objects'
``cell`` ids rewritten shard-locally and child metadata scattered +
merged, children clamped into the parent's sound value interval. The
:class:`~repro.core.refine.EpochDriver` runs the session loop (step →
epoch → re-step on miss → exact-ish φ=0 fallback) with the same
stopping predicate as the host driver, and
:class:`DistributedAQPEngine` records every query into an
:class:`~repro.core.engine.EngineTrace` so ``totals()`` and the
benchmarks' ``mixed_io_summary`` cover distributed sessions.

:func:`make_query_step` / :func:`make_heatmap_step` remain as stateless
one-shot wrappers (fresh state per call) preserving the original step
contracts for dry-runs and differential tests.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import fused_select as fsel
from .bounds import (AccuracyPolicy, HeatmapResult, QueryResult,
                     bin_budgets_met, budget_ratios, phi_budgets)
from .engine import EngineTrace
from .refine import EpochDriver

NEG = -3.4e38
POS = 3.4e38


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Configuration of the sharded session (validated at construction;
    the step builders validate bins and mesh axes with clear errors)."""
    grid: Tuple[int, int] = (32, 32)   # initial cracked grid (host grid0)
    alpha: float = 1.0
    # static cap on tiles processed per query (resource-aware bound, like
    # VETI); default = no cap beyond the table itself
    max_process: int = 1 << 20
    capacity: int = 4096               # tile-table slots (static bound)
    split_grid: Tuple[int, int] = (2, 2)   # refine-epoch split grid
    epoch_k: int = 8                   # tiles split per refine epoch
    min_split_count: int = 256         # I/O-cost split factor (paper §2.2)
    max_level: int = 12
    max_epochs: int = 2                # re-selection passes per query

    def __post_init__(self):
        for name, pair in (("grid", self.grid),
                           ("split_grid", self.split_grid)):
            if len(pair) != 2 or int(pair[0]) <= 0 or int(pair[1]) <= 0:
                raise ValueError(f"DistConfig.{name} must be two positive "
                                 f"ints, got {pair}")
        if self.capacity < self.grid[0] * self.grid[1]:
            raise ValueError(
                f"DistConfig.capacity={self.capacity} cannot hold the "
                f"initial {self.grid[0]}x{self.grid[1]} grid "
                f"({self.grid[0] * self.grid[1]} tiles)")
        if self.epoch_k <= 0:
            raise ValueError(f"epoch_k must be > 0, got {self.epoch_k}")
        if self.max_epochs < 0:
            raise ValueError(f"max_epochs must be >= 0, got "
                             f"{self.max_epochs}")


class ShardedTileState(NamedTuple):
    """Device-resident session index state (a pytree; ``cell`` sharded
    over the mesh, everything else replicated). Persists across queries
    and is refined in place by :func:`make_refine_epoch`."""
    cell: jax.Array     # (N,) int32 — per-object tile id (cracked)
    bbox: jax.Array     # (cap, 4) f32 — tile extents [x0, y0, x1, y1]
    active: jax.Array   # (cap,) bool — leaf tiles
    level: jax.Array    # (cap,) int32
    count: jax.Array    # (cap,) f32 — global per-tile object counts
    vmin: jax.Array     # (cap,) f32 — sound value bounds (session attr)
    vmax: jax.Array     # (cap,) f32
    n_tiles: jax.Array  # () int32 — table rows in use


class GroupedCache(NamedTuple):
    """Per-(tile, bin) exact state materialized by past reads — valid
    for ``window`` only (a viewport change invalidates it wholesale; a
    split invalidates the parent's row by deactivating the tile)."""
    cnt_tb: jax.Array   # (cap, nb) f32 — exact in-window per-bin counts
    val_tb: jax.Array   # (cap, nb) f32 — sum (or grouped extremum) per bin
    valid: jax.Array    # (cap,) bool
    window: jax.Array   # (4,) f32 — the window the rows were read under


def _all_axes(mesh: Mesh):
    axes = tuple(mesh.axis_names)
    if not axes:
        raise ValueError(
            "distributed AQP needs a mesh with at least one NAMED axis "
            "to shard the object store over (got a mesh with no axis "
            "names — build it with jax.make_mesh((n,), ('data',)))")
    return axes


def _check_bins(bins) -> Tuple[int, int]:
    bx, by = int(bins[0]), int(bins[1])
    if bx <= 0 or by <= 0:
        raise ValueError(f"heatmap bins must be positive, got {bins}")
    return bx, by


def _grid_cell_ids(xs, ys, domain, gx: int, gy: int):
    """Tile cell id of every local object under the implicit gx×gy grid
    over ``domain`` (the same clip-binning ownership rule as the host
    index) — the session state's INITIAL cracked assignment."""
    x0, y0 = domain[0], domain[1]
    cw = (domain[2] - x0) / gx
    ch = (domain[3] - y0) / gy
    cx = jnp.clip(jnp.floor((xs - x0) / cw).astype(jnp.int32), 0, gx - 1)
    cy = jnp.clip(jnp.floor((ys - y0) / ch).astype(jnp.int32), 0, gy - 1)
    return cy * gx + cx


def _window_mask(xs, ys, window):
    """Closed-rectangle selection mask (the paper's query semantics)."""
    return ((xs >= window[0]) & (xs <= window[2])
            & (ys >= window[1]) & (ys <= window[3]))


def _window_bin_ids(xs, ys, window, bx: int, by: int):
    """jnp mirror of ``kernels.ref.window_bin_ids_np``: the heatmap
    grid laid over the query window — ``(in_window_mask, bin_id)`` with
    bin id = by_row·bx + bx_col, closed-max-edge objects clipped into
    the last bin. Shared by the heatmap step and the tests' oracles."""
    qx0, qy0, qx1, qy1 = window[0], window[1], window[2], window[3]
    m = _window_mask(xs, ys, window)
    cw = jnp.maximum((qx1 - qx0) / bx, 1e-30)
    ch = jnp.maximum((qy1 - qy0) / by, 1e-30)
    wx = jnp.clip(jnp.floor((xs - qx0) / cw).astype(jnp.int32), 0, bx - 1)
    wy = jnp.clip(jnp.floor((ys - qy0) / ch).astype(jnp.int32), 0, by - 1)
    return m, wy * bx + wx


def _scatter_grouped(cell, wid, inq, vf, cap: int, nb: int, agg: str,
                     axes):
    """Per-(tile, bin) masked binned scatter + cross-shard merge: ONE
    pass over the local objects gives every (tile, bin) cell's in-window
    count and value state (sum for ``agg="sum"``, grouped extrema for
    min/max — the distributed analog of the packed segment kernels'
    channels), psum/pmin/pmax-merged into replicated ``(cap, nb)``
    arrays. Shared by the heatmap step and the stateless wrapper."""
    key = cell * nb + wid
    cnt_tb = jnp.zeros((cap * nb,), jnp.float32).at[key].add(
        jnp.where(inq, 1.0, 0.0))
    cnt_tb = jax.lax.psum(cnt_tb, axes).reshape(cap, nb)
    if agg == "sum":
        v_tb = jnp.zeros((cap * nb,), jnp.float32).at[key].add(
            jnp.where(inq, vf, 0.0))
        v_tb = jax.lax.psum(v_tb, axes).reshape(cap, nb)
    elif agg == "min":
        v_tb = jnp.full((cap * nb,), POS, jnp.float32).at[key].min(
            jnp.where(inq, vf, POS))
        v_tb = jax.lax.pmin(v_tb, axes).reshape(cap, nb)
    else:  # max
        v_tb = jnp.full((cap * nb,), NEG, jnp.float32).at[key].max(
            jnp.where(inq, vf, NEG))
        v_tb = jax.lax.pmax(v_tb, axes).reshape(cap, nb)
    return cnt_tb, v_tb


def _classify_tiles(bbox, active, window):
    """(disjoint, full) masks of the tile table against the closed query
    window. Conservative like the host ``geometry.classify_tiles``:
    borderline tiles demote to partial; inactive rows are disjoint."""
    qx0, qy0, qx1, qy1 = window[0], window[1], window[2], window[3]
    tx0, ty0, tx1, ty1 = bbox[:, 0], bbox[:, 1], bbox[:, 2], bbox[:, 3]
    disjoint = ((~active) | (tx1 < qx0) | (tx0 > qx1)
                | (ty1 < qy0) | (ty0 > qy1))
    full = (active & (tx0 >= qx0) & (tx1 <= qx1)
            & (ty0 >= qy0) & (ty1 <= qy1))
    return disjoint, full


def _snapped_edges(e0, e1, g: int, q0, q1, b: int):
    """Pure-jnp port of ``geometry._snap_axis_edges``, vectorized over
    tiles: uniform g+1 split edges of each ``[e0, e1]`` with every
    interior edge snapped to the nearest bin-grid line of ``([q0, q1],
    b)`` strictly inside the extent; falls back to the uniform edges
    when no line crosses the extent or snapping would collapse two
    children. ``e0``/``e1`` are (K,); returns (K, g+1) float32."""
    frac = jnp.arange(g + 1, dtype=jnp.float32) / g
    edges = e0[:, None] * (1.0 - frac) + e1[:, None] * frac
    if b <= 1 or g <= 1:
        return edges
    lines = q0 + (q1 - q0) / b * jnp.arange(1, b, dtype=jnp.float32)
    inside = ((lines[None, :] > e0[:, None])
              & (lines[None, :] < e1[:, None]) & (q1 > q0))
    has = inside.any(axis=1)
    d = jnp.abs(lines[None, None, :] - edges[:, 1:g, None])
    d = jnp.where(inside[:, None, :], d, jnp.inf)
    snapped_int = lines[jnp.argmin(d, axis=2)]          # (K, g-1)
    snapped = jnp.concatenate([e0[:, None], snapped_int, e1[:, None]],
                              axis=1)
    snapped = jnp.sort(snapped, axis=1)
    collapse = (jnp.diff(snapped, axis=1) <= 0).any(axis=1)
    return jnp.where((has & ~collapse)[:, None], snapped, edges)


def _empty_cache(cap: int, nb: int) -> GroupedCache:
    return GroupedCache(cnt_tb=jnp.zeros((cap, nb), jnp.float32),
                        val_tb=jnp.zeros((cap, nb), jnp.float32),
                        valid=jnp.zeros((cap,), bool),
                        window=jnp.full((4,), jnp.nan, jnp.float32))


# --------------------------------------------------------------------- #
# step builders
# --------------------------------------------------------------------- #

def _state_specs(axes):
    return ShardedTileState(cell=P(axes), bbox=P(), active=P(), level=P(),
                            count=P(), vmin=P(), vmax=P(), n_tiles=P())


def _cache_specs():
    return GroupedCache(cnt_tb=P(), val_tb=P(), valid=P(), window=P())


def _init_state_raw(mesh: Mesh, cfg: DistConfig):
    gx, gy = cfg.grid
    t = gx * gy
    cap = cfg.capacity
    axes = _all_axes(mesh)

    def local(xs, ys, vals, domain):
        cid = _grid_cell_ids(xs, ys, domain, gx, gy)
        vf = vals.astype(jnp.float32)
        cnt = jnp.zeros((cap,), jnp.float32).at[cid].add(
            jnp.ones_like(vf))
        mn = jnp.full((cap,), POS, jnp.float32).at[cid].min(vf)
        mx = jnp.full((cap,), NEG, jnp.float32).at[cid].max(vf)
        cnt = jax.lax.psum(cnt, axes)
        mn = jax.lax.pmin(mn, axes)
        mx = jax.lax.pmax(mx, axes)
        # empty tiles carry the attribute's global bounds (sound for any
        # object a later epoch might move in — none can, but the rule
        # matches the host index's root fallback)
        gmn = jax.lax.pmin(jnp.min(vf, initial=POS), axes)
        gmx = jax.lax.pmax(jnp.max(vf, initial=NEG), axes)
        vmin = jnp.where(cnt > 0, mn, gmn)
        vmax = jnp.where(cnt > 0, mx, gmx)
        x0, y0 = domain[0], domain[1]
        cw = (domain[2] - x0) / gx
        ch = (domain[3] - y0) / gy
        ti = jnp.arange(cap)
        tx0 = x0 + (ti % gx).astype(jnp.float32) * cw
        ty0 = y0 + (ti // gx).astype(jnp.float32) * ch
        bbox = jnp.stack([tx0, ty0, tx0 + cw, ty0 + ch], axis=1)
        return ShardedTileState(
            cell=cid, bbox=bbox.astype(jnp.float32), active=ti < t,
            level=jnp.zeros((cap,), jnp.int32), count=cnt,
            vmin=vmin, vmax=vmax, n_tiles=jnp.int32(t))

    obj = P(axes)
    return jax.shard_map(local, mesh=mesh, in_specs=(obj, obj, obj, P()),
                         out_specs=_state_specs(axes), check_vma=False)


def make_init_state(mesh: Mesh, cfg: DistConfig = DistConfig()):
    """Jitted builder of a fresh :class:`ShardedTileState` —
    ``init(xs, ys, vals, domain)``: the crude ``cfg.grid`` cracked
    assignment plus psum-merged per-tile metadata (the SPMD analog of
    the host index's init pass)."""
    return jax.jit(_init_state_raw(mesh, cfg))


def _session_query_raw(mesh: Mesh, cfg: DistConfig, fused: bool = True):
    cap = cfg.capacity
    axes = _all_axes(mesh)

    def local(state, xs, ys, vals, window, phi):
        cell = state.cell
        if fused:
            # one fused classify+scatter primitive (the heatmap's
            # nb = 1 degenerate: bin id ≡ 0, key ≡ cell) — bit-for-bit
            # the composed expressions below
            cnt_q, s_q = fsel.fused_count_val(cell, xs, ys, vals, window,
                                              cap, 1, 1, 1, "sum")
        else:
            inq = _window_mask(xs, ys, window)
            vf = vals.astype(jnp.float32)
            cnt_q = jnp.zeros((cap,), jnp.float32).at[cell].add(
                jnp.where(inq, 1.0, 0.0))
            s_q = jnp.zeros((cap,), jnp.float32).at[cell].add(
                jnp.where(inq, vf, 0.0))
        cnt_q = jax.lax.psum(cnt_q, axes)
        s_q = jax.lax.psum(s_q, axes)

        disjoint, full = _classify_tiles(state.bbox, state.active, window)
        partial = (~disjoint) & (~full) & (cnt_q > 0)

        # --- CI from the persistent metadata (sum aggregate; §3.1) ---
        exact_sum = jnp.sum(jnp.where(full, s_q, 0.0))
        lo_p = jnp.where(partial, cnt_q * state.vmin, 0.0)
        hi_p = jnp.where(partial, cnt_q * state.vmax, 0.0)
        mid_p = jnp.where(partial,
                          cnt_q * 0.5 * (state.vmin + state.vmax), 0.0)

        # --- score + static-k greedy selection via prefix sums ---
        width = hi_p - lo_p
        w_hat = width / jnp.maximum(jnp.max(width), 1e-9)
        c_hat = cnt_q / jnp.maximum(
            jnp.max(jnp.where(partial, cnt_q, 0.0)), 1e-9)
        score = jnp.where(
            partial,
            cfg.alpha * w_hat + (1 - cfg.alpha) / jnp.maximum(c_hat, 1e-9),
            -jnp.inf)
        order = jnp.argsort(-score)
        width_sorted = width[order]
        if fused:
            resid = fsel.suffix_residual(width_sorted, "sum")
        else:
            # residual CI width if tiles [0..j) are processed. Reversed
            # cumsum, not total−prefix: the subtraction leaves f32 ≈+ε
            # at j = n_partial and φ=0 would then select nothing.
            resid = jnp.concatenate(
                [jnp.cumsum(width_sorted[::-1])[::-1], jnp.zeros((1,))])
        approx0 = exact_sum + jnp.sum(mid_p)
        surrogate = (0.5 * resid) / jnp.maximum(jnp.abs(approx0), 1e-9)
        n_partial = jnp.sum(partial.astype(jnp.int32))
        jmeet = jnp.argmax(surrogate <= phi)  # smallest prefix meeting φ
        j = jnp.minimum(jnp.minimum(jmeet, n_partial), cfg.max_process)

        sel = jnp.zeros((cap,), bool).at[order].set(jnp.arange(cap) < j)
        sel = sel & partial
        # processed tiles contribute exact values; rest keep midpoints
        value = exact_sum + jnp.sum(jnp.where(sel, s_q, mid_p))
        lo = exact_sum + jnp.sum(jnp.where(sel, s_q, lo_p))
        hi = exact_sum + jnp.sum(jnp.where(sel, s_q, hi_p))
        bound = jnp.maximum(hi - value, value - lo) / \
            jnp.maximum(jnp.abs(value), 1e-9)
        objects_read = jnp.sum(jnp.where(sel, state.count, 0.0))
        return {"value": value, "lo": lo, "hi": hi, "bound": bound,
                "budget_bound": bound,
                "n_processed": j.astype(jnp.int32),
                "n_partial": n_partial,
                "n_full": jnp.sum((full & (state.count > 0))
                                  .astype(jnp.int32)),
                "objects_read": objects_read, "sel": sel}

    obj = P(axes)
    keys = ("value", "lo", "hi", "bound", "budget_bound", "n_processed",
            "n_partial", "n_full", "objects_read", "sel")
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(_state_specs(axes), obj, obj, obj, P(),
                                   P()),
                         out_specs={k: P() for k in keys}, check_vma=False)


def make_session_query_step(mesh: Mesh, cfg: DistConfig = DistConfig(),
                            fused: bool = True):
    """Jitted scalar (sum) query step over the session state:
    ``step(state, xs, ys, vals, window, phi)`` — classification,
    pending intervals, and selection all come from the PERSISTENT tile
    table, so a cracked session answers the same window with fewer and
    cheaper pending tiles than the fresh-surrogate wrapper.

    ``fused=True`` (default) routes the per-device classify→scatter and
    the selection suffix scan through the
    :mod:`repro.kernels.fused_select` primitives; ``fused=False`` keeps
    the historical composed chain. The two are bit-for-bit identical
    (asserted in tests/test_distributed.py)."""
    return jax.jit(_session_query_raw(mesh, cfg, fused))


def _session_heatmap_raw(mesh: Mesh, cfg: DistConfig,
                         bins: Tuple[int, int], agg: str,
                         with_policy: bool, fused: bool = True):
    assert agg in ("sum", "min", "max"), agg
    bx, by = _check_bins(bins)
    nb = bx * by
    cap = cfg.capacity
    axes = _all_axes(mesh)

    def local(state, cache, xs, ys, vals, window, phi, phi_b, eps_abs):
        if fused:
            # fused classify→scatter: one kernels-layer primitive gives
            # the pre-merge per-(tile, bin) count/value tables —
            # bit-for-bit the composed _window_bin_ids+_scatter_grouped
            # chain it replaces
            cnt_f, v_f = fsel.fused_count_val(state.cell, xs, ys, vals,
                                              window, cap, nb, bx, by,
                                              agg, neg=NEG, pos=POS)
            cnt_tb = jax.lax.psum(cnt_f, axes).reshape(cap, nb)
            if agg == "sum":
                v_tb = jax.lax.psum(v_f, axes).reshape(cap, nb)
            elif agg == "min":
                v_tb = jax.lax.pmin(v_f, axes).reshape(cap, nb)
            else:
                v_tb = jax.lax.pmax(v_f, axes).reshape(cap, nb)
        else:
            inq, wid = _window_bin_ids(xs, ys, window, bx, by)
            vf = vals.astype(jnp.float32)
            cnt_tb, v_tb = _scatter_grouped(state.cell, wid, inq, vf,
                                            cap, nb, agg, axes)
        mn, mx = state.vmin, state.vmax

        # --- classification + per-(tile, bin) exact-state reuse ---
        disjoint, full = _classify_tiles(state.bbox, state.active, window)
        cnt_q = jnp.sum(cnt_tb, axis=1)
        partial = (~disjoint) & (~full) & (cnt_q > 0)
        same_win = jnp.all(cache.window == window)
        cached = cache.valid & same_win & partial
        # cached rows are authoritative: the registry holds the exact
        # grouped state those reads materialized (bit-identical to the
        # recomputed scatter while the store is immutable)
        cnt_tb = jnp.where(cached[:, None], cache.cnt_tb, cnt_tb)
        v_tb = jnp.where(cached[:, None], cache.val_tb, v_tb)
        touch = cnt_tb > 0
        occ = jnp.sum(cnt_tb, axis=0) > 0
        exact_t = full | cached
        pend = partial & ~cached
        n_partial = jnp.sum(pend.astype(jnp.int32))

        # --- grouped pending intervals + initial midpoint surrogate ---
        if agg == "sum":
            exact_b = jnp.sum(jnp.where(exact_t[:, None], v_tb, 0.0),
                              axis=0)
            lo_tb = jnp.where(pend[:, None], cnt_tb * mn[:, None], 0.0)
            hi_tb = jnp.where(pend[:, None], cnt_tb * mx[:, None], 0.0)
            mid_tb = jnp.where(pend[:, None],
                               cnt_tb * (0.5 * (mn + mx))[:, None], 0.0)
            width_tb = hi_tb - lo_tb
            approx0_b = exact_b + jnp.sum(mid_tb, axis=0)
        else:
            red = jnp.min if agg == "min" else jnp.max
            sent = POS if agg == "min" else NEG
            ex0 = red(jnp.where(exact_t[:, None] & touch, v_tb, sent),
                      axis=0)
            p_lo0 = red(jnp.where(pend[:, None] & touch, mn[:, None],
                                  sent), axis=0)
            p_hi0 = red(jnp.where(pend[:, None] & touch, mx[:, None],
                                  sent), axis=0)
            lo0 = red(jnp.stack([ex0, p_lo0]), axis=0)
            hi0 = red(jnp.stack([ex0, p_hi0]), axis=0)
            approx0_b = 0.5 * (lo0 + hi0)
        denom0 = jnp.maximum(jnp.abs(approx0_b), 1e-9)

        # --- grouped score: worst per-bin CI width / value-range,
        #     budget-normalized under a φ_b policy ---
        if with_policy:
            # inverse deviation budgets 1/τ_b as bin weights — the SPMD
            # mirror of GroupedAccumulator.score_bin_weight (don't-care
            # bins, φ_b = ∞, weigh 0)
            tau0 = phi_budgets(phi_b, denom0, eps_abs, xp=jnp)
            bin_w = jnp.where(jnp.isinf(tau0), 0.0,
                              1.0 / jnp.maximum(tau0, 1e-30))
            if agg == "sum":
                w_t = jnp.max(width_tb * bin_w[None, :], axis=1)
            else:
                w_t = jnp.where(pend, mx - mn, 0.0) * jnp.max(
                    jnp.where(touch, bin_w[None, :], 0.0), axis=1)
            # tiny budgets (incl. the φ=0 fallback's zeroed ones) make
            # 1/τ huge; clamp below f32 inf so w_hat = w_t/max(w_t)
            # stays NaN-free — a NaN score would sort the WIDEST
            # pending tiles past the -inf non-pending rows and silently
            # exclude them from selection
            w_t = jnp.minimum(w_t, POS)
        elif agg == "sum":
            w_t = jnp.max(width_tb, axis=1)
        else:
            w_t = jnp.where(pend, mx - mn, 0.0)
        w_hat = w_t / jnp.maximum(jnp.max(w_t), 1e-9)
        c_hat = cnt_q / jnp.maximum(
            jnp.max(jnp.where(pend, cnt_q, 0.0)), 1e-9)
        score = jnp.where(
            pend,
            cfg.alpha * w_hat + (1 - cfg.alpha) / jnp.maximum(c_hat, 1e-9),
            -jnp.inf)
        order = jnp.argsort(-score)

        # --- static-k greedy selection via suffix scans ---
        if agg == "sum":
            width_sorted = width_tb[order]   # (cap, nb)
            # residual per-bin width if tiles [0..j) are processed.
            # Reversed cumsum, not total−prefix: the f32 subtraction
            # leaves ≈+ε at j = n_partial and φ=0 would then select
            # nothing.
            if fused:
                resid = fsel.suffix_residual(width_sorted, "sum")
            else:
                resid = jnp.concatenate(
                    [jnp.cumsum(width_sorted[::-1], axis=0)[::-1],
                     jnp.zeros((1, nb))])    # (cap+1, nb)
        else:
            # an unprocessed pending tile leaves at most its value-range
            # width of deviation on every bin it touches — suffix
            # RUNNING MAX plays the role the suffix cumsum plays for sum
            wb_tb = jnp.where(pend[:, None] & touch,
                              (mx - mn)[:, None], 0.0)
            if fused:
                resid = fsel.suffix_residual(wb_tb[order], agg)
            else:
                resid = jnp.concatenate(
                    [jax.lax.cummax(wb_tb[order], axis=0, reverse=True),
                     jnp.zeros((1, nb))])    # (cap+1, nb)
        ratio = (0.5 * resid) / denom0[None, :]
        if with_policy:
            # per-bin budgets τ_b = max(φ_b·|v_b|, ε_abs) replace the
            # scalar-φ test: a prefix meets once EVERY occupied bin's
            # residual fits its own budget. The ratio form keeps the
            # uniform policy (φ_b = φ·1, ε_abs = 0) bit-for-bit the
            # scalar test below.
            ok = ((~occ)[None, :] | (ratio <= phi_b[None, :])
                  | (0.5 * resid <= eps_abs))
            meets = ok.all(axis=1)
        else:
            surr = jnp.where(occ[None, :], ratio, 0.0)
            meets = jnp.max(surr, axis=1) <= phi
        jmeet = jnp.argmax(meets)   # smallest prefix meeting every budget
        j = jnp.minimum(jnp.minimum(jmeet, n_partial), cfg.max_process)

        sel = jnp.zeros((cap,), bool).at[order].set(jnp.arange(cap) < j)
        sel = sel & pend
        sel_c = sel[:, None]
        if agg == "sum":
            values = exact_b + jnp.sum(jnp.where(sel_c, v_tb, mid_tb),
                                       axis=0)
            lo = exact_b + jnp.sum(jnp.where(sel_c, v_tb, lo_tb), axis=0)
            hi = exact_b + jnp.sum(jnp.where(sel_c, v_tb, hi_tb), axis=0)
            dev = jnp.maximum(hi - values, values - lo)
        else:
            # exact parts: full ∪ cached ∪ selected tiles' grouped
            # extrema; unprocessed pending tiles keep their tile-level
            # intervals on every touched bin
            red = jnp.min if agg == "min" else jnp.max
            sent = POS if agg == "min" else NEG
            ex_b = red(jnp.where((exact_t | sel)[:, None] & touch, v_tb,
                                 sent), axis=0)
            pendm = pend[:, None] & (~sel_c) & touch
            p_lo = red(jnp.where(pendm, mn[:, None], sent), axis=0)
            p_hi = red(jnp.where(pendm, mx[:, None], sent), axis=0)
            lo = red(jnp.stack([ex_b, p_lo]), axis=0)
            hi = red(jnp.stack([ex_b, p_hi]), axis=0)
            mid = 0.5 * (lo + hi)
            values = jnp.where(occ, mid, sent)
            dev = jnp.where(occ, jnp.maximum(hi - values, values - lo),
                            0.0)
        bin_bound = jnp.where(
            occ & (dev > 0),
            dev / jnp.maximum(jnp.abs(values), 1e-9), 0.0)
        bound = jnp.max(bin_bound, initial=0.0)
        if with_policy:
            # the driver's stopping quantity: the φ-scaled worst budget
            # ratio (GroupedAccumulator.query_bound, in-SPMD)
            tau = phi_budgets(phi_b, jnp.maximum(jnp.abs(values), 1e-9),
                              eps_abs, xp=jnp)
            dev_f = jnp.where(occ & jnp.isfinite(dev), dev, 0.0)
            ratios = budget_ratios(dev_f, tau, xp=jnp)
            # the φ=0 fallback pass zeroes the budgets (τ = 0), where
            # dev/τ would poison the field with inf/NaN — report the
            # plain bound there (the driver ignores it at φ = 0 anyway)
            budget_bound = jnp.where(
                phi > 0, phi * jnp.max(jnp.where(jnp.isfinite(ratios),
                                                 ratios, 0.0),
                                       initial=0.0), bound)
            bin_met = bin_budgets_met(dev, values, phi_b, eps_abs, occ,
                                      xp=jnp)
        else:
            budget_bound = bound
            bin_met = bin_budgets_met(dev, values, phi, 0.0, occ,
                                      xp=jnp)
        objects_read = jnp.sum(jnp.where(sel, state.count, 0.0))

        # --- write the round's reads into the exact-state registry ---
        nvalid = (cache.valid & same_win) | sel
        new_cache = GroupedCache(
            cnt_tb=jnp.where(nvalid[:, None], cnt_tb, 0.0),
            val_tb=jnp.where(nvalid[:, None], v_tb, 0.0),
            valid=nvalid, window=window)

        out = {"values": values, "lo": lo, "hi": hi,
               "bin_bound": bin_bound, "bound": bound,
               "budget_bound": budget_bound, "bin_met": bin_met,
               "bin_count": jnp.sum(cnt_tb, axis=0),
               "n_processed": j.astype(jnp.int32),
               "n_partial": n_partial,
               "n_cached": jnp.sum(cached.astype(jnp.int32)),
               "n_full": jnp.sum((full & (state.count > 0))
                                 .astype(jnp.int32)),
               "objects_read": objects_read, "sel": sel}
        return out, new_cache

    obj = P(axes)
    keys = ("values", "lo", "hi", "bin_bound", "bound", "budget_bound",
            "bin_met", "bin_count", "n_processed", "n_partial",
            "n_cached", "n_full", "objects_read", "sel")
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(_state_specs(axes), _cache_specs(), obj,
                                   obj, obj, P(), P(), P(), P()),
                         out_specs=({k: P() for k in keys},
                                    _cache_specs()),
                         check_vma=False)


def make_session_heatmap_step(mesh: Mesh, cfg: DistConfig,
                              bins: Tuple[int, int], agg: str = "sum",
                              with_policy: bool = False,
                              fused: bool = True):
    """Jitted distributed HEATMAP (2-D group-by) step over the session
    state: ``step(state, cache, xs, ys, vals, window, phi, phi_b,
    eps_abs) → (out, new_cache)``.

    The SPMD unrolling of the unified refinement driver's grouped loop:
    classification and pending intervals come from the PERSISTENT tile
    table, previously-read tiles answer from the per-(tile, bin) exact
    registry at zero read cost, and selection stops at the per-bin
    budgets ``τ_b = max(φ_b·|v_b|, ε_abs)`` (``with_policy=True``; the
    ``with_policy=False`` build takes the same arguments but tests the
    scalar φ — the two are bit-for-bit identical under the uniform
    policy, regression-tested in tests/test_distributed.py).

    ``fused=True`` (default) replaces the in-step
    classify→scatter→select chain with the
    :mod:`repro.kernels.fused_select` primitives (one fused count/value
    scatter + the suffix-scan selection epilogue); ``fused=False``
    keeps the historical composed chain. Answers and index evolution
    are bit-for-bit identical between the two (asserted in
    tests/test_distributed.py)."""
    return jax.jit(_session_heatmap_raw(mesh, cfg, bins, agg,
                                        with_policy, fused))


def _refine_epoch_raw(mesh: Mesh, cfg: DistConfig,
                      bins: Tuple[int, int]):
    gx, gy = cfg.split_grid
    k = gx * gy
    kk = cfg.epoch_k
    cap = cfg.capacity
    bx, by = _check_bins(bins)
    axes = _all_axes(mesh)

    def local(state, xs, ys, vals, window, sel):
        vf = vals.astype(jnp.float32)
        # split candidates: tiles the preceding step just READ (their
        # segments are hot — splitting is free I/O-wise, exactly like
        # host process(t)'s split side effect)
        elig = (sel & state.active
                & (state.count >= cfg.min_split_count)
                & (state.level < cfg.max_level))
        score = jnp.where(elig, (state.vmax - state.vmin) * state.count,
                          -jnp.inf)
        n_elig = jnp.sum(elig.astype(jnp.int32))
        room = jnp.maximum((cap - state.n_tiles) // k, 0)
        n_val = jnp.minimum(jnp.minimum(n_elig, kk), room)
        order = jnp.argsort(-score)
        parents = order[:kk]                        # (K,)
        slot_ok = jnp.arange(kk) < n_val

        # bin-aligned split edges, snapped to THIS query's bin grid
        pb = state.bbox[parents]
        xe = _snapped_edges(pb[:, 0], pb[:, 2], gx, window[0], window[2],
                            bx)                     # (K, gx+1)
        ye = _snapped_edges(pb[:, 1], pb[:, 3], gy, window[1], window[3],
                            by)                     # (K, gy+1)

        # shard-local cell-id rewrite: objects of split parents move to
        # their child's fresh table row (the cracking step)
        # (per-slot loops over flat (n,) arrays: an (n, K) or (n, gx+1)
        # intermediate pads its minor axis to 128 lanes on a TPU — 51 GB
        # at 10^8 objects; parents are distinct, so ≤ 1 slot matches)
        has = jnp.zeros(xs.shape, bool)
        j = jnp.zeros(xs.shape, jnp.int32)          # parent slot per object
        for s in range(kk):
            m = (state.cell == parents[s]) & slot_ok[s]
            has = has | m
            j = jnp.where(m, s, j)
        cx = jnp.zeros(xs.shape, jnp.int32)
        for i in range(1, gx):
            cx = cx + (xs >= xe[:, i][j]).astype(jnp.int32)
        cy = jnp.zeros(ys.shape, jnp.int32)
        for i in range(1, gy):
            cy = cy + (ys >= ye[:, i][j]).astype(jnp.int32)
        child = cy * gx + cx
        new_cell = jnp.where(
            has, state.n_tiles + j * k + child, state.cell).astype(
                jnp.int32)

        # child metadata: scatter + merge (out-of-range sentinel rows of
        # invalid slots drop)
        ckey = jnp.where(has, j * k + child, kk * k)
        ccnt = jnp.zeros((kk * k,), jnp.float32).at[ckey].add(
            jnp.where(has, 1.0, 0.0))
        cmn = jnp.full((kk * k,), POS, jnp.float32).at[ckey].min(
            jnp.where(has, vf, POS))
        cmx = jnp.full((kk * k,), NEG, jnp.float32).at[ckey].max(
            jnp.where(has, vf, NEG))
        ccnt = jax.lax.psum(ccnt, axes).reshape(kk, k)
        cmn = jax.lax.pmin(cmn, axes).reshape(kk, k)
        cmx = jax.lax.pmax(cmx, axes).reshape(kk, k)
        # (no per-child sum column: exact in-window sums re-derive from
        # the query steps' scatters; only the sound BOUNDS persist)
        # children clamp into the parent's sound interval (the host
        # metadata soundness rule); empty children inherit it outright
        pv_lo = state.vmin[parents][:, None]
        pv_hi = state.vmax[parents][:, None]
        cvmin = jnp.where(ccnt > 0, jnp.maximum(cmn, pv_lo), pv_lo)
        cvmax = jnp.where(ccnt > 0, jnp.minimum(cmx, pv_hi), pv_hi)

        # child extents from the snapped edges (row-major y, like host)
        cxs = jnp.arange(k) % gx
        cys = jnp.arange(k) // gx
        cb = jnp.stack([xe[:, cxs], ye[:, cys], xe[:, cxs + 1],
                        ye[:, cys + 1]], axis=-1)   # (K, k, 4)

        # one masked table append for all children of all valid slots
        rows = jnp.where(
            slot_ok[:, None],
            state.n_tiles + jnp.arange(kk)[:, None] * k
            + jnp.arange(k)[None, :], cap).reshape(-1)
        prow = jnp.where(slot_ok, parents, cap)
        clev = jnp.broadcast_to((state.level[parents] + 1)[:, None],
                                (kk, k)).reshape(-1)
        bbox2 = state.bbox.at[rows].set(cb.reshape(-1, 4), mode="drop")
        active2 = state.active.at[rows].set(True, mode="drop") \
            .at[prow].set(False, mode="drop")
        level2 = state.level.at[rows].set(clev, mode="drop")
        count2 = state.count.at[rows].set(ccnt.reshape(-1), mode="drop")
        vmin2 = state.vmin.at[rows].set(cvmin.reshape(-1), mode="drop")
        vmax2 = state.vmax.at[rows].set(cvmax.reshape(-1), mode="drop")
        new_state = ShardedTileState(
            cell=new_cell, bbox=bbox2, active=active2, level=level2,
            count=count2, vmin=vmin2, vmax=vmax2,
            n_tiles=state.n_tiles + n_val * k)
        info = {"n_split": n_val,
                "objects_reorganized": jnp.sum(
                    jnp.where(slot_ok, state.count[parents], 0.0))}
        return new_state, info

    obj = P(axes)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(_state_specs(axes), obj, obj, obj, P(), P()),
        out_specs=(_state_specs(axes),
                   {"n_split": P(), "objects_reorganized": P()}),
        check_vma=False)


def make_refine_epoch(mesh: Mesh, cfg: DistConfig,
                      bins: Tuple[int, int] = (1, 1)):
    """Jitted sharded refine epoch: ``epoch(state, xs, ys, vals,
    window, sel) → (new_state, info)``.

    Splits up to ``cfg.epoch_k`` of the tiles ``sel`` marks (the ones
    the preceding selection step just read — zero additional I/O) along
    ``cfg.split_grid`` edges snapped to the bin grid of ``bins`` laid
    over ``window`` (``bins=(1, 1)`` degenerates to the even split —
    the scalar path), rewriting the sharded ``cell`` ids in place and
    appending psum-merged child metadata to the replicated table — the
    sharded, bin-aligned analog of the host index's
    ``process → split → reorganize`` epilogue."""
    return jax.jit(_refine_epoch_raw(mesh, cfg, bins))


# --------------------------------------------------------------------- #
# stateless one-shot wrappers (the original step contracts)
# --------------------------------------------------------------------- #

def make_query_step(mesh: Mesh, cfg: DistConfig = DistConfig()):
    """Stateless one-shot query step — the original contract:
    ``step(xs, ys, vals, domain, window, phi)`` → dict of replicated
    scalars (value/lo/hi/bound/n_processed/n_partial/objects_read).
    Builds a fresh session state per call, so every query sees the
    crude ``cfg.grid`` surrogate (the session engine keeps the state)."""
    init = _init_state_raw(mesh, cfg)
    sess = _session_query_raw(mesh, cfg)

    @jax.jit
    def step(xs, ys, vals, domain, window, phi):
        st = init(xs, ys, vals, domain)
        out = sess(st, xs, ys, vals, window, phi)
        return {key: out[key] for key in
                ("value", "lo", "hi", "bound", "n_processed",
                 "n_partial", "objects_read")}
    return step


def make_heatmap_step(mesh: Mesh, cfg: DistConfig,
                      bins: Tuple[int, int], agg: str = "sum"):
    """Stateless one-shot heatmap step — the original contract:
    ``step(xs, ys, vals, domain, window, phi)`` → dict of replicated
    per-bin arrays (values/lo/hi/bin_bound/bin_count) and scalars
    (bound/n_processed/n_partial/objects_read). For min/max, empty bins
    carry the ±``3.4e38`` sentinel (the engine maps them to ±inf)."""
    bx, by = _check_bins(bins)
    nb = bx * by
    init = _init_state_raw(mesh, cfg)
    sess = _session_heatmap_raw(mesh, cfg, (bx, by), agg,
                                with_policy=False)

    @jax.jit
    def step(xs, ys, vals, domain, window, phi):
        st = init(xs, ys, vals, domain)
        out, _ = sess(st, _empty_cache(cfg.capacity, nb), xs, ys, vals,
                      window, phi, jnp.zeros((nb,), jnp.float32),
                      jnp.float32(0.0))
        return {key: out[key] for key in
                ("values", "lo", "hi", "bin_bound", "bound", "bin_count",
                 "n_processed", "n_partial", "objects_read")}
    return step


# --------------------------------------------------------------------- #
# the session engine
# --------------------------------------------------------------------- #

class DistributedAQPEngine:
    """Host-facing session wrapper: shards a dataset over the mesh once,
    keeps one :class:`ShardedTileState` per queried attribute (plus a
    per-(attr, bins, agg) grouped exact-state registry), and serves
    φ-constrained queries through the :class:`~repro.core.refine
    .EpochDriver` loop — select → re-select on a budget miss (earlier
    passes' reads answer from the registry) → exact-ish φ=0 fallback →
    crack-what-you-read. Every query appends a
    :class:`~repro.core.bounds.QueryResult` /
    :class:`~repro.core.bounds.HeatmapResult` to :attr:`trace`, so
    ``EngineTrace.totals()`` (and the benchmarks' ``mixed_io_summary``)
    cover distributed sessions exactly like host ones.

    ``dataset`` may be a :class:`~repro.data.rawfile.RawDataset` or a
    :class:`~repro.data.chunked.ChunkedDataset` — the constructor
    materializes the data onto the mesh ONCE, so a chunked dataset is
    device-resident as a snapshot of its live chunks at construction
    time: later ``ingest``/``retire`` calls do not reshard (rebuild the
    engine, or use the host ``AQPEngine`` whose chunk forest tracks the
    lifecycle natively)."""

    def __init__(self, dataset, mesh: Mesh,
                 cfg: DistConfig = DistConfig(), *,
                 defer_epochs: bool = False):
        self.mesh = mesh
        self.cfg = cfg
        # epoch publication seam (the SPMD analog of the serving
        # layer's EpochStage): with defer_epochs=True, refine epochs
        # are STAGED instead of applied inside the query — the session
        # state stays frozen for a whole serving tick, and
        # publish_epochs() applies them atomically between ticks
        self.defer_epochs = bool(defer_epochs)
        self._staged_epochs: List[tuple] = []
        axes = _all_axes(mesh)
        n_dev = int(np.prod([mesh.shape[a] for a in axes]))
        n = (dataset.n // n_dev) * n_dev  # truncate to shardable length
        if n == 0:
            raise ValueError(
                f"dataset of {dataset.n} objects cannot be sharded over "
                f"{n_dev} devices (fewer objects than devices)")
        spec = NamedSharding(mesh, P(axes))
        self.xs = jax.device_put(dataset.x[:n], spec)
        self.ys = jax.device_put(dataset.y[:n], spec)
        self.vals = {a: jax.device_put(
            dataset.read_all_unaccounted(a)[:n], spec)
            for a in dataset.attributes}
        self.domain = jnp.asarray(dataset.domain(), jnp.float32)
        self.trace = EngineTrace()
        self._init = make_init_state(mesh, cfg)
        self._query_step = make_session_query_step(mesh, cfg)
        self._states: Dict[str, ShardedTileState] = {}
        self._caches: Dict[tuple, GroupedCache] = {}
        self._heatmap_steps = {}   # (bx, by, agg, policy) → jitted step
        self._epochs = {}          # (bx, by) → jitted refine epoch

    # ------------------------- plumbing ------------------------------ #
    def _state(self, attr: str) -> ShardedTileState:
        if attr not in self._states:
            self._states[attr] = self._init(self.xs, self.ys,
                                            self.vals[attr], self.domain)
        return self._states[attr]

    def reset_session(self, attr: Optional[str] = None):
        """Drop the cracked state (and caches) — back to the crude grid."""
        if attr is None:
            self._states.clear()
            self._caches.clear()
        else:
            self._states.pop(attr, None)
            for key in [c for c in self._caches if c[0] == attr]:
                self._caches.pop(key)

    def _epoch(self, bins: Tuple[int, int]):
        if bins not in self._epochs:
            self._epochs[bins] = make_refine_epoch(self.mesh, self.cfg,
                                                   bins)
        return self._epochs[bins]

    def _heatmap_step(self, bins, agg: str, with_policy: bool):
        key = (bins[0], bins[1], agg, with_policy)
        if key not in self._heatmap_steps:
            self._heatmap_steps[key] = make_session_heatmap_step(
                self.mesh, self.cfg, bins, agg, with_policy)
        return self._heatmap_steps[key]

    def _epoch_runner(self, holder, attr: str, bins, win):
        """The EpochDriver's ``run_epoch`` hook, shared by both query
        paths: crack the tiles the final pass read, persist the state
        in the caller's holder, report how many split.

        Under ``defer_epochs`` the crack is STAGED instead — recorded
        with the query's selection mask and applied by
        :meth:`publish_epochs` once the tick has quiesced. The answer
        is unaffected (the epoch runs strictly after the last
        selection pass anyway); only the state mutation moves."""
        epoch = self._epoch(bins)

        def run_epoch(out):
            if self.defer_epochs:
                self._staged_epochs.append(
                    (attr, bins, np.asarray(win), np.asarray(out["sel"])))
                return 0
            st2, info = epoch(holder["state"], self.xs, self.ys,
                              self.vals[attr], win,
                              jnp.asarray(out["sel"]))
            holder["state"] = st2
            return int(info["n_split"])
        return run_epoch

    def publish_epochs(self) -> Dict[str, int]:
        """Apply every staged refine epoch atomically (staging order =
        arrival order) and invalidate the grouped exact-state registry
        rows of tiles the publication deactivated.

        The first-claimant rule of the host
        :class:`~repro.core.index.EpochStage` holds by construction: a
        tile split by an earlier staged epoch is inactive when a later
        epoch's selection mask reaches it, so its candidate row drops
        out of the later epoch's eligibility (``sel & active``) and a
        tile can never split twice. Registry invalidation is the SPMD
        analog of the host payloads' apply-time ``hm_key`` resolution:
        rows of now-inactive parents are cleared wholesale so a
        post-publication query re-reads the children instead of
        trusting state keyed to the pre-publication table."""
        staged, self._staged_epochs = self._staged_epochs, []
        n_split = 0
        touched = set()
        for attr, bins, win, sel in staged:
            if attr not in self._states:
                continue
            st2, info = self._epoch(bins)(
                self._states[attr], self.xs, self.ys, self.vals[attr],
                jnp.asarray(win), jnp.asarray(sel))
            self._states[attr] = st2
            n_split += int(info["n_split"])
            touched.add(attr)
        invalidated = 0
        for attr in touched:
            invalidated += self._invalidate_caches(attr)
        return {"epochs_published": len(staged), "tiles_split": n_split,
                "cache_rows_invalidated": invalidated}

    def _invalidate_caches(self, attr: str) -> int:
        """Drop registry rows of tiles no longer active in the
        published state (split parents); returns rows cleared."""
        active = np.asarray(self._states[attr].active)
        dropped = 0
        for key, cache in list(self._caches.items()):
            if key[0] != attr:
                continue
            valid = np.asarray(cache.valid)
            stale = valid & ~active
            if not stale.any():
                continue
            dropped += int(stale.sum())
            nvalid = jnp.asarray(valid & active)
            self._caches[key] = GroupedCache(
                cnt_tb=jnp.where(nvalid[:, None], cache.cnt_tb, 0.0),
                val_tb=jnp.where(nvalid[:, None], cache.val_tb, 0.0),
                valid=nvalid, window=cache.window)
        return dropped

    @property
    def n_active(self) -> Dict[str, int]:
        """Active tile count per attribute session (diagnostics)."""
        return {a: int(np.asarray(s.active).sum())
                for a, s in self._states.items()}

    # ------------------------- queries ------------------------------- #
    def query(self, window, attr: str, phi: float) -> QueryResult:
        """One φ-constrained scalar (sum) window aggregate over the
        session state; returns a :class:`QueryResult` (recorded in
        :attr:`trace`)."""
        t0 = time.perf_counter()
        win = jnp.asarray(window, jnp.float32)
        holder = {"state": self._state(attr)}

        def run_step(p):
            out = self._query_step(holder["state"], self.xs, self.ys,
                                   self.vals[attr], win,
                                   jnp.float32(p))
            return {key: np.asarray(v) for key, v in out.items()}

        # stateful_steps=False: the scalar step has no per-pass read
        # registry, so a same-φ re-selection would be byte-identical
        out, stats = EpochDriver(
            run_step, self._epoch_runner(holder, attr, (1, 1), win),
            phi, max_epochs=self.cfg.max_epochs,
            max_process=self.cfg.max_process, stateful_steps=False).run()
        self._states[attr] = holder["state"]
        r = QueryResult(
            agg="sum", attr=attr, value=float(out["value"]),
            lo=float(out["lo"]), hi=float(out["hi"]),
            bound=float(out["bound"]),
            exact=int(out["n_processed"]) >= int(out["n_partial"]),
            tiles_full=int(out["n_full"]),
            tiles_partial=int(out["n_partial"]),
            tiles_processed=stats.tiles_processed,
            objects_read=stats.objects_read, read_calls=stats.rounds,
            batch_rounds=stats.epochs,
            eval_time_s=time.perf_counter() - t0)
        self.trace.results.append(r)
        return r

    def heatmap(self, window, attr: str, bins: Tuple[int, int] = (8, 8),
                phi: float = 0.0, agg: str = "sum",
                policy: Optional[AccuracyPolicy] = None) -> HeatmapResult:
        """One φ-constrained heatmap (2-D group-by) query over the
        session state; returns a :class:`HeatmapResult` (flat per-bin
        arrays, empty min/max bins ±inf; recorded in :attr:`trace`).

        ``policy`` allocates the constraint per bin IN-SPMD: the step's
        prefix selection stops at ``τ_b = max(φ_b·|v_b|, ε_abs)`` and
        the stopping quantity becomes the φ-scaled worst budget ratio —
        the :class:`~repro.core.bounds.AccuracyPolicy` semantics of the
        host engine, vectorized over the mesh. A trivial policy (or
        φ = 0) runs the plain scalar-φ build, bit-for-bit the uniform
        selection."""
        t0 = time.perf_counter()
        bins = _check_bins(bins)
        nb = bins[0] * bins[1]
        with_policy = (policy is not None and phi > 0.0
                       and not policy.is_uniform())
        phi_b = (policy.phi_b(phi, bins).astype(np.float32)
                 if with_policy else None)
        eps_abs = float(policy.eps_abs) if with_policy else 0.0
        step = self._heatmap_step(bins, agg, with_policy)
        ckey = (attr, bins[0], bins[1], agg)
        if ckey not in self._caches:
            self._caches[ckey] = _empty_cache(self.cfg.capacity, nb)
        win = jnp.asarray(window, jnp.float32)
        holder = {"state": self._state(attr),
                  "cache": self._caches[ckey]}

        def run_step(p):
            if with_policy and p > 0.0:
                pb, ea = jnp.asarray(phi_b), jnp.float32(eps_abs)
            else:
                # the φ=0 fallback (and the uniform build) processes to
                # exactness — zeroed budgets, scalar test
                pb, ea = jnp.zeros((nb,), jnp.float32), jnp.float32(0.0)
            out, cache2 = step(holder["state"], holder["cache"], self.xs,
                               self.ys, self.vals[attr], win,
                               jnp.float32(p), pb, ea)
            holder["cache"] = cache2
            return {key: np.asarray(v) for key, v in out.items()}

        out, stats = EpochDriver(
            run_step, self._epoch_runner(holder, attr, bins, win), phi,
            max_epochs=self.cfg.max_epochs,
            max_process=self.cfg.max_process).run()
        self._states[attr] = holder["state"]
        self._caches[ckey] = holder["cache"]

        values = out["values"].astype(np.float64)
        lo = out["lo"].astype(np.float64)
        hi = out["hi"].astype(np.float64)
        bin_met = None
        if with_policy:
            # recompute the verdict against the USER's budgets: the
            # final pass may have been the φ=0 fallback, whose in-step
            # bin_met was evaluated under zeroed budgets
            occ = out["bin_count"] > 0
            with np.errstate(invalid="ignore"):
                dev = np.maximum(hi - values, values - lo)
            bin_met = bin_budgets_met(dev, values,
                                      phi_b.astype(np.float64), eps_abs,
                                      occ)
        if agg in ("min", "max"):
            # empty bins carry the f32 ±3.4e38 scatter sentinel in-SPMD;
            # map them to the HeatmapResult ±inf convention on host
            empty = out["bin_count"] == 0
            fill = np.inf if agg == "min" else -np.inf
            values = np.where(empty, fill, values)
            lo = np.where(empty, fill, lo)
            hi = np.where(empty, fill, hi)
        r = HeatmapResult(
            agg=agg, attr=attr, bins=bins, values=values, lo=lo, hi=hi,
            bin_bound=out["bin_bound"].astype(np.float64),
            bound=float(out["bound"]),
            exact=int(out["n_processed"]) >= int(out["n_partial"]),
            tiles_full=int(out["n_full"]),
            tiles_partial=int(out["n_partial"]),
            tiles_processed=stats.tiles_processed,
            objects_read=stats.objects_read, read_calls=stats.rounds,
            batch_rounds=stats.epochs,
            eval_time_s=time.perf_counter() - t0,
            phi_b=(phi_b.astype(np.float64) if with_policy else None),
            eps_abs=eps_abs, bin_met=bin_met)
        self.trace.results.append(r)
        return r

    def refine(self, attr: str, window=None,
               bins: Tuple[int, int] = (1, 1)) -> dict:
        """Force one refine epoch over the session state (all active
        tiles are candidates; ``window``/``bins`` control the snapping
        grid — default: even splits over the whole domain)."""
        state = self._state(attr)
        win = (jnp.asarray(window, jnp.float32) if window is not None
               else self.domain)
        st2, info = self._epoch(_check_bins(bins))(
            state, self.xs, self.ys, self.vals[attr], win, state.active)
        self._states[attr] = st2
        return {key: int(np.asarray(v)) for key, v in info.items()}
