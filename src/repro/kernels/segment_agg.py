"""Pallas TPU kernel: packed multi-segment aggregation — the one kernel.

Every device data-plane op of :mod:`repro.kernels.ops` reduces
``(count, sum, min, max)`` of the aggregate attribute per (segment,
cell) over ONE concatenated object stream:

- window ops (``segment_window_agg[_multi]``, ``window_agg``): one cell
  per segment, objects inside the segment's closed window — the exact
  in-window contribution of every tile of a refinement round;
- heatmap ops (``segment_window_bin_agg[_multi]`` and the fused
  selects of :mod:`repro.kernels.fused_select`): the ``bx × by`` grid
  laid over the segment's window, in-window objects only;
- split ops (``segment_bin_agg[_edges]``, ``bin_agg``): the segment's
  own ``gx × gy`` child cells — the metadata of every tile split in a
  round.

They differ only in the per-segment row of scalars the kernel reads
from SMEM: ``(x0, y0, x1, y1)`` when a window masks the segment, then
``gx−1`` x-thresholds and ``gy−1`` y-thresholds. Cell ids are
``Σ_j 1[x ≥ tx_j]`` (and likewise in y): compares only, no division,
so they are bit-identical to the f64 host mirrors' ownership rules on
any hardware (the thresholds come from ``ref.window_bin_thresholds``,
``ref.split_thresholds`` and ``ref.edge_thresholds``).

Layout: the ``pack2d`` planes of :mod:`repro.kernels.ops` — flat object
arrays padded to ``(rows, 128)`` f32 planes plus an int8 validity
plane, and one more f32 plane holding each object's *segment id*
(small integers, exactly representable). Segments are contiguous in
the stream, so on TPU this is one fully sequential HBM→VMEM stream;
the per-segment masks are VREG compares against the resident sid
plane. The grid, the planner and the lane-dense accumulator are in
:mod:`repro.kernels.gridplan`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .gridplan import (  # noqa: F401 — LANES/MAX_GROUP_CELLS re-exported
    LANES, DEFAULT_BLOCK_ROWS, MAX_GROUP_CELLS, acc_cell, acc_finalize,
    acc_init, acc_out, pad_flat, plan_cell_groups, plane_specs, smem_spec)

MAX_SEGMENTS = 64


def _make_kernel(group: int, ncol: int, window: bool, gx: int, gy: int,
                 with_sid: bool):
    k = gx * gy

    def kernel(par_ref, *refs):
        if with_sid:
            x_ref, y_ref, v_ref, sid_ref, valid_ref, out_ref = refs
        else:
            x_ref, y_ref, v_ref, valid_ref, out_ref = refs
        g = pl.program_id(0)    # cell group (outer)

        @pl.when(pl.program_id(1) == 0)
        def _init():
            acc_init(out_ref)

        def segment(t, carry):
            s = g * group + t
            base = s * ncol
            xs = x_ref[...]
            ys = y_ref[...]
            vs = v_ref[...]
            m = valid_ref[...] != 0
            if with_sid:
                m = m & (sid_ref[...] == s.astype(jnp.float32))
            col = 0
            if window:
                m = (m & (xs >= par_ref[base]) & (ys >= par_ref[base + 1])
                     & (xs <= par_ref[base + 2]) & (ys <= par_ref[base + 3]))
                col = 4
            if k == 1:
                acc_cell(out_ref, t, m, vs)
                return carry
            cx = jnp.zeros(xs.shape, jnp.int32)
            for j in range(gx - 1):
                cx = cx + (xs >= par_ref[base + col + j]).astype(jnp.int32)
            cy = jnp.zeros(ys.shape, jnp.int32)
            for j in range(gy - 1):
                cy = cy + (ys >= par_ref[base + col + gx - 1 + j]
                           ).astype(jnp.int32)
            cid = cy * gx + cx

            def cell(c, carry2):
                acc_cell(out_ref, t * k + c, m & (cid == c), vs)
                return carry2
            jax.lax.fori_loop(0, k, cell, 0)
            return carry

        jax.lax.fori_loop(0, group, segment, 0)
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("n_seg", "window", "gx", "gy",
                                    "block_rows", "seg_group", "interpret"))
def grouped_agg_pallas(params, xs2d, ys2d, vals2d, sid2d, valid2d, *,
                       n_seg, window, gx=1, gy=1,
                       block_rows=DEFAULT_BLOCK_ROWS, seg_group=None,
                       interpret=False):
    """Per-(segment, cell) ``(count, sum, min, max)`` in one kernel.

    Args:
      params: f32 ``(n_seg, ncol)`` per-segment scalar rows: the window
        ``(x0, y0, x1, y1)`` when ``window``, then ``gx−1`` x- and
        ``gy−1`` y-thresholds (see the module docstring).
      xs2d/ys2d/vals2d/sid2d: f32 ``(R, 128)`` planes (R a multiple of
        ``block_rows``); ``sid2d`` may be None for a single segment.
      valid2d: int8/bool ``(R, 128)``.
      seg_group: force the segments-per-program group size (tests use
        it to exercise the multi-group outer axis at small shapes).
    Returns:
      f32 ``(n_seg, gx·gy, 4)``; cell id = cy·gx + cx; empty cell ⇒
      ``(0, 0, +inf, −inf)``.
    """
    k = gx * gy
    assert n_seg <= MAX_SEGMENTS or sid2d is None, n_seg
    assert sid2d is not None or n_seg == 1, n_seg
    rows = xs2d.shape[0]
    assert rows % block_rows == 0, (rows, block_rows)
    group, n_groups, n_pad = plan_cell_groups(n_seg, k,
                                              block_rows=block_rows,
                                              group=seg_group)
    ncol = params.reshape(n_seg, -1).shape[1]
    # padded segments are never matched by any object's sid
    par = pad_flat(params, n_seg, n_pad)
    planes = [xs2d, ys2d, vals2d] + ([] if sid2d is None else [sid2d])
    planes = [p.astype(jnp.float32) for p in planes]
    out_spec, out_shape = acc_out(n_groups, group * k)
    out = pl.pallas_call(
        _make_kernel(group, ncol, window, gx, gy, sid2d is not None),
        grid=(n_groups, rows // block_rows),
        in_specs=[smem_spec()] + plane_specs(block_rows, len(planes) + 1),
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(par, *planes, valid2d.astype(jnp.int8))
    return acc_finalize(out).reshape(n_pad, k, 4)[:n_seg]
