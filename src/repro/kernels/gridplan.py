"""Grid planning and the shared accumulator of the grouped kernel family.

Every kernel in this package streams ``(block_rows, 128)`` object planes
over a 2-D ``(cell_groups, row_blocks)`` grid and reduces
``(count, sum, min, max)`` per (segment, cell):

- the OUTER grid axis walks *cell groups* — contiguous runs of
  ``group`` segments holding ``group · k ≤ MAX_GROUP_CELLS`` cells — so
  a call may carry arbitrarily many segments without growing any one
  program's resident output block;
- the MINOR grid axis walks row blocks with the group's output block
  mapped to the SAME location every step, so the block stays
  VMEM-resident and is accumulated in-kernel (``@pl.when(r == 0)``
  init, then one update per row block) — no partial slab, no
  host-side reduction.

The accumulator is lane-dense (:func:`acc_out`): the resident block is
``(1, 4, cells, 128)`` f32, channel-major, one 128-lane row per cell.
Each row block folds a cell's masked reductions over the SUBLANE axis
only (:func:`acc_cell` — VPU adds, min and max), and the 128 lane
partials are reduced once, after the kernel, by XLA inside the same jit
(:func:`acc_finalize`). The TPU compiler refuses per-cell scalar
read-modify-writes into VMEM ("Cannot store scalars to VMEM"); lane rows
are plain vector stores. Counts stay exact (integers below 2**24 in
f32) and extrema exact; sums are f32 in a different order than the f64
host mirrors, within the usual device tolerance.

Per-segment scalars (window corners and cell thresholds) are
whole flat arrays in SMEM (:func:`smem_spec`): the scalar unit reads
them at dynamic offsets; VMEM holds only vectors. The segment and cell
loops are ``lax.fori_loop`` s, not Python unrolls, so compile time does
not grow with ``group · k`` (a 512-cell static unroll took ~30 s to
compile for a v5e; the loop compiles in well under a second).

The plan is sized against the ~16 MiB default scoped VMEM of a v5e
core: per program the resident set is the streamed f32 operand planes +
the int8 validity plane (×2 for double buffering) + the group's
double-buffered output block.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 256
MAX_GROUP_CELLS = 512       # bound on group·k cells per program
VMEM_BUDGET = 16 * 2**20    # v5e default scoped VMEM per core


def vmem_bytes(block_rows: int = DEFAULT_BLOCK_ROWS, cells: int = 1,
               n_planes: int = 4) -> int:
    """Resident VMEM bytes of one grouped-kernel program.

    ``n_planes`` f32 operand planes of ``(block_rows, LANES)`` plus one
    int8 validity plane, and the ``(4, cells, LANES)`` f32 accumulator
    block — each ×2 for the pipeline's double buffers. Per-segment
    parameters live in SMEM and are not counted.
    """
    streams = block_rows * LANES * (n_planes * 4 + 1)
    out = 4 * cells * LANES * 4
    return 2 * (streams + out)


def plan_cell_groups(n_seg: int, k: int, *,
                     max_cells: int = MAX_GROUP_CELLS,
                     block_rows: int = DEFAULT_BLOCK_ROWS,
                     n_planes: int = 4,
                     budget: int = VMEM_BUDGET,
                     group: int | None = None) -> Tuple[int, int, int]:
    """Size the outer (cell-group) grid axis for a grouped kernel call.

    Returns ``(group, n_groups, n_seg_pad)``: ``group`` segments per
    program (``group · k ≤ max_cells`` and the program's
    :func:`vmem_bytes` fits ``budget``), ``n_groups`` programs on the
    outer axis, and ``n_seg_pad = group · n_groups`` (callers pad their
    per-segment parameter arrays to this row count; padded rows are
    never matched by any object's segment id and are sliced off the
    result). ``group`` may be forced (tests use it to exercise the
    multi-group path at small shapes).
    """
    if n_seg <= 0 or k <= 0:
        raise ValueError(f"need n_seg > 0 and k > 0, got {n_seg}, {k}")
    if k > max_cells:
        raise ValueError(f"k={k} cells per segment exceeds the "
                         f"per-program cell bound {max_cells}")
    if group is None:
        group = max(1, min(n_seg, max_cells // k))
        while group > 1 and vmem_bytes(block_rows, group * k,
                                       n_planes) > budget:
            group -= 1
    else:
        group = max(1, min(int(group), n_seg))
        assert group * k <= max_cells, (group, k)
    n_groups = -(-n_seg // group)
    return group, n_groups, group * n_groups


# --------------------------------------------------------------------- #
# the shared lane-dense accumulator
# --------------------------------------------------------------------- #

def smem_spec():
    """Whole-array SMEM operand: a flat f32 table of per-segment
    scalars, read at dynamic offsets by the scalar unit."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def plane_specs(block_rows: int, n: int):
    """BlockSpecs of ``n`` streamed object planes: row block r of the
    minor axis, re-streamed for every cell group g."""
    return [pl.BlockSpec((block_rows, LANES), lambda g, r: (r, 0))
            for _ in range(n)]


def acc_out(n_groups: int, cells: int):
    """``(out_spec, out_shape)`` of the resident accumulator: group g
    owns block ``(1, 4, cells, 128)``, the same block on every row
    step."""
    spec = pl.BlockSpec((1, 4, cells, LANES), lambda g, r: (g, 0, 0, 0))
    shape = jax.ShapeDtypeStruct((n_groups, 4, cells, LANES), jnp.float32)
    return spec, shape


def acc_init(out_ref):
    """Write the (count, sum, min, max) neutral element to the whole
    resident block — run under ``@pl.when(r == 0)``."""
    shp = out_ref.shape[2:]
    out_ref[0, 0] = jnp.zeros(shp, jnp.float32)
    out_ref[0, 1] = jnp.zeros(shp, jnp.float32)
    out_ref[0, 2] = jnp.full(shp, jnp.inf, jnp.float32)
    out_ref[0, 3] = jnp.full(shp, -jnp.inf, jnp.float32)


def acc_cell(out_ref, i, m, vs):
    """Fold one row block's masked reductions into cell ``i``'s lane
    row (``i`` may be traced): sublane reductions only, the lane axis
    stays partial until :func:`acc_finalize`."""
    row = pl.ds(i, 1)
    out_ref[0, 0, row, :] += jnp.sum(m.astype(jnp.float32), axis=0,
                                     keepdims=True)
    out_ref[0, 1, row, :] += jnp.sum(jnp.where(m, vs, 0.0), axis=0,
                                     keepdims=True)
    out_ref[0, 2, row, :] = jnp.minimum(
        out_ref[0, 2, row, :],
        jnp.min(jnp.where(m, vs, jnp.inf), axis=0, keepdims=True))
    out_ref[0, 3, row, :] = jnp.maximum(
        out_ref[0, 3, row, :],
        jnp.max(jnp.where(m, vs, -jnp.inf), axis=0, keepdims=True))


def acc_finalize(out):
    """Lane epilogue: ``(n_groups, 4, cells, 128)`` partials →
    ``(n_groups · cells, 4)`` (count, sum, min, max) rows."""
    res = jnp.stack([out[:, 0].sum(-1), out[:, 1].sum(-1),
                     out[:, 2].min(-1), out[:, 3].max(-1)], axis=-1)
    return res.reshape(-1, 4)


def pad_flat(a, n_seg: int, n_pad: int):
    """Zero-pad a per-segment parameter table ``(n_seg, c)`` to
    ``n_pad`` rows and flatten it for :func:`smem_spec`."""
    a = a.reshape(n_seg, -1).astype(jnp.float32)
    if n_pad > n_seg:
        a = jnp.concatenate(
            [a, jnp.zeros((n_pad - n_seg, a.shape[1]), jnp.float32)])
    return a.reshape(-1)
