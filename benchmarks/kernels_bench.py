"""B3 — data-plane kernel microbenchmarks.

Off the chip (``JAX_PLATFORMS=cpu``) the Pallas kernel executes in
interpret mode (the correctness path) and its wall time is NOT a TPU
number; on a TPU ``ops`` compiles it. Off the chip we therefore
benchmark (a) the jnp oracle under jit — the CPU stand-in whose data
movement matches the kernel — at full size, and (b) the Pallas kernel in
interpret mode at reduced size to document the validation cost. The
structural VMEM analysis (the 2-D grid plan of ``kernels/gridplan.py``
against the ~16 MiB budget) is printed alongside; device time is only
ever measured on the chip.

Every bandwidth row is also scored against the AQP-kernel roofline
(:func:`repro.launch.roofline.aqp_kernel_roofline`): these kernels do
O(1) FLOPs per streamed byte, so bytes/bandwidth is the floor and
``roofline_fraction`` = achieved/bound lands in the BENCH_*.json
artifact per backend. Under ``--smoke`` the jnp grouped path asserts
its bandwidth floor (the CI regression gate for the scatter_agg4
grouped-oracle rewrite; benchmarks/compare.py gates the rest against
the committed baseline).

Timing is min-of-reps: the benches share the container with the rest of
the CI lane, and the minimum is the least-contended estimate of the
kernel's actual cost (mean-of-reps regressed spuriously by 2× under
lane noise).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.kernels import ops
from repro.kernels.gridplan import (DEFAULT_BLOCK_ROWS, VMEM_BUDGET,
                                    plan_cell_groups, vmem_bytes)
from repro.launch.roofline import aqp_kernel_roofline

from . import common
from .common import emit

# the jnp grouped heatmap path's bandwidth floor on the 200K smoke
# shape: the pre-rewrite scatter baseline measured 0.40 GB/s, the
# scatter_agg4 masked-reduction rewrite ≥2× that (0.77–0.89 GB/s
# across device-staged min-of-reps runs on this container — the 0.80
# floor flaked on lane noise; 0.70 still fails any revert to 0.40)
MIN_GROUPED_JNP_GB_S = 0.70

# the fused jnp SEGMENT oracle's floor at the 16-cell (4 seg × 2×2)
# bench shape: the flat broadcast path measured 0.088 GB/s; the
# segment_bin_agg4 keyed rewrite (one-hot contraction for count+sum,
# class-stream sweeps only for min/max) measured 0.17 GB/s min-of-reps
# on this container — floor set with ~20% lane-noise headroom
MIN_FUSED_SELECT_JNP_GB_S = 0.14

# the MULTI-window fused jnp oracle (per-segment own window via the
# contract params, the serving-tick heatmap op) at the same 16-cell
# shape: the keyed segment_bin_agg4 core plus the per-point param
# gather and the span-suffix epilogue — 0.11 GB/s measured min-of-reps
# on this container; floor set with ~25% lane-noise headroom
MIN_FUSED_MULTI_JNP_GB_S = 0.08


def _sync(out):
    """Materialize a result (or tuple of results) on host."""
    for o in out if isinstance(out, tuple) else (out,):
        np.asarray(o)


def _time(fn, *args, reps=15, **kw):
    """Min-of-reps seconds per call (see module docstring)."""
    _sync(fn(*args, **kw))  # warmup/compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _sync(fn(*args, **kw))
        best = min(best, time.perf_counter() - t0)
    return best


def _klabel(n: int) -> str:
    """Row label suffix derived from the actual element count, so smoke
    rows can't be mistaken for full-size numbers in BENCH output."""
    return f"{n // 1000}K" if n < 1_000_000 else f"{n // 1_000_000}M"


def _bw_derived(n_bytes: int, t: float, backend: str, extra: str = ""):
    r = aqp_kernel_roofline(n_bytes, t, backend)
    s = (f"GB_s={r['achieved_GB_s']:.2f}"
         f";roofline_fraction={r['roofline_fraction']:.4f}"
         f";bound_GB_s={r['bound_GB_s']:.0f};backend={backend}")
    return (s + ";" + extra) if extra else s, r


def main():
    rng = np.random.default_rng(0)
    n = 200_000 if common.SMOKE else 1_000_000
    xs = rng.uniform(0, 1000, n).astype(np.float32)
    ys = rng.uniform(0, 1000, n).astype(np.float32)
    vs = rng.normal(0, 10, n).astype(np.float32)
    win = np.array([200, 200, 600, 600], np.float32)
    bbox = np.array([0, 0, 1000, 1000], np.float32)
    # device-staged copies for the jnp rows: jit's device_put can alias
    # np f32 buffers, but staging once removes even that bookkeeping
    # from the measured loop (~10% at 200K)
    xs_d, ys_d, vs_d = (jax.device_put(a) for a in (xs, ys, vs))
    nb3 = 3 * n * 4  # x, y, v planes streamed once

    t = _time(ops.window_agg, xs_d, ys_d, vs_d, win, backend="jnp")
    d, _ = _bw_derived(nb3, t, "jnp")
    emit(f"window_agg_jnp_{_klabel(n)}", t * 1e6, d)

    t = _time(ops.bin_agg, xs_d, ys_d, vs_d, bbox, gx=2, gy=2,
              backend="jnp")
    d, r = _bw_derived(nb3, t, "jnp")
    emit(f"bin_agg_jnp_{_klabel(n)}_2x2", t * 1e6, d)
    if common.SMOKE:
        assert r["achieved_GB_s"] >= MIN_GROUPED_JNP_GB_S, (
            f"jnp grouped path regressed: {r['achieved_GB_s']:.2f} GB/s "
            f"< {MIN_GROUPED_JNP_GB_S} floor on the smoke shape")

    t = _time(ops.window_agg, xs, ys, vs, win, backend="np")
    d, _ = _bw_derived(nb3, t, "np")
    emit(f"window_agg_np_{_klabel(n)}", t * 1e6, d)

    # --- fused selection megakernel (classify→scatter→select) ---
    # 4 tiles' concatenated segments + their pending value intervals:
    # the batched-refinement round shape
    n_seg = 4
    bounds = np.linspace(0, n, n_seg + 1).astype(np.int64)
    vmin_s = np.full(n_seg, -30.0)
    vmax_s = np.full(n_seg, 30.0)
    nb4 = 4 * n * 4  # + the segment-id plane

    t = _time(ops.segment_window_bin_select, xs, ys, vs, bounds, win,
              vmin_s, vmax_s, bx=2, by=2, backend="np")
    d, _ = _bw_derived(nb4, t, "np")
    emit(f"fused_select_np_{_klabel(n)}_4seg_2x2", t * 1e6, d)

    t = _time(ops.segment_window_bin_select, xs, ys, vs, bounds, win,
              vmin_s, vmax_s, bx=2, by=2, backend="jnp")
    d, r = _bw_derived(nb4, t, "jnp")
    emit(f"fused_select_jnp_{_klabel(n)}_4seg_2x2", t * 1e6, d)
    if common.SMOKE:
        assert r["achieved_GB_s"] >= MIN_FUSED_SELECT_JNP_GB_S, (
            f"fused jnp segment oracle regressed: "
            f"{r['achieved_GB_s']:.3f} GB/s "
            f"< {MIN_FUSED_SELECT_JNP_GB_S} floor on the smoke shape")

    # --- multi-window fused select (the serving-tick heatmap op):
    # per-segment OWN window + per-span suffix widths in one dispatch
    wins = np.stack([win + 40.0 * s for s in range(n_seg)]).astype(
        np.float32)
    qb = np.array([0, 2, n_seg], np.int64)   # two query spans

    t = _time(ops.segment_window_bin_select_multi, xs, ys, vs, bounds,
              wins, vmin_s, vmax_s, qbounds=qb, bx=2, by=2, backend="np")
    d, _ = _bw_derived(nb4, t, "np")
    emit(f"fused_multi_np_{_klabel(n)}_4seg_2x2", t * 1e6, d)

    t = _time(ops.segment_window_bin_select_multi, xs, ys, vs, bounds,
              wins, vmin_s, vmax_s, qbounds=qb, bx=2, by=2,
              backend="jnp")
    d, r = _bw_derived(nb4, t, "jnp")
    emit(f"fused_multi_jnp_{_klabel(n)}_4seg_2x2", t * 1e6, d)
    if common.SMOKE:
        assert r["achieved_GB_s"] >= MIN_FUSED_MULTI_JNP_GB_S, (
            f"fused multi-window jnp oracle regressed: "
            f"{r['achieved_GB_s']:.3f} GB/s "
            f"< {MIN_FUSED_MULTI_JNP_GB_S} floor on the smoke shape")

    n2 = 16_384 if common.SMOKE else 65_536
    b2 = np.linspace(0, n2, n_seg + 1).astype(np.int64)
    t = _time(ops.segment_window_bin_select, xs[:n2], ys[:n2], vs[:n2],
              b2, win, vmin_s, vmax_s, bx=2, by=2, backend="pallas",
              reps=2)
    emit(f"fused_select_pallas_interpret_{_klabel(n2)}_4seg_2x2", t * 1e6,
         "validation_path")

    t = _time(ops.segment_window_bin_select_multi, xs[:n2], ys[:n2],
              vs[:n2], b2, wins, vmin_s, vmax_s, bx=2, by=2,
              backend="pallas", reps=2)
    d, _ = _bw_derived(4 * n2 * 4, t, "pallas", "validation_path")
    emit(f"fused_multi_pallas_interpret_{_klabel(n2)}_4seg_2x2", t * 1e6,
         d)

    t = _time(ops.window_agg, xs[:n2], ys[:n2], vs[:n2], win,
              backend="pallas", reps=2)
    emit(f"window_agg_pallas_interpret_{_klabel(n2)}", t * 1e6,
         "validation_path")

    # --- structural VMEM sizing of the 2-D grid plan ---
    group, n_groups, _ = plan_cell_groups(n_seg, 4)
    vmem = vmem_bytes(DEFAULT_BLOCK_ROWS, group * 4)
    emit("fused_select_vmem_per_program", 0.0,
         f"bytes={vmem};group={group};n_groups={n_groups}"
         f";fits_16MiB={vmem < VMEM_BUDGET}")
    vmem = vmem_bytes(DEFAULT_BLOCK_ROWS, 1, n_planes=3)
    emit("window_agg_vmem_per_step", 0.0,
         f"bytes={vmem};fits_16MiB={vmem < 16*2**20}")
    return None


if __name__ == "__main__":
    main()
