"""Bytes that one call of a data-plane op needs, from its arguments.

On the device every public op of ``repro.kernels.ops`` runs one kernel,
``grouped_agg_pallas`` (the fused select ops add a small XLA epilogue
over its output, which is not the kernel's and is not counted). The
kernel streams the call's objects once: x, y and the aggregated value,
each float32, of the real rows (``boundaries[-1]``, before any bucket or
block padding). Besides, it reads one row of float32 scalars per
segment (the window corners when a window masks the segment, then
``gx - 1`` x- and ``gy - 1`` y-thresholds) and writes
``(count, sum, min, max)`` in float32 per segment and cell. Padding, the
segment-id and validity planes, and re-reading the stream for further
cell groups are the implementation's, not the algorithm's, and are not
counted.

The kernel does compares and adds only, so its least time on a chip is
these bytes over the chip's memory bandwidth.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

OBJECT_BYTES = 3 * 4          # x, y, value: float32 each
F32 = 4

# the public ops of repro.kernels.ops, and the device kernel each runs
KERNEL = {op: "grouped_agg" for op in (
    "window_agg", "bin_agg", "segment_window_agg",
    "segment_window_agg_multi", "segment_window_bin_agg",
    "segment_window_bin_agg_multi", "segment_bin_agg",
    "segment_bin_agg_edges", "segment_window_bin_select",
    "segment_window_bin_select_multi")}


def call_shape(op: str, args: tuple, kwargs: dict) -> Optional[dict]:
    """``{"rows", "segments", "gx", "gy", "window"}`` of one call, read
    from the op's arguments (the signatures of ``repro.kernels.ops``)."""
    if op not in KERNEL:
        return None
    if op in ("window_agg", "bin_agg"):
        n = kwargs.get("n")
        rows = len(args[0]) if n is None else int(n)
        if op == "window_agg":
            return {"rows": rows, "segments": 1, "gx": 1, "gy": 1,
                    "window": True}
        return {"rows": rows, "segments": 1, "gx": int(kwargs["gx"]),
                "gy": int(kwargs["gy"]), "window": False}
    boundaries = np.asarray(args[3])
    shape = {"rows": int(boundaries[-1]), "segments": len(boundaries) - 1,
             "gx": 1, "gy": 1, "window": True}
    if op == "segment_bin_agg":
        shape.update(gx=int(kwargs["gx"]), gy=int(kwargs["gy"]),
                     window=False)
    elif op == "segment_bin_agg_edges":
        shape.update(gx=np.shape(args[4])[1] - 1, gy=np.shape(args[5])[1] - 1,
                     window=False)
    elif "bin" in op:
        shape.update(gx=int(kwargs["bx"]), gy=int(kwargs["by"]))
    return shape


def bytes_needed(shape: dict) -> int:
    """Least bytes one call's kernel moves between memory and the chip."""
    s, gx, gy = shape["segments"], shape["gx"], shape["gy"]
    params = (4 if shape["window"] else 0) + (gx - 1) + (gy - 1)
    return int(shape["rows"] * OBJECT_BYTES + s * params * F32
               + s * gx * gy * 4 * F32)
