"""Plain float64 reference answers for window aggregates and heatmaps.

Independent of the program: it imports nothing of ``repro`` and reads
only the generated columns. The answer to a view is a full scan of the
objects inside the closed window ``x0 <= x <= x1, y0 <= y <= y1``,
accumulated in float64. To keep hundreds of views short, the rows are
first bucketed once by a ``grid x grid`` cell of their own (a stable
counting sort), and a view scans only the rows of the cells that its
window overlaps; the answer is the same as a scan of every row.

Heatmap bins follow the binning contract the program documents for a
viewport grid: over the window's ``bx x by`` grid, with the cell width
``(x1 - x0) / bx`` worked out in float64 and rounded to float32, an
object's column is ``clip(floor((x - x0) / cw), 0, bx - 1)`` computed in
float32 arithmetic (likewise rows in y); bin id ``row * bx + column``.
Window corners are float32 values (the traffic rounds them so).
"""
from __future__ import annotations

import numpy as np


class Reference:
    def __init__(self, x, y, columns, grid: int = 128):
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.float32)
        self.grid = g = int(grid)
        self.x0, self.x1 = float(x.min()), float(x.max())
        self.y0, self.y1 = float(y.min()), float(y.max())
        self.sx = np.float32(g / max(self.x1 - self.x0, 1e-30))
        self.sy = np.float32(g / max(self.y1 - self.y0, 1e-30))
        key = self._cy(y) * g + self._cx(x)
        key = key.astype(np.uint16 if g <= 256 else np.uint32)
        order = np.argsort(key, kind="stable")
        self.starts = np.concatenate(
            [[0], np.cumsum(np.bincount(key, minlength=g * g))])
        del key
        self.x = x[order]
        self.y = y[order]
        self.cols = {k: np.asarray(v, np.float32)[order]
                     for k, v in columns.items()}

    # a bucket is a monotone function of the coordinate, so the buckets
    # from that of x0 to that of x1 hold every object with x0 <= x <= x1
    def _cx(self, x):
        c = (np.asarray(x, np.float32) - np.float32(self.x0)) * self.sx
        return np.clip(c, 0, self.grid - 1).astype(np.int32)

    def _cy(self, y):
        c = (np.asarray(y, np.float32) - np.float32(self.y0)) * self.sy
        return np.clip(c, 0, self.grid - 1).astype(np.int32)

    def _select(self, window, attr):
        """Coordinates and values of the objects inside ``window``."""
        x0, y0, x1, y1 = (float(v) for v in window)
        if x1 < self.x0 or x0 > self.x1 or y1 < self.y0 or y0 > self.y1:
            e = np.zeros(0, np.float32)
            return e, e, e
        cx0, cx1 = int(self._cx(x0)), int(self._cx(x1))
        cy0, cy1 = int(self._cy(y0)), int(self._cy(y1))
        g = self.grid
        spans = [(self.starts[cy * g + cx0], self.starts[cy * g + cx1 + 1])
                 for cy in range(cy0, cy1 + 1)]
        idx = np.concatenate([np.arange(a, b) for a, b in spans])
        xs, ys = self.x[idx], self.y[idx]
        m = (xs >= x0) & (xs <= x1) & (ys >= y0) & (ys <= y1)
        return xs[m], ys[m], self.cols[attr][idx][m]

    def scalar(self, window, agg: str, attr: str):
        """``(value, count)`` of ``agg`` over the window; an empty window
        gives 0."""
        _, _, v = self._select(window, attr)
        n = len(v)
        s = float(v.sum(dtype=np.float64))
        if agg == "count":
            return float(n), n
        if agg == "sum":
            return s, n
        if agg == "mean":
            return (s / n if n else 0.0), n
        raise ValueError(f"unsupported aggregate {agg!r}")

    def heatmap(self, window, agg: str, attr: str, bins):
        """``(values, counts)``, each ``(bx * by,)``; empty bins are 0."""
        xs, ys, v = self._select(window, attr)
        bx, by = int(bins[0]), int(bins[1])
        x0, y0, x1, y1 = (float(c) for c in window)
        cw = np.float32(max((x1 - x0) / bx, 1e-30))
        ch = np.float32(max((y1 - y0) / by, 1e-30))
        cx = np.clip(np.floor((xs - np.float32(x0)) / cw), 0, bx - 1)
        cy = np.clip(np.floor((ys - np.float32(y0)) / ch), 0, by - 1)
        cid = cy.astype(np.int64) * bx + cx.astype(np.int64)
        cnt = np.bincount(cid, minlength=bx * by)
        s = np.bincount(cid, weights=v.astype(np.float64),
                        minlength=bx * by)
        if agg == "count":
            return cnt.astype(np.float64), cnt
        if agg == "sum":
            return s, cnt
        if agg == "mean":
            return np.divide(s, cnt, out=np.zeros(bx * by),
                             where=cnt > 0), cnt
        raise ValueError(f"unsupported aggregate {agg!r}")


def to_bfloat16(a) -> np.ndarray:
    """``a`` rounded to bfloat16 (nearest, ties to even), held as float32:
    the precision one step below the file's float32."""
    b = np.ascontiguousarray(a, np.float32).view(np.uint32).astype(np.uint64)
    b = ((b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000).astype(np.uint32)
    return b.view(np.float32)
