"""One analyst dragging a viewport across the map: the paper's path, one
drag after another.

A copy of the program's ``exploration_path``, drawn ``path_views`` views
at a time: a square window sized to hold ``target_objects`` objects at
the file's global density, starting on a cell of a 24 x 24 histogram of
the points, and moved each step by ``U(shift_frac) * side`` in a
uniform direction, clamped to the domain. The program's path starts on
the densest cell; here each drag starts on a cell drawn in proportion to
its count (where the data is, with the densest cell the likeliest), so
that a window holds several drags over different regions and its
numbers do not rest on one path. Starts and steps are drawn from the
mix's own ``path_seed``: every run sends the same drags over a file
drawn anew from its seed, so the seed does not change how much work
they ask for, and a longer window only appends drags.

The first window is the first answer on the unindexed file; the next
``rate * seconds`` windows are due every ``1 / rate`` seconds of the
window, an open loop. Aggregates cycle through ``aggs`` over ``attr`` at
accuracy ``phi``.
"""
from __future__ import annotations

import math

import numpy as np

from . import Schedule, View, f32

BINS = 24


def _cell(v, lo, hi, bins):
    c = (v - np.float32(lo)) * np.float32(bins / (hi - lo))
    c = c.astype(np.int32)
    np.minimum(c, bins - 1, out=c)
    return c


def cell_counts(x, y, domain, bins: int = BINS):
    """Points per cell of a ``bins x bins`` histogram over the domain, as
    ``np.histogram2d(x, y, bins).ravel()`` counts them."""
    x0, y0, x1, y1 = domain
    key = _cell(x, x0, x1, bins)
    key *= bins
    key += _cell(y, y0, y1, bins)
    return np.bincount(key, minlength=bins * bins)


def schedule(spec, data, seconds):
    rng = np.random.default_rng(spec["path_seed"])
    x0d, y0d, x1d, y1d = data.domain()
    side = math.sqrt((x1d - x0d) * (y1d - y0d)
                     * spec["target_objects"] / data.n)
    cdf = np.cumsum(cell_counts(data.x, data.y, (x0d, y0d, x1d, y1d)))
    cdf = cdf / cdf[-1]
    cw, ch = (x1d - x0d) / BINS, (y1d - y0d) / BINS
    rate = float(spec["rate"])
    n = 1 + int(math.floor(rate * seconds + 1e-9))
    per_path = int(spec["path_views"])
    lo, hi = spec["shift_frac"]
    aggs = spec["aggs"]
    views = []
    for i in range(n):
        if i % per_path == 0:
            c = int(np.searchsorted(cdf, rng.uniform(), side="right"))
            ci, cj = divmod(min(c, BINS * BINS - 1), BINS)
            cx, cy = x0d + (ci + 0.5) * cw, y0d + (cj + 0.5) * ch
        wx0 = min(max(cx - side / 2, x0d), x1d - side)
        wy0 = min(max(cy - side / 2, y0d), y1d - side)
        window = (f32(wx0), f32(wy0), f32(wx0 + side), f32(wy0 + side))
        views.append(View(due_s=(i - 1) / rate, session=0, kind="query",
                          window=window, agg=aggs[i % len(aggs)],
                          attr=spec["attr"], phi=float(spec["phi"])))
        mag = rng.uniform(lo, hi) * side
        ang = rng.uniform(0, 2 * np.pi)
        cx = min(max(cx + mag * np.cos(ang), x0d + side / 2), x1d - side / 2)
        cy = min(max(cy + mag * np.sin(ang), y0d + side / 2), y1d - side / 2)
    return Schedule(views=views[1:], first=views[0],
                    params={"rate": rate, "side": side,
                            "paths": -(-n // per_path)})
