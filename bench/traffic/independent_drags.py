"""Many analysts, each dragging their own viewport: the paper's path once
per session, an open loop per session.

Each of ``sessions`` sessions runs its own copy of
``exploration_path``'s drag: a square window sized to hold
``target_objects`` objects at the file's global density; a drag of
``path_views`` views starts on a cell of a 24 x 24 histogram of the
points drawn in proportion to its count, and moves each step by
``U(shift_frac) * side`` in a uniform direction, clamped to the domain.
Session ``s`` draws its phase, starts and steps from its own stream of
the mix's ``path_seed``, so every run sends the same views over a file
drawn anew from its seed, and sessions do not share a path.

``rate`` is the aggregate views per second. Each session sends one
view every ``sessions / rate`` seconds (its think time), starting at a
phase drawn ``U(0, sessions / rate)``, so that arrivals are spread out
and not synchronised; a view is due at its send time whether or not
the session's previous answer has come (an open loop). Aggregates
cycle through ``aggs`` over ``attr`` at accuracy ``phi`` within each
session. There is no first view on the unindexed file.
"""
from __future__ import annotations

import math

import numpy as np

from . import Schedule, View, f32
from .exploration_path import BINS, cell_counts


def _drag_views(rng, spec, n, side, cdf, domain):
    """The first ``n`` windows of one session's drags."""
    x0d, y0d, x1d, y1d = domain
    cw, ch = (x1d - x0d) / BINS, (y1d - y0d) / BINS
    per_path = int(spec["path_views"])
    lo, hi = spec["shift_frac"]
    out = []
    for i in range(n):
        if i % per_path == 0:
            c = int(np.searchsorted(cdf, rng.uniform(), side="right"))
            ci, cj = divmod(min(c, BINS * BINS - 1), BINS)
            cx, cy = x0d + (ci + 0.5) * cw, y0d + (cj + 0.5) * ch
        wx0 = min(max(cx - side / 2, x0d), x1d - side)
        wy0 = min(max(cy - side / 2, y0d), y1d - side)
        out.append((f32(wx0), f32(wy0), f32(wx0 + side), f32(wy0 + side)))
        mag = rng.uniform(lo, hi) * side
        ang = rng.uniform(0, 2 * np.pi)
        cx = min(max(cx + mag * np.cos(ang), x0d + side / 2), x1d - side / 2)
        cy = min(max(cy + mag * np.sin(ang), y0d + side / 2), y1d - side / 2)
    return out


def schedule(spec, data, seconds):
    domain = data.domain()
    x0d, y0d, x1d, y1d = domain
    side = math.sqrt((x1d - x0d) * (y1d - y0d)
                     * spec["target_objects"] / data.n)
    cdf = np.cumsum(cell_counts(data.x, data.y, domain))
    cdf = cdf / cdf[-1]
    sessions = int(spec["sessions"])
    rate = float(spec["rate"])
    period = sessions / rate
    aggs = spec["aggs"]
    per_path = int(spec["path_views"])
    streams = np.random.SeedSequence(spec["path_seed"]).spawn(sessions)
    views, paths = [], 0
    for s, seq in enumerate(streams):
        rng = np.random.default_rng(seq)
        phase = rng.uniform(0.0, period)
        n = max(0, math.ceil((seconds - phase) / period - 1e-9))
        paths += -(-n // per_path)
        for j, w in enumerate(_drag_views(rng, spec, n, side, cdf, domain)):
            views.append(View(due_s=phase + j * period, session=s,
                              kind="query", window=w, agg=aggs[j % len(aggs)],
                              attr=spec["attr"], phi=float(spec["phi"])))
    views.sort(key=lambda v: (v.due_s, v.session))
    return Schedule(views=views, first=None,
                    params={"rate": rate, "sessions": sessions, "side": side,
                            "think_s": period, "paths": paths})
