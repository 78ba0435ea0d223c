"""Whether the answers of a run are correct: each compared with the plain
reference (``bench/reference.py``), once the window has closed.

The configuration's guarantee is the paper's: an answer carries an
interval ``[lo, hi]`` that holds the true aggregate, and its value lies
within ``phi * |value|`` of the truth (the relative error bound at most
``phi``, or the answer exact). Two numbers are compared, over every
answer of the run (the first answer and every view of the window) and
every occupied bin of a heatmap:

- ``outside``: how far the truth lies outside the answer's interval,
  ``max(0, lo - t, t - hi) / max(|t|, 1)``, the worst over answers;
- ``err_over_phi``: the error in units of the accuracy asked,
  ``|value - t| / (phi * |value|)``, the worst over answers; the
  guarantee is that it is at most 1.

``failed`` counts views that raised or gave no answer. The limits are in
``bench/limits/<cell>.json``; ``PERF.md`` gives the readings they were
set from.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
EPS = 1e-12


def limits(cell: str) -> dict:
    return json.loads((HERE / "limits" / f"{cell}.json").read_text())


def _answer(view, result, ref):
    """Truth, value, lo, hi (arrays over occupied bins for heatmaps)."""
    if view.kind == "query":
        t, _ = ref.scalar(view.window, view.agg, view.attr)
        return (np.array([t]), np.array([result.value]),
                np.array([result.lo]), np.array([result.hi]))
    t, cnt = ref.heatmap(view.window, view.agg, view.attr, view.bins)
    occ = cnt > 0
    return (t[occ], np.asarray(result.values, np.float64)[occ],
            np.asarray(result.lo, np.float64)[occ],
            np.asarray(result.hi, np.float64)[occ])


def compare(views, results, ref) -> dict:
    """The numbers compared, over ``views`` and their ``results`` (None
    where a view failed)."""
    outside = err = 0.0
    failed = 0
    for view, result in zip(views, results):
        if result is None:
            failed += 1
            continue
        t, v, lo, hi = _answer(view, result, ref)
        if not t.size:
            continue
        gap = np.maximum(np.maximum(lo - t, t - hi), 0.0)
        outside = max(outside, float(np.max(gap / np.maximum(np.abs(t), 1.0))))
        if view.phi > 0:
            e = np.abs(v - t) / (view.phi * np.maximum(np.abs(v), EPS))
            err = max(err, float(np.max(e)))
    return {"outside": outside, "err_over_phi": err, "failed": failed}


def judge(numbers: dict, lim: dict) -> dict:
    """``{name: {"value", "limit"}}`` of every compared number, and
    whether all of them are within their limits."""
    checks = {name: {"value": numbers[name], "limit": lim[name]}
              for name in ("outside", "err_over_phi")}
    checks["failed_views"] = {"value": numbers["failed"], "limit": 0}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return {"correct": ok, "checks": checks}
