#!/usr/bin/env python3
"""The control of the correctness check: answers that break the
configuration's guarantee, which the check has to refuse.

The control is the plain reference put in the program's place and
computed one precision below the file's float32: coordinates, values
and window corners rounded to bfloat16, every answer claimed exact
(``lo = hi = value``), as a full scan would claim. It answers the views
of a cell's run, for each seed, and prints the numbers that
``bench/correctness.py`` compares, one JSON line per seed:

    python3 bench/control.py --workload paper_1e8.explore --seconds 30 \
        --seeds 11 12 13

Data and views are made from each seed exactly as a run of the cell
makes them. The program is not run. ``--rows`` runs the same at a
smaller size (the tests use it on the CPU).
"""
from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_answers(views, cref):
    from bench.reference import to_bfloat16

    out = []
    for v in views:
        w = tuple(float(c) for c in to_bfloat16(v.window))
        if v.kind == "query":
            value, _ = cref.scalar(w, v.agg, v.attr)
            out.append(types.SimpleNamespace(value=value, lo=value,
                                             hi=value))
        else:
            values, _ = cref.heatmap(w, v.agg, v.attr, v.bins)
            out.append(types.SimpleNamespace(values=values, lo=values,
                                             hi=values))
    return out


def readings(workload: str, seed: int, seconds: float, rows=None) -> dict:
    from bench import correctness, harness, traffic
    from bench.data import synthetic
    from bench.reference import Reference, to_bfloat16

    w, cfg = harness.cell(harness.benchmark(), workload)
    if rows is not None:
        cfg = {**cfg, "rows": int(rows)}
    x, y, cols = synthetic.generate(cfg, seed)
    sch = traffic.schedule(w["traffic"], _Data(x, y), seconds)
    views = ([sch.first] if sch.first else []) + sch.views
    attrs = {v.attr for v in views}
    ref = Reference(x, y, {a: cols[a] for a in attrs})
    cref = Reference(to_bfloat16(x), to_bfloat16(y),
                     {a: to_bfloat16(cols[a]) for a in attrs})
    nums = correctness.compare(views, control_answers(views, cref), ref)
    return {"workload": workload, "seed": seed, "views": len(views), **nums}


class _Data:
    """What a traffic generator reads of a file: its points and domain."""

    def __init__(self, x, y):
        self.x, self.y, self.n = x, y, len(x)
        self._domain = (float(x.min()), float(y.min()), float(x.max()),
                        float(y.max()))

    def domain(self):
        return self._domain


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, args.seconds,
                                  args.rows)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
