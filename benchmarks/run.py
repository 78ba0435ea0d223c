"""Benchmark harness — one entry per paper table/figure/claim.

Prints ``name,us_per_call,derived`` CSV rows (B1–B9) and writes them
to ``experiments/BENCH_{smoke,full}.json``.

    PYTHONPATH=src python -m benchmarks.run            # full size
    PYTHONPATH=src python -m benchmarks.run --smoke    # tiny-n CI smoke

``--smoke`` runs every benchmark at toy size (120 K rows, 12-query
paths) so CI exercises B1–B9 end-to-end each push — the numbers are
meaningless, the code paths are not. B7 (serving_concurrency) carries
hard acceptance gates: φ-containment on every served answer and
bit-for-bit parity of a micro-batched tick vs the sequential reference.
B9 (predictive_exploration) gates the predictive pre-cracking claim:
at equal total I/O, the predicted arm's p99 query-time reads must beat
the reactive arm's on the linear-pan script, with φ=0 answers
bit-identical.
"""
from __future__ import annotations

import json
import os
import platform
import sys
import time


def main(smoke: bool = False) -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # smoke config must land BEFORE the benchmark modules bind their
    # imported constants
    from . import common
    if smoke:
        common.configure_smoke()
    print("name,us_per_call,derived")
    from . import (accuracy_sweep, adaptation_cost, fig2_exploration,
                   heatmap_exploration, kernels_bench, objects_read,
                   predictive_exploration, serving_concurrency,
                   streaming_exploration)
    os.makedirs("experiments", exist_ok=True)
    fig2_exploration.main(save_csv="experiments/fig2.csv")
    objects_read.main()
    kernels_bench.main()
    accuracy_sweep.main()
    adaptation_cost.main()
    heatmap_exploration.main()
    serving_concurrency.main()
    streaming_exploration.main()
    predictive_exploration.main()

    # persist the full sweep: CI uploads experiments/BENCH_*.json as a
    # workflow artifact so regressions are diffable across pushes
    out = {
        "smoke": smoke,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "python": platform.python_version(),
        "platform": platform.platform(),
        "rows": common.EMITTED,
    }
    path = os.path.join(
        "experiments", f"BENCH_{'smoke' if smoke else 'full'}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# wrote {path} ({len(common.EMITTED)} rows)")


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
