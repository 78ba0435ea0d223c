#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload paper_1e8.explore --seed 7 \
        --seconds 30 --trace 0

Set-up (data from ``--seed`` on the device, the driver's engine, and a
warm-up that compiles every program the run uses) is timed as
``setup_s``; then the cell's traffic runs for ``--seconds``
seconds, the answers are checked against the plain reference, and the
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics read from a profiler trace
of the window), ``device``, ``breakdown`` (traced runs) and ``checks``
(each compared number with its limit, also the last lines of standard
error). Exits non-zero, printing no result, without the TPU chips the
cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the compile cache at a fixed path of this checkout, for the
    # program too (it keeps to JAX_COMPILATION_CACHE_DIR when set)
    CACHE.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    if out is None:
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
