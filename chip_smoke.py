#!/usr/bin/env python3
"""Drive the system's main path once on a TPU and check every answer.

    python chip_smoke.py              # one chip: host engine, serving tick,
                                      # SPMD session on a (1,) mesh
    python chip_smoke.py --chips 4    # only the SPMD session, on a (4,) mesh

Data is the paper's synthetic file (10 numeric columns, clustered
points), generated from ``--seed`` at ``--rows`` rows (default 10^8, the
paper's 11 GB file). Every answer is checked against the engines' f64
full-scan oracle: the oracle must lie inside ``[lo, hi]`` and the
accuracy bound must be met (or the answer exact). Any miss, any phase
failure, or a platform other than TPU exits non-zero. The last line of
standard output is ``{"ok": true, "device": {...}}``.

The script uses one process and JAX's persistent compilation cache
(``repro.compile_cache``): a second run compiles less.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import AQPEngine, IndexConfig  # noqa: E402
from repro.core import query as query_mod  # noqa: E402
from repro.core.distributed import DistributedAQPEngine  # noqa: E402
from repro.data import make_synthetic_dataset  # noqa: E402
from repro.data.synthetic import exploration_path  # noqa: E402
from repro.kernels import ops  # noqa: E402

ATTR = "a0"
BINS = (8, 8)
PHI = 0.05
# f32 device sums against the f64 oracle: the slack the device-backend
# tests allow (tests/test_distributed.py), relative + absolute per agg
RTOL = 1e-4
ATOL = {"sum": 1.0, "mean": 1e-3}
EXACT_RTOL = 1e-3


def _require(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _oracle(ds):
    """The engines' f64 full-scan oracle over ``ds`` (query.py)."""
    return types.SimpleNamespace(ds=ds)


def check_scalar(r, truth, phi, what):
    eps = RTOL * abs(truth) + ATOL[r.agg]
    _require(r.lo - eps <= truth <= r.hi + eps,
             f"{what}: oracle {truth!r} outside [{r.lo!r}, {r.hi!r}]")
    _require(r.exact or r.bound <= phi + 1e-6,
             f"{what}: bound {r.bound!r} > phi {phi}")
    if phi == 0.0:
        _require(abs(r.value - truth)
                 <= EXACT_RTOL * abs(truth) + ATOL[r.agg],
                 f"{what}: exact answer {r.value!r} != oracle {truth!r}")


def check_heatmap(h, truth, occupied, phi, what):
    eps = RTOL * np.abs(truth) + ATOL[h.agg]
    lo, hi = h.lo[occupied], h.hi[occupied]
    t, e = truth[occupied], eps[occupied]
    _require(bool(np.all(lo - e <= t) and np.all(t <= hi + e)),
             f"{what}: oracle outside [lo, hi] in "
             f"{int(np.sum((lo - e > t) | (t > hi + e)))} bins")
    _require(h.exact or h.bound <= phi + 1e-6,
             f"{what}: bound {h.bound!r} > phi {phi}")


def make_data(rows: int, seed: int, n_windows: int):
    ds = make_synthetic_dataset(n=rows, n_columns=10, seed=seed)
    windows = exploration_path(ds, n_queries=n_windows,
                               target_objects=min(100_000, rows // 20),
                               seed=seed + 1)
    return ds, windows


def host_phase(ds, windows, n_scalar=6, backend=None):
    """``AQPEngine`` along the exploration path: scalar sum/mean at
    φ=0.05 and φ=0 on ``n_scalar`` windows, then 8×8 sum and mean
    heatmaps at φ=0.05 on the next two. ``backend`` pins the index's
    data plane (default: resolved from the platform)."""
    eng = AQPEngine(ds, IndexConfig(init_metadata_attrs=(ATTR,),
                                    backend=backend))
    oracle = _oracle(ds)
    checked = 0
    for i, w in enumerate(windows[:n_scalar]):
        for agg in ("sum", "mean"):
            for phi in (PHI, 0.0):
                r = eng.query(w, agg, ATTR, phi=phi)
                check_scalar(r, query_mod.evaluate_oracle(
                    oracle, w, agg, ATTR), phi,
                    f"host {agg} phi={phi} w{i}")
                checked += 1
    for i, (w, agg) in enumerate(zip(windows[n_scalar:n_scalar + 2],
                                     ("sum", "mean"))):
        h = eng.heatmap(w, agg, ATTR, bins=BINS, phi=PHI)
        truth = query_mod.evaluate_heatmap_oracle(oracle, w, agg, ATTR,
                                                  BINS)
        occ = query_mod.evaluate_heatmap_oracle(oracle, w, "count", ATTR,
                                                BINS) > 0
        check_heatmap(h, truth, occ, PHI, f"host heatmap {agg} w{i}")
        checked += 1
    return eng, {"answers_checked": checked,
                 "backend": eng.index._backend,
                 "kernel_calls": eng.adapt_stats.kernel_calls,
                 "rows_read": eng.io_stats.rows_read}


def serving_phase(eng, windows, n_sessions=4, n_ticks=3):
    """``eng.serve()``: ``n_sessions`` sessions, ``n_ticks`` ticks, each
    tick a mix of scalar and 8×8 heatmap tickets on fresh viewports."""
    oracle = _oracle(eng.dataset)
    srv = eng.serve()
    sessions = [srv.open_session() for _ in range(n_sessions)]
    calls0 = eng.adapt_stats.kernel_calls
    checked = 0
    for t in range(n_ticks):
        sent = []
        for i, s in enumerate(sessions):
            w = windows[t * n_sessions + i]
            agg = ("sum", "mean")[i % 2]
            if (i + t) % 2 == 0:
                s.query(w, agg, ATTR, phi=PHI)
                sent.append(("query", w, agg))
            else:
                s.heatmap(w, agg, ATTR, bins=BINS, phi=PHI)
                sent.append(("heatmap", w, agg))
        results = srv.tick()
        _require(len(results) == len(sent), "tick lost a ticket")
        for (kind, w, agg), r in zip(sent, results):
            what = f"serving tick {t} {kind} {agg}"
            if kind == "query":
                check_scalar(r, query_mod.evaluate_oracle(
                    oracle, w, agg, ATTR), PHI, what)
            else:
                truth = query_mod.evaluate_heatmap_oracle(oracle, w, agg,
                                                          ATTR, BINS)
                occ = query_mod.evaluate_heatmap_oracle(
                    oracle, w, "count", ATTR, BINS) > 0
                check_heatmap(r, truth, occ, PHI, what)
            checked += 1
    return {"answers_checked": checked, "ticks": n_ticks,
            "sessions": n_sessions,
            "kernel_calls": eng.adapt_stats.kernel_calls - calls0}


def spmd_phase(ds, windows, devices):
    """``DistributedAQPEngine`` on a ``(len(devices),)`` mesh: scalar
    sums at φ=0.05 and φ=0 on three windows, one 8×8 sum heatmap."""
    mesh = jax.sharding.Mesh(np.array(devices), ("data",))
    dq = DistributedAQPEngine(ds, mesh)
    placement = sorted({int(s.device.id)
                        for s in dq.xs.addressable_shards})
    _require(len(placement) == len(devices),
             f"object shards on devices {placement}, expected "
             f"{len(devices)} distinct")
    n = len(dq.xs)
    _require(n == ds.n, f"SPMD truncated {ds.n} rows to {n}")
    oracle = _oracle(ds)
    checked = 0
    for i, w in enumerate(windows[:3]):
        for phi in (PHI, 0.0):
            r = dq.query(w, ATTR, phi)
            check_scalar(r, query_mod.evaluate_oracle(oracle, w, "sum",
                                                      ATTR),
                         phi, f"spmd sum phi={phi} w{i}")
            checked += 1
    w = windows[3]
    h = dq.heatmap(w, ATTR, bins=BINS, phi=PHI, agg="sum")
    truth = query_mod.evaluate_heatmap_oracle(oracle, w, "sum", ATTR, BINS)
    occ = query_mod.evaluate_heatmap_oracle(oracle, w, "count", ATTR,
                                            BINS) > 0
    check_heatmap(h, truth, occ, PHI, "spmd heatmap sum")
    checked += 1
    return {"answers_checked": checked, "shard_devices": placement,
            "rows_per_shard": n // len(devices)}


class CompileCounter:
    """Counts compile requests and persistent-cache hits (JAX
    monitoring events); compilations = requests − hits."""

    def __init__(self):
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits


def _phase(name, counter, fn, *args):
    r0, h0 = counter.snapshot()
    t0 = time.perf_counter()
    out = fn(*args)
    secs = time.perf_counter() - t0
    r1, h1 = counter.snapshot()
    stats = jax.devices()[0].memory_stats() or {}
    report = out[1] if isinstance(out, tuple) else out
    line = {"phase": name, "seconds": round(secs, 3),
            "compilations": (r1 - r0) - (h1 - h0),
            "cache_hits": h1 - h0,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    if isinstance(report, dict):
        line.update(report)
    print(json.dumps(line), flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rows", type=int, default=100_000_000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(dev) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(dev)} devices",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    counter = CompileCounter()
    print(json.dumps({"rows": args.rows, "columns": 10, "seed": args.seed,
                      "chips": args.chips, "compile_cache": cache,
                      "index_backend": ops.index_backend(),
                      "interpret": ops.resolve_interpret()}), flush=True)
    _require(ops.index_backend() == "pallas" and not ops.resolve_interpret(),
             "the device data plane did not resolve to compiled Pallas")

    ds, windows = _phase("data", counter, make_data, args.rows, args.seed,
                         20)
    if args.chips == 1:
        eng, host = _phase("host_engine", counter, host_phase, ds, windows)
        _require(host["backend"] == "pallas" and host["kernel_calls"] > 0,
                 f"host engine made no compiled kernel calls: {host}")
        serve = _phase("serving_tick", counter, serving_phase, eng,
                       windows[8:])
        _require(serve["kernel_calls"] > 0, "serving made no kernel calls")
        del eng
    _phase("spmd_session", counter, spmd_phase, ds, windows,
           dev[:args.chips])
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
