"""A run drives the program with its timed path broken underneath, at a
small size on the CPU (the harness's look for a chip skipped), and its
check has to come out not correct. One case per fault a cell can have:

- ``altered``: every aggregate a kernel call produces has its sum
  altered where it is produced;
- ``half_left_out``: every kernel call sees half of its batch (every
  other object) and aggregates over the rest;
- ``stale``: a query step returns the previous answer unchanged.

The cell runs on one chip, so there is no exchange between chips to
leave out.
"""
import time

import numpy as np
import pytest

from bench import harness, opcost

ROWS = 200_000
SEED = 2_718_281_828_459
WORKLOADS = ["paper_1e8.explore"]


def _run(workload):
    return harness.run_cell(workload, SEED, 2.0, False,
                            t_start=time.perf_counter(), require_chip=False,
                            overrides={"rows": ROWS})


def _altered(fn):
    def call(*args, **kwargs):
        out = fn(*args, **kwargs)
        agg = np.array(out[0] if isinstance(out, tuple) else out)
        agg[..., 1] *= 1.5
        return (agg, *out[1:]) if isinstance(out, tuple) else agg
    return call


def _half_left_out(name, fn):
    def call(*args, **kwargs):
        xs, ys, vals = (np.asarray(a)[::2] for a in args[:3])
        if name in ("window_agg", "bin_agg"):
            if kwargs.get("n") is not None:
                kwargs["n"] = (kwargs["n"] + 1) // 2
            return fn(xs, ys, vals, *args[3:], **kwargs)
        bounds = (np.asarray(args[3]) + 1) // 2
        return fn(xs, ys, vals, bounds, *args[4:], **kwargs)
    return call


def _plant(monkeypatch, fault):
    from repro.core import query
    from repro.kernels import ops

    if fault in ("altered", "half_left_out"):
        for name in opcost.KERNEL:
            fn = getattr(ops, name)
            monkeypatch.setattr(ops, name, _altered(fn) if fault == "altered"
                                else _half_left_out(name, fn))
        return
    last = {}
    evaluate = query.evaluate

    def stale_evaluate(*args, **kwargs):
        r = evaluate(*args, **kwargs)
        prev, last["r"] = last.get("r"), r
        return prev if prev is not None else r
    monkeypatch.setattr(query, "evaluate", stale_evaluate)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    out = _run(workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


@pytest.mark.parametrize("fault", ["altered", "half_left_out", "stale"])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_fault_is_not_correct(workload, fault, monkeypatch):
    _plant(monkeypatch, fault)
    out = _run(workload)
    assert out["correct"] is False, out["checks"]
