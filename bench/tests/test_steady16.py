"""The 16-analyst serving cell at a small size on the CPU: a sound run
is correct, each planted fault and the control are refused, and the
generator sends every session its own drag, the same in every run."""
import time

import numpy as np
import pytest

from bench import correctness, harness, traffic
from bench.control import _Data, readings
from bench.data import synthetic
from bench.tests import test_faults

WORKLOAD = "dashboard_1e8.steady16"
ROWS = 200_000
# about two views a session: enough for a planted fault to move answers
# past a limit at this size, where most of a window is whole tiles
SECONDS = 4.0


def _run():
    return harness.run_cell(WORKLOAD, test_faults.SEED, SECONDS, False,
                            t_start=time.perf_counter(), require_chip=False,
                            overrides={"rows": ROWS})


def _stale(monkeypatch):
    """A tick's query step returns the previous answer unchanged."""
    from repro.core import serving

    last = {}
    build = serving._QueryRun.build_result

    def stale_build(self, *args, **kwargs):
        r = build(self, *args, **kwargs)
        prev, last["r"] = last.get("r"), r
        return prev if prev is not None else r
    monkeypatch.setattr(serving._QueryRun, "build_result", stale_build)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 16


@pytest.mark.parametrize("fault", ["altered", "half_left_out", "stale"])
def test_fault_is_not_correct(fault, monkeypatch):
    if fault == "stale":
        _stale(monkeypatch)
    else:
        test_faults._plant(monkeypatch, fault)
    out = _run()
    assert out["correct"] is False, out["checks"]


def test_control_is_refused():
    r = readings(WORKLOAD, 4_000_000_123, SECONDS, rows=ROWS)
    verdict = correctness.judge(r, correctness.limits(WORKLOAD))
    assert not verdict["correct"]
    assert r["outside"] > correctness.limits(WORKLOAD)["outside"]


CFG = {"rows": 60_000, "n_columns": 1, "n_clusters": 24,
       "cluster_frac": 0.7, "domain": 1000.0, "layout_seed": 2407}


def _schedule(seconds=20.0, **params):
    x, y, _ = synthetic.generate(CFG, 5)
    # ~600 objects keeps a window a small part of this small file
    return traffic.schedule("steady16", _Data(x, y), seconds,
                            {"target_objects": 600, **params})


def test_steady16_schedule():
    a, b = _schedule(), _schedule()
    assert a.views == b.views and a.first is None
    mix = traffic.load("steady16")
    sessions, rate = mix["sessions"], mix["rate"]
    assert a.params["sessions"] == sessions
    due = np.array([v.due_s for v in a.views])
    assert (np.diff(due) >= 0).all() and due[0] >= 0 and due[-1] < 20.0
    assert abs(len(a.views) - rate * 20.0) <= sessions
    by = {s: [v for v in a.views if v.session == s] for s in range(sessions)}
    # each session: one view per think time, from its own phase
    period = sessions / rate
    phases = []
    for s, vs in by.items():
        gaps = np.diff([v.due_s for v in vs])
        assert np.allclose(gaps, period)
        assert 0.0 <= vs[0].due_s < period
        phases.append(vs[0].due_s)
        assert [v.agg for v in vs[:3]] == ["mean", "sum", "mean"]
        # a drag moves 10-20 % of the side a step
        ws = np.array([v.window for v in vs])
        side = ws[0, 2] - ws[0, 0]
        step = np.hypot(np.diff(ws[:, 0]), np.diff(ws[:, 1])) / side
        assert step[:mix["path_views"] - 1].max() <= 0.2 + 1e-4
    assert len(set(phases)) == sessions
    # sessions do not share a path (two may start on one cell)
    paths = {tuple(v.window for v in vs[:3]) for vs in by.values()}
    assert len(paths) == sessions
    # the rate changes when views are sent, not where
    c = _schedule(rate=2 * rate)
    for s in range(sessions):
        mine = [v.window for v in c.views if v.session == s]
        assert mine[:len(by[s])] == [v.window for v in by[s]]
