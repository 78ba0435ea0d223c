"""The trace reduction on hand-made event lists with known answers, and
on a small trace recorded on one v5e (``data/trace_v5e.json``)."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce

HERE = Path(__file__).resolve().parent
PATTERNS = {"grouped_agg": "^%kern"}


def _ms(v):
    return v * 1e6          # ms -> ns


def test_innermost_partition():
    spans = [(_ms(1), _ms(9), "view"), (_ms(2), _ms(4), "read_values"),
             (_ms(5), _ms(6), "ops.x")]
    segs = trace_reduce.innermost(spans, 0.0, _ms(10))
    assert [(s / 1e6, e / 1e6, n) for s, e, n in segs] == [
        (0, 1, "no_span"), (1, 2, "view"), (2, 4, "read_values"),
        (4, 5, "view"), (5, 6, "ops.x"), (6, 9, "view"), (9, 10, "no_span")]


def test_reduce_busy_kernels_and_gaps():
    ex = {
        "host": [
            ["window", _ms(0), _ms(10), "main"],
            ["view", _ms(1), _ms(8), "main"],
            ["read_values", _ms(2), _ms(2), "main"],
            ["view", _ms(0), _ms(3), "other thread"],     # not the window's
        ],
        "device": {"/device:TPU:0": [
            ["%kern.1 = f32[8] custom-call()", _ms(4), _ms(1)],
            ["%fusion.1 = f32[8] fusion()", _ms(4.5), _ms(1)],   # overlaps
            ["%kern.2 = f32[8] custom-call()", _ms(7), _ms(0.5)],
            ["%copy = f32[8] copy()", _ms(-1), _ms(1.5)],   # clipped to 0.5
            ["%late.3 = f32[8] copy()", _ms(9.5), _ms(2)],  # clipped to 0.5
        ]},
    }
    r = trace_reduce.reduce(ex, PATTERNS)
    assert r["window_s"] == pytest.approx(0.010)
    # busy: [0, 0.5] + [4, 5.5] + [7, 7.5] + [9.5, 10] = 3 ms
    assert r["busy_s"] == pytest.approx(0.003)
    assert r["kernel_s"] == {"grouped_agg": pytest.approx(0.0015)}
    assert r["device_ops"][0] == ["kern", pytest.approx(0.0015)]
    assert dict(r["device_ops"])["fusion"] == pytest.approx(0.001)
    gaps = dict(r["idle_gaps"])
    # idle: [0.5, 1] none, [1, 2] view, [2, 4] read_values, [5.5, 7]
    # view, [7.5, 9] view, [9, 9.5] none
    assert gaps == {"read_values": pytest.approx(0.002),
                    "view": pytest.approx(0.004),
                    "no_span": pytest.approx(0.001)}


def test_reduce_without_window():
    assert trace_reduce.reduce({"host": [], "device": {}}, PATTERNS) is None


def _sweep_busy(ivs):
    """Busy length by counting open intervals across sorted boundaries."""
    marks = sorted([(s, 1) for s, e in ivs] + [(e, -1) for s, e in ivs])
    busy, depth, last = 0.0, 0, None
    for t, d in marks:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_v5e_trace():
    ex = json.loads((HERE / "data" / "trace_v5e.json").read_text())["events"]
    r = trace_reduce.reduce(ex)
    (_, w0, wd, _), *views = ex["host"]
    w1 = w0 + wd
    ops = [(max(s, w0), min(s + d, w1)) for _, s, d in ex["device"]["/device:TPU:0"]
           if s < w1 and s + d > w0]
    busy = _sweep_busy(ops)
    assert r["window_s"] == pytest.approx(wd / 1e9)
    assert r["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    kern = sum(e - s for (s, e), (n, *_) in zip(
        ops, [v for v in ex["device"]["/device:TPU:0"]
              if v[1] < w1 and v[1] + v[2] > w0])
        if n.startswith("%grouped_agg_pallas"))
    assert r["kernel_s"]["grouped_agg"] == pytest.approx(kern / 1e9)
    assert r["device_ops"][0][0] == "grouped_agg_pallas"
    # idle time splits into the views and the rest of the window
    in_views = sum(d for _, _, d, _ in views)
    busy_in_views = _sweep_busy([(max(s, vs), min(e, vs + vd))
                                 for s, e in ops for _, vs, vd, _ in views
                                 if s < vs + vd and e > vs])
    gaps = dict(r["idle_gaps"])
    assert gaps["view"] == pytest.approx((in_views - busy_in_views) / 1e9,
                                         rel=1e-9)
    assert sum(gaps.values()) == pytest.approx((wd - busy) / 1e9, rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
