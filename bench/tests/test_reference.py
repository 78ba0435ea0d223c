import numpy as np
import pytest

from bench.reference import Reference, to_bfloat16


def test_scalar_hand_computed():
    x = np.array([0.0, 1.0, 2.0, 3.0, 4.0], np.float32)
    y = np.array([0.0, 1.0, 2.0, 3.0, 4.0], np.float32)
    v = np.array([10.0, 20.0, 30.0, 40.0, 50.0], np.float32)
    ref = Reference(x, y, {"a": v}, grid=4)
    # closed window: objects on the edges count
    assert ref.scalar((1.0, 1.0, 3.0, 3.0), "sum", "a") == (90.0, 3)
    assert ref.scalar((1.0, 1.0, 3.0, 3.0), "mean", "a") == (30.0, 3)
    assert ref.scalar((1.0, 1.0, 3.0, 3.0), "count", "a") == (3.0, 3)
    assert ref.scalar((5.0, 5.0, 6.0, 6.0), "mean", "a") == (0.0, 0)
    assert ref.scalar((1.5, 0.0, 1.9, 4.0), "sum", "a") == (0.0, 0)


def test_heatmap_hand_computed():
    # window [0, 4] x [0, 2], 2 x 2 bins: columns split at x = 2, rows
    # at y = 1; the max edges clip into the last bin
    x = np.array([0.5, 1.99, 2.0, 4.0, 0.0, 3.0], np.float32)
    y = np.array([0.5, 0.2, 0.0, 2.0, 1.0, 9.0], np.float32)
    v = np.array([1.0, 2.0, 4.0, 8.0, 16.0, 32.0], np.float32)
    ref = Reference(x, y, {"a": v}, grid=8)
    values, counts = ref.heatmap((0.0, 0.0, 4.0, 2.0), "sum", "a", (2, 2))
    assert counts.tolist() == [2, 1, 1, 1]
    assert values.tolist() == [3.0, 4.0, 16.0, 8.0]
    means, _ = ref.heatmap((0.0, 0.0, 4.0, 2.0), "mean", "a", (2, 2))
    assert means.tolist() == [1.5, 4.0, 16.0, 8.0]


def test_bucketed_scan_equals_full_scan():
    rng = np.random.default_rng(3)
    n = 20_000
    x = rng.uniform(0, 1000, n).astype(np.float32)
    y = rng.normal(500, 150, n).astype(np.float32)
    v = rng.normal(50, 15, n).astype(np.float32)
    ref = Reference(x, y, {"a": v}, grid=32)
    for _ in range(50):
        x0, y0 = rng.uniform(-50, 900, 2)
        s = rng.uniform(1, 300)
        w = tuple(float(np.float32(c)) for c in (x0, y0, x0 + s, y0 + s))
        m = (x >= w[0]) & (x <= w[2]) & (y >= w[1]) & (y <= w[3])
        total, count = ref.scalar(w, "sum", "a")
        assert count == int(m.sum())
        assert total == pytest.approx(float(v[m].sum(dtype=np.float64)),
                                      rel=1e-12, abs=1e-9)
        values, counts = ref.heatmap(w, "sum", "a", (8, 8))
        assert counts.sum() == m.sum()
        assert values.sum() == pytest.approx(total, rel=1e-9, abs=1e-9)


def test_bfloat16_rounding():
    a = np.array([1.0, 1.00390625, 1.005859375, 1000.7, -3.0], np.float32)
    b = to_bfloat16(a)
    # bfloat16 keeps 8 significant bits: 1 + 2**-8 ties to even (1.0),
    # 1 + 1.5 * 2**-8 rounds up to 1 + 2**-7; near 1000 the step is 4
    assert b.tolist() == [1.0, 1.0, 1.0078125, 1000.0, -3.0]
