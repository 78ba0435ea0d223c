"""The data generator is fixed by the seed, the traffic by its mix; both
draw the distributions of the program's ``make_synthetic_dataset`` and
``exploration_path``."""
import numpy as np

from bench import traffic
from bench.traffic import exploration_path
from bench.control import _Data
from bench.data import synthetic

CFG = {"rows": 60_000, "n_columns": 4, "n_clusters": 24,
       "cluster_frac": 0.7, "domain": 1000.0, "layout_seed": 2407}
SEED = 3_000_000_017      # above 2**31, as the driver's seeds are


def _data(seed, chunk_rows=synthetic.CHUNK_ROWS):
    x, y, cols = synthetic.generate(CFG, seed, chunk_rows=chunk_rows)
    return np.stack([x, y, *cols.values()])


def test_data_same_seed_same_bytes():
    a, b = _data(SEED), _data(SEED)
    assert a.tobytes() == b.tobytes()
    assert a.dtype == np.float32 and a.shape == (6, CFG["rows"])
    assert not np.array_equal(a, _data(SEED + 1))


def test_data_chunked_generation_is_whole():
    a = _data(SEED, chunk_rows=16_384)      # 4 chunks, the last partial
    assert np.isfinite(a).all()
    assert not np.array_equal(a[:, :16_384], a[:, 16_384:32_768])


def test_data_distributions():
    x, y, cols = synthetic.generate({**CFG, "rows": 400_000}, SEED)
    d = CFG["domain"]
    assert x.min() >= 0 and x.max() <= d and y.min() >= 0 and y.max() <= d
    # 30 % uniform background: the part of the domain farther than 5
    # scales from every cluster centre holds 30 % * its area share
    centres, scales = synthetic.layout(CFG)

    def far(px, py):
        return np.all(np.hypot(centres[:, 0, None] - px,
                               centres[:, 1, None] - py)
                      > 5 * scales[:, None], axis=0)

    g = (np.arange(400) + 0.5) * d / 400
    gx, gy = np.meshgrid(g, g)
    share = far(gx.ravel(), gy.ravel()).mean()
    assert abs(far(x, y).mean() - 0.3 * share) < 0.003
    a0, a1, a2, a3 = (cols[f"a{j}"] for j in range(4))
    assert abs(a0.mean() - 50) < 0.2 and abs(a0.std() - 15) < 0.2
    assert abs(np.log(a1).mean() - 2) < 0.01 and abs(np.log(a1).std() - 0.6) < 0.01
    assert a2.min() >= -100 and a2.max() <= 100 and abs(a2.mean()) < 0.5
    assert abs((a3 > 0).mean() - 0.5) < 0.01 and abs(np.abs(a3).mean() - 40) < 0.2


def _schedule(mix, seconds=5.0):
    x, y, _ = synthetic.generate(CFG, 5)
    # a window of 1e5 objects would cover this small file whole; ~600
    # objects keeps it a small part of the domain
    return traffic.schedule(mix, _Data(x, y), seconds,
                            {"target_objects": 600})


def test_explore_schedule():
    a, b = _schedule("explore"), _schedule("explore")
    assert a.views == b.views and a.first == b.first
    assert len(a.views) == 50                         # 10 views/s for 5 s
    assert [v.due_s for v in a.views[:3]] == [0.0, 0.1, 0.2]
    assert [v.agg for v in [a.first] + a.views[:3]] == \
        ["mean", "sum", "mean", "sum"]
    # a longer window appends drags
    assert _schedule("explore", 9.0).views[:50] == a.views
    # windows keep their side, and each step of a drag moves 10-20 % of it
    ws = np.array([v.window for v in [a.first] + a.views])
    side = ws[:, 2] - ws[:, 0]
    assert np.allclose(side, side[0], rtol=1e-5)
    step = np.hypot(np.diff(ws[:, 0]), np.diff(ws[:, 1])) / side[0]
    per = traffic.load("explore")["path_views"]
    inside = np.arange(len(step)) % per != per - 1
    assert step[inside].max() <= 0.2 + 1e-4


def test_explore_drags_start_where_the_data_is():
    x, y, _ = synthetic.generate(CFG, 5)
    data = _Data(x, y)
    sch = traffic.schedule("explore", data, 60.0, {"target_objects": 600})
    per = traffic.load("explore")["path_views"]
    ws = np.array([v.window for v in [sch.first] + sch.views])
    starts = ws[::per]
    assert sch.params["paths"] == len(starts) == 13   # 601 views
    assert len({tuple(w) for w in starts}) == len(starts)
    # every drag starts on an occupied cell of the 24 x 24 histogram
    x0, y0, x1, y1 = data.domain()
    counts = exploration_path.cell_counts(x, y, (x0, y0, x1, y1))
    cx = (starts[:, 0] + starts[:, 2]) / 2
    cy = (starts[:, 1] + starts[:, 3]) / 2
    ci = ((cx - x0) / (x1 - x0) * 24).astype(int).clip(0, 23)
    cj = ((cy - y0) / (y1 - y0) * 24).astype(int).clip(0, 23)
    assert (counts[ci * 24 + cj] > 0).all()
