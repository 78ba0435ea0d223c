"""A split child's metadata comes from the rows the split has ordered on
the host: after ``_split`` (sequential) and ``_split_batch`` (batched),
every child's count, sum, min and max equal a float64 scan of that
child's own rows, on every backend, and applying a round makes no op
call."""
import numpy as np
import pytest

from repro.core import IndexConfig
from repro.core.index import TileIndex
from repro.data.rawfile import RawDataset

BACKENDS = ("np", "jnp", "pallas")
# None: the even 2×2 split; else heatmap bins whose bin-aligned edges
# cut each 50×50 root tile into a 3×2 or a 4×4 grid
GRIDS = {None: (2, 2), (6, 4): (3, 2), (8, 8): (4, 4)}
WINDOW = (0.0, 0.0, 100.0, 100.0)
# the root tiles of grid0=(2, 2) over [0, 100]², in one round's order:
# tile 0's rows all fall in one child of the even split, and tile 1,
# last, leaves its last child empty under every grid
ORDER = [0, 2, 3, 1]


def _dataset():
    rng = np.random.default_rng(1601)

    def block(n, x0, y0, x1, y1):
        return rng.uniform(x0, x1, n), rng.uniform(y0, y1, n)

    parts = [block(300, 1, 1, 20, 20),                 # tile 0, one child
             ([0.0], [0.0]),                           # domain corner
             block(150, 50, 0, 75, 50),                # tile 1: children
             block(150, 75, 0, 100, 25),               # 0-2, last empty
             block(300, 0, 50, 50, 100),               # tile 2
             block(300, 50, 50, 100, 100),             # tile 3
             ([100.0], [100.0])]                       # domain corner
    x = np.concatenate([p[0] for p in parts]).astype(np.float32)
    y = np.concatenate([p[1] for p in parts]).astype(np.float32)
    # multiples of 1/8: every float64 sum of them is exact in any order
    vals = (rng.integers(-8000, 8000, len(x)) / 8).astype(np.float32)
    return RawDataset(x, y, {"a0": vals})


def _index(backend):
    ti = TileIndex(_dataset(), IndexConfig(grid0=(2, 2), min_split_count=64,
                                           backend=backend))
    assert ti.domain == WINDOW
    return ti


def _read(ti, bins):
    if bins is None:
        return ti.read_batch(ORDER, WINDOW, "a0")[1]
    return ti.read_batch_heatmap(ORDER, WINDOW, "a0", bins)[1]


def _refine(ti, path, bins):
    if path == "batched":
        ti.apply_batch(_read(ti, bins), len(ORDER), [True] * len(ORDER))
    elif bins is None:
        for t in ORDER:
            ti.process(t, WINDOW, "a0")
    else:
        for t in ORDER:
            ti.process_heatmap(t, WINDOW, "a0", bins)


@pytest.mark.parametrize("bins", list(GRIDS))
@pytest.mark.parametrize("path", ["sequential", "batched"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_children_match_a_scan_of_their_rows(backend, path, bins):
    ti = _index(backend)
    _refine(ti, path, bins)
    gx, gy = GRIDS[bins]
    k = gx * gy
    assert ti.adapt_stats.tiles_split == len(ORDER)
    assert ti.n_tiles == len(ORDER) * (1 + k)
    col = ti.ds.read_all_unaccounted("a0")
    for p in ORDER:
        kids = np.flatnonzero(ti.parent[:ti.n_tiles] == p)
        assert len(kids) == k
        assert ti.count[kids].sum() == ti.count[p]
        for t in kids:
            o, c = ti.offset[t], ti.count[t]
            rows = col[ti.perm[o:o + c]].astype(np.float64).tolist()
            assert ti.meta_valid["a0"][t]
            assert ti.meta_sum["a0"][t] == sum(rows, 0.0)
            lo, hi = ((min(rows), max(rows)) if rows else
                      (ti.meta_min["a0"][p], ti.meta_max["a0"][p]))
            assert ti.meta_min["a0"][t] == lo
            assert ti.meta_max["a0"][t] == hi
    # the cases the dataset was built for
    kids0 = np.flatnonzero(ti.parent[:ti.n_tiles] == 0)
    if bins is None:
        assert (ti.count[kids0] > 0).sum() == 1
    assert ti.count[ti.n_tiles - 1] == 0
    assert (ti.count[:ti.n_tiles][ti.parent[:ti.n_tiles] >= 0] == 0).sum() \
        > 1
    ti.check_invariants("a0")


@pytest.mark.parametrize("bins", list(GRIDS))
@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_split_equals_sequential(backend, bins):
    seq, bat = _index(backend), _index(backend)
    _refine(seq, "sequential", bins)
    _refine(bat, "batched", bins)
    for f in ("perm", "x_s", "y_s", "offset", "count", "bbox", "parent"):
        assert np.array_equal(getattr(seq, f), getattr(bat, f)), f
    for f in ("meta_sum", "meta_min", "meta_max", "meta_valid"):
        assert np.array_equal(getattr(seq, f)["a0"],
                              getattr(bat, f)["a0"]), f


@pytest.mark.parametrize("bins", list(GRIDS))
@pytest.mark.parametrize("backend", BACKENDS)
def test_apply_makes_no_op_call(backend, bins):
    ti = _index(backend)
    payload = _read(ti, bins)
    before = ti.adapt_stats.snapshot()
    ti.apply_batch(payload, len(ORDER), [True] * len(ORDER))
    delta = ti.adapt_stats.delta(before)
    assert delta.tiles_split == len(ORDER)
    assert delta.kernel_calls == 0 and delta.h2d_bytes == 0
