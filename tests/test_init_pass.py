"""The index's init pass on the device route ("jnp" here, XLA:CPU) builds
the same index as the host rule of the "np" mirror, bit for bit: the
stable permutation, the permuted axes, every tile's offset and count,
and the init metadata. Small chunk sizes make several chunks, with a
padded last one, on small files."""
import functools

import numpy as np
import pytest

from repro.core import ChunkIndexSet, IndexConfig, TileIndex
from repro.core import geometry
from repro.data import ChunkedDataset, RawDataset, make_synthetic_dataset
from repro.kernels import ops

ATTR = "a0"


def _dataset(x, y, seed=0):
    rng = np.random.default_rng(seed)
    return RawDataset(x, y, {ATTR: rng.normal(size=len(x))})


def _synthetic():
    return make_synthetic_dataset(n=60_000, n_columns=2, seed=11)


def _edges():
    """Points on every grid edge, one float32 ulp either side of it, on
    each threshold of the rule and its neighbours, and at the domain's
    corners; the rest uniform."""
    lo, hi, g = np.float32(0.3), np.float32(977.1), 16
    cw = (float(hi) - float(lo)) / g
    tx, _ = geometry.bin_thresholds((float(lo), float(lo), float(hi),
                                     float(hi)), g, g)
    edge = np.concatenate([
        np.float32(lo) + np.float32(cw) * np.arange(g + 1, dtype=np.float32),
        (lo + np.arange(g + 1) * cw).astype(np.float32), tx])
    pts = np.concatenate([edge, np.nextafter(edge, np.float32(-np.inf)),
                          np.nextafter(edge, np.float32(np.inf))])
    pts = np.clip(pts, lo, hi).astype(np.float32)
    rng = np.random.default_rng(3)
    x = np.concatenate([pts, np.repeat(pts, len(pts)), [lo, hi],
                        rng.uniform(lo, hi, 3000)]).astype(np.float32)
    y = np.concatenate([pts, np.tile(pts, len(pts)), [hi, lo],
                        rng.uniform(lo, hi, 3000)]).astype(np.float32)
    return _dataset(x, y)


def _ties():
    """Many rows on a few identical coordinates: the order among them is
    the file's."""
    rng = np.random.default_rng(5)
    spots = rng.uniform(0, 100, (7, 2)).astype(np.float32)
    pick = rng.integers(0, 7, 20_000)
    return _dataset(spots[pick, 0], spots[pick, 1])


def _empty_cells():
    """Two clusters in opposite corners: most cells hold no row."""
    rng = np.random.default_rng(7)
    a = rng.normal(5, 1, (3000, 2))
    b = rng.normal(95, 1, (2000, 2))
    pts = np.clip(np.concatenate([a, b]), 0, 100).astype(np.float32)
    return _dataset(pts[:, 0], pts[:, 1])


def _single():
    return _dataset(np.float32([4.25]), np.float32([-1.5]))


FILES = {"synthetic": _synthetic, "edges": _edges, "ties": _ties,
         "empty_cells": _empty_cells, "single": _single}


def _pair(ds, grid0, chunk_rows, monkeypatch):
    monkeypatch.setattr(ops, "init_order", functools.partial(
        ops.init_order, chunk_rows=chunk_rows))
    return [TileIndex(ds, IndexConfig(grid0=grid0, backend=b,
                                      init_metadata_attrs=(ATTR,)))
            for b in ("np", "jnp")]


# (file, grid0, chunk_rows): full chunks with a padded last one, a last
# chunk padded to a bucket smaller than the chunk, a chunk size no power
# of two, one chunk, and a grid of 4096 cells (a narrower row field)
CASES = [("synthetic", (16, 16), 4096), ("synthetic", (16, 16), 1 << 23),
         ("synthetic", (5, 3), 7000), ("synthetic", (64, 64), 1 << 23),
         ("edges", (16, 16), 1 << 23), ("edges", (16, 16), 1024),
         ("ties", (16, 16), 3000), ("ties", (8, 8), 1 << 14),
         ("empty_cells", (16, 16), 1 << 14), ("empty_cells", (4, 4), 1000),
         ("single", (16, 16), 1 << 23), ("single", (2, 2), 1024)]


@pytest.mark.parametrize("name,grid0,chunk_rows", CASES)
def test_device_route_builds_the_host_index(name, grid0, chunk_rows,
                                            monkeypatch):
    ds = FILES[name]()
    host, dev = _pair(ds, grid0, chunk_rows, monkeypatch)
    for f in ("perm", "x_s", "y_s", "offset", "count", "bbox", "active"):
        a, b = getattr(host, f), getattr(dev, f)
        assert a.dtype == b.dtype and np.array_equal(a, b), f
    assert dev.n_tiles == host.n_tiles == grid0[0] * grid0[1]
    assert dev.global_minmax == host.global_minmax
    for meta in ("meta_sum", "meta_min", "meta_max", "meta_valid"):
        assert np.array_equal(getattr(host, meta)[ATTR],
                              getattr(dev, meta)[ATTR]), meta
    assert ds.stats.init_rows == 2 * ds.n
    dev.check_invariants(ATTR)


def test_cells_match_the_host_rule_on_edges():
    """The thresholds reproduce ``bin_cell_ids`` on every float32 edge
    point and its neighbours, not only on the ones the index orders."""
    ds = _edges()
    dom = ds.domain()
    tx, ty = geometry.bin_thresholds(dom, 16, 16)
    cells = geometry.bin_cell_ids(ds.x, ds.y, dom, 16, 16)
    cx = (ds.x[:, None] >= tx[None, :]).sum(1)
    cy = (ds.y[:, None] >= ty[None, :]).sum(1)
    assert np.array_equal(cy * 16 + cx, cells)
    assert len(np.unique(cells)) == 256


@pytest.mark.parametrize("backend,device_rows", [("np", 0), ("jnp", 1)])
def test_init_device_rows_and_writable_arrays(backend, device_rows):
    ds = _empty_cells()
    index = TileIndex(ds, IndexConfig(backend=backend))
    assert index.adapt_stats.init_device_rows == device_rows * ds.n
    for f in ("perm", "x_s", "y_s"):
        assert getattr(index, f).flags.writeable, f
    assert index.perm.dtype == np.int64
    assert index.x_s.dtype == index.y_s.dtype == np.float32


def test_chunk_forest_counts_device_rows():
    """A chunked forest's shared ledger counts every chunk's init pass."""
    cds = ChunkedDataset()
    for seed in (1, 2):
        cds.ingest_dataset(_dataset(
            *np.random.default_rng(seed).uniform(0, 10, (2, 900 * seed)),
            seed=seed))
    forest = ChunkIndexSet(cds, IndexConfig(backend="jnp"))
    for chunk in cds.chunks():
        forest.index_for(chunk)
    assert forest.adapt_stats.init_device_rows == cds.n == 2700
