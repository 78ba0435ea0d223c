"""RawDataset storage modes + IOStats accounting (previously untested).

Covers the three access modes (array gather, csv fixed-width text parse,
mmap binary) and the exact per-call accounting deltas the paper's cost
model — "objects read from the raw file" — is measured in.
"""
import numpy as np
import pytest

from repro.core import AQPEngine, IndexConfig
from repro.data import make_synthetic_dataset
from repro.data.rawfile import IOStats, RawDataset


def _columns(n=257, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0, 100, n).astype(np.float32)
    y = rng.uniform(0, 100, n).astype(np.float32)
    cols = {"a": rng.normal(12.5, 80, n).astype(np.float32),
            "b": rng.lognormal(1.0, 0.7, n).astype(np.float32)}
    return x, y, cols


def test_csv_fixed_width_parse_round_trip():
    """csv mode stores %.6g fixed-width text records; read_values parses
    them back, and the parsed value IS the ground truth the oracle sees."""
    x, y, cols = _columns()
    ds = RawDataset(x, y, cols, storage="csv")
    rows = np.arange(ds.n)
    got = ds.read_values("a", rows)
    # reads return exactly what the "file" contains…
    np.testing.assert_array_equal(got, ds.read_all_unaccounted("a"))
    # …which round-trips the original values to %.6g precision
    np.testing.assert_allclose(got, cols["a"], rtol=1e-5, atol=1e-6)
    # records really are fixed-width text
    assert ds._text["a"].dtype == np.dtype(f"S{RawDataset.CSV_WIDTH}")
    assert all(len(r) <= RawDataset.CSV_WIDTH for r in ds._text["a"])


def test_mmap_read_path(tmp_path):
    """mmap mode persists columns to disk and reads through np.memmap."""
    x, y, cols = _columns()
    ds = RawDataset(x, y, cols, mmap_dir=str(tmp_path))
    assert ds.storage == "mmap"
    assert (tmp_path / "a.f32").exists() and (tmp_path / "b.f32").exists()
    assert (tmp_path / "a.f32").stat().st_size == ds.n * RawDataset.ITEM_BYTES
    assert isinstance(ds._cols["a"], np.memmap)
    rows = np.array([0, 5, 17, ds.n - 1])
    np.testing.assert_array_equal(ds.read_values("a", rows),
                                  cols["a"][rows])
    np.testing.assert_array_equal(ds.read_all_unaccounted("b"), cols["b"])


@pytest.mark.parametrize("storage,item_bytes", [
    ("array", RawDataset.ITEM_BYTES), ("csv", RawDataset.CSV_WIDTH)])
def test_iostats_accounting_deltas(storage, item_bytes):
    """Every read_values accounts rows, bytes (mode-dependent width), and
    exactly one read call; oracle access accounts nothing."""
    x, y, cols = _columns()
    ds = RawDataset(x, y, cols, storage=storage)
    assert ds.stats == IOStats()
    before = ds.stats.snapshot()

    ds.read_values("a", np.arange(100))
    ds.read_values("b", np.array([3, 1, 4, 1, 5]))  # repeats still cost
    d = ds.stats.delta(before)
    assert d.rows_read == 105
    assert d.read_calls == 2
    assert d.bytes_read == 105 * item_bytes
    assert d.init_rows == 0

    mid = ds.stats.snapshot()
    ds.read_all_unaccounted("a")                    # ground-truth access
    assert ds.stats.delta(mid) == IOStats()

    ds.account_init_pass()
    d2 = ds.stats.delta(mid)
    assert d2.init_rows == ds.n
    assert d2.rows_read == 0 and d2.read_calls == 0


def test_iostats_mmap_bytes(tmp_path):
    x, y, cols = _columns()
    ds = RawDataset(x, y, cols, mmap_dir=str(tmp_path))
    before = ds.stats.snapshot()
    ds.read_values("a", np.arange(64))
    d = ds.stats.delta(before)
    assert (d.rows_read, d.bytes_read, d.read_calls) == (
        64, 64 * RawDataset.ITEM_BYTES, 1)


@pytest.mark.parametrize("storage", ["array", "csv"])
def test_engine_answers_identical_across_storage_modes(storage):
    """The engine's exact answers are storage-independent up to the csv
    %.6g quantization, and csv reads cost text-record bytes."""
    ds = make_synthetic_dataset(n=8_000, n_columns=2, seed=9,
                                storage=storage)
    eng = AQPEngine(ds, IndexConfig(grid0=(4, 4), min_split_count=64,
                                    init_metadata_attrs=("a0",)))
    w = (200.0, 200.0, 600.0, 600.0)
    r = eng.query(w, "sum", "a0", phi=0.0)
    truth = eng.oracle(w, "sum", "a0")
    np.testing.assert_allclose(r.value, truth, rtol=1e-6, atol=1e-3)
    width = (RawDataset.CSV_WIDTH if storage == "csv"
             else RawDataset.ITEM_BYTES)
    assert ds.stats.bytes_read == ds.stats.rows_read * width


def test_engine_mmap_end_to_end(tmp_path):
    ds = make_synthetic_dataset(n=8_000, n_columns=2, seed=9,
                                mmap_dir=str(tmp_path))
    eng = AQPEngine(ds, IndexConfig(grid0=(4, 4), min_split_count=64))
    w = (200.0, 200.0, 600.0, 600.0)
    r = eng.query(w, "mean", "a1", phi=0.05)
    truth = eng.oracle(w, "mean", "a1")
    assert r.lo - 1e-3 <= truth <= r.hi + 1e-3
    eng.index.check_invariants("a1")


def test_io_accounting():
    from repro.data import make_synthetic_dataset
    ds = make_synthetic_dataset(n=10_000, seed=1)
    before = ds.stats.snapshot()
    ds.read_values("a0", np.arange(500))
    d = ds.stats.delta(before)
    assert d.rows_read == 500
    assert d.bytes_read == 500 * 4
    assert d.read_calls == 1


def test_exploration_path_selectivity():
    from repro.data import make_synthetic_dataset
    from repro.data.synthetic import exploration_path
    ds = make_synthetic_dataset(n=100_000, seed=2)
    wins = exploration_path(ds, n_queries=10, target_objects=10_000)
    from repro.kernels.ops import window_mask_np
    counts = [window_mask_np(ds.x, ds.y, w).sum() for w in wins]
    # windows hold roughly the target object count (clustered data ⇒
    # generous tolerance; the paper says "approximately 100K")
    assert np.median(counts) > 2_000
    assert max(counts) < 60_000
