"""Batched adaptation pipeline vs the sequential reference oracle.

The batched path (one gathered raw-file read + one packed segment kernel
per refinement round, vectorized multi-tile splits) must be
indistinguishable from the per-tile sequential path in everything but
cost: same QueryResult value/lo/hi/bound, same folded-tile counts, same
index evolution (permutation, tile table, metadata), same invariants —
while issuing strictly fewer raw-file read calls and kernel invocations.
"""
import numpy as np
import pytest

from repro.core import AQPEngine, IndexConfig
from repro.data import make_synthetic_dataset
from repro.data.synthetic import exploration_path

AGGS = ["count", "sum", "mean", "min", "max"]
PHIS = [0.0, 0.01, 0.05]


def small_engine(n=60_000, seed=5, **kw):
    ds = make_synthetic_dataset(n=n, seed=seed)
    cfg = IndexConfig(grid0=(8, 8), min_split_count=64,
                      init_metadata_attrs=("a0",), **kw)
    return AQPEngine(ds, cfg)


@pytest.mark.parametrize("agg", AGGS)
@pytest.mark.parametrize("phi", PHIS)
def test_batched_matches_sequential(agg, phi):
    e_seq = small_engine(seed=5)
    e_bat = small_engine(seed=5)
    wins = exploration_path(e_seq.dataset, n_queries=4, target_objects=4000)
    for w in wins:
        rs = e_seq.query(w, agg, "a0", phi=phi, sequential=True)
        rb = e_bat.query(w, agg, "a0", phi=phi)
        # counts bit-for-bit; sums/bounds to f64 identity (the host
        # mirrors reproduce the sequential float64 accumulation exactly)
        assert rb.tiles_processed == rs.tiles_processed
        assert rb.tiles_full == rs.tiles_full
        assert rb.tiles_partial == rs.tiles_partial
        assert rb.exact == rs.exact
        assert rb.value == pytest.approx(rs.value, rel=1e-12, abs=1e-9)
        assert rb.lo == pytest.approx(rs.lo, rel=1e-12, abs=1e-9)
        assert rb.hi == pytest.approx(rs.hi, rel=1e-12, abs=1e-9)
        assert rb.bound == pytest.approx(rs.bound, rel=1e-12, abs=1e-12)
    # identical index evolution across the whole workload…
    i_seq, i_bat = e_seq.index, e_bat.index
    assert i_bat.n_tiles == i_seq.n_tiles
    n = i_seq.n_tiles
    assert np.array_equal(i_bat.perm, i_seq.perm)
    assert np.array_equal(i_bat.offset[:n], i_seq.offset[:n])
    assert np.array_equal(i_bat.count[:n], i_seq.count[:n])
    assert np.array_equal(i_bat.active[:n], i_seq.active[:n])
    assert np.array_equal(i_bat.meta_valid["a0"][:n],
                          i_seq.meta_valid["a0"][:n])
    np.testing.assert_allclose(i_bat.meta_sum["a0"][:n],
                               i_seq.meta_sum["a0"][:n], rtol=1e-12)
    # …and the invariants hold in both
    i_seq.check_invariants("a0")
    i_bat.check_invariants("a0")


def test_phi_zero_equals_oracle_regression():
    """φ=0 ⇒ exact: the batched pipeline's answer IS the ground truth."""
    eng = small_engine(seed=17)
    wins = exploration_path(eng.dataset, n_queries=5, target_objects=5000)
    for agg in AGGS:
        for w in wins:
            r = eng.query(w, agg, "a0", phi=0.0)
            assert r.exact
            truth = eng.oracle(w, agg, "a0")
            np.testing.assert_allclose(r.value, truth, rtol=1e-5, atol=1e-3)


def test_batched_amortizes_reads_and_kernels():
    """One gathered read + packed kernels per round, not per tile."""
    e_seq = small_engine(seed=11)
    e_bat = small_engine(seed=11)
    w = exploration_path(e_seq.dataset, n_queries=1,
                         target_objects=20_000)[0]
    rs = e_seq.query(w, "mean", "a0", phi=0.0, sequential=True)
    rb = e_bat.query(w, "mean", "a0", phi=0.0)
    assert rs.tiles_processed == rb.tiles_processed > 8
    # sequential: one read call per tile; batched: one per round
    assert rs.read_calls == rs.tiles_processed
    k = e_bat.index.cfg.batch_k
    assert rb.batch_rounds == -(-rs.tiles_processed // k)
    assert rb.read_calls == rb.batch_rounds < rs.read_calls
    # φ=0: no speculative overshoot — identical rows read
    assert rb.objects_read == rs.objects_read
    # one packed kernel per round; the sequential reference folds and
    # splits each tile on the host
    assert e_bat.adapt_stats.kernel_calls == rb.batch_rounds
    assert e_seq.adapt_stats.kernel_calls == 0


def test_batch_k_knob():
    """batch_k=1 degenerates to per-tile rounds; larger k means fewer."""
    results = {}
    for k in (1, 4, 32):
        eng = small_engine(seed=13)
        w = exploration_path(eng.dataset, n_queries=1,
                             target_objects=15_000)[0]
        r = eng.query(w, "sum", "a0", phi=0.0, batch_k=k)
        results[k] = r
    assert results[1].batch_rounds == results[1].tiles_processed
    assert (results[32].batch_rounds < results[4].batch_rounds
            < results[1].batch_rounds)
    for r in results.values():
        assert r.value == pytest.approx(results[1].value, rel=1e-12)


def test_fresh_config_per_engine():
    """Regression: engines must not share one mutable IndexConfig."""
    ds1 = make_synthetic_dataset(n=2_000, seed=3)
    ds2 = make_synthetic_dataset(n=2_000, seed=3)
    e1 = AQPEngine(ds1)
    e1.index.cfg.min_split_count = 1
    e2 = AQPEngine(ds2)
    assert e2.index.cfg.min_split_count != 1
    assert e1.index.cfg is not e2.index.cfg
    from repro.core.index import TileIndex
    t1, t2 = TileIndex(ds1), TileIndex(ds2)
    assert t1.cfg is not t2.cfg


def test_child_bounds_clamped_sound():
    """Split-child min/max stay inside the parent's sound interval and
    bound every owned object exactly (no tolerance)."""
    eng = small_engine(seed=23)
    wins = exploration_path(eng.dataset, n_queries=6, target_objects=6000)
    for w in wins:
        eng.query(w, "sum", "a0", phi=0.0)
    idx = eng.index
    col = eng.dataset.read_all_unaccounted("a0")
    ids = np.flatnonzero(idx.active[:idx.n_tiles])
    checked = 0
    for t in ids:
        o, c = idx.offset[t], idx.count[t]
        p = idx.parent[t]
        if c == 0 or p < 0:
            continue
        seg = col[idx.perm[o:o + c]]
        assert seg.min() >= idx.meta_min["a0"][t]
        assert seg.max() <= idx.meta_max["a0"][t]
        if idx.meta_valid["a0"][p]:
            assert idx.meta_min["a0"][t] >= idx.meta_min["a0"][p]
            assert idx.meta_max["a0"][t] <= idx.meta_max["a0"][p]
        checked += 1
    assert checked > 32  # splits actually happened
