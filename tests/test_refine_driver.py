"""Unified refinement driver: round-sizing certainty + bin-aligned splits.

Three contracts introduced by the driver refactor:

- **Certainty of predictive round sizing** — ``min_folds_needed`` (scalar
  and grouped) is a LOWER bound: it never exceeds the fold count at
  which the sequential stopping rule actually fires, for any φ, so a
  round sized by it can never read past the stopping point.
- **Zero speculative rows** — for sum/mean at φ>0, the batched driver
  reads exactly the rows the sequential reference reads (scalar AND
  heatmap; the heatmap geometric ramp is gone), and reports
  ``speculative_rows == 0``.
- **Bin-aligned splits** — after one heatmap over a grid, a repeated
  identical heatmap answers with strictly fewer objects read than under
  the even 2×2 split policy (children nest inside single bins after ONE
  split).

And the driver's two ways to run: stepped by hand (``next_batch`` /
``read_batch`` / ``fold``, as the serving tick does) it refines exactly
as ``run()`` does.
"""
import numpy as np
import pytest

from repro.core import AQPEngine, IndexConfig
from repro.core.query import (_build_accumulator, _build_grouped_accumulator,
                              classify)
from repro.core import adapt
from repro.data import make_synthetic_dataset
from repro.data.synthetic import exploration_path

PHIS = [0.005, 0.02, 0.05, 0.2]


def small_engine(n=50_000, seed=5, **kw):
    ds = make_synthetic_dataset(n=n, seed=seed)
    cfg = IndexConfig(grid0=(8, 8), min_split_count=64,
                      init_metadata_attrs=("a0",), **kw)
    return AQPEngine(ds, cfg)


@pytest.mark.parametrize("agg", ["sum", "mean"])
@pytest.mark.parametrize("phi", PHIS)
def test_min_folds_needed_never_exceeds_sequential_stop(agg, phi):
    """Scalar certainty: the predictive bound never overshoots the fold
    count the sequential stopping rule actually needed."""
    e_ref = small_engine(seed=7)
    e_probe = small_engine(seed=7)
    wins = exploration_path(e_ref.dataset, n_queries=4,
                            target_objects=6000)
    checked = 0
    for w in wins:
        # probe BEFORE the reference run mutates its (identical) index
        acc, _, _, _ = _build_accumulator(e_probe.index, w, agg, "a0")
        bound0 = acc.query_bound()
        order = adapt.score_tiles(acc.pending, agg, 1.0)
        rs = e_ref.query(w, agg, "a0", phi=phi, sequential=True)
        if acc.pending and bound0 > phi:
            j = acc.min_folds_needed(order, phi)
            assert j <= max(rs.tiles_processed, 1), (phi, w)
            checked += 1
        # keep the probe index in lockstep with the reference
        e_probe.query(w, agg, "a0", phi=phi, sequential=True)
    assert checked > 0


@pytest.mark.parametrize("agg", ["sum", "mean"])
@pytest.mark.parametrize("phi", PHIS)
def test_grouped_min_folds_needed_never_exceeds_sequential_stop(agg, phi):
    """Grouped certainty: same property for the per-bin-max stopping
    rule (the bound that replaced the heatmap geometric ramp)."""
    e_ref = small_engine(seed=11)
    e_probe = small_engine(seed=11)
    wins = exploration_path(e_ref.dataset, n_queries=4,
                            target_objects=6000)
    bins = (5, 3)
    checked = 0
    for w in wins:
        acc, _, _ = _build_grouped_accumulator(
            e_probe.index, w, agg, "a0", bins)
        bound0 = acc.query_bound()
        order = adapt.score_tiles_grouped(acc.pending, agg, 1.0)
        rs = e_ref.heatmap(w, agg, "a0", bins=bins, phi=phi,
                           sequential=True)
        if acc.pending and bound0 > phi:
            j = acc.min_folds_needed(order, phi)
            assert j <= max(rs.tiles_processed, 1), (phi, w)
            checked += 1
        e_probe.heatmap(w, agg, "a0", bins=bins, phi=phi, sequential=True)
    assert checked > 0


@pytest.mark.parametrize("agg", ["sum", "mean"])
@pytest.mark.parametrize("phi", [0.01, 0.05, 0.2])
def test_predictive_rounds_read_zero_speculative_rows_scalar(agg, phi):
    e_seq = small_engine(seed=13)
    e_bat = small_engine(seed=13)
    wins = exploration_path(e_seq.dataset, n_queries=4,
                            target_objects=6000)
    for w in wins:
        rs = e_seq.query(w, agg, "a0", phi=phi, sequential=True)
        rb = e_bat.query(w, agg, "a0", phi=phi)
        assert rb.objects_read == rs.objects_read, (agg, phi, w)
        assert rb.speculative_rows == 0
        assert rs.speculative_rows == 0   # sequential never speculates


@pytest.mark.parametrize("agg", ["sum", "mean"])
@pytest.mark.parametrize("phi", [0.01, 0.05, 0.2])
def test_predictive_rounds_read_zero_speculative_rows_heatmap(agg, phi):
    """The acceptance criterion: heatmap refinement at φ>0 with sum/mean
    reads exactly what the sequential reference reads — the predictive
    grouped sizing replaced the geometric ramp's overshoot."""
    e_seq = small_engine(seed=17)
    e_bat = small_engine(seed=17)
    wins = exploration_path(e_seq.dataset, n_queries=4,
                            target_objects=6000)
    refined = 0
    for w in wins:
        rs = e_seq.heatmap(w, agg, "a0", bins=(4, 4), phi=phi,
                           sequential=True)
        rb = e_bat.heatmap(w, agg, "a0", bins=(4, 4), phi=phi)
        assert rb.objects_read == rs.objects_read, (agg, phi, w)
        assert rb.speculative_rows == 0
        refined += rb.tiles_processed
    assert refined > 0   # the property was actually exercised


def test_min_max_ramp_still_bounds_overshoot():
    """min/max keep the geometric ramp: overshoot is possible but the
    accounting must agree with the extra rows actually read."""
    e_seq = small_engine(seed=19)
    e_bat = small_engine(seed=19)
    wins = exploration_path(e_seq.dataset, n_queries=4,
                            target_objects=6000)
    for w in wins:
        rs = e_seq.query(w, "min", "a0", phi=0.05, sequential=True)
        rb = e_bat.query(w, "min", "a0", phi=0.05)
        assert rb.objects_read == rs.objects_read + rb.speculative_rows
        assert rb.tiles_processed == rs.tiles_processed


def test_bin_aligned_split_beats_even_split_on_repeat_heatmap():
    """Acceptance regression: after one heatmap over a grid, repeating
    the identical heatmap reads strictly fewer objects under bin-aligned
    splits than under the even 2×2 policy (and no more on the first)."""
    reads = {}
    for aligned in (False, True):
        eng = small_engine(seed=5, bin_aligned_splits=aligned)
        w = exploration_path(eng.dataset, n_queries=1,
                             target_objects=15_000)[0]
        first = eng.heatmap(w, "sum", "a0", bins=(6, 6), phi=0.0)
        second = eng.heatmap(w, "sum", "a0", bins=(6, 6), phi=0.0)
        eng.index.check_invariants("a0")
        reads[aligned] = (first.objects_read, second.objects_read)
    assert reads[True][0] == reads[False][0]   # split policy is free on Q1
    assert reads[True][1] < reads[False][1]    # …and pays on the repeat
    assert reads[True][1] < reads[True][0]


def test_bin_matched_split_resolves_wide_tiles_in_one_split():
    """Bin-count-MATCHED split grids: a tile spanning s ≥ 3 bins per
    axis (up to ``IndexConfig.max_split_span``) nests EVERY child in a
    single bin after ONE split — the 2×2-cut policy needed several —
    so the repeat heatmap answers entirely from metadata (zero reads)."""
    from repro.core import AQPEngine, IndexConfig
    from repro.data import make_synthetic_dataset

    ds = make_synthetic_dataset(n=30_000, seed=9)
    eng = AQPEngine(ds, IndexConfig(grid0=(1, 1), min_split_count=64,
                                    init_metadata_attrs=("a0",)))
    d = ds.domain()
    w = (d[0], d[1], d[2], d[3])          # the root spans all 4x4 bins
    bins = (4, 4)
    r1 = eng.heatmap(w, "sum", "a0", bins=bins, phi=0.0)
    idx = eng.index
    # one split, bin-count-matched: 4x4 children, all nested
    lvl1 = [t for t in range(idx.n_tiles) if idx.parent[t] == 0]
    assert len(lvl1) == 16
    xl = np.linspace(w[0], w[2], 5)[1:-1]
    yl = np.linspace(w[1], w[3], 5)[1:-1]
    for t in lvl1:
        x0, y0, x1, y1 = idx.bbox[t]
        assert not ((xl > x0 + 1e-9) & (xl < x1 - 1e-9)).any(), t
        assert not ((yl > y0 + 1e-9) & (yl < y1 - 1e-9)).any(), t
    r2 = eng.heatmap(w, "sum", "a0", bins=bins, phi=0.0)
    assert r1.objects_read == ds.n and r2.objects_read == 0
    eng.index.check_invariants("a0")
    # batched ≡ sequential under per-tile (variable) split grids
    e_seq = AQPEngine(make_synthetic_dataset(n=30_000, seed=9),
                      IndexConfig(grid0=(4, 4), min_split_count=64,
                                  init_metadata_attrs=("a0",)))
    e_bat = AQPEngine(make_synthetic_dataset(n=30_000, seed=9),
                      IndexConfig(grid0=(4, 4), min_split_count=64,
                                  init_metadata_attrs=("a0",)))
    for wq in exploration_path(e_seq.dataset, n_queries=3,
                               target_objects=8000):
        rs = e_seq.heatmap(wq, "sum", "a0", bins=(5, 5), phi=0.0,
                           sequential=True)
        rb = e_bat.heatmap(wq, "sum", "a0", bins=(5, 5), phi=0.0)
        assert rb.objects_read == rs.objects_read
        assert e_seq.index.n_tiles == e_bat.index.n_tiles
        np.testing.assert_array_equal(
            e_seq.index.bbox[:e_seq.index.n_tiles],
            e_bat.index.bbox[:e_bat.index.n_tiles])
        np.testing.assert_allclose(rb.values, rs.values, rtol=1e-9)
    print("BIN-MATCHED-OK")


def test_bin_aligned_children_nest_in_single_bins():
    """A split tile's children lie inside single bins of the query grid
    wherever at most one bin line per axis crossed the parent — the one
    split the 2×2 grid can place (the mechanism behind the
    repeat-heatmap win; parents spanning 3+ bins per axis need further
    splits, which snapping accelerates but cannot collapse to one)."""
    eng = small_engine(seed=23)
    w = exploration_path(eng.dataset, n_queries=1,
                         target_objects=15_000)[0]
    bins = (6, 6)
    eng.heatmap(w, "sum", "a0", bins=bins, phi=0.0)
    idx = eng.index
    bx, by = bins
    x_lines = np.linspace(w[0], w[2], bx + 1)[1:-1]
    y_lines = np.linspace(w[1], w[3], by + 1)[1:-1]
    ids = np.flatnonzero(idx.active[:idx.n_tiles])
    crossed = 0
    for t in ids:
        if idx.parent[t] < 0 or idx.count[t] == 0:
            continue
        x0, y0, x1, y1 = idx.bbox[t]
        p = idx.parent[t]
        px0, py0, px1, py1 = idx.bbox[p]
        if not (px0 >= w[0] and px1 <= w[2] and py0 >= w[1]
                and py1 <= w[3]):
            continue
        n_cx = int(((x_lines > px0) & (x_lines < px1)).sum())
        n_cy = int(((y_lines > py0) & (y_lines < py1)).sum())
        # parents a single snapped cut per axis can fully resolve
        if n_cx > 1 or n_cy > 1 or (n_cx == 0 and n_cy == 0):
            continue
        crossed += 1
        assert not ((x_lines > x0 + 1e-9) & (x_lines < x1 - 1e-9)).any(), t
        assert not ((y_lines > y0 + 1e-9) & (y_lines < y1 - 1e-9)).any(), t
    assert crossed > 0


def test_trace_totals_breaks_out_query_types():
    """EngineTrace.totals() attributes I/O per query type for mixed
    sessions (consumed by benchmarks/common.py)."""
    eng = small_engine(seed=29)
    w = exploration_path(eng.dataset, n_queries=1,
                         target_objects=10_000)[0]
    r1 = eng.query(w, "sum", "a0", phi=0.05)
    r2 = eng.heatmap(w, "sum", "a0", bins=(4, 4), phi=0.05)
    r3 = eng.query(w, "mean", "a0", phi=0.0)
    tot = eng.trace.totals()
    assert tot["queries"] == 3
    assert tot["scalar_queries"] == 2 and tot["heatmap_queries"] == 1
    assert tot["scalar_objects_read"] == r1.objects_read + r3.objects_read
    assert tot["heatmap_objects_read"] == r2.objects_read
    assert (tot["scalar_objects_read"] + tot["heatmap_objects_read"]
            == tot["total_objects_read"])
    assert tot["scalar_read_calls"] + tot["heatmap_read_calls"] \
        == tot["total_read_calls"]
    assert tot["total_speculative_rows"] == (r1.speculative_rows
                                             + r2.speculative_rows
                                             + r3.speculative_rows)


def _index_state(idx):
    n = idx.n_tiles
    return ([n, idx.active[:n].copy(), idx.count[:n].copy(),
             idx.perm.copy(), idx.bbox[:n].copy()]
            + [m[a][:n].copy() for m in (idx.meta_sum, idx.meta_min,
                                         idx.meta_max, idx.meta_valid)
               for a in sorted(m)])


@pytest.mark.parametrize("agg", ["count", "sum", "mean", "min", "max"])
@pytest.mark.parametrize("bins", [None, (4, 4)], ids=["scalar", "heatmap"])
@pytest.mark.parametrize("phi", [0.0, 0.05])
def test_stepped_driver_equals_run(agg, bins, phi):
    """Stepping the driver by hand with no stage gives the same answer,
    speculative rows, rounds and index evolution as ``run()`` on a
    second engine over the same data, query after query."""
    e_step = small_engine(seed=5)
    e_run = small_engine(seed=5)
    wins = exploration_path(e_step.dataset, n_queries=4,
                            target_objects=2000)
    rounds = 0
    for w in wins:
        stepped = classify(e_step.index, w, agg, "a0", phi,
                           bins=bins).driver
        while (batch := stepped.next_batch()) is not None:
            stepped.fold(batch, *stepped.adapter.read_batch(batch))
        assert stepped.finished
        ran = classify(e_run.index, w, agg, "a0", phi, bins=bins).driver
        assert ran.run() == ran.processed == stepped.processed
        for a, b in zip(stepped.acc.interval(), ran.acc.interval()):
            np.testing.assert_array_equal(a, b)
        assert stepped.speculative_rows == ran.speculative_rows
        assert stepped.rounds == ran.rounds
        for a, b in zip(_index_state(e_step.index),
                        _index_state(e_run.index)):
            np.testing.assert_array_equal(a, b)
        rounds += stepped.rounds
    e_step.index.check_invariants("a0")
    # every case reads, but a count at φ > 0, which its metadata answers
    assert (rounds > 0) == (agg != "count" or phi == 0.0)
