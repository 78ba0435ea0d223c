"""Concurrent serving engine: epoch isolation, micro-batch ≡ sequential
parity, skip-under-contention, and mid-session retire degradation."""
import numpy as np
import pytest

from repro.core import AQPEngine, IndexConfig, ServingEngine
from repro.core.index import ChunkIndexSet, EpochStage, TileIndex
from repro.data.chunked import ChunkedDataset
from repro.data.rawfile import RawDataset

PHI = 0.05
# answer fields that must match bit-for-bit across serving modes;
# cost fields (objects_read/read_calls/batch_rounds/eval_time_s) are
# attribution and legitimately differ
ANSWER_FIELDS = ("value", "lo", "hi", "bound", "exact", "tiles_full",
                 "tiles_partial", "tiles_processed", "speculative_rows",
                 "retired_during_query")


def _dataset(n=60_000, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.uniform(0, 1000, n)
    ys = rng.uniform(0, 1000, n)
    a0 = (xs / 10 + rng.normal(0, 5, n) + 100).astype(np.float64)
    return RawDataset(xs, ys, {"a0": a0})


def _server(seed=0, *, chunked=False, mode="batched", crack_budget=None,
            n=60_000, prefetch_rows=None):
    ds = _dataset(n, seed)
    if chunked:
        ds = ChunkedDataset.from_dataset(ds)
    cfg = IndexConfig(grid0=(8, 8), min_split_count=256,
                      init_metadata_attrs=("a0",))
    return ServingEngine(AQPEngine(ds, cfg), mode=mode,
                         crack_budget=crack_budget,
                         prefetch_rows=prefetch_rows)


# a deterministic two-session interleaving: per tick, each session's
# (window, kind) submissions in arrival order
def _script(rng):
    ticks = []
    for _ in range(3):
        subs = []
        for sid in range(2):
            cx, cy = rng.uniform(150, 850, 2)
            w = rng.uniform(60, 200)
            subs.append((sid, "query",
                         (cx - w, cy - w, cx + w, cy + w), None))
        # session 1 also pans a heatmap over session 0's region —
        # same-tile contention between the two sessions
        subs.append((1, "heatmap", subs[0][2], (4, 4)))
        ticks.append(subs)
    return ticks


def _play(server, sessions, ticks, *, agg="mean", phi=PHI):
    out = []
    for subs in ticks:
        for sid, kind, win, bins in subs:
            s = sessions[sid]
            if kind == "query":
                s.query(win, agg, "a0", phi=phi)
            else:
                s.heatmap(win, agg, "a0", bins=bins, phi=phi)
        out.extend(server.tick())
    return out


def _parts(index):
    if isinstance(index, TileIndex):
        return [index]
    return [index._indexes[k] for k in sorted(index._indexes)]


def _fingerprint(index):
    return [(ti.n_tiles, int(ti.active.sum()), ti.count[:ti.n_tiles].copy(),
             ti.perm.copy(),
             {a: (v[:ti.n_tiles].copy(),
                  ti.meta_min[a][:ti.n_tiles].copy(),
                  ti.meta_max[a][:ti.n_tiles].copy(),
                  ti.meta_valid[a][:ti.n_tiles].copy())
              for a, v in ti.meta_sum.items()})
            for ti in _parts(index)]


def _assert_fingerprint_equal(fa, fb):
    assert len(fa) == len(fb)
    for (n1, a1, c1, p1, m1), (n2, a2, c2, p2, m2) in zip(fa, fb):
        assert n1 == n2 and a1 == a2
        np.testing.assert_array_equal(c1, c2)
        np.testing.assert_array_equal(p1, p2)
        assert m1.keys() == m2.keys()
        for k in m1:
            for x, y in zip(m1[k], m2[k]):
                np.testing.assert_array_equal(x, y)


def _assert_answers_equal(ra, rb):
    assert type(ra) is type(rb)
    for f in ANSWER_FIELDS:
        if not hasattr(ra, f):
            continue
        va, vb = getattr(ra, f), getattr(rb, f)
        if isinstance(va, np.ndarray):
            np.testing.assert_array_equal(va, vb, err_msg=f)
        else:
            assert va == vb, (f, va, vb)
    if hasattr(ra, "values"):       # heatmap grids
        np.testing.assert_array_equal(ra.values, rb.values)
        np.testing.assert_array_equal(ra.bin_bound, rb.bin_bound)


@pytest.mark.parametrize("chunked", [False, True])
@pytest.mark.parametrize("crack_budget", [None, 1])
@pytest.mark.parametrize("agg", ["count", "sum", "mean", "min", "max"])
@pytest.mark.parametrize("phi", [0.0, PHI])
def test_batched_tick_equals_sequential(chunked, crack_budget, agg, phi):
    """The tentpole contract: a micro-batched tick produces bit-for-bit
    the same answers AND the same published index evolution as the
    per-query sequential reference — with and without the
    skip-under-contention budget. Each aggregate and φ takes its own
    branch of the driver's round head and body: predictive sizing (sum,
    mean at φ > 0), the geometric ramp (min, max at φ > 0), full-size
    rounds and the suffix-width certainty check (φ = 0), and the
    metadata fast path (count)."""
    sa = _server(chunked=chunked, mode="batched",
                 crack_budget=crack_budget)
    sb = _server(chunked=chunked, mode="sequential",
                 crack_budget=crack_budget)
    ses_a = [sa.open_session() for _ in range(2)]
    ses_b = [sb.open_session() for _ in range(2)]
    ticks = _script(np.random.default_rng(7))
    ra = _play(sa, ses_a, ticks, agg=agg, phi=phi)
    rb = _play(sb, ses_b, ticks, agg=agg, phi=phi)
    assert len(ra) == len(rb) == sum(len(t) for t in ticks)
    for x, y in zip(ra, rb):
        _assert_answers_equal(x, y)
    _assert_fingerprint_equal(_fingerprint(sa.index),
                              _fingerprint(sb.index))
    assert sa.last_publish == sb.last_publish


def test_oracle_containment_while_cracking():
    """Every answer served during active index cracking keeps its
    deterministic guarantee: truth ∈ [lo, hi] and bound ≤ φ."""
    server = _server()
    sessions = [server.open_session() for _ in range(2)]
    tickets = []
    rng = np.random.default_rng(11)
    for _ in range(4):
        for s in sessions:
            cx, cy = rng.uniform(200, 800, 2)
            w = rng.uniform(80, 250)
            tickets.append(s.query((cx - w, cy - w, cx + w, cy + w),
                                   "mean", "a0", phi=PHI))
        server.tick()
    assert server.epoch == 4
    for tk in tickets:
        r = tk.result
        assert r.exact or r.bound <= PHI + 1e-12
        truth = server.engine.oracle(tk.window, "mean", "a0")
        assert r.lo - 1e-9 <= truth <= r.hi + 1e-9


def test_no_reader_observes_half_applied_split(monkeypatch):
    """Epoch isolation: the shared index is byte-identical to its
    pre-tick state up to the instant of publication — every mutation of
    the tick goes through the stage, none lands mid-round."""
    server = _server()
    s0 = server.open_session()
    s1 = server.open_session()
    pre = {}
    seen = {"published": 0}
    orig_publish = EpochStage.publish

    def checked_publish(self):
        _assert_fingerprint_equal(_fingerprint(server.index),
                                  pre["fp"])
        seen["published"] += 1
        return orig_publish(self)

    monkeypatch.setattr(EpochStage, "publish", checked_publish)
    for tick in range(2):
        s0.query((100, 100, 600, 600), "mean", "a0", phi=PHI)
        s1.query((150, 150, 700, 700), "sum", "a0", phi=PHI)
        s1.heatmap((100, 100, 600, 600), "mean", "a0", bins=(4, 4),
                   phi=PHI)
        pre["fp"] = _fingerprint(server.index)
        server.tick()
    assert seen["published"] == 2
    # publication DID mutate the index afterwards (splits landed)
    post = _fingerprint(server.index)
    assert post[0][0] > pre["fp"][0][0]


def test_same_tick_queries_read_frozen_epoch():
    """Two identical same-tick queries each see the pre-tick index: the
    second does NOT benefit from the first one's cracking (equal work,
    equal answers); after publication a repeat costs strictly less."""
    win = (200, 200, 700, 700)
    server = _server(mode="sequential")   # per-query cost attribution
    sa, sb = server.open_session(), server.open_session()
    ta = sa.query(win, "mean", "a0", phi=PHI)
    tb = sb.query(win, "mean", "a0", phi=PHI)
    server.tick()
    assert ta.result.objects_read == tb.result.objects_read > 0
    assert ta.result.value == tb.result.value
    tc = sa.query(win, "mean", "a0", phi=PHI)
    server.tick()
    assert tc.result.objects_read < ta.result.objects_read


def test_same_tile_split_contention_masked():
    """Two sessions refining the same region stage splits of the same
    tiles; publication lets the first claimant split and masks the
    later one to an enrichment — and counts it."""
    win = (200, 200, 700, 700)
    server = _server()
    sa, sb = server.open_session(), server.open_session()
    sa.query(win, "mean", "a0", phi=PHI)
    sb.query(win, "sum", "a0", phi=PHI)
    server.tick()
    assert server.last_publish["rounds_published"] > 0
    assert server.last_publish["splits_masked"] > 0


def test_crack_budget_skip_still_meets_phi():
    """Queries past the per-tick crack budget skip staging entirely but
    still answer within φ; only budgeted queries publish rounds."""
    server = _server(crack_budget=1)
    sessions = [server.open_session() for _ in range(3)]
    win = (150, 150, 800, 800)
    tickets = [s.query(win, "mean", "a0", phi=PHI) for s in sessions]
    server.tick()
    for tk in tickets:
        r = tk.result
        assert r.exact or r.bound <= PHI + 1e-12
        truth = server.engine.oracle(win, "mean", "a0")
        assert r.lo - 1e-9 <= truth <= r.hi + 1e-9
    # an unbudgeted run of the same tick publishes strictly more rounds
    free = _server(crack_budget=None)
    ses = [free.open_session() for _ in range(3)]
    for s in ses:
        s.query(win, "mean", "a0", phi=PHI)
    free.tick()
    assert (free.last_publish["rounds_published"]
            > server.last_publish["rounds_published"])


def test_metadata_fast_path_skips_reads():
    """φ met from pending-interval bounds alone ⇒ zero reads, zero
    staged rounds (the SKIP fast path)."""
    server = _server()
    s = server.open_session()
    t = s.query((-1e9, -1e9, 1e9, 1e9), "count", "a0", phi=0.5)
    server.tick()
    assert t.result.objects_read == 0
    assert server.last_publish["rounds_published"] == 0


def test_retired_during_query_degrades_gracefully():
    """A chunk retired mid-session: read-time detection drops its tiles
    from the answer set and surfaces ``retired_during_query`` — in both
    serving modes, with identical degraded answers."""
    results = {}
    for mode in ("batched", "sequential"):
        server = _server(chunked=True, mode=mode)
        s = server.open_session()
        win = (100, 100, 900, 900)
        s.query(win, "mean", "a0", phi=PHI)
        server.tick()               # materializes per-chunk indexes
        ds = server.engine.dataset
        ds.chunk(ds.live_ids[0]).data.close()
        t = s.query(win, "mean", "a0", phi=0.0)
        server.tick()
        assert t.result.retired_during_query
        results[mode] = t.result
    _assert_answers_equal(results["batched"], results["sequential"])


def test_per_session_traces_and_lifecycle():
    server = _server()
    sa = server.open_session("alice")
    sb = server.open_session("bob")
    sa.query((100, 100, 500, 500), "mean", "a0", phi=PHI)
    sb.query((300, 300, 700, 700), "mean", "a0", phi=PHI)
    sb.heatmap((300, 300, 700, 700), "mean", "a0", bins=(2, 2), phi=PHI)
    server.tick()
    assert sa.trace.totals()["queries"] == 1
    tb = sb.trace.totals()
    assert tb["queries"] == 2
    assert tb["scalar_queries"] == 1 and tb["heatmap_queries"] == 1
    # closing drops queued tickets and rejects new submissions
    sb.query((0, 0, 100, 100), "mean", "a0", phi=PHI)
    sb.close()
    assert server.n_queued == 0
    with pytest.raises(RuntimeError):
        sb.query((0, 0, 100, 100), "mean", "a0", phi=PHI)
    assert server.tick() == []      # empty tick is a no-op
    assert sa.trace.totals()["queries"] == 1


def test_engine_serve_shares_index():
    """AQPEngine.serve() lifts the live engine: serving-published splits
    are visible to direct engine queries and vice versa."""
    ds = _dataset()
    eng = AQPEngine(ds, IndexConfig(grid0=(8, 8), min_split_count=256,
                                    init_metadata_attrs=("a0",)))
    server = eng.serve()
    assert server.engine is eng and server.index is eng.index
    s = server.open_session()
    # φ=1%: tighter than the seed grid's metadata bound, forcing reads
    t = s.query((200, 200, 800, 800), "mean", "a0", phi=0.01)
    server.tick()
    assert t.result.objects_read > 0
    # adaptation published through serving is visible to direct engine
    # queries on the same index: the repeat answers more from metadata
    r = eng.query((200, 200, 800, 800), "mean", "a0", phi=0.01)
    assert r.exact or r.bound <= 0.01 + 1e-12
    assert r.objects_read < t.result.objects_read


# --------------------------------------------------------------------- #
# satellite: per-session round-robin crack budget (starvation fix)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ["batched", "sequential"])
def test_crack_budget_round_robin_no_starvation(mode):
    """Regression: the crack budget used to be keyed on ARRIVAL order,
    so a chatty session's earlier arrivals consumed every slot and a
    quieter session never got to refine its region. Slots are now
    granted round-robin across sessions — with budget 2 and a session
    submitting 3 tickets before another's 1, the quiet session's
    ticket takes the second slot."""
    sv = _server(mode=mode, crack_budget=2)
    chatty = sv.open_session("chatty")
    quiet = sv.open_session("quiet")
    wb = (600.0, 600.0, 900.0, 900.0)
    for d in (0.0, 15.0, 30.0):
        chatty.query((100 + d, 100 + d, 400 + d, 400 + d), "mean", "a0",
                     phi=PHI)
    # φ tight enough that metadata alone can't answer: quiet MUST read
    # — and with its grant, its refinement publishes
    t_quiet = quiet.query(wb, "mean", "a0", phi=0.005)
    sv.tick()
    # arrival order is chatty,chatty,chatty,quiet; the old arrival-
    # keyed budget granted chatty's first TWO tickets and starved quiet
    assert sv.last_grants == [True, False, False, True]
    assert t_quiet.result.objects_read > 0

    # the grant is real: quiet's published refinement makes the repeat
    # of its own query strictly cheaper next tick (disjoint windows, so
    # only quiet's own cracks can explain the drop)
    t_again = quiet.query(wb, "mean", "a0", phi=0.005)
    sv.tick()
    assert t_again.result.objects_read < t_quiet.result.objects_read


# --------------------------------------------------------------------- #
# tentpole: per-session predictive pre-cracking between ticks
# --------------------------------------------------------------------- #
def _pan_script(server, n_ticks=4, phi=PHI):
    a = server.open_session("A")
    b = server.open_session("B")
    out = []
    for i in range(n_ticks):
        wa = (100 + 40 * i, 100 + 30 * i, 380 + 40 * i, 380 + 30 * i)
        wb = (500 - 20 * i, 500 + 10 * i, 800 - 20 * i, 800 + 10 * i)
        a.heatmap(wa, "mean", "a0", bins=(4, 4), phi=phi)
        b.heatmap(wb, "mean", "a0", bins=(4, 4), phi=phi)
        out.extend(server.tick())
    return out


@pytest.mark.parametrize("chunked", [False, True])
def test_prefetch_keeps_batched_sequential_parity(chunked):
    """Predictive pre-cracking is staged through the same epoch with
    owners past every query, and its inputs (tickets + submit-time
    predictor states) are mode-independent — so the cross-mode parity
    contract survives with prefetching on."""
    sa = _server(chunked=chunked, mode="batched", crack_budget=8,
                 prefetch_rows=3_000)
    sb = _server(chunked=chunked, mode="sequential", crack_budget=8,
                 prefetch_rows=3_000)
    ra = _pan_script(sa)
    rb = _pan_script(sb)
    for x, y in zip(ra, rb):
        _assert_answers_equal(x, y)
    _assert_fingerprint_equal(_fingerprint(sa.index),
                              _fingerprint(sb.index))
    assert sa.last_publish == sb.last_publish
    # prefetching actually happened and was attributed per session
    assert [p["session"] for p in sa.last_prefetch] == ["A", "B"]
    assert sa.last_prefetch == sb.last_prefetch


def test_prefetch_never_alters_served_answers():
    """φ=0 served answers are bit-identical with and without predictive
    pre-cracking (splits/enrichments are answer-neutral), and prefetch
    only ever runs between ticks (leftover budget)."""
    s_on = _server(mode="batched", prefetch_rows=4_000)
    s_off = _server(mode="batched", prefetch_rows=None)
    r_on = _pan_script(s_on, phi=0.0)
    r_off = _pan_script(s_off, phi=0.0)
    assert any(p["rows_read"] > 0 for p in s_on.last_prefetch)
    for x, y in zip(r_on, r_off):
        np.testing.assert_array_equal(x.values, y.values)
        np.testing.assert_array_equal(x.lo, y.lo)
        np.testing.assert_array_equal(x.hi, y.hi)
        assert x.exact and y.exact
    # the prefetched server answered the SAME exact answers with fewer
    # query-time reads on the extrapolable pan
    read_on = sum(r.objects_read for r in r_on)
    read_off = sum(r.objects_read for r in r_off)
    assert read_on < read_off


def test_prefetch_consumes_only_leftover_budget():
    """With the whole crack budget spent on queries there is nothing
    left over — no prefetch runs, however chatty the sessions."""
    sv = _server(mode="batched", crack_budget=2, prefetch_rows=4_000)
    _pan_script(sv)
    assert sv.last_prefetch == []


def test_batched_tick_hot_path_is_fused_multi():
    """The heatmap serving tick's hot path must be the fused multi-window
    op, not the retired per-segment host-mirror loop: serving may not
    reference ``segment_window_bin_agg_multi_np`` at all (the batched ≡
    sequential parity above proves the replacement answer-neutral)."""
    import inspect
    from repro.core import serving as serving_mod
    src = inspect.getsource(serving_mod)
    assert "segment_window_bin_agg_multi_np" not in src
    assert "segment_window_bin_select_multi" in src
