"""Viewport prediction + predictive pre-cracking: extrapolation
exactness, model fallback, answer-neutrality of prefetch, and
learned-salience policy composition.

The load-bearing guarantees:

- the linear candidate is EXACT on linear pans (constant-velocity
  windows), and selection prefers it whenever the online model does not
  strictly beat its rolling hit-rate (random walks fall back to it);
- prefetching NEVER changes any answer: φ=0 queries are bit-identical
  to the reactive engine's, φ>0 intervals stay oracle-containing with
  the bound met — prefetch only splits/enriches tiles, which keeps
  metadata sound;
- prefetch reads are hard-capped by the row budget and fold everything
  they read (zero speculative rows);
- ``salience="learned"`` composes through the existing ``phi_budgets``
  machinery: per-bin budgets still met, zero speculative rows, and the
  unresolved marker is rejected if a query bypasses the engines.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AccuracyPolicy, AQPEngine, IndexConfig,
                        ViewportPredictor)
from repro.core import query as query_mod
from repro.core.predict import (_mlp_init, _sgd_step,
                                resolve_learned_salience)
from repro.data import make_synthetic_dataset

PHI = 0.05


def _engine(n=60_000, seed=3):
    ds = make_synthetic_dataset(n=n, seed=seed)
    cfg = IndexConfig(grid0=(8, 8), min_split_count=256,
                      init_metadata_attrs=("a0",))
    return AQPEngine(ds, cfg)


def _linear_pan(n_steps, step=(40.0, 30.0), start=(100.0, 120.0),
                size=(300.0, 300.0)):
    sx, sy = step
    x0, y0 = start
    w, h = size
    return [(x0 + sx * i, y0 + sy * i, x0 + sx * i + w, y0 + sy * i + h)
            for i in range(n_steps)]


# --------------------------------------------------------------------- #
# the predictor itself
# --------------------------------------------------------------------- #
def test_linear_pan_extrapolation_exact():
    """On a constant-velocity pan the linear candidate reproduces the
    next window EXACTLY (2·w_last − w_prev is affine-exact), and
    selection keeps it (ties never hand over to the model)."""
    p = ViewportPredictor()
    wins = _linear_pan(10)
    for i, w in enumerate(wins[:-1]):
        p.observe(w, bins=(4, 4))
        pred = p.predict()
        if i == 0:
            assert pred is None          # one window can't extrapolate
        else:
            assert p.source == "linear"
            assert pred == wins[i + 1]   # exact, not approximate
    assert p.hit_rate("linear") == 1.0


def test_zoom_is_linear_in_window_coordinates():
    """A constant-rate zoom (each edge moves linearly) is also exactly
    extrapolated — the candidate is per-coordinate affine."""
    p = ViewportPredictor()
    wins = [(100.0 + 10 * i, 100.0 + 10 * i,
             900.0 - 10 * i, 900.0 - 10 * i) for i in range(8)]
    for w in wins[:-1]:
        p.observe(w)
    assert p.predict() == wins[-1]
    assert p.source == "linear"


def test_model_fallback_on_random_walk():
    """On an unpredictable random walk the online model never strictly
    beats the linear baseline's rolling hit-rate, so prediction falls
    back to the exact extrapolation candidate."""
    rng = np.random.default_rng(0)
    p = ViewportPredictor()
    for i in range(25):
        x, y = rng.uniform(100, 800, 2)
        p.observe((x, y, x + 150.0, y + 150.0))
        if p.predict() is not None:
            assert p.source == "linear"
    assert len(p.trajectory) == 25
    assert p.hit_rate("model") <= p.hit_rate("linear")


def test_observe_records_trajectory_and_trains_online():
    p = ViewportPredictor(history=3)
    for w in _linear_pan(6):
        p.observe(w, bins=(8, 8), dwell_s=2.0)
    assert len(p.trajectory) == 6
    assert all(s.bins == (8, 8) and s.dwell_s == 2.0
               for s in p.trajectory)
    # online SGD ran once the delta history was deep enough: 6 windows
    # = 5 deltas; training needs history+1 windows for the input
    assert p.n_trained == 6 - (3 + 1)


@pytest.mark.parametrize("history", [1, 3])
def test_features_are_the_newest_deltas(history):
    """The model's input is the last ``history`` deltas of the whole
    trajectory, each normalized by the newest window's span, however
    long the trajectory has grown."""
    rng = np.random.default_rng(history)
    p = ViewportPredictor(history=history)
    for n in range(1, 40):
        x, y = rng.uniform(0, 900, 2)
        s = rng.uniform(20.0, 60.0)
        p.observe((x, y, x + s, y + 0.5 * s))
        f = p._features()
        if n < history + 1:
            assert f is None
            continue
        ws = np.array([t.window for t in p.trajectory], np.float64)
        last = ws[-1]
        span = np.array([last[2] - last[0], last[3] - last[1]] * 2)
        want = ((ws[1:] - ws[:-1])[-history:] / span).reshape(-1)
        assert f.dtype == np.float32
        np.testing.assert_array_equal(f, want.astype(np.float32))


def test_host_sgd_step_matches_jax_grad():
    """The hand-written backward pass is the gradient of
    ``mean((out − y)²)``: 200 seeded random steps from the predictor's
    init track a ``jax.grad`` reference of the same loss, and every
    parameter stays float32."""
    def loss(params, x, y):
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        return jnp.mean((h @ params["w2"] + params["b2"] - y) ** 2)

    @jax.jit
    def ref_step(params, x, y, lr):
        g = jax.grad(loss)(params, x, y)
        return {k: params[k] - lr * g[k] for k in params}

    rng = np.random.default_rng(11)
    host = _mlp_init(3)
    ref = {k: jnp.asarray(v) for k, v in host.items()}
    lr = np.float32(0.1)
    for _ in range(200):
        x = rng.normal(0.0, 1.0, 12).astype(np.float32)
        y = rng.normal(0.0, 0.5, 4).astype(np.float32)
        host = _sgd_step(host, x, y, lr)
        ref = ref_step(ref, x, y, lr)
        for k in host:
            assert host[k].dtype == np.float32, k
            np.testing.assert_allclose(host[k], np.asarray(ref[k]),
                                       rtol=0, atol=1e-6, err_msg=k)


def test_predictor_touches_no_device():
    """Init, 30 observations on a curved path and a prediction make no
    host↔device transfer; the model's parameters are host arrays."""
    with jax.transfer_guard("disallow"):
        p = ViewportPredictor(history=3)
        for i in range(30):
            x, y = 100.0 + 12.0 * i, 150.0 + 0.8 * i * i
            p.observe((x, y, x + 200.0, y + 200.0), bins=(4, 4))
        assert p.predict() is not None
    assert p.n_trained == 30 - (3 + 1)
    assert all(type(v) is np.ndarray for v in p._params.values())


def test_salience_map_dwell_histogram_properties():
    p = ViewportPredictor()
    q = (0.0, 0.0, 400.0, 400.0)
    # empty trajectory → the uniform fallback
    np.testing.assert_array_equal(p.salience_map(q, (4, 4)),
                                  np.ones(16))
    # dwell concentrated in the lower-left quadrant of the query window
    p.observe((0.0, 0.0, 200.0, 200.0), dwell_s=5.0)
    p.observe((600.0, 600.0, 900.0, 900.0), dwell_s=1.0)  # off-window
    s = p.salience_map(q, (2, 2), floor=0.25)
    assert s.shape == (4,)
    assert ((s >= 0.25) & (s <= 1.0)).all()
    assert s[0] == 1.0                   # the dwelled bin is maximal
    np.testing.assert_allclose(s[1:], 0.25)   # never-visited bins floor


# --------------------------------------------------------------------- #
# predictive pre-cracking never changes answers
# --------------------------------------------------------------------- #
def test_prefetch_exact_answers_bit_identical():
    """φ=0 heatmaps and scalars on a prefetching engine are bit-for-bit
    the reactive engine's — and the predicted pre-cracking makes the
    pan strictly cheaper at query time."""
    reactive, pred = _engine(), _engine()
    wins = _linear_pan(8)
    ra, rb = [], []
    for w in wins:
        ra.append(reactive.heatmap(w, "mean", "a0", bins=(4, 4), phi=0.0))
        pred.prefetch(5_000)
        rb.append(pred.heatmap(w, "mean", "a0", bins=(4, 4), phi=0.0))
    for a, b in zip(ra, rb):
        np.testing.assert_array_equal(a.values, b.values)
        np.testing.assert_array_equal(a.lo, b.lo)
        np.testing.assert_array_equal(a.hi, b.hi)
        assert a.exact and b.exact
    assert (sum(r.objects_read for r in rb)
            < sum(r.objects_read for r in ra))
    # scalar queries too (same index, different accumulator path)
    qa = reactive.query(wins[-1], "sum", "a0", phi=0.0)
    qb = pred.query(wins[-1], "sum", "a0", phi=0.0)
    assert qa.value == qb.value and qa.lo == qb.lo and qa.hi == qb.hi


def test_prefetch_approximate_answers_stay_contained():
    """Under φ>0 the prefetched engine's intervals still contain the
    oracle and meet φ — pre-cracking shifts WHERE refinement effort is
    spent, never the soundness of the bounds."""
    eng = _engine()
    for w in _linear_pan(8):
        eng.prefetch(4_000)
        h = eng.heatmap(w, "mean", "a0", bins=(4, 4), phi=PHI)
        assert h.exact or h.bound <= PHI + 1e-12
        truth = eng.heatmap_oracle(w, "mean", "a0", bins=(4, 4))
        occ = eng.heatmap_oracle(w, "count", "a0", bins=(4, 4)) > 0
        assert (h.lo[occ] - 1e-9 <= truth[occ]).all()
        assert (truth[occ] <= h.hi[occ] + 1e-9).all()


def test_prefetch_budget_is_hard_and_speculation_free():
    eng = _engine()
    wins = _linear_pan(6)
    for w in wins[:3]:
        eng.heatmap(w, "mean", "a0", bins=(4, 4), phi=PHI)
    spec_before = eng.adapt_stats.speculative_rows
    rec = eng.prefetch(2_500)
    assert rec["source"] in ("linear", "model")
    assert 0 < rec["rows_read"] <= 2_500       # the HARD row budget
    assert rec["tiles_cracked"] > 0
    # everything read was folded: prefetching adds zero speculation
    assert eng.adapt_stats.speculative_rows == spec_before
    assert eng.trace.prefetches[-1] is rec
    assert eng.trace.totals()["prefetch_rows"] == rec["rows_read"]


def test_prefetch_without_trajectory_is_a_no_op():
    eng = _engine()
    rec = eng.prefetch(10_000)
    assert rec["predicted"] is None and rec["rows_read"] == 0
    eng.heatmap((100, 100, 400, 400), "mean", "a0", bins=(4, 4))
    rec = eng.prefetch(10_000)     # one observation still can't predict
    assert rec["predicted"] is None and rec["rows_read"] == 0


def test_prefetch_warms_bin_grid_memory_for_predicted_viewport():
    """A correct prediction turns the NEXT heatmap into a (near) pure
    metadata/bin-grid answer: with a budget covering the window, the
    repeat on the predicted viewport costs far less than the reactive
    engine pays for the same step."""
    reactive, pred = _engine(), _engine()
    wins = _linear_pan(6)
    for w in wins[:-1]:
        reactive.heatmap(w, "mean", "a0", bins=(4, 4), phi=0.0)
        pred.heatmap(w, "mean", "a0", bins=(4, 4), phi=0.0)
    pred.prefetch(60_000)          # budget ≥ dataset: full pre-crack
    r_react = reactive.heatmap(wins[-1], "mean", "a0", bins=(4, 4),
                               phi=0.0)
    r_pred = pred.heatmap(wins[-1], "mean", "a0", bins=(4, 4), phi=0.0)
    np.testing.assert_array_equal(r_react.values, r_pred.values)
    assert r_pred.objects_read < r_react.objects_read


# --------------------------------------------------------------------- #
# learned salience composes through the phi_budgets machinery
# --------------------------------------------------------------------- #
def test_learned_salience_budgets_met_zero_speculation():
    eng = _engine()
    wins = _linear_pan(5)
    pol = AccuracyPolicy(salience="learned", eps_abs=1e-3)
    for w in wins:
        h = eng.heatmap(w, "mean", "a0", bins=(4, 4), phi=0.1,
                        policy=pol, dwell_s=1.5)
        assert h.speculative_rows == 0
        assert h.phi_b is not None and h.bin_met is not None
        occ = np.asarray(h.values) != 0
        assert np.asarray(h.bin_met)[occ].all()


def test_learned_salience_resolves_from_dwell_history():
    """The resolved policy tightens where the session dwelled (salience
    1 → φ_b = φ) and relaxes elsewhere (floor → φ/floor)."""
    eng = _engine()
    # dwell repeatedly on one region
    stay = (100.0, 100.0, 300.0, 300.0)
    for _ in range(3):
        eng.heatmap(stay, "mean", "a0", bins=(4, 4), phi=PHI)
    pol = AccuracyPolicy(salience="learned")
    q = (100.0, 100.0, 500.0, 500.0)   # half dwelled, half fresh
    resolved = resolve_learned_salience(pol, eng.predictor, q, (2, 2))
    assert isinstance(resolved.salience, np.ndarray)
    phi_b = resolved.phi_b(PHI, (2, 2))
    assert phi_b[0] == pytest.approx(PHI)          # dwelled quadrant
    assert phi_b[3] == pytest.approx(PHI / pol.salience_floor)
    # pass-through for everything that is not the marker
    assert resolve_learned_salience(None, eng.predictor, q, (2, 2)) is None
    keep = AccuracyPolicy(salience="center")
    assert resolve_learned_salience(keep, eng.predictor, q,
                                    (2, 2)) is keep


def test_unresolved_learned_salience_rejected_off_engine():
    """A query that bypasses the engines cannot silently run with the
    unresolved marker — the accumulator path raises."""
    eng = _engine(n=10_000)
    pol = AccuracyPolicy(salience="learned")
    with pytest.raises(ValueError, match="resolved"):
        query_mod.evaluate_heatmap(eng.index, (100, 100, 400, 400),
                                   "mean", "a0", bins=(4, 4), phi=PHI,
                                   policy=pol)
