"""Sixteen analysts, each dragging their own viewport, share one
``ServingEngine``: every answer against a float64 scan of the file on
each backend the CPU runs, batched ≡ sequential bit for bit, and the
tick's spans and counters."""
import sys
from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import spans
from repro.core import AQPEngine, IndexConfig, ServingEngine
from repro.data import make_synthetic_dataset

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench import trace_reduce  # noqa: E402

N = 200_000
SESSIONS = 16
STEPS = 3
PHI = 0.05
AGGS = ("mean", "sum")
ANSWER_FIELDS = ("value", "lo", "hi", "bound", "exact", "tiles_full",
                 "tiles_partial", "tiles_processed", "speculative_rows")


@pytest.fixture(scope="module")
def ds():
    return make_synthetic_dataset(n=N, n_columns=2, seed=15)


def _paths(ds, target=4000):
    """One drag per session: a window of ~``target`` objects starting on
    a point of the file drawn for the session, moved 10-20 % of its
    side a step; corners are float32 values."""
    rng = np.random.default_rng(1501)
    x0d, y0d, x1d, y1d = ds.domain()
    side = float(np.sqrt((x1d - x0d) * (y1d - y0d) * target / ds.n))
    paths = []
    for row in rng.choice(ds.n, SESSIONS, replace=False):
        cx, cy = float(ds.x[row]), float(ds.y[row])
        path = []
        for _ in range(STEPS):
            x0 = min(max(cx - side / 2, x0d), x1d - side)
            y0 = min(max(cy - side / 2, y0d), y1d - side)
            path.append(tuple(float(np.float32(v))
                              for v in (x0, y0, x0 + side, y0 + side)))
            mag = rng.uniform(0.10, 0.20) * side
            ang = rng.uniform(0, 2 * np.pi)
            cx = min(max(cx + mag * np.cos(ang), x0d + side / 2),
                     x1d - side / 2)
            cy = min(max(cy + mag * np.sin(ang), y0d + side / 2),
                     y1d - side / 2)
        paths.append(path)
    return paths


def _serve(ds, backend, mode="batched"):
    """Every session sends its next view, then one tick serves them all,
    ``STEPS`` times. Returns the server and, per tick, the views sent,
    the answers and the tick's ``AdaptStats`` delta."""
    cfg = IndexConfig(grid0=(8, 8), min_split_count=256,
                      init_metadata_attrs=("a0",), backend=backend)
    server = ServingEngine(AQPEngine(ds, cfg), mode=mode)
    sessions = [server.open_session() for _ in range(SESSIONS)]
    paths = _paths(ds)
    ticks = []
    for step in range(STEPS):
        views = []
        for s, (ses, path) in enumerate(zip(sessions, paths)):
            views.append((path[step], AGGS[(s + step) % 2]))
            ses.query(path[step], views[-1][1], "a0", phi=PHI)
        before = server.index.adapt_stats.snapshot()
        results = server.tick()
        ticks.append((views, results,
                      server.index.adapt_stats.delta(before)))
    return server, ticks


def _truth(ds, window, agg):
    """The aggregate of ``a0`` over the closed window: a float64 scan."""
    x0, y0, x1, y1 = window
    m = (ds.x >= x0) & (ds.x <= x1) & (ds.y >= y0) & (ds.y <= y1)
    v = ds.read_all_unaccounted("a0")[m].astype(np.float64)
    return v.sum() if agg == "sum" else v.mean()


@pytest.mark.parametrize("backend", ["np", "jnp", "pallas"])
def test_sixteen_drags_hold_the_scan(ds, backend):
    _, ticks = _serve(ds, backend)
    for views, results, _ in ticks:
        assert len(results) == SESSIONS
        for (window, agg), r in zip(views, results):
            t = _truth(ds, window, agg)
            # device backends sum in float32 (the f64 fold keeps it)
            tol = 1e-6 * max(abs(t), 1.0)
            assert r.lo - tol <= t <= r.hi + tol, (window, agg, r)
            assert abs(r.value - t) <= PHI * abs(t) + tol


def test_sixteen_drags_batched_equals_sequential(ds):
    sa, ta = _serve(ds, "np", "batched")
    sb, tb = _serve(ds, "np", "sequential")
    for (_, ra, _), (_, rb, _) in zip(ta, tb):
        for x, y in zip(ra, rb):
            for f in ANSWER_FIELDS:
                assert getattr(x, f) == getattr(y, f), f
    assert sa.last_publish == sb.last_publish


@pytest.mark.parametrize("mode", ["batched", "sequential"])
def test_answers_share_the_tick_op_counters(ds, mode):
    """Each fused pass is credited in equal shares to the queries it
    packs, each published apply to its query: a tick's answers sum to
    the tick's op calls and H2D bytes."""
    _, ticks = _serve(ds, "jnp", mode)
    for _, results, delta in ticks:
        assert delta.kernel_calls > 0 and delta.h2d_bytes > 0
        assert sum(r.kernel_calls for r in results) == delta.kernel_calls
        assert sum(r.h2d_bytes for r in results) == delta.h2d_bytes
        assert all(isinstance(r.kernel_calls, int)
                   and isinstance(r.h2d_bytes, int) for r in results)


def test_tick_counters(ds):
    server, ticks = _serve(ds, "np")
    for k, (_, results, _) in enumerate(ticks):
        counters = {r.serving for r in results}
        assert {c.tick for c in counters} == {k}
        assert {c.tick_tickets for c in counters} == {len(results)}
        assert len({(c.publish_s, c.splits_masked) for c in counters}) == 1
        assert all(c.queue_s >= 0.0 and c.publish_s >= 0.0
                   for c in counters)
    assert results[0].serving.splits_masked == \
        server.last_publish["splits_masked"]
    eng = AQPEngine(ds, IndexConfig(grid0=(8, 8)))
    assert eng.query(ticks[0][0][0][0], "sum", "a0", phi=PHI).serving is None


@pytest.fixture(scope="module")
def traced(ds, tmp_path_factory):
    d = tmp_path_factory.mktemp("serve_trace")
    with jax.profiler.trace(str(d)):
        server, ticks = _serve(ds, "jnp")
    found = sorted(d.glob("plugins/profile/*/*.xplane.pb"))
    names = set(spans.SERVE_NAMES) | set(spans.NAMES)
    host = trace_reduce.extract(found[-1], lambda n: n in names)["host"]
    return host, ticks


def _intervals(host, name):
    return [(s, s + d) for n, s, d, _ in host if n == name]


def _inside(inner, outer):
    return all(any(os <= s and e <= oe for os, oe in outer)
               for s, e in inner)


def test_tick_opens_every_serve_span(traced):
    host, ticks = traced
    calls = Counter(n for n, *_ in host)
    rounds = sum(max(r.batch_rounds for r in results)
                 for _, results, _ in ticks)
    assert set(spans.SERVE_NAMES) <= set(calls)
    assert calls[spans.SERVE_TICK] == calls[spans.SERVE_PUBLISH] == STEPS
    assert calls[spans.SERVE_ROUND] == calls[spans.SERVE_FOLD] == rounds > 0
    assert calls[spans.GATHER] == sum(d.batch_rounds for *_, d in ticks)
    assert calls[spans.PREDICT] == STEPS * SESSIONS
    assert calls[spans.KERNEL] == sum(d.kernel_calls for *_, d in ticks)
    assert len({t for *_, t in host}) == 1


@pytest.mark.parametrize("inner,outer", [
    (spans.SERVE_ROUND, spans.SERVE_TICK),
    (spans.SERVE_PUBLISH, spans.SERVE_TICK),
    (spans.SERVE_FOLD, spans.SERVE_ROUND),
    (spans.GATHER, spans.SERVE_ROUND),
    (spans.READ, spans.GATHER),
    (spans.APPLY, spans.SERVE_PUBLISH)])
def test_serve_spans_nest(traced, inner, outer):
    host, _ = traced
    inner_iv = _intervals(host, inner)
    if inner == spans.READ:    # the init pass reads outside any tick
        meta = _intervals(host, spans.INIT_META)
        inner_iv = [iv for iv in inner_iv if not _inside([iv], meta)]
    assert inner_iv and _inside(inner_iv, _intervals(host, outer))


def test_predictor_and_kernels_outside_folds(traced):
    """Submits update the predictor before any tick; every op call runs
    in a round's fused pass or in the publication, never in a fold."""
    host, _ = traced
    ticks = _intervals(host, spans.SERVE_TICK)
    assert not any(_inside([iv], ticks)
                   for iv in _intervals(host, spans.PREDICT))
    kernels = _intervals(host, spans.KERNEL)
    work = (_intervals(host, spans.SERVE_ROUND)
            + _intervals(host, spans.SERVE_PUBLISH))
    assert kernels and _inside(kernels, work)
    assert not any(_inside([k], _intervals(host, spans.SERVE_FOLD))
                   for k in kernels)
