"""The program's spans and counters on a profiler trace of a small
session (CPU, device data plane on the "jnp" backend): every phase of a
view opens its span once per query or round, properly nested, and the
kernel counters agree with the spans and with the shapes shipped."""
import sys
from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import spans
from repro.core import AQPEngine, IndexConfig
from repro.data import make_synthetic_dataset
from repro.data.synthetic import exploration_path
from repro.kernels import ops

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench import trace_reduce  # noqa: E402

N_VIEWS = 4


def _config(backend):
    return IndexConfig(grid0=(8, 8), min_split_count=64,
                       init_metadata_attrs=("a0",), backend=backend)


def _views(ds):
    wins = exploration_path(ds, n_queries=N_VIEWS, target_objects=4000)
    return [(w, ("sum", "mean")[i % 2]) for i, w in enumerate(wins)]


class _Shipped:
    """Records the shapes of every packed op call the index makes, and
    the object planes ``_segment_stream`` pads for them."""

    COLS = {"segment_window_agg": lambda a, kw: ops.kernel_cols(True),
            "segment_bin_agg": lambda a, kw: ops.kernel_cols(
                False, kw["gx"], kw["gy"]),
            "segment_bin_agg_edges": lambda a, kw: ops.kernel_cols(
                False, np.shape(a[4])[1] - 1, np.shape(a[5])[1] - 1)}

    def __init__(self, monkeypatch):
        self.calls, self.planes = [], []
        for name, cols in self.COLS.items():
            monkeypatch.setattr(ops, name, self._wrap(getattr(ops, name),
                                                      cols))
        stream = ops._segment_stream

        def recorded(xs, ys, vals, boundaries):
            out = stream(xs, ys, vals, boundaries)
            self.planes.append((out[4], [len(p) for p in out[:4]]))
            return out
        monkeypatch.setattr(ops, "_segment_stream", recorded)

    def _wrap(self, fn, cols):
        def call(*args, **kwargs):
            bounds = np.asarray(args[3])
            self.calls.append((int(bounds[-1]), len(bounds) - 1,
                               cols(args, kwargs), kwargs["backend"]))
            return fn(*args, **kwargs)
        return call

    def h2d_bytes(self):
        return sum(ops.h2d_bytes(b, n, s, c) for n, s, c, b in self.calls)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A session traced from engine construction on: its host spans (the
    window's thread), the answers, and the adaptation counters' delta."""
    ds = make_synthetic_dataset(n=60_000, seed=11)
    mp = pytest.MonkeyPatch()
    shipped = _Shipped(mp)
    d = tmp_path_factory.mktemp("trace")
    try:
        with jax.profiler.trace(str(d)):
            eng = AQPEngine(ds, _config("jnp"))
            before = eng.adapt_stats.snapshot()
            results = [eng.query(w, agg, "a0", phi=0.05)
                       for w, agg in _views(ds)]
            results.append(eng.heatmap(_views(ds)[0][0], "sum", "a0",
                                       bins=(4, 4), phi=0.05))
    finally:
        mp.undo()
    delta = eng.adapt_stats.delta(before)
    found = sorted(d.glob("plugins/profile/*/*.xplane.pb"))
    ex = trace_reduce.extract(found[-1], lambda n: n in spans.NAMES)
    return ex["host"], results, delta, shipped


def _intervals(host, name):
    return [(s, s + d) for n, s, d, _ in host if n == name]


def _inside(inner, outer):
    return all(any(os <= s and e <= oe for os, oe in outer)
               for s, e in inner)


def test_every_span_opens(traced):
    host = traced[0]
    assert {n for n, *_ in host} == set(spans.NAMES)
    assert all(n.startswith(spans.PREFIX) for n in spans.NAMES)
    assert len({t for *_, t in host}) == 1


@pytest.mark.parametrize("inner,outer", [
    (spans.CLASSIFY, spans.QUERY), (spans.REFINE, spans.QUERY),
    (spans.PREDICT, spans.QUERY), (spans.GATHER, spans.REFINE),
    (spans.APPLY, spans.REFINE), (spans.KERNEL, spans.REFINE),
    (spans.KERNEL_PACK, spans.KERNEL), (spans.KERNEL_DISPATCH, spans.KERNEL),
    (spans.KERNEL_WAIT, spans.KERNEL), (spans.READ, spans.GATHER)])
def test_spans_nest(traced, inner, outer):
    host = traced[0]
    inner_iv = _intervals(host, inner)
    if inner == spans.READ:    # the init pass reads outside any query
        meta = _intervals(host, spans.INIT_META)
        inner_iv = [iv for iv in inner_iv if not _inside([iv], meta)]
    assert inner_iv and _inside(inner_iv, _intervals(host, outer))


def test_apply_opens_no_kernel(traced):
    """A round's apply takes split children's metadata from the rows it
    orders on the host: no op call under ``aqp.apply``."""
    host = traced[0]
    applies = _intervals(host, spans.APPLY)
    assert applies and not any(_inside([iv], applies)
                               for iv in _intervals(host, spans.KERNEL))


def test_wait_follows_dispatch(traced):
    host = traced[0]
    dispatch = _intervals(host, spans.KERNEL_DISPATCH)
    for k0, k1 in _intervals(host, spans.KERNEL):
        waits = [w for w in _intervals(host, spans.KERNEL_WAIT)
                 if k0 <= w[0] < k1]
        inside = [d for d in dispatch if k0 <= d[0] < k1]
        # the heatmap fold runs the host mirror: no dispatch, no wait
        assert len(waits) == len(inside) <= 1
        assert all(d1 <= w0 for (_, d1), (w0, _) in zip(inside, waits))


def test_one_span_per_phase(traced):
    host, results, delta, _ = traced
    calls = Counter(n for n, *_ in host)
    views = len(results)
    assert calls[spans.KERNEL] == delta.kernel_calls > 0
    assert sum(r.kernel_calls for r in results) == delta.kernel_calls
    assert calls[spans.REFINE] == calls[spans.QUERY] == views
    assert calls[spans.CLASSIFY] == calls[spans.PREDICT] == views
    assert calls[spans.GATHER] == delta.batch_rounds
    # the fused heatmap fold runs the host mirror: no pack or dispatch
    assert (calls[spans.KERNEL_PACK] == calls[spans.KERNEL_DISPATCH]
            == calls[spans.KERNEL_WAIT] < calls[spans.KERNEL])
    assert all(calls[n] == 1 for n in (spans.INIT_BIN, spans.INIT_SORT,
                                       spans.INIT_PERMUTE, spans.INIT_META))


def test_h2d_bytes_match_shipped_shapes(traced):
    _, results, delta, shipped = traced
    assert shipped.calls and all(b == "jnp" for *_, b in shipped.calls)
    assert sum(r.h2d_bytes for r in results) == delta.h2d_bytes
    assert delta.h2d_bytes == shipped.h2d_bytes()
    # the object planes the formula counts are the ones padded
    for n, lens in shipped.planes:
        assert lens == [ops.object_bucket(n)] * ops.PLANES


def test_np_mirror_ships_nothing():
    ds = make_synthetic_dataset(n=30_000, seed=12)
    eng = AQPEngine(ds, _config("np"))
    results = [eng.query(w, agg, "a0", phi=0.05) for w, agg in _views(ds)]
    assert sum(r.kernel_calls for r in results) > 0
    assert all(r.h2d_bytes == 0 for r in results)
    totals = eng.trace.totals()
    assert totals["total_h2d_bytes"] == 0
    assert totals["total_kernel_calls"] == sum(r.kernel_calls
                                               for r in results)


@pytest.mark.parametrize("n,n_seg", [(1, 1), (1024, 2), (1025, 3),
                                     (70_000, 9), (131_072, 256)])
def test_padding_rule_written_once(n, n_seg):
    xs = np.zeros(n, np.float32)
    bounds = np.linspace(0, n, n_seg + 1).astype(np.int64)
    out = ops._segment_stream(xs, xs, xs, bounds)
    assert len(out[0]) == ops.object_bucket(n) >= max(n, 1024)
    pad, (table,) = ops._seg_bucket(n_seg, np.zeros((n_seg, 4), np.float32))
    assert pad == table.shape[0] == ops.segment_bucket(n_seg) >= n_seg
    assert ops.h2d_bytes("pallas", n, n_seg, 4) == 4 * (
        ops.PLANES * len(out[0]) + 4 * pad)
    assert ops.h2d_bytes("np", n, n_seg, 4) == 0
