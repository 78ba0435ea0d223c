"""Many analysts on one ``ServingEngine``. Set-up builds the engine over
the unindexed file (the init pass, timed), wraps it in a
``ServingEngine`` with its defaults (batched ticks, no crack budget, no
prefetch) and opens one ``Session`` per analyst of the mix. The timed
part is the server loop: submit every view that is due to its own
session, tick if anything is queued, stamp each of the tick's views
done when the tick returns (the client then has its answer), repeat.

Which views share a tick depends on how long ticks take, so the shapes
of the fused passes do too. The warm-up therefore runs the same loop on
a copy of the fresh engine with the idle waits skipped, recording the
most rows one call of each device op carried; then it compiles each op
at every segment count and every object bucket up to the one above
that.
"""
from __future__ import annotations

import contextlib
import copy
import time

import numpy as np

from . import ask, attempt, index_config, wait_until
from ..instrument import span


class _Clock:
    """Seconds since ``start``. ``wait`` waits for the due time and
    records how late it woke (``run`` given), or jumps to it."""

    def __init__(self, start: float, run=None):
        self.start, self.run = start, run

    def now(self) -> float:
        return time.perf_counter() - self.start

    def wait(self, t: float) -> None:
        if self.run is None:
            self.start = time.perf_counter() - t
        else:
            self.run.lateness.append(wait_until(self.start + t))


def _open(eng, sessions: int):
    from repro.core import ServingEngine

    server = ServingEngine(eng)
    return server, [server.open_session() for _ in range(sessions)]


def serve(server, sessions, views, clock, results, done):
    """The server loop over ``views`` (in due order); fills ``results``
    and ``done`` (perf_counter when the answer came back)."""
    i, n = 0, len(views)
    while i < n:
        if views[i].due_s > clock.now():
            clock.wait(views[i].due_s)
        queued = []
        with span("view"):
            while i < n and views[i].due_s <= clock.now():
                queued.append((i, attempt(ask, sessions[views[i].session],
                                          views[i])))
                i += 1
            ok = server.n_queued == 0 or attempt(server.tick) is not None
        t = time.perf_counter()
        for j, ticket in queued:
            results[j] = ticket.result if ok and ticket is not None else None
            done[j] = t


# the ops a batched tick runs on the device: the fused scalar pass and
# the split metadata of a published round
OPS = ("segment_window_agg_multi", "segment_bin_agg")


@contextlib.contextmanager
def _largest_calls():
    """Yields ``{op: most rows one call carried}`` over the block."""
    from repro.kernels import ops

    most = dict.fromkeys(OPS, 0)
    saved = {name: getattr(ops, name) for name in OPS}

    def wrap(name, fn):
        def call(*args, **kwargs):
            most[name] = max(most[name], int(np.asarray(args[3])[-1]))
            return fn(*args, **kwargs)
        return call
    try:
        for name, fn in saved.items():
            setattr(ops, name, wrap(name, fn))
        yield most
    finally:
        for name, fn in saved.items():
            setattr(ops, name, fn)


def _warm_ops(index, most) -> None:
    """Compile the tick's device ops at every segment count (up to
    ``MAX_SEGMENTS`` for the fused pass, ``batch_k`` for a round's
    splits) and every object bucket up to the one above ``most[op]``."""
    from repro.kernels import ops
    from repro.kernels.segment_agg import MAX_SEGMENTS

    backend = index._backend
    if backend == "np":
        return
    gx, gy = index.cfg.split_grid
    box = np.array([0.0, 0.0, 1.0, 1.0])
    for name, max_seg in zip(OPS, (MAX_SEGMENTS, index.cfg.batch_k)):
        op = getattr(ops, name)
        kw = {} if name == OPS[0] else {"gx": gx, "gy": gy}
        b = ops.object_bucket(1)
        while b <= 2 * ops.object_bucket(most[name]):
            xs = np.linspace(0.0, 1.0, b, dtype=np.float32)
            # every count at the smallest bucket, powers of two above it
            segs = (range(1, max_seg + 1) if b == ops.object_bucket(1)
                    else [1 << k for k in range((max_seg - 1).bit_length()
                                                + 1)])
            for s in segs:
                bounds = np.linspace(0, b, s + 1).astype(np.int64)
                op(xs, xs, xs, bounds, np.tile(box, (s, 1)),
                   backend=backend, **kw)
            b *= 2


def setup(ds, cfg, sch, run):
    """The engine to measure, built from the unindexed file (its init
    pass timed), and the warm-up on a copy of it."""
    from repro.core import AQPEngine

    t0 = time.perf_counter()
    with span("init_pass"):
        eng = AQPEngine(ds, index_config(cfg))
    run.init_s = time.perf_counter() - t0
    twin = copy.deepcopy(eng, memo={id(ds): ds})
    server, sessions = _open(twin, sch.params["sessions"])
    n = len(sch.views)
    with _largest_calls() as most:
        serve(server, sessions, sch.views, _Clock(time.perf_counter()),
              [None] * n, [0.0] * n)
    _warm_ops(twin.index, most)
    return eng


def measure(ds, cfg, sch, run, window, eng):
    server, sessions = _open(eng, sch.params["sessions"])
    with window(run) as tw:
        serve(server, sessions, sch.views, _Clock(tw, run), run.results,
              run.done)
