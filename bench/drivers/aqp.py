"""One analyst on ``AQPEngine``. Set-up builds the engine over the
unindexed file (the init pass, timed), then compiles every program the
run will use on a copy of that fresh engine, which answers the same
views: the engine is deterministic, so the timed engine meets exactly
these shapes and its index stays as built. The timed part answers the
first view, then the window's views in due order, each as soon as it is
due and the previous one is answered."""
from __future__ import annotations

import copy
import time

from . import ask, attempt, index_config, wait_until
from ..instrument import span


def setup(ds, cfg, sch, run):
    """The engine to measure, built from the unindexed file; its init
    pass runs on the host alone (no device program), so it is timed here
    as the first part of ``first_answer_s``."""
    from repro.core import AQPEngine

    t0 = time.perf_counter()
    with span("init_pass"):
        eng = AQPEngine(ds, index_config(cfg))
    run.init_s = time.perf_counter() - t0
    twin = copy.deepcopy(eng, memo={id(ds): ds})
    for v in [sch.first] + sch.views:
        attempt(ask, twin, v)
    return eng


def measure(ds, cfg, sch, run, window, eng):
    t0 = time.perf_counter()
    with span("view"):
        run.first_result = attempt(ask, eng, sch.first)
    run.first_answer_s = run.init_s + time.perf_counter() - t0
    with window(run) as tw:
        for i, v in enumerate(sch.views):
            due = tw + v.due_s
            if time.perf_counter() < due:
                run.lateness.append(wait_until(due))
            with span("view"):
                run.results[i] = attempt(ask, eng, v)
            run.done[i] = time.perf_counter()
