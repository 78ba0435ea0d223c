"""How a configuration's engine is driven: one module per ``"engine"``
kind of a configuration file, each with ``setup`` (the engine to
measure, and a warm-up on a copy of it) and ``measure`` (the timed
part)."""
from __future__ import annotations

import sys
import time
import traceback

from ..instrument import span


def index_config(cfg: dict):
    from repro.core import IndexConfig

    return IndexConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in cfg["index"].items()})


def wait_until(t: float) -> float:
    """Sleep, then spin, until ``perf_counter() >= t``; returns how late
    the wake-up came (the generator's lateness)."""
    with span("wait_arrival"):
        left = t - time.perf_counter()
        if left > 2e-3:
            time.sleep(left - 1e-3)
        while time.perf_counter() < t:
            pass
    return time.perf_counter() - t


def ask(session_or_engine, view):
    """Send ``view`` (an ``AQPEngine`` answers, a ``Session`` queues)."""
    if view.kind == "heatmap":
        return session_or_engine.heatmap(view.window, view.agg, view.attr,
                                         bins=view.bins, phi=view.phi)
    return session_or_engine.query(view.window, view.agg, view.attr,
                                   phi=view.phi)


def attempt(fn, *args):
    """``fn(*args)``, or None with the traceback on standard error: a
    failing view is counted by the check, and the run goes on."""
    try:
        return fn(*args)
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return None
