"""Traffic mixes: a data file ``<mix>.json`` per mix, read by the
generator module that its ``"generator"`` key names. A mix draws its
views from its own seed, so every run of a cell sends the same views
and ``--seed`` changes only the file they are sent to."""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path
from typing import List, Optional, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class View:
    """One viewport request of a client."""
    due_s: float                 # when it is due, seconds after the window opens
    session: int
    kind: str                    # "query" (scalar) or "heatmap"
    window: Tuple[float, float, float, float]
    agg: str
    attr: str
    phi: float
    bins: Optional[Tuple[int, int]] = None


@dataclasses.dataclass
class Schedule:
    """What a run sends: an optional first view, answered on the
    unindexed file before the window opens, then the window's views in
    due order."""
    views: List[View]
    first: Optional[View] = None
    params: dict = dataclasses.field(default_factory=dict)


def load(mix: str) -> dict:
    return json.loads((HERE / f"{mix}.json").read_text())


def schedule(mix: str, data, seconds: float,
             params: Optional[dict] = None) -> Schedule:
    """The schedule of ``mix`` over ``data`` for a window of ``seconds``.
    ``params`` overrides the mix file's keys (the tests' small windows)."""
    spec = load(mix)
    if params:
        spec = {**spec, **params}
    gen = importlib.import_module(f"bench.traffic.{spec['generator']}")
    return gen.schedule(spec, data, float(seconds))


def f32(v: float) -> float:
    """A window coordinate as the float32 value the program compares
    against, so that every reader of the window agrees on it."""
    return float(np.float32(v))
