"""Split requests a serving tick's publication masked because an earlier
query of the same tick split the tile first: the mean over the window's
ticks (the answers' ``serving.splits_masked``)."""
from bench.metrics.tickets_per_tick import ticks


def read(run):
    t = ticks(run)
    return sum(c.splits_masked for c in t) / len(t) if t else None
