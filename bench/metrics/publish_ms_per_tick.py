"""The epoch publication of a serving tick (``EpochStage.publish``: the
staged enrichments and splits applied), on the program's clock: the
mean over the window's ticks (the answers' ``serving.publish_s``)."""
from bench.metrics.tickets_per_tick import ticks


def read(run):
    t = ticks(run)
    return 1e3 * sum(c.publish_s for c in t) / len(t) if t else None
