"""Tickets a serving tick answered: the mean over the window's ticks,
each counted once (the answers' ``serving.tick_tickets``, by
``serving.tick``). A program whose answers carry no tick counters has
nothing to read."""


def ticks(run):
    """The window's ticks' counters, one per tick, or None."""
    counters = [getattr(r, "serving", None) for r in run.results
                if r is not None]
    if not counters or None in counters:
        return None
    return list({c.tick: c for c in counters}.values())


def read(run):
    t = ticks(run)
    return sum(c.tick_tickets for c in t) / len(t) if t else None
