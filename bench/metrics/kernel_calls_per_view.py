"""Data-plane op calls per view: the answers' ``kernel_calls`` (each
query's delta of the program's ``AdaptStats.kernel_calls``), summed over
the window's views, over their count. A program whose answers carry no
such counter has nothing to read."""


def read(run):
    calls = [getattr(r, "kernel_calls", None) for r in run.results
             if r is not None]
    if not run.views or not calls or None in calls:
        return None
    return sum(calls) / len(run.views)
