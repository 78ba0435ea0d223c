"""How long a view waited in the serving queue: from joining it to the
start of the tick that answered it (the answers' ``serving.queue_s``,
the program's clock), the mean over the window's answers. A program
whose answers carry no tick counters has nothing to read."""


def read(run):
    waits = [getattr(r, "serving", None) for r in run.results
             if r is not None]
    if not waits or None in waits:
        return None
    return 1e3 * sum(w.queue_s for w in waits) / len(waits)
