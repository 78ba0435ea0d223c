"""Bytes of host arrays handed to device kernels per view: the answers'
``h2d_bytes`` (each query's delta of the program's
``AdaptStats.h2d_bytes``: bucket-padded object planes and per-segment
tables), summed over the window's views, over their count. A program
whose answers carry no such counter has nothing to read."""


def read(run):
    shipped = [getattr(r, "h2d_bytes", None) for r in run.results
               if r is not None]
    if not run.views or not shipped or None in shipped:
        return None
    return sum(shipped) / len(run.views)
