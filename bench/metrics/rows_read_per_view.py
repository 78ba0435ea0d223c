"""Raw-file rows read in the window (``IOStats.rows_read``) per view."""


def read(run):
    return run.rows_read / len(run.views) if run.views else None
