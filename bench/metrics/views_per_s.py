"""Views answered inside the window, over the window's seconds."""


def read(run):
    return run.answered_in_window() / run.seconds
