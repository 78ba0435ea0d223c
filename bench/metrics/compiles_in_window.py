"""XLA compilations and persistent-cache loads inside the window (JAX
monitoring): the warm-up should leave none."""


def read(run):
    return float(run.compiles)
