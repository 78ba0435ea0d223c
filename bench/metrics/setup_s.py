"""Process start to the start of the timed part: data generation, the
driver's engine and warm-up and, on a checkout's first run, compilation
(host clock)."""


def read(run):
    return run.setup_s
