"""One reader per metric: ``<name>.py`` defines ``read(run)``, the
metric's value for a finished ``harness.Run``, or None where the run
has nothing to read for it."""
