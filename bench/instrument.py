"""What the benchmark observes around the program, from its own side.

- ``OpTap`` wraps the public ops of ``repro.kernels.ops`` for one run: a
  host span ``ops.<name>`` around every call, and, while recording, the
  call's shape (``opcost.call_shape``) and resolved backend.
- ``traced_dataset`` makes the program's ``RawDataset`` with a host span
  ``read_values`` around every accounted read.
- ``CompileCounter`` counts XLA compilations and persistent-cache loads
  (JAX monitoring events).
- ``span`` is a host span that the profiler records when a trace runs.

The spans are ``jax.profiler.TraceAnnotation``s: with no trace running
each costs about a microsecond.
"""
from __future__ import annotations

import contextlib
import functools

import jax

from . import opcost

span = jax.profiler.TraceAnnotation


class OpTap:
    def __init__(self, ops_module):
        self.ops = ops_module
        self.recording = False
        self.calls = []          # (op, shape, backend) while recording

    def _wrap(self, name, fn):
        tag = f"ops.{name}"

        @functools.wraps(fn)
        def call(*args, **kwargs):
            if self.recording:
                backend = kwargs.get("backend") or self.ops.default_backend()
                self.calls.append((name, opcost.call_shape(name, args, kwargs),
                                   backend))
            with span(tag):
                return fn(*args, **kwargs)
        return call

    @contextlib.contextmanager
    def installed(self):
        saved = {n: getattr(self.ops, n) for n in opcost.KERNEL}
        try:
            for n, fn in saved.items():
                setattr(self.ops, n, self._wrap(n, fn))
            yield self
        finally:
            for n, fn in saved.items():
                setattr(self.ops, n, fn)


def traced_dataset(x, y, columns, storage):
    """The program's ``RawDataset`` in the configuration's ``storage``,
    with a host span around each accounted read."""
    from repro.data.rawfile import RawDataset

    class TracedRawDataset(RawDataset):
        def read_values(self, attr, rows):
            with span("read_values"):
                return super().read_values(attr, rows)

    return TracedRawDataset(x, y, columns, storage=storage)


class CompileCounter:
    """Compile requests (each XLA backend compile or persistent-cache
    load) and persistent-cache hits seen by this process."""

    def __init__(self):
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits
