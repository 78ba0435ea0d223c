"""The readers of the program's kernel counters: per view of the window,
and nothing to read from a program whose answers lack the counters."""
from types import SimpleNamespace

import pytest

from bench.metrics import h2d_bytes_per_view, kernel_calls_per_view

READERS = [(kernel_calls_per_view, "kernel_calls"),
           (h2d_bytes_per_view, "h2d_bytes")]


def _run(results, views):
    return SimpleNamespace(results=results, views=[object()] * views)


@pytest.mark.parametrize("reader,field", READERS)
def test_per_view(reader, field):
    rs = [SimpleNamespace(**{field: v}) for v in (3, 5, 0, 4)]
    assert reader.read(_run(rs, 4)) == pytest.approx(3.0)


@pytest.mark.parametrize("reader,field", READERS)
def test_nothing_to_read(reader, field):
    assert reader.read(_run([SimpleNamespace(batch_rounds=2)], 1)) is None
    assert reader.read(_run([None], 1)) is None
    assert reader.read(_run([], 0)) is None


@pytest.mark.parametrize("reader,field", READERS)
def test_reads_the_program(reader, field):
    from repro.core import AQPEngine, IndexConfig
    from repro.data import make_synthetic_dataset

    ds = make_synthetic_dataset(n=20_000, seed=3)
    eng = AQPEngine(ds, IndexConfig(grid0=(8, 8), min_split_count=64,
                                    init_metadata_attrs=("a0",),
                                    backend="jnp"))
    rs = [eng.query((100, 100, 400 + 50 * i, 400), "sum", "a0", phi=0.05)
          for i in range(3)]
    total = eng.trace.totals()[f"total_{field}"]
    assert total > 0
    assert reader.read(_run(rs, 3)) == pytest.approx(total / 3)
