"""The control (the reference one precision below float32, claiming
exact answers) fails a number of each cell's check, at a size a test
run holds; the same views answered by the program pass."""
import pytest

from bench import correctness
from bench.control import readings

WORKLOADS = ["paper_1e8.explore"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_is_refused(workload):
    r = readings(workload, 4_000_000_123, 2.0, rows=200_000)
    verdict = correctness.judge(r, correctness.limits(workload))
    assert not verdict["correct"]
    assert r["outside"] > correctness.limits(workload)["outside"]


def test_heatmap_answers_are_judged_bin_by_bin():
    """Mixes with heatmap views are judged over every occupied bin."""
    import types

    import numpy as np

    from bench.reference import Reference
    from bench.traffic import View

    x = np.array([0.5, 1.5, 2.5, 3.5], np.float32)
    y = np.array([0.5, 0.5, 1.5, 1.5], np.float32)
    ref = Reference(x, y, {"a": np.array([1, 2, 3, 4], np.float32)}, grid=4)
    view = View(due_s=0.0, session=0, kind="heatmap",
                window=(0.0, 0.0, 4.0, 2.0), agg="sum", attr="a", phi=0.1,
                bins=(2, 2))
    truth = np.array([3.0, 0.0, 0.0, 7.0])       # bins 1 and 2 are empty
    sound = types.SimpleNamespace(values=truth, lo=truth - 0.1,
                                  hi=truth + 0.1)
    r = correctness.compare([view], [sound], ref)
    assert r["outside"] == 0.0 and r["err_over_phi"] == 0.0
    off = truth + np.array([0.0, 0.0, 0.0, 1.0])  # the last bin misses
    wrong = types.SimpleNamespace(values=off, lo=off, hi=off)
    r = correctness.compare([view], [wrong], ref)
    assert r["outside"] == pytest.approx(1.0 / 7.0)
    assert r["err_over_phi"] == pytest.approx(1.0 / (0.1 * 8.0))
