"""The line behind ``poisson_iter_ms`` and ``step_base_ms``: it finds a
known base and slope whatever iteration counts the window holds, and
reads nothing where it holds one count only."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.readers import step_fit  # noqa: E402


def _ctx(counts, term, base=63.0, per_iter=37.5):
    steps = list(range(16, 16 + len(counts)))
    return {"metric": {"term": term},
            "window": {"steps": steps,
                       "step_ms": [base + per_iter * c for c in counts]},
            "records": [{"step": s, "poisson_iters": c}
                        for s, c in zip(steps, counts)]}


@pytest.mark.parametrize("counts", [[0, 1, 2, 3] * 5, [4, 6, 6, 9, 4],
                                    [1] * 20 + [2]])
def test_line_is_found(counts):
    assert step_fit.read(_ctx(counts, "per_iter")) == pytest.approx(37.5)
    assert step_fit.read(_ctx(counts, "base")) == pytest.approx(63.0)


def test_one_count_reads_nothing():
    assert step_fit.read(_ctx([2] * 30, "per_iter")) is None


def test_a_lone_stalled_step_does_not_bend_the_line():
    ctx = _ctx([0, 1, 2, 3] * 5 + [9], "per_iter")
    ctx["window"]["step_ms"][-1] += 2000.0
    assert step_fit.read(ctx) == pytest.approx(37.5)
