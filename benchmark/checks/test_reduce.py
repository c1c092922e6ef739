"""The reduction from a profiler trace to metrics, held to a small
recorded trace: ``benchmark/fixtures/tiny-cavity-64.xplane.pb.gz`` is
three traced steps of the cavity at 64^2 on one TPU v5 lite (PR 23,
chip call 1). The known numbers below were read off that trace by hand
(``python3 benchmark/reduce.py <file>`` lists what a trace holds) and
with the reduction; a change to the reduction that moves them is a
change of the yardstick. No TensorFlow import anywhere.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import reduce  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "tiny-cavity-64.xplane.pb.gz")


@pytest.fixture(scope="module")
def trace():
    return reduce.reduce_trace(FIXTURE, ["jit_step"])


def test_window_busy_and_step(trace):
    assert trace["planes"] == ["/device:TPU:0"]
    assert trace["steps"] == 2          # three runs of jit_step
    assert trace["window_s"] == pytest.approx(0.009378882, rel=1e-9)
    assert trace["busy_s"] == pytest.approx(0.000173389, rel=1e-9)
    assert 100 * trace["busy_s"] / trace["window_s"] == \
        pytest.approx(1.84872, rel=1e-5)
    assert 1e3 * trace["device_step_s"] == pytest.approx(0.095071, rel=1e-6)


def test_top_operations_are_self_times(trace):
    names = [n for n, _ in trace["device_ops"]]
    assert names[0] == "while.396"
    assert set(names[1:3]) == {"fusion.439", "fusion.443"}
    assert trace["device_ops"][0][1] == pytest.approx(2.3617e-05, rel=1e-6)
    assert all(len(n) <= 80 for n in names) and len(names) <= 10
    # self times never add up to more than the busy time
    assert sum(s for _, s in trace["device_ops"]) <= trace["busy_s"]


def test_idle_goes_to_host_events(trace):
    gaps = trace["idle_gaps"]
    assert gaps[0][0] == "$array.py:621 copy_to_host_async"
    assert sum(s for _, s in gaps) == pytest.approx(
        trace["window_s"] - trace["busy_s"], rel=1e-6)


def test_no_step_executable_reads_nothing():
    assert reduce.reduce_trace(FIXTURE, ["jit_no_such_module"]) is None


def test_no_tensorflow():
    assert "tensorflow" not in sys.modules
    with open(reduce.__file__) as f:
        assert "tensorflow" not in f.read().split('"""', 2)[2]
