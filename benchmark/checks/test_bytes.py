"""The compulsory-bytes function behind ``step_hbm_pct`` depends on the
grid's shape and the iteration count only (derivation beside it, in
``benchmark/bytes_model.py``). Run: ``python3 -m pytest benchmark/checks``.
"""

import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import bytes_model  # noqa: E402


def test_arguments_are_shape_and_iterations_only():
    assert list(inspect.signature(bytes_model.step_bytes).parameters) == \
        ["ny", "nx", "iters", "itemsize"]
    src = inspect.getsource(bytes_model)
    for word in ("import os", "environ", "cup2d_tpu", "jax"):
        assert word not in src.split('"""', 2)[2], word


def test_value_and_linearity():
    b = bytes_model.step_bytes
    assert b(64, 64, 0) == 4 * 64 * 64 * 23
    assert b(64, 64, 2) == 4 * 64 * 64 * (23 + 32)
    assert b(128, 64, 1.5) == 2 * b(64, 64, 1.5)          # linear in cells
    assert b(64, 64, 3) - b(64, 64, 2) == b(64, 64, 1) - b(64, 64, 0)
    assert b(64, 64, 1, itemsize=2) == b(64, 64, 1) / 2


def test_same_number_whatever_tier_ran():
    """Two program runs of one shape and iteration count, one on each
    advection tier, are read against the same bytes: nothing of the
    run but its shape and its measured iterations reaches the
    function (the reader passes exactly those)."""
    from benchmark.readers import step_hbm_pct
    src = inspect.getsource(step_hbm_pct.read)
    assert 'step_bytes(\n        g["ny"], g["nx"], sum(iters) / len(iters))' in src
