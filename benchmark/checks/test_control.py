"""The control of ``correct`` for the uniform-box reference, at a size
a test run can hold (64^2, CPU): the plain reference put in the
program's place and computed in the nearest precision below the
configuration's float32 — its advection operands rounded through
bfloat16 — has to come out NOT correct against the cell's own limits,
and the float32 reference in the program's place has to pass them.
The chip readings at the cell's own size are in PERF.md.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import generator, seeded  # noqa: E402
from benchmark.references import uniform_walls as ref  # noqa: E402


def _load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


def _rows(config, n, seed, cast):
    g, ph = config["grid"], config["physics"]
    h = g["extent"] / max(g["ny"], g["nx"])
    return ref.follow(seeded.start_velocity(config, seed), n, h=h,
                      nu=ph["nu"], cfl=ph["cfl"],
                      walls=[tuple(w) for w in config["walls"]], cast=cast)


@pytest.mark.parametrize("cell_name", ["cavity-re10k-8192.solo"])
@pytest.mark.parametrize("seed", [11, 2**31 + 5, 4242424242])
def test_bf16_control_is_not_correct(cell_name, seed):
    import jax.numpy as jnp
    cell = _load("workloads", cell_name)
    config = _load("configs", cell["config"])
    config = generator.merge(config, config["rehearsal"])
    n = cell["reference_steps"]
    ours = _rows(config, n, seed, None)
    control = ref.gaps(_rows(config, n, seed, jnp.bfloat16), ours)
    same = ref.gaps(_rows(config, n, seed, None), ours)
    limits = cell["limits"]
    assert limits, "the cell compares nothing"
    assert any(control[k] > limits[k] for k in limits), control
    assert all(same[k] <= limits[k] for k in limits), same
