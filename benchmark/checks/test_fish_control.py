"""The controls of ``correct`` for the fish reference, at the cell's
rehearsal size (CPU): the plain reference put in the program's place
and

- computed in the nearest precision below the configuration's float32
  (velocity operands of the advection and the bodies' tables rounded
  through bfloat16) has to come out NOT correct by the cell's own
  limits, and by ``mass_gap`` among them — the rasterisation is what a
  lower precision moves first;
- with its own three faults (the wave frozen, one body's velocity
  update skipped, the mass scaled by 1.0001) likewise;
- computed again in float32 has to pass every limit.

The chip readings at the cell's own size are in PERF.md (PR 28).
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import generator  # noqa: E402
from benchmark.references import fish_box as ref  # noqa: E402

CELL = "twofish-amr-l8.wake"


def _load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


def _records(rows):
    """Reference rows in the shape of the program's records."""
    finest = 10 ** 6          # a reference has no forest: never short
    return [dict(r, schema=13, blocks_per_level={"4": finest})
            for r in rows[1:]]


@pytest.mark.parametrize("control,broken", [
    ({}, ()),
    ({"cast": "bfloat16"}, ("mass_gap",)),
    ({"frozen": True}, ("energy_gap", "vel_gap")),
    ({"skip_body": 0}, ("vel_gap", "spin_gap")),
    ({"mass_scale": 1.0001}, ("mass_gap",)),
])
@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5, 4242424242])
def test_control_is_not_correct(seed, control, broken):
    import jax.numpy as jnp
    cell = _load("workloads", CELL)
    config = _load("configs", cell["config"])
    config = generator.merge(config, config["rehearsal"])
    # a reference in the program's place has no forest and is given its
    # clock: the clock's and the forest's numbers are not its to break
    limits = {k: v for k, v in cell["limits"].items()
              if k not in ("t_gap", "cover_gap") and not k.startswith("wake_")}
    # the start-up stretch alone: a reference in the program's place is
    # given a clock, and a constant one is stable only that far
    dts = [0.05] * int(cell["startup_steps"])
    ours = ref.follow(config, seed, dts)
    kw = dict(control)
    if "cast" in kw:
        kw["cast"] = getattr(jnp, kw["cast"])
    got = ref.gaps(config, _records(ref.follow(config, seed, dts, **kw)),
                   ours)
    over = {k for k in limits if got[k] > limits[k]}
    assert set(broken) <= over, (over, {k: got[k] for k in limits})
    assert bool(over) is bool(broken), {k: got[k] for k in over}
    for k in broken:
        assert got[k] >= 2.0 * limits[k], (k, got[k])
