"""The plain reference of the fish cell (benchmark/references/fish_box.py)
against the program, at sizes a test run holds (ISSUE 28):

- the forest run through the benchmark's own entry at the cell's
  rehearsal size (levelMax 5, one fish of L = 0.4) reads ``correct``
  over the start-up steps on three seeds;
- the reference's mass, centre and inertia of one rasterised fish
  against the program's uniform driver at three lengths;
- telemetry schema 13: ``bodies`` and ``pad_blocks`` in a forest
  record, null where there is no body / no forest; schema 14 (ISSUE
  29): ``force_blocks`` and ``force_cap`` likewise;
- the schema rule of ``compare``: records older than schema 13 leave the
  body numbers OUT, records of schema 13 without ``bodies`` read None.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "twofish-amr-l8.wake"
SEEDS = (11, 2 ** 31 + 5, 4242424242)
_RUNS: dict = {}
_FILES: dict = {}       # what a run left beside its records, as text


def _load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


def _files():
    from benchmark import generator
    cell = _load("workloads", CELL)
    config = _load("configs", cell["config"])
    return (generator.merge(cell, cell["rehearsal"]),
            generator.merge(config, config["rehearsal"]))


def _run(seed, capsys):
    """One rehearsal run of the cell through benchmark/run.py (cached a
    seed): its result line and the records it left."""
    if seed not in _RUNS:
        from benchmark import run
        rc = run.main(["--workload", CELL, "--seed", str(seed),
                       "--seconds", "8", "--trace", "0", "--rehearsal"])
        assert rc == 0
        lines = [ln for ln in capsys.readouterr().out.splitlines()
                 if ln.strip()]
        with open(os.path.join(ROOT, "benchmark_out", CELL,
                               "metrics.jsonl")) as f:
            rows = [json.loads(ln) for ln in f if ln.strip()]
        _RUNS[seed] = (json.loads(lines[-1]),
                       [r for r in rows if r.get("event") == "metrics"])
        _FILES[seed] = {name: _text(name)
                        for name in ("events.jsonl", "forces.csv")}
    return _RUNS[seed]


def _text(name):
    with open(os.path.join(ROOT, "benchmark_out", CELL, name)) as f:
        return f.read()


@pytest.mark.parametrize("seed", SEEDS)
def test_forest_start_up_against_the_reference(seed, capsys):
    res, _ = _run(seed, capsys)
    cell, _ = _files()
    assert set(res["compared"]) == set(cell["limits"])
    assert res["correct"] is True and res["failed"] == 0, res["compared"]
    for name, c in res["compared"].items():
        # with room: the rehearsal reads at most half of each start-up
        # limit; the wake's limits are the chip size's, where the fish
        # is 102 cells long and not 51, and hold here with less room
        room = 1.0 if name.startswith("wake_") else 0.5
        assert c["value"] <= room * c["limit"], (name, c)


def test_forest_record_carries_bodies_and_pad_blocks(capsys):
    from cup2d_tpu.profiling import METRICS_SCHEMA_VERSION
    _, records = _run(SEEDS[0], capsys)
    assert METRICS_SCHEMA_VERSION >= 14     # force_blocks came with 14
    gets = {r["device_gets"] for r in records[12:]}
    assert gets == {2}, gets        # the step's pull + the guard's: as before
    for r in records:
        assert r["schema"] == METRICS_SCHEMA_VERSION
        assert r["pad_blocks"] >= r["n_blocks"] > 0
        assert r["pad_blocks"] & (r["pad_blocks"] - 1) == 0     # a bucket
        (b,) = r["bodies"]
        assert set(b) == {"com", "angle", "u", "v", "omega", "mass",
                          "inertia"}
        assert all(isinstance(v, float) for v in
                   [*b["com"], *(b[k] for k in b if k != "com")])
        assert b["mass"] > 0 and b["inertia"] > 0
    # the body moves: the path of its centre is in the records
    first, last = records[0]["bodies"][0], records[-1]["bodies"][0]
    assert np.hypot(*np.subtract(first["com"], last["com"])) > 1e-5


def test_forest_record_carries_the_force_lists(capsys):
    """Schema 14: the rows the surface-force pass ran over and the
    capacity they are padded to, per body, in every forest record; the
    capacity is a bucket sized before the first step and never grown."""
    _, records = _run(SEEDS[0], capsys)
    caps = {tuple(r["force_cap"]) for r in records}
    assert len(caps) == 1, caps             # sticky: one capacity a run
    for r in records:
        (n,), (cap,) = r["force_blocks"], r["force_cap"]
        assert 0 < n <= cap and cap & (cap - 1) == 0
        assert n < r["n_blocks"]            # a list, not the forest
    left = _FILES[SEEDS[0]]
    assert "force_cap_grow" not in left["events.jsonl"]
    # the diagnostics keep their cadence: one row a body a step
    header, *rows = [ln.split(",")
                     for ln in left["forces.csv"].splitlines() if ln]
    assert header[:3] == ["time", "shape", "perimeter"]
    assert len(rows) == len(records) and {r[1] for r in rows} == {"0"}
    assert all(float(r[2]) > 0.0 for r in rows)     # a perimeter, each


def test_uniform_records_null_where_nothing_applies():
    from cup2d_tpu.config import SimConfig
    from cup2d_tpu.models import FishShape
    from cup2d_tpu.profiling import MetricsRecorder
    from cup2d_tpu.sim import Simulation

    cfg = SimConfig(bpdx=2, bpdy=1, level_max=3, level_start=2, extent=2.0,
                    nu=4e-5, lam=1e6, dtype="float32")
    empty = Simulation(cfg, shapes=[], level=2)
    rec = MetricsRecorder()
    rec.prime(empty)
    r = rec.record(empty, empty.step_once())
    assert r["bodies"] is None and r["pad_blocks"] is None
    assert r["force_blocks"] is None and r["force_cap"] is None
    fish = Simulation(cfg, level=3, shapes=[
        FishShape(0.4, 1.0, 0.5, 0.0, cfg.min_h)])
    r = MetricsRecorder().record(fish, fish.step_once())
    assert r["pad_blocks"] is None          # no forest
    assert r["force_blocks"] is None and r["force_cap"] is None
    (b,) = r["bodies"]
    assert b["mass"] == pytest.approx(fish.shapes[0].M)
    assert b["com"] == [float(c) for c in fish.shapes[0].com]


def test_schema_rule_of_compare(capsys):
    from benchmark.references import fish_box
    _, records = _run(SEEDS[0], capsys)
    cell, config = _files()
    new = fish_box.compare(config, cell, SEEDS[0], records, {})
    assert set(new) == set(cell["limits"])
    body = set(fish_box.BODY) & set(new)
    assert {"mass_gap", "vel_gap", "spin_gap"} <= body
    # a program from before schema 13: judged on the flow numbers
    old = [{k: v for k, v in r.items() if k not in ("bodies", "pad_blocks")}
           | {"schema": 12} for r in records]
    got = fish_box.compare(config, cell, SEEDS[0], old, {})
    assert set(got) == set(cell["limits"]) - body
    assert got and all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in got.values())
    assert got == {k: new[k] for k in got}
    # schema 13 that dropped the telemetry: never correct
    bare = [dict(r, bodies=None) for r in records]
    got = fish_box.compare(config, cell, SEEDS[0], bare, {})
    assert set(got) == set(cell["limits"])
    assert all(got[k]["value"] is None for k in body)
    assert all(got[k] == new[k] for k in set(got) - body)
    # a step without a record: nothing is correct
    got = fish_box.compare(config, cell, SEEDS[0], records[1:], {})
    assert all(c["value"] is None for c in got.values())
    capsys.readouterr()


def test_cell_is_in_the_manifest():
    """``BENCHMARK.json`` names the configuration, the cell and every
    per-layer metric the cell reports, each entry as the cell's own
    files give it, and ``benchmark/checks/test_manifest.py`` holds the
    whole of it."""
    from benchmark.checks import test_manifest as held_to
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = _load("workloads", CELL)
    config = _load("configs", cell["config"])
    def entry(kind, name):
        return next(e for e in manifest[kind] if e["name"] == name)

    assert entry("configs", config["name"]) == {
        "name": config["name"], "source": config["source"],
        "file": f"benchmark/configs/{config['name']}.json",
        "reduced": [], "why": config["why"]}
    assert entry("workloads", CELL) == {
        "name": CELL, "config": config["name"], "traffic": "wake",
        "chips": 1, "why": cell["why"]}
    named = {m["name"]: m
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    for name in cell["metrics"]:
        assert CELL in named[name].get("workloads", [CELL]), name
        if named[name].get("workloads") == [CELL]:
            m = _load("metrics", name)
            assert named[name] == {
                k: m[k] for k in ("name", "unit", "better", "source",
                                  "layer", "moves")} | {"workloads": [CELL]}
    held_to.test_configs_match_their_files(manifest)
    held_to.test_cells_match_their_files(manifest)
    held_to.test_metrics_match_their_files(manifest)


@pytest.mark.parametrize("late,broken", [
    ("energy", {"wake_energy_gap"}),
    ("bodies", {"wake_vel_gap"}),
    ("clock", {"wake_energy_gap", "wake_vel_gap"}),
])
def test_window_steps_are_compared(late, broken, capsys):
    """Records that go wrong only PAST the warm-up — the steps the
    window times — are seen by the ``wake_`` numbers and by nothing of
    the start-up stretch: the energy half as large again, the bodies'
    velocities quartered, and a clock (dt x 40) that blows the reference
    up, which has to read None and not NaN."""
    from benchmark.references import fish_box
    _, records = _run(SEEDS[0], capsys)
    cell, config = _files()
    w = int(cell["warmup_steps"])
    assert int(cell["startup_steps"]) < w < int(cell["reference_steps"])
    sound = fish_box.compare(config, cell, SEEDS[0], records, {})

    def wrong(r):
        if r["step"] <= w:
            return r
        if late == "energy":
            return dict(r, energy=1.5 * r["energy"])
        if late == "clock":
            return dict(r, dt=40.0 * r["dt"])
        return dict(r, bodies=[dict(b, u=0.25 * b["u"], v=0.25 * b["v"],
                                    omega=0.25 * b["omega"])
                               for b in r["bodies"]])
    got = fish_box.compare(config, cell, SEEDS[0],
                           [wrong(r) for r in records], {})
    capsys.readouterr()
    over = {k for k, c in got.items()
            if c["value"] is None or c["value"] > c["limit"]}
    assert over == broken, got
    assert all(got[k] == sound[k] for k in set(got) - broken)
    if late == "clock":
        assert all(got[k]["value"] is None for k in broken)
        json.dumps(got, allow_nan=False)
    else:
        assert all(got[k]["value"] >= 1.2 * got[k]["limit"] for k in broken)


@pytest.mark.parametrize("length", [0.2, 0.3, 0.4])
def test_reference_body_integrals_against_the_program(length):
    """Mass, centre and inertia of one rasterised fish, at rest at
    t = 0 on a 256 x 128 uniform grid: the reference's against the
    program's uniform driver."""
    from benchmark.references import fish_box
    from cup2d_tpu.config import SimConfig
    from cup2d_tpu.models import FishShape
    from cup2d_tpu.sim import Simulation

    x, y, angle = 0.9731, 0.5179, 20.0
    cfg = SimConfig(bpdx=2, bpdy=1, level_max=5, level_start=3, extent=2.0,
                    nu=4e-5, lam=1e7, dtype="float32")
    sim = Simulation(cfg, level=4, shapes=[
        FishShape(length, x, y, angle, cfg.min_h)])
    sim.initialize()
    theirs = sim.shapes[0]
    config = {"grid": {"bpdx": 2, "bpdy": 1, "block": 8, "level_max": 5,
                       "extent": 2.0},
              "physics": {"nu": 4e-5, "lambda": 1e7, "cfl": 0.5},
              "shapes": [{"angle": angle, "L": length, "xpos": x,
                          "ypos": y}]}
    (mine,) = fish_box.follow(config, 0, [])[0]["bodies"]
    assert mine["mass"] == pytest.approx(theirs.M, rel=2e-6)
    assert mine["inertia"] == pytest.approx(theirs.J, rel=5e-6)
    assert np.hypot(*np.subtract(mine["com"], theirs.com)) < 2e-6 * length
    # and the fish's own area by the trapezoid rule is the program's
    f = fish_box.Fish(length, x, y, angle, cfg.min_h)
    f.midline(0.0)
    assert f.area == pytest.approx(theirs.area, rel=1e-12)
