"""The forest's host side in the flight recorder's span timeline: the
table rebuild after a regrid split into its parts, and a shaped step's
``dispatch`` into the dt decision, the enqueue, the one pull and the
bodies' bookkeeping (``tracing.SPANS``) — with the zero-overhead
contract of tests/test_tracing.py held on the forest: a recorder-on run
is bit-identical to a recorder-off run, with equal pulls and compiles.
"""

import ast
import json
import os

import jax
import numpy as np
import pytest

from cup2d_tpu import tracing
from cup2d_tpu.amr import AMRSim
from cup2d_tpu.config import SimConfig
from cup2d_tpu.models import FishShape
from cup2d_tpu.profiling import HostCounters
from cup2d_tpu.resilience import EventLog, StepGuard
from cup2d_tpu.tracing import FlightRecorder

TABLE_PARTS = {"tables.topo", "tables.halo", "tables.faces", "tables.pad",
               "tables.flux", "tables.coarse", "tables.geom"}
HALO_SETS = {"vec3", "vec1", "sca1", "vec1t", "sca1t", "sca4t", "vec4t"}
STEP_PARTS = ("dt", "kinematics", "shape_inputs", "enqueue", "pull",
              "bodies")
STEPS = 4           # start-up solves: one step executable to compile


def _fish_run(traced: bool, path: str, monkeypatch):
    """One fish on a levelMax-4 forest through the guarded loop as the
    CLI drives it (a drained regrid before each of the first ten
    steps): the flow, the clock and the pull and compile counts."""
    cfg = SimConfig(bpdx=2, bpdy=1, level_max=4, level_start=2,
                    extent=2.0, dtype="float32", nu=4e-5, lam=1e6,
                    rtol=2.0, ctol=1.0)
    flight = sink = None
    if traced:
        sink = EventLog(path)
        flight = FlightRecorder(capture_memory=False, sink=sink).install()
    counters = HostCounters().install()
    pulls = {"n": 0}
    real_get = jax.device_get

    def counting_get(x):
        pulls["n"] += 1
        return real_get(x)

    try:
        with monkeypatch.context() as m:
            m.setattr(jax, "device_get", counting_get)
            sim = AMRSim(cfg, shapes=[FishShape(0.4, 1.0, 0.5, 0.0,
                                                cfg.min_h)])
            sim.compute_forces_every = 0    # a third off the compile
            guard = StepGuard(sim)
            for _ in range(STEPS):
                if sim.step_count <= 10:     # as the CLI's loop does
                    guard.drain()
                    sim.adapt()
                guard.step()
            guard.drain()
            sim.sync_fields()
    finally:
        counters.uninstall()
        if flight is not None:
            flight.close()
            sink.close()
    fields = {k: np.asarray(v) for k, v in sim.forest.fields.items()}
    return (fields, sim.time, pulls["n"], counters.device_gets,
            counters.jit_compiles)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """A throwaway run (jax's in-process caches then serve both twins
    alike), the recorder-off twin and the recorder-on twin with its
    span rows in the order the recorder closed them."""
    out = tmp_path_factory.mktemp("forest_spans")
    mp = pytest.MonkeyPatch()
    try:
        _fish_run(False, "", mp)
        off = _fish_run(False, "", mp)
        on = _fish_run(True, str(out / "spans.jsonl"), mp)
    finally:
        mp.undo()
    with open(out / "spans.jsonl") as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    return off, on, rows


def _with_children(rows):
    """(row, its direct children) for every row. The ring holds a span
    when it closes, so a span's children are the rows one level deeper
    closed since the last row at its own depth or above."""
    waiting, out = {}, []
    for r in rows:
        d = r["depth"]
        kids = waiting.pop(d + 1, [])
        for k in [k for k in waiting if k > d]:
            del waiting[k]
        out.append((r, kids))
        waiting.setdefault(d, []).append(r)
    return out


def test_table_rebuild_parts_nest_and_cover_it(runs):
    rebuilds = [(r, kids) for r, kids in _with_children(runs[2])
                if r["name"] == "tables"]
    assert len(rebuilds) >= 3          # the climb and the regrids
    inside = whole = 0
    for r, kids in rebuilds:
        names = [k["name"] for k in kids]
        assert set(names) <= TABLE_PARTS
        assert all(k["depth"] == r["depth"] + 1 for k in kids)
        for part in ("tables.topo", "tables.faces", "tables.pad",
                     "tables.flux", "tables.geom"):
            assert names.count(part) == 1, (part, names)
        assert sorted(k["set"] for k in kids
                      if k["name"] == "tables.halo") == sorted(HALO_SETS)
        # the start-up solves take the two-level maps on every rebuild
        assert names.count("tables.coarse") == (r["step"] < 10)
        inside += sum(k["dur_us"] for k in kids)
        whole += r["dur_us"]
    assert inside >= 0.9 * whole, (inside, whole)


def test_every_shaped_step_names_its_parts_once(runs):
    dispatches = [(r, kids) for r, kids in _with_children(runs[2])
                  if r["name"] == "dispatch"]
    assert len(dispatches) == STEPS
    for r, kids in dispatches:
        names = [k["name"] for k in kids if k["name"] != "tables"]
        assert names == list(STEP_PARTS), names
        assert all(k["step"] == r["step"] for k in kids
                   if k["name"] in STEP_PARTS)
    # the regrids before the first steps leave no dt the previous
    # megastep computed for this forest: those steps pull one
    pulled = {r["step"]: r["pulled"] for r in runs[2] if r["name"] == "dt"}
    assert pulled[0] is True
    assert set(pulled.values()) <= {True, False}


def test_forest_spans_cost_no_pull_and_no_compile(runs):
    (f_off, t_off, *counts_off), (f_on, t_on, *counts_on), rows = runs
    assert rows and t_on == t_off
    assert f_on.keys() == f_off.keys()
    for k in f_off:
        assert np.array_equal(f_on[k], f_off[k]), k
    assert counts_on == counts_off     # device_get calls, device_gets,
    #                                    jit_compiles


def test_every_span_name_is_in_the_vocabulary():
    """Every ``tracing.span("<literal>")`` in the package names a member
    of ``tracing.SPANS``: the host's vocabulary stays one list."""
    pkg = os.path.dirname(tracing.__file__)
    found = set()
    for root, _, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(root, fn)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "span" \
                        and isinstance(node.func.value, ast.Name) \
                        and node.func.value.id == "tracing" \
                        and node.args \
                        and isinstance(node.args[0], ast.Constant):
                    found.add(node.args[0].value)
    assert {"step", "tables", "tables.halo", "pull"} <= found
    assert found <= set(tracing.SPANS), found - set(tracing.SPANS)
    assert len(set(tracing.SPANS)) == len(tracing.SPANS)
