"""``benchmark/xplane_meta.py`` and the readers built on it, held to two
small recorded traces of the cavity at 64^2 on one TPU v5 lite:

``tiny-cavity-64.xplane.pb.gz``         PR 23: a program with no scopes
                                        and no annotations
``tiny-cavity-64-scoped.xplane.pb.gz``  PR 24: the same three traced
                                        steps with the scope vocabulary
                                        in every operation's ``tf_op``
                                        and the ``cup2d:*`` spans on the
                                        host's line

The known numbers were read off the files by hand (``python3
benchmark/reduce.py <file>`` lists what a trace holds; the metadata
stats with a throw-away protobuf dump) and with the readers; a change
that moves them is a change of the yardstick. No TensorFlow anywhere.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import reduce, xplane_meta  # noqa: E402

FIXTURES = os.path.join(ROOT, "benchmark", "fixtures")
PLAIN = os.path.join(FIXTURES, "tiny-cavity-64.xplane.pb.gz")
SCOPED = os.path.join(FIXTURES, "tiny-cavity-64-scoped.xplane.pb.gz")
TPU = "/device:TPU:0"
VOCABULARY = ("advect", "substage0", "substage1", "penalize",
              "poisson_rhs", "poisson_solve", "project_correct", "diag",
              "krylov", "mg_cycle", "mg_smooth", "mg_transfer",
              "mg_coarse", "fft_diag")


def _device(path):
    return next(p for p in xplane_meta.planes(path) if p["name"] == TPU)


# -- the fixture without scopes -----------------------------------------

def test_tf_op_of_an_operation_read_by_hand():
    ops = xplane_meta.tf_ops(PLAIN)
    assert list(ops) == [TPU]
    assert ops[TPU]["compare_select_fusion.40"] == \
        "jit(step)/jit(_where)/select_n:"


def test_counts_of_named_operations():
    dev = _device(PLAIN)
    assert len(dev["meta"]) == 284
    assert sum(1 for _, st in dev["meta"].values()
               if st.get("tf_op")) == 207
    events = xplane_meta._line_events(dev, reduce.OPS_LINE)
    assert len(events) == 2151
    assert sum(1 for _, _, mid in events
               if dev["meta"][mid][1].get("tf_op")) == 1980
    # stats the reader does not use ride along untouched
    stats = next(st for name, st in dev["meta"].values()
                 if reduce.short(name) == "compare_select_fusion.40")
    assert {"hlo_category", "source", "source_stack", "tf_op"} <= set(stats)
    assert stats["hlo_category"] == "loop fusion"


def test_event_times_are_profile_data_times():
    """Both readers cut the same window only if they time an event the
    same: every event of every line, to the nanosecond."""
    mine = {p["name"]: p for p in xplane_meta.planes(PLAIN)}
    seen = 0
    for plane in reduce.load(PLAIN).planes:
        lines = {ln[0]: ln for ln in mine[plane.name]["lines"]}
        for line in plane.lines:
            _, ts, events = lines[line.name]
            for e, (mid, off, dur) in zip(line.events, events):
                assert (e.start_ns, e.start_ns + e.duration_ns) == \
                    xplane_meta._span_ns(ts, off, dur)
                assert e.name == mine[plane.name]["meta"][mid][0]
                seen += 1
    assert seen == 7976


def test_a_program_without_scopes_is_all_unscoped():
    trace = reduce.reduce_trace(PLAIN, ["jit_step"])
    t = xplane_meta.self_ms_by_scope(PLAIN, ["jit_step"], VOCABULARY)
    assert t["steps"] == trace["steps"] == 2
    assert list(t["self_ms"]) == [xplane_meta.UNSCOPED] and not t["loops"]
    # operations of one line nest and never cross, so their self times
    # add up to the reduction's busy time
    assert sum(t["self_ms"].values()) == pytest.approx(
        1e3 * trace["busy_s"], rel=1e-9)
    mods = xplane_meta.module_ms(PLAIN, ["jit_step"])
    assert mods["jit_step"] / mods["steps"] == pytest.approx(
        1e3 * trace["device_step_s"], rel=1e-9)
    assert mods["jit_copy"] == pytest.approx(0.008797, rel=1e-6)
    # counted inside the step executables only: the copies drop out
    inside = xplane_meta.self_ms_by_scope(
        PLAIN, ["jit_step"], VOCABULARY, inside=["jit_step"])
    assert inside["self_ms"][xplane_meta.UNSCOPED] == pytest.approx(
        0.16581, rel=1e-6)
    assert xplane_meta.host_events(PLAIN) == []
    assert xplane_meta.self_ms_by_scope(
        PLAIN, ["jit_no_such_module"], VOCABULARY) is None


def test_scope_path_passes_over_what_is_no_scope():
    path = "jit(step)/poisson_solve/while/body/krylov/mg_cycle/" \
           "mg_smooth/jit(_where)/select_n"
    assert xplane_meta.scope_path(path, VOCABULARY) == (
        "poisson_solve", "krylov", "mg_cycle", "mg_smooth")
    assert xplane_meta.scope_path(
        "jit(step)/advect/substage1/mul", VOCABULARY) == (
        "advect", "substage1")
    assert xplane_meta.scope_path("jit(step)/while/cond/lt",
                                  VOCABULARY) == ()
    assert xplane_meta.scope_path(None, VOCABULARY) == ()
    t = {"poisson_solve/krylov": 2.0, "poisson_solve/krylov/mg_cycle": 3.0,
         "poisson_solve": 1.0, "advect/substage0": 4.0}
    assert xplane_meta.under(t, "poisson_solve") == 6.0
    assert xplane_meta.under(t, "mg_cycle") == 3.0
    assert xplane_meta.under(t, "advect") == 4.0


def test_no_tensorflow():
    assert "tensorflow" not in sys.modules
    with open(xplane_meta.__file__) as f:
        assert "import tensorflow" not in f.read()


# -- the fixture with scopes and annotations (PR 24, chip call 1) -------

CELL = {"name": "tiny", "warmup_steps": 12,
        "trace": {"after_warmup": 1, "steps": 3,
                  "step_modules": ["jit_step"]}}


def _metric(name):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        return json.load(f)


@pytest.fixture()
def ctx(monkeypatch, tmp_path):
    """What run.py hands a reader, with the recorded trace in the
    run's place (every traced step there ran 2 Krylov iterations)."""
    from benchmark.readers import scope_ms, traced_steps
    monkeypatch.setattr(traced_steps, "xplane", lambda ctx: SCOPED)
    monkeypatch.setattr(traced_steps, "out_dir", lambda ctx: str(tmp_path))
    scope_ms._table.cache_clear()
    return {"cell": CELL, "trace": reduce.reduce_trace(SCOPED, ["jit_step"]),
            "records": [{"step": s, "poisson_iters": 2, "precond_cycles": 4}
                        for s in range(1, 30)],
            "out": str(tmp_path)}


def test_scoped_fixture_names_and_spans():
    ops = xplane_meta.tf_ops(SCOPED)[TPU]
    assert ops["dynamic-update-slice.28"] == \
        "jit(step)/advect/substage1/scatter:"
    spans = xplane_meta.host_events(SCOPED)
    for name in ("step", "dispatch", "verdict", "snapshot", "record"):
        assert sum(1 for *_, n in spans if n == "cup2d:" + name) == 3
    # on the device's clock: the spans overlap the traced window
    _, lo, hi, steps, _, in_flight = xplane_meta.device_windows(
        SCOPED, ["jit_step"])[0]
    assert steps == 2 and not in_flight
    assert spans[0][0] < lo < hi < spans[-1][1]
    # the reduction, unedited, now names the idle gaps by them
    gaps = dict(reduce.reduce_trace(SCOPED, ["jit_step"])["idle_gaps"])
    assert gaps["cup2d:verdict"] == pytest.approx(0.002665142, rel=1e-6)
    assert not any(name.startswith("$") for name in gaps)


def test_scope_table_of_the_scoped_fixture(ctx):
    t = xplane_meta.self_ms_by_scope(SCOPED, ["jit_step"], VOCABULARY,
                                     inside=["jit_step"])
    per_step = {k: v / t["steps"] for k, v in t["self_ms"].items()}
    assert per_step["advect/substage0"] == pytest.approx(0.008245, rel=1e-6)
    assert per_step["poisson_solve/krylov/mg_cycle/mg_coarse"] == \
        pytest.approx(0.011847, rel=1e-6)
    assert per_step[xplane_meta.UNSCOPED] == pytest.approx(0.0063795,
                                                           rel=1e-6)
    # 2 steps x 2 iterations x 2 cycles: the coarsest level's loop
    assert t["loops"]["mg_coarse"] == 8
    # every operation inside the step executables is in the table
    whole = xplane_meta.self_ms_by_scope(SCOPED, ["jit_step"], VOCABULARY)
    copies = whole["self_ms"][xplane_meta.UNSCOPED] \
        - t["self_ms"][xplane_meta.UNSCOPED]
    assert sum(whole["self_ms"].values()) == pytest.approx(
        1e3 * ctx["trace"]["busy_s"], rel=1e-9)
    assert 0 < copies <= xplane_meta.module_ms(
        SCOPED, ["jit_step"])["jit_copy"]


@pytest.mark.parametrize("name,value", [
    ("advect_ms", 0.015605), ("poisson_solve_ms", 0.058874),
    ("mg_cycle_ms", 0.009578875), ("projection_ms", 0.0023325),
    ("snapshot_copy_ms", 0.0041985),
    ("idle_spanned_pct", 93.47764137313463)])
def test_trace_readers_on_the_scoped_fixture(ctx, name, value):
    import importlib
    m = _metric(name)
    reader = importlib.import_module("benchmark.readers." + m["reader"])
    assert reader.read({**ctx, "metric": m}) == pytest.approx(value,
                                                              rel=1e-6)
    if m["reader"] == "scope_ms":
        with open(os.path.join(ctx["out"], "scopes.json")) as f:
            table = json.load(f)
        assert table["loops"]["mg_coarse"] == \
            table["precond_cycles_recorded"] == 8
        whole = table["reduction_window"]     # no run in flight here
        assert table["sum_ms_per_step"] == whole["sum_ms_per_step"] \
            <= whole["device_step_ms"]


@pytest.mark.parametrize("name", ["advect_ms", "mg_cycle_ms",
                                  "snapshot_copy_ms", "idle_spanned_pct"])
def test_trace_readers_leave_out_what_is_not_there(ctx, monkeypatch, name):
    """A program without scopes or annotations (the parent of PR 24),
    and a run without a device trace (a CPU rehearsal)."""
    import importlib
    from benchmark.readers import scope_ms, traced_steps
    m = _metric(name)
    reader = importlib.import_module("benchmark.readers." + m["reader"])
    monkeypatch.setattr(traced_steps, "xplane", lambda ctx: PLAIN)
    scope_ms._table.cache_clear()
    plain = {**ctx, "trace": reduce.reduce_trace(PLAIN, ["jit_step"]),
             "metric": m}
    if name == "snapshot_copy_ms":      # the copies were always there
        assert reader.read(plain) == pytest.approx(0.0043985, rel=1e-6)
    else:
        assert reader.read(plain) is None
    assert reader.read({**ctx, "trace": None, "metric": m}) is None


# -- the readers of the program's spans, on hand-made spans -------------

def _span_ctx(tmp_path, monkeypatch, periods, waits, iters, events=()):
    from benchmark.readers import traced_steps
    monkeypatch.setattr(traced_steps, "out_dir", lambda ctx: str(tmp_path))
    with open(tmp_path / "events.jsonl", "w") as f:
        for row in events:
            f.write(json.dumps(row) + "\n")
    ts = [1_000_000]
    for ms in periods:
        ts.append(ts[-1] + int(ms * 1000))
    steps = list(range(16, 16 + len(periods)))
    spans = [{"name": "verdict", "ts_us": t + 100, "dur_us": int(w * 1000)}
             for t, w in zip(ts, waits)]
    return {"cell": CELL, "spans": spans, "metric": {},
            "window": {"steps": steps, "step_ms": list(periods),
                       "ts_us": ts},
            "records": [{"step": s, "poisson_iters": it}
                        for s, it in zip(steps, iters)]}


def test_loop_host_ms_is_the_period_less_the_wait(tmp_path, monkeypatch):
    from benchmark.readers import loop_host_ms
    ctx = _span_ctx(tmp_path, monkeypatch,
                    periods=[100, 140, 100, 900, 100],
                    waits=[97, 136, 95, 200, 97], iters=[0] * 5,
                    events=[{"event": "trace_start", "step": 18},
                            {"event": "trace_stop", "step": 19}])
    # steps 18 and 19 hold the profiler: 3, 4 and 3 ms are left
    assert loop_host_ms.read(ctx) == 3
    os.remove(tmp_path / "events.jsonl")
    assert loop_host_ms.read(ctx) == 4      # untraced: 3 4 5 700 3


def test_step_unexplained_ms_reads_what_the_count_does_not(
        tmp_path, monkeypatch):
    from benchmark.readers import step_unexplained_ms
    iters = [0, 1, 2] * 6
    periods = [60.0 + 40.0 * it for it in iters]
    clean = _span_ctx(tmp_path, monkeypatch, periods, [1] * 18, iters)
    assert step_unexplained_ms.read(clean) == pytest.approx(0.0, abs=1e-9)
    periods[7] += 1800.0                    # one step of 2 s
    struck = _span_ctx(tmp_path, monkeypatch, periods, [1] * 18, iters)
    assert step_unexplained_ms.read(struck) == pytest.approx(100.0)
    one_count = _span_ctx(tmp_path, monkeypatch, [60.0] * 8, [1] * 8,
                          [0] * 8)
    assert step_unexplained_ms.read(one_count) is None   # no line


# -- read_run.py: the parked metrics of a finished run -------------------

def test_read_run_evaluates_what_no_cell_lists_yet(tmp_path, monkeypatch,
                                                   capsys):
    """A finished run's directory, rebuilt by hand around the scoped
    fixture: read_run.py reports the metrics the cell file lists AND
    the ones that wait for a `benchmark` PR, through run.py's own
    window and the same readers."""
    import gzip

    from benchmark import read_run, run
    from benchmark.readers import scope_ms, traced_steps

    cell = "cavity-re10k-8192.solo"
    out = tmp_path / cell
    prof = out / "trace" / "plugins" / "profile" / "x"
    prof.mkdir(parents=True)
    with gzip.open(SCOPED, "rb") as f:
        (prof / "t.xplane.pb").write_bytes(f.read())
    t0, rows, recs = 1_000_000, [], []
    for s in range(12, 40):                 # 4 ms steps, 3 ms waits
        rows.append({"event": "span", "name": "step", "step": s,
                     "ts_us": t0 + 4000 * s, "dur_us": 3900})
        rows.append({"event": "span", "name": "verdict", "step": s - 1,
                     "ts_us": t0 + 4000 * s + 500, "dur_us": 3000})
        recs.append({"event": "metrics", "step": s + 1,
                     "poisson_iters": 2, "precond_cycles": 4})
    (out / "spans.jsonl").write_text(
        "\n".join(map(json.dumps, rows)) + "\n")
    (out / "metrics.jsonl").write_text(
        "\n".join(map(json.dumps, recs)) + "\n")
    (out / "events.jsonl").write_text(
        json.dumps({"event": "trace_start", "step": 13}) + "\n"
        + json.dumps({"event": "trace_stop", "step": 16}) + "\n")
    monkeypatch.setattr(run, "OUT_ROOT", str(tmp_path))
    monkeypatch.setattr(traced_steps, "out_dir", lambda ctx: str(out))
    monkeypatch.setattr(traced_steps, "xplane", lambda ctx: str(
        prof / "t.xplane.pb"))
    scope_ms._table.cache_clear()
    assert read_run.main(["--workload", cell, "--rehearsal"]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    m = res["metrics"]
    assert m["device_step_ms"]["listed"] and not m["advect_ms"]["listed"]
    assert m["advect_ms"]["value"] == pytest.approx(0.015605, rel=1e-6)
    assert m["mg_cycle_ms"]["value"] == pytest.approx(0.009578875, rel=1e-6)
    assert m["snapshot_copy_ms"]["value"] == pytest.approx(0.0041985,
                                                           rel=1e-6)
    assert m["loop_host_ms"]["value"] == pytest.approx(1.0)
    assert "step_unexplained_ms" not in m       # one count: no line
    assert (out / "scopes.json").exists()
