"""Flight-recorder subsystem tests (tracing.py, PR 18).

- Span timeline: a guarded run with an injected fault produces the
  full hierarchy (step > dispatch/snapshot/verdict + recover > rung
  actions) in the flushed JSONL stream, and the Perfetto export is a
  structurally valid Chrome trace with correct nesting.
- Compile attribution + HBM memory ledger: every named_jit compile
  lands on its label with a duration, memory_analysis bytes and the
  Poisson components observed at trace time; the ledger's own
  re-lower compile is suppressed from HostCounters (the
  equal-compile-count contract).
- THE zero-overhead contract: a tracing-on run is bit-identical to a
  tracing-off run with EQUAL device_gets and EQUAL jit_compiles, on
  the guarded UniformSim hot loop and on FleetServer churn.
- Serving latency histograms: log2 bucket math, percentile ordering,
  the submit/admit/step collector flow.
- Log rotation (EventLog + ClientStreams) and the torn-tail-tolerant
  metrics reader (satellites).
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup2d_tpu import tracing
from cup2d_tpu.config import SimConfig
from cup2d_tpu.faults import FaultPlan
from cup2d_tpu.profiling import (HostCounters, load_metrics,
                                 load_metrics_report)
from cup2d_tpu.resilience import EventLog, StepGuard
from cup2d_tpu.tracing import (FlightRecorder, LatencyHistogram,
                               ServingLatency, spans_to_perfetto)
from cup2d_tpu.uniform import UniformSim, taylor_green_state


def _cfg(**kw):
    base = dict(bpdx=1, bpdy=1, level_max=1, level_start=0, extent=1.0,
                nu=1e-3, cfl=0.4, lam=1e6, dtype="float64",
                max_poisson_iterations=100)
    base.update(kw)
    return SimConfig(**base)


def _usim(level=1):
    """16^2 production-regime uniform sim (exact startup skipped) with
    a Taylor-Green state — the instruments are size-independent."""
    sim = UniformSim(_cfg(), level=level)
    sim.state = taylor_green_state(sim.grid)
    sim.step_count = 20
    return sim


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    """Every test leaves the module recorder uninstalled (a leaked
    recorder would silently turn every later test tracing-on)."""
    yield
    r = tracing.recorder()
    if r is not None:
        r.uninstall()


# ---------------------------------------------------------------------------
# latency histogram / serving collector units
# ---------------------------------------------------------------------------

def test_latency_histogram_buckets_and_percentiles():
    h = LatencyHistogram()
    assert h.report() == {"count": 0}
    assert h.percentile(0.5) is None
    for us in (1, 3, 5, 100, 1000, 10_000, 100_000):
        h.add(us / 1e6)
    rep = h.report()
    assert rep["count"] == 7
    # percentiles are bucket upper edges clamped to the max — ordered,
    # positive, and never above the observed maximum
    assert 0 < rep["p50_ms"] <= rep["p90_ms"] <= rep["p99_ms"] \
        <= rep["max_ms"]
    assert rep["max_ms"] == pytest.approx(100.0)
    # conservative within one bucket: the true p50 (100 us) maps into
    # [64, 128) us, so the reported edge is 128 us = 0.128 ms
    assert rep["p50_ms"] == pytest.approx(0.128)
    # negative / zero durations clamp into bucket 0, never raise
    h.add(-1.0)
    h.add(0.0)
    assert h.report()["count"] == 9


def test_latency_histogram_overflow_bucket():
    h = LatencyHistogram()
    h.add(2e6)            # ~23 days: beyond the 40-bucket (2^40 us) range
    assert h.counts[-1] == 1
    assert h.report()["p99_ms"] == pytest.approx(2e9)  # clamped to max


def test_serving_latency_collector_flow():
    lat = ServingLatency()
    lat.on_submit("a")
    lat.on_submit("b")
    lat.on_admit("a")
    lat.on_step(["a", None], 0.002)      # None slots are skipped
    lat.on_step(["a", None], 0.002)
    rep = lat.report()
    pool = rep["pool"]
    assert pool["queue_wait"]["count"] == 1
    # admit_to_first_step observes exactly ONCE (popped at first step)
    assert pool["admit_to_first_step"]["count"] == 1
    assert pool["step"]["count"] == 2
    assert rep["clients"]["a"]["step"]["count"] == 2
    assert "b" not in rep["clients"]     # submitted, never admitted
    assert "untracked_clients" not in rep


def test_serving_latency_client_cap(monkeypatch):
    monkeypatch.setattr(ServingLatency, "MAX_CLIENTS", 2)
    lat = ServingLatency()
    for cid in ("a", "b", "c"):
        lat.on_step([cid], 0.001)
    rep = lat.report()
    # pool-wide keeps counting; the overflow id is reported, not lost
    assert rep["pool"]["step"]["count"] == 3
    assert set(rep["clients"]) == {"a", "b"}
    assert rep["untracked_clients"] == 1


# ---------------------------------------------------------------------------
# span timeline + Perfetto export (fault -> recovery rungs on the path)
# ---------------------------------------------------------------------------

def test_span_timeline_and_perfetto_export(tmp_path):
    sink = EventLog(str(tmp_path / "spans.jsonl"))
    flight = FlightRecorder(capture_memory=False, sink=sink).install()
    try:
        sim = _usim()
        guard = StepGuard(sim, faults=FaultPlan("nan_vel@22"))
        for _ in range(4):
            guard.step()
        guard.drain()
        flight.flush()
    finally:
        flight.uninstall()
        sink.close()
    rows = [json.loads(ln)
            for ln in open(tmp_path / "spans.jsonl") if ln.strip()]
    assert rows and all(r["event"] == "span" for r in rows)
    names = {r["name"] for r in rows}
    # the full guarded hierarchy, recovery rungs included
    assert {"step", "dispatch", "snapshot", "verdict",
            "recover", "retry"} <= names
    rec = next(r for r in rows if r["name"] == "recover")
    assert rec["verdict"] == "nonfinite" and rec["depth"] >= 1
    rungs = [r for r in rows if r["name"] in ("retry", "escalate")]
    assert all(isinstance(r["rung"], int) for r in rungs)
    # every row is a positive-duration interval with a step attribute
    assert all(r["dur_us"] >= 1 and isinstance(r["ts_us"], int)
               for r in rows)

    # Perfetto export: valid trace-event JSON, nested intervals
    trace = spans_to_perfetto(rows)
    evs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert evs and any(m["name"] == "process_name" for m in meta)
    step_ev = max((e for e in evs if e["name"] == "step"),
                  key=lambda e: e["dur"])
    inner = [e for e in evs
             if e["name"] in ("dispatch", "snapshot")
             and e["ts"] >= step_ev["ts"]
             and e["ts"] + e["dur"] <= step_ev["ts"] + step_ev["dur"]]
    assert inner, "no span nests inside the longest step interval"
    json.dumps(trace)   # serializable end-to-end


def test_span_ring_bounded_without_sink():
    flight = FlightRecorder(max_spans=16, sink=None,
                            capture_memory=False).install()
    try:
        for i in range(50):
            with tracing.span("s", i=i):
                pass
    finally:
        flight.uninstall()
    assert flight.span_count == 50
    assert len(flight._buf) == 16          # ring capped
    assert flight.spans_dropped == 34      # accounted, not silent


def test_spans_off_returns_shared_nullcontext():
    # library default (no recorder): span() must not allocate
    assert tracing.span("x") is tracing.span("y")


def test_post_trace_export_cli(tmp_path):
    from cup2d_tpu.post import main as post_main, trace_export
    sink = EventLog(str(tmp_path / "spans.jsonl"))
    flight = FlightRecorder(capture_memory=False, sink=sink).install()
    try:
        with tracing.span("step", step=1):
            with tracing.span("dispatch", step=1):
                pass
        flight.flush()
    finally:
        flight.uninstall()
        sink.close()
    out = trace_export(str(tmp_path / "spans.jsonl"))
    assert out == str(tmp_path / "trace.json")
    trace = json.load(open(out))
    assert any(e["name"] == "dispatch" for e in trace["traceEvents"])
    assert post_main(["--trace", str(tmp_path / "spans.jsonl")]) == 0


# ---------------------------------------------------------------------------
# compile attribution + HBM memory ledger
# ---------------------------------------------------------------------------

def test_compile_ledger_attribution_memory_and_suppression():
    # the operand exists BEFORE any instrument: an eager fill op can
    # itself fire a backend compile, which belongs to neither twin
    x = jnp.ones((8, 8), jnp.float32)
    x.block_until_ready()
    flight = FlightRecorder(spans=False).install()
    counters = HostCounters().install()
    try:
        def impl(a, b):
            tracing.note_component("unit.component")
            return a * 2.0 + b

        tracing.note_step(7)
        tracing.note_token("unit-token")
        fn = tracing.named_jit("unit.fn", jax.jit(impl))
        fn(x, x)
        fn(x, x)      # cache hit: no second compile
    finally:
        counters.uninstall()
    flight.uninstall()
    # ONE countable compile: the memory ledger's re-lower is hidden
    # from HostCounters and from the ledger (suppression contract)
    assert counters.jit_compiles == 1
    rep = flight.ledger_report()
    assert rep["compiles"] == 1
    assert rep["compile_ms_total"] > 0
    (row,) = rep["executables"]
    assert row["label"] == "unit.fn"
    assert row["compiles"] == 1 and row["ms"] > 0
    assert row["first_step"] == row["last_step"] == 7
    assert row["token"] == "unit-token"
    assert row["components"] == ["unit.component"]
    mem = row["memory"]
    assert mem and "error" not in mem
    assert mem["argument_bytes"] == 2 * 8 * 8 * 4
    assert mem["output_bytes"] == 8 * 8 * 4
    assert rep["hbm_exec_bytes"] == flight.hbm_exec_bytes() > 0


def test_named_jit_variant_label_and_passthrough():
    x = jnp.ones((4,), jnp.float32)    # built before the recorder
    x.block_until_ready()
    flight = FlightRecorder(spans=False, capture_memory=False).install()
    try:
        fn = tracing.named_jit(
            "unit.var",
            jax.jit(lambda v, flag=False: v + (1.0 if flag else 0.0),
                    static_argnames=("flag",)),
            variant=("flag",))
        fn(x, flag=True)
        fn(x, flag=False)
    finally:
        flight.uninstall()
    labels = {r["label"] for r in flight.ledger_report()["executables"]}
    assert labels == {"unit.var[flag=True]", "unit.var[flag=False]"}
    # attribute access passes through to the wrapped jit
    assert hasattr(tracing.named_jit("l", jax.jit(lambda x: x)),
                   "lower")


def test_uniform_sim_compiles_fully_attributed():
    """The acceptance criterion's attribution half on the solo driver:
    with the recorder on, every jit compile of a fresh UniformSim run
    lands in the ledger with a duration, and the driver's own
    executables carry their names + the Poisson component tag."""
    flight = FlightRecorder(spans=False, capture_memory=False).install()
    counters = HostCounters().install()
    try:
        sim = _usim()
        for _ in range(2):
            sim.step_once()
    finally:
        counters.uninstall()
    flight.uninstall()
    rep = flight.ledger_report()
    # nothing escapes: the ledger total equals the CI counter
    assert rep["compiles"] == counters.jit_compiles > 0
    by_label = {r["label"]: r for r in rep["executables"]}
    step_rows = [r for lbl, r in by_label.items()
                 if lbl.startswith("uniform.step")]
    assert step_rows and all(r["ms"] > 0 for r in step_rows)
    assert any("poisson.bicgstab" in (r["components"] or ())
               or "poisson.mg_solve" in (r["components"] or ())
               for r in step_rows)
    assert "uniform.dt" in by_label


# ---------------------------------------------------------------------------
# THE zero-overhead contract (acceptance-pinned): tracing-on is
# bit-identical with equal device_gets AND equal jit_compiles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [False, True],
                         ids=["recorder", "recorder+trace-window"])
def test_tracing_zero_overhead_uniform(tmp_path, monkeypatch, window):
    """``window``: the traced twin additionally runs inside an open
    ``CUP2D_TRACE`` window (PR 24), so every span also opens its
    ``cup2d:*`` profiler annotation — still bit-identical, still equal
    pulls and compiles."""
    from cup2d_tpu.profiling import TraceWindow

    def run(traced, tag):
        flight = tw = None
        if traced:
            sink = EventLog(str(tmp_path / f"spans_{tag}.jsonl"))
            flight = FlightRecorder(sink=sink).install()
            if window:
                tw = TraceWindow(0, 99, str(tmp_path / f"trace_{tag}"))
                tw.maybe_start(0)
                assert tracing.profiling()
        counters = HostCounters().install()
        pulls = {"n": 0}
        real_get = jax.device_get

        def counting_get(x):
            pulls["n"] += 1
            return real_get(x)

        try:
            with monkeypatch.context() as m:
                m.setattr(jax, "device_get", counting_get)
                sim = _usim()
                guard = StepGuard(sim)
                for _ in range(4):
                    guard.step()
                guard.drain()
        finally:
            counters.uninstall()
            if tw is not None:
                tw.close()
                assert not tracing.profiling()
            if flight is not None:
                flight.close()
        return (np.asarray(sim.state.vel), np.asarray(sim.state.pres),
                sim.time, pulls["n"], counters.jit_compiles,
                counters.device_gets)

    # throwaway warmup: jax's HLO-level compile cache spans runs in
    # one process, so the FIRST run of a fresh program pays compiles
    # its twin would inherit — warm it once, then compare twins in the
    # same cache regime
    run(False, "warm")
    va, pa, ta, pulls_a, compiles_a, gets_a = run(False, "off")
    vb, pb, tb, pulls_b, compiles_b, gets_b = run(True, "on")
    assert np.array_equal(va, vb)
    assert np.array_equal(pa, pb)
    assert ta == tb
    assert pulls_b == pulls_a          # raw jax.device_get calls
    assert gets_b == gets_a            # the counted CI metric
    assert compiles_b == compiles_a    # memory re-lowers suppressed


def test_tracing_zero_overhead_fleet_churn(tmp_path):
    """The serving half of the contract: a FleetServer churn run
    (admit/step/retire/refill) under the full recorder — spans,
    compile attribution, memory ledger, latency histograms — is
    bit-identical to the untraced twin with equal counted pulls and
    compiles."""
    from cup2d_tpu.fleet import FleetRequest, FleetServer, FleetSim
    from cup2d_tpu.uniform import taylor_green_state

    def run(traced, tag):
        flight = None
        if traced:
            sink = EventLog(str(tmp_path / f"fspans_{tag}.jsonl"))
            flight = FlightRecorder(sink=sink).install()
        counters = HostCounters().install()
        try:
            sim = FleetSim(_cfg(), level=1, members=2)
            sim.step_count = 20
            server = FleetServer(
                sim, latency=ServingLatency() if traced else None)
            g = sim.grid

            def req(cid, m, t_end=np.inf):
                st = taylor_green_state(g)
                return FleetRequest(client_id=cid,
                                    state=st._replace(
                                        vel=st.vel * (0.8 ** m)),
                                    t_end=float(t_end))

            server.submit(req("keep", 0))
            dt1 = float(sim._member_dt(taylor_green_state(g).vel
                                       * 0.8))
            server.submit(req("s1", 1, 1.9 * dt1))  # retires mid-run
            for k in range(5):
                if k == 3:
                    server.submit(req("s2", 1, 1.9 * dt1))
                server.step()
        finally:
            counters.uninstall()
            if flight is not None:
                flight.close()
        assert server.retired >= 1 and server.admitted >= 3
        return (np.asarray(sim.member_state(0).vel),
                float(sim.times[0]), counters.jit_compiles,
                counters.device_gets)

    run(False, "warm")     # HLO-cache warmup — see the uniform twin
    v_a, t_a, compiles_a, gets_a = run(False, "off")
    v_b, t_b, compiles_b, gets_b = run(True, "on")
    assert np.array_equal(v_a, v_b)
    assert t_a == t_b
    assert gets_b == gets_a
    assert compiles_b == compiles_a


# ---------------------------------------------------------------------------
# satellites: size-capped rotation + torn-tail-tolerant reader
# ---------------------------------------------------------------------------

def test_eventlog_rotation_and_segmented_read(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    log = EventLog(path, rotate_mb=0.001)     # ~1 KiB per segment
    n = 60
    for i in range(n):
        log.emit(event="metrics", i=i, pad="x" * 40)
    log.close()
    segs = sorted(f for f in os.listdir(tmp_path)
                  if f.startswith("metrics.jsonl."))
    assert len(segs) >= 2                      # rotation actually fired
    assert all(os.path.getsize(tmp_path / s) < 2048 for s in segs)
    # the reader folds segments back in write order
    recs = load_metrics(path)
    assert [r["i"] for r in recs] == list(range(n))


def test_eventlog_rotation_resumes_numbering(tmp_path):
    # a restarted run must append segments AFTER the existing ones
    path = str(tmp_path / "m.jsonl")
    for _ in range(2):
        log = EventLog(path, rotate_mb=0.0001)   # ~105 bytes
        for i in range(4):
            log.emit(event="metrics", i=i, pad="y" * 80)
        log.close()
    recs = load_metrics(path)
    assert len(recs) == 8                      # nothing overwritten


def test_client_streams_rotation(tmp_path):
    from cup2d_tpu.profiling import ClientStreams
    cs = ClientStreams(str(tmp_path), rotate_mb=0.001)
    for i in range(60):
        cs.emit("c1", {"i": i, "pad": "z" * 40})
    cs.close()
    segs = [f for f in os.listdir(tmp_path)
            if f.startswith("c1.jsonl.")]
    assert segs
    recs = load_metrics(str(tmp_path / "c1.jsonl"))
    assert [r["i"] for r in recs] == list(range(60))


def test_metrics_reader_tolerates_torn_and_empty(tmp_path):
    p = tmp_path / "torn.jsonl"
    with open(p, "w") as f:
        for i in range(3):
            f.write(json.dumps({"event": "metrics", "i": i}) + "\n")
        f.write('{"event": "metrics", "i": 3, "tr')   # SIGKILL tail
    recs, torn = load_metrics_report(str(p))
    assert [r["i"] for r in recs] == [0, 1, 2]
    assert torn == 1

    empty = tmp_path / "empty.jsonl"
    empty.touch()
    assert load_metrics_report(str(empty)) == ([], 0)

    with pytest.raises(FileNotFoundError):
        load_metrics_report(str(tmp_path / "missing.jsonl"))


def test_post_metrics_summary_reports_truncated(tmp_path):
    from cup2d_tpu.post import metrics_summary
    p = tmp_path / "metrics.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"event": "serving_latency",
                            "pool": {"step": {"count": 5}}}) + "\n")
        f.write(json.dumps({"event": "compile_ledger", "compiles": 3,
                            "executables": []}) + "\n")
        f.write('{"torn')
    out = metrics_summary(str(p))
    assert out["truncated_records"] == 1
    assert out["steps"] == 0                   # no metrics rows: no crash
    # the run-report rows surface verbatim in the summary
    assert out["serving_latency"]["pool"]["step"]["count"] == 5
    assert out["compile_ledger"]["compiles"] == 3
