"""Run-telemetry subsystem tests (profiling.py + the PR-3 resilience
additions): the frozen metrics schema, the zero-extra-sync contract
(metrics-on bit-identical to metrics-off with EQUAL device_get counts —
the PR-2 trace-count harness extended), the physics-invariant watchdog
against injected wrong-but-finite corruption, the steady-state
recompile/transfer-count guard, the readers' contract for streams of
older schemas, and the windowed trace driver."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup2d_tpu.config import SimConfig
from cup2d_tpu.faults import FaultPlan
from cup2d_tpu.models import DiskShape
from cup2d_tpu.profiling import (METRICS_KEYS, HostCounters,
                                 MetricsRecorder, TraceWindow,
                                 load_metrics, summarize_metrics)
from cup2d_tpu.resilience import (EventLog, PhysicsWatchdog, StepGuard)
from cup2d_tpu.sim import Simulation


def _cfg(**kw):
    base = dict(bpdx=1, bpdy=1, level_max=1, level_start=0, extent=1.0,
                nu=1e-3, cfl=0.4, lam=1e6, dtype="float64",
                max_poisson_iterations=100)
    base.update(kw)
    return SimConfig(**base)


def _sim():
    disk = DiskShape(0.1, 0.4, 0.5, prescribed=(0.2, 0.0))
    return Simulation(_cfg(), shapes=[disk], level=3)


def _amr_sim():
    from cup2d_tpu.amr import AMRSim
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=3, level_start=1,
                    extent=1.0, dtype="float64", nu=1e-3, lam=1e5,
                    rtol=0.5, ctol=0.05, max_poisson_iterations=40,
                    poisson_tol=1e-4, poisson_tol_rel=1e-3)
    sim = AMRSim(cfg, shapes=[DiskShape(0.08, 0.4, 0.5,
                                        prescribed=(0.2, 0.0))])
    sim.compute_forces_every = 0
    return sim


# ---------------------------------------------------------------------------
# schema stability (golden key set): every producer emits the SAME keys
# ---------------------------------------------------------------------------

# the LITERAL schema-v8 key set: METRICS_KEYS is the producers' truth,
# this tuple is the consumers' — any drift between them (a key renamed,
# dropped, or added without bumping the schema) fails here on purpose.
# v3 added the fleet-batching fields (fleet_members / member_steps_per_s
# / member_health, fleet.py); v4 the solve-path attribution pair
# (poisson_mode — the active CUP2D_POIS latch + trigger state — and the
# per-step preconditioner-cycle count, PR 6); v5 the elastic-topology
# group (topology_epoch / remesh_count / remesh_ms — the TopologyGuard
# + StepGuard.elastic_recover subsystem, PR 7); v6 the kernel-tier
# attribution pair (kernel_tier — the active CUP2D_PALLAS megakernel
# latch — and prec_mode, the CUP2D_PREC storage-precision contract,
# PR 9); v7 the continuous-batching serving gauges (active_members /
# occupancy / admitted / evicted / queue_depth — the FleetServer
# slot-pool lifecycle, fleet.py); v8 the boundary-condition attribution
# pair (bc_table — the driver's BCTable token, e.g. "fs,fs,fs,fs" —
# and case, the case-registry tag or null for ad-hoc runs, bc.py +
# cases.py); v9 the host-redundant mirror-tier group (mirror_bytes /
# mirror_ms / restore_source — the neighbor-mirrored snapshot ring and
# the rung attribution of elastic recoveries, PR 17); v10 the
# flight-recorder gauges (span_count / compile_ms_total /
# hbm_exec_bytes — the tracing.FlightRecorder span ring and
# compile/memory ledger, PR 18); v11 the smoother-tier attribution
# (smoother_tier — the pressure hierarchy's sweep-chain latch, xla |
# strip | strip+bf16 with "+bf16" suffixing whatever base the shape
# gate left armed, ISSUE 19); v12 a VALUE-vocabulary rev, no key
# moved (ISSUE 20): poisson_mode gains the uniform-family direct
# tokens "fftd" / "fftd+tridiag" (FFT-diagonalized per-mode solves,
# poisson_iters == 1 by contract, precond_cycles == 0) and bc_table
# gains the "pd" periodic face token ("pd,pd,pd,pd" turbulence box,
# "pd,pd,ns,ns" periodic channel); v13 the bodies and the pad bucket
# (ISSUE 28): bodies — one entry a shape of com / angle / u / v / omega
# / mass / inertia from the step's one existing pull, null without
# shapes — and pad_blocks beside n_blocks, null off the forest; v14 the
# force pass's block lists (ISSUE 29): force_blocks — per shape, the
# block rows the forest's surface-force reduction ran over — and
# force_cap, the sticky power-of-two capacity they are padded to; null
# without shapes and off the forest; v15 takes phase_ms OUT (ISSUE 30:
# the fencing phase timers went, and the key was null in every record
# a run without them wrote) — readers ignore it in older streams
# (test_older_schema_streams_still_load).
_SCHEMA_V15_KEYS = (
    "schema", "step", "t", "dt", "wall_ms",
    "umax", "dt_next",
    "poisson_iters", "poisson_residual",
    "poisson_converged", "poisson_stalled",
    "poisson_mode", "precond_cycles",
    "kernel_tier", "prec_mode",
    "smoother_tier",
    "bc_table", "case",
    "energy", "div_linf",
    "n_blocks", "pad_blocks", "blocks_per_level", "refines", "coarsens",
    "bodies",
    "force_blocks", "force_cap",
    "halo_real_bytes", "halo_padded_bytes",
    "jit_compiles", "device_gets", "state_gathers", "hbm_peak_bytes",
    "snap_ring_bytes", "replayed_steps",
    "topology_epoch", "remesh_count", "remesh_ms",
    "mirror_bytes", "mirror_ms", "restore_source",
    "fleet_members", "member_steps_per_s", "member_health",
    "active_members", "occupancy", "admitted", "evicted",
    "queue_depth",
    "span_count", "compile_ms_total", "hbm_exec_bytes",
)


def test_metrics_schema_v15_key_set_pinned():
    from cup2d_tpu.profiling import METRICS_SCHEMA_VERSION
    assert METRICS_SCHEMA_VERSION == 15
    assert METRICS_KEYS == _SCHEMA_V15_KEYS


# the keys each older schema did not have yet (all three had phase_ms):
# the streams a run of that time left behind
_NOT_YET_IN = {
    12: ("pad_blocks", "bodies", "force_blocks", "force_cap"),
    13: ("force_blocks", "force_cap"),
    14: (),
}


@pytest.mark.parametrize("schema", sorted(_NOT_YET_IN))
def test_older_schema_streams_still_load(schema, tmp_path, capsys):
    """The readers' contract that lets a key leave: a metrics.jsonl
    written under an older schema — with phase_ms, without the keys
    that came later — loads, summarises and prints through
    post --metrics; unknown keys are ignored, missing keys read null."""
    from cup2d_tpu import post

    absent = _NOT_YET_IN[schema]
    keys = [k for k in _SCHEMA_V15_KEYS if k not in absent]
    path = tmp_path / "metrics.jsonl"
    with open(path, "w") as f:
        for n in (1, 2, 3):
            rec = dict.fromkeys(keys)
            rec.update(schema=schema, step=n, t=0.1 * n, dt=0.1,
                       wall_ms=2.0, umax=1.0, poisson_iters=n,
                       poisson_converged=True, energy=0.5,
                       div_linf=1e-3, n_blocks=64, jit_compiles=0,
                       device_gets=2,
                       phase_ms={"flow": 1.5} if n == 2 else None)
            assert set(rec) == set(keys) | {"phase_ms"}
            f.write(json.dumps({"event": "metrics", "wall": 1.0 * n,
                                **rec}) + "\n")
    recs = load_metrics(str(path))
    assert [r["step"] for r in recs] == [1, 2, 3]
    assert all(r.get(k) is None for r in recs for k in absent)
    s = summarize_metrics(recs)
    assert s["steps"] == 3 and s["n_blocks_last"] == 64
    assert s["poisson_iters"] == {"mean": 2.0, "max": 3.0}
    assert "phase_ms" not in s
    capsys.readouterr()
    assert post.main(["--metrics", str(path)]) == 0
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed["steps"] == 3 and printed["truncated_records"] == 0


@pytest.mark.slow   # ~17 s; duplicative tier-1 coverage: the frozen key
#                     SET is pinned as a literal tuple in
#                     test_metrics_schema_v15_key_set_pinned and the
#                     uniform producer stream (every record, key-exact)
#                     in test_cli_metrics_stream_and_post_report; the
#                     AMR records drilled here ride the identical
#                     MetricsRecorder.record_step path
def test_metrics_schema_stable_uniform_amr():
    gold = set(METRICS_KEYS)

    # uniform driver path
    sim = _sim()
    rec = MetricsRecorder()
    rec.prime(sim)
    r = rec.record(sim, sim.step_once())
    assert set(r) == gold
    # the dt baseline was primed: the first record carries a real dt
    assert r["dt"] is not None and r["dt"] > 0
    assert r["energy"] > 0 and r["div_linf"] >= 0
    assert r["n_blocks"] is None        # uniform: AMR fields null
    # schema v4 solve-path attribution: the driver's latch string and
    # the cycle count riding the same diag (BiCGSTAB applies the MG
    # preconditioner twice per iteration)
    assert r["poisson_mode"] == "bicgstab+mg"
    assert r["precond_cycles"] == 2 * r["poisson_iters"]
    # schema v6 kernel-tier attribution: the driver's constructor
    # latches ride the same pull (default environment: XLA tier, and
    # prec_mode reports the f64 state dtype of _cfg)
    assert r["kernel_tier"] == "xla"
    assert r["prec_mode"] == "f64"
    # schema v8 BC attribution: the default table's token, and no case
    # tag on an ad-hoc (non-registry) run
    assert r["bc_table"] == "fs,fs,fs,fs"
    assert r["case"] is None

    # forest driver path
    asim = _amr_sim()
    asim.initialize()
    arec = MetricsRecorder()
    arec.prime(asim)
    ar = arec.record(asim, asim.step_once())
    assert set(ar) == gold
    assert ar["n_blocks"] > 0
    assert sum(ar["blocks_per_level"].values()) == ar["n_blocks"]
    assert ar["energy"] > 0
    # forest attribution: default latch, exact first step = two-level
    # coarse operand on, 2 M-applies/iter + the x0 = M(b) application
    assert ar["poisson_mode"] == "bicgstab+jacobi"
    assert ar["precond_cycles"] == 2 * ar["poisson_iters"] + 1

    # record_step without a sim (a caller that holds host scalars
    # only): same key set
    host_diag = {k: r[k] for k in ("umax", "dt_next", "poisson_iters",
                                   "poisson_residual",
                                   "poisson_converged",
                                   "poisson_stalled", "energy",
                                   "div_linf")}
    br = MetricsRecorder().record_step(step=1, t=0.1, dt=0.1,
                                       diag=host_diag, wall_ms=2.0)
    assert set(br) == gold


def test_metrics_forest_fas_mode_strings(monkeypatch):
    """Schema v8 KEY set is frozen, but PR 13 grew the poisson_mode
    VALUE vocabulary: a fas/fas-f-latched forest driver must stamp
    "fas+forest" / "fas-f+forest" on its records (the "+forest" suffix
    keeps the forest FAS hierarchy distinguishable from the uniform
    path's plain "fas"/"fas-f" in merged fleet streams), and the FAS
    full-solver convention precond_cycles == poisson_iters (one cycle
    per outer iteration — no Krylov wrapper doubling) must ride the
    diag unchanged. Recorder-level: the driver-side cycle accounting
    itself is pinned by test_forest_fas_matches_krylov_pressure."""
    from cup2d_tpu.amr import AMRSim
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=2, level_start=1,
                    extent=1.0, dtype="float64")
    for tok, mode in (("fas", "fas+forest"), ("fas-f", "fas-f+forest")):
        monkeypatch.setenv("CUP2D_POIS", tok)
        sim = AMRSim(cfg, shapes=[])
        r = MetricsRecorder().record_step(
            step=1, t=0.1, dt=0.1, sim=sim,
            diag={"poisson_iters": 3, "precond_cycles": 3,
                  "poisson_converged": True})
        assert set(r) == set(METRICS_KEYS)      # no new keys rode in
        assert r["poisson_mode"] == mode
        assert r["precond_cycles"] == r["poisson_iters"] == 3
    # the fft latch keeps its pre-PR-13 string: the vocabulary grew,
    # existing values did not move
    monkeypatch.setenv("CUP2D_POIS", "fft")
    sim = AMRSim(cfg, shapes=[])
    assert sim.poisson_mode == "bicgstab+fft"


def test_metrics_kernel_tier_bc_suffix(monkeypatch):
    """Schema v8 KEY set is frozen, but ISSUE 16 grew the kernel_tier
    VALUE vocabulary: a BC'd sim on the fused tier stamps the literal
    "pallas-fused+bc(<token>)" — captured at DISPATCH via the guard's
    _Pending slot (PR-6 pattern: the tier the step actually RAN with,
    immune to a drain-time latch change) and mirrored by the recorder's
    diag-first pull — alongside the v8 bc_table token it suffixes. The
    default free-slip table keeps the bare PR-9 string (pinned above in
    test_metrics_schema_stable_uniform_amr)."""
    from cup2d_tpu.cases import cavity_table
    from cup2d_tpu.uniform import UniformSim, taylor_green_state
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    cfg = _cfg(dtype="float32", nu=4e-5, max_poisson_iterations=60)
    sim = UniformSim(cfg, level=2, bc=cavity_table(1.0))
    assert sim.kernel_tier == "pallas-fused+bc(ns,ns,ns,ns(1,0))"
    sim.state = taylor_green_state(sim.grid)
    rec = MetricsRecorder()
    rec.prime(sim)
    r = rec.record(sim, sim.step_once(0.25 * sim.grid.h))
    assert r["kernel_tier"] == "pallas-fused+bc(ns,ns,ns,ns(1,0))"
    assert r["prec_mode"] == "f32"
    assert r["bc_table"] == "ns,ns,ns,ns(1,0)"


def test_metrics_jsonl_stream_and_summary(tmp_path):
    sink = EventLog(str(tmp_path / "metrics.jsonl"))
    sim = _sim()
    rec = MetricsRecorder(sink=sink)
    rec.prime(sim)
    for _ in range(3):
        rec.record(sim, sim.step_once(), wall_ms=1.5)
    sink.close()
    recs = load_metrics(str(tmp_path / "metrics.jsonl"))
    ms = [r for r in recs if r.get("event") == "metrics"]
    assert [r["step"] for r in ms] == [1, 2, 3]
    # the stream carries the schema keys plus the EventLog envelope
    assert set(ms[0]) - {"event", "wall"} == set(METRICS_KEYS)
    s = summarize_metrics(recs)
    assert s["steps"] == 3
    assert s["t_final"] == pytest.approx(sim.time)
    assert s["poisson_iters"]["max"] >= s["poisson_iters"]["mean"] > 0
    assert s["energy_last"] > 0
    assert s["wall_ms"]["mean"] == pytest.approx(1.5)


# ---------------------------------------------------------------------------
# physics-invariant watchdog
# ---------------------------------------------------------------------------

def test_watchdog_policy_unit():
    wd = PhysicsWatchdog(window=3, energy_factor=4.0, div_factor=50.0)
    # warm-up: no verdicts until the window is full of good steps
    assert wd.check({"energy": 100.0, "div_linf": 100.0}) is None
    for _ in range(3):
        wd.observe({"umax": 2.0, "energy": 1.0, "div_linf": 0.1})
    assert wd.check({"umax": 2.1, "energy": 1.2,
                     "div_linf": 0.12}) is None
    # umax jump flags first (it is the earliest-armed invariant)
    assert wd.check({"umax": 20.0, "energy": 1.0}) == "invariant_umax"
    # energy jump and collapse both flag
    assert wd.check({"energy": 5.0}) == "invariant_energy"
    assert wd.check({"energy": 0.2}) == "invariant_energy"
    # divergence blow-up flags; inside the bound does not
    assert wd.check({"energy": 1.0, "div_linf": 6.0}) \
        == "invariant_divergence"
    assert wd.check({"energy": 1.0, "div_linf": 4.0}) is None
    # a flagged step must never enter its own baseline: the window
    # still describes the good history
    assert wd.check({"energy": 1.1}) is None
    wd.reset()
    assert wd.check({"energy": 50.0}) is None   # cleared = warm-up again


def test_watchdog_unsettled_signal_stays_dormant():
    """Relative drift bounds on an unsettled invariant are meaningless
    (spin-up from rest legitimately multiplies the energy per step —
    a dt/2 retry measured 8x the window max on the fish case), so an
    invariant whose window is not settled must NOT arm: a full window
    of exponential growth never fires, while the settled umax band
    still catches the same corruption."""
    wd = PhysicsWatchdog(window=4, energy_settle=2.0)
    for k in range(4):
        wd.observe({"energy": 10.0 ** k, "umax": 1.0})
    # energy window spans 1..1000 (ratio 1000 > settle 2): dormant even
    # for a 100x jump...
    assert wd.check({"energy": 1e5}) is None
    # ...but the settled umax band catches the same corrupted step
    assert wd.check({"energy": 1e5, "umax": 10.0}) == "invariant_umax"


def test_watchdog_catches_injected_finite_corruption(tmp_path):
    """faults.scale_vel multiplies the velocity x10 — finite
    everywhere, invisible to the isfinite verdict — and the watchdog
    flags it within its window; the ladder's rewind-retry recovers."""
    sim = _sim()
    log = EventLog(str(tmp_path / "events.jsonl"))
    guard = StepGuard(sim, watchdog=PhysicsWatchdog(window=4),
                      faults=FaultPlan("scale_vel@6"), event_log=log)
    for _ in range(8):
        guard.step()
    with open(tmp_path / "events.jsonl") as f:
        evs = [json.loads(line) for line in f if line.strip()]
    recov = [e for e in evs if e.get("event") == "recovery"]
    assert [e["action"] for e in recov] == ["retry"]
    assert recov[0]["step"] == 6
    assert recov[0]["verdict"].startswith("invariant_")
    assert sim.step_count == 8
    assert np.all(np.isfinite(np.asarray(sim.state.vel)))


def test_fault_plan_scale_vel_parse():
    p = FaultPlan("scale_vel@4*2")
    assert p.vel_scale[4] == [10.0, 2]
    assert bool(p)
    with pytest.raises(ValueError):
        FaultPlan("scale_vel")             # step is required


# ---------------------------------------------------------------------------
# zero-extra-sync contract: metrics-on == metrics-off, bit for bit,
# with EQUAL device_get counts (the PR-2 harness extended to the full
# telemetry stack: recorder + counters + watchdog)
# ---------------------------------------------------------------------------

def test_metrics_on_bit_identical_equal_pulls(tmp_path, monkeypatch):
    traces = {"n": 0}
    orig_impl = Simulation._flow_step_impl

    def counted_impl(self, *a, **k):
        traces["n"] += 1
        return orig_impl(self, *a, **k)

    monkeypatch.setattr(Simulation, "_flow_step_impl", counted_impl)

    def run(telemetry):
        sim = _sim()
        counters = guard = rec = None
        if telemetry:
            counters = HostCounters().install()
            sink = EventLog(str(tmp_path / "metrics.jsonl"))
            rec = MetricsRecorder(sink=sink, counters=counters)
            rec.prime(sim)
            guard = StepGuard(sim, watchdog=PhysicsWatchdog())
        pulls = {"n": 0}
        real_get = jax.device_get

        def counting_get(x):
            pulls["n"] += 1
            return real_get(x)

        t0 = traces["n"]
        try:
            with monkeypatch.context() as m:
                m.setattr(jax, "device_get", counting_get)
                for _ in range(5):
                    if telemetry:
                        rec.record(sim, guard.step())
                    else:
                        sim.step_once()
        finally:
            if counters is not None:
                counters.uninstall()
        return (np.asarray(sim.state.vel), np.asarray(sim.state.pres),
                sim.time, pulls["n"], traces["n"] - t0)

    va, pa, ta, pulls_a, traces_a = run(False)
    vb, pb, tb, pulls_b, traces_b = run(True)
    assert np.array_equal(va, vb)
    assert np.array_equal(pa, pb)
    assert ta == tb
    # the whole telemetry stack rides the step's existing batched pull:
    # no extra device_get, no extra trace of the step function
    assert pulls_b == pulls_a
    assert traces_b == traces_a


@pytest.mark.slow   # ~13 s; duplicative tier-1 coverage: the no-extra-
#                     device_get contract is pinned on the Simulation
#                     family by test_metrics_on_bit_identical_equal_
#                     pulls, and the lagged AMR path's pull accounting
#                     by test_snapshot_ring (device_gets == n,
#                     state_gathers == 0 on every record)
def test_metrics_no_second_pull_on_device_diag(monkeypatch):
    """The obstacle-free AMR step deliberately keeps its diag scalars
    ON DEVICE; the guard's LAGGED verdict pulls them once (batched,
    after the next step's dispatch), and the guard must hand those host
    values to the recorder — metrics-on must not re-pull what the
    verdict already fetched (code review PR 3; lagged since PR 4)."""
    from cup2d_tpu.amr import AMRSim
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=2, level_start=1,
                    extent=1.0, dtype="float64", nu=1e-3,
                    max_poisson_iterations=40)
    def run(metrics):
        rng = np.random.default_rng(0)
        sim = AMRSim(cfg, shapes=[])
        f = sim.forest
        f.fields["vel"] = f.fields["vel"] + jnp.asarray(
            0.1 * rng.standard_normal(f.fields["vel"].shape))
        guard = StepGuard(sim)
        rec = MetricsRecorder(guard=guard) if metrics else None
        pulls = {"n": 0}
        real_get = jax.device_get

        def counting_get(x):
            pulls["n"] += 1
            return real_get(x)

        def record(r):
            if rec is not None and r is not None:
                rec.record_step(step=r["step"], t=r["t"], dt=r["dt"],
                                diag=r, sim=sim)

        with monkeypatch.context() as m:
            m.setattr(jax, "device_get", counting_get)
            for _ in range(3):
                record(guard.step())
            for r in guard.drain():     # the final lagged verdict
                record(r)
        assert sim.step_count == 3
        return pulls["n"]

    assert run(True) == run(False)


# ---------------------------------------------------------------------------
# CI guard: steady-state steps compile NOTHING and pull a bounded count
# ---------------------------------------------------------------------------

def test_steady_state_zero_recompiles_bounded_transfers():
    sim = _sim()
    sim.compute_forces_every = 0
    for _ in range(3):
        sim.step_once()                    # warm every executable
    c = HostCounters().install()
    try:
        n = 4
        for _ in range(n):
            sim.step_once()
    finally:
        c.uninstall()
    # a steady-state step must be a pure cache hit: one XLA compile
    # here means a shape/static-arg leak (the r1 per-count retrace bug
    # class) — and it would cost a full compile per occurrence
    assert c.jit_compiles == 0
    # the hot-path pull discipline: exactly TWO batched device_gets per
    # shaped uniform step (the rasterize scalar sync + the step's one
    # diag/uvw pull); anything above means a new per-step host sync
    # leaked in
    assert c.device_gets == 2 * n


# ---------------------------------------------------------------------------
# windowed device tracing
# ---------------------------------------------------------------------------

def test_trace_window_parse(monkeypatch, tmp_path):
    monkeypatch.delenv("CUP2D_TRACE", raising=False)
    assert TraceWindow.from_env() is None
    monkeypatch.setenv("CUP2D_TRACE", f"2:4:{tmp_path}/tr")
    tw = TraceWindow.from_env()
    assert (tw.start, tw.stop, tw.logdir) == (2, 4, f"{tmp_path}/tr")
    monkeypatch.setenv("CUP2D_TRACE", "7:9")
    assert TraceWindow.from_env().logdir == "trace"
    for bad in ("5", "4:2", "a:b", "-1:3"):
        monkeypatch.setenv("CUP2D_TRACE", bad)
        with pytest.raises(ValueError):
            TraceWindow.from_env()


def test_trace_window_wraps_exact_steps(tmp_path):
    logdir = str(tmp_path / "trace")
    tw = TraceWindow(1, 3, logdir)
    f = jax.jit(lambda x: x * 2.0)
    x = jnp.ones(16)
    seen = []
    for step in range(5):
        tw.maybe_start(step)
        seen.append(tw.active)
        x = f(x)
        tw.maybe_stop(step + 1)
    tw.close()
    # active exactly while stepping steps 1 and 2
    assert seen == [False, True, True, False, False]
    assert tw.done and not tw.active
    # the trace actually materialized (TensorBoard xplane dump)
    assert glob.glob(os.path.join(logdir, "plugins", "profile",
                                  "*", "*")), \
        "trace window left no profile dump"


# ---------------------------------------------------------------------------
# CLI end-to-end (in-process): metrics stream + post --metrics report
# ---------------------------------------------------------------------------

_CLI_BOX = [
    "-bpdx", "1", "-bpdy", "1", "-levelMax", "1", "-levelStart", "0",
    "-Rtol", "2", "-Ctol", "1", "-extent", "1", "-CFL", "0.4",
    "-tend", "1", "-lambda", "1e6", "-nu", "0.001",
    "-poissonTol", "1e-3", "-poissonTolRel", "1e-2",
    "-maxPoissonRestarts", "0", "-maxPoissonIterations", "100",
    "-AdaptSteps", "20", "-tdump", "0", "-dtype", "float64"]


def test_cli_metrics_stream_and_post_report(tmp_path, monkeypatch,
                                            capsys):
    from cup2d_tpu import post
    from cup2d_tpu.__main__ import main

    monkeypatch.delenv("CUP2D_FAULTS", raising=False)
    monkeypatch.delenv("CUP2D_TRACE", raising=False)
    out = tmp_path / "run"
    rc = main(_CLI_BOX + [
        "-level", "3", "-shapes", "angle=0 L=0.25 xpos=0.5 ypos=0.5",
        "-output", str(out), "-maxSteps", "3"])
    assert rc == 0
    recs = load_metrics(str(out / "metrics.jsonl"))
    ms = [r for r in recs if r.get("event") == "metrics"]
    assert [r["step"] for r in ms] == [1, 2, 3]
    assert set(ms[0]) - {"event", "wall"} == set(METRICS_KEYS)
    # per-step counters came through the CLI's HostCounters install
    assert all(r["device_gets"] is not None for r in ms)
    assert ms[-1]["jit_compiles"] == 0     # steady state by step 3
    capsys.readouterr()
    rc = post.main(["--metrics", str(out / "metrics.jsonl")])
    assert rc == 0
    summary = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["steps"] == 3
    assert summary["source"].endswith("metrics.jsonl")


def test_profile_flag_is_gone(tmp_path, monkeypatch, capsys):
    """``-profile`` switched the fencing phase timers on; they went
    (ISSUE 30), so the flag now reads as any flag the parser does not
    know: the run is the plain run, no record carries phase_ms and no
    phase table or throughput line is printed at exit."""
    from cup2d_tpu.__main__ import main

    monkeypatch.delenv("CUP2D_FAULTS", raising=False)
    monkeypatch.delenv("CUP2D_TRACE", raising=False)
    out = tmp_path / "run"
    rc = main(_CLI_BOX + ["-level", "2", "-profile",
                          "-output", str(out), "-maxSteps", "2"])
    assert rc == 0
    ms = [r for r in load_metrics(str(out / "metrics.jsonl"))
          if r.get("event") == "metrics"]
    assert [r["step"] for r in ms] == [1, 2]
    assert all("phase_ms" not in r for r in ms)
    err = capsys.readouterr().err
    assert "ms/call" not in err and "cells_steps_per_sec" not in err
    assert "done at t=" in err
