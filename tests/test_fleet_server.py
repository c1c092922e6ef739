"""Continuous-batching fleet serving (fleet.FleetServer, PR 11):

- Slot invariance: a live member's trajectory AND clock are
  bit-identical regardless of co-member churn — sessions retiring,
  admitting and parking around it change values only in lanes it never
  reads (the select-freeze + frozen-Poisson-lane contracts).
- The masked trace at full occupancy is bit-identical to the unmasked
  historical trace (``where(True, new, old)`` selects new verbatim),
  and a parked slot is FROZEN bit-exact — state, pressure, clock and
  diag lane — however many fused steps its co-members take.
- Admit-from-checkpoint resumes a parked session bit-exact: state,
  clock and the chained per-member dt all round-trip through
  ``io.save_member_checkpoint``, so split serving == uninterrupted.
- The guard's eviction rung: an exhausted per-member ladder EVICTS the
  bad member (slot freed, fleet lives on) while the healthy members'
  trajectories and clocks stay bit-identical to an unfaulted twin.
- Zero steady-state recompiles: once every serving executable is warm
  (masked step, slot scatter, fresh-dt admit, eviction ladder), an
  arbitrary admit/retire/evict churn — a SECOND eviction included —
  compiles nothing (jax.monitoring compile counter flat).
- Shaped membership: per-member frozen obstacles (disk chi + nonzero
  solid velocity) ride the member axis; each member matches the solo
  ``UniformGrid.step(obstacle_terms=True)`` trajectory to <= 1e-12.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup2d_tpu.config import SimConfig
from cup2d_tpu.faults import FaultPlan
from cup2d_tpu.fleet import (FleetRequest, FleetServer, FleetSim,
                             stack_states, taylor_green_fleet)
from cup2d_tpu.profiling import HostCounters
from cup2d_tpu.resilience import EventLog, FleetStepGuard
from cup2d_tpu.uniform import taylor_green_state


# 32^2 grid: the serving contracts are size-independent (tier-1 budget)
LVL = 2


def _cfg(**kw):
    base = dict(bpdx=1, bpdy=1, level_max=1, level_start=0, extent=1.0,
                nu=1e-3, cfl=0.4, lam=1e6, dtype="float64",
                max_poisson_iterations=100)
    base.update(kw)
    return SimConfig(**base)


def _pool(members=3):
    """A production-regime slot pool (exact-mode startup skipped, as in
    tests/test_fleet.py — the serving loop is a steady-state machine)."""
    sim = FleetSim(_cfg(), level=LVL, members=members)
    sim.step_count = 20
    return sim


def _session_state(grid, m):
    """Session m's admission state: the amplitude-laddered Taylor-Green
    vortex (distinct umax -> distinct per-member dt, as in the fleet
    tests — identical sessions would hide cross-lane leaks)."""
    st = taylor_green_state(grid)
    return st._replace(vel=st.vel * (0.8 ** m))


def _events(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


# ---------------------------------------------------------------------------
# slot invariance under churn
# ---------------------------------------------------------------------------

def test_member_trajectory_bit_identical_under_co_member_churn():
    """THE serving contract: client "keep"'s trajectory through n
    serving cycles is bit-identical whether it runs alone in the pool
    or surrounded by a full churn of co-sessions (two retirement waves
    + refills from the queue). Its lane's arithmetic is elementwise
    independent, its Poisson lane select-frozen once converged — dead
    or alive co-lanes change nothing it reads, clocks included."""
    n = 8

    def run(churn):
        sim = _pool(3)
        server = FleetServer(sim)
        g = sim.grid

        def req(cid, m, t_end=np.inf):
            return FleetRequest(client_id=cid,
                                state=_session_state(g, m),
                                t_end=float(t_end))

        # short horizons measured in the session's OWN first dt, so the
        # retirement points are robust to the slow CFL drift
        dt1 = float(sim._member_dt(_session_state(g, 1).vel))
        dt2 = float(sim._member_dt(_session_state(g, 2).vel))
        server.submit(req("keep", 0))
        if churn:
            server.submit(req("s1", 1, 1.9 * dt1))   # retires ~cycle 2
            server.submit(req("s2", 2, 2.9 * dt2))   # retires ~cycle 3
        for k in range(n):
            if churn and k == 4:
                # second wave through the freed slots
                server.submit(req("s3", 1, 1.9 * dt1))
                server.submit(req("s4", 2, 2.9 * dt2))
            assert server.step() is not None
        return (np.asarray(sim.member_state(0).vel),
                np.asarray(sim.member_state(0).pres),
                float(sim.times[0]), server)

    v_a, p_a, t_a, srv_a = run(False)
    v_b, p_b, t_b, srv_b = run(True)
    # the churn was real: both waves retired, the pool refilled
    assert srv_a.retired == 0 and srv_a.admitted == 1
    assert srv_b.admitted == 5 and srv_b.retired >= 3
    assert srv_b.client_of(0) == "keep"
    assert np.array_equal(v_a, v_b)
    assert np.array_equal(p_a, p_b)
    assert t_a == t_b


def test_all_true_mask_bit_identical_and_parked_slot_frozen():
    """Two halves of the mask contract. (1) The masked trace at full
    occupancy is bit-identical to the historical unmasked trace —
    where(True, new, old) selects new verbatim, so flipping a fixed-B
    fleet to serving mode costs no trajectory change. (2) A parked
    slot is frozen BIT-EXACT: state, pressure and clock unchanged over
    further fused steps, its diag lane inert (zero dt/div, converged
    at iteration zero)."""
    n = 3
    plain = _pool(3)
    plain.state = taylor_green_fleet(plain.grid, 3)
    masked = _pool(3)
    masked.state = taylor_green_fleet(masked.grid, 3)
    masked.set_active(np.ones(3, dtype=bool))
    dp = dm = None
    for _ in range(n):
        dp = plain.step_once()
        dm = masked.step_once()
    assert np.array_equal(np.asarray(plain.state.vel),
                          np.asarray(masked.state.vel))
    assert np.array_equal(np.asarray(plain.state.pres),
                          np.asarray(masked.state.pres))
    assert np.array_equal(plain.times, masked.times)
    assert np.array_equal(np.asarray(dp["poisson_iters"]),
                          np.asarray(dm["poisson_iters"]))

    # park slot 2 and keep stepping the others
    v2 = np.asarray(masked.member_state(2).vel)
    p2 = np.asarray(masked.member_state(2).pres)
    t2 = float(masked.times[2])
    v0 = np.asarray(masked.member_state(0).vel)
    masked.set_active(np.array([True, True, False]))
    diag = None
    for _ in range(3):
        diag = masked.step_once()
    assert np.array_equal(np.asarray(masked.member_state(2).vel), v2)
    assert np.array_equal(np.asarray(masked.member_state(2).pres), p2)
    assert float(masked.times[2]) == t2
    # the live members genuinely advanced
    assert not np.array_equal(np.asarray(masked.member_state(0).vel), v0)
    # the dead lane's diag is inert: it costs the solver nothing and
    # never pollutes the fold aggregates
    assert int(np.asarray(diag["poisson_iters"])[2]) == 0
    assert bool(np.asarray(diag["poisson_converged"])[2])
    assert float(np.asarray(diag["dt"])[2]) == 0.0
    assert float(np.asarray(diag["div_linf"])[2]) == 0.0
    # fleet time reads min over LIVE slots only
    assert masked.time == min(float(masked.times[0]),
                              float(masked.times[1]))


# ---------------------------------------------------------------------------
# admit-from-checkpoint: bit-exact session resume
# ---------------------------------------------------------------------------

def test_admit_from_checkpoint_bit_exact_resume(tmp_path):
    """A session parked mid-flight (retire -> member checkpoint) and
    re-admitted from that checkpoint lands EXACTLY where the
    uninterrupted run lands: the state, the clock and the chained
    per-member dt all round-trip losslessly, so the split trajectory's
    dt sequence is the uninterrupted one."""
    from cup2d_tpu.io import load_member_checkpoint

    probe = _pool(2)
    dt0 = float(probe._member_dt(
        _session_state(probe.grid, 0).vel))
    T = 4.6 * dt0        # ~5 steps total
    t_mid = 2.6 * dt0    # parked after ~3 steps

    def serve(sdir, horizons):
        sim = _pool(2)
        server = FleetServer(sim, session_dir=str(sdir))
        ckpt, times = None, []
        for t_end in horizons:
            server.submit(FleetRequest(
                client_id="X", checkpoint=ckpt,
                state=None if ckpt else _session_state(sim.grid, 0),
                t_end=t_end))
            assert server.drain() > 0
            ckpt = os.path.join(str(sdir), "X")
            # the leg's parked clock, read between legs: proves the
            # split run really parked mid-flight before resuming
            times.append(load_member_checkpoint(ckpt, sim.grid)[1]["time"])
        return sim, ckpt, times

    sim_ref, ck_ref, t_ref = serve(tmp_path / "ref", [T])
    sim_spl, ck_spl, t_spl = serve(tmp_path / "split", [t_mid, T])
    assert t_mid <= t_spl[0] < T           # a genuine mid-flight park

    st_r, meta_r = load_member_checkpoint(ck_ref, sim_ref.grid)
    st_s, meta_s = load_member_checkpoint(ck_spl, sim_spl.grid)
    assert meta_r["time"] >= T and meta_s["time"] >= T
    for name, a, b in zip(st_r._fields, st_r, st_s):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    assert meta_r["time"] == meta_s["time"]
    assert meta_r["next_dt"] == meta_s["next_dt"]


# ---------------------------------------------------------------------------
# the eviction rung: bad member out, fleet lives, healthy members pinned
# ---------------------------------------------------------------------------

def test_eviction_pins_healthy_members_bit_identical(tmp_path):
    """A member whose per-member ladder exhausts (nan_vel re-poisoned
    through retry AND escalate: *3) is EVICTED — slot freed and
    zeroed, fleet stepping on — instead of the fleet dying. The
    surviving members' trajectories and clocks stay bit-identical to
    an unfaulted twin, through the recovery AND the post-eviction
    masked steps."""
    n = 7

    def run(spec):
        sim = _pool(3)
        log = EventLog(str(tmp_path / f"ev_{bool(spec)}.jsonl"))
        guard = FleetStepGuard(
            sim, event_log=log,
            faults=FaultPlan(spec) if spec else None)
        server = FleetServer(sim, guard=guard, event_log=log)
        for m in range(3):
            server.submit(FleetRequest(
                client_id=f"c{m}", state=_session_state(sim.grid, m)))
        for _ in range(n):
            assert server.step() is not None
        log.close()
        return sim, server

    sim_t, srv_t = run(None)
    sim_f, srv_f = run("nan_vel@24*3")     # faults.py poisons member 0

    assert srv_t.evicted == 0
    assert srv_f.evicted == 1 and srv_f.guard.evictions == 1
    assert not srv_f.active[0] and srv_f.client_of(0) is None
    assert srv_f.active[1] and srv_f.active[2]
    vt = np.asarray(sim_t.state.vel)
    vf = np.asarray(sim_f.state.vel)
    for m in (1, 2):                       # healthy members NEVER rewind
        assert np.array_equal(vt[m], vf[m]), m
        assert sim_t.times[m] == sim_f.times[m], m
    # the evicted slot was zeroed (a NaN corpse would poison the
    # masked step's member_health diag rows) and the shared counter
    # kept advancing: the fleet survived the eviction
    assert np.all(np.asarray(sim_f.member_state(0).vel) == 0.0)
    assert sim_f.step_count == sim_t.step_count == 20 + n
    evs = _events(tmp_path / "ev_True.jsonl")
    aborted = [e for e in evs if e.get("event") == "member_aborted"]
    evicted = [e for e in evs if e.get("event") == "member_evict"]
    assert len(aborted) == 1 and aborted[0]["member"] == 0
    assert aborted[0]["action"] == "evict"
    assert len(evicted) == 1 and evicted[0]["client"] == "c0"
    # the ladder was climbed before giving up: retry then escalate
    recs = [e for e in evs if e.get("event") == "recovery"]
    assert [e["action"] for e in recs] == ["retry", "escalate"]


# ---------------------------------------------------------------------------
# zero steady-state recompiles
# ---------------------------------------------------------------------------

def test_zero_recompile_steady_state_churn(tmp_path):
    """The perf contract the whole slot-pool design exists for: once
    the serving executables are warm (masked fused step, slot scatter
    with the device-int32 index, fresh-CFL-dt admit, the eviction
    ladder's solo retry/escalate pair), an arbitrary admit/retire/
    evict churn — including a SECOND eviction — compiles NOTHING. The
    jax.monitoring compile counter is the measurement, as in the
    telemetry steady-state test."""
    sim = _pool(3)
    log = EventLog(str(tmp_path / "events.jsonl"))
    guard = FleetStepGuard(
        sim, event_log=log,
        faults=FaultPlan("nan_vel@26*3,nan_vel@33*3"))
    server = FleetServer(sim, guard=guard, event_log=log)
    g = sim.grid
    n_req = 0

    def submit(horizon_steps):
        nonlocal n_req
        st = _session_state(g, n_req % 3)
        dt0 = float(sim._member_dt(st.vel))
        server.submit(FleetRequest(
            client_id=f"c{n_req:03d}", state=st,
            t_end=(horizon_steps - 0.1) * dt0))
        n_req += 1

    # warm phase: full pool, short-horizon retires + refills, then the
    # first ladder exhaustion (fault at shared step 26) — every
    # executable the churn below touches compiles HERE
    for _ in range(3):
        submit(2)
    for _ in range(9):                     # steps 20..28, evict at 26
        submit(2)
        server.step()
    assert server.evicted == 1             # warm ladder really ran

    # measured churn: more sessions, retires, admits and the SECOND
    # eviction (step 33) — with zero compiles
    c = HostCounters().install()
    try:
        retired0, admitted0 = server.retired, server.admitted
        for _ in range(8):                 # steps 29..36, evict at 33
            submit(3)
            server.step()
    finally:
        c.uninstall()
    snap = c.snapshot()
    assert server.evicted == 2 and guard.evictions == 2
    assert server.retired > retired0       # churn happened in-window
    assert server.admitted > admitted0
    assert snap["jit_compiles"] == 0, snap
    log.close()


def test_served_pool_fills_latency_histograms():
    """A real served pool under churn fills all three pool-wide
    latency distributions (tests/test_tracing.py holds the collector's
    arithmetic alone): five sessions on three slots, so two requests
    wait in the queue for a retirement before they are seated."""
    from cup2d_tpu.tracing import ServingLatency

    sim = _pool(3)
    server = FleetServer(sim, latency=ServingLatency())
    for n in range(5):
        st = _session_state(sim.grid, n % 3)
        server.submit(FleetRequest(
            client_id=f"c{n:03d}", state=st,
            t_end=1.9 * float(sim._member_dt(st.vel))))
    steps = 0
    while server.retired < 5 and steps < 12:
        server.step()
        steps += 1
    assert server.admitted == server.retired == 5 and server.evicted == 0
    pool = server.latency.report()["pool"]
    assert pool["queue_wait"]["count"] == 5, pool
    assert pool["admit_to_first_step"]["count"] == 5, pool
    # every fused step is observed once for each client it carried
    assert pool["step"]["count"] >= 10, pool
    for kind in ServingLatency.KINDS:
        assert pool[kind]["p99_ms"] >= pool[kind]["p50_ms"] > 0, pool


def test_zero_recompile_bc_pallas_pool_churn(tmp_path, monkeypatch):
    """ISSUE-16 acceptance: the zero-recompile contract extends to a
    BC'd fused-kernel pool. All BC coefficients are trace-time
    constants (one executable per BCTable token) and the kernel_tier
    suffix lives on the host-side property only — so a cavity-table
    pool on the pallas tier (f32 state, the tier's dtype contract)
    serves a measured admit/retire churn window with jit_compiles ==
    0, exactly like the XLA pool above."""
    from cup2d_tpu.cases import cavity_table
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    sim = FleetSim(_cfg(dtype="float32", nu=4e-5), level=LVL,
                   members=3, bc=cavity_table(1.0))
    assert sim.kernel_tier == "pallas-fused+bc(ns,ns,ns,ns(1,0))"
    sim.step_count = 20
    log = EventLog(str(tmp_path / "events.jsonl"))
    server = FleetServer(sim, event_log=log)
    g = sim.grid
    n_req = 0

    def submit(horizon_steps):
        nonlocal n_req
        st = _session_state(g, n_req % 3)
        dt0 = float(sim._member_dt(st.vel))
        server.submit(FleetRequest(
            client_id=f"b{n_req:03d}", state=st,
            t_end=(horizon_steps - 0.1) * dt0))
        n_req += 1

    # warm phase: fill, retire, refill — every executable the measured
    # window touches compiles here
    for _ in range(3):
        submit(2)
    for _ in range(5):
        submit(2)
        server.step()

    c = HostCounters().install()
    try:
        retired0, admitted0 = server.retired, server.admitted
        for _ in range(6):
            submit(3)
            server.step()
    finally:
        c.uninstall()
    snap = c.snapshot()
    assert server.retired > retired0       # churn happened in-window
    assert server.admitted > admitted0
    assert snap["jit_compiles"] == 0, snap
    log.close()


# ---------------------------------------------------------------------------
# shaped membership: per-member frozen obstacles
# ---------------------------------------------------------------------------

def _shaped_state(grid, m):
    """Member m's shaped session: amplitude-laddered Taylor-Green flow
    around a frozen disk (chi) translating at a nonzero solid velocity
    (us), with a small divergence-bearing deformation field (udef) so
    the chi*div(u_def) RHS term is exercised for real."""
    g = grid
    xs = (np.arange(g.nx) + 0.5) * g.h
    ys = (np.arange(g.ny) + 0.5) * g.h
    X, Y = np.meshgrid(xs, ys)
    cx = 0.35 + 0.1 * m                    # per-member disk position
    chi = (((X - cx) ** 2 + (Y - 0.5) ** 2) < 0.15 ** 2)
    chi = chi.astype(np.float64)
    us = np.stack([0.2 * chi, 0.05 * chi])
    udef = 0.02 * np.stack([chi * np.sin(2 * np.pi * Y),
                            chi * np.cos(2 * np.pi * X)])
    base = taylor_green_state(grid)
    return base._replace(
        vel=base.vel * (0.8 ** m),
        chi=jnp.asarray(chi, g.dtype),
        us=jnp.asarray(us, g.dtype),
        udef=jnp.asarray(udef, g.dtype))


def test_shaped_fleet_members_match_solo_obstacle_step():
    """``FleetSim(shaped=True)``: per-member obstacle fields ride the
    member axis as frozen solids — Brinkman penalization and the
    chi-weighted divergence RHS batched over B. Each member matches
    the solo ``UniformGrid.step(obstacle_terms=True)`` trajectory to
    <= 1e-12 (the documented MG FMA-contraction bound), per-member dt
    chains included."""
    B, n = 2, 3
    sim = FleetSim(_cfg(), level=LVL, members=B, shaped=True)
    sim.step_count = 20
    g = sim.grid
    sim.state = stack_states([_shaped_state(g, m) for m in range(B)])
    diag = None
    for _ in range(n):
        diag = sim.step_once()

    solo_step = jax.jit(g.step,
                        static_argnames=("exact_poisson",
                                         "obstacle_terms"))
    for m in range(B):
        st = _shaped_state(g, m)
        dt = float(sim._member_dt(st.vel))
        t = 0.0
        for _ in range(n):
            st, d = solo_step(st, jnp.asarray(dt, g.dtype),
                              exact_poisson=False, obstacle_terms=True)
            t += dt
            dt = float(d["dt_next"])
        vs = np.asarray(st.vel)
        vf = np.asarray(sim.state.vel)[m]
        scale = max(1.0, np.abs(vs).max())
        assert np.abs(vs - vf).max() <= 1e-12 * scale, m
        assert np.abs(np.asarray(st.pres)
                      - np.asarray(sim.state.pres)[m]).max() \
            <= 1e-12, m
        assert abs(sim.times[m] - t) <= 1e-12, m
        # penalization really bit: the solid region moves with us
        assert float(np.asarray(diag["umax"])[m]) > 0
    # the disk broke the symmetry: members' solves differ
    assert int(np.asarray(diag["poisson_iters"])[0]) >= 1


# -- a -case pool serves the case's own states (PR 21) -----------------

from cup2d_tpu import cases  # noqa: E402
from cup2d_tpu.__main__ import main  # noqa: E402

FLEET_CASES = [c.name for c in cases.CASES if c.fleet_ok]


@pytest.mark.parametrize("name", FLEET_CASES)
def test_initial_states_are_what_the_builder_installs(name):
    """cases.initial_states == the state build(members=n) starts from,
    leaf for leaf — the server's sessions and a plain fleet run of the
    same case begin identically."""
    sim = cases.make_sim(name, level=2, members=3)
    ens = cases.initial_states(name, sim.grid, 3)
    for got, want in zip(ens, sim.state):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_case_pool_serves_the_cases_own_states(tmp_path, monkeypatch):
    """``-case turb2d -fleet 2 -serve 3``: every submitted session
    starts from the case's state for its index (seed + i — three
    different flows), not from the plain-flag Taylor-Green ladder,
    which is discontinuous across a periodic wrap."""
    seen = []
    submit = FleetServer.submit

    def spy(self, req):
        seen.append(req)
        return submit(self, req)

    monkeypatch.setattr(FleetServer, "submit", spy)
    rc = main(["-case", "turb2d", "-level", "2", "-fleet", "2",
               "-serve", "3", "-tend", "0.01", "-maxSteps", "3",
               "-output", str(tmp_path)])
    assert rc == 0
    assert [r.client_id for r in seen] == ["s0000", "s0001", "s0002"]
    grid = cases.make_sim("turb2d", level=2).grid
    for i, req in enumerate(seen):
        want = cases.turb2d_vel(grid, i).astype(grid.dtype)
        np.testing.assert_array_equal(np.asarray(req.state.vel), want)
    assert not np.array_equal(np.asarray(seen[0].state.vel),
                              np.asarray(seen[1].state.vel))
