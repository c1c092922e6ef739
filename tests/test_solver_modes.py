"""Solve-path latch tests (PR 6): the CUP2D_POIS=fas FAS-multigrid
full solver on the uniform/fleet drivers, the CUP2D_POIS=fft forest-FFT
two-grid production preconditioner, latch validation, and the
FAS-vs-Krylov pressure agreement the acceptance pins.

Expensive developed-regime A/B probes live in the slow tier
(per-test justifications below); this module's tier-1 half runs small
grids only.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup2d_tpu.config import SimConfig


def _cfg(**kw):
    base = dict(bpdx=1, bpdy=1, level_max=1, level_start=0, extent=1.0,
                nu=1e-3, cfl=0.4, dtype="float64",
                max_poisson_iterations=200)
    base.update(kw)
    return SimConfig(**base)


# ---------------------------------------------------------------------------
# latch validation
# ---------------------------------------------------------------------------

def test_uniform_latch_rejects_typo(monkeypatch):
    from cup2d_tpu.uniform import UniformGrid
    monkeypatch.setenv("CUP2D_POIS", "fass")
    with pytest.raises(ValueError, match="CUP2D_POIS"):
        UniformGrid(_cfg(), level=3)


def test_forest_latch_accepts_fas_rejects_unknown(monkeypatch):
    """PR 13 grew the forest latch: 'fas'/'fas-f' now select the
    forest-native FAS full solver (they were uniform-only refusals
    before), while a genuinely unknown token must still fail loudly at
    construction — never silently run the default on one A/B arm."""
    from cup2d_tpu.amr import AMRSim
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=2, level_start=1,
                    extent=1.0, dtype="float64")
    for tok, mode in (("fas", "fas+forest"), ("fas-f", "fas-f+forest")):
        monkeypatch.setenv("CUP2D_POIS", tok)
        sim = AMRSim(cfg, shapes=[])
        assert sim._pois_mode == tok
        assert sim.poisson_mode == mode
    monkeypatch.setenv("CUP2D_POIS", "fasx")
    with pytest.raises(ValueError, match="CUP2D_POIS"):
        AMRSim(cfg, shapes=[])


def test_twolevel_latch_accepts_mg2(monkeypatch):
    from cup2d_tpu.amr import AMRSim
    monkeypatch.setenv("CUP2D_TWOLEVEL", "mg2")
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=2, level_start=1,
                    extent=1.0, dtype="float64")
    sim = AMRSim(cfg, shapes=[])
    assert sim._twolevel_form == "mg2"


# ---------------------------------------------------------------------------
# FAS on the uniform driver: converged pressure matches Krylov
# ---------------------------------------------------------------------------

def _tg_sim(monkeypatch, mode):
    from cup2d_tpu.uniform import UniformSim, taylor_green_state
    if mode:
        monkeypatch.setenv("CUP2D_POIS", mode)
    else:
        monkeypatch.delenv("CUP2D_POIS", raising=False)
    sim = UniformSim(_cfg(), level=3)   # 64^2
    sim.state = taylor_green_state(sim.grid)
    sim.step_count = 20                 # production regime
    return sim


def test_fas_matches_krylov_pressure(monkeypatch):
    """Acceptance pin: the FAS path's converged pressure/velocity
    match the Krylov path's on the Taylor-Green case to the documented
    tolerance — both solve to the same Linf criterion, so trajectories
    agree to the solver-tolerance band (the two paths' error lives in
    modes whose residual is below target; measured headroom ~10x)."""
    a = _tg_sim(monkeypatch, None)
    b = _tg_sim(monkeypatch, "fas")
    assert a.poisson_mode == "bicgstab+mg"
    assert b.poisson_mode == "fas"
    for _ in range(4):
        da = a.step_once()
        db = b.step_once()
    assert bool(db["poisson_converged"])
    # cycle-count accounting: FAS iters ARE preconditioner cycles
    assert int(db["precond_cycles"]) == int(db["poisson_iters"])
    # documented tolerance: production poisson_tol=1e-3 (undivided
    # Linf); pressure agreement to ~tol, velocity tighter (the
    # correction applies grad dp scaled by dt/h)
    dp = float(jnp.max(jnp.abs(a.state.pres - b.state.pres)))
    dv = float(jnp.max(jnp.abs(a.state.vel - b.state.vel)))
    assert dp < 1e-3, dp
    assert dv < 1e-4, dv


def test_fleet_fas_latch_wiring(monkeypatch):
    """Cheap tier-1 wiring assert: FleetSim under CUP2D_POIS=fas
    reads the GRID's latch (fleet.py stays env-read-free) and routes
    production solves to the member-batched mg_solve branch. The
    member-vs-solo trajectory drill runs in the slow tier below; the
    freeze contract itself is tier-1 at the solver level
    (test_poisson.py::test_mg_solve_member_freeze_is_exact)."""
    from cup2d_tpu.fleet import FleetSim
    monkeypatch.setenv("CUP2D_POIS", "fas")
    fleet = FleetSim(_cfg(), level=3, members=2)
    assert fleet.poisson_mode == "fas"
    assert fleet.grid.solver_mode == "fas"


@pytest.mark.slow   # ~8 s — duplicative composition: the converged-
#                     member freeze is tier-1 at the solver level
#                     (test_mg_solve_member_freeze_is_exact), the
#                     member-vs-solo ≤1e-12 contract is tier-1 for the
#                     Krylov path (test_fleet.py), and the fas branch
#                     wiring is tier-1 via the latch assert above;
#                     this drills the composition end-to-end.
def test_fleet_fas_members_match_solo(monkeypatch):
    """The fleet fas path (member-batched mg_solve): B=2 members match
    their solo fas runs to the documented fleet deviation bound, with
    identical per-member cycle counts."""
    from cup2d_tpu.fleet import FleetSim, taylor_green_fleet
    monkeypatch.setenv("CUP2D_POIS", "fas")
    cfg = _cfg()
    fleet = FleetSim(cfg, level=3, members=2)   # 64^2
    fleet.state = taylor_green_fleet(fleet.grid, 2)
    fleet.step_count = 20
    solos = []
    for m in range(2):
        from cup2d_tpu.uniform import UniformSim, taylor_green_state
        s = UniformSim(cfg, level=3)
        st = taylor_green_state(s.grid)
        s.state = st._replace(vel=st.vel * (0.8 ** m))
        s.step_count = 20
        solos.append(s)
    for _ in range(3):
        df = fleet.step_once()
        ds = [s.step_once() for s in solos]
    assert fleet.poisson_mode == "fas"
    for m in range(2):
        dv = float(jnp.max(jnp.abs(
            fleet.state.vel[m] - solos[m].state.vel)))
        assert dv <= 1e-12, (m, dv)
        assert int(df["poisson_iters"][m]) == int(ds[m]["poisson_iters"])
        assert int(df["precond_cycles"][m]) == \
            int(ds[m]["precond_cycles"])


def test_sharded_fas_attach_mesh_wiring(monkeypatch):
    """ShardedUniformSim under CUP2D_POIS=fas rebuilds the MG
    hierarchy mesh-aware in __init__ (UniformGrid.attach_mesh): the
    compiled step then captures the overlapped smoother. Cheap wiring
    assert — the overlapped solve's NUMERICS are tier-1-pinned at the
    solver level (test_poisson: overlap sweeps == laplacian5_neumann,
    sharded mg_solve == meshless); the full sharded trajectory runs in
    the slow tier below."""
    from cup2d_tpu.parallel.mesh import ShardedUniformSim, make_mesh

    monkeypatch.setenv("CUP2D_POIS", "fas")
    cfg = _cfg(bpdx=2, bpdy=1, extent=2.0)
    mesh = make_mesh(8)
    sh = ShardedUniformSim(cfg, mesh, level=3)
    assert sh.grid.solver_mode == "fas"
    assert sh.grid.mg.overlap_levels > 0    # the overlapped smoother
    assert sh.grid.mg.mesh is mesh
    # the Krylov default must NOT swap hierarchies (its GSPMD
    # sharded==single equality is pinned elsewhere)
    monkeypatch.delenv("CUP2D_POIS")
    sh2 = ShardedUniformSim(cfg, mesh, level=3)
    assert sh2.grid.mg.overlap_levels == 0


@pytest.mark.slow   # ~50 s (sharded jit compiles dominate) —
#                     end-to-end confirmation of the wiring test
#                     above; the overlapped smoother's numerics are
#                     tier-1 via the solver-level equivalences in
#                     test_poisson.py
def test_sharded_fas_matches_single_device(monkeypatch):
    """End-to-end sharded FAS driver: ShardedUniformSim under
    CUP2D_POIS=fas rebuilds the MG hierarchy mesh-aware
    (UniformGrid.attach_mesh -> overlap_jacobi_sweeps at the finest
    level) and its trajectory matches the single-device FAS run to the
    sharded-equality bound — the attach_mesh wiring itself, not just
    the solver-level pieces test_poisson pins."""
    from cup2d_tpu.parallel.mesh import ShardedUniformSim, make_mesh
    from cup2d_tpu.uniform import UniformSim, taylor_green_state

    monkeypatch.setenv("CUP2D_POIS", "fas")
    cfg = _cfg(bpdx=2, bpdy=1, extent=2.0)
    ref = UniformSim(cfg, level=3)          # 128x64; Nx=128 / 8 devs
    ref.state = taylor_green_state(ref.grid)
    ref.step_count = 20
    mesh = make_mesh(8)
    sh = ShardedUniformSim(cfg, mesh, level=3)
    sh.set_state(taylor_green_state(sh.grid))
    sh.step_count = 20
    assert sh.grid.solver_mode == "fas"
    assert sh.grid.mg.overlap_levels > 0    # the overlapped smoother
    for _ in range(3):
        ref.advance(1)
        sh.advance(1)
    assert len(sh.state.vel.sharding.device_set) == 8
    dv = np.max(np.abs(np.asarray(ref.state.vel)
                       - np.asarray(sh.state.vel)))
    assert dv < 1e-12, dv


# ---------------------------------------------------------------------------
# forest-FFT production preconditioner (CUP2D_POIS=fft)
# ---------------------------------------------------------------------------

def test_fft_mode_cuts_cold_production_iters(monkeypatch):
    """The tentpole's acceptance shape at tier-1 scale: on a 256-block
    uniform-level forest with a cold multi-scale RHS, the always-on
    fft two-grid path converges the first production solve in <= half
    the block-Jacobi default's iterations at the same tolerance
    criterion. (The developed-regime 1e4-block record is
    validation/poisson_ab_r6.json; iteration counts are
    platform-independent.)"""
    from validation.poisson_ab import build_forest_sim

    monkeypatch.delenv("CUP2D_POIS", raising=False)
    a = build_forest_sim(bpd=4, level_start=2)
    a._refresh()
    monkeypatch.setenv("CUP2D_POIS", "fft")
    b = build_forest_sim(bpd=4, level_start=2)
    b._refresh()
    assert b.poisson_mode == "bicgstab+fft"
    da = a.step_once()
    db = b.step_once()
    assert bool(da["poisson_converged"]) and bool(db["poisson_converged"])
    ia, ib = int(da["poisson_iters"]), int(db["poisson_iters"])
    assert ia > 2, f"default arm trivially easy (iters={ia})"
    assert ib <= max(1, ia // 2), (ia, ib)
    # cycle accounting: 2 two-grid cycles per Krylov iteration
    assert int(db["precond_cycles"]) == 2 * ib
    # the default arm never engaged the correction (sub-trigger)
    assert int(da["precond_cycles"]) == 0
    assert a.poisson_mode == "bicgstab+jacobi"


@pytest.mark.slow   # ~2-4 min: the round-6 1e4-block probe
#                     itself (10.5k blocks over levels 6-8 — the
#                     synthetic builder STARTS at 8,192 level-6
#                     blocks, so the target must exceed that for the
#                     forest to actually refine into the multi-level
#                     regime where the base-level correction is
#                     genuinely approximate) — duplicative coverage
#                     of the tier-1 256-block A/B above, pinning the
#                     acceptance numbers of validation/poisson_ab_r6.json
#                     (additive 10/9/8 -> mg2 4/4/4 iters/step).
def test_fft_mode_multilevel_regime_iters(monkeypatch):
    from validation.poisson_ab import run_path

    monkeypatch.delenv("CUP2D_POIS", raising=False)
    monkeypatch.delenv("CUP2D_TWOLEVEL", raising=False)
    add = run_path("additive", bpd=0, steps=2, synthetic=10000,
                   levelmax=8)
    mg2 = run_path("mg2", bpd=0, steps=2, synthetic=10000, levelmax=8)
    assert mg2["n_blocks"] > 8192          # really multi-level
    assert all(add["converged"]) and all(mg2["converged"])
    assert sum(mg2["iters"]) <= sum(add["iters"]), (add, mg2)
    assert max(mg2["iters"]) <= 4, mg2


# ---------------------------------------------------------------------------
# forest-native FAS full solver (CUP2D_POIS=fas|fas-f, PR 13)
# ---------------------------------------------------------------------------

def test_forest_fas_matches_krylov_pressure():
    """Acceptance pin at tier-1 scale: on a genuinely MULTI-LEVEL
    forest (vortex-tagged, levels straddling the coarse base level c),
    the forest-FAS full solve converges in no more cycles than the
    mg2-Krylov arm takes iterations, and its pressure/velocity match
    that arm's to the solve criterion — both paths solve the identical
    composite operator to the same Linf target (pinned TIGHT here so
    the sub-tolerance mode band is small against the O(10) pressure
    scale). Cycle accounting rides along: FAS iters ARE the cycles."""
    from validation.poisson_ab import build_multilevel_sim

    sa = build_multilevel_sim(tol=1e-7, tol_rel=1e-7)
    sa._refresh()
    sa._pois_mode = "fft"            # the mg2-Krylov reference arm
    sa._coarse_on = True
    sb = build_multilevel_sim(tol=1e-7, tol_rel=1e-7)
    sb._refresh()
    sb._pois_mode = "fas"
    sb._coarse_on = True
    assert sa.poisson_mode == "bicgstab+fft"
    assert sb.poisson_mode == "fas+forest"
    for s in (sa, sb):
        s._last_iters = 0
        s._last_iters_dev = None
    da = sa.step_once(1e-3)
    db = sb.step_once(1e-3)
    assert bool(da["poisson_converged"]) and bool(db["poisson_converged"])
    # the full-solver cycle train beats the Krylov iteration count at
    # the same (deep) target — the ISSUE-13 acceptance shape; the
    # 1e4-block record is the slow drill below + poisson_ab_r10.json
    assert int(db["poisson_iters"]) <= int(da["poisson_iters"]), (da, db)
    assert int(db["precond_cycles"]) == int(db["poisson_iters"])
    va = sa._ordered_state()
    vb = sb._ordered_state()
    dp = float(jnp.max(jnp.abs(va["pres"] - vb["pres"])))
    dv = float(jnp.max(jnp.abs(va["vel"] - vb["vel"])))
    pscale = float(jnp.max(jnp.abs(va["pres"])))
    # both solved to 1e-7 undivided Linf; the pressure gap is the
    # sub-tolerance band amplified by A^-1 (O(N^2) in undivided
    # units), so the honest bound is RELATIVE to the O(100) field
    # scale — measured 2.7e-4 relative, ~7x headroom here; velocity
    # is tighter by dt/h (measured 2.5e-8 absolute)
    assert dp < 2e-3 * pscale, (dp, pscale)
    assert dv < 1e-6, dv


@pytest.mark.slow   # ~4-6 min: the ISSUE-13 acceptance drill at the
#                     1e4-block probe itself (10.5k blocks,
#                     levels 6-8 — a multi-RUNG window ladder, the
#                     regime that exposed the Dirichlet-ghost
#                     instability) — duplicative of the tier-1
#                     multi-level A/B above except for the recorded
#                     acceptance numbers (fas <= mg2's 4 iters/step,
#                     validation/poisson_ab_r10.json)
def test_forest_fas_multilevel_regime_iters(monkeypatch):
    from validation.poisson_ab import run_path

    monkeypatch.delenv("CUP2D_POIS", raising=False)
    monkeypatch.delenv("CUP2D_TWOLEVEL", raising=False)
    mg2 = run_path("mg2", bpd=0, steps=2, synthetic=10000, levelmax=8)
    fas = run_path("fas", bpd=0, steps=2, synthetic=10000, levelmax=8)
    assert fas["n_blocks"] > 8192          # really multi-level
    assert all(mg2["converged"]) and all(fas["converged"])
    # acceptance: FAS cycles per step <= the mg2-Krylov iteration
    # count per step (each cycle costs ~half an mg2-preconditioned
    # Krylov iteration: 3 A-applies + 2 GEMMs vs 6 A + 6 GEMM + 2 DCT)
    assert max(fas["iters"]) <= max(mg2["iters"]), (mg2, fas)
    assert max(mg2["iters"]) <= 4, mg2


# ---------------------------------------------------------------------------
# lagged-verdict trigger freshness (the hysteresis fix)
# ---------------------------------------------------------------------------

def test_lagged_trigger_engages_without_extra_step(monkeypatch):
    """Regression for the r4-documented one-step-late trigger under
    the lagged verdict: with the freshness window
    (resilience.StepGuard.step), the iters>15 evidence of production
    step 1 is pulled BEFORE step 2's dispatch, so the coarse
    correction engages at step 2 — the same step the eager driver
    engages at (pinned against an eager twin)."""
    from cup2d_tpu.resilience import StepGuard
    from validation.poisson_ab import build_forest_sim

    monkeypatch.delenv("CUP2D_POIS", raising=False)
    sim = build_forest_sim(bpd=2, level_start=2,
                           tol=1e-9, tol_rel=1e-8)
    guard = StepGuard(sim, lag=True, recover=False)
    engaged_at = None
    recs = []
    for call in range(1, 4):
        recs.append(guard.step())
        if engaged_at is None and sim._coarse_on:
            engaged_at = call
    guard.drain()
    # step 1 (verdicted during call 2's freshness window) supplied the
    # >15-iteration evidence...
    assert recs[0] is None                      # lag-1: still in flight
    assert recs[1]["poisson_iters"] > 15
    # ...and call 2 = step-1 evidence consumed at step-2's dispatch —
    # the eager driver's engagement step (drained via the dt pull
    # there); the pre-fix lagged pipeline engaged at call 3
    assert engaged_at == 2, engaged_at
    # schema-v4 attribution under lag: each record labels the path its
    # step actually TOOK (captured at dispatch, _Pending.mode) — a
    # live read at commit time would stamp step 1 with the trigger
    # state AFTER step 2's dispatch flipped it
    assert recs[1]["poisson_mode"] == "bicgstab+jacobi"
    assert recs[2]["poisson_mode"] == "bicgstab+twolevel"
