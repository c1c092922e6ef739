"""FFT-diagonalized direct Poisson solve + periodic-case tests
(ISSUE 20, CUP2D_POIS=fftd).

Contracts pinned here:

- Latch + attribution: "fftd" rides the sanctioned UniformGrid
  CUP2D_POIS read (construct-once — a post-construction env mutation
  is inert) and reports poisson_mode "fftd" (doubly periodic, pure
  spectral divide) or "fftd+tridiag" (one periodic axis, per-mode
  Thomas systems on the wall axis).
- Selection (ISSUE 35): with CUP2D_POIS unset the boundary table
  picks — both axes wrap, float32 and no sharded spatial axis ->
  "fftd", every other table (and float64: the chip has no c128
  transform) "bicgstab+mg"; an explicit value wins; a mesh attached
  to a SELECTED grid falls back to "bicgstab+mg", to an explicit
  "fftd" it is refused. The Krylov arm on a wrap table is named
  explicitly below: ``pressure_solve(exact="krylov")`` (the
  supervision ladder's escalate entry) or the mesh fall-back.
- Direct-solve correctness: one application reaches the production
  Linf criterion (iters == 1, converged) on the doubly-periodic box
  AND both mixed channels; the solution agrees with converged
  BiCGSTAB and FAS on the same operator to tight tolerance; the
  fully-periodic / all-Neumann nullspace is handled by the mean-zero
  pin (solution mean == 0, residual unaffected for mean-free RHS).
- Fleet batching: member_axis=True pushes B systems through ONE
  transform — batched == solo per member, iters == 1 for every
  member (the freeze contract is trivially inert: no member can
  observe another's iteration count).
- The doubly-periodic transform as matmuls (ops/dft.py): forward and
  inverse against numpy's real FFT in f64 and f32, the solve against
  the XLA transform form it replaced, that form kept for a length
  with no factorization, the compile ledger's note naming which was
  built, and a member batch BIT-equal to solo solves.
- Loud refusal everywhere the diagonalization cannot go: wall-only
  tables (nothing to diagonalize), the device-mesh x-split (it shards
  the transform or scan axis), AMRSim (uniform-family token), the
  Pallas megakernel tier and the strip smoother on periodic tokens
  (no wrap-ghost variants) — silent free-slip fallback is impossible.
- Physics: the doubly-periodic Taylor-Green vortex's kinetic energy
  decays as exp(-4 nu k^2 t) within 1% at 128^2 (the catalog's
  analytic anchor), and a served periodic fleet pool runs its
  steady-state churn with jit_compiles == 0.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from cup2d_tpu.bc import BCTable, no_slip, periodic
from cup2d_tpu.cases import (cavity_table, make_sim,
                             periodic_channel_table, periodic_table)
from cup2d_tpu.config import SimConfig


def _cfg(**kw):
    base = dict(bpdx=1, bpdy=1, level_max=1, level_start=0, extent=1.0,
                nu=1e-3, cfl=0.4, lam=1e6, dtype="float64",
                max_poisson_iterations=200)
    base.update(kw)
    return SimConfig(**base)


def _grid(bc, monkeypatch, pois="fftd", level=3, **kw):
    from cup2d_tpu.uniform import UniformGrid
    if pois:
        monkeypatch.setenv("CUP2D_POIS", pois)
    else:
        monkeypatch.delenv("CUP2D_POIS", raising=False)
    return UniformGrid(_cfg(**kw), level=level, bc=bc)


def _mean_free(shape, seed):
    rng = np.random.default_rng(seed)
    b = rng.standard_normal(shape)
    return jnp.asarray(b - b.mean(axis=(-2, -1), keepdims=True))


def _py_channel_table():
    return BCTable(no_slip(), no_slip(), periodic(), periodic())


# ---------------------------------------------------------------------------
# latch + poisson_mode attribution
# ---------------------------------------------------------------------------

def test_fftd_latch_and_mode_strings(monkeypatch):
    g = _grid(periodic_table(), monkeypatch)
    assert g.solver_mode == "fftd"
    assert g.poisson_mode == "fftd"
    # construct-once: a mid-run env mutation is inert (ADVICE r5)
    monkeypatch.delenv("CUP2D_POIS", raising=False)
    assert g.solver_mode == "fftd" and g.poisson_mode == "fftd"

    gx = _grid(periodic_channel_table(), monkeypatch)   # periodic x
    assert gx.poisson_mode == "fftd+tridiag"
    gy = _grid(_py_channel_table(), monkeypatch)        # periodic y
    assert gy.poisson_mode == "fftd+tridiag"


def _free_slip():
    return None


_SELECTION = {
    # id: (table, CUP2D_POIS, grid kwargs, attach a mesh, poisson_mode
    #      and who chose the direct solve | the refusal)
    "wrap-both": (periodic_table, "", {"dtype": "float32"}, False,
                  ("fftd", "table")),
    # the chip has no float64 transform: f64 keeps the solver that
    # runs on both platforms
    "wrap-both-f64": (periodic_table, "", {}, False,
                      ("bicgstab+mg", None)),
    "wrap-x": (periodic_channel_table, "", {}, False,
               ("bicgstab+mg", None)),
    "wrap-y": (_py_channel_table, "", {}, False, ("bicgstab+mg", None)),
    "cavity-walls": (cavity_table, "", {}, False, ("bicgstab+mg", None)),
    "free-slip": (_free_slip, "", {}, False, ("bicgstab+mg", None)),
    "wrap-both-env-fas": (periodic_table, "fas", {}, False, ("fas", None)),
    "wrap-both-env-fas-f": (periodic_table, "fas-f", {}, False,
                            ("fas-f", None)),
    "wrap-both-env-fftd": (periodic_table, "fftd", {}, False,
                           ("fftd", "env")),
    "wrap-x-env-fftd": (periodic_channel_table, "fftd", {}, False,
                        ("fftd+tridiag", "env")),
    "wrap-both-sharded-axes": (periodic_table, "",
                               {"dtype": "float32", "spmd_safe": True},
                               False, ("bicgstab+mg", None)),
    "wrap-both-then-mesh": (periodic_table, "", {"dtype": "float32"},
                            True, ("bicgstab+mg", None)),
    "wrap-both-env-fftd-then-mesh": (periodic_table, "fftd", {}, True,
                                     "fftd cannot attach"),
}


@pytest.mark.parametrize("case", sorted(_SELECTION))
def test_solver_is_selected_from_the_table(case, monkeypatch):
    """ISSUE 35: which solver a uniform grid runs, from what it can
    see — its boundary table, an explicit CUP2D_POIS, a mesh."""
    from cup2d_tpu.uniform import UniformGrid
    table, pois, kw, mesh, want = _SELECTION[case]
    monkeypatch.setenv("CUP2D_POIS", pois)
    kw = dict(kw)
    spmd = kw.pop("spmd_safe", False)
    g = UniformGrid(_cfg(**kw), level=3, bc=table(), spmd_safe=spmd)
    if isinstance(want, str):
        with pytest.raises(ValueError, match=want):
            g.attach_mesh(object())
        return
    if mesh:
        assert g.poisson_mode == "fftd"      # selected, then dropped
        g.attach_mesh(object())
    assert (g.poisson_mode, g.fftd_by) == want
    assert (g._fft_plan is not None) == (g.solver_mode == "fftd")
    # the request a driver hands the step: truthy for start-up and
    # for the ladder alike; the ladder's names Krylov exactly where
    # the direct solve is the production method
    assert g.exact_request(True, False) is True
    assert g.exact_request(False, False) is False
    assert g.exact_request(False, True) == (
        "krylov" if g.solver_mode == "fftd" else True)
    rhs = _mean_free((g.ny, g.nx), 21).astype(g.dtype)
    res = g.pressure_solve(rhs)
    assert bool(res.converged)
    # no hierarchy cycle is counted exactly where the direct solve ran
    assert (int(g.precond_cycles(res, False)) == 0) == (
        g.solver_mode == "fftd")


def test_ladder_escalates_a_selected_grid_to_krylov(monkeypatch,
                                                    tmp_path):
    """ISSUE 35, the backstop: on a grid whose direct solve the table
    selected, a planted non-converged verdict walks the ladder to its
    escalate rung, and the rung runs the tol-0 Krylov solve — not the
    method whose verdict just failed once more — in a step variant
    that no start-up or production step had compiled."""
    from cup2d_tpu.faults import FaultPlan
    from cup2d_tpu.resilience import EventLog, StepGuard
    from cup2d_tpu.tracing import FlightRecorder

    monkeypatch.delenv("CUP2D_POIS", raising=False)
    krylov = "uniform.step[exact_poisson=krylov]"
    flight = FlightRecorder(spans=False, capture_memory=False).install()
    try:
        sim = make_sim("turb2d", level=3, seed=7, dtype="float32")
        assert (sim.poisson_mode, sim.grid.fftd_by) == ("fftd", "table")
        guard = StepGuard(
            sim, faults=FaultPlan("poisson_giveup@12*2"),
            event_log=EventLog(str(tmp_path / "events.jsonl")))
        recs = []
        while sim.step_count < 12:
            recs.append(guard.step())
        before = {r["label"]: r
                  for r in flight.ledger_report()["executables"]}
        while sim.step_count < 16:
            recs.append(guard.step())
        recs += guard.drain()
    finally:
        flight.uninstall()
    after = {r["label"]: r for r in flight.ledger_report()["executables"]}
    # start-up (steps 1-10) and production had compiled, both the
    # direct solve and nothing of the hierarchy; the backstop had not
    for label in ("uniform.step[exact_poisson=True]",
                  "uniform.step[exact_poisson=False]"):
        assert before[label]["components"] == [
            "poisson.fft_diag_solve", "poisson.fftd[selected=table]",
            "poisson.fftd_dft[mxu,y=64,x=64]"]
    assert krylov not in before
    assert after[krylov]["compiles"] == 1
    assert "poisson.bicgstab" in after[krylov]["components"]
    assert not any(c.startswith("poisson.fft")
                   for c in after[krylov]["components"])
    with open(tmp_path / "events.jsonl") as f:
        actions = [e["action"] for e in map(json.loads, f)
                   if e.get("event") == "recovery"]
    assert actions == ["retry", "escalate"]
    by_step = {r["step"]: r for r in recs if r}
    assert sorted(by_step) == list(range(1, 17))
    for step, r in by_step.items():
        # the run's solver is what it was; only the escalated step
        # (the failed step 12 run again) iterated
        assert r.get("poisson_mode", sim.poisson_mode) == "fftd"
        if step == 13:
            assert r["poisson_iters"] > 1 and r["precond_cycles"] > 2
        else:
            assert (r["poisson_iters"], r["precond_cycles"]) == (1, 0)
    assert not sim._force_exact and sim.poisson_mode == "fftd"


def test_fftd_refuses_wall_only_box(monkeypatch):
    with pytest.raises(ValueError, match="at least one periodic"):
        _grid(cavity_table(), monkeypatch)
    with pytest.raises(ValueError, match="at least one periodic"):
        _grid(None, monkeypatch)   # default free-slip box


# ---------------------------------------------------------------------------
# direct-solve correctness: 1 iteration at the production criterion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("table", [periodic_table(),
                                   periodic_channel_table(),
                                   _py_channel_table()],
                         ids=["doubly-periodic", "periodic-x",
                              "periodic-y"])
def test_fftd_one_application_converges(table, monkeypatch):
    g = _grid(table, monkeypatch)
    rhs = _mean_free((g.ny, g.nx), 11)
    res = g.pressure_solve(rhs)
    assert int(res.iters) == 1
    assert bool(res.converged) and not bool(res.stalled)
    # f64 direct solve: the true residual sits at transform rounding,
    # far below the production criterion it is judged against
    lin = float(jnp.max(jnp.abs(rhs - g.laplacian(res.x))))
    assert lin < 1e-10, lin
    # nullspace pin on the fully-periodic box: zeroing the (0,0) mode
    # IS the mean-zero solution. (The tridiag channels pin one VALUE
    # of the singular k=0 system instead — any mean offset is removed
    # downstream by the projection's standing mean-free contract,
    # exactly as for the Krylov solvers.)
    if table == periodic_table():
        assert abs(float(jnp.mean(res.x))) < 1e-12


def test_fftd_f32_production_criterion(monkeypatch):
    """The acceptance probe's tier-1 twin: cold mean-free RHS in f32 at
    128^2 meets the production Linf criterion in the single direct
    application."""
    g = _grid(periodic_table(), monkeypatch, level=4, dtype="float32")
    rhs = _mean_free((g.ny, g.nx), 12).astype(jnp.float32)
    res = g.pressure_solve(rhs)
    assert int(res.iters) == 1
    assert bool(res.converged), float(res.residual)


# ---------------------------------------------------------------------------
# agreement with the iterative solvers on the same operator
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("table", [periodic_channel_table(),
                                   _py_channel_table()],
                         ids=["periodic-x", "periodic-y"])
def test_fftd_matches_bicgstab_and_fas(table, monkeypatch):
    """Mixed periodic/no-slip channels: the per-mode direct solve, the
    MG-preconditioned Krylov solve and the FAS full solver are three
    implementations of ONE operator — converged answers must agree to
    tight (mean-adjusted) tolerance."""
    rhs = _mean_free((64, 64), 13)

    def demean(a):
        return np.asarray(a) - float(jnp.mean(a))

    xf = demean(_grid(table, monkeypatch).pressure_solve(rhs).x)
    gb = _grid(table, monkeypatch, pois=None)
    rb = gb.pressure_solve(rhs, exact=True)       # tol-0 Krylov
    assert bool(rb.converged) or bool(rb.stalled)  # precision floor
    np.testing.assert_allclose(xf, demean(rb.x), atol=5e-9)

    gf = _grid(table, monkeypatch, pois="fas")
    rf = gf.pressure_solve(rhs, exact=True)
    np.testing.assert_allclose(xf, demean(rf.x), atol=5e-9)


def test_fftd_periodic_box_matches_bicgstab(monkeypatch):
    """Fully-periodic box (pure spectral divide, true nullspace): both
    solvers produce the SAME mean-free solution."""
    rhs = _mean_free((64, 64), 14)
    xf = _grid(periodic_table(), monkeypatch).pressure_solve(rhs).x
    # the Krylov arm by name (in float32 this table selects the
    # direct solve, and the ladder's entry is how Krylov is reached)
    gb = _grid(periodic_table(), monkeypatch, pois=None)
    rb = gb.pressure_solve(rhs, exact="krylov")
    assert int(rb.iters) > 1
    xb = np.asarray(rb.x) - float(jnp.mean(rb.x))
    np.testing.assert_allclose(np.asarray(xf), xb, atol=5e-9)


# ---------------------------------------------------------------------------
# fleet batching: B systems through one transform
# ---------------------------------------------------------------------------

def test_fftd_member_batched_matches_solo(monkeypatch):
    from cup2d_tpu.poisson import fft_diag_solve
    g = _grid(periodic_channel_table(), monkeypatch)
    B = 3
    rhs = _mean_free((B, g.ny, g.nx), 15)
    # a dead slot (zero RHS) rides along: its direct solve is exact
    rhs = rhs.at[1].set(0.0)
    batched = fft_diag_solve(g.laplacian, rhs, g._fft_plan,
                             tol=1e-4, tol_rel=1e-3, member_axis=True)
    # freeze contract trivially inert: iters == 1 for EVERY member
    # (dead slots included) — no member observes another's count
    np.testing.assert_array_equal(np.asarray(batched.iters),
                                  np.ones(B, np.int32))
    assert bool(jnp.all(batched.converged))
    assert batched.residual.shape == (B,)
    for m in range(B):
        solo = fft_diag_solve(g.laplacian, rhs[m], g._fft_plan,
                              tol=1e-4, tol_rel=1e-3)
        np.testing.assert_allclose(np.asarray(batched.x[m]),
                                   np.asarray(solo.x), atol=1e-12)


@pytest.mark.parametrize("pois", ["fftd", ""], ids=["env", "selected"])
def test_fftd_fleet_trajectory_matches_solo(pois, monkeypatch):
    """A member-batched periodic fleet steps bit-close to the solo sim
    under fftd, asked for or selected by the table (the fleet and the
    solo driver build the same grid): same IC in every slot, one fused
    dispatch."""
    monkeypatch.setenv("CUP2D_POIS", pois)
    # the table selects in float32 alone (the chip's transform)
    dtype, atol = ("float64", 1e-12) if pois else ("float32", 1e-6)
    fs = make_sim("tgv_periodic", level=2, members=3, dtype=dtype)
    solo = make_sim("tgv_periodic", level=2, dtype=dtype)
    assert fs.poisson_mode == solo.poisson_mode == "fftd"
    assert fs.grid.fftd_by == ("env" if pois else "table")
    dt = 1e-3
    for _ in range(3):
        fs.step_once(dt)
        solo.step_once(dt)
    vs = np.asarray(solo.state.vel)
    vf = np.asarray(fs.state.vel)
    for m in range(3):
        np.testing.assert_allclose(vf[m], vs, atol=atol)


# ---------------------------------------------------------------------------
# the doubly-periodic transform pair as matmuls (ops/dft.py)
# ---------------------------------------------------------------------------

# one dense stage (<= 256, 96 not a power of two) and two stages (384, 512)
DFT_SHAPES = [(16, 16), (64, 32), (256, 256), (384, 512), (96, 96)]
DFT_RTOL = {np.float64: 1e-10, np.float32: 1e-5}


def _hartley_of(x):
    """numpy's separable Hartley spectrum of a real field, from rfft2:
    the Hermitian half extended to the full DFT F, then
    H[ky, kx] = Re F[ky, -kx] - Im F[ky, kx]
    (cas a cas b = cos(a - b) + sin(a + b))."""
    ny, nx = x.shape
    half = np.fft.rfft2(x)
    F = np.empty((ny, nx), complex)
    F[:, :nx // 2 + 1] = half
    kx = np.arange(nx // 2 + 1, nx)
    F[:, kx] = np.conj(half[(-np.arange(ny)) % ny][:, nx - kx])
    return F[:, (-np.arange(nx)) % nx].real - F.imag


def _rel(a, b):
    return float(np.max(np.abs(np.asarray(a) - b)) / np.max(np.abs(b)))


@pytest.mark.parametrize("dtype", [np.float64, np.float32],
                         ids=["f64", "f32"])
@pytest.mark.parametrize("shape", DFT_SHAPES,
                         ids=[f"{a}x{b}" for a, b in DFT_SHAPES])
def test_fftd_dft_matches_numpy(shape, dtype):
    """The matmul transform against numpy's real FFT: the forward
    spectrum, read through its slot order, is the Hartley spectrum
    rfft2 implies; the inverse of that spectrum is irfft2's field."""
    from cup2d_tpu.ops.dft import HartleyPlan2D
    ny, nx = shape
    plan = HartleyPlan2D.build(ny, nx, dtype)
    x = np.random.default_rng(ny + nx).standard_normal(shape)
    ref = _hartley_of(x)[np.ix_(plan.y.freq, plan.x.freq)].T   # [nx', ny']
    fwd = plan.forward(jnp.asarray(x, dtype))
    assert fwd.dtype == dtype
    assert _rel(fwd, ref) < DFT_RTOL[dtype]
    back = plan.inverse(jnp.asarray(ref, dtype)) / (ny * nx)
    assert _rel(back, np.fft.irfft2(np.fft.rfft2(x), s=shape)) \
        < DFT_RTOL[dtype]


@pytest.mark.parametrize("shape", [(64, 64), (384, 512), (96, 160)],
                         ids=["64x64", "384x512", "96x160"])
def test_fftd_dft_solve_matches_xla_transform(shape):
    """In f32, FFTDiagPlan.solve on the MXU form agrees with the real
    FFT form it replaced (rfft2, the f64 reciprocal eigenvalues with
    the (0, 0) mode zeroed, irfft2) on a seeded right-hand side."""
    from cup2d_tpu.poisson import FFTDiagPlan
    ny, nx = shape
    plan = FFTDiagPlan(ny, nx, jnp.float32, True, True, (1.0,) * 4)
    assert plan.dft is not None
    b = np.asarray(_mean_free(shape, 21), np.float32)
    lx = 2.0 * np.cos(2.0 * np.pi * np.arange(nx // 2 + 1) / nx) - 2.0
    ly = 2.0 * np.cos(2.0 * np.pi * np.arange(ny) / ny) - 2.0
    lam = ly[:, None] + lx[None, :]
    ilam = np.where(lam < -1e-12, 1.0 / np.where(lam < -1e-12, lam, 1.0),
                    0.0).astype(np.float32)
    ref = jnp.fft.irfft2(jnp.fft.rfft2(jnp.asarray(b)) * ilam, s=shape)
    x = plan.solve(jnp.asarray(b))
    assert x.dtype == jnp.float32
    assert _rel(x, np.asarray(ref)) < 1e-5


def test_fftd_dft_keeps_xla_where_no_factorization():
    """A length with no split into parts <= 256 (2 * 257) keeps the
    XLA transform, and that solve is still the direct one."""
    from cup2d_tpu.ops.dft import split
    from cup2d_tpu.poisson import FFTDiagPlan
    assert split(2 * 257) is None and split(8192) == (128, 64)
    plan = FFTDiagPlan(2 * 257, 64, jnp.float64, True, True, (1.0,) * 4)
    assert plan.dft is None and plan.ilam.shape == (2 * 257, 33)
    b = _mean_free((2 * 257, 64), 22)
    x = plan.solve(b)
    lap = (jnp.roll(x, 1, 0) + jnp.roll(x, -1, 0) + jnp.roll(x, 1, 1)
           + jnp.roll(x, -1, 1) - 4.0 * x)
    assert float(jnp.max(jnp.abs(lap - b))) < 1e-10


@pytest.mark.parametrize("shape,note", [
    ((64, 64), "mxu,y=64,x=64"),
    ((384, 512), "mxu,y=24x16,x=32x16"),
    ((2 * 257, 64), "xla"),
], ids=["one-stage", "two-stage", "xla"])
def test_fftd_dft_component_note_names_the_transform(shape, note):
    """The compile ledger's component note says which transform the
    plan built, so every run records whether the MXU form engaged."""
    import jax

    from cup2d_tpu import tracing
    from cup2d_tpu.poisson import FFTDiagPlan
    plan = FFTDiagPlan(*shape, jnp.float32, True, True, (1.0,) * 4)
    flight = tracing.FlightRecorder(spans=False,
                                    capture_memory=False).install()
    try:
        tracing.named_jit("probe.fftd", jax.jit(plan.solve))(
            jnp.zeros(shape, jnp.float32)).block_until_ready()
    finally:
        flight.uninstall()
    rows = {r["label"]: r for r in flight.ledger_report()["executables"]}
    assert rows["probe.fftd"]["components"] == [f"poisson.fftd_dft[{note}]"]


@pytest.mark.parametrize("shape", [(64, 64), (384, 512)],
                         ids=["one-stage", "two-stage"])
def test_fftd_dft_member_batched_is_bit_equal_to_solo(shape):
    """The fleet's member axis is a batch dimension of every matmul:
    a [3, ny, nx] doubly-periodic solve is BIT-equal to three solo
    solves, through the whole direct-solve contract."""
    from cup2d_tpu.poisson import FFTDiagPlan, fft_diag_solve
    plan = FFTDiagPlan(*shape, jnp.float32, True, True, (1.0,) * 4)
    rhs = _mean_free((3,) + shape, 23).astype(jnp.float32)

    def lap(x):
        return (jnp.roll(x, 1, -2) + jnp.roll(x, -1, -2)
                + jnp.roll(x, 1, -1) + jnp.roll(x, -1, -1) - 4.0 * x)

    batched = fft_diag_solve(lap, rhs, plan, tol=1e-4, tol_rel=1e-3,
                             member_axis=True)
    assert bool(jnp.all(batched.converged))
    for m in range(3):
        solo = fft_diag_solve(lap, rhs[m], plan, tol=1e-4, tol_rel=1e-3)
        np.testing.assert_array_equal(np.asarray(batched.x[m]),
                                      np.asarray(solo.x))
        assert float(batched.residual[m]) == float(solo.residual)


# ---------------------------------------------------------------------------
# refusal matrix: every tier that cannot honor periodic/fftd says so
# ---------------------------------------------------------------------------

def test_attach_mesh_refuses_fftd(monkeypatch):
    g = _grid(periodic_table(), monkeypatch)
    with pytest.raises(ValueError, match="fftd cannot attach"):
        g.attach_mesh(object())


def test_amr_refuses_fftd_token(monkeypatch):
    from cup2d_tpu.amr import AMRSim
    monkeypatch.setenv("CUP2D_POIS", "fftd")
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=2, level_start=1,
                    extent=1.0, dtype="float64")
    with pytest.raises(ValueError, match="uniform-family"):
        AMRSim(cfg, shapes=[])


def test_amr_refuses_periodic_table(monkeypatch):
    from cup2d_tpu.amr import AMRSim
    monkeypatch.delenv("CUP2D_POIS", raising=False)
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=2, level_start=1,
                    extent=1.0, dtype="float64")
    with pytest.raises(ValueError, match="does not support"):
        AMRSim(cfg, shapes=[], bc=periodic_table())


def test_pallas_megakernel_refuses_periodic(monkeypatch):
    """CUP2D_PALLAS=1 + a periodic table refuses AT CONSTRUCTION,
    naming the face/kind/token (the PR-16 capability-gate pattern) —
    a silent free-slip fallback is impossible."""
    from cup2d_tpu.uniform import UniformGrid
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.delenv("CUP2D_POIS", raising=False)
    cfg = _cfg(dtype="float32")
    with pytest.raises(ValueError, match="periodic"):
        UniformGrid(cfg, level=4, bc=periodic_table())
    with pytest.raises(ValueError, match="pd"):
        UniformGrid(cfg, level=4, bc=periodic_channel_table())


def test_strip_smoother_falls_back_on_periodic():
    """The strip pipeline has no wrap-ghost form: a periodic axis is a
    silent, reported fall-back to the XLA legs (ISSUE 26; a
    construction-time refusal before)."""
    from cup2d_tpu.poisson import MultigridPreconditioner
    mg = MultigridPreconditioner(
        64, 64, jnp.float32, edge_signs=(0.0, 0.0, 1.0, 1.0),
        smoother="strip", periodic=(True, False))
    assert (mg.smoother_tier, mg.fused_levels) == ("xla", 0)


def test_mg_periodic_needs_edge_signs():
    from cup2d_tpu.poisson import MultigridPreconditioner
    with pytest.raises(ValueError, match="edge_signs"):
        MultigridPreconditioner(64, 64, jnp.float64,
                                periodic=(True, True))


# ---------------------------------------------------------------------------
# MG cycles on the wrapped operator (the bicgstab/fas arms' engine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("table", [periodic_table(),
                                   periodic_channel_table()],
                         ids=["doubly-periodic", "periodic-x"])
def test_bicgstab_mg_converges_on_periodic(table, monkeypatch):
    """The ITERATIVE path must also honor wrap stencils (periodicity
    persists under coarsening) — it is the fftd A/B baseline and the
    only sharded-periodic option."""
    # float64: the table selects the direct solve in float32 alone
    g = _grid(table, monkeypatch, pois=None)
    assert g.poisson_mode == "bicgstab+mg"
    rhs = _mean_free((g.ny, g.nx), 16)
    res = g.pressure_solve(rhs)
    assert bool(res.converged) and int(g.precond_cycles(res, False)) > 1
    lin = float(jnp.max(jnp.abs(rhs - g.laplacian(res.x))))
    tgt = max(g.cfg.poisson_tol,
              g.cfg.poisson_tol_rel * float(jnp.max(jnp.abs(rhs))))
    assert lin <= 1.01 * tgt, (lin, tgt)


# ---------------------------------------------------------------------------
# telemetry: the v12 vocabulary on a REAL record
# ---------------------------------------------------------------------------

def test_fftd_metrics_record_attribution(monkeypatch):
    from cup2d_tpu.profiling import MetricsRecorder
    monkeypatch.setenv("CUP2D_POIS", "fftd")
    sim = make_sim("tgv_periodic", level=2, dtype="float64")
    sim.step_count = 20     # production regime: the startup exact
    #                         (tol-0) override reports stalled, not
    #                         converged — same semantics as bicgstab
    rec = MetricsRecorder()
    rec.prime(sim)
    r = rec.record(sim, sim.step_once(1e-3))
    assert r["poisson_mode"] == "fftd"
    assert r["bc_table"] == "pd,pd,pd,pd"
    assert r["case"] == "tgv_periodic"
    assert r["poisson_iters"] == 1
    assert r["precond_cycles"] == 0
    assert r["poisson_converged"] is True


# ---------------------------------------------------------------------------
# physics: the analytic anchor + the serving contract
# ---------------------------------------------------------------------------

def test_tgv_periodic_ke_decay_within_1pct(monkeypatch):
    """Acceptance (ISSUE 20): tgv_periodic at 128^2 under fftd — KE
    decays as exp(-4 nu k^2 t), k = 2 pi, within 1%."""
    nu = 1e-3
    monkeypatch.setenv("CUP2D_POIS", "fftd")
    sim = make_sim("tgv_periodic", level=4, nu=nu, dtype="float64")
    ke0 = float(jnp.mean(sim.state.vel ** 2))
    t_end = 0.1
    sim.advance(n_steps=10_000, tend=t_end)
    assert sim.time >= t_end
    ke = float(jnp.mean(sim.state.vel ** 2))
    k = 2.0 * np.pi
    expected = np.exp(-4.0 * nu * k * k * sim.time)
    measured = ke / ke0
    assert abs(measured - expected) / expected < 0.01, (measured,
                                                       expected)


@pytest.mark.slow   # developed-regime trajectory (O(300) steps at
#                     128^2 through roll-up, t=0.8). The tier-1
#                     physics anchor for the periodic stack is the 1%
#                     TGV KE-decay test above — this pins the CATALOG
#                     case qualitatively (perturbation growth +
#                     bounded, decaying invariants), which needs the
#                     developed regime by definition.
def test_shear_layer_rolls_up(monkeypatch):
    """Double shear layer under fftd: the delta*sin(2pi x) seed grows
    into the roll-up (v-energy rises an order of magnitude), while KE
    decays monotonically-in-aggregate and the fields stay finite —
    the classic BCG sanity on the periodic advection + projection."""
    monkeypatch.setenv("CUP2D_POIS", "fftd")
    sim = make_sim("shear_layer", level=4, dtype="float64")
    v2_0 = float(jnp.mean(sim.state.vel[1] ** 2))
    ke0 = float(jnp.mean(sim.state.vel ** 2))
    sim.advance(n_steps=10_000, tend=0.8)   # roll-up developed:
    #                                         measured v-energy growth
    #                                         ~x110 by t=0.8 (x5 at
    #                                         0.4 — still linear)
    vel = sim.state.vel
    assert bool(jnp.all(jnp.isfinite(vel)))
    ke = float(jnp.mean(vel ** 2))
    v2 = float(jnp.mean(vel[1] ** 2))
    assert ke < ke0                        # dissipative
    assert v2 > 10.0 * v2_0, (v2, v2_0)   # roll-up grew the seed


@pytest.mark.slow   # seeded-spectrum decay trajectory at 128^2 (same
#                     developed-regime justification as the
#                     shear-layer test; tier-1 already pins turb2d's
#                     build + solve contracts via the fftd tests
#                     above)
def test_turb2d_selective_decay(monkeypatch):
    """Decaying 2D turbulence under fftd: energy and enstrophy both
    decay (selective decay — enstrophy faster), deterministically per
    seed."""
    monkeypatch.setenv("CUP2D_POIS", "fftd")
    sim = make_sim("turb2d", level=4, seed=7, dtype="float64")
    g = sim.grid

    def invariants():
        w = g.vorticity_field(sim.state.vel)
        return (float(jnp.mean(sim.state.vel ** 2)),
                float(jnp.mean(w ** 2)))

    ke0, ens0 = invariants()
    sim.advance(n_steps=10_000, tend=0.2)
    ke1, ens1 = invariants()
    assert bool(jnp.all(jnp.isfinite(sim.state.vel)))
    assert ke1 < ke0
    assert ens1 < ens0
    # enstrophy decays FASTER than energy (2D selective decay)
    assert ens1 / ens0 < ke1 / ke0


def test_zero_recompile_served_periodic_pool(monkeypatch, tmp_path):
    """Acceptance (ISSUE 20): a served periodic case runs its
    steady-state churn with jit_compiles == 0 — the fftd direct solve
    and wrap stencils compile once in the warm phase and the slot-pool
    executables are reused through admit/retire churn."""
    from cup2d_tpu.fleet import FleetRequest, FleetServer, FleetSim
    from cup2d_tpu.profiling import HostCounters
    from cup2d_tpu.resilience import EventLog

    monkeypatch.setenv("CUP2D_POIS", "fftd")
    cfg = _cfg(lam=1e6)
    sim = FleetSim(cfg, level=2, members=3, bc=periodic_table())
    sim.step_count = 20          # production regime (serving steady state)
    log = EventLog(str(tmp_path / "events.jsonl"))
    server = FleetServer(sim, event_log=log)
    g = sim.grid
    x, y = g.cell_centers()
    k = 2.0 * np.pi
    n_req = 0

    def submit(horizon_steps):
        nonlocal n_req
        amp = 0.8 ** (n_req % 3)
        st = g.zero_state()._replace(vel=jnp.asarray(np.stack([
            amp * np.sin(k * x) * np.cos(k * y),
            -amp * np.cos(k * x) * np.sin(k * y)]), dtype=g.dtype))
        dt0 = float(sim._member_dt(st.vel))
        server.submit(FleetRequest(
            client_id=f"c{n_req:03d}", state=st,
            t_end=(horizon_steps - 0.1) * dt0))
        n_req += 1

    # warm phase: fill the pool, retire, refill — every executable the
    # measured window touches compiles here
    for _ in range(3):
        submit(2)
    for _ in range(6):
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
    assert server.retired > retired0 and server.admitted > admitted0
    assert snap["jit_compiles"] == 0, snap
    log.close()
