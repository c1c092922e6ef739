"""Fused RK-substage megakernel (PR 9): equivalence + composition pins.

Everything here runs the REAL Pallas kernels in interpret mode (tier-1,
CPU box) — interpret executes the same kernel body, DMA schedule and
value-level halo construction as the TPU lowering, so kernel-logic bugs
(ring-slot collisions, wrong ghost mirror signs, per-member scale-row
mixups) fail HERE, not on the first TPU drive. What interpret cannot
check — Mosaic lowering, real DMA overlap — is test_pallas.py's
TPU-only job.

Measured error bounds (pinned with ~16x headroom, CPU interpret):

- full-Heun f32 vs the XLA op chain: max-abs 1.1920928955078125e-07 on
  the 32x64 unit-scale operand — NOT bit-exact because XLA contracts
  `a*b+c` into FMAs differently inside vs outside the kernel body; the
  prior single-op probe measured 2.9e-11 per RHS evaluation, and the
  Heun update multiplies the RHS by ih2 = 4096, giving exactly ~1 ulp
  at unit scale. Asserted <= 2e-6.
- forest-block fused_lab_rhs vs advect_diffuse_rhs: bit-exact (0.0) —
  no ih2 amplification on the raw RHS, identical contraction.
- fused projection-correction vs the XLA epilogue: 2.4e-7 (uniform) /
  4.8e-7 (fleet) — the mean-subtract reassociates. Asserted <= 5e-6.
- bf16 storage tier vs the f32 reference trajectory: ~3.2e-3 after one
  step (bf16 mantissa 2^-8), drifting with step count. The Taylor-
  Green golden asserts <= 2e-2 after 10 steps.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup2d_tpu.config import SimConfig
from cup2d_tpu.ops.pallas_kernels import (fused_advect_heun, fused_lab_rhs,
                                          fused_tier_supported)
from cup2d_tpu.ops.stencil import advect_diffuse_rhs, heun_substage
from cup2d_tpu.poisson import project_correct
from cup2d_tpu.uniform import (UniformGrid, UniformSim, pad_vector,
                               taylor_green_state)

NY, NX = 32, 64
H = 1.0 / NX
NU = 4e-5
FULL_HEUN_BOUND = 2e-6     # measured 1.19e-07 (see module docstring)
CORRECTION_BOUND = 5e-6    # measured 2.4e-7 / 4.8e-7


def _rand(shape, seed):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), jnp.float32)


def _xla_heun(vel, h, nu, dt):
    """The pre-PR-9 XLA op chain, verbatim (uniform: scalar dt; fleet:
    dt [B] broadcast exactly like FleetSim._step_impl's dt4)."""
    ih2 = 1.0 / (h * h)
    dt_b = dt[:, None, None, None] if jnp.ndim(dt) == 1 else dt
    vold = vel
    v = vel
    for c in (0.5, 1.0):
        lab = pad_vector(v, 3)
        rhs = advect_diffuse_rhs(lab, 3, h, nu, dt_b)
        v = heun_substage(vold, c, rhs, ih2)
    return v


def _cfg32(**kw):
    base = dict(bpdx=1, bpdy=1, level_max=1, level_start=0, extent=1.0,
                nu=NU, cfl=0.4, dtype="float32",
                max_poisson_iterations=60)
    base.update(kw)
    return SimConfig(**base)


# ---------------------------------------------------------------------------
# f32 equivalence vs the XLA chain: all three operand families
# ---------------------------------------------------------------------------

def test_fused_heun_matches_xla_uniform():
    """UniformSim's operand family: vel [2,Ny,Nx], scalar dt."""
    vel = _rand((2, NY, NX), 0)
    dt = jnp.float32(0.5 * H)
    ref = _xla_heun(vel, H, NU, dt)
    got = fused_advect_heun(vel, H, NU, dt)
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err <= FULL_HEUN_BOUND, err


def test_fused_heun_matches_xla_member_batched():
    """FleetSim's operand family: vel [B,2,Ny,Nx] with DISTINCT
    per-member dt — pins the kernel's per-member (afac, dfac) scale
    rows (a transposed or broadcast-shared row would blow the ~1-ulp
    bound by the dt ratio)."""
    vel = _rand((3, 2, NY, NX), 1)
    dt = jnp.asarray([0.5 * H, 0.35 * H, 0.27 * H], jnp.float32)
    ref = _xla_heun(vel, H, NU, dt)
    got = fused_advect_heun(vel, H, NU, dt)
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err <= FULL_HEUN_BOUND, err


def test_fused_lab_rhs_bitexact_forest_blocks():
    """AMRSim's operand family: pre-assembled labs [N,2,BS+6,BS+6] with
    PER-BLOCK h [N,1,1,1] (the forest mixes levels in one batch). The
    raw RHS has no ih2 amplification, so this one is bit-exact."""
    n, bs, g = 5, 8, 3
    lab = _rand((n, 2, bs + 2 * g, bs + 2 * g), 2)
    hb = jnp.asarray([H, H / 2, H, H / 4, H / 2],
                     jnp.float32).reshape(n, 1, 1, 1)
    dt = jnp.float32(0.5 * H)
    # both sides jitted — the production configuration (AMRSim's step
    # is one jit); eagerly the op-by-op dispatch contracts FMAs
    # differently and the match is ~1 ulp (1.5e-10) instead of exact
    ref = jax.jit(lambda l: advect_diffuse_rhs(l, g, hb, NU, dt))(lab)
    got = jax.jit(lambda l: fused_lab_rhs(l, hb, NU, dt))(lab)
    assert float(jnp.max(jnp.abs(got - ref))) == 0.0


def test_fused_correction_matches_xla():
    """project_correct: the fused single-kernel epilogue vs the
    historical XLA chain, uniform (scalar means) and fleet (per-member
    means, per-member dt) operands."""
    x = _rand((NY, NX), 3)
    pold = _rand((NY, NX), 4)
    vel = _rand((2, NY, NX), 5)
    dt = jnp.float32(0.5 * H)
    vr, pr = project_correct(x, pold, vel, H, dt, tier="xla")
    vf, pf = project_correct(x, pold, vel, H, dt, tier="pallas-fused")
    assert float(jnp.max(jnp.abs(vf - vr))) <= CORRECTION_BOUND
    assert float(jnp.max(jnp.abs(pf - pr))) <= CORRECTION_BOUND

    xb = _rand((3, NY, NX), 6)
    pb = _rand((3, NY, NX), 7)
    vb = _rand((3, 2, NY, NX), 8)
    dtb = jnp.asarray([0.5 * H, 0.35 * H, 0.27 * H], jnp.float32)
    vr, pr = project_correct(xb, pb, vb, H, dtb,
                             mean_axes=(-2, -1), tier="xla")
    vf, pf = project_correct(xb, pb, vb, H, dtb,
                             mean_axes=(-2, -1), tier="pallas-fused")
    assert float(jnp.max(jnp.abs(vf - vr))) <= CORRECTION_BOUND
    assert float(jnp.max(jnp.abs(pf - pr))) <= CORRECTION_BOUND


# ---------------------------------------------------------------------------
# BC-aware kernel (ISSUE 16): the four ghost kinds, corner composition
# and the parabolic clamp vs bc.py's XLA chain, all four face tables
# ---------------------------------------------------------------------------

def _xla_bc_heun(vel, h, nu, dt, bc):
    """The BC'd XLA op chain (uniform.py's fallback path, verbatim):
    bc.py ghost paint -> WENO RHS -> Heun substage."""
    from cup2d_tpu.bc import pad_vector_bc
    ih2 = 1.0 / (h * h)
    dt_b = dt[:, None, None, None] if jnp.ndim(dt) == 1 else dt
    vold = vel
    v = vel
    for c in (0.5, 1.0):
        # dt_b: the member-batched path broadcasts dt like fleet.py's
        # dt4 so the outflow extrapolation speed is per-member
        lab = pad_vector_bc(v, 3, bc, h, dt_b)
        rhs = advect_diffuse_rhs(lab, 3, h, nu, dt_b)
        v = heun_substage(vold, c, rhs, ih2)
    return v


def _bc_tables():
    from cup2d_tpu.bc import (BCTable, convective_outflow,
                              dirichlet_inflow, no_slip)
    from cup2d_tpu.cases import cavity_table, channel_table
    return {
        # four no-slip walls + moving lid: 2*uw - edge on every face,
        # corners compose x-ghosts from the y-painted columns
        "cavity": cavity_table(1.0),
        # uniform Dirichlet inflow + convective outflow on the x faces
        # (the dt-dependent extrapolation speed, clipped to [0,1])
        "channel_uniform": channel_table(1.0),
        # parabolic inflow: the 4s(1-s) profile along the x_lo face's
        # PADDED rows, s clipped outside the interior band
        "channel_parabolic": channel_table(1.0, profile="parabolic"),
        # y-face inflow/outflow: the parabolic profile along a y face
        # (tangent = global column index) and outflow at y_hi, with
        # no-slip x walls reading the y-painted corners
        "outflow_y": BCTable(no_slip(), no_slip(),
                             dirichlet_inflow(0.0, 1.0,
                                              profile="parabolic"),
                             convective_outflow()),
    }


@pytest.mark.parametrize("name", sorted(_bc_tables()))
def test_fused_heun_matches_xla_bc(name):
    """Every supported ghost kind, ~1-ulp f32 equivalence vs the bc.py
    XLA chain (the same FMA-contraction bound as the free-slip pin)."""
    bc = _bc_tables()[name]
    vel = _rand((2, NY, NX), 11)
    dt = jnp.float32(0.5 * H)
    ref = _xla_bc_heun(vel, H, NU, dt, bc)
    got = fused_advect_heun(vel, H, NU, dt, bc=bc)
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err <= FULL_HEUN_BOUND, (name, err)


def test_fused_heun_bc_member_batched():
    """BC'd kernel under the fleet's operand family: distinct
    per-member dt rides the widened facs row (col 2 feeds the outflow
    extrapolation speed per member)."""
    from cup2d_tpu.cases import channel_table
    bc = channel_table(1.0, profile="parabolic")
    vel = _rand((3, 2, NY, NX), 12)
    dt = jnp.asarray([0.5 * H, 0.35 * H, 0.27 * H], jnp.float32)
    ref = _xla_bc_heun(vel, H, NU, dt, bc)
    got = fused_advect_heun(vel, H, NU, dt, bc=bc)
    err = float(jnp.max(jnp.abs(got - ref)))
    # measured 2.03e-6: the outflow speed c = clip(s*en*dt/h, 0, 1) is
    # associated differently inside the kernel ((s*en)*dtf)/h and the
    # ~1-ulp difference in c rides (edge - inner) through the same
    # ih2 = 4096 amplification as the base bound — a few extra ulp,
    # not a logic error (the solo BC'd arms above stay <= 2e-6)
    assert err <= 1e-5, err


def test_free_slip_table_normalizes_to_base_kernel():
    """The ISSUE-16 acceptance pin: an explicit all-free-slip table
    normalizes to bc=None inside fused_advect_heun, so the default
    table stays BIT-identical to the PR-9 kernel (same executable, not
    merely close)."""
    from cup2d_tpu.bc import BCTable
    vel = _rand((2, NY, NX), 13)
    dt = jnp.float32(0.5 * H)
    base = fused_advect_heun(vel, H, NU, dt)
    got = fused_advect_heun(vel, H, NU, dt, bc=BCTable())
    assert float(jnp.max(jnp.abs(got - base))) == 0.0


def test_fused_correction_carries_pressure_signs():
    """The fused projection epilogue with a Dirichlet (outflow) face:
    the kernel's edge-gradient coefficients take bc.py's derived
    pressure-row signs and match the XLA chain; the default signs stay
    bit-identical to explicit all-Neumann (1,1,1,1)."""
    from cup2d_tpu.bc import pressure_signs
    from cup2d_tpu.cases import channel_table
    gs = pressure_signs(channel_table(1.0))
    assert gs == (1.0, -1.0, 1.0, 1.0)     # x_hi outflow -> Dirichlet
    x = _rand((NY, NX), 14)
    pold = _rand((NY, NX), 15)
    vel = _rand((2, NY, NX), 16)
    dt = jnp.float32(0.5 * H)
    vr, pr = project_correct(x, pold, vel, H, dt, tier="xla",
                             grad_signs=gs)
    vf, pf = project_correct(x, pold, vel, H, dt, tier="pallas-fused",
                             grad_signs=gs)
    assert float(jnp.max(jnp.abs(vf - vr))) <= CORRECTION_BOUND
    assert float(jnp.max(jnp.abs(pf - pr))) <= CORRECTION_BOUND
    v0, p0 = project_correct(x, pold, vel, H, dt, tier="pallas-fused")
    v1, p1 = project_correct(x, pold, vel, H, dt, tier="pallas-fused",
                             grad_signs=(1.0, 1.0, 1.0, 1.0))
    assert float(jnp.max(jnp.abs(v1 - v0))) == 0.0
    assert float(jnp.max(jnp.abs(p1 - p0))) == 0.0


def test_sharded_kernel_matches_solo_kernel():
    """The halo-mode kernel on a 2-device x-split vs the solo kernel,
    same BC table: the per-shard ghost synthesis reads global position
    from the info row and edge columns from the ppermuted halo operand,
    so the split must be invisible (observed bit-identical in
    interpret mode; asserted <= 1e-11)."""
    from cup2d_tpu.cases import channel_table
    from cup2d_tpu.parallel.mesh import make_mesh
    from cup2d_tpu.parallel.shard_halo import fused_advect_heun_sharded
    bc = channel_table(1.0, profile="parabolic")
    vel = _rand((2, NY, NX), 17)
    dt = jnp.float32(0.5 * H)
    solo = fused_advect_heun(vel, H, NU, dt, bc=bc)
    shard = fused_advect_heun_sharded(vel, H, NU, dt, make_mesh(2),
                                      bc=bc)
    assert float(jnp.max(jnp.abs(shard - solo))) <= 1e-11


@pytest.mark.slow
def test_sharded_sim_trajectory_matches_solo(monkeypatch):
    """End-to-end ISSUE-16 acceptance: ShardedUniformSim on the fused
    tier (2-device x-split, halo-mode kernel — the configuration the
    pre-16 tier REFUSED at construction) tracks the solo spmd_safe sim
    step for step to <= 1e-11 over 5 steps, and the tier string names
    the boundary table.

    slow, like PR 13's sharded FAS trajectory drill: full-sim sharded
    trajectories pay two interpret-mode shard_map compiles (~18 s on
    one CPU core).  The tier-1 pin for sharded == solo is the
    kernel-level bit-identity test above, which exercises the same
    halo-mode kernel without the sim scaffolding."""
    from cup2d_tpu.cases import channel_table
    from cup2d_tpu.parallel.mesh import ShardedUniformSim, make_mesh
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    bc = channel_table(1.0, profile="parabolic")
    cfg = _cfg32()
    solo = UniformSim(cfg, level=2, spmd_safe=True, bc=bc)
    solo.state = taylor_green_state(solo.grid)
    sh = ShardedUniformSim(cfg, make_mesh(2), level=2, bc=bc)
    assert sh.kernel_tier == \
        "pallas-fused+bc(in(1,0)[parabolic],out,fs,fs)"
    sh.set_state(taylor_green_state(sh.grid))
    dt = 0.25 * solo.grid.h
    for _ in range(5):
        solo.step_once(dt)
        sh.step_once(dt)
    dv = np.abs(np.asarray(sh.state.vel)
                - np.asarray(solo.state.vel)).max()
    assert dv <= 1e-11, dv


# ---------------------------------------------------------------------------
# tier latch + composition pins (the use_pallas composition gap, closed
# LOUDLY — ISSUE 9 satellite)
# ---------------------------------------------------------------------------

def test_sharded_x_split_constructs_fused_tier(monkeypatch):
    """ISSUE 16 retired the PR-9 construction refusal: the sharded
    x-split now routes to the halo-mode kernel (edge-column ppermutes
    feed a per-shard halo operand before the strip pipeline), so
    spmd_safe construction with the tier requested SUCCEEDS and latches
    pallas-fused — the pre-16 ValueError("sharded ...") is gone."""
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    g = UniformGrid(_cfg32(), level=2, spmd_safe=True)
    assert g.kernel_tier == "pallas-fused"


def test_tier_activates_for_spatial_fleet(monkeypatch):
    """The fleet's spatial placement is a mesh caller: big grids fall
    back to the x-split, and with the fused tier requested that now
    takes the SAME halo-mode kernel instead of the pre-16 loud
    refusal."""
    from cup2d_tpu.fleet import FleetSim
    from cup2d_tpu.parallel.mesh import make_mesh
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    fleet = FleetSim(_cfg32(), level=3, members=2, mesh=make_mesh(8),
                     member_cells_cap=0)   # force the spatial branch
    assert fleet.placement == "spatial"
    assert fleet.kernel_tier == "pallas-fused"


def test_kernel_supports_refuses_unknown_kind_naming_the_token():
    """The ONE remaining refusal (kernel_supports): a ghost kind with
    no in-VMEM synthesis fails at construction time, loudly, naming
    the offending face, kind and the full table token."""
    from cup2d_tpu.bc import BCTable, FaceBC
    from cup2d_tpu.ops.pallas_kernels import kernel_supports
    bad = BCTable(FaceBC("periodic"), FaceBC(), FaceBC(), FaceBC())
    with pytest.raises(ValueError) as ei:
        kernel_supports(bad)
    msg = str(ei.value)
    assert "x_lo" in msg and "periodic" in msg and bad.token in msg


def test_tier_activates_for_member_batched_fleet(monkeypatch):
    """Member placement keeps spatial axes whole, so the fleet gets the
    fused tier — the kernel is leading-dim agnostic by construction."""
    from cup2d_tpu.fleet import FleetSim
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    fleet = FleetSim(_cfg32(), level=2, members=2)
    assert fleet.kernel_tier == "pallas-fused"
    assert fleet.prec_mode == "f32"


def test_bf16_requires_the_fused_tier(monkeypatch):
    """bf16 is a storage property of the megakernel's HBM operands —
    meaningless without the tier, so requesting it tier-less is loud."""
    monkeypatch.delenv("CUP2D_PALLAS", raising=False)
    monkeypatch.setenv("CUP2D_PREC", "bf16")
    with pytest.raises(ValueError, match="CUP2D_PALLAS"):
        UniformGrid(_cfg32(), level=2)


def test_bf16_refuses_unsupported_shape(monkeypatch):
    """An explicit precision request must never silently degrade: the
    bf16 tier needs ny % 16 strips, and an 8-row grid gets a ValueError
    where the f32 tier's shape miss keeps the historical silent-XLA
    fallback (asserted below)."""
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.setenv("CUP2D_PREC", "bf16")
    with pytest.raises(ValueError, match="bf16"):
        UniformGrid(_cfg32(), level=0)     # ny = 8


def test_bad_prec_token_is_loud(monkeypatch):
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.setenv("CUP2D_PREC", "fp8")
    with pytest.raises(ValueError, match="f32|bf16"):
        UniformGrid(_cfg32(), level=2)


def test_f32_shape_miss_keeps_silent_xla_fallback(monkeypatch):
    """The f32 tier is an optimization, not a semantic: a dtype/shape
    miss falls back to the XLA chain exactly like pre-PR-9 CUP2D_PALLAS
    behavior (only EXPLICIT bf16 requests refuse)."""
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    g = UniformGrid(_cfg32(dtype="float64"), level=2)
    assert g.kernel_tier == "xla" and not g.use_pallas
    assert g.prec_mode == "f64"


def test_telemetry_carries_kernel_tier(monkeypatch):
    """Schema v6: the record's kernel_tier/prec_mode come from the
    sim's latch (the xla/f64 side is pinned in test_telemetry.py)."""
    from cup2d_tpu.profiling import MetricsRecorder
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    sim = UniformSim(_cfg32(), level=2)
    assert sim.kernel_tier == "pallas-fused"
    sim.state = taylor_green_state(sim.grid)
    rec = MetricsRecorder()
    rec.prime(sim)
    diag = sim.step_once(0.25 * sim.grid.h)
    r = rec.record(sim, diag)
    assert r["kernel_tier"] == "pallas-fused"
    assert r["prec_mode"] == "f32"


# ---------------------------------------------------------------------------
# bf16 storage tier: Taylor-Green tolerance golden, watchdog armed
# ---------------------------------------------------------------------------

def test_bf16_taylor_green_watchdog_golden(tmp_path, monkeypatch):
    """10 guarded steps of the 32x32 Taylor-Green on the bf16 tier vs
    the f32 XLA reference at the SAME fixed dt: the trajectory stays in
    the bf16 band (<= 2e-2; the one-step measurement is ~3.2e-3) and
    the for_prec('bf16') watchdog — widened settle ratios, doubled
    div_factor — arms on the settled flow WITHOUT a false trip (a trip
    would show as a recovery event and a forked trajectory)."""
    from cup2d_tpu.resilience import EventLog, PhysicsWatchdog, StepGuard
    monkeypatch.delenv("CUP2D_PALLAS", raising=False)
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    cfg = _cfg32()
    ref = UniformSim(cfg, level=2)         # xla tier, f32
    ref.state = taylor_green_state(ref.grid)

    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.setenv("CUP2D_PREC", "bf16")
    sim = UniformSim(cfg, level=2)
    assert sim.kernel_tier == "pallas-fused-bf16"
    assert sim.prec_mode == "bf16"
    sim.state = taylor_green_state(sim.grid)

    wd = PhysicsWatchdog.for_prec(sim.prec_mode, window=4)
    assert (wd.div_factor, wd.div_settle) == (100.0, 8.0)  # bf16 band
    log = EventLog(str(tmp_path / "events.jsonl"))
    guard = StepGuard(sim, watchdog=wd, event_log=log)
    dt = 0.25 * sim.grid.h                 # fixed: same clock both runs
    for _ in range(10):
        guard.step(dt)
        ref.step_once(dt)
    guard.drain()
    assert sim.step_count == 10

    # armed, and no false trip
    assert wd._armed(wd.umax, wd.umax_settle) is not None
    with open(tmp_path / "events.jsonl") as f:
        evs = [json.loads(ln) for ln in f if ln.strip()]
    assert not [e for e in evs if e.get("event") == "recovery"], evs

    dv = np.abs(np.asarray(sim.state.vel)
                - np.asarray(ref.state.vel)).max()
    assert 0.0 < dv <= 2e-2, dv            # really bf16, inside band
    assert np.all(np.isfinite(np.asarray(sim.state.vel)))


def test_fused_tier_supported_strip_rules():
    """The support predicate the constructors latch on: sublane-tile
    strip heights (8 rows f32, 16 rows bf16), lane alignment enforced
    only on real accelerators (interpret mode has no lane tiling)."""
    assert fused_tier_supported(32, 64, prec="f32")
    assert fused_tier_supported(8, 64, prec="f32")
    assert not fused_tier_supported(12, 64, prec="f32")   # ny % 8
    assert fused_tier_supported(32, 64, prec="bf16")
    assert not fused_tier_supported(8, 64, prec="bf16")   # ny % 16
    # the scoped-VMEM rule (what the chip's compiler says, per column
    # of a full-row strip): 8192-wide rows fit the requested limit in
    # both precisions; at 16384 the f32 strip still fits and the bf16
    # one is refused — tests/test_chip_compile.py compiles the passing
    # widths for real
    assert fused_tier_supported(8192, 8192, prec="f32")
    assert fused_tier_supported(8192, 8192, prec="bf16")
    assert fused_tier_supported(16384, 16384, prec="f32")
    assert not fused_tier_supported(16384, 16384, prec="bf16")
