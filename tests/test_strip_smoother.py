"""Memory-tiered FAS tests (ISSUE 19): the fused Pallas strip
smoother vs the XLA sweep chain (~1-ulp, all operand families), the
bf16-leg cycle tier (same f32 true-residual criterion, iters within
+1), the fused forest block-Jacobi update, the sharded halo strip
form, the driver latch composition with loud refusals, and the
for_prec watchdog band on the bf16-leg cavity case.

CPU boxes run every Pallas kernel in interpret mode (the real kernel
body through the interpreter) — parity bounds are identical there by
construction; only ms figures need hardware."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup2d_tpu.config import SimConfig
from cup2d_tpu.ops.pallas_kernels import (block_update_supported,
                                          fused_block_jacobi_update,
                                          fused_jacobi_sweeps,
                                          fused_mg_down, fused_mg_up,
                                          jacobi_strip_supported,
                                          mg_leg_supported)
from cup2d_tpu.ops.stencil import (_edge_ones, laplacian5_bc,
                                   laplacian5_neumann)
from cup2d_tpu.poisson import (MultigridPreconditioner,
                               apply_block_precond_blocks, bicgstab,
                               block_precond_matrix, mg_solve)

SIGNED = (1.0, -1.0, 1.0, 1.0)


def _xla_chain(e, r, omega, n, edge_signs=None, from_zero=False):
    """The exact _smooth arithmetic: stencil laplacian + the fori-body
    grouping e + omega*(r - lap)*inv_d, from_zero shortcut included."""
    ny, nx = r.shape[-2:]
    if edge_signs is None:
        ey, ex = _edge_ones(ny, r.dtype), _edge_ones(nx, r.dtype)
        lap = laplacian5_neumann
    else:
        sx_lo, sx_hi, sy_lo, sy_hi = edge_signs
        ey = _edge_ones(ny, r.dtype, lo=sy_lo, hi=sy_hi)
        ex = _edge_ones(nx, r.dtype, lo=sx_lo, hi=sx_hi)
        lap = lambda p: laplacian5_bc(p, *edge_signs)
    inv_d = 1.0 / (ey[:, None] + ex[None, :] - 4.0)
    if from_zero and n > 0:
        e = omega * r * inv_d
        n -= 1
    for _ in range(n):
        e = e + omega * (r - lap(e)) * inv_d
    return e


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       dtype)


# ---------------------------------------------------------------------------
# f32 parity: all three operand families, chains 1..6, both BC signs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(32, 128),      # solo grid
                                   (4, 32, 128),   # fleet member batch
                                   (2, 2, 16, 256)])  # nested lead
def test_strip_parity_f32_operand_families(shape):
    """~1-ulp vs the XLA sweep chain (the only allowed delta is FMA
    contraction inside the compiled stencil), every chain depth the
    cycle uses, from_zero both ways, Neumann and signed walls."""
    omega = 0.8
    for n in (1, 2, 3, 6):
        for fz in (False, True):
            for signs in (None, SIGNED):
                r = _rand(shape, 7 * n + fz)
                e = _rand(shape, 100 + n)
                ref = _xla_chain(e, r, omega, n, signs, fz)
                got = fused_jacobi_sweeps(e, r, omega, n,
                                          edge_signs=signs,
                                          from_zero=fz)
                assert got.shape == ref.shape
                assert got.dtype == ref.dtype
                tol = 1e-6 * float(jnp.max(jnp.abs(ref)))
                assert float(jnp.max(jnp.abs(got - ref))) <= tol, \
                    (shape, n, fz, signs)


def test_strip_gate():
    """The optimization gate: f32/bf16 only, sublane-aligned strips,
    bounded chain depth. A False is a silent XLA fallback by design
    (MultigridPreconditioner demotes truthfully, below)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert jacobi_strip_supported(32, 128, f32, 3)
    assert jacobi_strip_supported(16, 128, bf16, 3)
    assert not jacobi_strip_supported(33, 128, f32, 1)   # ny % by
    assert not jacobi_strip_supported(8, 128, bf16, 1)   # ny < by
    assert not jacobi_strip_supported(32, 128, f32, 7)   # depth cap
    assert not jacobi_strip_supported(32, 128, f32, 0)
    assert not jacobi_strip_supported(32, 128, jnp.float64, 2)


def test_strip_bf16_storage_f32_accumulate():
    """bf16 legs: storage dtype rides the operands, one rounding per
    sweep — the result tracks the f32 chain to bf16 resolution."""
    r = _rand((32, 128), 3).astype(jnp.bfloat16)
    e = _rand((32, 128), 4).astype(jnp.bfloat16)
    got = fused_jacobi_sweeps(e, r, 0.8, 2)
    assert got.dtype == jnp.bfloat16
    ref = _xla_chain(e.astype(jnp.float32), r.astype(jnp.float32),
                     0.8, 2)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))
    assert err <= 2e-2 * float(jnp.max(jnp.abs(ref)))


# ---------------------------------------------------------------------------
# hierarchy integration: cycle parity, truthful tier label, demotion
# ---------------------------------------------------------------------------

F32, BF16 = jnp.float32, jnp.bfloat16
# one bf16 storage rounding (half an ulp of an 8-bit mantissa, on the
# field's largest value) — what a fused leg may differ by from the same
# arithmetic rounded at the same storage points
BF16_ROUNDING = 2.0 ** -8


def _legs_reference(r, e0, ec, signs, from_zero, n=2, omega=0.8):
    """The XLA legs of ``MultigridPreconditioner._cycle`` with f32
    arithmetic and ONE rounding wherever a strip is stored (each
    sweep's result, the prolonged sum, the restricted residual): for
    f32 operands these
    are the XLA legs themselves, for bf16 operands the legs as the
    fused kernels define them."""
    store = r.dtype
    f = lambda a: a.astype(F32)
    rf = f(r)

    def sweeps(e, k, fz):
        for m in range(k):
            e = f(_xla_chain(e, rf, omega, 1, signs,
                             fz and m == 0).astype(store))
        return e

    lap = (laplacian5_neumann if signs is None
           else (lambda p: laplacian5_bc(p, *signs)))
    down = sweeps(None if from_zero else f(e0), n, from_zero)
    res = rf - lap(down)
    rows = res[..., 0::2, :] + res[..., 1::2, :]
    rc = (rows[..., :, 0::2] + rows[..., :, 1::2]).astype(store)
    start = f(e0) + jnp.repeat(jnp.repeat(f(ec), 2, axis=-2), 2,
                               axis=-1)
    up = sweeps(f(start.astype(store)), n, False)
    return down.astype(store), rc, up.astype(store)


def _close(got, ref, dtype):
    assert got.dtype == ref.dtype and got.shape == ref.shape
    tol = 2e-6 if dtype == F32 else BF16_ROUNDING
    g, x = got.astype(F32), ref.astype(F32)
    return float(jnp.max(jnp.abs(g - x))) <= tol * float(
        jnp.max(jnp.abs(x)))


# leading dims 1 and 3; the batch of 3 on an ODD strip count (96 / 32)
LEG_SHAPES = {"lead1": (1, 64, 256), "lead3-3strips": (3, 96, 256)}


@pytest.mark.parametrize("shape", sorted(LEG_SHAPES))
@pytest.mark.parametrize("signs", [None, SIGNED],
                         ids=["neumann", "one-dirichlet-face"])
@pytest.mark.parametrize("from_zero", [True, False])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_fused_down_leg_matches_xla_legs(dtype, from_zero, signs, shape):
    """sweeps + residual + restriction in one pipeline against the XLA
    legs it replaces: f32 in the file's ~1-ulp band, bf16 within one
    storage rounding."""
    shp = LEG_SHAPES[shape]
    r, e0 = _rand(shp, 31, dtype), _rand(shp, 32, dtype)
    ec = jnp.zeros(shp[:-2] + (shp[-2] // 2, shp[-1] // 2), dtype)
    e_ref, rc_ref, _ = _legs_reference(r, e0, ec, signs, from_zero)
    e, rc = fused_mg_down(None if from_zero else e0, r, 0.8, 2,
                          edge_signs=signs, from_zero=from_zero)
    assert _close(e, e_ref, dtype)
    assert _close(rc, rc_ref, dtype)


@pytest.mark.parametrize("shape", sorted(LEG_SHAPES))
@pytest.mark.parametrize("signs", [None, SIGNED],
                         ids=["neumann", "one-dirichlet-face"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_fused_up_leg_matches_xla_legs(dtype, signs, shape):
    """prolongation + sweeps in one pipeline against the XLA legs."""
    shp = LEG_SHAPES[shape]
    r, e0 = _rand(shp, 41, dtype), _rand(shp, 42, dtype)
    ec = _rand(shp[:-2] + (shp[-2] // 2, shp[-1] // 2), 43, dtype)
    _, _, up_ref = _legs_reference(r, e0, ec, signs, False)
    up = fused_mg_up(e0, r, ec, 0.8, 2, edge_signs=signs)
    assert _close(up, up_ref, dtype)


def test_leg_gate():
    """Whole 32-row strips and whole 256-lane pair chunks on top of
    the sweep-chain gate; a False is the silent XLA fall-back."""
    assert mg_leg_supported(64, 256, F32, 2)
    assert mg_leg_supported(32, 512, BF16, 2)
    assert not mg_leg_supported(48, 256, F32, 2)     # ny % 32
    assert not mg_leg_supported(64, 128, F32, 2)     # nx % 256
    assert not mg_leg_supported(64, 256, jnp.float64, 2)
    assert not mg_leg_supported(64, 256, F32, 7)     # depth cap


# ---------------------------------------------------------------------------
# hierarchy integration: cycle parity, truthful tier label, selection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_mg_cycle_strip_matches_xla(dtype):
    """One full V-cycle through the strip tier (fused legs at the
    finest level, fused sweep chains below it, XLA at the coarse end)
    against the XLA hierarchy, and the truthful labels."""
    b = _rand((128, 256), 11)
    mgx = MultigridPreconditioner(128, 256, F32, cycle_dtype=dtype,
                                  smoother="xla")
    mgs = MultigridPreconditioner(128, 256, F32, cycle_dtype=dtype,
                                  smoother="strip")
    suffix = "+bf16" if dtype == BF16 else ""
    assert (mgx.smoother_tier, mgs.smoother_tier) == (
        "xla", "strip" + suffix)
    assert (mgx.fused_levels, mgs.fused_levels) == (0, 1)
    cx, cs = mgx(b), mgs(b)
    assert cs.dtype == cx.dtype == F32         # out_dtype restored
    # bf16: a cycle's worth of storage roundings taken at other points
    tol = (2e-6 if dtype == F32 else 3e-2) * float(jnp.max(jnp.abs(cx)))
    assert float(jnp.max(jnp.abs(cs - cx))) <= tol


def test_fcycle_strip_matches_xla():
    """The F-cycle hands the down-leg a prolonged initial guess (the
    leg's e-given form) — same parity."""
    b = _rand((64, 256), 12)
    mgx = MultigridPreconditioner(64, 256, F32, cycle_dtype=F32,
                                  smoother="xla")
    mgs = MultigridPreconditioner(64, 256, F32, cycle_dtype=F32,
                                  smoother="strip")
    assert mgs.fused_levels == 1
    cx, cs = mgx.fcycle(b), mgs.fcycle(b)
    assert float(jnp.max(jnp.abs(cs - cx))) <= 2e-6 * float(
        jnp.max(jnp.abs(cx)))


def test_strip_tier_labels():
    """The shape-gate demotion and the leg-suffix composition."""
    # unsupported finest shape: truthful demotion, identical results
    mgd = MultigridPreconditioner(36, 36, F32, cycle_dtype=F32,
                                  smoother="strip")
    assert (mgd.smoother_tier, mgd.fused_levels) == ("xla", 0)
    # bf16 legs survive a demotion in the label (no hidden tier)
    mgdb = MultigridPreconditioner(36, 36, F32, cycle_dtype=F32,
                                   leg_dtype=BF16, smoother="strip")
    assert mgdb.smoother_tier == "xla+bf16"
    mgb = MultigridPreconditioner(128, 256, F32, cycle_dtype=F32,
                                  leg_dtype=BF16, smoother="strip")
    assert mgb.smoother_tier == "strip+bf16"
    # sweep chains fused, legs not (128 lanes hold no pair chunk)
    mgw = MultigridPreconditioner(64, 128, F32, cycle_dtype=F32,
                                  smoother="strip")
    assert (mgw.smoother_tier, mgw.fused_levels) == ("strip", 0)


def _mesh8():
    from jax.sharding import Mesh
    if jax.device_count() < 8:
        pytest.skip("needs the 8 forced host devices")
    return Mesh(np.array(jax.devices()[:8]), ("x",))


SELECTION = {
    # what the hierarchy picks for itself (smoother=None) ...
    "default-cpu": (dict(), "xla"),
    # ... and what a forced strip tier falls back to, silently
    "periodic-axis": (dict(smoother="strip", periodic=(True, False),
                           edge_signs=(0.0, 0.0, 1.0, 1.0)), "xla"),
    "f64": (dict(smoother="strip", dtype=jnp.float64), "xla"),
    "ny-not-strips": (dict(smoother="strip", ny=36), "xla"),
    "partitioned-operands": (dict(smoother="strip", spmd_safe=True),
                             "xla"),
    "forced": (dict(smoother="strip"), "strip"),
}


@pytest.mark.parametrize("case", sorted(SELECTION))
def test_smoother_selection(case):
    """Selection by what the code can see: no knob. Every fall-back is
    silent and reported."""
    kw, tier = SELECTION[case]
    kw = dict(kw)
    ny, dtype = kw.pop("ny", 128), kw.pop("dtype", F32)
    mg = MultigridPreconditioner(ny, 256, dtype, cycle_dtype=dtype, **kw)
    assert mg.smoother_tier == tier
    assert (mg.fused_levels > 0) == (tier == "strip")


def test_smoother_selection_mesh_attached():
    """A mesh-attached hierarchy fuses no legs: picking for itself it
    stays XLA, and handed the strip tier it runs what it always has
    (the halo strip sweep of its overlapped levels)."""
    mesh = _mesh8()
    auto = MultigridPreconditioner(128, 1024, F32, cycle_dtype=F32,
                                   mesh=mesh)
    assert (auto.smoother_tier, auto.fused_levels) == ("xla", 0)
    armed = MultigridPreconditioner(128, 1024, F32, cycle_dtype=F32,
                                    mesh=mesh, smoother="strip")
    assert (armed.smoother_tier, armed.fused_levels) == ("strip", 0)


@pytest.mark.parametrize("ny,nx,dtype", [(256, 256, BF16),
                                         (512, 1024, F32),
                                         (1024, 2048, BF16),
                                         (96, 768, F32)])
def test_fused_level_counter_equals_gate(ny, nx, dtype):
    mg = MultigridPreconditioner(ny, nx, F32, cycle_dtype=dtype,
                                 smoother="strip")
    # the finest three levels (63/64 of the cells), where the gate
    # admits them
    admitted = [lvl < 3 and mg_leg_supported(y, x, dtype, 2)
                for lvl, (y, x) in enumerate(mg.shapes[:-1])]
    assert mg.fused_levels == sum(admitted) >= 1
    assert mg._leg_fused == admitted


def test_bicgstab_same_iterations_either_hierarchy():
    """BiCGSTAB preconditioned by the default bf16 cycle reaches the
    production tolerance in the same iteration count with the strip
    hierarchy as with the XLA one (256^2, all-Neumann)."""
    n = 256
    x = (np.arange(n) + 0.5) / n
    X, Y = np.meshgrid(x, x)
    b = jnp.asarray(np.cos(2 * np.pi * X) * np.cos(3 * np.pi * Y)
                    + 0.3 * np.cos(9 * np.pi * X) * np.cos(np.pi * Y),
                    F32)
    iters = {}
    for tier in ("xla", "strip"):
        mg = MultigridPreconditioner(n, n, F32, smoother=tier)
        assert mg.dtype == BF16
        res = jax.jit(lambda rhs, mg=mg: bicgstab(
            laplacian5_neumann, rhs, M=mg, tol=1e-4, tol_rel=1e-3,
            max_iter=50))(b)
        assert bool(res.converged), tier
        iters[tier] = int(res.iters)
    assert iters["strip"] == iters["xla"] > 0, iters


def multiscale_state(grid):
    """O(1) velocity with genuine multi-scale divergence: a shear-layer
    pair, a mid-scale mode, and a non-solenoidal mode at a FIXED 64
    cells/wavelength, which keeps the Poisson load resolution-invariant
    (undivided divergence ~ A^2 * h * k stays constant when k grows
    with N). Free-slip-compatible normal components (sin -> 0 at the
    walls) keep the box BCs consistent."""
    x, y = grid.cell_centers()
    lx, ly = grid.cfg.extents
    xs, ys = np.pi * x / lx, np.pi * y / ly
    m = max(grid.nx // 64, 32)
    u = (np.sin(xs) * np.cos(ys)
         + 0.25 * np.sin(8 * xs) * np.cos(8 * ys)
         + 0.3 * np.sin(m * xs) * np.sin(m * ys))
    v = (-np.cos(xs) * np.sin(ys)
         + 0.25 * np.sin(16 * ys) * np.sin(16 * xs)
         + 0.3 * np.sin(m * ys) * np.sin(m * xs))
    vel = jnp.asarray(np.stack([u, v]), dtype=grid.dtype)
    return grid.zero_state()._replace(vel=vel)


def test_bf16_leg_mg_solve_same_criterion():
    """The tentpole's convergence contract: bf16 legs under mg_solve's
    f32 true-residual outer loop converge by the SAME Linf criterion
    with iters within +1 of the f32-leg arm (iterative refinement —
    the legs only shape the correction). The probe is a REALISTIC
    RHS (vortex-field divergence at production tol_rel): on a
    white-noise RHS at tol_rel 1e-4 the bf16 correction's resolution
    floor costs 29-vs-19 cycles — the +1 claim is a claim about
    production solves, not adversarial spectra."""
    from cup2d_tpu.ops.stencil import divergence_rhs
    from cup2d_tpu.uniform import UniformGrid, pad_vector

    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, nu=4e-5, cfl=0.5, dtype="float32")
    grid = UniformGrid(cfg, level=4)        # 128^2 probe
    st = multiscale_state(grid)
    dt = jnp.asarray(0.5 * grid.h, grid.dtype)
    b = divergence_rhs(pad_vector(st.vel, 1), pad_vector(st.udef, 1),
                       st.chi, 1, grid.h, dt)
    arms = {}
    for name, kw in (("f32", {}),
                     ("bf16leg", {"leg_dtype": jnp.bfloat16})):
        mg = MultigridPreconditioner(grid.ny, grid.nx, grid.dtype,
                                     cycle_dtype=grid.dtype,
                                     smoother="strip", **kw)
        res = mg_solve(grid.laplacian, b, mg, tol=0.0, tol_rel=1e-3,
                       max_cycles=100)
        assert bool(res.converged), name
        arms[name] = int(res.iters)
    assert arms["bf16leg"] <= arms["f32"] + 1, arms


# ---------------------------------------------------------------------------
# fused forest block-Jacobi update
# ---------------------------------------------------------------------------

def test_block_jacobi_update_parity():
    assert block_update_supported(jnp.float32)
    assert not block_update_supported(jnp.float64)
    bs = 16
    p_inv = jnp.asarray(block_precond_matrix(bs), jnp.float32)
    for N in (1, 7, 130):
        e = _rand((N, bs, bs), N)
        r = _rand((N, bs, bs), N + 1)
        lap = _rand((N, bs, bs), N + 2)
        ref = e + apply_block_precond_blocks(r - lap, p_inv)
        got = fused_block_jacobi_update(e, r, lap, p_inv)
        tol = 2e-6 * float(jnp.max(jnp.abs(ref)))
        assert float(jnp.max(jnp.abs(got - ref))) <= tol, N


# ---------------------------------------------------------------------------
# sharded halo strip (8 forced host devices, conftest)
# ---------------------------------------------------------------------------

def test_sharded_strip_matches_gspmd_overlap():
    """The tier="strip" form of overlap_jacobi_sweeps (edge-column
    ppermutes FIRST, then the per-sweep halo strip kernel) against the
    pinned GSPMD overlap body — the in-kernel device-masked wall
    diagonal reproduces it exactly."""
    from cup2d_tpu.parallel.shard_halo import overlap_jacobi_sweeps

    mesh = _mesh8()
    ny, nx = 32, 1024
    e, r = _rand((ny, nx), 21), _rand((ny, nx), 22)
    ey, ex = _edge_ones(ny, r.dtype), _edge_ones(nx, r.dtype)
    inv_d = 1.0 / (ey[:, None] + ex[None, :] - 4.0)
    for n in (1, 3):
        ref = overlap_jacobi_sweeps(e, r, inv_d, 0.8, n, mesh,
                                    tier="xla")
        got = overlap_jacobi_sweeps(e, r, inv_d, 0.8, n, mesh,
                                    tier="strip")
        tol = 1e-6 * float(jnp.max(jnp.abs(ref)))
        assert float(jnp.max(jnp.abs(got - ref))) <= tol, n


# ---------------------------------------------------------------------------
# driver latch composition + loud refusals
# ---------------------------------------------------------------------------

def test_uniform_latch_composition(monkeypatch):
    """The hierarchy's smoother no longer follows CUP2D_PALLAS + fas
    (ISSUE 26): a CPU run picks XLA whatever the latches say, the fas
    bf16-leg tier still rides CUP2D_PREC, and an owner that hands the
    strip tier down gets it under Krylov and fas alike."""
    from cup2d_tpu.uniform import UniformGrid

    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, nu=4e-5, cfl=0.5, dtype="float32")
    monkeypatch.delenv("CUP2D_PALLAS", raising=False)
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    monkeypatch.setenv("CUP2D_POIS", "fas")
    assert UniformGrid(cfg, level=4).smoother_tier == "xla"
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    g = UniformGrid(cfg, level=4)
    assert g.smoother_tier == "xla" and g.mg.leg_dtype is None
    assert UniformGrid(cfg, level=4,
                       mg_smoother="strip").smoother_tier == "strip"
    monkeypatch.setenv("CUP2D_PREC", "bf16")
    g = UniformGrid(cfg, level=4)
    assert g.smoother_tier == "xla+bf16"
    assert g.mg.leg_dtype == jnp.bfloat16
    g = UniformGrid(cfg, level=4, mg_smoother="strip")
    assert g.smoother_tier == "strip+bf16"
    # Krylov: the preconditioner cycles keep their bf16-storage
    # default, and take the strip tier the same way
    monkeypatch.setenv("CUP2D_POIS", "")
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    assert UniformGrid(cfg, level=4).smoother_tier == "xla"
    g = UniformGrid(cfg, level=5, mg_smoother="strip")
    assert (g.smoother_tier, g.mg.fused_levels) == ("strip+bf16", 1)


def test_forest_latch_composition_and_refusals(monkeypatch):
    from cup2d_tpu.amr import AMRSim

    cfg = SimConfig(bpdx=2, bpdy=2, level_max=3, level_start=1,
                    extent=1.0, nu=4e-5, cfl=0.5, dtype="float32")
    monkeypatch.delenv("CUP2D_PALLAS", raising=False)
    monkeypatch.setenv("CUP2D_POIS", "fas")
    monkeypatch.setenv("CUP2D_PREC", "bf16")
    sim = AMRSim(cfg, shapes=[])
    assert sim._fas_leg_dtype == jnp.bfloat16
    assert sim.smoother_tier == "xla+bf16"
    monkeypatch.setenv("CUP2D_PALLAS", "1")
    assert AMRSim(cfg, shapes=[]).smoother_tier == "strip+bf16"
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    assert AMRSim(cfg, shapes=[]).smoother_tier == "strip"
    # refusals are LOUD: a latch that cannot route must not relabel
    monkeypatch.setenv("CUP2D_PREC", "bf16")
    monkeypatch.setenv("CUP2D_POIS", "structured")
    with pytest.raises(ValueError, match="CUP2D_POIS"):
        AMRSim(cfg, shapes=[])
    monkeypatch.setenv("CUP2D_POIS", "fas")
    cfg64 = SimConfig(bpdx=2, bpdy=2, level_max=3, level_start=1,
                      extent=1.0, nu=4e-5, cfl=0.5, dtype="float64")
    with pytest.raises(ValueError, match="f32 solver state"):
        AMRSim(cfg64, shapes=[])
    monkeypatch.setenv("CUP2D_PREC", "bf32")
    with pytest.raises(ValueError, match="CUP2D_PREC"):
        AMRSim(cfg, shapes=[])


def test_forest_bf16_leg_solve_iters(monkeypatch):
    """Forest FAS with bf16 ladder legs: a production step's solve
    converges with cycles within +1 of the f32-leg arm (the
    poisson_ab fas-bf16leg arm, tier-1-sized)."""
    from cup2d_tpu.amr import AMRSim

    monkeypatch.setenv("CUP2D_POIS", "fas")
    monkeypatch.delenv("CUP2D_PALLAS", raising=False)
    cfg = SimConfig(bpdx=2, bpdy=2, level_max=3, level_start=1,
                    extent=1.0, nu=4e-5, cfl=0.5, dtype="float32")
    iters = {}
    for prec in ("f32", "bf16"):
        if prec == "bf16":
            monkeypatch.setenv("CUP2D_PREC", "bf16")
        else:
            monkeypatch.delenv("CUP2D_PREC", raising=False)
        sim = AMRSim(cfg, shapes=[])
        sim.step_count = 20        # production regime (no exact mode)
        d = sim.step_once()
        assert bool(d["poisson_converged"]), prec
        iters[prec] = int(d["poisson_iters"])
    assert iters["bf16"] <= iters["f32"] + 1, iters


def test_forest_strip_block_smoother_dispatch(monkeypatch):
    """CUP2D_PALLAS=1 + fas routes the composite smoother's update
    tail through fused_block_jacobi_update; the step's solve agrees
    with the XLA form to solver tolerance."""
    from cup2d_tpu.amr import AMRSim

    monkeypatch.setenv("CUP2D_POIS", "fas")
    monkeypatch.delenv("CUP2D_PREC", raising=False)
    cfg = SimConfig(bpdx=2, bpdy=2, level_max=3, level_start=1,
                    extent=1.0, nu=4e-5, cfl=0.5, dtype="float32")
    press = {}
    for tier in ("xla", "strip"):
        if tier == "strip":
            monkeypatch.setenv("CUP2D_PALLAS", "1")
        else:
            monkeypatch.delenv("CUP2D_PALLAS", raising=False)
        sim = AMRSim(cfg, shapes=[])
        sim.step_count = 20
        d = sim.step_once()
        assert bool(d["poisson_converged"]), tier
        press[tier] = np.asarray(sim.forest.fields["pres"])
    scale = np.max(np.abs(press["xla"])) or 1.0
    assert np.max(np.abs(press["strip"] - press["xla"])) <= 1e-4 * scale


# ---------------------------------------------------------------------------
# watchdog band on the bf16-leg cavity case
# ---------------------------------------------------------------------------

def test_bf16_leg_cavity_watchdog(tmp_path, monkeypatch):
    """Guarded lid-driven cavity on the full bf16 composition
    (advection tier + FAS bf16 legs): the for_prec('bf16') band arms
    on the settling flow WITHOUT a false trip, and the telemetry
    record carries the smoother_tier latch."""
    from cup2d_tpu.cases import cavity_table
    from cup2d_tpu.profiling import MetricsRecorder
    from cup2d_tpu.resilience import (EventLog, PhysicsWatchdog,
                                      StepGuard)
    from cup2d_tpu.uniform import UniformSim

    monkeypatch.setenv("CUP2D_PALLAS", "1")
    monkeypatch.setenv("CUP2D_PREC", "bf16")
    monkeypatch.setenv("CUP2D_POIS", "fas")
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, nu=1e-3, cfl=0.4, dtype="float32",
                    max_poisson_iterations=60)
    sim = UniformSim(cfg, level=2, bc=cavity_table(1.0))
    assert sim.prec_mode == "bf16"
    # a CPU run keeps the XLA sweeps (ISSUE 26); the bf16 legs show
    assert sim.smoother_tier == "xla+bf16"

    wd = PhysicsWatchdog.for_prec(sim.prec_mode, window=4)
    assert (wd.div_factor, wd.div_settle) == (100.0, 8.0)
    log = EventLog(str(tmp_path / "events.jsonl"))
    guard = StepGuard(sim, watchdog=wd, event_log=log)
    dt = 0.25 * sim.grid.h                 # fixed clock, as the golden
    for _ in range(10):
        guard.step(dt)
    guard.drain()
    assert sim.step_count == 10
    # the v11 telemetry latch rides the record
    rec = MetricsRecorder()
    rec.prime(sim)
    r = rec.record(sim, sim.step_once(dt))
    assert r["smoother_tier"] == "xla+bf16"
    assert wd._armed(wd.umax, wd.umax_settle) is not None
    with open(tmp_path / "events.jsonl") as f:
        evs = [json.loads(ln) for ln in f if ln.strip()]
    assert not [e for e in evs if e.get("event") == "recovery"], evs
