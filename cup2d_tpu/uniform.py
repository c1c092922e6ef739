"""Uniform-grid execution path: the whole state is dense global arrays.

When every block sits at one level (the reference's levelMax=1 degenerate
case, and the oracle configuration for the AMR path), the TPU-idiomatic
representation is NOT a block forest but plain `[Ny, Nx]` arrays: stencils
become shifted slices XLA fuses into a few kernels, the Poisson solve is
matrix-free over the same arrays, and sharding is a one-line
`NamedSharding` over rows. This module is that path, end-to-end jitted.

It reproduces the reference timestep (`/root/reference/main.cpp:6576-7290`):
CFL dt control, two-stage Heun advection-diffusion (WENO5 + central
diffusion), Brinkman penalization, pressure projection with the deltap
formulation (initial guess = old pressure, main.cpp:7007-7027), and
free-slip / Neumann box boundaries (main.cpp:3126-3256).
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .bc import (
    FREE_SLIP,
    BCTable,
    divergence_affine_bc,
    divergence_coeffs,
    pad_vector_bc,
    periodic_axes,
    pressure_signs,
)
from . import tracing
from .config import SimConfig
from .ops.stencil import (
    advect_diffuse_rhs,
    divergence_bc,
    divergence_freeslip,
    divergence_rhs_fused,
    dt_from_umax,
    heun_substage,
    laplacian5_bc,
    laplacian5_neumann,
    vorticity,
)
from .poisson import (
    FFTDiagPlan,
    MultigridPreconditioner,
    apply_block_precond,
    bicgstab,
    block_precond_matrix,
    fft_diag_solve,
    mg_solve,
    project_correct,
)


# ---------------------------------------------------------------------------
# Ghost padding with the reference's physical BCs (main.cpp:3126-3256):
#  - vector: free-slip mirror — ghost takes the wall-adjacent cell's value
#    with the normal component negated (zeroth-order, like the reference)
#  - scalar: zero-Neumann copy of the wall-adjacent cell
# ---------------------------------------------------------------------------

def pad_scalar(p: jnp.ndarray, g: int) -> jnp.ndarray:
    """[..., Ny, Nx] -> [..., Ny+2g, Nx+2g], Neumann copy (ScalarLab)."""
    pad = [(0, 0)] * (p.ndim - 2) + [(g, g), (g, g)]
    return jnp.pad(p, pad, mode="edge")


def pad_vector(v: jnp.ndarray, g: int) -> jnp.ndarray:
    """[..., 2, Ny, Nx] -> [..., 2, Ny+2g, Nx+2g], free-slip mirror
    (VectorLab::applyBCface): u flips sign in x-ghost columns, v flips
    in y-ghost rows; corners compose both flips — exactly the
    reference's two-pass face sweep. Built as a ZERO pad (a fusible pad
    HLO) plus ghost-strip writes of the sign-flipped edge lines: the
    edge-mode pad + per-component strip multiplies this replaces cost
    4.8x more standalone at 8192^2/g=3 (75 -> 16 ms — each integer-
    indexed strip update materialized a full copy)."""
    pad = [(0, 0)] * (v.ndim - 2) + [(g, g), (g, g)]
    out = jnp.pad(v, pad)
    # per-component SLICE-indexed strip writes: integer component
    # indices materialize full copies, and a [2]-element sign-vector
    # constant costs a ~0.09 ms DMA staging per use on this chip
    # (3.5 ms/step traced) — the negation belongs in the expression.
    # y-ghosts copy u, flip v; x-ghosts flip u, copy v.
    out = out.at[..., 0:1, :g, g:-g].set(v[..., 0:1, :1, :])
    out = out.at[..., 1:2, :g, g:-g].set(-v[..., 1:2, :1, :])
    out = out.at[..., 0:1, -g:, g:-g].set(v[..., 0:1, -1:, :])
    out = out.at[..., 1:2, -g:, g:-g].set(-v[..., 1:2, -1:, :])
    # x strips read the y-padded columns so corners compose both flips
    out = out.at[..., 0:1, :, :g].set(-out[..., 0:1, :, g:g + 1])
    out = out.at[..., 1:2, :, :g].set(out[..., 1:2, :, g:g + 1])
    out = out.at[..., 0:1, :, -g:].set(-out[..., 0:1, :, -g - 1:-g])
    out = out.at[..., 1:2, :, -g:].set(out[..., 1:2, :, -g - 1:-g])
    return out


class FlowState(NamedTuple):
    """Device-side per-step state (the reference's 7 field grids,
    main.cpp:3264-3278, minus the scratch fields XLA fuses away; the
    previous pressure — the reference's ``pold`` — is just ``pres`` at
    entry to step()).

    ``us`` is the full solid velocity (rigid + deformation) targeted by
    penalization (main.cpp:6974-6975); ``udef`` is the *deformation-only*
    part entering the pressure RHS's chi*div(udef) term (main.cpp:6980-7006
    accumulates only o->udef — rigid motion is divergence-free and dropped).
    """

    vel: jnp.ndarray    # [2, Ny, Nx]
    pres: jnp.ndarray   # [Ny, Nx]
    chi: jnp.ndarray    # [Ny, Nx]
    us: jnp.ndarray     # [2, Ny, Nx]
    udef: jnp.ndarray   # [2, Ny, Nx]


def taylor_green_state(grid) -> "FlowState":
    """Taylor–Green vortex compatible with the free-slip box: u = sin cos,
    v = -cos sin has zero normal velocity at all four walls and decays
    analytically as exp(-2 nu pi^2 (1/Lx^2 + 1/Ly^2) t) — the validation
    case SURVEY.md §4 prescribes. Shared by the CLI's sharded run, the
    tests and __graft_entry__.py."""
    x, y = grid.cell_centers()
    lx, ly = grid.cfg.extents
    u = np.sin(np.pi * x / lx) * np.cos(np.pi * y / ly)
    v = -(ly / lx) * np.cos(np.pi * x / lx) * np.sin(np.pi * y / ly)
    vel = jnp.asarray(np.stack([u, v]), dtype=grid.dtype)
    return grid.zero_state()._replace(vel=vel)


class UniformGrid:
    """Geometry + jitted operators for one uniform resolution.

    ``use_pallas`` (or env CUP2D_PALLAS=1) swaps the whole advection +
    projection-correction chain for the fused Pallas megakernel tier
    (ops/pallas_kernels.fused_advect_heun): one HBM read, one write per
    RK substage. CUP2D_PREC=bf16 additionally stores the advection
    operands bf16 (f32 accumulation). On a CPU run the tier is in
    Pallas interpret mode — validation, not speed; on a TPU it is
    always compiled. XLA remains the default tier."""

    def __init__(self, cfg: SimConfig, level: Optional[int] = None,
                 use_pallas: Optional[bool] = None,
                 spmd_safe: bool = False,
                 bc: Optional[BCTable] = None,
                 mg_smoother: Optional[str] = None):
        # spmd_safe: the fused-BC stencil forms have a fast pad+slice
        # variant the SPMD partitioner miscompiles on sharded axes
        # (see ops/stencil._zshift); sharded sims set True
        self.spmd_safe = spmd_safe
        self.cfg = cfg
        # per-face boundary-condition table (bc.py, ISSUE 12): the
        # single source of truth for the box-edge treatment. None/
        # FREE_SLIP keeps every consumer on the UNMODIFIED legacy
        # expressions (bit-identity pinned in tests/test_bc.py).
        self.bc = (FREE_SLIP if bc is None else bc).validate()
        lvl = cfg.level_start if level is None else level
        if use_pallas is None:
            use_pallas = os.environ.get("CUP2D_PALLAS", "") == "1"
        # storage-precision latch for the fused tier (the ONE sanctioned
        # CUP2D_PREC read site — tests/test_env_latch.py): bf16 is a
        # property of the megakernel's HBM operands, meaningless without
        # the tier, so requesting it tier-less fails loudly.
        prec = os.environ.get("CUP2D_PREC", "") or "f32"
        if prec not in ("f32", "bf16"):
            raise ValueError(f"CUP2D_PREC={prec!r}: expected f32|bf16")
        if prec == "bf16" and not use_pallas:
            raise ValueError(
                "CUP2D_PREC=bf16 selects the bf16-storage variant of the "
                "fused Pallas tier; set CUP2D_PALLAS=1 (or use_pallas=True)"
                " or drop CUP2D_PREC")
        tier = "xla"
        if use_pallas:
            # capability check (ISSUE 16 retired the two construction
            # refusals): every bc.py ghost kind now has an in-VMEM
            # synthesis, and the sharded x-split routes through the
            # halo-mode kernel (shard_halo.fused_advect_heun_sharded,
            # dispatched in advect_heun once a mesh is attached) — only
            # a genuinely unsupported future kind refuses, loudly and
            # naming the token.
            from .ops.pallas_kernels import kernel_supports
            kernel_supports(self.bc)
            ny = cfg.bpdy * cfg.bs << lvl
            nx = cfg.bpdx * cfg.bs << lvl
            from .ops.pallas_kernels import fused_tier_supported
            ok = (jnp.dtype(cfg.dtype) == jnp.float32
                  and fused_tier_supported(ny, nx, prec=prec))
            if ok:
                tier = "pallas-fused-bf16" if prec == "bf16" \
                    else "pallas-fused"
            elif prec == "bf16":
                raise ValueError(
                    f"CUP2D_PREC=bf16 unsupported for this grid "
                    f"({cfg.dtype} {ny}x{nx}): the bf16 tier needs f32 "
                    "state, sublane-aligned strips (ny % 16 == 0) and "
                    "a row inside the kernel's VMEM budget "
                    "(pallas_kernels.fused_tier_supported)")
            # an f32 shape/dtype miss runs the XLA tier instead (the
            # tier is an optimization, not a semantic) — visibly: the
            # stamped kernel_tier says "xla", and chip_smoke.py asserts
            # the stamp equals the tier it asked for
        self._kernel_tier = tier
        self.use_pallas = tier != "xla"   # back-compat bool alias
        # device mesh of the sharded x-split (attach_mesh): routes the
        # fused tier through the halo-mode kernel wrapper
        self._mesh = None
        # Poisson solve-path latch (read ONCE here, the AMRSim.__init__
        # pattern — tests/test_env_latch.py sanctions this site): the
        # uniform/fleet/sharded-uniform drivers accept "fas"/"fas-f"
        # (matrix-free FAS multigrid replacing Krylov on production
        # solves, poisson.mg_solve; -f opens each solve with an
        # F-cycle); the forest-only tokens (structured/tables/fft) are
        # valid but inert here so one latched env serves a mixed
        # process. A typo must fail loudly, not silently measure the
        # default on both A/B arms.
        # "fftd" (ISSUE 20): FFT-diagonalized DIRECT solve — rides
        # THIS sanctioned read, no new latch site (the graftlint
        # assertion in tests/test_analysis.py pins that).
        pois = os.environ.get("CUP2D_POIS", "")
        if pois not in ("", "structured", "tables", "fft",
                        "fas", "fas-f", "fftd"):
            raise ValueError(
                f"CUP2D_POIS={pois!r}: expected "
                "structured|tables|fft|fas|fas-f|fftd")
        self.solver_mode = ("fftd" if pois == "fftd"
                            else "fas" if pois in ("fas", "fas-f")
                            else "bicgstab")
        self.fas_fmg = pois == "fas-f"
        # who chose a direct solve: "env" (CUP2D_POIS=fftd) or "table"
        # (the boundary table below); None under an iterative mode
        self.fftd_by = "env" if pois == "fftd" else None
        self.level = lvl
        self.nx = cfg.bpdx * cfg.bs << lvl
        self.ny = cfg.bpdy * cfg.bs << lvl
        self.h = cfg.h_at(lvl)
        self.dtype = jnp.dtype(cfg.dtype)
        self.p_inv = jnp.asarray(block_precond_matrix(cfg.bs), dtype=self.dtype)
        # derived per-face operator coefficients (None on the default
        # table => every consumer takes the legacy branch verbatim)
        if self.bc.is_free_slip:
            self._psigns = None
            self._dcoeffs = None
            self._div_affine = None
            self._paxes = (False, False)
        else:
            self._psigns = pressure_signs(self.bc)
            self._dcoeffs = divergence_coeffs(self.bc)
            self._div_affine = divergence_affine_bc(
                self.bc, self.ny, self.nx, self.dtype)
            # periodic axis flags (ISSUE 20): wrap shifts in the
            # operator/divergence/gradient stencils
            self._paxes = periodic_axes(self.bc)
        # the table picks the solver (ISSUE 35): a box that wraps on
        # BOTH axes diagonalizes completely, and one transform pair
        # with a spectral divide replaces the Krylov train (the chip:
        # turb2d-8192.solo against the cavity, PERF.md). An explicit
        # CUP2D_POIS wins; one periodic axis (the tridiagonal form)
        # and every wall table keep bicgstab+mg — no cell has timed
        # them; spatial axes that will be sharded (spmd_safe) have no
        # form of the transform (see attach_mesh); and the chip's
        # compiler refuses a float64 transform ("Unexpected operand
        # type for FFT: c128", PERF.md §6 PR 35), so f64 — the CPU
        # validation precision — keeps the solver that runs on both.
        if (pois == "" and self._paxes == (True, True) and not spmd_safe
                and self.dtype == jnp.float32):
            self.solver_mode, self.fftd_by = "fftd", "table"
        # FFT-diagonalized direct solve: the plan's
        # transforms/eigenvalues/tridiagonal elimination coefficients
        # are host-precomputed once per grid. Needs >= 1 periodic
        # direction — a wall-only box has nothing to diagonalize.
        if self.solver_mode == "fftd":
            px, py = self._paxes
            if not (px or py):
                raise ValueError(
                    f"CUP2D_POIS=fftd needs at least one periodic "
                    f"direction, got BCTable ({self.bc.token}): the "
                    "FFT diagonalizes a periodic axis's second "
                    "difference — run wall-only boxes under "
                    "bicgstab/fas")
            self._fft_plan = FFTDiagPlan(
                self.ny, self.nx, self.dtype, px, py, self._psigns)
        else:
            self._fft_plan = None
        # multigrid V-cycle preconditioner: O(1) Krylov iterations in N,
        # where the reference's single-level block-Jacobi (kept above for
        # the oracle/AMR paths) degrades linearly in N_1d/BS.
        # The FAS full-solver path runs the cycle at SOLVER precision:
        # as a preconditioner a bf16 cycle only shapes the error and
        # flexible BiCGSTAB absorbs the inexactness, but as THE solver
        # the cycle's floor caps the reachable residual (measured: f32
        # fields + bf16 cycles stall at ~2e-4 relative, above the 1e-4
        # bench target). f32 cycles double the per-cycle bytes; the
        # solve spends 2-4 cycles total vs Krylov's 2 M-applies x 8-11
        # iterations, so the byte TOTAL still drops.
        #
        # Memory-tiered FAS (ISSUE 19): the CUP2D_PREC composition
        # extends to the SOLVER side of the fas latch — bf16 lives on
        # the cycle's smoother/transfer LEGS only (leg_dtype), while
        # mg_solve's outer loop keeps the f32 true residual (iterative
        # refinement: the legs cannot floor the solve the way the
        # fully-bf16 solver above does); prec=bf16 without the Pallas
        # tier already refused above.
        #
        # The hierarchy picks its own smoother tier by what it can see
        # (ISSUE 26): the fused strip legs on an accelerator, under
        # Krylov and fas alike, XLA on a CPU run, under a mesh, on a
        # periodic table, in f64 or past the shape gate. ``mg_smoother``
        # lets an owner that shards the operands itself (FleetSim's
        # mesh placements) hold it to "xla", and a CPU test ask for the
        # interpreted kernels.
        self._fas_leg_dtype = (
            jnp.bfloat16
            if (prec == "bf16" and self.solver_mode == "fas")
            else None)
        self.mg = MultigridPreconditioner(
            self.ny, self.nx, self.dtype, spmd_safe=spmd_safe,
            cycle_dtype=(self.dtype if self.solver_mode == "fas"
                         else None),
            edge_signs=self._psigns,
            leg_dtype=self._fas_leg_dtype,
            smoother=mg_smoother,
            periodic=self._paxes)
        # f64 dot-product accumulation when fields are f32 AND x64 is
        # available (the Krylov scalars are precision-critical, SURVEY.md §7
        # hard part 5). Without x64, XLA's tree reduction keeps f32 error at
        # ~log(N)*eps, which holds to the reference's 1e-3 tolerance.
        self.sum_dtype = (
            jnp.float64
            if (self.dtype == jnp.float32 and jax.config.jax_enable_x64)
            else None
        )

    # -- coordinate helpers (cell centers) --
    def cell_centers(self):
        x = (np.arange(self.nx) + 0.5) * self.h
        y = (np.arange(self.ny) + 0.5) * self.h
        return np.meshgrid(x, y, indexing="xy")  # X[j,i], Y[j,i] -> [Ny, Nx]

    def zero_state(self) -> FlowState:
        # distinct buffers per field: the stepping jits donate the state,
        # and donating one aliased buffer through several fields is a
        # runtime error ("donate the same buffer twice")
        def z():
            return jnp.zeros((self.ny, self.nx), dtype=self.dtype)

        def zv():
            return jnp.zeros((2, self.ny, self.nx), dtype=self.dtype)

        return FlowState(vel=zv(), pres=z(), chi=z(), us=zv(), udef=zv())

    # -- dt control (main.cpp:6579-6595) --
    def dt_from_umax(self, umax) -> jnp.ndarray:
        return dt_from_umax(
            jnp.asarray(umax, self.dtype),
            jnp.asarray(self.h, self.dtype), self.cfg.nu, self.cfg.cfl)

    def compute_dt(self, vel: jnp.ndarray) -> jnp.ndarray:
        return self.dt_from_umax(jnp.max(jnp.abs(vel)))

    # -- Poisson operator: undivided 5-point Laplacian with the table's
    # per-face pressure rows (fused-BC form: zero-ghost shifts + rank-1
    # edge correction — see ops/stencil.laplacian5_neumann/_bc). The
    # default table takes the legacy all-Neumann expression verbatim.
    def laplacian(self, p: jnp.ndarray) -> jnp.ndarray:
        if self._psigns is None:
            return laplacian5_neumann(p, self.spmd_safe)
        sx_lo, sx_hi, sy_lo, sy_hi = self._psigns
        px, py = self._paxes
        return laplacian5_bc(p, sx_lo, sx_hi, sy_lo, sy_hi,
                             self.spmd_safe, px, py)

    # -- BC-aware ghost paint + divergence, shared with fleet.py's
    # inlined member-batched step so the table dispatch cannot
    # desynchronize between the solo and fleet paths --
    def pad_vector_field(self, v: jnp.ndarray, g: int,
                         dt=None) -> jnp.ndarray:
        """Velocity ghost paint per the table; the default table is the
        legacy free-slip mirror (``pad_vector``) unchanged. ``dt``
        feeds the convective-outflow extrapolation speed (None degrades
        outflow to zeroth-order — diagnostics only)."""
        if self.bc.is_free_slip:
            return pad_vector(v, g)
        return pad_vector_bc(v, g, self.bc, self.h, dt)

    def poisson_rhs(self, vel, chi, udef, dt) -> jnp.ndarray:
        """(h/2dt)[div u* - chi div u_def] with the table's per-face
        edge coefficients + the prescribed wall-normal-velocity affine
        term (bc.divergence_affine_bc). ``chi=None`` drops the
        obstacle term. Default table = the legacy fused expressions
        bit-identically."""
        h = self.h
        if self._dcoeffs is None:
            if chi is None:
                return (0.5 * h / dt) * divergence_freeslip(
                    vel, self.spmd_safe)
            return divergence_rhs_fused(vel, udef, chi, h, dt,
                                        self.spmd_safe)
        fac = 0.5 * h / dt
        b = fac * divergence_bc(vel, *self._dcoeffs, self.spmd_safe,
                                *self._paxes)
        if self._div_affine is not None:
            b = b + fac * self._div_affine
        if chi is not None:
            b = b - (fac * chi) * divergence_bc(
                udef, *self._dcoeffs, self.spmd_safe, *self._paxes)
        return b

    def precond(self, r: jnp.ndarray) -> jnp.ndarray:
        return apply_block_precond(r, self.p_inv, self.cfg.bs)

    def exact_request(self, startup: bool, forced: bool):
        """The static ``exact_poisson`` value of one step, truthy for
        the ten tol-0 start-up steps and for the supervision ladder's
        escalate rung (``_force_exact``). Where the direct solve is
        the production method the rung must not run again the method
        whose verdict just failed: it names the Krylov backstop — its
        own step variant, compiled on its first use only."""
        if forced and self.solver_mode == "fftd":
            return "krylov"
        return bool(startup or forced)

    def runs_direct(self, exact) -> bool:
        """Whether a solve under request ``exact`` is the plan's one
        application (every request but the ladder's "krylov")."""
        return self.solver_mode == "fftd" and exact != "krylov"

    @property
    def poisson_mode(self) -> str:
        """The active solve-path latch, for the telemetry stream
        (schema v4 ``poisson_mode``; v12 adds the fftd vocabulary):
        ``fftd`` = pure spectral divide (both directions periodic),
        ``fftd+tridiag`` = per-mode Thomas systems (one periodic)."""
        if self.solver_mode == "fftd":
            return "fftd" if (self._paxes[0] and self._paxes[1]) \
                else "fftd+tridiag"
        if self.solver_mode == "fas":
            return "fas-f" if self.fas_fmg else "fas"
        return "bicgstab+mg" if self.cfg.precond else "bicgstab"

    @property
    def kernel_tier(self) -> str:
        """Active advection-kernel tier latch (telemetry schema v6):
        xla | pallas-fused | pallas-fused-bf16, with the BC token
        suffixed on BC'd fused tiers (ISSUE 16, e.g.
        ``pallas-fused+bc(in,out,fs,fs)``) — the suffix IS the
        executable identity (one compile per token). Internal
        dispatch compares the bare ``_kernel_tier`` latch."""
        if self._kernel_tier != "xla" and not self.bc.is_free_slip:
            return f"{self._kernel_tier}+bc({self.bc.token})"
        return self._kernel_tier

    @property
    def prec_mode(self) -> str:
        """Storage-precision contract of the advection hot loop
        (telemetry schema v6): the bf16 tier stores HBM operands bf16
        (f32 accumulation); otherwise the state dtype."""
        if self._kernel_tier == "pallas-fused-bf16":
            return "bf16"
        return {"float32": "f32", "float64": "f64"}.get(
            self.dtype.name, self.dtype.name)

    @property
    def smoother_tier(self) -> str:
        """Active smoother tier of the pressure hierarchy (telemetry
        schema v11): ``xla`` (sweep-chain lowered by XLA), ``strip``
        (fused Pallas strip pipeline, f32 legs), or ``strip+bf16``
        (strip pipeline over bf16-storage legs). Reported by the
        preconditioner itself so shape-gate demotions stay truthful."""
        return self.mg.smoother_tier

    @property
    def bc_table(self) -> str:
        """Compact per-face BC token string (telemetry schema v8)."""
        return self.bc.token

    def attach_mesh(self, mesh) -> None:
        """Record the device mesh of the sharded x-split. The fused
        advection tier then dispatches through the halo-mode kernel
        (shard_halo.fused_advect_heun_sharded: edge-column ppermutes
        issued before the strip pipeline); the FAS path additionally
        rebuilds its MG hierarchy so the finest-level smoothing sweeps
        use the explicit overlapped ppermute exchange
        (shard_halo.overlap_jacobi_sweeps). The default Krylov
        preconditioner cycles stay on the GSPMD form whose
        sharded==single equality is already pinned."""
        if self.fftd_by == "table":
            # a direct solve the TABLE picked gives way to the mesh:
            # the grid runs bicgstab+mg, as a sharded periodic box
            # always has (self.mg was built for exactly that)
            self.solver_mode, self.fftd_by = "bicgstab", None
            self._fft_plan = None
        if self.solver_mode == "fftd":
            # documented refusal (ISSUE 20): the FFT transform and the
            # per-mode tridiagonal scan are whole-array sequential
            # along their axes — the mesh's x-split always shards one
            # of them (periodic x: the transform axis; periodic y
            # only: the scan axis), and neither has a shard_map form
            # (parallel/shard_halo.py). Sharded periodic cases run
            # under bicgstab/fas, whose wrap stencils GSPMD partitions
            # correctly.
            raise ValueError(
                "CUP2D_POIS=fftd cannot attach a device mesh: the "
                "x-split shards the FFT transform axis (periodic x) "
                "or the tridiagonal scan axis (periodic y) — run "
                "sharded periodic cases under bicgstab/fas")
        self._mesh = mesh
        if self.solver_mode == "fas":
            self.mg = MultigridPreconditioner(
                self.ny, self.nx, self.dtype,
                spmd_safe=self.spmd_safe, mesh=mesh,
                cycle_dtype=self.dtype,
                edge_signs=self._psigns,
                leg_dtype=self._fas_leg_dtype,
                # what a mesh-attached fas hierarchy has always run:
                # the Pallas latch arms the halo strip sweep of its
                # overlapped levels
                smoother=("strip" if self._kernel_tier != "xla"
                          else "xla"))

    def direct_solve(self, rhs: jnp.ndarray, exact,
                     member_axis: bool = False):
        """The plan's one application, shared with the fleet's
        member-batched solve; the compile ledger's component note says
        who chose it (``selected=table`` | ``selected=env``)."""
        tracing.note_component(f"poisson.fftd[selected={self.fftd_by}]")
        return fft_diag_solve(
            self.laplacian, rhs, self._fft_plan,
            tol=0.0 if exact else self.cfg.poisson_tol,
            tol_rel=0.0 if exact else self.cfg.poisson_tol_rel,
            member_axis=member_axis)

    def pressure_solve(self, rhs: jnp.ndarray, exact: bool = False):
        """Solve lap(dp) = rhs (undivided). ``exact`` reproduces the
        reference's first-10-steps override — tol 0 with 100 restarts
        while the pold initial guess is cold (main.cpp:7028-7030). A
        literal tol 0 is unreachable in finite precision; instead of the
        r2 builds' hardcoded f32 relative floor (grid-dependent magic,
        VERDICT r2 #8) exact mode now runs at tol 0 and exits through
        the solver's stall detector at whatever the actual precision
        floor is, with a tight refresh cadence so the exit is prompt.
        ``exact="krylov"`` (``exact_request``) is that tol-0 Krylov
        solve on a grid whose production method is the direct one."""
        cfg = self.cfg
        if self.runs_direct(exact):
            # direct solve: exact to the precision floor in ONE
            # application — the tol-0 "exact" startup request simply
            # reports the floor through the benign stalled bit exactly
            # like bicgstab's tol-0 stall exit. The supervision
            # ladder's escalate rung asks for "krylov" instead
            # (exact_request) and falls through to the tol-0 Krylov
            # solve below.
            return self.direct_solve(rhs, exact)
        if self.solver_mode == "fas" and not exact:
            # production solves as pure MG cycles (CUP2D_POIS=fas):
            # 1 A-apply + 1 V-cycle per iteration vs Krylov's 2 + 2.
            # Exact (tol-0 startup) and escalation solves keep the
            # Krylov path — its stall-out-at-the-precision-floor
            # pedigree (r2-r4) is the robustness backstop, and the
            # unbatched BiCGSTAB stays bit-unchanged.
            return mg_solve(
                self.laplacian, rhs, self.mg,
                tol=cfg.poisson_tol, tol_rel=cfg.poisson_tol_rel,
                max_cycles=cfg.max_poisson_iterations,
                fmg=self.fas_fmg,
            )
        return bicgstab(
            self.laplacian,
            rhs,
            M=self.mg if cfg.precond else None,
            tol=0.0 if exact else cfg.poisson_tol,
            tol_rel=0.0 if exact else cfg.poisson_tol_rel,
            max_iter=cfg.max_poisson_iterations,
            max_restarts=100 if exact else cfg.max_poisson_restarts,
            sum_dtype=self.sum_dtype,
            refresh_every=10 if exact else 50,
            stall_iters=20 if exact else 120,
            stall_rtol=0.99 if exact else 0.999,
        )

    # -- step stages, shared by the obstacle-free and Simulation paths --
    def advect_heun(self, vel: jnp.ndarray, dt) -> jnp.ndarray:
        """Advection-diffusion, 2-stage Heun (main.cpp:6607-6642).
        On the fused tier both substages run as Pallas megakernels
        (one HBM read/write per substage) instead of the
        pad -> WENO-RHS -> update dispatch chain."""
        if self._kernel_tier != "xla":
            bf16 = self._kernel_tier == "pallas-fused-bf16"
            bc = None if self.bc.is_free_slip else self.bc
            with tracing.scope("advect"):
                if self._mesh is not None:
                    from .parallel.shard_halo import \
                        fused_advect_heun_sharded
                    return fused_advect_heun_sharded(
                        vel, self.h, self.cfg.nu, dt, self._mesh,
                        bc=bc, bf16=bf16)
                from .ops.pallas_kernels import fused_advect_heun
                return fused_advect_heun(
                    vel, self.h, self.cfg.nu, dt, bc=bc, bf16=bf16)
        ih2 = 1.0 / (self.h * self.h)
        vold = vel
        for k, c in enumerate((0.5, 1.0)):
            with tracing.scope(f"advect/substage{k}"):
                lab = self.pad_vector_field(vel, 3, dt)
                rhs = advect_diffuse_rhs(lab, 3, self.h, self.cfg.nu, dt)
                vel = heun_substage(vold, c, rhs, ih2)
        return vel

    def project(self, vel, pres_old, chi, udef, dt, exact_poisson=False):
        """deltap pressure solve + velocity correction
        (main.cpp:7007-7187): b = (h/2dt)[div u* - chi div u_def] -
        lap(pold); p = dp + pold (both mean-free); u += -dt/(2h) grad p.
        Returns (vel, pres, solver_result, div_linf). ``chi=None``
        (obstacle-free callers) drops the identically-zero
        chi*div(u_def) term. ``div_linf`` is max |∇·(u* − χ u_def)| of
        the pre-projection velocity — the divergence field the step
        already forms as the Poisson RHS, rescaled to physical units
        (zero extra field passes; the telemetry watchdog's second
        invariant, resilience.PhysicsWatchdog)."""
        h = self.h
        with tracing.scope("poisson_rhs"):
            b = self.poisson_rhs(vel, chi, udef, dt)
            # |b| = (h/2dt) * |undivided div|; physical div =
            # undivided/(2h)
            div_linf = jnp.max(jnp.abs(b)) * (dt / (h * h))
            b = b - self.laplacian(pres_old)
        with tracing.scope("poisson_solve"):
            res = self.pressure_solve(b, exact=exact_poisson)
        # any-Dirichlet tables (outflow face) pin the pressure level:
        # the operator is non-singular and the legacy mean removal
        # would shift the anchored solution — skip it (bc.py docs)
        # the fused correction kernel has no halo-mode form (its
        # stencil is purely local, but the strip DMA cannot be GSPMD-
        # partitioned) — mesh-attached grids keep the XLA epilogue,
        # whose sharded==single equality is pinned
        corr_tier = "xla" if self._mesh is not None else self._kernel_tier
        vel, pres = project_correct(
            res.x, pres_old, vel, h, dt,
            spmd_safe=self.spmd_safe, tier=corr_tier,
            remove_mean=self.bc.all_neumann, grad_signs=self._psigns,
            periodic=self._paxes)
        return vel, pres, res, div_linf

    def precond_cycles(self, res, exact):
        """Preconditioner/MG cycle count of one solve (telemetry
        schema v4), shared by the solo and fleet diag producers so the
        accounting convention cannot desynchronize between them: FAS
        iterations ARE cycles; flexible BiCGSTAB applies M twice per
        iteration; block-Jacobi-only solves report 0 (no hierarchy
        cycles). A host-derived count would desynchronize from the
        device iters under the lagged verdict, so this rides the same
        diag pull as the iters themselves."""
        if self.runs_direct(exact):
            # direct solve: no hierarchy cycles at all
            return jnp.zeros_like(res.iters)
        if self.solver_mode == "fas" and not exact:
            return res.iters
        if self.cfg.precond:
            return 2 * res.iters
        return jnp.zeros_like(res.iters)

    @tracing.in_scope("diag")
    def step_diag(self, vel, pres, res, div_linf=None,
                  exact=False) -> dict:
        umax = jnp.max(jnp.abs(vel))
        # kinetic energy: the telemetry watchdog's first invariant —
        # one extra fused reduction over a field the diag pass reads
        # anyway (umax); accumulated in sum_dtype like the Krylov dots
        vv = vel.astype(self.sum_dtype) if self.sum_dtype is not None \
            else vel
        energy = 0.5 * self.h * self.h * jnp.sum(vv * vv)
        return {
            "poisson_iters": res.iters,
            "poisson_residual": res.residual,
            "poisson_stalled": res.stalled,
            # the solver has always computed `converged`; surfacing it
            # here lets the resilience verdict consume it for free
            # (resilience.health_verdict — PR 2)
            "poisson_converged": res.converged,
            # fused isfinite reduction over vel AND pres: the health
            # verdict's cheap NaN/Inf detector, riding the same device
            # call (umax alone misses a NaN confined to the pressure)
            "finite": jnp.all(jnp.isfinite(vel))
            & jnp.all(jnp.isfinite(pres)),
            "umax": umax,
            # physics invariants for the watchdog + metrics stream,
            # riding the same batched diag pull (PR 3)
            "energy": energy,
            "div_linf": div_linf,
            "precond_cycles": self.precond_cycles(res, exact),
            # next step's dt rides the same device call (no separate
            # dt round trip, r1 weak #10)
            "dt_next": self.dt_from_umax(umax),
        }

    # -- one full projection step (the reference hot loop 6576-7290) --
    def step(self, state: FlowState, dt: jnp.ndarray,
             exact_poisson: bool = False,
             obstacle_terms: bool = True) -> tuple[FlowState, dict]:
        """``obstacle_terms=False`` statically drops the penalization
        update and the chi*div(u_def) RHS term — they are identically
        zero without shapes, but XLA cannot know that and spends ~4 ms
        of full-field passes on them at 8192^2. The obstacle-free
        drivers (UniformSim, Simulation's empty branch) pass
        False; the shaped path never calls this (it penalizes in
        Simulation._flow_step_impl)."""
        cfg = self.cfg
        vel = self.advect_heun(state.vel, dt)

        if obstacle_terms:
            # Brinkman penalization implicit update (main.cpp:6961-6977):
            # alpha = chi>0.5 ? 1/(1+lambda dt) : 1; u <- alpha u + (1-alpha) u_s
            with tracing.scope("penalize"):
                alpha = jnp.where(state.chi > 0.5,
                                  1.0 / (1.0 + cfg.lam * dt), 1.0)
                vel = alpha * vel + (1.0 - alpha) * state.us

        vel, pres, res, div_linf = self.project(
            vel, state.pres,
            state.chi if obstacle_terms else None,
            state.udef if obstacle_terms else None, dt, exact_poisson)
        return state._replace(vel=vel, pres=pres), \
            self.step_diag(vel, pres, res, div_linf,
                           exact=exact_poisson)

    def vorticity_field(self, vel: jnp.ndarray) -> jnp.ndarray:
        return vorticity(self.pad_vector_field(vel, 1), 1, self.h)


class UniformSim:
    """Host-side driver: owns time/step counters, jits the device step."""

    def __init__(self, cfg: SimConfig, level: Optional[int] = None,
                 spmd_safe: bool = False,
                 bc: Optional[BCTable] = None):
        self.grid = UniformGrid(cfg, level, spmd_safe=spmd_safe, bc=bc)
        self.cfg = cfg
        self.state = self.grid.zero_state()
        self.time = 0.0
        self.step_count = 0
        self.shapes: list = []          # obstacle-free by construction
        self.case: Optional[str] = None  # case-registry tag (cases.py)
        self.force_log = None
        self._next_dt = None            # cached end-state dt_next
        # supervision hooks (resilience.StepGuard): escalation-rung
        # exact solve + the lagged-verdict device-diag mode — see
        # sim.Simulation for the contract
        self._force_exact = False
        self.async_diag = False
        # donate the state: without it XLA copies the pass-through
        # fields (us/udef/chi) every step — 3.3 ms/step of dead copies
        # at 8192^2 (round-4 trace). Callers read the NEW state from the
        # return value; the donated input buffers are invalidated.
        # UniformSim is the obstacle-free driver, so the obstacle terms
        # are statically dropped.
        self._step = tracing.named_jit(
            "uniform.step", jax.jit(
                self.grid.step, donate_argnums=(0,),
                static_argnames=("exact_poisson", "obstacle_terms")),
            variant=("exact_poisson",))
        self._dt = tracing.named_jit(
            "uniform.dt", jax.jit(self.grid.compute_dt))

    @property
    def poisson_mode(self) -> str:
        """Active solve-path latch (telemetry schema v4)."""
        return self.grid.poisson_mode

    @property
    def kernel_tier(self) -> str:
        """Active advection-kernel tier (telemetry schema v6)."""
        return self.grid.kernel_tier

    @property
    def prec_mode(self) -> str:
        """Hot-loop storage precision (telemetry schema v6)."""
        return self.grid.prec_mode

    @property
    def smoother_tier(self) -> str:
        """Pressure-hierarchy smoother tier (telemetry schema v11)."""
        return self.grid.smoother_tier

    @property
    def bc_table(self) -> str:
        """Per-face BC token string (telemetry schema v8)."""
        return self.grid.bc_table

    def step_once(self, dt: Optional[float] = None):
        """One supervised-loop-compatible step (the StepGuard driver
        contract shared with Simulation/AMRSim): cached device dt_next,
        one batched diag pull — or, under ``async_diag``, no pull at
        all: the diag (incl. the dt used) stays on device and the
        guard's lagged verdict settles the clock."""
        g = self.grid
        if dt is None:
            if self._next_dt is not None:
                dt = self._next_dt
            else:
                dt = float(self._dt(self.state.vel))
        exact = g.exact_request(self.step_count < 10, self._force_exact)
        dt_dev = jnp.asarray(dt, g.dtype)
        self.state, diag = self._step(
            self.state, dt_dev,
            exact_poisson=exact, obstacle_terms=False)
        if self.async_diag:
            diag = dict(diag)
            diag["dt"] = dt_dev
            self._next_dt = diag["dt_next"]
            self.step_count += 1
            return diag
        diag = jax.device_get(diag)
        diag["dt"] = float(dt)   # exact dt for the guard's replay record
        self._next_dt = float(diag["dt_next"])
        self.time += dt
        self.step_count += 1
        return diag

    def advance(self, n_steps: int = 1, tend: Optional[float] = None,
                exact_first_steps: bool = False):
        """``exact_first_steps`` mirrors the reference's tol-0 solve for
        steps < 10 (main.cpp:7028-7030); off by default because obstacle-free
        validation runs don't need the cold-start treatment."""
        diag = {}
        for _ in range(n_steps):
            if tend is not None and self.time >= tend:
                break
            dt = float(self._dt(self.state.vel))
            if tend is not None:
                dt = min(dt, tend - self.time + 1e-15)
            exact = exact_first_steps and self.step_count < 10
            self.state, diag = self._step(
                self.state, jnp.asarray(dt, self.grid.dtype),
                exact_poisson=exact, obstacle_terms=False,
            )
            self.time += dt
            self.step_count += 1
        return diag
