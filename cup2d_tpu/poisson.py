"""Matrix-free pressure-Poisson solver: block-preconditioned BiCGSTAB.

TPU-native replacement for the reference's GPU subsystem
(`/root/reference/cuda.cu:24-548` BiCGSTABSolver + the host COO assembly at
`main.cpp:5747-6020, 7031-7115`): instead of materializing a distributed
sparse matrix for cuSPARSE, the variable-resolution 5-point Laplacian is
applied *matrix-free* as a stencil (a function passed in by the caller), and
the whole Krylov iteration runs inside one `lax.while_loop` on device — no
host round-trips per iteration, no explicit halo staging (XLA inserts the
collectives when the operand arrays are sharded).

The preconditioner is the reference's exact block-Jacobi inverse
(`main.cpp:6451-6488`): P = -inv(A_local) where A_local is the BS^2 x BS^2
single-block 5-point Laplacian (`getA_local`, main.cpp:46-57), applied as a
batched [nblocks, BS^2] x [BS^2, BS^2] GEMM — MXU work, where the reference
used a batched cuBLAS GEMM (`cuda.cu:484-486`).

Algorithm: flexible BiCGSTAB with Linf convergence on max(tol_abs,
tol_rel * |r0|_inf), breakdown detection with re-orthogonalization restarts,
and best-solution tracking (`x_opt`, cuda.cu:525-542) — same control flow as
the reference's device loop (cuda.cu:403-548).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing
from .ops.dft import HartleyPlan2D


def block_precond_matrix(bs: int, dtype=np.float64) -> np.ndarray:
    """P_inv = -inv(A_local), the negated inverse of the bs^2 x bs^2
    single-block 5-point Laplacian with homogeneous Dirichlet truncation at
    the block edge (reference getA_local main.cpp:46-57 + Cholesky inversion
    main.cpp:6451-6488; dense inverse here — same matrix, host-side once)."""
    n = bs * bs
    ii = np.arange(n)
    xi, yi = ii % bs, ii // bs
    a = np.zeros((n, n), dtype=np.float64)
    dx = np.abs(xi[:, None] - xi[None, :])
    dy = np.abs(yi[:, None] - yi[None, :])
    a[(dx + dy) == 1] = -1.0
    np.fill_diagonal(a, 4.0)
    return (-np.linalg.inv(a)).astype(dtype)


def apply_block_precond(r: jnp.ndarray, p_inv: jnp.ndarray, bs: int) -> jnp.ndarray:
    """z = P_inv r applied per bs x bs tile of a [Ny, Nx] field (batched GEMM).

    Works for any [Ny, Nx] divisible by bs; the AMR path passes fields
    already shaped [N, bs, bs] via `apply_block_precond_blocks`.
    """
    ny, nx = r.shape[-2], r.shape[-1]
    nby, nbx = ny // bs, nx // bs
    tiles = r.reshape(*r.shape[:-2], nby, bs, nbx, bs)
    tiles = jnp.swapaxes(tiles, -3, -2).reshape(*r.shape[:-2], nby, nbx, bs * bs)
    z = tiles @ p_inv.T  # P_inv is symmetric; .T keeps intent explicit
    z = z.reshape(*r.shape[:-2], nby, nbx, bs, bs).swapaxes(-3, -2)
    return z.reshape(r.shape)


def apply_block_precond_blocks(r: jnp.ndarray, p_inv: jnp.ndarray) -> jnp.ndarray:
    """Same, for block-forest layout [N, bs, bs]."""
    n, bs, _ = r.shape
    return (r.reshape(n, bs * bs) @ p_inv.T).reshape(n, bs, bs)


# ---------------------------------------------------------------------------
# Geometric multigrid V-cycle preconditioner (uniform grids)
#
# The reference is stuck with single-level block-Jacobi because its solver
# needs an assembled sparse matrix on the GPU (cuda.cu); matrix-free on TPU
# we can do the textbook-right thing instead. With block-Jacobi, BiCGSTAB
# iteration counts grow ~linearly in N_1d/BS (measured: 11 at 1024^2 ->
# 174 at 4096^2); a V(2,2) cycle makes them O(1) in N. Used as the M of
# the flexible BiCGSTAB below; each cycle is a few 5-point stencil sweeps
# per level plus 2x2 mean restriction / nearest prolongation — pure
# VPU/HBM streaming work that XLA fuses well.
# ---------------------------------------------------------------------------

# How deep a single-device hierarchy takes its strip kernels: the
# finest three levels hold 63/64 of the cells, and every further level
# would add two more kernel bodies to trace and lower (about a second
# of host time each, per step executable, on the chip's host — set-up
# time) for under 2 % of a cycle's work. Coarser levels keep the XLA
# legs, as the shape gate's misses do.
_STRIP_DEPTH = 3


class MultigridPreconditioner:
    """V(nu1, nu2)-cycle for lap(e) = r on a [Ny, Nx] uniform grid.

    All operators are the *undivided* Laplacian (matching the solver's
    convention); the restricted residual is scaled by 4 per level because
    the undivided coarse operator is 4x the fine one (h_c^2 = 4 h_f^2).
    Damped Jacobi smoothing with the exact Neumann-aware diagonal,
    assembled on the fly from 1-D edge indicators — a materialized
    [Ny, Nx] diagonal would be baked into the jitted HLO as a
    full-field constant (268 MB at 8192^2, enough to break remote
    compilation), while two length-N 1-D constants fuse for free.
    """

    def __init__(self, ny: int, nx: int, dtype, nu1: int = 2,
                 nu2: int = 2, coarsest: int = 16, omega: float = 0.8,
                 cycle_dtype=None, spmd_safe: bool = False,
                 mesh=None, overlap_levels: int = 1,
                 edge_signs=None, leg_dtype=None,
                 smoother: str | None = None,
                 periodic=(False, False)):
        self.shapes = []
        self.nu1 = nu1
        self.nu2 = nu2
        self.omega = omega
        # a mesh-aware hierarchy always hands x-split operands to the
        # partitioner below its overlapped levels, so it carries the
        # fenced shift form itself rather than trusting the caller to
        # pass both (re-tested on jax 0.9.0: the fast pad+slice form
        # inside a partitioned cycle still returns garbage)
        self.spmd_safe = spmd_safe or mesh is not None
        # periodic (px, py) — bc.periodic_axes (ISSUE 20): the cycle's
        # operator uses wrap (roll) shifts along periodic axes at EVERY
        # level (periodicity persists under 2x coarsening) and the
        # matching edge signs come in as 0 through edge_signs, so the
        # Jacobi diagonal keeps the interior -4 on periodic rows.
        self.periodic = (bool(periodic[0]), bool(periodic[1]))
        # all four faces wrap (ISSUE 34): the Jacobi diagonal is -4 on
        # every row of every level, so a constant goes through sweeps,
        # restriction and prolongation as a constant and M(1) comes out
        # EXACTLY constant (-322 at 64^2, x4 a level: ~ -1e6 at 8192^2)
        # — in the operator's null space, where no residual sees it. A
        # tol-0 start-up solve that iterates on past the f32 floor then
        # lets the mean of x drift without bound (1e12 seen at 128^2)
        # until lap(x) is rounding noise: 12 of 120 start-up solves at
        # 128^2 came back with a residual of 1e-4..4e-2. With a wall
        # anywhere the edge rows break the constant and Krylov damps
        # it. So here each COARSE level takes its residual mean-free
        # (_range): what is left is the finest level's own sweeps,
        # M(1) = -(nu1 + nu2) omega / 4.
        self._const_null = all(self.periodic)
        if any(self.periodic):
            if edge_signs is None:
                raise ValueError(
                    "MultigridPreconditioner: periodic axes need the "
                    "BC table's edge_signs (bc.pressure_signs — the "
                    "periodic entries are 0); the legacy all-Neumann "
                    "default would paint wall corrections over wrap "
                    "rows")
        # edge_signs: the BC table's per-face pressure-ghost signs
        # (sx_lo, sx_hi, sy_lo, sy_hi) from bc.pressure_signs — the
        # cycle's operator and Jacobi diagonal carry the same per-face
        # rows at EVERY level (face kinds persist under coarsening, the
        # wall diagonal stays in [-6, -2], never 0). None (default
        # table) keeps the legacy all-Neumann forms verbatim. The
        # overlapped sharded smoother (shard_halo) is free-slip-
        # specific, so signed hierarchies keep the GSPMD sweeps — the
        # Krylov/FAS outer loop drives the true per-face residual
        # either way.
        self.edge_signs = edge_signs
        if edge_signs is not None:
            overlap_levels = 0
        # mesh: opt-in comm/compute-overlapped smoothing for x-split
        # sharded fields (the FAS full-solver path, mesh.py): the
        # finest ``overlap_levels`` levels run their Jacobi sweeps
        # under shard_map with explicit per-offset ppermute edge-column
        # exchanges whose latency hides behind the interior update
        # (parallel.shard_halo.overlap_jacobi_sweeps — the
        # arXiv:1309.7128 schedule). Coarser levels are cheap and stay
        # on the GSPMD-partitioned form. None (default) = GSPMD
        # everywhere, bit-identical to the pre-mesh behavior.
        self.mesh = mesh
        self.overlap_levels = overlap_levels if mesh is not None else 0
        # The cycle runs in bf16 when the solver is f32: a preconditioner
        # only needs to capture the error's shape, flexible BiCGSTAB
        # absorbs the inexactness, and halving the bytes both doubles
        # effective HBM bandwidth and keeps the 8192^2 cycle inside HBM
        # (f32 temporaries alone exceeded it). f64 solves (CPU validation)
        # keep an f64 cycle for convergence-order tests.
        #
        # leg_dtype (ISSUE 19): the memory-tiered FAS-solver variant of
        # the same tradeoff — when the cycle IS the solver
        # (CUP2D_POIS=fas, cycle_dtype = the solver dtype), leg_dtype
        # puts ONLY the cycle interior (smoother/transfer legs) in bf16
        # while mg_solve's outer loop keeps the f32 true residual:
        # iterative refinement, so the bf16 legs cannot floor the
        # achievable residual the way a fully-bf16 solver does
        # (~2e-4 rel floor). Takes precedence over
        # cycle_dtype when set.
        self.leg_dtype = leg_dtype
        self.dtype = leg_dtype or cycle_dtype or (
            jnp.bfloat16 if jnp.dtype(dtype) == jnp.float32 else dtype)
        self.out_dtype = dtype
        ny0, nx0 = ny, nx
        while ny >= coarsest and nx >= coarsest \
                and ny % 2 == 0 and nx % 2 == 0:
            self.shapes.append((ny, nx))
            ny //= 2
            nx //= 2
        self.shapes.append((ny, nx))
        # smoother: "strip" runs each level as fused strip pipelines
        # (ops/pallas_kernels): where ``mg_leg_supported`` admits the
        # level, the whole down-leg (sweeps + residual + restriction)
        # and up-leg (prolongation + sweeps) are one kernel each
        # (ISSUE 26: reads r, writes e and rc; reads e, r, ec, writes
        # e); where only ``jacobi_strip_supported`` does, the sweep
        # chains alone are fused (ISSUE 19) and the transfers stay
        # XLA; the rest (coarse shapes, the 24-sweep coarsest chain)
        # keeps the identical-result XLA form. None (the default)
        # lets the hierarchy pick by what it can see: "strip" on an
        # accelerator, "xla" on a CPU run (where a kernel would be
        # interpreted — tests pass smoother="strip" to get that).
        # Either way the strip forms are taken only where they exist:
        # no mesh and no GSPMD-partitioned operands (a strip DMA cannot
        # be partitioned; a mesh-attached hierarchy keeps the tier it
        # was handed, whose sweeps ride the halo kernel), no periodic
        # axis (the pipeline synthesizes wall ghosts and has no wrap
        # form), f32 or bf16 storage, and a finest level that passes
        # the shape gate — so the reported smoother_tier is always
        # what runs.
        from .ops import pallas_kernels as pk
        if smoother is None:
            smoother = ("strip" if mesh is None and not self.spmd_safe
                        and pk._on_accel() else "xla")
        nmax = max(nu1, nu2, 1)
        if smoother == "strip" and (
                any(self.periodic)
                or (mesh is None and self.spmd_safe)
                or not pk.jacobi_strip_supported(ny0, nx0, self.dtype,
                                                 nmax)):
            smoother = "xla"
        self.smoother = smoother
        # levels whose two legs run fused: the finest _STRIP_DEPTH that
        # the gate admits (never the coarsest, which has no legs); a
        # mesh-attached hierarchy fuses none
        self._leg_fused = [
            smoother == "strip" and mesh is None
            and lvl < _STRIP_DEPTH and min(nu1, nu2) >= 1
            and pk.mg_leg_supported(ny_, nx_, self.dtype, nmax)
            for lvl, (ny_, nx_) in enumerate(self.shapes[:-1])]

    @property
    def fused_levels(self) -> int:
        """How many levels run the two fused legs — the "does it
        engage" reading of the strip tier (0 under "xla")."""
        return sum(self._leg_fused)

    @property
    def smoother_tier(self) -> str:
        """Telemetry label of the legs' implementation: "xla" or
        "strip", with "+bf16" suffixed when the cycle legs store bf16
        (so a shape-gate demotion of the strip pipeline cannot hide an
        armed bf16 leg tier — e.g. "xla+bf16"). The XLA form of the
        default bf16 PRECONDITIONER cycle (cycle_dtype=None under
        Krylov) keeps the bare "xla": that tier predates the label
        and is carried by poisson_mode."""
        base = "strip" if self.smoother == "strip" else "xla"
        if jnp.dtype(self.dtype) == jnp.bfloat16 and (
                self.leg_dtype is not None or base == "strip"):
            return base + "+bf16"
        return base

    def _lap(self, p):
        """Undivided 5-point Laplacian, zero-Neumann edge ghosts —
        fused-BC form (zero-ghost shifts + rank-1 edge correction)
        instead of an edge-mode pad, whose concatenate lowering
        materialized ~4.5 ms/step of bf16 strips inside the V-cycle at
        8192^2 (round-3 trace)."""
        if self.edge_signs is not None:
            from .ops.stencil import laplacian5_bc
            sx_lo, sx_hi, sy_lo, sy_hi = self.edge_signs
            px, py = self.periodic
            return laplacian5_bc(p, sx_lo, sx_hi, sy_lo, sy_hi,
                                 self.spmd_safe, px, py)
        from .ops.stencil import laplacian5_neumann
        return laplacian5_neumann(p, self.spmd_safe)

    def _inv_diag(self, lvl):
        """1/(-4 + signed wall-side count), from broadcast 1-D iota
        indicators (in-register, not DMA-staged constants — see
        stencil._edge_ones). Per-face signs keep the diagonal in
        [-6, -2]: always invertible."""
        from .ops.stencil import _edge_ones
        ny, nx = self.shapes[lvl]
        if self.edge_signs is not None:
            sx_lo, sx_hi, sy_lo, sy_hi = self.edge_signs
            ex = _edge_ones(nx, self.dtype, lo=sx_lo, hi=sx_hi)
            ey = _edge_ones(ny, self.dtype, lo=sy_lo, hi=sy_hi)
        else:
            ex = _edge_ones(nx, self.dtype)
            ey = _edge_ones(ny, self.dtype)
        return 1.0 / (ey[:, None] + ex[None, :] - 4.0)

    def _smooth(self, e, r, lvl, n, from_zero=False):
        # the coarsest level's sweeps ARE this hierarchy's coarse solve
        name = "mg_coarse" if lvl == len(self.shapes) - 1 else "mg_smooth"
        with tracing.scope(name):
            return self._sweeps(e, r, lvl, n, from_zero)

    def _sweeps(self, e, r, lvl, n, from_zero):
        sharded = n > 0 and lvl < self.overlap_levels and r.ndim == 2
        if self.smoother == "strip" and n > 0 and not sharded and (
                lvl < _STRIP_DEPTH or self.mesh is not None):
            # strip tier (ISSUE 19): the whole sweep chain as ONE
            # time-skewed strip pipeline — n sweeps cost one HBM read
            # of (e, r) and one write instead of ~2n+1 field passes.
            # Unsupported levels (coarse shapes, the 24-sweep coarsest
            # chain) fall back to the identical-result XLA loop below.
            from .ops.pallas_kernels import (fused_jacobi_sweeps,
                                             jacobi_strip_supported)
            ny, nx = self.shapes[lvl]
            if jacobi_strip_supported(ny, nx, self.dtype, n):
                return fused_jacobi_sweeps(e, r, self.omega, n,
                                           edge_signs=self.edge_signs,
                                           from_zero=from_zero)
        inv_d = self._inv_diag(lvl)
        # fori_loop (not Python unroll) so XLA reuses one sweep's buffers
        # across sweeps — unrolled at 8192^2 the live temporaries of all
        # sweeps stack up and buffer assignment exceeds HBM
        if from_zero and n > 0:
            # first sweep from e=0 is e = omega r / d — skip the full
            # lap(0) stencil pass it would otherwise spend
            e = self.omega * r * inv_d
            n = n - 1
        if n > 0 and lvl < self.overlap_levels and r.ndim == 2:
            # sharded finest level(s): explicit edge-column ppermutes
            # overlapped with the interior sweep (see __init__); the
            # strip tier rides the same halo form (ISSUE 19)
            from .parallel.shard_halo import overlap_jacobi_sweeps
            return overlap_jacobi_sweeps(e, r, inv_d, self.omega, n,
                                         self.mesh, tier=self.smoother)
        return jax.lax.fori_loop(
            0, n,
            lambda _, ee: ee + self.omega * (r - self._lap(ee)) * inv_d,
            e,
        )

    def _note(self):
        # trace-time only: the tier and its fused-level count on the
        # compiling executable's ledger row
        tracing.note_component(
            f"poisson.mg[{self.smoother_tier},"
            f"fused_levels={self.fused_levels}/{len(self.shapes)}]")

    @tracing.in_scope("mg_cycle")
    def __call__(self, r):
        self._note()
        return self._cycle(r.astype(self.dtype), 0).astype(self.out_dtype)

    @tracing.in_scope("mg_cycle")
    def fcycle(self, r):
        """One F(ull)MG cycle: recurse to the coarsest level FIRST,
        prolongate each coarse solution as the next-finer level's
        initial guess, and run one V-cycle relaxation there. ~2x a
        V-cycle's cost for a much better cold-start correction — the
        opening move of the FAS solver's ``fmg`` mode (mg_solve)."""
        self._note()
        return self._fcycle(r.astype(self.dtype), 0).astype(self.out_dtype)

    def _range(self, r, lvl):
        """``r`` as the cycle's level ``lvl`` takes it: mean-free on the
        coarse levels of an all-periodic hierarchy (see __init__),
        untouched everywhere else."""
        if self._const_null and lvl >= 1:
            with tracing.scope("mg_transfer"):
                return r - jnp.mean(r, axis=(-2, -1), keepdims=True)
        return r

    def _fcycle(self, r, lvl):
        if lvl == len(self.shapes) - 1:
            return self._smooth(jnp.zeros_like(r), self._range(r, lvl),
                                lvl, 24, from_zero=True)
        # same full-weighting restriction (+x4 undivided scale) as the
        # V-cycle below
        with tracing.scope("mg_transfer"):
            rows = r[..., 0::2, :] + r[..., 1::2, :]
            rc = rows[..., :, 0::2] + rows[..., :, 1::2]
        ec = self._fcycle(rc, lvl + 1)
        with tracing.scope("mg_transfer"):
            e0 = jnp.repeat(jnp.repeat(ec, 2, axis=-2), 2, axis=-1)
        return self._cycle(r, lvl, e0=e0)

    def _cycle(self, r, lvl, e0=None):
        r = self._range(r, lvl)
        if lvl == len(self.shapes) - 1:
            # coarsest: enough Jacobi sweeps to wash out the local modes;
            # the global constant mode is BiCGSTAB's job, not M's
            if e0 is not None:
                return self._smooth(e0, r, lvl, 24)
            return self._smooth(jnp.zeros_like(r), r, lvl, 24,
                                from_zero=True)
        if self._leg_fused[lvl]:
            return self._fused_level(r, lvl, e0)
        if e0 is not None:
            e = self._smooth(e0, r, lvl, self.nu1)
        else:
            e = self._smooth(jnp.zeros_like(r), r, lvl, self.nu1,
                             from_zero=True)
        with tracing.scope("mg_transfer"):
            rc = self._restrict(r, e)
        ec = self._cycle(rc, lvl + 1)
        with tracing.scope("mg_transfer"):
            # nearest prolongation (2x2 replicate)
            e = e + jnp.repeat(jnp.repeat(ec, 2, axis=-2), 2, axis=-1)
        return self._smooth(e, r, lvl, self.nu2)

    def _fused_level(self, r, lvl, e0):
        """One level as two strip pipelines (ISSUE 26): sweeps +
        residual + restriction going down, prolongation + sweeps
        coming up. Traced as ``mg_smooth``, the part that dominates
        each leg; ``mg_transfer`` then holds the unfused levels only."""
        from .ops.pallas_kernels import fused_mg_down, fused_mg_up
        with tracing.scope("mg_smooth"):
            e, rc = fused_mg_down(e0, r, self.omega, self.nu1,
                                  edge_signs=self.edge_signs,
                                  from_zero=e0 is None)
        ec = self._cycle(rc, lvl + 1)
        with tracing.scope("mg_smooth"):
            return fused_mg_up(e, r, ec, self.omega, self.nu2,
                               edge_signs=self.edge_signs)

    def _restrict(self, r, e):
        res = r - self._lap(e)
        # full-weighting restriction (2x2 mean), x4 for the undivided
        # coarse operator scale, decomposed as row-pair sum then
        # column-pair sum. Neither reshape(ny/2,2,...).mean (tiny
        # trailing dims pad to the (8,128) TPU tile: 4 GB of temporaries
        # at 4096^2) nor a 4-way doubly-strided slice sum (measured
        # 1.8 s at 8192^2) — the two-stage form keeps each slice
        # single-strided and runs at the latency floor. `...` indexing:
        # the cycle is leading-dim agnostic so the fleet path can run
        # one V-cycle over a whole [B, Ny, Nx] member batch.
        rows = res[..., 0::2, :] + res[..., 1::2, :]
        return rows[..., :, 0::2] + rows[..., :, 1::2]


def dct_neumann_operators(ncy: int, ncx: int, dtype=np.float32):
    """Host-precomputed operators for the matmul form of
    ``coarse_neumann_solve``: DCT-II basis matrices (forward + exact
    inverse via the orthogonality weights), and the reciprocal
    eigenvalue grid with the constant nullspace mode zeroed.

    Why matmuls and not jnp.fft: the mirror-extension rfft2 lowers to
    an XLA FFT custom call whose operand staging dominated the entire
    coarse solve on TPU (r5 trace of the 1e4-block probe: ~3.7 ms per
    128 KB copy-start around each FFT, ~19 of them per step — more
    device time than the Krylov arithmetic). The same diagonalization
    as the even extension, cos(pi k (i+0.5)/n) with eigenvalues
    2cos(pi k/n) - 2 per axis, is four tiny matmuls on the MXU."""
    def fwd(n):
        k = np.arange(n)[:, None]
        i = np.arange(n)[None, :]
        return np.cos(np.pi * k * (i + 0.5) / n)

    cyf = fwd(ncy)
    cxf = fwd(ncx)
    # exact inverse from DCT-II row orthogonality: row norms are n (k=0)
    # and n/2 (k>0)
    wy = np.full(ncy, 2.0 / ncy); wy[0] = 1.0 / ncy
    wx = np.full(ncx, 2.0 / ncx); wx[0] = 1.0 / ncx
    cyi = (cyf * wy[:, None]).T
    cxi = (cxf * wx[:, None]).T
    ky = 2.0 * np.cos(np.pi * np.arange(ncy) / ncy) - 2.0
    kx = 2.0 * np.cos(np.pi * np.arange(ncx) / ncx) - 2.0
    lam = ky[:, None] + kx[None, :]
    ilam = np.where(lam < -1e-12, 1.0 / np.where(lam < -1e-12, lam, 1.0),
                    0.0)
    return (cyf.astype(dtype), cyi.astype(dtype),
            cxf.astype(dtype), cxi.astype(dtype), ilam.astype(dtype))


def coarse_neumann_solve_dct(rc: jnp.ndarray, ops, h2) -> jnp.ndarray:
    """Exact undivided-Neumann solve as 4 matmuls (see
    dct_neumann_operators); identical diagonalization to
    ``coarse_neumann_solve``, returning e * h2 with the constant mode
    projected out. HIGHEST matmul precision: the default bf16 MXU pass
    would corrupt the cosine bases exactly like it corrupted the
    structured-operator strip maps (flux.poisson_apply_structured)."""
    cyf, cyi, cxf, cxi, ilam = ops

    def mm(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    F = mm(mm(cyf, rc), cxf.T)
    return h2 * mm(mm(cyi, F * ilam), cxi.T)


def coarse_neumann_solve(rc: jnp.ndarray, h2) -> jnp.ndarray:
    """Exact solve of the UNDIVIDED 5-point Neumann Laplacian on a small
    uniform grid, L e = rc, returning e * h2 (the divided-operator
    solution for spacing h = sqrt(h2)); the nullspace (constant mode) is
    projected out. Used as the coarse half of the exact-mode two-level
    preconditioner (VERDICT r2 #6): block-Jacobi alone leaves the global
    pressure modes to the Krylov iteration, which is exactly why cold
    startup solves burned hundreds of iterations.

    Mechanism: mirror (even) extension to a 2x grid turns the Neumann
    problem into a periodic one solved by FFT diagonalization — no
    precomputed factorization, works at any size, MXU/FFT-friendly.
    """
    ncy, ncx = rc.shape
    top = jnp.concatenate([rc, rc[:, ::-1]], axis=1)
    ext = jnp.concatenate([top, top[::-1, :]], axis=0)
    F = jnp.fft.rfft2(ext)
    ky = 2.0 * jnp.cos(jnp.pi * jnp.arange(2 * ncy) / ncy) - 2.0
    kx = 2.0 * jnp.cos(jnp.pi * jnp.arange(ncx + 1) / ncx) - 2.0
    lam = ky[:, None] + kx[None, :]
    E = jnp.where(lam < -1e-12, F / jnp.where(lam < -1e-12, lam, 1.0),
                  0.0)
    e = jnp.fft.irfft2(E, s=(2 * ncy, 2 * ncx))[:ncy, :ncx]
    return (h2 * e).astype(rc.dtype)


class BiCGSTABResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray
    residual: jnp.ndarray   # Linf of best residual seen
    converged: jnp.ndarray
    stalled: jnp.ndarray    # exited via the L2 stall detector


class _State(NamedTuple):
    x: jnp.ndarray
    r: jnp.ndarray
    rhat: jnp.ndarray
    p: jnp.ndarray
    v: jnp.ndarray
    rho: jnp.ndarray
    alpha: jnp.ndarray
    omega: jnp.ndarray
    it: jnp.ndarray          # global loop counter (scalar)
    restarts: jnp.ndarray
    x_opt: jnp.ndarray
    norm_opt: jnp.ndarray
    norm0: jnp.ndarray
    best_it: jnp.ndarray
    best_l2: jnp.ndarray
    impr_it: jnp.ndarray
    it_m: jnp.ndarray        # per-member iteration count (== it unbatched)
    done: jnp.ndarray


def bicgstab(
    A: Callable[[jnp.ndarray], jnp.ndarray],
    b: jnp.ndarray,
    M: Callable[[jnp.ndarray], jnp.ndarray] | None = None,
    x0: jnp.ndarray | None = None,
    tol: float = 1e-3,
    tol_rel: float = 1e-2,
    max_iter: int = 1000,
    max_restarts: int = 0,
    sum_dtype=None,
    refresh_every: int = 50,
    stall_iters: int = 120,
    stall_rtol: float = 0.999,
    member_axis: bool = False,
) -> BiCGSTABResult:
    """Preconditioned flexible BiCGSTAB, whole loop jitted on device.

    A, M are matrix-free operators on fields shaped like ``b``. Convergence
    is Linf(r) <= max(tol, tol_rel * Linf(r0)) — the reference's criterion
    (cuda.cu:434-436, 525-542). Inner products accumulate in ``sum_dtype``
    (default: b's dtype; pass jnp.float64 for compensated f32 runs).

    Beyond the reference's breakdown-restart (cuda.cu:457-477, budget
    ``max_restarts``), every ``refresh_every`` iterations the recursive
    residual is replaced by the true residual b - A(x) of the CURRENT
    iterate and the Krylov space restarted from there. This is the
    standard f32 mitigation: the recursive residual drifts from the true
    one after ~50-100 iterations, and the reference never needs it only
    because it iterates in f64. ``stall_iters`` bounds wasted work when the
    target sits below the precision floor (e.g. exact-mode solves with a
    warm initial guess): if the recursive L2 residual has not dropped by
    >= 0.1% below its running best for that many iterations, the solve
    exits with the best iterate instead of burning max_iter. L2 — not
    Linf — because BiCGSTAB's Linf is transiently non-monotonic by orders
    of magnitude (see below) while L2 decreases steadily; a stall in L2
    means the Krylov space is genuinely exhausted at this precision.
    The refresh must keep the current x — NOT
    jump back to the best-Linf iterate: BiCGSTAB's Linf residual
    transiently rises orders of magnitude above Linf(r0) while converging
    steadily in L2 (measured at 1024^2: Linf 0.04 -> 1.4 -> recovery over
    ~40 iterations), so any restart policy keyed on Linf improvement
    livelocks by restarting from x0 forever. Costs one extra operator
    application per refresh (lax.cond — not per iteration).

    ``member_axis`` (the fleet path, fleet.py): ``b`` carries a leading
    MEMBER axis of B independent systems solved in one fused loop. Every
    reduction becomes per-member (axes 1..; Krylov scalars are [B,1,..]
    broadcastables), convergence is a per-member ``done`` mask, the
    while-loop predicate is "any member unconverged", and a converged
    member's ENTIRE iteration state is frozen via select — the extra
    sweeps the loop runs for the slowest member are bit-exact identity
    for the converged ones, so each member's solution equals its solo
    solve (tests/test_fleet.py pins this). ``iters``/``residual``/
    ``converged``/``stalled`` come back per-member [B].
    """
    # trace-time only: tags the enclosing named executable's compile-
    # ledger entry with this solver component (tracing.py); a no-op
    # inside an already-compiled launch
    tracing.note_component("poisson.bicgstab")
    if M is None:
        M = lambda v: v
    dt_ = b.dtype
    sd = sum_dtype or dt_

    if member_axis:
        raxes = tuple(range(1, b.ndim))

        def dot(a_, b_):
            return jnp.sum((a_ * b_).astype(sd), axis=raxes,
                           keepdims=True).astype(dt_)

        def linf(a_):
            return jnp.max(jnp.abs(a_), axis=raxes, keepdims=True)
    else:
        def dot(a_, b_):
            return jnp.sum((a_ * b_).astype(sd)).astype(dt_)

        def linf(a_):
            return jnp.max(jnp.abs(a_))

    if x0 is None:
        # A is linear (a Laplacian), so A(0) = 0: starting from zero
        # the initial residual IS b — skip a full operator application
        # and the zeros broadcast feeding it
        x0 = jnp.zeros_like(b)
        r0 = b
    else:
        r0 = b - A(x0)
    norm0 = linf(r0)
    target = jnp.maximum(jnp.asarray(tol, dt_), tol_rel * norm0)
    # per-member-shaped constants under member_axis ([B,1,..], so the
    # while-loop carry shapes are stable); plain scalars otherwise
    one = jnp.ones_like(norm0)
    i0 = jnp.zeros_like(norm0, dtype=jnp.int32) if member_axis \
        else jnp.asarray(0, jnp.int32)

    init = _State(
        x=x0, r=r0, rhat=r0, p=jnp.zeros_like(b), v=jnp.zeros_like(b),
        rho=one, alpha=one, omega=one,
        it=jnp.asarray(0, jnp.int32), restarts=i0,
        x_opt=x0, norm_opt=norm0, norm0=norm0,
        best_it=i0,
        best_l2=jnp.sqrt(dot(r0, r0)),
        impr_it=i0,
        it_m=i0,
        done=norm0 <= target,
    )

    breakdown_eps = jnp.asarray(1e-21 if dt_ == jnp.float64 else 1e-30, dt_)

    def cond(s: _State):
        # member_axis: run while ANY member is unconverged (each member
        # freezes independently in the body below)
        return jnp.any(~s.done) & (s.it < max_iter)

    @tracing.in_scope("krylov")
    def body(s: _State):
        frozen = s.done   # members already converged at loop entry
        rho_probe = dot(s.rhat, s.r)
        # serious breakdown -> restart with rhat = r (cuda.cu:457-477)
        norm_r = jnp.sqrt(dot(s.r, s.r))
        norm_rhat = jnp.sqrt(dot(s.rhat, s.rhat))
        breakdown = jnp.abs(rho_probe) < (
            jnp.asarray(1e-16, dt_) * norm_r * norm_rhat + breakdown_eps
        )
        can_restart = s.restarts < max_restarts
        refresh = (s.it - s.best_it) >= refresh_every
        if member_axis:
            # a frozen member's best_it stops moving while the global
            # it keeps climbing, so its refresh flag would latch true
            # and force the expensive true-residual cond branch on
            # every remaining iteration of the fused loop — for state
            # the freeze discards anyway. Mask it: only ACTIVE members
            # request refreshes.
            refresh = refresh & ~frozen
        do_restart = (breakdown & can_restart) | refresh
        give_up = breakdown & ~can_restart & ~refresh

        # periodic true-residual refresh from the CURRENT iterate (see
        # docstring for why never from a "best" iterate). The refresh
        # also re-grounds (x_opt, norm_opt) in TRUE residuals: between
        # refreshes they are tracked by the recursive norm, which can
        # drift low and would otherwise freeze x_opt at a stale iterate
        # while reporting a spuriously small residual.
        x = s.x
        def refreshed():
            r_true = b - A(s.x)
            n_true = linf(r_true)
            n_opt_true = linf(b - A(s.x_opt))
            take_x = n_true <= n_opt_true
            return (r_true,
                    jnp.where(take_x, s.x, s.x_opt),
                    jnp.where(take_x, n_true, n_opt_true))

        if member_axis:
            # refresh is a per-member vector: pay the true-residual
            # operator applications only when ANY member refreshes, and
            # select per member inside
            def refreshed_m():
                r_t, xo_t, no_t = refreshed()
                return (jnp.where(refresh, r_t, s.r),
                        jnp.where(refresh, xo_t, s.x_opt),
                        jnp.where(refresh, no_t, s.norm_opt))

            r, x_opt0, norm_opt0 = jax.lax.cond(
                jnp.any(refresh),
                refreshed_m,
                lambda: (s.r, s.x_opt, s.norm_opt),
            )
        else:
            r, x_opt0, norm_opt0 = jax.lax.cond(
                refresh,
                refreshed,
                lambda: (s.r, s.x_opt, s.norm_opt),
            )
        rhat = jnp.where(do_restart, r, s.rhat)
        rho_new = jnp.where(do_restart, dot(rhat, r), rho_probe)
        beta = jnp.where(
            do_restart, jnp.zeros_like(rho_new),
            (rho_new / (s.rho + breakdown_eps)) * (s.alpha / (s.omega + breakdown_eps)),
        )
        p = r + beta * (s.p - s.omega * s.v)
        z = M(p)
        v = A(z)
        alpha = rho_new / (dot(rhat, v) + breakdown_eps)
        h = x + alpha * z
        sres = r - alpha * v
        zs = M(sres)
        t = A(zs)
        omega = dot(t, sres) / (dot(t, t) + breakdown_eps)
        x = h + omega * zs
        r = sres - omega * t

        norm = linf(r)
        better = norm < norm_opt0
        x_opt = jnp.where(better, x, x_opt0)
        norm_opt = jnp.where(better, norm, norm_opt0)
        # stall exit keyed on the L2 norm, sampled ONLY at refresh
        # iterations: r was re-grounded on the TRUE residual this
        # iteration (one Krylov update ago), so consecutive samples are
        # like-for-like. Comparing per-iteration recursive norms against
        # a refresh-corrected history would latch a drifted-low floor
        # that true residuals can never beat, firing mid-convergence.
        # stall_rtol sets what counts as progress: 0.999 (production)
        # keeps grinding for any 0.1%/window gain; exact mode passes
        # 0.99 so windows improving < 1% stop the solve — a
        # diminishing-returns cut that trims the tol-0 startup tail
        # (71 -> ~40 iterations on the canonical probe) at the cost of
        # one order of residual depth nobody consumes
        l2_now = jnp.sqrt(dot(r, r))
        improved = refresh & (l2_now < stall_rtol * s.best_l2)
        best_l2 = jnp.where(refresh, jnp.minimum(s.best_l2, l2_now),
                            s.best_l2)
        impr_it = jnp.where(improved, s.it, s.impr_it)
        stalled = (s.it - impr_it) >= stall_iters
        done = (norm <= target) | give_up | stalled

        # only breakdown-triggered restarts consume the reference's
        # max_restarts budget; periodic refreshes are unbudgeted.
        # best_it here records the last refresh iteration.
        new = _State(
            x=x, r=r, rhat=rhat, p=p, v=v,
            rho=rho_new, alpha=alpha, omega=omega,
            it=s.it + 1,
            restarts=s.restarts + (breakdown & can_restart).astype(jnp.int32),
            x_opt=x_opt, norm_opt=norm_opt, norm0=s.norm0,
            best_it=jnp.where(do_restart, s.it, s.best_it),
            best_l2=best_l2,
            impr_it=impr_it,
            it_m=s.it_m + 1,
            done=done,
        )
        if not member_axis:
            return new
        # per-member convergence mask: a member that was done at loop
        # entry FREEZES its entire iteration state — the sweeps the
        # loop keeps running for slower members are exact identity for
        # it, so its solution is bit-equal to its solo solve
        keep = lambda old, cur: jnp.where(frozen, old, cur)
        return _State(
            x=keep(s.x, new.x), r=keep(s.r, new.r),
            rhat=keep(s.rhat, new.rhat), p=keep(s.p, new.p),
            v=keep(s.v, new.v), rho=keep(s.rho, new.rho),
            alpha=keep(s.alpha, new.alpha), omega=keep(s.omega, new.omega),
            it=new.it,
            restarts=keep(s.restarts, new.restarts),
            x_opt=keep(s.x_opt, new.x_opt),
            norm_opt=keep(s.norm_opt, new.norm_opt), norm0=s.norm0,
            best_it=keep(s.best_it, new.best_it),
            best_l2=keep(s.best_l2, new.best_l2),
            impr_it=keep(s.impr_it, new.impr_it),
            it_m=keep(s.it_m, new.it_m),
            done=frozen | new.done,
        )

    final = jax.lax.while_loop(cond, body, init)
    # the loop may exit on the CURRENT residual crossing target while
    # x_opt still holds an older iterate — return whichever is better
    final_norm = linf(final.r)
    use_x = final_norm <= final.norm_opt
    converged = jnp.minimum(final_norm, final.norm_opt) <= target
    # stall classification against the member's OWN frozen counter
    # (it_m == it unbatched): under member_axis the global it keeps
    # climbing after a member froze, which would misclassify an early
    # give-up exit as a stall
    stalled = ~converged & ((final.it_m - final.impr_it) >= stall_iters)
    sq = (lambda v: v.reshape(-1)) if member_axis else (lambda v: v)
    return BiCGSTABResult(
        x=jnp.where(use_x, final.x, final.x_opt),
        iters=sq(final.it_m) if member_axis else final.it,
        residual=sq(jnp.where(use_x, final_norm, final.norm_opt)),
        converged=sq(converged),
        stalled=sq(stalled),
    )


# ---------------------------------------------------------------------------
# Matrix-free multigrid as a FULL solver (not a preconditioner)
#
# For uniform / sharded-uniform / fleet-batched grids the MG hierarchy
# is strong enough to BE the solver: each iteration is one V-cycle
# correction x += M(b - A x) with the true residual recomputed at full
# precision (iterative refinement — the bf16 cycle interior cannot
# limit the achievable residual), so the production tolerances are
# reached in ~2-3 cycles from a warm deltap guess where Krylov spends
# 2 operator + 2 preconditioner applications per iteration on dot
# products the cycle never needs. Linear problem, exactly-represented
# coarse operators: the FAS formulation (arXiv:2510.11152) reduces to
# the correction scheme, implemented here directly. Kept as a LATCHED
# alternative (CUP2D_POIS=fas) — BiCGSTAB stays the default and the
# robustness backstop (exact/escalation solves always run Krylov).
# ---------------------------------------------------------------------------

class _MGSolveState(NamedTuple):
    x: jnp.ndarray
    r: jnp.ndarray
    norm: jnp.ndarray
    best: jnp.ndarray     # running best Linf (the stall baseline: at
    #                       the precision floor the per-cycle norm
    #                       wanders, so consecutive-cycle comparison
    #                       would keep resetting the counter)
    it: jnp.ndarray       # global cycle counter (scalar)
    it_m: jnp.ndarray     # per-member cycle count (== it unbatched)
    no_impr: jnp.ndarray  # consecutive cycles without stall_rtol gain
    done: jnp.ndarray


def mg_solve(
    A: Callable[[jnp.ndarray], jnp.ndarray],
    b: jnp.ndarray,
    mg: "MultigridPreconditioner",
    x0: jnp.ndarray | None = None,
    tol: float = 1e-3,
    tol_rel: float = 1e-2,
    max_cycles: int = 50,
    stall_cycles: int = 4,
    stall_rtol: float = 0.999,
    member_axis: bool = False,
    fmg: bool = False,
) -> BiCGSTABResult:
    """Solve A x = b by repeated multigrid cycles, whole loop on device.

    Same result contract and convergence criterion as ``bicgstab``
    (Linf(r) <= max(tol, tol_rel * Linf(r0))), so every driver/telemetry
    consumer reads it unchanged; ``iters`` counts CYCLES — one operator
    application and one V-cycle each, vs Krylov's 2 A + 2 M per
    iteration.

    ``fmg``: open with one F-cycle (coarsest-first, prolongated initial
    guesses — ``MultigridPreconditioner.fcycle``) before the V-cycle
    loop; counted as a cycle in ``iters``. Worth it on cold RHSes; the
    warm per-step production solves don't need it.

    ``stall_cycles``/``stall_rtol``: a cycle that fails to shrink the
    Linf residual by stall_rtol for that many consecutive cycles exits
    ``stalled`` — the solver's precision floor (the health verdict
    treats a stalled exit as benign, resilience.health_verdict), so a
    target below what the cycle can reach degrades gracefully instead
    of burning max_cycles. Unlike BiCGSTAB no refresh bookkeeping is
    needed: the residual here is always the TRUE residual.

    ``member_axis``: leading member axis of B independent systems, one
    fused cycle loop; a converged member's state is frozen via select
    (extra cycles are bit-exact identity — the fleet freeze contract,
    tests/test_fleet.py / test_poisson.py), and
    iters/residual/converged/stalled come back per-member [B].
    """
    # trace-time only — see the bicgstab note
    tracing.note_component("poisson.mg_solve")
    dt_ = b.dtype
    if member_axis:
        raxes = tuple(range(1, b.ndim))

        def linf(a_):
            return jnp.max(jnp.abs(a_), axis=raxes, keepdims=True)
    else:
        def linf(a_):
            return jnp.max(jnp.abs(a_))

    if x0 is None:
        # A(0) = 0: the initial residual IS b (same skip as bicgstab)
        x0 = jnp.zeros_like(b)
        r0 = b
    else:
        r0 = b - A(x0)
    norm0 = linf(r0)
    target = jnp.maximum(jnp.asarray(tol, dt_), tol_rel * norm0)
    i0 = jnp.zeros_like(norm0, dtype=jnp.int32) if member_axis \
        else jnp.asarray(0, jnp.int32)

    if fmg:
        x0 = x0 + mg.fcycle(r0)
        r0 = b - A(x0)
        norm0_f = linf(r0)
        init_norm = norm0_f
        it0 = jnp.asarray(1, jnp.int32)
        itm0 = i0 + 1
    else:
        init_norm = norm0
        it0 = jnp.asarray(0, jnp.int32)
        itm0 = i0

    init = _MGSolveState(
        x=x0, r=r0, norm=init_norm, best=init_norm,
        it=it0, it_m=itm0, no_impr=i0,
        done=init_norm <= target,
    )

    def cond(s: _MGSolveState):
        return jnp.any(~s.done) & (s.it < max_cycles)

    def body(s: _MGSolveState):
        frozen = s.done
        x = s.x + mg(s.r)
        r = b - A(x)            # TRUE residual, solver precision
        norm = linf(r)
        improved = norm < stall_rtol * s.best
        best = jnp.minimum(s.best, norm)
        no_impr = jnp.where(improved, jnp.zeros_like(s.no_impr),
                            s.no_impr + 1)
        done = (norm <= target) | (no_impr >= stall_cycles)
        new = _MGSolveState(
            x=x, r=r, norm=norm, best=best,
            it=s.it + 1, it_m=s.it_m + 1, no_impr=no_impr, done=done,
        )
        if not member_axis:
            return new
        keep = lambda old, cur: jnp.where(frozen, old, cur)
        return _MGSolveState(
            x=keep(s.x, new.x), r=keep(s.r, new.r),
            norm=keep(s.norm, new.norm),
            best=keep(s.best, new.best),
            it=new.it,
            it_m=keep(s.it_m, new.it_m),
            no_impr=keep(s.no_impr, new.no_impr),
            done=frozen | new.done,
        )

    final = jax.lax.while_loop(cond, body, init)
    converged = final.norm <= target
    stalled = ~converged & (final.no_impr >= stall_cycles)
    sq = (lambda v: v.reshape(-1)) if member_axis else (lambda v: v)
    return BiCGSTABResult(
        x=final.x,
        iters=sq(final.it_m) if member_axis else final.it,
        residual=sq(final.norm),
        converged=sq(converged),
        stalled=sq(stalled),
    )


# ---------------------------------------------------------------------------
# FFT-diagonalized DIRECT solve (ISSUE 20, CUP2D_POIS=fftd)
#
# A periodic direction's wrap second difference is diagonalized by the
# real FFT into per-mode eigenvalues lam(k) = 2 cos(2 pi k / n) - 2
# (the structural template is arXiv:2106.03583's FFT-accelerated
# multi-block solver). Both directions periodic -> pointwise spectral
# divide; one periodic -> an independent tridiagonal system per mode
# along the wall axis, whose Neumann/Dirichlet rows come from the BC
# table's pressure signs. Either way the per-step V-cycle train
# collapses into ONE direct solve.
# ---------------------------------------------------------------------------

class FFTDiagPlan:
    """Host-precomputed plan for the FFT-diagonalized direct Poisson
    solve of the undivided per-face Laplacian (bc.py periodic kind).

    * ``px and py`` (fully-periodic box): 2D transform, pointwise
      divide by lam_y(m) + lam_x(k), inverse transform. The transform
      is the real Hartley pair as matmul stages on the MXU
      (``ops.dft.HartleyPlan2D``) wherever both lengths split into
      factors <= 256, XLA's real FFT elsewhere. The (0, 0) nullspace
      mode is pinned to zero, so the returned solution is exactly
      mean-free (the projection's mean removal is then a no-op).
    * one periodic direction: real FFT along it, then one TRIDIAGONAL
      system per mode along the other (wall) axis — unit off-diagonals
      and diagonal lam(k) - 2 + wall sign at the edge rows, solved by
      the Thomas algorithm as two length-n first-order scans batched
      over all modes and fleet members. The elimination coefficients
      depend only on the STATIC diagonal, so they are precomputed here
      in f64 numpy and baked as two [n, nmodes] device constants; the
      per-solve work is the two complex recurrences plus the
      transforms. The py-only case runs as the TRANSPOSED px-only
      problem (the 5-point operator is symmetric under transposing the
      grid), so one kernel serves both orientations.

    Nullspace (periodic channel): the k=0 mode of all-Neumann walls is
    the singular 1D Neumann Laplacian. Its RHS is mean-removed and the
    first row pinned to x[0] = 0; the singular matrix's columns sum to
    zero, so the pinned solve satisfies the original system EXACTLY
    for a mean-free RHS — no residual leaks into the reported Linf.
    Dirichlet walls (outflow faces) are non-singular and skip the pin.

    Sharding: the transform and the tridiagonal scan are whole-array
    sequential along their axes — there is no shard_map form, and the
    mesh's x-split always shards one of the two (periodic x: the
    transform axis; periodic y only: the scan axis).
    ``UniformGrid.attach_mesh`` refuses the fftd latch outright and drops
    a plan the table selected (parallel/shard_halo.py); sharded periodic
    cases run under bicgstab/fas, whose wrap stencils GSPMD partitions.
    """

    def __init__(self, ny: int, nx: int, dtype, px: bool, py: bool,
                 edge_signs):
        if not (px or py):
            raise ValueError(
                "FFTDiagPlan needs at least one periodic direction "
                "(got px=False, py=False): with walls on all four "
                "faces there is nothing to diagonalize — use "
                "bicgstab/mg_solve")
        self.ny, self.nx = ny, nx
        self.px, self.py = px, py
        self.dtype = jnp.dtype(dtype)
        sx_lo, sx_hi, sy_lo, sy_hi = edge_signs
        if px and py:
            self.pin = True     # the zeroed (0,0) mode IS the pin
            self.dft = HartleyPlan2D.build(ny, nx, self.dtype)
            if self.dft is not None:
                # the MXU transform's spectrum is [..., nx', ny'] in its
                # slot order: one eigenvalue vector per axis in that
                # order (f64 on the host: 2 cos x - 2 cancels in f32
                # near k = 0), the divide formed from them in solve
                def lam(freq, n):
                    return jnp.asarray(
                        2.0 * np.cos(2.0 * np.pi * freq / n) - 2.0,
                        self.dtype)
                self.lx = lam(self.dft.x.freq, nx)
                self.ly = lam(self.dft.y.freq, ny)
                return
            lx = 2.0 * np.cos(
                2.0 * np.pi * np.arange(nx // 2 + 1) / nx) - 2.0
            ly = 2.0 * np.cos(2.0 * np.pi * np.arange(ny) / ny) - 2.0
            lam = ly[:, None] + lx[None, :]
            mask = lam < -1e-12
            ilam = np.where(mask, 1.0 / np.where(mask, lam, 1.0), 0.0)
            self.ilam = jnp.asarray(ilam, self.dtype)
            return
        # single periodic direction: transform length n_t, tridiagonal
        # system length n_s with the wall axis's signs
        if px:
            n_t, n_s = nx, ny
            s_lo, s_hi = sy_lo, sy_hi
        else:
            n_t, n_s = ny, nx
            s_lo, s_hi = sx_lo, sx_hi
        nk = n_t // 2 + 1
        lam = 2.0 * np.cos(2.0 * np.pi * np.arange(nk) / n_t) - 2.0
        d = np.tile(lam[None, :], (n_s, 1)) - 2.0
        d[0, :] += s_lo    # lint: allow[leading-dim] -- host numpy precompute, fixed [n_s, nk] matrix rows, never batched
        d[-1, :] += s_hi   # lint: allow[leading-dim] -- host numpy precompute, fixed [n_s, nk] matrix rows, never batched
        c = np.ones((n_s, nk))
        c[-1, :] = 0.0     # lint: allow[leading-dim] -- host numpy precompute: no superdiagonal on the last row
        self.pin = (s_lo == 1.0) and (s_hi == 1.0)
        if self.pin:
            # singular k=0 all-Neumann mode: row 0 -> identity
            d[0, 0] = 1.0  # lint: allow[leading-dim] -- host numpy precompute of the k=0 nullspace pin
            c[0, 0] = 0.0  # lint: allow[leading-dim] -- host numpy precompute of the k=0 nullspace pin
        # Thomas forward elimination on the static matrix (unit
        # subdiagonal): denom_j = d_j - cp_{j-1}, cp_j = c_j / denom_j
        denom = np.empty((n_s, nk))
        cp = np.empty((n_s, nk))
        denom[0] = d[0]
        cp[0] = c[0] / denom[0]
        for j in range(1, n_s):
            denom[j] = d[j] - cp[j - 1]
            cp[j] = c[j] / denom[j]
        self.cp = jnp.asarray(cp, self.dtype)
        self.inv_denom = jnp.asarray(1.0 / denom, self.dtype)

    def solve(self, b: jnp.ndarray) -> jnp.ndarray:
        """Direct solve lap(x) = b (undivided per-face operator).
        Leading axes (the fleet's member batch) ride the same
        transforms — the mode axis is embarrassingly parallel."""
        if self.px and self.py:
            if self.dft is None:
                tracing.note_component("poisson.fftd_dft[xla]")
                F = jnp.fft.rfft2(b)
                x = jnp.fft.irfft2(F * self.ilam, s=(self.ny, self.nx))
                return x.astype(b.dtype)
            tracing.note_component(f"poisson.fftd_dft[{self.dft.note()}]")
            s = self.dft.forward(b)                  # [..., nx', ny']
            lam = self.lx[:, None] + self.ly[None, :]
            mask = lam < -1e-12
            # the Hartley pair is unnormalized: 1 / (ny nx) rides here
            ilam = jnp.where(mask, 1.0 / (self.ny * self.nx)
                             / jnp.where(mask, lam, 1.0), 0.0)
            return self.dft.inverse(s * ilam).astype(b.dtype)
        swap = not self.px        # py-only: transposed px-only problem
        if swap:
            b = jnp.swapaxes(b, -1, -2)
        n_t = b.shape[-1]
        bh = jnp.fft.rfft(b, axis=-1)          # [..., n_s, nk]
        if self.pin:
            # mean-free RHS for the singular k=0 mode, then pin row 0
            col0 = bh[..., :, 0]
            col0 = col0 - jnp.mean(col0, axis=-1, keepdims=True)
            bh = bh.at[..., :, 0].set(col0)
            bh = bh.at[..., 0, 0].set(0.0)
        bt = jnp.moveaxis(bh, -2, 0)           # [n_s, ..., nk]

        def fwd(dp_prev, xs):
            bj, idj = xs
            dp = (bj - dp_prev) * idj
            return dp, dp

        _, dps = jax.lax.scan(fwd, jnp.zeros_like(bt[0]),
                              (bt, self.inv_denom))

        def bwd(x_next, xs):
            dpj, cpj = xs
            xj = dpj - cpj * x_next
            return xj, xj

        _, xt = jax.lax.scan(bwd, jnp.zeros_like(bt[0]),
                             (dps, self.cp), reverse=True)
        x = jnp.fft.irfft(jnp.moveaxis(xt, 0, -2), n=n_t, axis=-1)
        if swap:
            x = jnp.swapaxes(x, -1, -2)
        return x.astype(b.dtype)


@tracing.in_scope("fft_diag")
def fft_diag_solve(
    A: Callable[[jnp.ndarray], jnp.ndarray],
    b: jnp.ndarray,
    plan: "FFTDiagPlan",
    tol: float = 1e-3,
    tol_rel: float = 1e-2,
    member_axis: bool = False,
) -> BiCGSTABResult:
    """One-shot FFT-diagonalized direct solve with the SAME result/
    stall/telemetry contract as ``bicgstab``/``mg_solve``, so drivers,
    health verdicts and ``poisson_mode`` attribution read it
    unchanged: ``x`` from :meth:`FFTDiagPlan.solve`, ``residual`` the
    TRUE Linf residual of that x, ``converged`` against the shared
    criterion Linf(r) <= max(tol, tol_rel * Linf(b)), ``iters`` = 1
    unconditionally. A tol-0 "exact" request reports the direct
    solve's precision floor through the benign ``stalled`` bit —
    exactly how bicgstab's stall detector classifies its own tol-0
    exits (resilience.health_verdict treats it as benign).

    ``member_axis``: the fleet's B independent systems batch through
    ONE transform (the mode axis is embarrassingly parallel); every
    member reports iters == 1, so the converged-member freeze contract
    of the iterative solvers is trivially inert — there are no extra
    sweeps a frozen member could diverge under (tests/test_fleet.py).
    """
    tracing.note_component("poisson.fft_diag_solve")
    dt_ = b.dtype
    if member_axis:
        raxes = tuple(range(1, b.ndim))

        def linf(a_):
            return jnp.max(jnp.abs(a_), axis=raxes)
    else:
        def linf(a_):
            return jnp.max(jnp.abs(a_))

    x = plan.solve(b)
    residual = linf(b - A(x))
    target = jnp.maximum(jnp.asarray(tol, dt_), tol_rel * linf(b))
    converged = residual <= target
    return BiCGSTABResult(
        x=x,
        iters=jnp.ones_like(converged, dtype=jnp.int32)
        if member_axis else jnp.asarray(1, jnp.int32),
        residual=residual,
        converged=converged,
        stalled=~converged,
    )


# ---------------------------------------------------------------------------
# Forest-native FAS hierarchy (the composite forest's own refinement
# levels as the multigrid levels)
# ---------------------------------------------------------------------------

def _up2_bilinear(a: jnp.ndarray) -> jnp.ndarray:
    """Cell-centered 2x bilinear upsample of a [H, W] image with edge
    clamp: fine centers sit at quarter offsets, so the separable
    weights are (3/4, 1/4). Pure slice/stack arithmetic — the ladder
    step of the structured two-level transfers (no per-cell indices).
    Lives here (with ``_down2_mean``) since the forest FAS cycle below
    walks the same ladder; ``amr`` re-imports both."""
    def up1(v):
        vm = jnp.concatenate([v[:1], v[:-1]], axis=0)
        vp = jnp.concatenate([v[1:], v[-1:]], axis=0)
        even = 0.75 * v + 0.25 * vm
        odd = 0.75 * v + 0.25 * vp
        return jnp.stack([even, odd], axis=1).reshape(
            2 * v.shape[0], *v.shape[1:])
    return up1(up1(a).T).T


def _down2_mean(a: jnp.ndarray) -> jnp.ndarray:
    """2x2 mean coarsening of a [H, W] image (full-weighting adjoint
    of nearest prolongation; each fine cell carries weight 1/4)."""
    rows = a[0::2, :] + a[1::2, :]
    return 0.25 * (rows[:, 0::2] + rows[:, 1::2])


def _img_lap_neumann(a: jnp.ndarray) -> jnp.ndarray:
    """Undivided 5-point Laplacian of a [H, W] image with ZERO-GRADIENT
    (edge-replicate) ghosts. The intermediate-level smoothing operator
    of the forest FAS cycle: a window edge is either a domain wall
    (truly Neumann) or a refinement interface to coarser blocks, where
    zero-gradient extrapolation is the consistent approximation for
    the SMOOTH error the coarser rungs carry. The zero-ghost
    (Dirichlet) variant is NOT usable here: it reads the O(1) boundary
    values of the prolonged base correction as O(1) artificial edge
    residuals, and on a multi-rung ladder (forest levels several steps
    above c) that injection compounds per cycle into divergence —
    measured on the deep-ladder probe (rate 1.5+ Dirichlet vs 0.13
    Neumann), while single-rung forests are insensitive."""
    p = jnp.pad(a, 1, mode="edge")
    return (p[2:, 1:-1] + p[:-2, 1:-1]
            + p[1:-1, 2:] + p[1:-1, :-2]) - 4.0 * a


class ForestFASCycle:
    """One multigrid cycle over the composite forest's OWN refinement
    levels — the ``mg`` object of :func:`mg_solve` for the
    ``CUP2D_POIS=fas`` forest path (linear problem, so the FAS
    formulation of arXiv:2510.11152 reduces to the correction scheme,
    same as the uniform solver above).

    Level structure (finest first):

    * the COMPOSITE level: all active blocks at their native
      resolutions, smoothed by damped block-Jacobi (the exact-inverse
      single-block preconditioner — ``apply_block_precond_blocks``)
      through ``smooth_blocks``; on the sharded forest this is the
      comm/compute-overlapped block-surface sweep
      (``shard_halo.overlap_block_jacobi_sweeps``);
    * one WINDOW-image level per forest refinement level above the
      coarse level c (the PR-4/PR-6 cropped active-tile windows —
      ``paint_fine`` deposits each block's residual at its own level),
      smoothed by damped Jacobi on ``_img_lap_neumann`` and walked
      2x sum/bilinear ladder;
    * the uniform BASE level c, solved EXACTLY by the DCT-II spectral
      Neumann solve (``base_solve`` — the PR-6 machinery, with the
      below-c block deposits folded in).

    All transfer closures are built by ``AMRSim._fas_transfers`` from
    the same ``_build_coarse_maps`` pytree as the two-level
    preconditioner, so the executable is keyed on the level SET like
    every other consumer. ``__call__`` runs a V-cycle (block pre-smooth
    first); ``fcycle`` opens base-level-first (no pre-smooth) for cold
    RHSes — ``mg_solve(fmg=True)``, the ``fas-f`` latch."""

    def __init__(self, A, smooth_blocks, paint_fine, base_solve,
                 extract_all, cih2, nu_img: int = 2,
                 omega: float = 0.8, nu_pre: int = 1, nu_post: int = 1,
                 leg_dtype=None):
        self.A = A
        self.smooth_blocks = tracing.scoped("mg_smooth", smooth_blocks)
        self.paint_fine = tracing.scoped("mg_transfer", paint_fine)
        self.base_solve = tracing.scoped("mg_coarse", base_solve)
        self.extract_all = tracing.scoped("mg_transfer", extract_all)
        self.cih2 = cih2
        self.nu_img = nu_img
        self.omega = omega
        self.nu_pre = nu_pre
        self.nu_post = nu_post
        # leg_dtype (ISSUE 19): storage dtype of the window-image
        # ladder legs ONLY — the V-down/V-up smooths, restrictions and
        # prolongations run in bf16 while mg_solve's outer loop keeps
        # the f32 true residual (iterative refinement absorbs the leg
        # rounding). The composite block smooth stays at solver
        # precision (its P_inv GEMM is the accuracy-critical finest
        # leg) and the DCT base solve stays f32 HIGHEST — the ladder
        # casts its restricted RHS back up before entering it. None =
        # solver-precision legs, bit-identical to the pre-tier cycle.
        self.leg_dtype = leg_dtype

    @tracing.in_scope("mg_smooth")
    def _img_smooth(self, e, r, n: int, from_zero: bool = False):
        # damped Jacobi on the Neumann-ghost window image; interior
        # diag of the undivided 5-point operator is -4
        if from_zero and n > 0:
            e = (-0.25 * self.omega) * r
            n -= 1
        for _ in range(n):
            e = e - 0.25 * self.omega * (r - _img_lap_neumann(e))
        return e

    def _cycle(self, r, pre: bool):
        if pre:
            e = self.smooth_blocks(None, r, self.nu_pre, from_zero=True)
            r1 = r - self.A(e)
        else:
            e = None
            r1 = r
        rdiv = r1 * self.cih2            # divided residual per block
        rimgs = self.paint_fine(rdiv)    # finest -> c+1, undivided
        if self.leg_dtype is not None:
            # bf16 ladder legs: one downcast per painted level; the
            # whole V-down/V-up below then runs at leg precision
            rimgs = [R.astype(self.leg_dtype) for R in rimgs]
        # V-down over the window-image levels: smooth, restrict the
        # smoothed residual one ladder step, fold in the next level's
        # own deposit (undivided restriction = sum-of-4)
        es, accs = [], []
        racc = None
        for R in rimgs:
            racc = R if racc is None else R + racc
            accs.append(racc)
            el = self._img_smooth(None, racc, self.nu_img,
                                  from_zero=True)
            es.append(el)
            res = racc - _img_lap_neumann(el)
            rows = res[0::2, :] + res[1::2, :]
            racc = rows[:, 0::2] + rows[:, 1::2]
        # exact spectral base solve (folds the <= c deposits of rdiv
        # in); awin = the window slice of the base correction. The
        # base solve is precision-critical (f32 HIGHEST DCT) — leg
        # storage casts back up at its door.
        if self.leg_dtype is not None and racc is not None:
            racc = racc.astype(rdiv.dtype)
        ec, awin = self.base_solve(rdiv, racc)
        # V-up: prolongate, add the stored level error, post-smooth
        # against the stored accumulated RHS
        if self.leg_dtype is not None and len(rimgs) > 0:
            awin = awin.astype(self.leg_dtype)
        for i in range(len(rimgs) - 1, -1, -1):
            a = _up2_bilinear(awin) + es[i]
            awin = self._img_smooth(a, accs[i], self.nu_img)
            es[i] = awin
        if self.leg_dtype is not None:
            es = [el.astype(rdiv.dtype) for el in es]
        corr = self.extract_all(ec, es)
        e = corr if e is None else e + corr
        return self.smooth_blocks(e, r, self.nu_post)

    @tracing.in_scope("mg_cycle")
    def __call__(self, r):
        return self._cycle(r, pre=True)

    @tracing.in_scope("mg_cycle")
    def fcycle(self, r):
        # coarse-first opening for cold RHSes (fas-f): the base modes
        # dominate a cold deltap RHS (VERDICT r3 #9), so spend the
        # first correction on them before any fine smoothing
        return self._cycle(r, pre=False)


# ---------------------------------------------------------------------------
# Shared projection-correction epilogue (PR 9)
# ---------------------------------------------------------------------------

@tracing.in_scope("project_correct")
def project_correct(x, pres_old, vel, h, dt, *, spmd_safe=False,
                    mean_axes=None, tier="xla", remove_mean=True,
                    grad_signs=None, periodic=None):
    """Post-solve projection epilogue shared by the uniform and fleet
    drivers: ``pres = (x - mean x) + pres_old - mean pres_old`` and
    ``vel += -dt/(2h) * grad_neumann(pres) / h^2``.

    x: the solver's deltap field; vel: [..., 2, Ny, Nx]; dt: scalar
    (uniform) or a flat per-member vector (fleet, with
    ``mean_axes=(-2, -1)`` selecting per-member means). ``tier`` is the
    caller's kernel-tier latch: on the fused tiers (f32 state) the
    whole epilogue after the means runs as ONE Pallas kernel
    (ops/pallas_kernels.fused_correction — one read of x/pold/vel, one
    write of pres/vel) instead of the XLA mean-subtract + gradient +
    update chain. The XLA branch is the historical expression verbatim,
    so tier="xla" callers are bit-identical to pre-PR-9 code.

    ``remove_mean=False`` (per-face BC engine, bc.py): tables with an
    outflow face carry a Dirichlet pressure row, the operator is
    non-singular and the mean subtraction would shift the anchored
    level — the epilogue then uses dp/pres as-is. ``grad_signs`` is
    the table's (sx_lo, sx_hi, sy_lo, sy_hi) pressure-ghost sign tuple;
    None keeps the legacy all-Neumann gradient verbatim. BOTH branches
    carry it (ISSUE 16): the XLA chain routes it to
    pressure_gradient_update_bc, the fused kernel bakes the static
    signs into its rank-1 edge correction (the all-Neumann default is
    bit-identical to the PR-9 kernel — mean subtraction of the zeros
    mx/mp is the identity, and gs=(1,1,1,1) reproduces the hard-coded
    edge constants).

    ``periodic`` is the table's (px, py) axis flags (bc.periodic_axes,
    ISSUE 20): the gradient's shifts wrap along periodic axes. Only
    the XLA branch carries it — the fused correction kernel has no
    wrap form, and periodic tables can never arm the fused tier
    (ops/pallas_kernels.kernel_supports refuses the pd token), so the
    kernel branch is statically unreachable for them; the guard here
    keeps that invariant structural rather than assumed.

    Returns (vel, pres).
    """
    from .ops.stencil import (pressure_gradient_update_bc,
                              pressure_gradient_update_fused)

    ih2 = 1.0 / (h * h)
    if not remove_mean:
        mx = jnp.zeros((), x.dtype)
        mp = jnp.zeros((), x.dtype)
    elif mean_axes is None:
        mx = jnp.mean(x)
        mp = jnp.mean(pres_old)
    else:
        mx = jnp.mean(x, axis=mean_axes, keepdims=True)
        mp = jnp.mean(pres_old, axis=mean_axes, keepdims=True)
    px, py = periodic if periodic is not None else (False, False)
    if tier != "xla" and x.dtype == jnp.float32 and not (px or py):
        from .ops.pallas_kernels import fused_correction
        lead = x.shape[:-2]
        ny, nx = x.shape[-2:]
        L = 1
        for d in lead:
            L *= int(d)
        L = max(L, 1)
        flat = lambda a: jnp.broadcast_to(
            jnp.asarray(a, jnp.float32), lead + (1, 1)).reshape((L,))
        dtv = jnp.broadcast_to(
            jnp.asarray(dt, jnp.float32), lead).reshape((L,))
        pres, velc = fused_correction(
            x.reshape((L, ny, nx)), pres_old.reshape((L, ny, nx)),
            vel.reshape((L, 2, ny, nx)),
            flat(mx), flat(mp), -0.5 * dtv * h, ih2,
            grad_signs=grad_signs)
        return velc.reshape(vel.shape), pres.reshape(x.shape)
    dt_b = dt[:, None, None, None] if jnp.ndim(dt) == 1 else dt
    if not remove_mean:
        pres = x + pres_old
    else:
        dp = x - mx
        pres = dp + pres_old - mp
    if grad_signs is None:
        dv = pressure_gradient_update_fused(pres, h, dt_b, spmd_safe)
    else:
        sx_lo, sx_hi, sy_lo, sy_hi = grad_signs
        dv = pressure_gradient_update_bc(pres, h, dt_b, sx_lo, sx_hi,
                                         sy_lo, sy_hi, spmd_safe,
                                         px, py)
    return vel + dv * ih2, pres
