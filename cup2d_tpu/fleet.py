"""Fleet batching: B independent uniform cases in ONE fused dispatch.

Every entry point before this module stepped exactly one case per
process, so small/medium grids leave the device dispatch-bound (a
step's device work is a fraction of its host dispatch + pull time;
the ratio on the current chip is not measured yet). Batching independent cases onto one device is the
classic inference-stack throughput lever, and the codebase is shaped
for it: the step core is pure and trivially batchable (every stencil op
in ops/stencil.py is leading-dim agnostic), dt chains on device, and
the diag pull is already one batched ``device_get``. Multi-case
throughput is the same axis AMReX exploits for multiphysics fleets
(arXiv:2009.12009) and the FFT multi-block solver exploits for
massively parallel runs (arXiv:2106.03583).

:class:`FleetSim` advances B independent obstacle-free ``UniformSim``
cases per dispatch:

- the state is one ``FlowState`` with a leading member axis
  ``[B, ...]``; the whole Heun + penalization-free projection step is
  ONE jitted executable regardless of B;
- each member integrates at ITS OWN dt — no lockstep: dt is a ``[B]``
  device vector chained on device from each member's end-state umax,
  and the per-member clocks live in ``times`` (host, settled through
  the same one batched diag pull the single-case drivers pay);
- the pressure solves of all members run in ONE fused Krylov loop
  (``poisson.bicgstab(member_axis=True)``): per-member convergence
  mask, predicate = any member unconverged, converged members frozen
  via select so the extra sweeps are bit-exact identity for them;
- supervision is per-member (``resilience.FleetStepGuard``): a bad
  member restores ONLY its slice of the device snapshot ring and
  replays solo through :meth:`FleetSim.member_step_once`; healthy
  members never rewind.

Contract with the single-case driver (tests/test_fleet.py):
``FleetSim`` with B = 1 is BIT-IDENTICAL to ``UniformSim`` — same
trajectory, equal ``device_get`` counts. For B > 1 each member's
trajectory matches its solo run to <= 1e-12: the advection, projection
and every reduction (umax/energy/Krylov dots) are bit-exact per member
(measured — per-member reductions over ``[B, Ny, Nx]`` reduce the same
elements in the same order as the solo form), but the multigrid
V-cycle's fused elementwise sweep chains compile with different
FMA-contraction choices for member-batched operands (LLVM
vectorization over the leading axis), deviating ~1 ulp per sweep.
Flexible BiCGSTAB absorbs preconditioner inexactness by construction,
so the per-step trajectory deviation stays at ~1e-16..1e-13, the
short warm-start production solves keep IDENTICAL per-member iteration
counts and solver health, and the per-member clock can differ from the
solo clock by at most an ulp per step (the state deviation perturbing
the umax cell's last bit perturbs dt_next's) — pinned by
``tests/test_fleet.py::test_fleet_members_match_solo_runs`` (production
regime — warm deltap guesses, short solves). Long ROUGH solves (~50+
iterations on O(1) residuals) can compound the rounding into a
different — equally converged — Krylov path, so batched-vs-solo
agreement there is at the solve's own convergence target rather than
1e-12; the frozen-member invariance (the select mask) is exact
regardless and pinned separately.

Sharding composes (``mesh=``): when the per-member grid is small,
WHOLE MEMBERS are placed along the existing ``"x"`` mesh axis
(member-parallel — each member's stencils and reductions stay
shard-local, zero per-step halo collectives); big grids fall back to
the spatial x-split of ``ShardedUniformSim`` (with its spmd_safe
stencil forms), where the member axis rides along replicated.
"""

from __future__ import annotations

import os
import time
from collections import deque
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing
from .config import SimConfig
from .ops.pallas_kernels import fused_advect_heun
from .ops.stencil import (
    advect_diffuse_rhs,
    divergence_freeslip,
    divergence_rhs_fused,
    dt_from_umax,
    heun_substage,
    laplacian5_neumann,
)
from .poisson import bicgstab, mg_solve, project_correct
from .uniform import FlowState, UniformGrid, pad_vector, taylor_green_state


def stack_states(states) -> FlowState:
    """Stack per-member FlowStates into one fleet state [B, ...]."""
    return FlowState(*(jnp.stack(list(leaves))
                       for leaves in zip(*states)))


def taylor_green_fleet(grid, members: int, amp0: float = 1.0,
                       decay: float = 0.8) -> FlowState:
    """A B-member ensemble of Taylor-Green vortices at geometrically
    decaying amplitudes (member m scaled by ``amp0 * decay**m``): the
    canonical obstacle-free validation case, with per-member umax — so
    every member runs at its OWN CFL dt and the no-lockstep contract is
    exercised for real (identical members would hide a lockstep bug)."""
    base = taylor_green_state(grid)
    return stack_states([
        base._replace(vel=base.vel * (amp0 * decay ** m))
        for m in range(members)])


class FleetSim:
    """Host-side driver for a B-member fleet: owns the shared step
    counter and per-member clocks, jits the fused member-batched step.

    Mirrors the ``UniformSim`` driver contract (``step_once`` /
    ``async_diag`` / ``_force_exact`` / ``_next_dt``) so the StepGuard
    machinery drives it unchanged — except the diag scalars are [B]
    vectors and the guard generalizes the verdict per member
    (resilience.FleetStepGuard).

    Placement (``mesh=``): ``placement="member"`` shards the leading
    member axis over the mesh (small grids — every member's compute is
    shard-local), ``"spatial"`` shards the x-axis like
    ``ShardedUniformSim`` (big grids), ``"auto"`` picks member-parallel
    when B divides the mesh and the per-member grid fits
    ``member_cells_cap`` cells, else spatial.
    """

    def __init__(self, cfg: SimConfig, level: Optional[int] = None,
                 members: int = 1, mesh=None, placement: str = "auto",
                 member_cells_cap: int = 1 << 22, shaped: bool = False,
                 bc=None):
        if members < 1:
            raise ValueError(f"need members >= 1, got {members}")
        self.cfg = cfg
        self.members = int(members)
        self.mesh = mesh
        # shaped membership: per-member obstacle chi/us/udef fields ride
        # the member axis as FROZEN solids (the moving-shape update loop
        # stays solo/AMR-side; the ROADMAP keeps the padded-forest half)
        self.shaped = bool(shaped)
        lvl = cfg.level_start if level is None else level
        nx = cfg.bpdx * cfg.bs << lvl
        ny = cfg.bpdy * cfg.bs << lvl
        if mesh is not None:
            ndev = mesh.devices.size
            if placement == "auto":
                placement = ("member"
                             if members % ndev == 0
                             and nx * ny <= member_cells_cap
                             else "spatial")
            if placement == "member" and members % ndev != 0:
                raise ValueError(
                    f"member placement needs members ({members}) "
                    f"divisible by mesh size {ndev}")
            if placement == "spatial" and nx % ndev != 0:
                raise ValueError(
                    f"spatial placement needs Nx={nx} divisible by "
                    f"mesh size {ndev}")
        else:
            placement = "single"
        self.placement = placement
        # spmd_safe only where spatial axes are actually sharded: the
        # member-parallel layout keeps every member's stencil axes
        # whole on one device, so the fast zero-shift form is safe
        # bc: the pool-wide per-face BCTable (bc.py) — every member of
        # a fleet shares ONE table (the slot-pool executable bakes the
        # edge treatment in; FleetServer._admit refuses mismatches)
        # a mesh placement hands the step to the partitioner, which
        # cannot split a strip pipeline: those hierarchies stay XLA
        self.grid = UniformGrid(cfg, level,
                                spmd_safe=(placement == "spatial"), bc=bc,
                                mg_smoother=(None if mesh is None
                                             else "xla"))
        g = self.grid
        self.state = stack_states([g.zero_state()
                                   for _ in range(self.members)])
        self.times = np.zeros(self.members, dtype=np.float64)
        self.time = 0.0           # min over members (the loop condition)
        self.step_count = 0       # shared: one dispatch = one step for all
        # slot-pool mask (FleetServer): host truth + device mirror.
        # ``_active=None`` keeps the historical unmasked trace; once
        # set_active() is called the mask is ALWAYS passed as a [B]
        # device operand so admit/evict churn never changes the jit
        # signature (zero steady-state recompiles)
        self.active_mask = np.ones(self.members, dtype=bool)
        self._active = None
        self.shapes: list = []    # obstacle-free by construction
        self.case: Optional[str] = None  # case-registry tag (cases.py)
        self.force_log = None
        self._next_dt = None      # [B] device vector (end-state dt_next)
        self._force_exact = False
        self.async_diag = False
        out_shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            if placement == "member":
                sv = NamedSharding(mesh, P("x", None, None, None))
                ss = NamedSharding(mesh, P("x", None, None))
            else:
                sv = NamedSharding(mesh, P(None, None, None, "x"))
                ss = NamedSharding(mesh, P(None, None, "x"))
            shardings = FlowState(vel=sv, pres=ss, chi=ss, us=sv, udef=sv)
            self.state = FlowState(*(jax.device_put(a, s) for a, s
                                     in zip(self.state, shardings)))
            out_shardings = (shardings, None)
        self._step = tracing.named_jit(
            "fleet.step", jax.jit(
                self._step_impl, donate_argnums=(0,),
                static_argnames=("exact_poisson",),
                **({"out_shardings": out_shardings}
                   if out_shardings is not None else {})),
            variant=("exact_poisson",))
        self._dt = tracing.named_jit("fleet.dt", jax.jit(self._dt_impl))
        # single-member core for the guard's per-member rewind/replay
        # (the cold path): the SAME pure step the solo driver jits, on
        # one member's slice
        self._member_step = tracing.named_jit(
            "fleet.solo_ladder", jax.jit(
                g.step, donate_argnums=(0,),
                static_argnames=("exact_poisson", "obstacle_terms")),
            variant=("exact_poisson",))
        self._member_dt = tracing.named_jit(
            "fleet.solo_dt", jax.jit(g.compute_dt))
        # slot-pool gather/scatter (FleetServer admit/retire churn):
        # ONE fused executable each, slot index as a device int32
        # operand (any slot, same executable) and the fleet state
        # DONATED on install — an admit/retire costs one dispatch, not
        # a per-field op chain plus a full-state copy
        self._extract_member = tracing.named_jit(
            "fleet.extract", jax.jit(
                lambda state, idx: FlowState(*(a[idx] for a in state))))
        self._install_member = tracing.named_jit(
            "fleet.install", jax.jit(
                lambda state, idx, st: FlowState(
                    *(a.at[idx].set(v) for a, v in zip(state, st))),
                donate_argnums=(0,)))
        self._scatter_next_dt = tracing.named_jit(
            "fleet.scatter_dt", jax.jit(
                lambda nd, idx, v: nd.at[idx].set(v),
                donate_argnums=(0,)))
        # the one-dispatch admit: state install + chained-dt scatter
        # fused, dtv <= 0 meaning "compute the fresh CFL dt from the
        # admitted velocity right here" (bit-identical to
        # grid.compute_dt: the max reduce is order-invariant and
        # dt_from_umax elementwise)
        self._admit_impl = tracing.named_jit(
            "fleet.admit", jax.jit(
                lambda state, nd, idx, st, dtv: (
                    FlowState(*(a.at[idx].set(v)
                                for a, v in zip(state, st))),
                    nd.at[idx].set(jnp.where(dtv > 0, dtv,
                                             g.compute_dt(st.vel)))),
                donate_argnums=(0, 1)))
        # per-slot device indices, transferred once: admit/retire churn
        # re-uses them so a slot op is one dispatch with zero fresh h2d
        self._idx = [jnp.asarray(m, jnp.int32)
                     for m in range(self.members)]
        self._dt_sentinel = jnp.zeros((), g.dtype)  # "fresh dt" flag

    # -- fused member-batched step core -------------------------------
    def _dt_impl(self, vel: jnp.ndarray) -> jnp.ndarray:
        """Per-member CFL dt [B] from the fleet velocity [B,2,Ny,Nx]."""
        g = self.grid
        umax = jnp.max(jnp.abs(vel), axis=(-3, -2, -1))
        return dt_from_umax(umax, jnp.asarray(g.h, g.dtype),
                            g.cfg.nu, g.cfg.cfl)

    @property
    def poisson_mode(self) -> str:
        """Active solve-path latch (telemetry schema v4). Fleet reads
        the grid's latch — this module stays env-read-free by design
        (tests/test_env_latch.py walks it)."""
        return self.grid.poisson_mode

    @property
    def kernel_tier(self) -> str:
        """Active advection-kernel tier (telemetry schema v6) — the
        grid's constructor latch, BC-token-suffixed on BC'd fused
        tiers; under spatial placement the fused tier rides the
        halo-mode kernel (shard_halo.fused_advect_heun_sharded) behind
        the same latch (ISSUE 16 retired the construction refusal)."""
        return self.grid.kernel_tier

    @property
    def prec_mode(self) -> str:
        """Hot-loop storage precision (telemetry schema v6)."""
        return self.grid.prec_mode

    @property
    def smoother_tier(self) -> str:
        """Pressure-hierarchy smoother tier (telemetry schema v11) —
        the pool shares the grid's preconditioner latch."""
        return self.grid.smoother_tier

    @property
    def bc_table(self) -> str:
        """Pool-wide per-face BC token string (telemetry schema v8)."""
        return self.grid.bc_table

    def _pressure_solve(self, rhs: jnp.ndarray, exact: bool):
        """Member-batched ``UniformGrid.pressure_solve``: same
        tolerances/refresh/stall policy and the same CUP2D_POIS solve
        path as the solo driver, ONE fused loop with the per-member
        convergence mask. Under ``fas`` the fleet runs member-batched
        MG cycles (the V-cycle is leading-dim agnostic) with the SAME
        converged-member freeze semantics — extra cycles the loop runs
        for the slowest member are bit-exact identity for converged
        ones (poisson.mg_solve member_axis); exact solves keep Krylov
        exactly like the solo path. Under ``fftd`` (ISSUE 20) the B
        member systems batch through ONE set of transforms — the mode
        axis is embarrassingly parallel — and every member reports
        iters == 1, so the converged-member freeze contract is
        trivially inert: there are no extra sweeps a frozen member
        could diverge under (tests/test_fleet.py pins members == solo
        bit-tight)."""
        g = self.grid
        cfg = self.cfg
        if g.runs_direct(exact):
            return g.direct_solve(rhs, exact, member_axis=True)
        if g.solver_mode == "fas" and not exact:
            return mg_solve(
                g.laplacian, rhs, g.mg,
                tol=cfg.poisson_tol, tol_rel=cfg.poisson_tol_rel,
                max_cycles=cfg.max_poisson_iterations,
                fmg=g.fas_fmg, member_axis=True,
            )
        return bicgstab(
            g.laplacian,
            rhs,
            M=g.mg if cfg.precond else None,
            tol=0.0 if exact else cfg.poisson_tol,
            tol_rel=0.0 if exact else cfg.poisson_tol_rel,
            max_iter=cfg.max_poisson_iterations,
            max_restarts=100 if exact else cfg.max_poisson_restarts,
            sum_dtype=g.sum_dtype,
            refresh_every=10 if exact else 50,
            stall_iters=20 if exact else 120,
            stall_rtol=0.99 if exact else 0.999,
            member_axis=True,
        )

    def _step_impl(self, state: FlowState, dt: jnp.ndarray,
                   active=None, exact_poisson: bool = False):
        """One fused step of every member: Heun advection-diffusion +
        deltap projection. Obstacle-free by default (the
        identically-zero penalization/chi terms are statically dropped,
        like ``UniformGrid.step(obstacle_terms=False)``); under
        ``shaped=True`` the Brinkman penalization and the chi-weighted
        divergence RHS ride the member axis (per-member obstacles).
        ``dt`` is [B].

        ``active`` (None or a [B] bool vector) is the slot-pool mask:
        inactive slots still ride the fused dispatch — the executable
        is shape-stable across arbitrary admit/evict churn — but every
        one of their outputs is select-frozen to the input state, the
        same trick ``poisson.bicgstab``/``mg_solve`` use for converged
        members. ``active=None`` traces the exact historical unmasked
        graph (bit-preserving for the fixed-B drivers); an all-True
        mask is itself bit-identical to unmasked (``where(True, new,
        old)`` selects ``new`` verbatim), so a serving fleet at full
        occupancy pays nothing but the selects."""
        g = self.grid
        h = g.h
        ih2 = 1.0 / (h * h)
        dt_req = dt
        if active is not None:
            # a dead slot's cached dt_next lane can be anything (an
            # evicted member leaves NaN behind): give dead lanes a
            # finite dt so their lane arithmetic stays NaN-free, and
            # select-freeze every output below
            dt = jnp.where(active, dt, jnp.ones_like(dt))
        dt3 = dt[:, None, None]            # broadcast vs [B, Ny, Nx]
        dt4 = dt[:, None, None, None]      # broadcast vs [B, 2, Ny, Nx]

        # -- advection-diffusion, 2-stage Heun (per-member dt) --
        vel = state.vel
        # dispatch on the BARE tier latch: the kernel_tier property
        # suffixes the BC token for telemetry and would never compare
        # equal to the bare strings here
        with tracing.scope("advect"):
            if g._kernel_tier != "xla":
                bf16 = g._kernel_tier == "pallas-fused-bf16"
                bc = None if g.bc.is_free_slip else g.bc
                if self.placement == "spatial":
                    # spatially sharded pool: the halo-mode kernel behind
                    # the explicit ppermute exchange — one executable for
                    # all shards, still member-batched on the leading axis
                    from .parallel.shard_halo import fused_advect_heun_sharded
                    vel = fused_advect_heun_sharded(
                        vel, h, g.cfg.nu, dt, self.mesh, bc=bc, bf16=bf16)
                else:
                    # fused megakernel tier, member-batched: the kernel is
                    # leading-dim agnostic with a per-member (afac, dfac)
                    # row, so B members share ONE dispatch per substage
                    vel = fused_advect_heun(
                        vel, h, g.cfg.nu, dt, bc=bc, bf16=bf16)
            else:
                vold = vel
                for k, c in enumerate((0.5, 1.0)):
                    # grid-level BC dispatch (bc.py): the default table
                    # is the legacy pad_vector verbatim; per-face tables
                    # paint their ghosts member-batched (dt4 broadcasts
                    # the per-member outflow extrapolation speed)
                    with tracing.scope(f"substage{k}"):
                        lab = g.pad_vector_field(vel, 3, dt4)
                        rhs = advect_diffuse_rhs(lab, 3, h, g.cfg.nu, dt4)
                        vel = heun_substage(vold, c, rhs, ih2)

        # -- deltap pressure projection --
        if self.shaped:
            # Brinkman penalization, member-batched (the SAME scalar
            # chain as UniformGrid.step's obstacle_terms=True branch,
            # so a shaped member matches its solo run to the documented
            # FMA bound): chi/us/udef ride the member axis as frozen
            # per-member obstacle fields
            with tracing.scope("penalize"):
                alpha = jnp.where(state.chi > 0.5,
                                  1.0 / (1.0 + g.cfg.lam * dt3), 1.0)
                vel = alpha[:, None] * vel \
                    + (1.0 - alpha)[:, None] * state.us
        with tracing.scope("poisson_rhs"):
            if self.shaped:
                b = g.poisson_rhs(vel, state.chi, state.udef, dt3)
            else:
                b = g.poisson_rhs(vel, None, None, dt3)
            div_linf = jnp.max(jnp.abs(b), axis=(-2, -1)) * (dt / (h * h))
            b = b - g.laplacian(state.pres)
            if active is not None:
                # zero the dead rows of the Poisson RHS: their initial
                # residual is 0 <= max(tol, tol_rel*0), so the
                # member-batched solvers mark them done AT ITERATION
                # ZERO with inert diag (iters=0, residual=0, converged)
                # and the existing converged-member freeze keeps their
                # lanes exact identity through every sweep the live
                # members need
                b = jnp.where(active[:, None, None], b, jnp.zeros_like(b))
        with tracing.scope("poisson_solve"):
            res = self._pressure_solve(b, exact_poisson)
        # bare latch again; under spatial placement the correction
        # kernel's strip DMA cannot be GSPMD-partitioned, so the
        # sharded pool keeps the XLA epilogue (pinned sharded==single)
        corr_tier = ("xla" if self.placement == "spatial"
                     else g._kernel_tier)
        vel, pres = project_correct(
            res.x, state.pres, vel, h, dt,
            spmd_safe=g.spmd_safe, mean_axes=(-2, -1),
            tier=corr_tier,
            remove_mean=g.bc.all_neumann, grad_signs=g._psigns,
            periodic=g._paxes)
        if active is not None:
            # freeze dead slots: state, diag and clock all read the
            # UNSTEPPED values (bit-exact slot preservation under
            # arbitrary co-member churn)
            with tracing.scope("project_correct"):
                vel = jnp.where(active[:, None, None, None], vel,
                                state.vel)
                pres = jnp.where(active[:, None, None], pres, state.pres)
                div_linf = jnp.where(active, div_linf,
                                     jnp.zeros_like(div_linf))
        diag = self._diag(vel, pres, res, div_linf, exact_poisson,
                          active, dt_req)
        return state._replace(vel=vel, pres=pres), diag

    @tracing.in_scope("diag")
    def _diag(self, vel, pres, res, div_linf, exact_poisson, active,
              dt_req) -> dict:
        """Per-member diag (the one batched pull's payload)."""
        g = self.grid
        h = g.h
        umax = jnp.max(jnp.abs(vel), axis=(-3, -2, -1))
        vv = vel.astype(g.sum_dtype) if g.sum_dtype is not None else vel
        energy = 0.5 * h * h * jnp.sum(vv * vv, axis=(-3, -2, -1))
        finite = (jnp.all(jnp.isfinite(vel), axis=(-3, -2, -1))
                  & jnp.all(jnp.isfinite(pres), axis=(-2, -1)))
        diag = {
            "poisson_iters": res.iters,
            "poisson_residual": res.residual,
            "poisson_stalled": res.stalled,
            "poisson_converged": res.converged,
            "finite": finite,
            "umax": umax,
            "energy": energy,
            "div_linf": div_linf,
            # per-member preconditioner-cycle counts [B] (schema v4;
            # the ONE shared accounting convention)
            "precond_cycles": g.precond_cycles(res, exact_poisson),
            "dt_next": dt_from_umax(umax, jnp.asarray(h, g.dtype),
                                    g.cfg.nu, g.cfg.cfl),
        }
        if active is not None:
            # the per-member clock increments ride the one pull: a dead
            # slot advances by exactly 0.0 (its host clock freezes with
            # its state); the requested dt — NaN lanes included — never
            # reaches the times accumulator
            diag["dt"] = jnp.where(active, dt_req,
                                   jnp.zeros_like(dt_req))
        return diag

    # -- driver contract (StepGuard-compatible) -----------------------
    def step_once(self, dt=None):
        """One fused fleet step. ``dt``: None (chained per-member
        device dt), a scalar (all members), or a [B] vector. One
        batched diag pull per step for the WHOLE fleet — or none under
        ``async_diag`` (the guard's lagged verdict pulls it)."""
        g = self.grid
        if dt is None:
            dt = (self._next_dt if self._next_dt is not None
                  else self._dt(self.state.vel))
        dt_dev = jnp.asarray(dt, g.dtype)
        if dt_dev.ndim == 0:
            dt_dev = jnp.full((self.members,), dt_dev, g.dtype)
        exact = g.exact_request(self.step_count < 10, self._force_exact)
        self.state, diag = self._step(self.state, dt_dev, self._active,
                                      exact_poisson=exact)
        diag = dict(diag)
        if "dt" not in diag:
            # unmasked path: every slot advances by the dispatched
            # dt (the masked trace returns its own zeroed-dead-lane
            # vector from inside the jit)
            diag["dt"] = dt_dev   # rides the one pull
        self._next_dt = diag["dt_next"]
        if self.async_diag:
            self.step_count += 1
            return diag
        diag = jax.device_get(diag)
        self.times = self.times + np.asarray(diag["dt"], np.float64)
        self.time = self._fleet_time()
        self.step_count += 1
        return diag

    def _fleet_time(self) -> float:
        """The loop-condition clock: min over LIVE slots — a retired
        slot's frozen clock must not pin the fleet time at its
        retirement point (empty pool: min over all, i.e. unchanged)."""
        act = self.active_mask
        if act.all() or not act.any():
            return float(self.times.min())
        return float(self.times[act].min())

    def set_active(self, mask) -> None:
        """Install the per-slot active mask (FleetServer lifecycle).
        From the first call on, the fused step runs the masked trace
        permanently — including at full occupancy, where the all-True
        selects are bit-identity — so slot churn re-uses ONE compiled
        executable."""
        m = np.asarray(mask, dtype=bool)
        if m.shape != (self.members,):
            raise ValueError(
                f"active mask shape {m.shape} != ({self.members},)")
        if self._active is not None \
                and np.array_equal(m, self.active_mask):
            # the device mirror already holds this pattern — in steady
            # full-pool churn a retire at one cycle's end and the
            # refill at the next cycle's start cancel out, so the mask
            # usually never changes value and the h2d push is skipped
            return
        self.active_mask = m.copy()
        self._active = jnp.asarray(self.active_mask)

    # -- per-member access (guard rewind + server admit/retire) -------
    # The slot index is passed as a DEVICE int32 operand, not a Python
    # int: a baked int index would compile one gather/scatter
    # executable per distinct slot, and the serving loop's admit/evict
    # churn touches arbitrary slots — with the index as an operand, ONE
    # executable covers the whole pool (the zero-recompile contract).
    def member_state(self, m: int) -> FlowState:
        """Member ``m``'s slice as a solo FlowState (fresh arrays)."""
        return self._extract_member(self.state, self._idx[m])

    def set_member_state(self, m: int, st: FlowState) -> None:
        """Install a solo FlowState into member ``m``'s slice; every
        other member's values pass through bit-unchanged (one donated
        fused scatter — the old state buffers are reused in place)."""
        self.state = self._install_member(self.state, self._idx[m], st)

    def set_member_next_dt(self, m: int, dt_next) -> None:
        if self._next_dt is None:
            # materialize the cache so a pre-first-step admission's dt
            # lands in it: the other lanes get exactly the dt step_once
            # would have computed from the current velocities
            self._next_dt = self._dt(self.state.vel)
        self._next_dt = self._scatter_next_dt(
            jnp.asarray(self._next_dt), self._idx[m],
            jnp.asarray(dt_next, self.grid.dtype))

    def admit_member(self, m: int, st: FlowState,
                     next_dt=None) -> None:
        """The serving hot path: install ``st`` into slot ``m`` AND
        scatter its chained dt in ONE donated dispatch.
        ``next_dt=None`` computes the fresh CFL dt from the admitted
        velocity inside the same executable (bit-identical to
        ``grid.compute_dt`` on the solo slice)."""
        if self._next_dt is None:
            self._next_dt = self._dt(self.state.vel)
        dtv = (self._dt_sentinel if next_dt is None
               else jnp.asarray(next_dt, self.grid.dtype))
        self.state, self._next_dt = self._admit_impl(
            self.state, jnp.asarray(self._next_dt), self._idx[m],
            st, dtv)

    def member_step_once(self, m: int, dt=None, exact: bool = False):
        """Advance ONLY member ``m`` one step through the solo
        single-member executable (the guard's replay/retry path —
        recovery is the cold path; the fused dispatch is the hot one).
        Leaves the shared step counter, the fleet dt cache and the
        clocks untouched: the caller (FleetStepGuard) owns those.
        Returns the solo diag dict (device scalars)."""
        st = self.member_state(m)
        if dt is None:
            dt = float(self._member_dt(st.vel))
        st, diag = self._member_step(
            st, jnp.asarray(dt, self.grid.dtype),
            exact_poisson=exact,
            obstacle_terms=bool(self.shaped))
        self.set_member_state(m, st)
        diag = dict(diag)
        diag["dt"] = float(dt)
        return diag

    def seed_taylor_green(self, amp0: float = 1.0,
                          decay: float = 0.8) -> None:
        """Seed the amplitude-laddered Taylor-Green ensemble (the CLI
        fleet mode's t=0 state: obstacle-free zero state would make a
        trivial run; the ladder gives every member its own umax/dt)."""
        st = taylor_green_fleet(self.grid, self.members, amp0, decay)
        if self.mesh is not None:
            st = FlowState(*(jax.device_put(np.asarray(a), b.sharding)
                             for a, b in zip(st, self.state)))
        self.state = st


# ---------------------------------------------------------------------------
# continuous-batching slot-pool serving
# ---------------------------------------------------------------------------

@dataclass
class FleetRequest:
    """One client session waiting for a fleet slot.

    Exactly one of ``state`` / ``checkpoint`` provides the admission
    state: ``state`` is a solo :class:`FlowState` at clock ``t0``;
    ``checkpoint`` is a per-member session directory written by
    ``io.save_member_checkpoint`` — admission from it resumes the
    session bit-exact (state, clock and the chained per-member dt all
    round-trip losslessly). The member is retired once its clock
    reaches ``t_end``; ``next_dt`` (optional) overrides the first
    step's dt (otherwise the checkpoint's chained dt, else a fresh CFL
    dt from the admitted velocity).

    ``bc`` (optional) declares the session's expected per-face
    :class:`~cup2d_tpu.bc.BCTable`: the pool's slot executables bake
    ONE table's edge treatment in, so admission refuses a mismatch
    loudly instead of stepping the session under the wrong ghosts.
    None means "whatever the pool runs" (back-compat)."""
    client_id: str
    state: Optional[FlowState] = None
    checkpoint: Optional[str] = None
    t0: float = 0.0
    t_end: float = float("inf")
    next_dt: Optional[float] = None
    bc: Optional[object] = None


class FleetServer:
    """Continuous-batching serving loop over a ``FleetSim`` slot pool.

    The inference-stack pattern on a flow fleet: a FIXED-B padded pool
    whose step executable never changes shape, with a per-slot active
    mask (``FleetSim.set_active``). Finished members retire (their
    session checkpoint lands in ``session_dir``), aborted members are
    EVICTED by the guard's per-member ladder (``on_member_abort`` —
    the slot is freed instead of the fleet dying), and free slots
    refill from the request queue — all without recompiling: the mask
    is a device operand, slot installs/slices run through
    device-int32-indexed executables, and dead lanes are select-frozen
    inside the fused step. A live member's trajectory is bit-identical
    regardless of co-member churn (its lane's arithmetic is
    elementwise-independent and dead/alive co-lanes only change values
    OTHER lanes never read).

    Lifecycle events (``member_admit`` / ``member_retire`` /
    ``member_evict``) go to ``event_log``; the serving gauges ride the
    schema-v7 metrics record (``telemetry_fields``); per-client JSONL
    streams split out of ``member_health`` when ``clients_dir`` is set
    (profiling.ClientStreams — the MetricsRecorder writes them).
    """

    def __init__(self, sim: FleetSim, *, guard=None,
                 session_dir: Optional[str] = None,
                 event_log=None, clients_dir: Optional[str] = None,
                 clients_rotate_mb=None, latency=None):
        self.sim = sim
        self.guard = guard
        if guard is not None:
            # wire the eviction rung: an exhausted per-member ladder
            # frees the slot (member_aborted event) instead of raising
            guard.on_member_abort = self._on_member_abort
        self.session_dir = session_dir
        self.event_log = event_log
        # tracing.ServingLatency (or None): queue-wait / admit-to-
        # first-step / per-step histograms, host clocks only
        self.latency = latency
        self.queue: deque = deque()
        self.active = np.zeros(sim.members, dtype=bool)
        self.t_end = np.full(sim.members, np.inf)
        self.client: list = [None] * sim.members
        self.admitted = 0
        self.retired = 0
        self.evicted = 0
        self.step_clients: list = [None] * sim.members
        self.clients = None
        if clients_dir is not None:
            from .profiling import ClientStreams
            self.clients = ClientStreams(clients_dir,
                                         rotate_mb=clients_rotate_mb)
        # one cached zero template: EVICTION re-zeroes the slot through
        # the same one-executable scatter admission uses (an aborted
        # member's NaN state must not leak into the masked step's
        # member_health diag rows). Plain retirement skips the zero —
        # the parked contents are the retiree's final state, finite,
        # mask-frozen, and fully overwritten by the next admit — so a
        # retire costs ZERO dispatches.
        self._zero = sim.grid.zero_state()
        # device-mask sync is coalesced: slot changes mark the mask
        # dirty and step() pushes it ONCE per cycle before dispatch
        self._mask_dirty = False
        sim.set_active(self.active)

    # -- client API ---------------------------------------------------
    def submit(self, req: FleetRequest) -> None:
        """Enqueue a session; it is admitted at the next free slot."""
        if self.latency is not None:
            self.latency.on_submit(req.client_id)
        self.queue.append(req)

    def client_of(self, m: int):
        """The client id occupying slot ``m`` (None when free)."""
        return self.client[m]

    @property
    def occupancy(self) -> float:
        return float(self.active.sum()) / self.sim.members

    def telemetry_fields(self) -> dict:
        """The schema-v7 serving gauges (host-side, no device work)."""
        return {
            "active_members": int(self.active.sum()),
            "occupancy": round(self.occupancy, 6),
            "admitted": int(self.admitted),
            "evicted": int(self.evicted),
            "queue_depth": len(self.queue),
        }

    def close(self) -> None:
        if self.clients is not None:
            self.clients.close()

    # -- slot lifecycle -----------------------------------------------
    def _emit(self, **fields) -> None:
        if self.event_log is not None:
            self.event_log.emit(**fields)

    def _fill_slots(self) -> int:
        n = 0
        for m in range(self.sim.members):
            if not self.queue:
                break
            if not self.active[m]:
                self._admit(m, self.queue.popleft())
                n += 1
        return n

    def _admit(self, slot: int, req: FleetRequest) -> None:
        with tracing.span("admit", member=slot,
                          client=str(req.client_id)):
            self._admit_inner(slot, req)
        if self.latency is not None:
            self.latency.on_admit(req.client_id)

    def _admit_inner(self, slot: int, req: FleetRequest) -> None:
        sim = self.sim
        if req.bc is not None and req.bc != sim.grid.bc:
            # slot-pool executables are BC-table-specific (the edge
            # treatment is baked into the fused step): stepping this
            # session would silently run it under the wrong ghosts
            raise ValueError(
                f"request {req.client_id!r}: session BCTable "
                f"({req.bc.token}) does not match the pool's "
                f"({sim.grid.bc.token}); submit it to a pool built "
                "with that table")
        meta: dict = {}
        if req.checkpoint is not None:
            from .io import load_member_checkpoint
            st, meta = load_member_checkpoint(req.checkpoint, sim.grid)
        else:
            st = req.state
        if st is None:
            raise ValueError(
                f"request {req.client_id!r}: neither state nor "
                "checkpoint provided")
        t0 = float(meta.get("time", req.t0))
        sim.times[slot] = t0
        nd = req.next_dt if req.next_dt is not None \
            else meta.get("next_dt")
        sim.admit_member(slot, st, nd)
        self.active[slot] = True
        self._mask_dirty = True
        self.client[slot] = req.client_id
        self.t_end[slot] = float(req.t_end)
        self.admitted += 1
        if self.guard is not None:
            # the slot's watchdog history belongs to the RETIRED
            # occupant — a fresh session starts with a fresh clone
            self.guard.reset_member_watchdog(slot)
        self._emit(event="member_admit", member=slot,
                   client=req.client_id, t0=t0, t_end=float(req.t_end))

    def _free_slot(self, slot: int, zero: bool = False) -> None:
        if zero:   # eviction only — see the _zero comment in __init__
            self.sim.set_member_state(slot, self._zero)
        self.active[slot] = False
        self._mask_dirty = True
        self.client[slot] = None
        self.t_end[slot] = np.inf

    def _retire(self, slot: int) -> None:
        cid = self.client[slot]
        with tracing.span("retire", member=slot, client=str(cid)):
            ckpt = None
            if self.session_dir is not None:
                from .io import save_member_checkpoint
                ckpt = os.path.join(self.session_dir, str(cid))
                save_member_checkpoint(ckpt, self.sim, slot)
            t_done = float(self.sim.times[slot])
            self._free_slot(slot)
            self.retired += 1
            if self.clients is not None:
                self.clients.close(cid)
            self._emit(event="member_retire", member=slot, client=cid,
                       t=t_done, checkpoint=ckpt)

    def _on_member_abort(self, m: int, reason: str, step: int) -> None:
        """The guard's eviction hook (per-member ladder exhausted):
        free the slot and count the eviction. The guard re-anchors its
        snapshot ring right after this returns, so the fresh anchor
        already holds the zeroed dead slot and the healthy members'
        live states — their trajectories and clocks pass through
        bit-unchanged."""
        cid = self.client[m]
        with tracing.span("evict", member=m, client=str(cid),
                          reason=reason):
            self._free_slot(m, zero=True)
            self.evicted += 1
            # sync NOW, not lazily: the guard is mid-step and its
            # replay of the surviving members runs against the device
            # mask
            self.sim.set_active(self.active)
            self._mask_dirty = False
            if self.clients is not None:
                self.clients.close(cid)
            self._emit(event="member_evict", member=m, client=cid,
                       reason=reason, step=step)

    # -- the serving loop ---------------------------------------------
    def step(self) -> Optional[dict]:
        """One serving cycle: refill free slots from the queue, advance
        the whole pool one fused step, retire members whose clocks
        crossed their horizon. Returns the step record (None when the
        pool is empty and the queue has nothing to admit)."""
        if self._fill_slots() and self.guard is not None:
            # fresh anchor AFTER admissions: a later rewind must
            # restore the admitted state, never pre-admit slot contents
            self.guard.reanchor()
        if not self.active.any():
            return None
        if self._mask_dirty:
            # ONE device-mask push per cycle, however many slots the
            # admissions/retirements above flipped
            self.sim.set_active(self.active)
            self._mask_dirty = False
        lat = self.latency
        t0 = time.perf_counter() if lat is not None else 0.0
        rec = (self.guard.step() if self.guard is not None
               else self.sim.step_once())
        # who occupied each slot DURING this fused step: the recorder
        # runs after step() returns, by which time a retiring member's
        # slot is already cleared — its final step's telemetry row
        # must still reach its client stream (times[] keeps the
        # retiree's final clock until the next cycle's refill)
        self.step_clients = list(self.client)
        if lat is not None:
            lat.on_step(self.step_clients, time.perf_counter() - t0)
        done = np.flatnonzero(self.active
                              & (self.sim.times >= self.t_end))
        for m in done:
            self._retire(int(m))   # mask push deferred to next cycle
        return rec

    def park_all(self) -> int:
        """Retire every live member NOW (the CLI's preemption path):
        each session's checkpoint lands in ``session_dir``, resumable
        bit-exact via admit-from-checkpoint; the queue is left to the
        caller (requests hold no device state). Returns the number of
        sessions parked."""
        live = np.flatnonzero(self.active)
        for m in live:
            self._retire(int(m))
        if live.size:
            self.sim.set_active(self.active)
        return int(live.size)

    def drain(self, *, max_steps: Optional[int] = None) -> int:
        """Serve until the queue is empty and every slot has retired
        (or ``max_steps`` serving cycles elapsed). Returns the number
        of fused steps taken."""
        n = 0
        while self.queue or self.active.any():
            if max_steps is not None and n >= max_steps:
                break
            if self.step() is None:
                break
            n += 1
        return n
