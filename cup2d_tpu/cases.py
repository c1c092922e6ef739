"""Validation-case catalog (ISSUE 12): named, runnable, serveable
workloads built on the per-face BC engine (bc.py).

Each case bundles the THREE things that define a workload — a
SimConfig, a BCTable and the initial/obstacle state — behind one name,
so the same case runs identically from the CLI (``-case cavity``), the
validation probes (validation/cavity.py, validation/channel.py), tests
and the fleet/serving layer. The registry is plain data + builder
functions: adding a case is one ``CaseSpec`` entry, no solver changes.

Catalog:

``cavity``
    Lid-driven cavity, THE canonical incompressible benchmark the
    free-slip-only box could never express: unit box, four no-slip
    walls, the y_hi lid translating at ``lid_u``. Obstacle-free
    (UniformSim family — also fleet-servable: the table is all-Neumann
    so the slot-pool solvers keep their mean-free contract). Validated
    against the Ghia et al. (1982) Re=100 centerline profiles
    (validation/cavity.py).

``channel``
    Channel flow past a FIXED cylinder: Dirichlet inflow at x_lo,
    convective outflow at x_hi, free-slip side walls, a prescribed-
    (0,0) disk in the stream, the whole domain impulsively started at
    the inflow velocity. The true inflow-outflow configuration the
    towed-cylinder case only approximates Galilean-ly. Validated by
    shedding Strouhal number vs the Williamson (1989) Re=200 band
    (validation/channel.py).

``cylinder``
    The legacy towed-cylinder drag/Strouhal case (free-slip box,
    prescribed (-U, 0) disk) folded into the registry so
    validation/cylinder.py runs through the same ``-case`` path it
    validates.

``tgv_periodic``
    Doubly-periodic Taylor-Green vortex (ISSUE 20): u = U sin(kx)
    cos(ky), v = -U cos(kx) sin(ky), k = 2pi/L on the unit box. The
    ONE periodic case with a closed-form answer — kinetic energy
    decays as exp(-4 nu k^2 t) — so it anchors both the wrap-ghost
    paint and the fftd direct solve against analysis, not another
    solver. Obstacle-free and fleet-servable (the sampled IC is
    discretely divergence-free under the centered stencils, and the
    all-periodic table keeps the mean-free pressure contract).

``shear_layer``
    Doubly-periodic double shear layer (Bell-Colella-Glaz): two tanh
    layers at y = 1/4 and 3/4 with a delta*sin(2pi x) vertical
    perturbation that rolls them up into the classic vortex pairs.
    The standard stress test for periodic advection + projection.

``turb2d``
    Seeded decaying 2D turbulence: random-phase vorticity spectrum
    E(k) ~ k / (1 + (k/k0)^4), phases drawn host-side, velocity
    synthesized from the streamfunction by CENTERED differences (discretely
    divergence-free by construction — Dx Dy psi == Dy Dx psi).
    Deterministic per seed; fleet members get seed + slot so a
    member-batched fleet serves an ensemble.

No environment reads here — cases parameterize through arguments only
(tests/test_env_latch.py walks this package)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

from .bc import (BCTable, FREE_SLIP, convective_outflow,
                 dirichlet_inflow, free_slip, no_slip, periodic)
from .config import SimConfig


@dataclass(frozen=True)
class CaseSpec:
    """One catalog entry: ``build(**kw)`` returns a ready-to-step
    driver with ``sim.case`` set; ``default_level`` is the validation
    resolution (CLI ``-level`` overrides); ``fleet_ok`` marks cases
    whose obstacle-free state can ride the fleet slot pool;
    ``initial_vel(grid, m) -> [2, Ny, Nx]`` (array) is member ``m``'s
    starting velocity at the case's default parameters (None: fluid at
    rest) — what ``build`` installs and ``initial_states`` serves."""

    name: str
    describe: str
    build: Callable
    default_level: int
    fleet_ok: bool = False
    initial_vel: Optional[Callable] = None


def cavity_table(lid_u: float = 1.0) -> BCTable:
    """Four no-slip walls, the y_hi lid moving at (+lid_u, 0)."""
    return BCTable(no_slip(), no_slip(), no_slip(), no_slip(lid_u, 0.0))


def channel_table(u_in: float, profile: str = "uniform") -> BCTable:
    """Dirichlet inflow at x_lo, convective outflow at x_hi, free-slip
    side walls."""
    return BCTable(dirichlet_inflow(u_in, profile=profile),
                   convective_outflow(), free_slip(), free_slip())


def periodic_table() -> BCTable:
    """Doubly-periodic box (all four faces wrap)."""
    return BCTable(periodic(), periodic(), periodic(), periodic())


def periodic_channel_table() -> BCTable:
    """Periodic in x, no-slip walls in y — the mixed table the
    fftd+tridiag solve exercises."""
    return BCTable(periodic(), periodic(), no_slip(), no_slip())


def _periodic_sim(cfg: SimConfig, lvl: int, mesh, members: int):
    """Shared driver dispatch for the obstacle-free periodic cases
    (the build_cavity pattern: fleet > sharded > solo)."""
    bc = periodic_table()
    if members > 0:
        from .fleet import FleetSim
        return FleetSim(cfg, level=lvl, members=members, mesh=mesh,
                        bc=bc)
    if mesh is not None:
        from .parallel.mesh import ShardedUniformSim
        return ShardedUniformSim(cfg, mesh, level=lvl, bc=bc)
    from .uniform import UniformSim
    return UniformSim(cfg, level=lvl, bc=bc)


def _install_vel(sim, members: int, vel_fn):
    """Overwrite the zero-state velocity with ``vel_fn(grid, m) ->
    [2, Ny, Nx]`` (numpy or device array), stacked over fleet slots."""
    import jax.numpy as jnp

    g = sim.grid
    if members > 0:
        v = jnp.stack([jnp.asarray(vel_fn(g, m)) for m in range(members)])
    else:
        v = vel_fn(g, 0)
    sim.state = sim.state._replace(
        vel=jnp.asarray(v, dtype=g.dtype))


def build_tgv_periodic(level: Optional[int] = None, nu: float = 1e-3,
                       u0: float = 1.0, dtype: str = "float32",
                       mesh=None, members: int = 0, cfl: float = 0.4):
    """Doubly-periodic Taylor-Green vortex on the unit box:
    u = u0 sin(kx) cos(ky), v = -u0 cos(kx) sin(ky), k = 2pi.

    The nonlinear term of this field is a pure gradient (absorbed by
    the pressure), so the exact solution is self-similar decay —
    KE(t) = KE(0) * exp(-4 nu k^2 t) — and the discrete IC sampled at
    cell centers is divergence-free under the centered divergence
    (the du/dx and dv/dy terms cancel mode-wise). Validation anchor
    for the periodic BC + fftd stack (tests/test_cases.py)."""
    lvl = 4 if level is None else level
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, dtype=dtype, nu=nu, cfl=cfl,
                    poisson_tol=1e-4, poisson_tol_rel=1e-3)
    sim = _periodic_sim(cfg, lvl, mesh, members)
    _install_vel(sim, members, lambda g, m: tgv_periodic_vel(g, m, u0))
    sim.case = "tgv_periodic"
    return sim


def tgv_periodic_vel(grid, m: int = 0, u0: float = 1.0):
    """Every member the same vortex (the analytic anchor)."""
    import numpy as np
    x, y = grid.cell_centers()
    k = 2.0 * np.pi / grid.cfg.extent
    return np.stack([u0 * np.sin(k * x) * np.cos(k * y),
                     -u0 * np.cos(k * x) * np.sin(k * y)])


def build_shear_layer(level: Optional[int] = None, nu: float = 2e-4,
                      rho: float = 30.0, delta: float = 0.05,
                      u0: float = 1.0, dtype: str = "float32",
                      mesh=None, members: int = 0, cfl: float = 0.4):
    """Doubly-periodic double shear layer (Bell-Colella-Glaz 1989):
    two tanh layers of width ~1/rho at y = 1/4 and y = 3/4, kicked by
    a delta*sin(2pi x) vertical velocity that rolls each layer up
    into the classic vortex pair."""
    lvl = 4 if level is None else level
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, dtype=dtype, nu=nu, cfl=cfl,
                    poisson_tol=1e-4, poisson_tol_rel=1e-3)
    sim = _periodic_sim(cfg, lvl, mesh, members)
    _install_vel(sim, members,
                 lambda g, m: shear_layer_vel(g, m, rho, delta, u0))
    sim.case = "shear_layer"
    return sim


def shear_layer_vel(grid, m: int = 0, rho: float = 30.0,
                    delta: float = 0.05, u0: float = 1.0):
    """Every member the same pair of layers."""
    import numpy as np
    x, y = grid.cell_centers()
    L = grid.cfg.extent
    u = u0 * np.where(y <= 0.5 * L,
                      np.tanh(rho * (y / L - 0.25)),
                      np.tanh(rho * (0.75 - y / L)))
    return np.stack([u, delta * u0 * np.sin(2.0 * np.pi * x / L)])


def build_turb2d(level: Optional[int] = None, nu: float = 1e-4,
                 seed: int = 0, k0: float = 6.0, urms: float = 1.0,
                 dtype: str = "float32", mesh=None, members: int = 0,
                 cfl: float = 0.4):
    """Seeded decaying 2D turbulence on the doubly-periodic unit box.

    The phases are host-side numpy (deterministic per seed, no
    device RNG): a random-phase streamfunction with energy spectrum
    E(k) ~ k / (1 + (k/k0)^4), inverse-FFT'd to the grid and
    differenced CENTRALLY to velocity (u = D_y psi, v = -D_x psi) so
    the discrete centered divergence vanishes identically, and scaled
    to rms speed ``urms``. Fleet members draw seed + slot index — one
    member-batched fleet is a turbulence ensemble."""
    lvl = 4 if level is None else level
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, dtype=dtype, nu=nu, cfl=cfl,
                    poisson_tol=1e-4, poisson_tol_rel=1e-3)
    sim = _periodic_sim(cfg, lvl, mesh, members)
    _install_vel(sim, members,
                 lambda g, m: turb2d_vel(g, m, seed, k0, urms))
    sim.case = "turb2d"
    return sim


def turb2d_vel(grid, m: int = 0, seed: int = 0, k0: float = 6.0,
               urms: float = 1.0):
    """Member ``m`` draws seed + m, so members (and served sessions)
    are different flows. The phases are drawn on the host (numpy, one
    stream per seed, as ever); amplitudes, differences and the two
    inverse transforms run where the grid lives, in the grid's
    precision — the host's complex128 ``ifft2`` and its temporaries
    were 33 s of a run's set-up at 8192^2 (ISSUE 34)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed + m)
    theta = jnp.asarray(2.0 * np.pi * rng.random((grid.ny, grid.nx)),
                        grid.dtype)
    return jax.jit(_turb2d_synth)(theta, grid.h, k0, urms)


def _turb2d_synth(theta, h, k0, urms):
    """[2, Ny, Nx] from the phases: psi-hat = amp * exp(i theta), u =
    D_y Re(psi), v = -D_x Re(psi) with D the centred difference on the
    wrap (discretely div-free: Dx Dy psi == Dy Dx psi), scaled to rms
    speed ``urms``. The differences are taken mode by mode — D turns
    mode k into i sin(2 pi k / n) / h times itself — because
    differencing psi on the grid costs 1/h of its digits, which f32
    does not have at 8192^2."""
    import jax.numpy as jnp
    ny, nx = theta.shape
    ky = jnp.fft.fftfreq(ny, d=1.0 / ny).astype(theta.dtype)[:, None]
    kx = jnp.fft.fftfreq(nx, d=1.0 / nx).astype(theta.dtype)[None, :]
    k2 = kx * kx + ky * ky
    # E(k) ~ k/(1+(k/k0)^4); psi-hat amplitude ~ sqrt(E(k)/k)/k
    # (vorticity = k^2 psi-hat) = 1/(k sqrt(1+(k/k0)^4)), 0 at k = 0
    amp = jnp.where(k2 > 0, 1.0 / jnp.sqrt(
        jnp.where(k2 > 0, k2, 1.0) * (1.0 + (k2 / (k0 * k0)) ** 2)), 0.0)
    psi_hat = amp * jnp.exp(1j * theta)
    u = jnp.fft.ifft2(
        1j * jnp.sin(2.0 * jnp.pi * ky / ny) / h * psi_hat).real
    v = -jnp.fft.ifft2(
        1j * jnp.sin(2.0 * jnp.pi * kx / nx) / h * psi_hat).real
    rms = jnp.sqrt(jnp.mean(u * u + v * v))
    return jnp.stack([u, v]) * jnp.where(rms > 0, urms / rms, 1.0)


def build_cavity(level: Optional[int] = None, re: float = 100.0,
                 lid_u: float = 1.0, dtype: str = "float32",
                 mesh=None, members: int = 0, cfl: float = 0.4):
    """Lid-driven cavity at Reynolds number ``re`` = lid_u * L / nu on
    the unit box. Obstacle-free: UniformSim, or ShardedUniformSim over
    ``mesh``, or a ``members``-slot FleetSim (every member the same
    table — the pool contract)."""
    lvl = 4 if level is None else level
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, dtype=dtype, nu=lid_u / re, cfl=cfl,
                    poisson_tol=1e-4, poisson_tol_rel=1e-3)
    bc = cavity_table(lid_u)
    if members > 0:
        from .fleet import FleetSim
        sim = FleetSim(cfg, level=lvl, members=members, mesh=mesh, bc=bc)
    elif mesh is not None:
        from .parallel.mesh import ShardedUniformSim
        sim = ShardedUniformSim(cfg, mesh, level=lvl, bc=bc)
    else:
        from .uniform import UniformSim
        sim = UniformSim(cfg, level=lvl, bc=bc)
    sim.case = "cavity"
    return sim


def build_channel(level: Optional[int] = None, re: float = 200.0,
                  u_in: float = 0.2, diameter: float = 0.1,
                  dtype: str = "float32", profile: str = "uniform",
                  xpos: float = 1.0):
    """Channel past a fixed cylinder: 4x1 domain, impulsive start at
    the inflow velocity, Re = u_in * diameter / nu. Returns a
    Simulation (the obstacle path) — run ``sim.initialize()`` before
    stepping, like any shaped case."""
    import jax.numpy as jnp

    from .models import DiskShape
    from .sim import Simulation

    lvl = 5 if level is None else level
    cfg = SimConfig(bpdx=4, bpdy=1, level_max=1, level_start=0,
                    extent=4.0, dtype=dtype, nu=u_in * diameter / re,
                    lam=1e6, cfl=0.5, max_poisson_iterations=200,
                    poisson_tol=1e-3, poisson_tol_rel=1e-2)
    bc = channel_table(u_in, profile)
    sim = Simulation(
        cfg, shapes=[DiskShape(diameter / 2, xpos, 0.5,
                               prescribed=(0.0, 0.0))],
        level=lvl, bc=bc)
    # impulsive start: the stream fills the domain at t=0 (the standard
    # setup for the literature Strouhal band)
    sim.state = sim.state._replace(
        vel=sim.state.vel.at[0].set(jnp.asarray(u_in, sim.grid.dtype)))
    sim.case = "channel"
    return sim


def build_cylinder(level: Optional[int] = None, D: float = 0.1,
                   U: float = 0.2, nu: float = 5e-4, xpos: float = 3.2,
                   bpdy: int = 1, dtype: str = "float32"):
    """Legacy towed-cylinder case (validation/cylinder.py's _build):
    free-slip box, prescribed (-U, 0) disk towed through still fluid —
    the Galilean twin of ``channel`` in the closed box."""
    from .models import DiskShape
    from .sim import Simulation

    lvl = 5 if level is None else level
    cfg = SimConfig(bpdx=4, bpdy=bpdy, level_max=1, level_start=0,
                    extent=4.0, dtype=dtype, nu=nu, lam=1e6, cfl=0.5,
                    max_poisson_iterations=200, poisson_tol=1e-3,
                    poisson_tol_rel=1e-2)
    sim = Simulation(
        cfg, shapes=[DiskShape(D / 2, xpos, 0.5 * bpdy,
                               prescribed=(-U, 0.0))],
        level=lvl, bc=FREE_SLIP)
    sim.case = "cylinder"
    return sim


CASES: Tuple[CaseSpec, ...] = (
    CaseSpec("cavity",
             "lid-driven cavity (4x no-slip, moving lid), Re=100",
             build_cavity, default_level=4, fleet_ok=True),
    CaseSpec("channel",
             "channel past a fixed cylinder (inflow/outflow), Re=200",
             build_channel, default_level=5),
    CaseSpec("cylinder",
             "towed cylinder in the free-slip box (legacy validation)",
             build_cylinder, default_level=5),
    CaseSpec("tgv_periodic",
             "doubly-periodic Taylor-Green vortex (analytic KE decay)",
             build_tgv_periodic, default_level=4, fleet_ok=True,
             initial_vel=tgv_periodic_vel),
    CaseSpec("shear_layer",
             "doubly-periodic double shear layer roll-up (BCG 1989)",
             build_shear_layer, default_level=4, fleet_ok=True,
             initial_vel=shear_layer_vel),
    CaseSpec("turb2d",
             "seeded decaying 2D turbulence, doubly-periodic",
             build_turb2d, default_level=4, fleet_ok=True,
             initial_vel=turb2d_vel),
)

REGISTRY = {c.name: c for c in CASES}


def case_names() -> Tuple[str, ...]:
    return tuple(c.name for c in CASES)


def make_sim(name: str, **kw):
    """Build a named case's driver. Unknown names fail loudly with the
    catalog listing (the CLI's ``-case`` error message)."""
    spec = REGISTRY.get(name)
    if spec is None:
        listing = ", ".join(
            f"{c.name} ({c.describe})" for c in CASES)
        raise ValueError(
            f"unknown case {name!r}; catalog: {listing}")
    return spec.build(**kw)


def initial_states(name: str, grid, n: int):
    """The first ``n`` member states of a fleet-capable case on
    ``grid``, stacked [n, ...] — what ``build(members=n)`` installs,
    without building a second driver (the fleet server admits sessions
    from these)."""
    import jax.numpy as jnp

    from .fleet import stack_states
    vel_fn = REGISTRY[name].initial_vel
    states = []
    for m in range(n):
        st = grid.zero_state()
        if vel_fn is not None:
            st = st._replace(vel=jnp.asarray(vel_fn(grid, m), grid.dtype))
        states.append(st)
    return stack_states(states)
