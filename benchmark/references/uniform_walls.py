"""Plain reference of one uniform box with prescribed-velocity walls.

Straightforward ``jax.numpy`` in float32, written from the published
scheme and importing nothing of the program: the reference CUP2D time
step (slitvinov/CUP2D main.cpp, the loop the program reproduces) on one
uniform level —

1. dt = min(h^2/4 / (nu + h umax/4), CFL h / (umax + 1e-8));
2. two Heun substages (c = 1/2, 1) of advection-diffusion on the OLD
   velocity: u <- u_old + c dt [-(u.grad)u + nu lap u], with the
   fifth-order WENO upwind derivative of Jiang & Shu (eps = 1e-6,
   squared smoothness weighting) and the 5-point Laplacian, over a
   field with 3 ghost layers painted zeroth-order from the wall
   velocity: ghost = 2 u_wall - edge, the y faces first, then the x
   faces over the full padded rows so that corners compose;
3. pressure projection in increment form: solve the cell-centred
   Neumann problem lap(dp) = (h / 2 dt) div(u*) - lap(p_old) with
   undivided central differences, remove the means, p = dp + p_old,
   u <- u* - dt/(2h) grad p.

Departures from the program, each on purpose: the WENO weights are the
textbook ratio form (the program normalises by an approximate
reciprocal; the weights are scale-invariant, so the two agree to
rounding); the Poisson problem is solved DIRECTLY by a type-II cosine
transform with two rounds of residual correction, where the program
iterates BiCGSTAB under multigrid — to the precision floor in its first
ten steps (the reference's tol-0 start-up), to the configuration's
tolerance afterwards.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

WENO_EPS = 1e-6


def pad_walls(v, g: int, walls):
    """[2, ny, nx] -> [2, ny+2g, nx+2g]; ``walls`` = wall velocity
    (u, v) of (x_lo, x_hi, y_lo, y_hi)."""
    def wall(face):
        return jnp.asarray(face, v.dtype)[:, None, None]

    lo = jnp.repeat(2.0 * wall(walls[2]) - v[:, :1, :], g, axis=1)
    hi = jnp.repeat(2.0 * wall(walls[3]) - v[:, -1:, :], g, axis=1)
    v = jnp.concatenate([lo, v, hi], axis=1)
    lo = jnp.repeat(2.0 * wall(walls[0]) - v[:, :, :1], g, axis=2)
    hi = jnp.repeat(2.0 * wall(walls[1]) - v[:, :, -1:], g, axis=2)
    return jnp.concatenate([lo, v, hi], axis=2)


def _weno5(a, b, c, d, e, g1, g2, g3):
    """One WENO5 reconstruction from five cell values a..e, ideal
    weights (g1, g2, g3) on the stencils (a,b,c), (b,c,d), (c,d,e)."""
    b1 = 13.0 / 12.0 * (a - 2.0 * b + c) ** 2 + 0.25 * (a - 4.0 * b + 3.0 * c) ** 2
    b2 = 13.0 / 12.0 * (b - 2.0 * c + d) ** 2 + 0.25 * (b - d) ** 2
    b3 = 13.0 / 12.0 * (c - 2.0 * d + e) ** 2 + 0.25 * (3.0 * c - 4.0 * d + e) ** 2
    w1 = g1 / (b1 + WENO_EPS) ** 2
    w2 = g2 / (b2 + WENO_EPS) ** 2
    w3 = g3 / (b3 + WENO_EPS) ** 2
    return w1, w2, w3, 1.0 / (w1 + w2 + w3)


def weno_plus(a, b, c, d, e):
    """Value at the + face of cell c, biased to the low side."""
    w1, w2, w3, inv = _weno5(a, b, c, d, e, 0.1, 0.6, 0.3)
    f1 = (2.0 * a - 7.0 * b + 11.0 * c) / 6.0
    f2 = (-b + 5.0 * c + 2.0 * d) / 6.0
    f3 = (2.0 * c + 5.0 * d - e) / 6.0
    return (w1 * f1 + w2 * f2 + w3 * f3) * inv


def weno_minus(a, b, c, d, e):
    """Value at the - face of cell c... biased to the high side (the
    mirror image of :func:`weno_plus`)."""
    w1, w2, w3, inv = _weno5(a, b, c, d, e, 0.3, 0.6, 0.1)
    f1 = (-a + 5.0 * b + 2.0 * c) / 6.0
    f2 = (2.0 * b + 5.0 * c - d) / 6.0
    f3 = (11.0 * c - 7.0 * d + 2.0 * e) / 6.0
    return (w1 * f1 + w2 * f2 + w3 * f3) * inv


def upwind_derivative(wind, m3, m2, m1, c, p1, p2, p3):
    """Undivided upwind WENO5 derivative at c."""
    plus = weno_plus(m2, m1, c, p1, p2) - weno_plus(m3, m2, m1, c, p1)
    minus = weno_minus(m1, c, p1, p2, p3) - weno_minus(m2, m1, c, p1, p2)
    return jnp.where(wind > 0, plus, minus)


def advect_diffuse(vel, vold, c, dt, h, nu, walls):
    g = 3
    lab = pad_walls(vel, g, walls)
    ny, nx = vel.shape[-2:]

    def s(dy, dx):
        return lab[:, g + dy:g + dy + ny, g + dx:g + dx + nx]

    u = s(0, 0)
    wu, wv = u[0:1], u[1:2]
    ddx = upwind_derivative(wu, s(0, -3), s(0, -2), s(0, -1), u,
                            s(0, 1), s(0, 2), s(0, 3))
    ddy = upwind_derivative(wv, s(-3, 0), s(-2, 0), s(-1, 0), u,
                            s(1, 0), s(2, 0), s(3, 0))
    lap = s(0, 1) + s(0, -1) + s(1, 0) + s(-1, 0) - 4.0 * u
    rhs = -dt * h * (wu * ddx + wv * ddy) + nu * dt * lap
    return vold + c * rhs / (h * h)


def lap_neumann(p):
    q = jnp.pad(p, 1, mode="edge")
    return (q[1:-1, 2:] + q[1:-1, :-2] + q[2:, 1:-1] + q[:-2, 1:-1]
            - 4.0 * p)


def neumann_solve(b):
    """lap_neumann(x) = b, mean-free, by the cosine transform that
    diagonalises the cell-centred Neumann Laplacian; two rounds of
    residual correction take out the transform's own rounding."""
    from jax.scipy.fft import dctn, idctn

    ny, nx = b.shape
    ky = 2.0 * jnp.cos(math.pi * jnp.arange(ny, dtype=b.dtype) / ny) - 2.0
    kx = 2.0 * jnp.cos(math.pi * jnp.arange(nx, dtype=b.dtype) / nx) - 2.0
    lam = ky[:, None] + kx[None, :]
    lam = lam.at[0, 0].set(1.0)

    def once(r):
        xh = dctn(r, type=2, norm="ortho") / lam
        return idctn(xh.at[0, 0].set(0.0), type=2, norm="ortho")

    x = once(b)
    for _ in range(2):
        x = x + once(b - lap_neumann(x))
    return x


def step(vel, pres, dt, h, nu, walls, cast=None):
    """One whole time step; returns (vel, pres, div_linf) with
    ``div_linf`` = max |div u*| of the velocity before projection.

    ``cast`` (the control): a dtype the velocity operands of each
    advection substage are rounded through, as a lower storage
    precision would; the projection stays in float32."""
    def low(a):
        return a if cast is None else a.astype(cast).astype(a.dtype)

    vold = vel
    for c in (0.5, 1.0):
        vel = advect_diffuse(low(vel), low(vold), c, dt, h, nu, walls)
    lab = pad_walls(vel, 1, walls)
    div = (lab[0, 1:-1, 2:] - lab[0, 1:-1, :-2]
           + lab[1, 2:, 1:-1] - lab[1, :-2, 1:-1])
    div_linf = jnp.max(jnp.abs(div)) / (2.0 * h)
    b = (0.5 * h / dt) * div - lap_neumann(pres)
    dp = neumann_solve(b)
    pres = (dp - jnp.mean(dp)) + (pres - jnp.mean(pres))
    q = jnp.pad(pres, 1, mode="edge")
    grad = jnp.stack([q[1:-1, 2:] - q[1:-1, :-2],
                      q[2:, 1:-1] - q[:-2, 1:-1]])
    return vel - (0.5 * dt / h) * grad, pres, div_linf


def timestep(umax, h, nu, cfl):
    return jnp.minimum(0.25 * h * h / (nu + 0.25 * h * umax),
                       cfl * h / (umax + 1e-8))


def follow(vel0, n_steps: int, *, h: float, nu: float, cfl: float,
           walls, cast=None):
    """Follow the first ``n_steps`` steps from ``vel0`` at rest
    pressure; returns one row per step of the scalars the program's
    telemetry records after that step: t, dt, umax, energy, div_linf.

    ``cast``: see :func:`step` (the lower-precision control)."""
    h32 = jnp.float32(h)

    def one(vel, pres, dt):
        vel, pres, dl = step(vel, pres, dt, h32, nu, walls, cast)
        umax = jnp.max(jnp.abs(vel))
        energy = 0.5 * h32 * h32 * jnp.sum(vel * vel)
        return vel, pres, (umax, energy, dl, timestep(umax, h32, nu, cfl))

    one = jax.jit(one, donate_argnums=(0, 1))
    vel = jnp.asarray(vel0, jnp.float32)
    pres = jnp.zeros(vel.shape[-2:], jnp.float32)
    dt = float(timestep(jnp.max(jnp.abs(vel)), h32, nu, cfl))
    t, rows = 0.0, []
    for _ in range(n_steps):
        vel, pres, out = one(vel, pres, jnp.float32(dt))
        umax, energy, dl, dt_next = (float(a) for a in jax.device_get(out))
        t += dt
        rows.append({"t": t, "dt": dt, "umax": umax, "energy": energy,
                     "div_linf": dl})
        dt = dt_next
    return rows


COMPARED = ("umax_gap", "energy_gap", "div_gap", "t_gap")


def gaps(theirs: list, ours: list) -> dict:
    """The numbers compared: the widest relative gap, over the steps
    followed, of each scalar the program's telemetry records after a
    step, and the gap of the clock after the last one. ``by_step``
    keeps every step's gap for the earlier ``readings`` line."""
    def rel(key):
        return [abs(a[key] - b[key]) / abs(b[key])
                for a, b in zip(theirs, ours)]

    by_step = {"umax_gap": rel("umax"), "energy_gap": rel("energy"),
               "div_gap": rel("div_linf"), "t_gap": rel("t")}
    got = {k: max(v) for k, v in by_step.items()}
    got["t_gap"] = by_step["t_gap"][-1]
    return {**got, "by_step": by_step}


def compare(config, cell, seed, records, grid, cast=None) -> dict:
    """Follow the run's first ``reference_steps`` steps from the seed
    — through the warm-up into the window's first steps, so that most
    of them come from the production executable the window drives —
    and hold the program's telemetry of those same steps, made by the
    same driver object through the same entry, against them.
    {name: {"value", "limit"}} for every number the cell gives a limit
    for; a step the program left no record of reads as None (never
    correct)."""
    import json

    from benchmark import seeded   # the benchmark's own input generator

    g, ph = config["grid"], config["physics"]
    n = int(cell["reference_steps"])
    h = float(g["extent"]) / max(int(g["ny"]), int(g["nx"]))
    mine = {"ny": int(g["ny"]), "nx": int(g["nx"]), "h": h,
            "nu": float(ph["nu"]), "cfl": float(ph["cfl"])}
    for k, v in mine.items():
        if k in grid and abs(grid[k] - v) > 1e-12 * abs(v):
            raise SystemExit(f"benchmark: the configuration file says "
                             f"{k}={v}, the program ran {grid[k]}")
    by_step = {r["step"]: r for r in records}
    theirs = [by_step.get(k) for k in range(1, n + 1)]
    limits = cell["limits"]
    if any(r is None for r in theirs):
        return {k: {"value": None, "limit": limits[k]} for k in limits}
    ours = follow(seeded.start_velocity(config, seed), n, h=h,
                  nu=mine["nu"], cfl=mine["cfl"],
                  walls=[tuple(w) for w in config["walls"]], cast=cast)
    got = gaps(theirs, ours)
    print(json.dumps({"phase": "readings", **got}), flush=True)
    return {k: {"value": got[k], "limit": limits[k]} for k in limits}
