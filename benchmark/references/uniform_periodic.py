"""Plain reference of one doubly-periodic uniform box.

Straightforward ``jax.numpy`` in float32, written from the published
scheme and importing nothing of the program: the same reference CUP2D
time step as ``uniform_walls.py`` (slitvinov/CUP2D main.cpp, the loop
the program reproduces) on one uniform level whose four faces wrap —

1. dt = min(h^2/4 / (nu + h umax/4), CFL h / (umax + 1e-8));
2. two Heun substages (c = 1/2, 1) of advection-diffusion on the OLD
   velocity: u <- u_old + c dt [-(u.grad)u + nu lap u], with the
   fifth-order WENO upwind derivative of Jiang & Shu and the 5-point
   Laplacian, over a field with 3 WRAP ghost layers (the far side's
   cells, both axes, corners included);
3. pressure projection in increment form: solve the singular periodic
   problem lap(dp) = (h / 2 dt) div(u*) - lap(p_old) with undivided
   central differences on one wrap ghost layer, remove the means,
   p = dp + p_old, u <- u* - dt/(2h) grad p.

The pieces that know no boundary (the WENO reconstruction, the dt rule,
the four gaps compared) are ``uniform_walls.py``'s own, imported and not
copied. Departures from the program, each on purpose: the WENO weights
are the textbook ratio form (the program normalises by an approximate
reciprocal); the Poisson problem is solved DIRECTLY by a real 2D
Fourier transform — divide by lam_y(m) + lam_x(k), lam(j) =
2 cos(2 pi j / n) - 2, the (0, 0) mode set to zero — with two rounds of
residual correction, where the program by default iterates BiCGSTAB
under multigrid (to the precision floor in its first ten steps, to the
configuration's tolerance afterwards) and under ``CUP2D_POIS=fftd``
takes ONE transform pair with no correction; the right-hand side is
not made mean-free first (on a wrap its mean is a sum of differences
that cancel to rounding, and the zeroed mode drops it).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.references.uniform_walls import (COMPARED, gaps, timestep,
                                                upwind_derivative)

__all__ = ["COMPARED", "compare", "follow", "gaps", "step"]


def pad_wrap(a, g: int):
    """[..., ny, nx] -> [..., ny+2g, nx+2g], the far side's cells."""
    a = jnp.concatenate([a[..., -g:, :], a, a[..., :g, :]], axis=-2)
    return jnp.concatenate([a[..., :, -g:], a, a[..., :, :g]], axis=-1)


def advect_diffuse(vel, vold, c, dt, h, nu):
    g = 3
    lab = pad_wrap(vel, g)
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


def lap_wrap(p):
    q = pad_wrap(p, 1)
    return (q[1:-1, 2:] + q[1:-1, :-2] + q[2:, 1:-1] + q[:-2, 1:-1]
            - 4.0 * p)


def periodic_solve(b):
    """lap_wrap(x) = b, mean-free, by the real Fourier transform that
    diagonalises the wrap Laplacian; two rounds of residual correction
    take out the transform's own rounding."""
    ny, nx = b.shape
    ly = 2.0 * jnp.cos(2.0 * math.pi * jnp.arange(ny, dtype=b.dtype) / ny) - 2.0
    lx = 2.0 * jnp.cos(
        2.0 * math.pi * jnp.arange(nx // 2 + 1, dtype=b.dtype) / nx) - 2.0
    lam = ly[:, None] + lx[None, :]
    lam = lam.at[0, 0].set(1.0)

    def once(r):
        with jax.default_matmul_precision("highest"):
            xh = jnp.fft.rfft2(r) / lam
            return jnp.fft.irfft2(xh.at[0, 0].set(0.0), s=(ny, nx))

    x = once(b)
    for _ in range(2):
        x = x + once(b - lap_wrap(x))
    return x


def step(vel, pres, dt, h, nu, cast=None):
    """One whole time step; returns (vel, pres, div_linf) with
    ``div_linf`` = max |div u*| of the velocity before projection.

    ``cast`` (the control): a dtype the velocity operands of each
    advection substage are rounded through, as a lower storage
    precision would; the projection stays in float32."""
    def low(a):
        return a if cast is None else a.astype(cast).astype(a.dtype)

    vold = vel
    for c in (0.5, 1.0):
        vel = advect_diffuse(low(vel), low(vold), c, dt, h, nu)
    lab = pad_wrap(vel, 1)
    div = (lab[0, 1:-1, 2:] - lab[0, 1:-1, :-2]
           + lab[1, 2:, 1:-1] - lab[1, :-2, 1:-1])
    div_linf = jnp.max(jnp.abs(div)) / (2.0 * h)
    b = (0.5 * h / dt) * div - lap_wrap(pres)
    dp = periodic_solve(b)
    pres = (dp - jnp.mean(dp)) + (pres - jnp.mean(pres))
    q = pad_wrap(pres, 1)
    grad = jnp.stack([q[1:-1, 2:] - q[1:-1, :-2],
                      q[2:, 1:-1] - q[:-2, 1:-1]])
    return vel - (0.5 * dt / h) * grad, pres, div_linf


def follow(vel0, n_steps: int, *, h: float, nu: float, cfl: float,
           cast=None):
    """Follow the first ``n_steps`` steps from ``vel0`` at rest
    pressure; returns one row per step of the scalars the program's
    telemetry records after that step: t, dt, umax, energy, div_linf.

    ``cast``: see :func:`step` (the lower-precision control)."""
    h32 = jnp.float32(h)

    def one(vel, pres, dt):
        vel, pres, dl = step(vel, pres, dt, h32, nu, cast)
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


def compare(config, cell, seed, records, grid) -> dict:
    """Follow the run's first ``reference_steps`` steps from the seed
    and hold the program's telemetry of those same steps against them
    (as ``uniform_walls.compare``, on a box with no wall).
    {name: {"value", "limit"}} for every number the cell gives a limit
    for; a step the program left no record of reads as None (never
    correct). ``BENCHMARK_REFERENCE_CAST`` (a cell's control sets it):
    the dtype the reference's advection operands are rounded through,
    so that the harness's own decision can be shown to fail the
    nearest precision below the configuration's at the cell's size."""
    import json
    import os

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
    cast = os.environ.get("BENCHMARK_REFERENCE_CAST")
    ours = follow(seeded.start_velocity(config, seed), n, h=h,
                  nu=mine["nu"], cfl=mine["cfl"],
                  cast=jnp.dtype(cast) if cast else None)
    got = gaps(theirs, ours)
    print(json.dumps({"phase": "readings", **got}), flush=True)
    return {k: {"value": got[k], "limit": limits[k]} for k in limits}
