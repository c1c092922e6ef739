"""Surface force/power diagnostics: the reference's KernelComputeForces
(`/root/reference/main.cpp:5573-5746`) + ComputeSurfaceNormals
(`3774-3830`) as one gather kernel.

Surface cells are detected from the combined chi/sdf gradients (the
delta-function weight D); each surface cell probes up to 4 cells along
its outward normal to find fluid (chi < 0.01), evaluates one-sided
5th-order velocity derivatives there, Taylor-corrects them back to the
surface cell, and accumulates traction (viscous nu/h * grad u . n_chi +
pressure * n_chi), torque, thrust/drag split along the body velocity,
lift, and output/deformation power — the reference's 19-component
per-shape reduction (main.cpp:7188-7284).

`surface_forces_block` is the single-tile core over ghost-padded labs,
with the reference's probe/stencil lab-edge gates. The AMR path vmaps it
over forest blocks with G=4 (the reference's own lab extent, including
its stencil-order degradation at lab edges — see `surface_forces_blocks`)
and, like the reference's loop over the blocks that hold a piece of that
obstacle (main.cpp:5573, the AreaSegment-AABB block lists of 4208-4269),
over the body's block list only: `AMRSim._shape_inputs` lists per body
the blocks within the pass's reach of its segments' boxes and
`AMRSim._forces_impl` hands `surface_forces_blocks` those rows (<= 128 at
the two-fish case) instead of all N padded rows, nine tenths of which
are pad rows, background or the other body (PR 29). The per-cell cost is
the ~30 data-dependent lab gathers of the probe walk and the one-sided
stencils; the surface detection's fixed +-1 neighbours are static
slices. The uniform wrapper `surface_forces` calls the core as one big
tile with G=10 ghosts, so derivative order degrades only near the
*domain* boundary — a documented improvement over the reference's
per-8-cell-block artifacts. Surface membership for overlapping bodies is
cell-granular (own-sdf band) instead of the reference's block-granular
choice — the second documented deviation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_EPS = 2.220446049250313e-16

# 5th-order one-sided first-derivative coefficients (main.cpp:5579-5584)
_C = (-137.0 / 60.0, 5.0, -5.0, 10.0 / 3.0, -5.0 / 4.0, 1.0 / 5.0)

FORCE_KEYS = (
    "perimeter", "circulation", "forcex", "forcey", "forcex_P", "forcey_P",
    "forcex_V", "forcey_V", "torque", "torque_P", "torque_V",
    "drag", "thrust", "lift", "Pout", "PoutBnd", "defPower", "defPowerBnd",
    "PoutNew",
)


def surface_forces_block(velp, pres, chip, sdfp, udef, own_sdf, xc, yc,
                         com, uvw, nu, h, G):
    """Force reduction over ONE ghost-padded tile.

    velp: [2, L, L] velocity lab; chip/sdfp: [L, L] combined chi/sdf
    labs; pres/own_sdf: [ny, nx] interiors; udef: [2, ny, nx] the
    shape's own deformation velocity; xc/yc: [ny, nx] cell centers;
    h scalar (this tile's spacing). Returns the 18 partial sums plus
    PoutNew assembled by the caller after summing.
    """
    ny, nx = pres.shape
    iy, ix = jnp.meshgrid(jnp.arange(ny), jnp.arange(nx), indexing="ij")

    def at_s(lab, yy, xx):
        return lab[yy + G, xx + G]

    def at_v(yy, xx):
        return velp[:, yy + G, xx + G]

    def nb(lab, dy, dx):
        """The interior shifted by a FIXED (dy, dx): a static slice of
        the lab, where at_s/at_v would gather through index arrays."""
        return lab[..., G + dy:G + dy + ny, G + dx:G + dx + nx]

    # --- surface detection (ComputeSurfaceNormals, main.cpp:3786-3810) ---
    grad_hx = nb(chip, 0, 1) - nb(chip, 0, -1)
    grad_hy = nb(chip, 1, 0) - nb(chip, -1, 0)
    i2h = 0.5 / h
    grad_ux = i2h * (nb(sdfp, 0, 1) - nb(sdfp, 0, -1))
    grad_uy = i2h * (nb(sdfp, 1, 0) - nb(sdfp, -1, 0))
    grad_usq = grad_ux * grad_ux + grad_uy * grad_uy + _EPS
    d_w = (0.5 * h) * (grad_hx * grad_ux + grad_hy * grad_uy) / grad_usq
    norm_x = -d_w * grad_ux
    norm_y = -d_w * grad_uy
    mask = ((grad_hx * grad_hx + grad_hy * grad_hy) >= 1e-12) \
        & (jnp.abs(d_w) > _EPS) & (own_sdf > -4.0 * h)

    nmag = jnp.sqrt(norm_x * norm_x + norm_y * norm_y) + _EPS
    dx_u = norm_x / nmag
    dy_u = norm_y / nmag

    # --- probe walk along the normal to fluid (main.cpp:5619-5632):
    # a step is taken only while its +-1 neighborhood stays inside the
    # lab (the reference's inrange gate) ---
    px_i = ix
    py_i = iy
    done = jnp.zeros_like(mask)
    for k in range(5):
        cx = ix + jnp.rint(k * dx_u).astype(jnp.int32)
        cy = iy + jnp.rint(k * dy_u).astype(jnp.int32)
        inb = (cx - 1 >= -G) & (cx + 1 <= nx + G - 1) \
            & (cy - 1 >= -G) & (cy + 1 <= ny + G - 1)
        take = inb & ~done
        px_i = jnp.where(take, cx, px_i)
        py_i = jnp.where(take, cy, py_i)
        done = done | (take & (at_s(chip, cy, cx) < 0.01))

    sx = jnp.where(norm_x > 0, 1, -1)
    sy = jnp.where(norm_y > 0, 1, -1)

    def deriv_1d(axis):
        """One-sided first derivative at the probe, 5th/2nd/1st order by
        distance to the lab edge (main.cpp:5640-5696), per component."""
        if axis == 0:
            off = lambda k: at_v(py_i, px_i + k * sx)  # noqa: E731
            pos, s_, n_ = px_i, sx, nx
        else:
            off = lambda k: at_v(py_i + k * sy, px_i)  # noqa: E731
            pos, s_, n_ = py_i, sy, ny
        in5 = (pos + 5 * s_ >= -G) & (pos + 5 * s_ <= n_ + G - 1)
        in2 = (pos + 2 * s_ >= -G) & (pos + 2 * s_ <= n_ + G - 1)
        d5 = sum(c * off(k) for k, c in enumerate(_C))
        d2 = -1.5 * off(0) + 2.0 * off(1) - 0.5 * off(2)
        d1 = off(1) - off(0)
        return s_ * jnp.where(in5, d5, jnp.where(in2, d2, d1))

    dveldx = deriv_1d(0)
    dveldy = deriv_1d(1)
    dveldx2 = at_v(py_i, px_i - 1) - 2.0 * at_v(py_i, px_i) \
        + at_v(py_i, px_i + 1)
    dveldy2 = at_v(py_i - 1, px_i) - 2.0 * at_v(py_i, px_i) \
        + at_v(py_i + 1, px_i)

    def d2nd(kx, ky):
        return (-1.5 * at_v(py_i, px_i + kx * sx)
                + 2.0 * at_v(py_i + sy, px_i + kx * sx)
                - 0.5 * at_v(py_i + 2 * sy, px_i + kx * sx))
    dveldxdy = (sx * sy) * (-0.5 * d2nd(2, 0) + 2.0 * d2nd(1, 0)
                            - 1.5 * d2nd(0, 0))

    tx = (ix - px_i)
    ty = (iy - py_i)
    du_dx = dveldx[0] + dveldx2[0] * tx + dveldxdy[0] * ty
    dv_dx = dveldx[1] + dveldx2[1] * tx + dveldxdy[1] * ty
    du_dy = dveldy[0] + dveldy2[0] * ty + dveldxdy[0] * tx
    dv_dy = dveldy[1] + dveldy2[1] * ty + dveldxdy[1] * tx

    # --- traction and reductions (main.cpp:5700-5745) ---
    nuoh = nu / h
    fxv = nuoh * (du_dx * norm_x + du_dy * norm_y)
    fyv = nuoh * (dv_dx * norm_x + dv_dy * norm_y)
    fxp = -pres * norm_x
    fyp = -pres * norm_y
    fxt = fxv + fxp
    fyt = fyv + fyp

    u_here, v_here = nb(velp, 0, 0)
    vel_norm = jnp.sqrt(uvw[0] ** 2 + uvw[1] ** 2)
    unit_x = jnp.where(vel_norm > 0, uvw[0] / (vel_norm + _EPS), 0.0)
    unit_y = jnp.where(vel_norm > 0, uvw[1] / (vel_norm + _EPS), 0.0)

    rx = xc - com[0]
    ry = yc - com[1]

    force_par = fxt * unit_x + fyt * unit_y
    force_perp = fxt * unit_y - fyt * unit_x
    pow_out = fxt * u_here + fyt * v_here
    pow_def = fxt * udef[0] + fyt * udef[1]

    def red(q):
        return jnp.sum(jnp.where(mask, q, 0.0))

    return {
        "perimeter": red(nmag - _EPS),
        "circulation": red(norm_x * v_here - norm_y * u_here),
        "forcex": red(fxt),
        "forcey": red(fyt),
        "forcex_P": red(fxp),
        "forcey_P": red(fyp),
        "forcex_V": red(fxv),
        "forcey_V": red(fyv),
        "torque": red(rx * fyt - ry * fxt),
        "torque_P": red(rx * fyp - ry * fxp),
        "torque_V": red(rx * fyv - ry * fxv),
        "thrust": red(0.5 * (force_par + jnp.abs(force_par))),
        "drag": -red(0.5 * (force_par - jnp.abs(force_par))),
        "lift": red(force_perp),
        "Pout": red(pow_out),
        "PoutBnd": red(jnp.minimum(0.0, pow_out)),
        "defPower": red(pow_def),
        "defPowerBnd": red(jnp.minimum(0.0, pow_def)),
    }


def _finish(sums, uvw):
    out = dict(sums)
    out["PoutNew"] = out["forcex"] * uvw[0] + out["forcey"] * uvw[1]
    return out


def surface_forces_blocks(velp, pres, chip, sdfp, udef, own_sdf, xc, yc,
                          com, uvw, nu, h, G=4):
    """AMR path: vmap the core over [N] forest blocks (velp [N, 2, L, L],
    labs [N, L, L], interiors [N, ...], h [N]) and sum the partials. The
    caller chooses the rows: all of the forest's, or a body's list."""
    core = functools.partial(surface_forces_block, G=G)
    per_block = jax.vmap(
        core, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None, None, None, 0),
    )(velp, pres, chip, sdfp, udef, own_sdf, xc, yc, com, uvw, nu, h)
    sums = {k: jnp.sum(v) for k, v in per_block.items()}
    return _finish(sums, uvw)


def surface_forces(vel, pres, chi, sdf, udef, own_sdf, com, uvw, nu, h):
    """Uniform-grid wrapper: one big tile with G=10 ghosts (edge-pad
    scalars, free-slip mirror velocity — VectorLab, main.cpp:3127).
    Fields are full-grid: vel/udef [2, Ny, Nx], rest [Ny, Nx]."""
    ny, nx = chi.shape
    G = 10  # covers probe walk (<=4) + 5-cell stencils away from walls
    chip = jnp.pad(chi, G, mode="edge")
    sdfp = jnp.pad(sdf, G, mode="edge")
    velp = jnp.pad(vel, ((0, 0), (G, G), (G, G)), mode="edge")
    sgnx = jnp.ones(nx + 2 * G, vel.dtype).at[:G].set(-1).at[nx + G:].set(-1)
    sgny = jnp.ones(ny + 2 * G, vel.dtype).at[:G].set(-1).at[ny + G:].set(-1)
    velp = jnp.stack([velp[0] * sgnx[None, :], velp[1] * sgny[:, None]])

    iy, ix = jnp.meshgrid(jnp.arange(ny), jnp.arange(nx), indexing="ij")
    xc = (ix + 0.5) * h
    yc = (iy + 0.5) * h
    sums = surface_forces_block(velp, pres, chip, sdfp, udef, own_sdf,
                                xc, yc, com, uvw, nu, h, G)
    return _finish(sums, uvw)
