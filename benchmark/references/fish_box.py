"""Plain reference of self-propelled fish in a free-slip box.

Written from the published method (slitvinov/CUP2D ``main.cpp``; the
line ranges are the ones ``SURVEY.md`` gives) and importing nothing of
the program: NumPy float64 for the midline, as the method's host side
is, ``jax.numpy`` float32 for the flow, and the plain WENO5 and
cosine-transform pieces of ``uniform_walls.py`` (benchmark code too).

The fish (Shape / ongrid, 3711-3773, 3991-4207, 6386-6446): the
StefanFish width profile on the published node layout (a uniform
middle, ends refined towards head and tail); the curvature wave
kappa(s, t) = A(s) r(t) sin(2 pi (t/T - s/L)), A the natural cubic
spline through the six published control values, r the cubic start-up
ramp from 0.01 to 1 over the first period; the Frenet integration; the
body's area, centre, linear and angular momentum by the trapezoid rule
over the nodes with the width^3 curvature terms; removal of the
deformation's linear and angular momentum (the internal angle
integrates the removed spin). Each step advects the body with its
rigid velocity and THEN evaluates the wave at the time before the step,
as the method does.

The flow (6576-6979, 7007-7187): ONE uniform grid over the whole box at
the forest's finest level (``assumed.reference_grid``), so that its
cells coincide with the finest blocks' cells around the bodies. The
outline polygon's signed distance, Towers' chi in the +-h band,
deformation velocity from the nearest midline node, the grid's own
centre-of-mass correction and de-meaning of the deformation velocity;
textbook WENO5 + Heun advection-diffusion on edge-copy free-slip ghosts
(normal component negated); the 3x3 momentum solve per body and the
implicit penalisation at lambda; the pressure increment from a direct
cosine-transform solve.

Departures from the published method, each on purpose:

- one uniform level instead of the block forest (no regrid, no
  coarse-fine interpolation): away from the bodies the program's blocks
  are coarser, which is most of the gap the limits leave room for;
- the Poisson problem is solved directly to rounding in every step,
  where the method iterates to 1e-3 / 1e-2 after its first ten steps;
- the Frenet recurrence (forward Euler with renormalised frame) is
  written in its closed form — each node turns the frame by
  atan(ds kappa), its rate by ds kappa_t / (1 + (ds kappa)^2) — rather
  than as the published loop; the two agree to second order in
  ds kappa;
- kappa in the width^3 terms is the analytic one, not a difference of
  the normals;
- no body-body collision: the compared steps (65 of them, t < 0.9,
  each fish 0.004 nearer the other) end long before the two fish,
  0.02-0.06 apart at the heads, can touch;
- the reference keeps the RUN's clock: it takes each step's dt from the
  records, so that both sides evaluate the wave at the same times, and
  holds the method's dt rule against the recorded dt as a number of its
  own (``dt_gap``).
"""

from __future__ import annotations

import functools
import json
import math

import numpy as np

from benchmark.references import uniform_walls as plain

# -- the fish (NumPy float64) ------------------------------------------

CURV_AT = np.array([0.0, 0.15, 0.4, 0.65, 0.9, 1.0])
CURV = np.array([0.82014, 1.46515, 2.57136, 3.75425, 5.09147, 5.70449])


def natural_spline(x, y, xx):
    """The natural cubic spline through (x, y) at xx: second
    derivatives from the tridiagonal system, zero at both ends."""
    n = len(x)
    a = np.zeros((n, n))
    b = np.zeros(n)
    a[0, 0] = a[-1, -1] = 1.0
    for i in range(1, n - 1):
        hl, hr = x[i] - x[i - 1], x[i + 1] - x[i]
        a[i, i - 1], a[i, i], a[i, i + 1] = hl, 2.0 * (hl + hr), hr
        b[i] = 6.0 * ((y[i + 1] - y[i]) / hr - (y[i] - y[i - 1]) / hl)
    m = np.linalg.solve(a, b)
    k = np.clip(np.searchsorted(x, xx, side="right") - 1, 0, n - 2)
    hk = x[k + 1] - x[k]
    lo, hi = (x[k + 1] - xx) / hk, (xx - x[k]) / hk
    return (lo * y[k] + hi * y[k + 1]
            + ((lo ** 3 - lo) * m[k] + (hi ** 3 - hi) * m[k + 1])
            * hk * hk / 6.0)


def nodes(length: float, min_h: float) -> np.ndarray:
    """Arclength of the midline nodes: a uniform middle over 80 % of
    the body at about min_h / sqrt(2), and two ends of 10 % whose
    spacing falls linearly towards head and tail (3733-3741)."""
    end = 0.1 * length
    n_mid = int(math.ceil((length - 2 * end) / (min_h / math.sqrt(2.0))
                          / 8.0)) * 8
    ds_mid = (length - 2 * end) / n_mid
    n_end = int(math.ceil(2.0 * end / (ds_mid + 0.125 * min_h) / 4.0)) * 4
    ds_end = 2.0 * end / n_end - ds_mid
    if ds_end < 0.0:
        # a grid too coarse for the body: fewer end nodes, so that the
        # spacing stays non-negative
        n_end = max(4, int(2.0 * end / ds_mid / 4.0) * 4)
        ds_end = max(2.0 * end / n_end - ds_mid, 0.0)
    ramp = ds_end + (ds_mid - ds_end) * np.arange(n_end) / (n_end - 1.0)
    ds = np.concatenate([ramp, np.full(n_mid, ds_mid), ramp[::-1]])
    s = np.concatenate([[0.0], np.cumsum(ds)])
    s[-1] = min(s[-1], length)
    return s


def width(s, length):
    """Half width: a round head of radius 0.04 L, a straight taper to
    0.01 L at 95 %, a straight tail to zero (6428-6443)."""
    sb, st, wh, wt = 0.04 * length, 0.95 * length, 0.04 * length, \
        0.01 * length
    return np.where(
        s < sb, np.sqrt(np.maximum(2.0 * wh * s - s * s, 0.0)),
        np.where(s < st, wh - (wh - wt) * (s - sb) / (st - sb),
                 wt * (length - s) / (length - st)))


def _rot(a, x, y):
    c, s = math.cos(a), math.sin(a)
    return c * x - s * y, s * x + c * y


class Fish:
    """One swimmer's state and its midline at a time."""

    def __init__(self, length, x, y, angle_deg, min_h, period=1.0,
                 frozen=False):
        self.length, self.period = float(length), float(period)
        self.com = np.array([x, y], np.float64)
        self.centre = self.com.copy()
        self.angle = math.radians(angle_deg)
        self.u = self.v = self.omega = 0.0
        self.offset = np.zeros(2)          # centre - com, body frame
        self.inner_angle = self.inner_spin = 0.0
        self.mass = self.inertia = 0.0
        self.frozen = frozen               # control: the wave stands still
        self.s = nodes(self.length, min_h)
        self.w = width(self.s, self.length)
        self.amp = natural_spline(CURV_AT * self.length,
                                  CURV / self.length, self.s)
        ds = np.diff(self.s)
        self.ds = ds
        # trapezoid weights over the nodes
        self.wgt = 0.5 * np.concatenate(
            [[ds[0]], self.s[2:] - self.s[:-2], [ds[-1]]])

    def advect(self, dt):
        self.com += dt * np.array([self.u, self.v])
        self.angle += dt * self.omega
        if self.angle > math.pi:
            self.angle -= 2.0 * math.pi
        if self.angle < -math.pi:
            self.angle += 2.0 * math.pi
        self.centre = self.com + np.array(
            _rot(self.angle, self.offset[0], self.offset[1]))
        self.inner_angle -= dt * self.inner_spin

    def midline(self, t):
        if self.frozen:
            t = 0.0
        s, w, ds = self.s, self.w, self.ds
        tau = min(max(t, 0.0), 1.0)
        ramp = 0.01 + 0.99 * (3.0 * tau * tau - 2.0 * tau ** 3)
        dramp = 0.99 * 6.0 * tau * (1.0 - tau) if 0.0 <= t <= 1.0 else 0.0
        arg = 2.0 * math.pi * (t / self.period - s / self.length)
        kap = self.amp * ramp * np.sin(arg)
        dkap = self.amp * (dramp * np.sin(arg) + ramp * np.cos(arg)
                           * 2.0 * math.pi / self.period)
        if self.frozen:
            dkap = np.zeros_like(kap)
        # the frame turns by atan(ds kappa) a node
        turn = ds * kap[:-1]
        th = np.concatenate([[0.0], np.cumsum(np.arctan(turn))])
        dth = np.concatenate([[0.0], np.cumsum(
            ds * dkap[:-1] / (1.0 + turn * turn))])
        cs, sn = np.cos(th), np.sin(th)
        rx = np.concatenate([[0.0], np.cumsum(ds * cs[:-1])])
        ry = np.concatenate([[0.0], np.cumsum(ds * sn[:-1])])
        vx = np.concatenate([[0.0], np.cumsum(-ds * dth[:-1] * sn[:-1])])
        vy = np.concatenate([[0.0], np.cumsum(ds * dth[:-1] * cs[:-1])])
        nx, ny = -sn, cs
        dnx, dny = -dth * cs, -dth * sn
        up = np.stack([rx + w * nx, ry + w * ny], 1)
        low = np.stack([rx - w * nx, ry - w * ny], 1)

        # area, centre and momenta of r + eta n, |eta| <= w, with the
        # area element (1 - kappa eta) d eta ds
        g = self.wgt
        f1, f2, f3 = 2.0 * w, -kap * 2.0 * w ** 3 / 3.0, 2.0 * w ** 3 / 3.0
        area = np.sum(f1 * g)
        cx = np.sum((rx * f1 + nx * f2) * g) / area
        cy = np.sum((ry * f1 + ny * f2) * g) / area
        px = np.sum((vx * f1 + dnx * f2) * g) / area
        py = np.sum((vy * f1 + dny * f2) * g) / area
        rx, ry, vx, vy = rx - cx, ry - cy, vx - px, vy - py
        spin = np.sum(((rx * vy - ry * vx) * f1
                       + (rx * dny - ry * dnx + nx * vy - ny * vx) * f2
                       + (nx * dny - ny * dnx) * f3) * g)
        inertia = np.sum(((rx * rx + ry * ry) * f1
                          + 2.0 * (rx * nx + ry * ny) * f2 + f3) * g)
        self.inner_spin = spin / inertia
        vx, vy = vx + self.inner_spin * ry, vy - self.inner_spin * rx
        rx, ry = _rot(self.inner_angle, rx, ry)
        vx, vy = _rot(self.inner_angle, vx, vy)
        for skin in (up, low):
            skin[:, 0], skin[:, 1] = _rot(
                self.inner_angle, skin[:, 0] - cx, skin[:, 1] - cy)

        # normals and their rates from the final midline's tangents; a
        # zero-length interval takes its neighbour's
        ok = ds > 0
        inv = np.where(ok, 1.0 / np.where(ok, ds, 1.0), 0.0)
        cols = [-np.diff(ry) * inv, np.diff(rx) * inv,
                -np.diff(vy) * inv, np.diff(vx) * inv]
        for c in cols:
            for i in np.nonzero(~ok)[0]:
                c[i] = c[i - 1] if i > 0 else c[i + 1]
        nx, ny, dnx, dny = (np.concatenate([c, c[-1:]]) for c in cols)
        self.area = area
        self.r, self.vel = np.stack([rx, ry], 1), np.stack([vx, vy], 1)
        self.nor, self.dnor = np.stack([nx, ny], 1), np.stack([dnx, dny], 1)
        self.outline = np.concatenate([up, low[::-1]], 0)

    def tables(self):
        """Outline polygon [E, 2] and node table [Nm, 9] (r, v, n, n_t,
        w) in the box's frame, relative to ``com``."""
        def place(p, shift):
            x, y = _rot(self.angle, p[:, 0], p[:, 1])
            return np.stack([x, y], 1) + shift
        rel = self.centre - self.com
        zero = np.zeros(2)
        table = np.concatenate(
            [place(self.r, rel), place(self.vel, zero),
             place(self.nor, zero), place(self.dnor, zero),
             self.w[:, None]], 1)
        return place(self.outline, rel), table

    def take(self, com, mass, inertia):
        self.com = np.asarray(com, np.float64).copy()
        self.mass, self.inertia = float(mass), float(inertia)
        d = self.centre - self.com
        self.offset = np.array(_rot(-self.angle, d[0], d[1]))


# -- the flow (jax.numpy float32) --------------------------------------

def _polygon_distance(px, py, poly):
    """Signed distance to a closed polygon, positive inside."""
    import jax.numpy as jnp
    a, b = poly, jnp.roll(poly, -1, axis=0)
    e = b - a
    qx, qy = px[..., None] - a[:, 0], py[..., None] - a[:, 1]
    along = jnp.clip((qx * e[:, 0] + qy * e[:, 1])
                     / (e[:, 0] ** 2 + e[:, 1] ** 2 + 1e-30), 0.0, 1.0)
    d2 = jnp.min((qx - along * e[:, 0]) ** 2 + (qy - along * e[:, 1]) ** 2,
                 axis=-1)
    above = (a[:, 1] > py[..., None]) != (b[:, 1] > py[..., None])
    cut = a[:, 0] + (py[..., None] - a[:, 1]) * e[:, 0] \
        / jnp.where(e[:, 1] == 0, 1.0, e[:, 1])
    inside = jnp.sum(above & (px[..., None] < cut), axis=-1) % 2 == 1
    return jnp.where(inside, 1.0, -1.0) * jnp.sqrt(d2)


def _edge(a, g, flip_x=None, flip_y=None):
    """g edge-copy ghosts on the last two axes; ``flip_x``/``flip_y``
    name the component negated beyond the x / y walls (free slip)."""
    import jax.numpy as jnp
    pad = [(0, 0)] * (a.ndim - 2)
    if flip_y is None:
        return jnp.pad(a, pad + [(g, g), (g, g)], mode="edge")
    ny, nx = a.shape[-2:]
    a = jnp.pad(a, pad + [(g, g), (0, 0)], mode="edge")
    rows = jnp.arange(ny + 2 * g)
    out_y = ((rows < g) | (rows >= ny + g))[:, None]
    a = a.at[flip_y].set(jnp.where(out_y, -a[flip_y], a[flip_y]))
    a = jnp.pad(a, pad + [(0, 0), (g, g)], mode="edge")
    cols = jnp.arange(nx + 2 * g)
    out_x = ((cols < g) | (cols >= nx + g))[None, :]
    return a.at[flip_x].set(jnp.where(out_x, -a[flip_x], a[flip_x]))


def _shifted(lab, g, ny, nx):
    return lambda dy, dx: lab[..., g + dy:g + dy + ny, g + dx:g + dx + nx]


def _advect_diffuse(vel, vold, c, dt, h, nu):
    g = 3
    ny, nx = vel.shape[-2:]
    s = _shifted(_edge(vel, g, flip_x=0, flip_y=1), g, ny, nx)
    u = s(0, 0)
    ddx = plain.upwind_derivative(u[0:1], s(0, -3), s(0, -2), s(0, -1), u,
                                  s(0, 1), s(0, 2), s(0, 3))
    ddy = plain.upwind_derivative(u[1:2], s(-3, 0), s(-2, 0), s(-1, 0), u,
                                  s(1, 0), s(2, 0), s(3, 0))
    lap = s(0, 1) + s(0, -1) + s(1, 0) + s(-1, 0) - 4.0 * u
    rhs = -dt * h * (u[0:1] * ddx + u[1:2] * ddy) + nu * dt * lap
    return vold + c * rhs / (h * h)


def _div(vel):
    """Undivided central divergence on free-slip ghosts."""
    ny, nx = vel.shape[-2:]
    s = _shifted(_edge(vel, 1, flip_x=0, flip_y=1), 1, ny, nx)
    return s(0, 1)[0] - s(0, -1)[0] + s(1, 0)[1] - s(-1, 0)[1]


def make_step(ny, nx, win, h, nu, lam, extent, n_bodies, bs=8, cast=None):
    """One whole step as one jitted function of (vel, pres, dt, per-body
    windows (x0, y0, x1, y1 in cells), outlines, node tables, centres
    of mass); see :func:`follow` for the order. ``cast`` (the control):
    a dtype the velocity operands of each advection substage and the
    bodies' tables are rounded through, as a lower storage precision
    would; the projection stays in float32."""
    import jax
    import jax.numpy as jnp

    def low(a):
        """``a`` rounded through ``cast``: an explicit reduction of
        precision, which the compiler may not take out again (a pair of
        converts it may: on the TPU it does)."""
        if cast is None:
            return a
        info = jnp.finfo(cast)
        return jax.lax.reduce_precision(a, exponent_bits=info.nexp,
                                        mantissa_bits=info.nmant)

    h32 = jnp.float32(h)
    hsq = h32 * h32
    xs = (jnp.arange(nx, dtype=jnp.float32) + 0.5) * h32
    ys = (jnp.arange(ny, dtype=jnp.float32) + 0.5) * h32

    def raster_one(box, poly, table, com):
        """One body over its window (``win`` cells from the box's low
        corner, the cells past its high corner masked by the caller):
        distance and raw deformation velocity; positions relative to
        ``com`` for float32's sake."""
        px = jax.lax.dynamic_slice(xs, (box[0],), (win,))[None, :] - com[0]
        py = jax.lax.dynamic_slice(ys, (box[1],), (win,))[:, None] - com[1]
        px, py = jnp.broadcast_to(px, (win, win)), \
            jnp.broadcast_to(py, (win, win))
        d = _polygon_distance(px, py, poly)
        qx, qy = px[..., None] - table[:, 0], py[..., None] - table[:, 1]
        near = table[jnp.argmin(qx * qx + qy * qy, axis=-1)]
        eta = jnp.clip((px - near[..., 0]) * near[..., 4]
                       + (py - near[..., 1]) * near[..., 5],
                       -near[..., 8], near[..., 8])
        ud = jnp.stack([near[..., 2] + eta * near[..., 6],
                        near[..., 3] + eta * near[..., 7]])
        return d, ud

    def rasterize(boxes, polys, tables, coms):
        far = jnp.float32(-extent)
        cols, rows = jnp.arange(nx)[None, :], jnp.arange(ny)[:, None]
        dist_k, ud_k, inside_k = [], [], []
        for k in range(n_bodies):
            box = boxes[k]
            d, ud = raster_one(box, polys[k], tables[k], coms[k])
            at = (box[1], box[0])
            inside = (cols >= box[0]) & (cols < box[2]) \
                & (rows >= box[1]) & (rows < box[3])
            dist_k.append(jnp.where(inside, jax.lax.dynamic_update_slice(
                jnp.full((ny, nx), far), d, at), far))
            ud_k.append(jnp.where(inside, jax.lax.dynamic_update_slice(
                jnp.zeros((2, ny, nx), jnp.float32), ud,
                (jnp.zeros((), box.dtype),) + at), 0.0))
            inside_k.append(inside)
        dist = functools.reduce(jnp.maximum, dist_k)
        # Towers' chi in the band |d| <= h, from the combined distance
        s = _shifted(_edge(dist, 1), 1, ny, nx)
        gx, gy = s(0, 1) - s(0, -1), s(1, 0) - s(-1, 0)
        px_ = jnp.maximum(s(0, 1), 0.0) - jnp.maximum(s(0, -1), 0.0)
        py_ = jnp.maximum(s(1, 0), 0.0) - jnp.maximum(s(-1, 0), 0.0)
        band = (px_ * gx + py_ * gy) / (gx * gx + gy * gy + 2.2e-16)
        chis, uds, com_n, mass, inertia = [], [], [], [], []
        for k in range(n_bodies):
            chi = jnp.where(dist_k[k] > h32, 1.0,
                            jnp.where(dist_k[k] < -h32, 0.0, band))
            wt = chi * hsq
            m0 = jnp.sum(wt)
            safe = jnp.where(m0 > 0, m0, 1.0)
            com = coms[k] + jnp.stack(
                [jnp.sum(wt * (xs[None, :] - coms[k, 0])),
                 jnp.sum(wt * (ys[:, None] - coms[k, 1]))]) / safe
            xr, yr = xs[None, :] - com[0], ys[:, None] - com[1]
            j = jnp.sum(wt * (xr * xr + yr * yr))
            mu = jnp.sum(wt * ud_k[k][0]) / safe
            mv = jnp.sum(wt * ud_k[k][1]) / safe
            ma = jnp.sum(wt * (xr * ud_k[k][1] - yr * ud_k[k][0])) \
                / jnp.where(j > 0, j, 1.0)
            uds.append(jnp.where(inside_k[k], ud_k[k] - jnp.stack(
                jnp.broadcast_arrays(mu - ma * yr, mv + ma * xr)), 0.0))
            chis.append(chi)
            com_n.append(com)
            mass.append(m0)
            inertia.append(j)
        return (jnp.stack(chis), jnp.stack(uds), jnp.stack(com_n),
                jnp.stack(mass), jnp.stack(inertia))

    def penalize(vel, chis, uds, coms, dt):
        chi = jnp.max(chis, axis=0)
        lamdt = lam * dt
        body = jnp.zeros_like(vel)
        uvw = []
        for k in range(n_bodies):
            xr, yr = xs[None, :] - coms[k, 0], ys[:, None] - coms[k, 1]
            f = jnp.where(chis[k] >= 0.5, hsq * lamdt / (1.0 + lamdt), 0.0)
            du, dv = vel[0] - uds[k][0], vel[1] - uds[k][1]
            pm, pj = jnp.sum(f), jnp.sum(f * (xr * xr + yr * yr))
            px, py = jnp.sum(f * xr), jnp.sum(f * yr)
            rhs = jnp.stack([jnp.sum(f * du), jnp.sum(f * dv),
                             jnp.sum(f * (xr * dv - yr * du))]) / pm
            # [[1, 0, -py], [0, 1, px], [-py, px, pj]] / pm, by hand
            a, b, c = -py / pm, px / pm, pj / pm
            w = (rhs[2] - a * rhs[0] - b * rhs[1]) / (c - a * a - b * b)
            sol = jnp.stack([rhs[0] - a * w, rhs[1] - b * w, w])
            uvw.append(sol)
            own = jnp.stack([sol[0] - sol[2] * yr + uds[k][0],
                             sol[1] + sol[2] * xr + uds[k][1]])
            body = jnp.where((chis[k] >= chi)[None], own, body)
        alpha = jnp.where(chi > 0.5, 1.0 / (1.0 + lamdt), 1.0)
        return alpha * vel + (1.0 - alpha) * body, jnp.stack(uvw)

    def deformation(chis, uds):
        chi = jnp.max(chis, axis=0)
        return chi, jnp.sum(jnp.where((chis >= chi)[:, None], uds, 0.0),
                            axis=0)

    def step(vel, pres, dt, boxes, polys, tables, coms):
        chis, uds, com_n, mass, inertia = rasterize(
            boxes, low(polys), low(tables), coms)
        vold = vel
        for c in (0.5, 1.0):
            vel = _advect_diffuse(low(vel), low(vold), c, dt, h32, nu)
        vel, uvw = penalize(vel, chis, uds, com_n, dt)
        chi, ud = deformation(chis, uds)
        div = _div(vel) - chi * _div(ud)
        div_linf = jnp.max(jnp.abs(div)) / (2.0 * h32)
        dp = plain.neumann_solve((0.5 * h32 / dt) * div
                                 - plain.lap_neumann(pres))
        pres = (dp - jnp.mean(dp)) + (pres - jnp.mean(pres))
        s = _shifted(_edge(pres, 1), 1, ny, nx)
        vel = vel - (0.5 * dt / h32) * jnp.stack(
            [s(0, 1) - s(0, -1), s(1, 0) - s(-1, 0)])
        umax = jnp.max(jnp.abs(vel))
        out = {"com": com_n, "mass": mass, "inertia": inertia, "uvw": uvw,
               "umax": umax, "energy": 0.5 * hsq * jnp.sum(vel * vel),
               "div_linf": div_linf}
        return vel, pres, out

    def start(boxes, polys, tables, coms):
        """The state at t = 0: rest, blended with the deformation
        velocity inside the bodies; and the bodies' grid integrals."""
        chis, uds, com_n, mass, inertia = rasterize(
            boxes, low(polys), low(tables), coms)
        chi, ud = deformation(chis, uds)
        return ud * chi, {"com": com_n, "mass": mass, "inertia": inertia,
                          "tiles": _tile_any(chi, bs)}

    return jax.jit(step, donate_argnums=(0, 1)), jax.jit(start)


def _tile_any(chi, bs):
    import jax.numpy as jnp
    ny, nx = chi.shape
    return jnp.max(chi.reshape(ny // bs, bs, nx // bs, bs),
                   axis=(1, 3)) > 0


def timestep(umax, h, nu, cfl, period):
    """The method's dt: diffusive and advective limits at the finest
    spacing, capped at a twentieth of the gait's period."""
    return min(0.25 * h * h / (nu + 0.25 * h * umax),
               cfl * h / (umax + 1e-8), 0.05 * period)


def geometry(config):
    """Grid and physics of a configuration file, at the forest's finest
    level: (ny, nx, h, window cells)."""
    g = config["grid"]
    finest = int(g["level_max"]) - 1
    nx = int(g["bpdx"]) * int(g["block"]) << finest
    ny = int(g["bpdy"]) * int(g["block"]) << finest
    h = float(g["extent"]) / max(nx, ny)
    longest = max(float(s["L"]) for s in config["shapes"])
    bs = int(g["block"])
    win = min(ny, (int(2.0 * window_radius(longest, h) / h / bs) + 3) * bs)
    return ny, nx, h, win


def window_radius(length, h):
    """Half side of the box a body is rasterised in: the method's
    padding of the body's bounding box (4237)."""
    return 0.625 * length + 12.0 * h


def follow(config, seed, dts, *, frozen=False, skip_body=None,
           mass_scale=1.0, cast=None):
    """Follow ``len(dts)`` steps of the configuration from the seed,
    each with the run's own dt; one row a step of what the program's
    record of that step carries: t, dt, umax, energy, div_linf, the dt
    the rule gives for the NEXT step, and the bodies. The rows end
    early where the run's dt blows this reference up.

    The controls (never used by ``compare``): ``frozen`` stops the
    curvature wave, ``skip_body`` leaves one body's velocity as it
    was, ``mass_scale`` scales the recorded masses, ``cast`` computes
    in a lower precision (see :func:`make_step`)."""
    import jax
    import jax.numpy as jnp

    from benchmark import seeded

    ny, nx, h, win = geometry(config)
    ph = config["physics"]
    nu, lam, cfl = float(ph["nu"]), float(ph["lambda"]), float(ph["cfl"])
    shapes = seeded.jittered_shapes(
        config["shapes"], config.get("seed_jitter", {}), seed)
    fish = []
    for line in shapes.splitlines():
        kv = dict(tok.split("=") for tok in line.split())
        fish.append(Fish(float(kv["L"]), float(kv["xpos"]),
                         float(kv["ypos"]), float(kv["angle"]), h,
                         period=float(kv.get("T", 1.0)), frozen=frozen))
    step, start = make_step(ny, nx, win, h, nu, lam,
                            float(config["grid"]["extent"]), len(fish),
                            bs=int(config["grid"]["block"]), cast=cast)

    bs = int(config["grid"]["block"])

    def inputs():
        """Per body: its window — the finest blocks that meet the box
        ``com +- window_radius`` — outline and node table."""
        boxes, polys, tables = [], [], []
        for f in fish:
            r, tile = window_radius(f.length, h), bs * h
            lo = [int(np.clip(math.floor((c - r) / tile) * bs, 0, n - win))
                  for c, n in zip(f.com, (nx, ny))]
            hi = [min(int(math.ceil((c + r) / tile)) * bs, o + win)
                  for c, o in zip(f.com, lo)]
            poly, table = f.tables()
            boxes.append(lo + hi)
            polys.append(poly)
            tables.append(table)
        return (jnp.asarray(boxes, jnp.int32),
                jnp.asarray(np.stack(polys), jnp.float32),
                jnp.asarray(np.stack(tables), jnp.float32),
                jnp.asarray(np.stack([f.com for f in fish]), jnp.float32))

    def bodies():
        return [{"com": [float(f.com[0]), float(f.com[1])],
                 "angle": f.angle, "u": f.u, "v": f.v, "omega": f.omega,
                 "mass": f.mass * mass_scale, "inertia": f.inertia}
                for f in fish]

    with jax.default_matmul_precision("highest"):
        for f in fish:
            f.advect(0.0)
            f.midline(0.0)
        vel, got = start(*inputs())
        got = jax.device_get(got)
        for k, f in enumerate(fish):
            f.take(got["com"][k], got["mass"][k], got["inertia"][k])
        pres = jnp.zeros((ny, nx), jnp.float32)
        rows = [{"step": 0, "t": 0.0, "bodies": bodies(),
                 "body_tiles": int(np.sum(got["tiles"])),
                 "dt_rule": timestep(float(jnp.max(jnp.abs(vel))), h, nu,
                                     cfl, min(f.period for f in fish))}]
        t = 0.0
        for n, dt in enumerate(dts):
            for f in fish:
                f.advect(dt)
                f.midline(t)
            vel, pres, out = step(vel, pres, jnp.float32(dt), *inputs())
            out = jax.device_get(out)
            if not (np.isfinite(out["umax"])
                    and np.all(np.isfinite(out["com"]))):
                # a run whose dt is not the method's (a frozen or
                # unchanged program stays at the gait's cap) can drive
                # this explicit scheme out of its stability: the rows
                # end here, and what they would have held reads None
                break
            for k, f in enumerate(fish):
                f.take(out["com"][k], out["mass"][k], out["inertia"][k])
                if k != skip_body:
                    f.u, f.v, f.omega = (float(a) for a in out["uvw"][k])
            t += dt
            umax = float(out["umax"])
            rows.append({
                "step": n + 1, "t": t, "dt": dt, "umax": umax,
                "energy": float(out["energy"]),
                "div_linf": float(out["div_linf"]),
                "dt_rule": timestep(umax, h, nu, cfl,
                                    min(f.period for f in fish)),
                "bodies": bodies()})
    return rows


# -- what is compared --------------------------------------------------

# the body numbers (they need telemetry schema 13); every other number
# is a flow number, from keys schema 12 already wrote. `umax_gap`,
# `path_gap` and their `wake_` twins did not pass the rule for a limit
# at the chip's size (PERF.md, PR 28) and are readings there
BODY = ("mass_gap", "inertia_gap", "centre_gap", "path_gap", "vel_gap",
        "spin_gap", "wake_path_gap", "wake_vel_gap", "wake_spin_gap")


def _l1(theirs, ours):
    """Path-summed gap of a series against the reference's: sum |a - b|
    over sum |b| (each entry a number or a vector)."""
    top = sum(float(np.linalg.norm(np.subtract(a, b)))
              for a, b in zip(theirs, ours))
    return top / sum(float(np.linalg.norm(b)) for b in ours)


def _series(rows, *keys):
    return [[[b[k] for k in keys] for b in r["bodies"]] for r in rows]


def _body_paths(theirs, ours, length):
    """Over the rows given, widest over the bodies: the centre's
    farthest distance from the reference's (in body lengths), and the
    path-summed gaps of linear velocity and spin."""
    def per_body(fn):
        return max(fn(k) for k in range(len(length)))

    com_t, com_o = _series(theirs, "com"), _series(ours, "com")
    return (
        per_body(lambda k: max(
            float(np.linalg.norm(np.subtract(a[k][0], b[k][0])))
            for a, b in zip(com_t, com_o)) / length[k]),
        per_body(lambda k: _l1(
            [r[k] for r in _series(theirs, "u", "v")],
            [r[k] for r in _series(ours, "u", "v")])),
        per_body(lambda k: _l1(
            [r[k] for r in _series(theirs, "omega")],
            [r[k] for r in _series(ours, "omega")])))


def gaps(config, theirs: list, ours: list, startup=None,
         wake_from=None) -> dict:
    """Every number this reference can compute from the run's records
    of steps 1..N (``theirs``) and its own rows 0..N (``ours``), in two
    stretches: the START-UP, steps 1..``startup`` (all N if None), where
    the run too solves its Poisson problem to the precision floor, and
    the WAKE, steps ``wake_from`` + 1..N (the cell's warm-up: these are
    steps the window times) under the ``wake_`` names.

    Flow numbers, from keys every schema writes: ``energy_gap`` and
    ``umax_gap`` (path-summed over the stretch, so that no single
    step's maximum decides), ``t_gap`` (the clock the method's dt rule
    gives on the reference's own umax against the run's, over the
    start-up), ``cover_gap`` (the share of the finest-level tiles the
    reference's bodies touch that the run's finest level cannot hold,
    widest over all N steps — a loose statement of the tagging rule:
    where chi > 0 the forest is at its finest level). Body numbers,
    from ``bodies`` (schema 13): mass, inertia and centre after the
    first step, and over each stretch the centre's path, the linear
    velocity and the spin. ``by_step`` and the max-norm readings are
    for the ``readings`` line only."""
    n = len(theirs)
    k = n if startup is None else min(int(startup), n)
    head_t, head_o = theirs[:k], ours[1:k + 1]
    finest = str(int(config["grid"]["level_max"]) - 1)
    tiles = ours[0]["body_tiles"]
    held = [int((r.get("blocks_per_level") or {}).get(finest, 0))
            for r in theirs]
    stretches = {"": (head_t, head_o)}
    if wake_from is not None and n > int(wake_from):
        stretches["wake_"] = (theirs[int(wake_from):],
                              ours[1 + int(wake_from):])
    got = {
        "t_gap": abs(sum(r["dt_rule"] for r in ours[:k]) - head_t[-1]["t"])
        / head_t[-1]["t"],
        "cover_gap": max(max(0, tiles - h) / tiles for h in held),
    }
    for pre, (a, b) in stretches.items():
        got[pre + "energy_gap"] = _l1([r["energy"] for r in a],
                                      [r["energy"] for r in b])
        got[pre + "umax_gap"] = _l1([r["umax"] for r in a],
                                    [r["umax"] for r in b])
    by_step = {
        "energy": [abs(a["energy"] - b["energy"]) / b["energy"]
                   for a, b in zip(theirs, ours[1:])],
        "umax": [abs(a["umax"] - b["umax"]) / b["umax"]
                 for a, b in zip(theirs, ours[1:])],
        "div_linf": [abs(a["div_linf"] - b["div_linf"]) / b["div_linf"]
                     for a, b in zip(theirs, ours[1:])],
        "dt": [abs(a["dt"] - b["dt_rule"]) / a["dt"]
               for a, b in zip(theirs, ours[:n])],
        "finest_blocks": held, "body_tiles": tiles,
    }
    got["umax_linf_gap"] = max(by_step["umax"][:k])
    got["dt_gap"] = max(by_step["dt"][:k])
    if all(r.get("bodies") for r in theirs):
        length = [float(s["L"]) for s in config["shapes"]]
        first, mine = theirs[0]["bodies"], ours[1]["bodies"]

        def per_body(fn):
            return max(fn(j) for j in range(len(length)))

        got.update({
            "mass_gap": per_body(lambda j: abs(
                first[j]["mass"] - mine[j]["mass"]) / mine[j]["mass"]),
            "inertia_gap": per_body(lambda j: abs(
                first[j]["inertia"] - mine[j]["inertia"])
                / mine[j]["inertia"]),
            "centre_gap": per_body(lambda j: float(np.linalg.norm(
                np.subtract(first[j]["com"], mine[j]["com"]))) / length[j]),
        })
        for pre, (a, b) in stretches.items():
            (got[pre + "path_gap"], got[pre + "vel_gap"],
             got[pre + "spin_gap"]) = _body_paths(a, b, length)
    return {**got, "by_step": by_step}


def compare(config, cell, seed, records, grid) -> dict:
    """Follow the run's first ``reference_steps`` steps from the seed,
    each with the run's own dt, and hold the run's records of those
    steps against them in two stretches (see :func:`gaps`): the
    start-up, steps 1..``startup_steps``, and — where the cell's
    ``reference_steps`` reach past its ``warmup_steps`` — the first
    steps of the measured window, which run the production Poisson
    solve, the regrids on their cadence and whatever step executable
    the forest then asks for. Returns {name: {"value", "limit"}} for
    every name the cell gives a limit for, and only those (every other
    reading goes to the ``readings`` line).

    Records older than schema 13 carry no ``bodies``: the body numbers
    are then LEFT OUT, so that such a program is judged on the flow
    numbers. Records of schema 13 or later without ``bodies`` read None
    — never correct — and so does a step the run left no record of."""
    limits = cell["limits"]
    by_step = {r["step"]: r for r in records}
    n = int(cell["reference_steps"])
    theirs = [by_step.get(k) for k in range(1, n + 1)]
    if any(r is None for r in theirs):
        return {k: {"value": None, "limit": limits[k]} for k in limits}
    old = all(int(r.get("schema", 0)) < 13 for r in theirs)
    ours = follow(config, seed, [float(r["dt"]) for r in theirs])
    followed = len(ours) - 1
    startup = int(cell.get("startup_steps", n))
    got = {"followed_steps": followed}
    if followed >= startup:
        # a stretch the reference could not follow to its end (the
        # run's own dt blew it up) is left out, and reads None below
        got.update(gaps(config, theirs[:followed], ours, startup=startup,
                        wake_from=cell.get("warmup_steps")
                        if followed == n else None))
    got = {k: v if isinstance(v, dict) or math.isfinite(v) else None
           for k, v in got.items()}
    print(json.dumps({"phase": "readings", "schema": theirs[0].get("schema"),
                      **got}), flush=True)
    return {k: {"value": got.get(k), "limit": limits[k]} for k in limits
            if not (old and k in BODY)}
