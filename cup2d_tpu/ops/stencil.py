"""Stencil operator library — the physics kernels, written once over
ghost-padded arrays and shared by the uniform-grid and AMR block paths.

TPU-native re-design of the reference's per-cell OpenMP loops
(`/root/reference/main.cpp:5441-5572` KernelAdvectDiffuse,
`main.cpp:3343-3366` KernelVorticity, `main.cpp:6105-6287` pressure RHS,
`main.cpp:6021-6104` pressure correction): every kernel here is a pure
function over whole arrays — shifts instead of indexed reads — so XLA fuses
each operator into a handful of elementwise/reduce HLOs over all cells (or
all blocks, when vmapped by the AMR path) at once.

Array convention: fields are padded with `g` ghost cells on each side of the
last two axes, i.e. shape `[..., Ny + 2g, Nx + 2g]`; kernels return interior
arrays `[..., Ny, Nx]`. Axis -2 is y, axis -1 is x. Velocity labs carry a
leading component axis of size 2 (u, v). "Undivided" differences (no 1/h)
are used where the reference uses them, so scalings match exactly.

This library is also the fused Pallas tier's semantic reference: the
megakernel (ops/pallas_kernels.py) reuses these op bodies over VMEM
strips and, since ISSUE 16, synthesizes every bc.py ghost kind in-VMEM
from the same affine edge/inner-line combinations the XLA chain paints
via pad_vector_bc — the pad -> advect_diffuse_rhs -> heun_substage
composition below is what the kernel equivalence tests pin against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def dt_from_umax(umax, h, nu, cfl):
    """CFL/diffusive timestep (main.cpp:6579-6595):
    min(0.25 h^2/(nu + 0.25 h umax), cfl h/(umax + 1e-8)). The ONE
    definition shared by the uniform and forest paths, on device and
    from host-pulled umax — cached next-dt and fallback recomputation
    must agree bit-for-bit or a checkpoint restart forks the
    trajectory."""
    dt_diff = 0.25 * h * h / (nu + 0.25 * h * umax)
    return jnp.minimum(dt_diff, cfl * h / (umax + 1e-8))


def interior(lab: jnp.ndarray, g: int) -> jnp.ndarray:
    """Strip g ghost layers from the last two axes."""
    if g == 0:
        return lab
    return lab[..., g:-g, g:-g]


def shift(lab: jnp.ndarray, g: int, dy: int, dx: int) -> jnp.ndarray:
    """Interior view displaced by (dy, dx); |dy|,|dx| <= g."""
    ny = lab.shape[-2] - 2 * g
    nx = lab.shape[-1] - 2 * g
    return lab[..., g + dy : g + dy + ny, g + dx : g + dx + nx]


# ---------------------------------------------------------------------------
# WENO5 (reference main.cpp:162-208) — vectorized; the `pow(b+e, 2)`
# smoothness weighting and e=1e-6 are kept bit-for-bit.
# ---------------------------------------------------------------------------

_WENO_EPS = 1e-6
# guard for the fast-weights denominator: legitimate denominators stay
# >= ~1e-21 (analysis in _weno5_weights); only total ratio-underflow
# (b_max/b_min beyond ~1e19, i.e. an already-blown-up field) trips it
_WENO_TINY = 1e-35


def _weno5_weights(b1, b2, b3, g1, g2, g3):
    # max-normalized single-divide form (2 divides instead of the
    # textbook 4): divide every smoothness indicator by the largest,
    # so the ratios r_i live in (0, 1] and the cross products
    # n_i = g_i * prod_{j != i} r_j^2 CANNOT overflow — which was the
    # measured objection (f32 blow-up at b ~ 2e9) that kept round 2 on
    # the ratio form. The weights are scale-invariant in b, so this is
    # the same algebra, just evaluated at a safe scale. Advection is
    # arithmetic- (divide-) bound, far above the HBM roofline, so
    # trading 2 divides for ~8 multiplies is the biggest lever on the
    # step.
    #
    # Degenerate tail: if b_max/b_min exceeds ~1e19 every cross
    # product underflows to 0 (TPU flushes denormals); the 0/0 is
    # caught by the _WENO_TINY select, which falls back to the optimal
    # (central) weights — finite, convex, and irrelevant in practice
    # because a field that rough has long since collapsed dt.
    bmax = jnp.maximum(jnp.maximum(b1, b2), b3) + _WENO_EPS
    if bmax.dtype == jnp.float32:
        # the weights are EXACTLY scale-invariant in the normalizer
        # (any common factor in the r_i cancels from n_i/den), so the
        # 1/b_max divide needs no accuracy at all: a bit-trick
        # approximate reciprocal (~2% error, one int subtract) replaces
        # a VPU divide per reconstruction. f64 (CPU validation) keeps
        # the exact divide — performance is irrelevant there and the
        # magic constant is format-specific.
        m = jax.lax.bitcast_convert_type(
            jnp.int32(0x7EF311C3)
            - jax.lax.bitcast_convert_type(bmax, jnp.int32),
            jnp.float32)
    else:
        m = 1.0 / bmax
    r1 = (b1 + _WENO_EPS) * m
    r2 = (b2 + _WENO_EPS) * m
    r3 = (b3 + _WENO_EPS) * m
    s1, s2, s3 = r1 * r1, r2 * r2, r3 * r3
    n1 = g1 * (s2 * s3)
    n2 = g2 * (s1 * s3)
    n3 = g3 * (s1 * s2)
    den = (n1 + n3) + n2
    ok = den > _WENO_TINY
    aux = 1.0 / jnp.where(ok, den, 1.0)
    w1 = jnp.where(ok, n1 * aux, g1)
    w2 = jnp.where(ok, n2 * aux, g2)
    w3 = jnp.where(ok, n3 * aux, g3)
    return w1, w2, w3


def _weno5_weights_ref(b1, b2, b3, g1, g2, g3):
    # the textbook ratio form (bit-matches the reference's
    # main.cpp:162-208 evaluation order) — kept for the bit-comparison
    # tests that pin the fast form against it
    w1 = g1 / (b1 + _WENO_EPS) ** 2
    w2 = g2 / (b2 + _WENO_EPS) ** 2
    w3 = g3 / (b3 + _WENO_EPS) ** 2
    aux = 1.0 / ((w1 + w3) + w2)
    return w1 * aux, w2 * aux, w3 * aux


def _smoothness(um2, um1, u, up1, up2):
    b1 = 13.0 / 12.0 * ((um2 + u) - 2 * um1) ** 2 + 0.25 * ((um2 + 3 * u) - 4 * um1) ** 2
    b2 = 13.0 / 12.0 * ((um1 + up1) - 2 * u) ** 2 + 0.25 * (um1 - up1) ** 2
    b3 = 13.0 / 12.0 * ((u + up2) - 2 * up1) ** 2 + 0.25 * ((3 * u + up2) - 4 * up1) ** 2
    return b1, b2, b3


def weno5_plus(um2, um1, u, up1, up2):
    """Upwind-biased flux reconstruction, wind > 0 (main.cpp:162-180)."""
    b1, b2, b3 = _smoothness(um2, um1, u, up1, up2)
    w1, w2, w3 = _weno5_weights(b1, b2, b3, 0.1, 0.6, 0.3)
    f1 = (11.0 / 6.0) * u + ((1.0 / 3.0) * um2 - (7.0 / 6.0) * um1)
    f2 = (5.0 / 6.0) * u + ((-1.0 / 6.0) * um1 + (1.0 / 3.0) * up1)
    f3 = (1.0 / 3.0) * u + ((5.0 / 6.0) * up1 - (1.0 / 6.0) * up2)
    return (w1 * f1 + w3 * f3) + w2 * f2


def weno5_minus(um2, um1, u, up1, up2):
    """Upwind-biased flux reconstruction, wind < 0 (main.cpp:181-201)."""
    b1, b2, b3 = _smoothness(um2, um1, u, up1, up2)
    w1, w2, w3 = _weno5_weights(b1, b2, b3, 0.3, 0.6, 0.1)
    f1 = (1.0 / 3.0) * u + ((-1.0 / 6.0) * um2 + (5.0 / 6.0) * um1)
    f2 = (5.0 / 6.0) * u + ((1.0 / 3.0) * um1 - (1.0 / 6.0) * up1)
    f3 = (11.0 / 6.0) * u + ((-7.0 / 6.0) * up1 + (1.0 / 3.0) * up2)
    return (w1 * f1 + w3 * f3) + w2 * f2


def weno_derivative(wind, um3, um2, um1, u, up1, up2, up3):
    """Undivided upwind WENO5 derivative (main.cpp:202-208): flux difference
    of the reconstruction chosen by the local wind sign.

    Exploits the exact mirror identity
    ``weno5_minus(a,b,c,d,e) == weno5_plus(e,d,c,b,a)`` (the smoothness
    indicators, ideal weights and candidate stencils all pair up under
    argument reversal, commutative adds only): selecting the five
    STENCIL ARGUMENTS by wind sign up front needs 10 one-cycle selects
    and TWO reconstructions, where the textbook both-branches-then-
    select form needs FOUR. Bit-identical to the latter (asserted in
    tests/test_fused_bc.py::test_weno_mirror_identity_bit_exact);
    advection is the VPU-bound hot spot of the whole step, so halving
    its reconstruction count is worth the obfuscation."""
    pos = wind > 0

    def sel(a, b):
        return jnp.where(pos, a, b)

    # wind>0: weno5_plus at i      | wind<0: weno5_minus at i (mirrored)
    t1 = weno5_plus(sel(um2, up3), sel(um1, up2), sel(u, up1),
                    sel(up1, u), sel(up2, um1))
    # wind>0: weno5_plus at i-1    | wind<0: weno5_minus at i-1 (mirrored)
    t2 = weno5_plus(sel(um3, up2), sel(um2, up1), sel(um1, u),
                    sel(u, um1), sel(up1, um2))
    return t1 - t2


# ---------------------------------------------------------------------------
# Advection–diffusion RHS (KernelAdvectDiffuse, main.cpp:5441-5503)
# ---------------------------------------------------------------------------

def advect_diffuse_rhs(vlab: jnp.ndarray, g: int, h, nu, dt):
    """RHS in the reference's block scaling: h^2 * du/dt * dt, i.e.
    ``afac*(u·∇)u + dfac*lap(u)`` with afac = -dt*h, dfac = nu*dt and
    *undivided* differences — exactly what the reference writes into tmpV;
    the integrator divides by h^2 (main.cpp:6619-6626).

    vlab: [..., 2, Ny+2g, Nx+2g] velocity with ghosts, g >= 3.
    Returns [..., 2, Ny, Nx].
    """
    return advect_diffuse_core(vlab, g, -dt * h, nu * dt)


def advect_diffuse_core(vlab: jnp.ndarray, g: int, afac, dfac):
    """Same, with the scale factors precomputed — shared verbatim by the
    XLA path above and the Pallas kernel (ops/pallas_kernels.py), so the
    two can never drift numerically.

    Deliberately the per-cell form: evaluating each reconstruction once
    on a one-cell-extended range and differencing by shift halves the
    arithmetic on paper but measured 26% SLOWER at 8192^2 — the
    odd-width (n+1) intermediates misalign XLA's (8, 128) lane tiling
    and the relayouts cost more than the saved flops."""
    assert g >= 3
    u = shift(vlab, g, 0, 0)
    wind_u = u[..., 0:1, :, :]  # u component drives x-derivatives
    wind_v = u[..., 1:2, :, :]  # v component drives y-derivatives

    dx = weno_derivative(
        wind_u,
        shift(vlab, g, 0, -3), shift(vlab, g, 0, -2), shift(vlab, g, 0, -1),
        u,
        shift(vlab, g, 0, 1), shift(vlab, g, 0, 2), shift(vlab, g, 0, 3),
    )
    dy = weno_derivative(
        wind_v,
        shift(vlab, g, -3, 0), shift(vlab, g, -2, 0), shift(vlab, g, -1, 0),
        u,
        shift(vlab, g, 1, 0), shift(vlab, g, 2, 0), shift(vlab, g, 3, 0),
    )
    lap = (
        shift(vlab, g, 0, 1) + shift(vlab, g, 0, -1)
        + shift(vlab, g, 1, 0) + shift(vlab, g, -1, 0)
        - 4.0 * u
    )
    return afac * (wind_u * dx + wind_v * dy) + dfac * lap


def heun_substage(vold, cfac, rhs, ih2):
    """One Heun stage update ``vold + cfac * rhs * ih2`` (rhs in the
    reference's undivided h^2-scaled form, ih2 = 1/h^2). Trivial on
    purpose: the expression lives HERE so the XLA drivers (uniform,
    fleet, amr) and the fused Pallas megakernel all evaluate the same
    association order — the f32 equivalence goldens pin it."""
    return vold + cfac * rhs * ih2


# ---------------------------------------------------------------------------
# Fused-BC forms of the LINEAR operators (uniform path).
#
# jnp.pad(mode="edge") lowers to concatenates of edge strips that XLA
# materializes (measured ~19 ms/step at 8192^2, the "halo-pad" slice of
# the round-3 trace). For a linear stencil the physical BC is
# equivalently a zero-ghost shift plus a rank-1 edge correction — the
# Neumann ghost contributes the edge cell once per adjacent wall, the
# free-slip ghost contributes +/- the edge cell — and zero padding is a
# plain `pad` HLO that fuses into the consumer. These forms take the
# UNPADDED field and produce bit-close (summation-order differs only in
# wall cells) results to laplacian5(pad_scalar(p, 1)) etc.
# ---------------------------------------------------------------------------

def _edge_ones(n, dtype, lo=1.0, hi=1.0):
    # iota + compares, NOT .at[].set on zeros: the latter bakes an HLO
    # constant that is DMA-staged from HBM on every use inside loop
    # bodies (~4.7 ms/step of f32[8192] copy-starts in the round-4
    # trace); an iota is generated in-register for free
    i = jnp.arange(n)
    z = jnp.zeros((), dtype)
    return jnp.where(i == 0, jnp.asarray(lo, dtype),
                     jnp.where(i == n - 1, jnp.asarray(hi, dtype), z))


def _zshift(p: jnp.ndarray, dy: int, dx: int,
            spmd_safe: bool = False) -> jnp.ndarray:
    """Shift with zero ghosts on an unpadded array (|dy|,|dx| <= 1).

    Default form: pad(0)+slice — XLA folds the resulting negative-pad
    into the consumer fusion (fastest single-device form, measured
    against the edge-pad original). The SPMD partitioner MISCOMPILES
    that negative-pad pattern when the sliced axis is sharded
    (compositions return garbage at small shard widths — caught by
    tests/test_poisson.py::test_mg_solve_sharded_matches_single_device;
    re-tested on jax 0.9.0: a single Laplacian partitions correctly,
    a whole jitted V-cycle of them is off by O(1));
    ``spmd_safe=True`` switches to slice-then-pad, which the
    partitioner handles exactly (to 1 ulp) at a cost the sharded paths
    accept."""
    ny, nx = p.shape[-2], p.shape[-1]
    if spmd_safe:
        ys = slice(max(dy, 0), ny + min(dy, 0))
        xs = slice(max(dx, 0), nx + min(dx, 0))
        q = p[..., ys, xs]
        pad = [(0, 0)] * (p.ndim - 2) + [(max(-dy, 0), max(dy, 0)),
                                         (max(-dx, 0), max(dx, 0))]
        return jnp.pad(q, pad)
    pad = [(0, 0)] * (p.ndim - 2) + [(max(-dy, 0), max(dy, 0)),
                                     (max(-dx, 0), max(dx, 0))]
    zp = jnp.pad(p, pad)
    oy, ox = max(dy, 0), max(dx, 0)
    return zp[..., oy:oy + ny, ox:ox + nx]


def laplacian5_neumann(p: jnp.ndarray, spmd_safe: bool = False) -> jnp.ndarray:
    """Undivided 5-point Laplacian with zero-Neumann walls, UNPADDED
    input [..., Ny, Nx] — fused-BC equivalent of
    ``laplacian5(pad_scalar(p, 1), 1)``."""
    ny, nx = p.shape[-2], p.shape[-1]
    ex = _edge_ones(nx, p.dtype)
    ey = _edge_ones(ny, p.dtype)
    zs = lambda dy, dx: _zshift(p, dy, dx, spmd_safe)
    return (
        zs(0, 1) + zs(0, -1) + zs(1, 0) + zs(-1, 0)
        + p * ((ey[:, None] + ex[None, :]) - 4.0)
    )


def divergence_freeslip(v: jnp.ndarray, spmd_safe: bool = False) -> jnp.ndarray:
    """Undivided central divergence with free-slip mirror walls,
    UNPADDED input [..., 2, Ny, Nx] — fused-BC equivalent of
    ``divergence(pad_vector(v, 1), 1)``. The mirrored normal component
    (ghost = -edge) adds +u at the low wall and -u at the high wall."""
    u = v[..., 0, :, :]
    w = v[..., 1, :, :]
    ny, nx = u.shape[-2], u.shape[-1]
    gx = _edge_ones(nx, v.dtype, lo=1.0, hi=-1.0)
    gy = _edge_ones(ny, v.dtype, lo=1.0, hi=-1.0)
    return (
        _zshift(u, 0, 1, spmd_safe) - _zshift(u, 0, -1, spmd_safe)
        + u * gx[None, :]
        + _zshift(w, 1, 0, spmd_safe) - _zshift(w, -1, 0, spmd_safe)
        + w * gy[:, None]
    )


def divergence_rhs_fused(v, udef, chi, h, dt, spmd_safe: bool = False):
    """Fused-BC pressure RHS: (h/2dt)[div(u*) - chi div(u_def)], all
    inputs unpadded — replaces divergence_rhs(pad_vector(v,1), ...)."""
    fac = 0.5 * h / dt
    return (fac * divergence_freeslip(v, spmd_safe)
            - (fac * chi) * divergence_freeslip(udef, spmd_safe))


def pressure_gradient_update_fused(p: jnp.ndarray, h, dt,
                                   spmd_safe: bool = False) -> jnp.ndarray:
    """Fused-BC equivalent of
    ``pressure_gradient_update(pad_scalar(p, 1), 1, h, dt)``: undivided
    central gradient with Neumann ghosts (ghost = edge ⇒ the one-sided
    difference p[1]-p[0] at the low wall, p[n-1]-p[n-2] at the high)."""
    ny, nx = p.shape[-2], p.shape[-1]
    gx = _edge_ones(nx, p.dtype, lo=-1.0, hi=1.0)
    gy = _edge_ones(ny, p.dtype, lo=-1.0, hi=1.0)
    pfac = -0.5 * dt * h
    zs = lambda dy, dx: _zshift(p, dy, dx, spmd_safe)
    dpx = (zs(0, 1) - zs(0, -1)) + p * gx[None, :]
    dpy = (zs(1, 0) - zs(-1, 0)) + p * gy[:, None]
    return pfac * jnp.stack([dpx, dpy], axis=-3)


# ---------------------------------------------------------------------------
# Per-face generalizations of the fused-BC forms (ISSUE 12, bc.py).
# Same zero-ghost-shift + rank-1 edge-correction construction; only the
# edge coefficients become per-face parameters. The legacy functions
# above are UNTOUCHED and remain the free-slip/Neumann fast path — the
# default BCTable dispatches to them verbatim (bit-identity contract).
# Signs/coefficients are derived once per table by bc.pressure_signs /
# bc.divergence_coeffs; they are Python floats, so each table traces
# its own executable (tables are static per driver).
#
# Periodic directions (ISSUE 20): the per-axis flags ``px`` / ``py``
# (bc.periodic_axes) switch the shifts ALONG that axis to wrap (roll)
# shifts, and the corresponding edge signs/coefficients come in as 0
# (bc.pressure_signs / bc.divergence_coeffs) — no edge correction, the
# wrapped interior cell IS the neighbor. Every shift here is
# axis-aligned, so the wrap/zero choice is per shift, not per array.
# ---------------------------------------------------------------------------

def _shift_bc(p: jnp.ndarray, dy: int, dx: int, px: bool, py: bool,
              spmd_safe: bool = False) -> jnp.ndarray:
    """Axis-aligned unit shift honoring periodic axes: wrap (roll)
    along a periodic axis, zero-ghost (_zshift) otherwise. roll lowers
    to two slices + a concatenate — GSPMD shards it correctly (it is
    the same pattern as the spmd_safe slice-then-pad form), so no
    sharded variant is needed."""
    if (dx != 0 and px) or (dy != 0 and py):
        return jnp.roll(p, shift=(-dy, -dx), axis=(-2, -1))
    return _zshift(p, dy, dx, spmd_safe)


def laplacian5_bc(p: jnp.ndarray, sx_lo: float, sx_hi: float,
                  sy_lo: float, sy_hi: float,
                  spmd_safe: bool = False,
                  px: bool = False, py: bool = False) -> jnp.ndarray:
    """Undivided 5-point Laplacian with per-face pressure-ghost signs
    (+1 Neumann ghost = edge, -1 Dirichlet ghost = -edge, 0 periodic —
    with the matching wrap shift via ``px``/``py``). All-(+1)
    reproduces ``laplacian5_neumann``. The wall diagonal becomes
    -4 + sum(adjacent face signs) in [-6, -2] — never 0, so the
    Jacobi smoother diagonal stays invertible at every level
    (periodic rows keep the full interior -4 diagonal)."""
    ny, nx = p.shape[-2], p.shape[-1]
    ex = _edge_ones(nx, p.dtype, lo=sx_lo, hi=sx_hi)
    ey = _edge_ones(ny, p.dtype, lo=sy_lo, hi=sy_hi)
    zs = lambda dy, dx: _shift_bc(p, dy, dx, px, py, spmd_safe)
    return (
        zs(0, 1) + zs(0, -1) + zs(1, 0) + zs(-1, 0)
        + p * ((ey[:, None] + ex[None, :]) - 4.0)
    )


def divergence_bc(v: jnp.ndarray, cx_lo: float, cx_hi: float,
                  cy_lo: float, cy_hi: float,
                  spmd_safe: bool = False,
                  px: bool = False, py: bool = False) -> jnp.ndarray:
    """Undivided central divergence with per-face edge coefficients on
    the wall-NORMAL component (bc.divergence_coeffs): mirror and
    2*uw-edge ghosts keep the free-slip (+1 lo, -1 hi) pattern,
    extrapolated outflow ghosts (ghost = edge) flip it, periodic wraps
    (coefficient 0, roll shift). Prescribed nonzero wall-normal
    velocities additionally contribute the state-independent
    bc.divergence_affine_bc constant — added by the caller, NOT here,
    so this stays linear in ``v`` (the fused RHS applies it once, not
    per div() call)."""
    u = v[..., 0, :, :]
    w = v[..., 1, :, :]
    ny, nx = u.shape[-2], u.shape[-1]
    gx = _edge_ones(nx, v.dtype, lo=cx_lo, hi=cx_hi)
    gy = _edge_ones(ny, v.dtype, lo=cy_lo, hi=cy_hi)
    su = lambda dy, dx: _shift_bc(u, dy, dx, px, py, spmd_safe)
    sw = lambda dy, dx: _shift_bc(w, dy, dx, px, py, spmd_safe)
    return (
        su(0, 1) - su(0, -1) + u * gx[None, :]
        + sw(1, 0) - sw(-1, 0) + w * gy[:, None]
    )


def pressure_gradient_update_bc(p: jnp.ndarray, h, dt,
                                sx_lo: float, sx_hi: float,
                                sy_lo: float, sy_hi: float,
                                spmd_safe: bool = False,
                                px: bool = False,
                                py: bool = False) -> jnp.ndarray:
    """Per-face-sign generalization of
    ``pressure_gradient_update_fused``: the undivided central gradient's
    edge coefficient is -s at the low wall and +s at the high wall
    (Neumann s=+1 reproduces the legacy (-1, +1) one-sided form;
    Dirichlet s=-1 differences against the reflected ghost -edge;
    periodic s=0 differences against the wrapped neighbor)."""
    ny, nx = p.shape[-2], p.shape[-1]
    gx = _edge_ones(nx, p.dtype, lo=-sx_lo, hi=sx_hi)
    gy = _edge_ones(ny, p.dtype, lo=-sy_lo, hi=sy_hi)
    pfac = -0.5 * dt * h
    zs = lambda dy, dx: _shift_bc(p, dy, dx, px, py, spmd_safe)
    dpx = (zs(0, 1) - zs(0, -1)) + p * gx[None, :]
    dpy = (zs(1, 0) - zs(-1, 0)) + p * gy[:, None]
    return pfac * jnp.stack([dpx, dpy], axis=-3)


# ---------------------------------------------------------------------------
# Vorticity (KernelVorticity, main.cpp:3343-3366)
# ---------------------------------------------------------------------------

def vorticity(vlab: jnp.ndarray, g: int, h):
    """omega = dv/dx - du/dy, central differences. vlab: [..., 2, Ny+2g, Nx+2g]."""
    assert g >= 1
    i2h = 0.5 / h
    du_dy = shift(vlab, g, 1, 0)[..., 0, :, :] - shift(vlab, g, -1, 0)[..., 0, :, :]
    dv_dx = shift(vlab, g, 0, 1)[..., 1, :, :] - shift(vlab, g, 0, -1)[..., 1, :, :]
    return i2h * (dv_dx - du_dy)


# ---------------------------------------------------------------------------
# Pressure RHS (pressure_rhs, main.cpp:6105-6139): block-scaled divergence
#   tmp = (h / 2 dt) * [ div(u*) - chi * div(u_def) ]   (undivided central)
# ---------------------------------------------------------------------------

def divergence(vlab: jnp.ndarray, g: int):
    """Undivided central divergence of a vector lab
    [..., 2, Ny+2g, Nx+2g] -> [..., Ny, Nx]."""
    assert g >= 1
    return (
        shift(vlab, g, 0, 1)[..., 0, :, :] - shift(vlab, g, 0, -1)[..., 0, :, :]
        + shift(vlab, g, 1, 0)[..., 1, :, :] - shift(vlab, g, -1, 0)[..., 1, :, :]
    )


def divergence_rhs(vlab: jnp.ndarray, ulab: jnp.ndarray, chi: jnp.ndarray,
                   g: int, h, dt):
    """vlab: velocity lab [..., 2, Ny+2g, Nx+2g]; ulab: u_def lab (same
    shape); chi: interior [..., Ny, Nx]. Returns h^2-scaled Poisson RHS."""
    assert g >= 1
    fac = 0.5 * h / dt
    return fac * divergence(vlab, g) - fac * chi * divergence(ulab, g)


# ---------------------------------------------------------------------------
# 5-point undivided Laplacian (pressure_rhs1 main.cpp:6209-6230 subtracts it;
# the Poisson operator itself uses the same stencil)
# ---------------------------------------------------------------------------

def laplacian5(plab: jnp.ndarray, g: int):
    """Undivided 5-point Laplacian of a scalar lab [..., Ny+2g, Nx+2g]."""
    assert g >= 1
    return (
        shift(plab, g, 0, 1) + shift(plab, g, 0, -1)
        + shift(plab, g, 1, 0) + shift(plab, g, -1, 0)
        - 4.0 * shift(plab, g, 0, 0)
    )


# ---------------------------------------------------------------------------
# Pressure correction (pressureCorrectionKernel, main.cpp:6021-6043):
#   dU = -(dt h / 2) * grad p  (undivided central), applied as u += dU / h^2
# ---------------------------------------------------------------------------

def pressure_gradient_update(plab: jnp.ndarray, g: int, h, dt):
    """Returns the h^2-scaled velocity increment [..., 2, Ny, Nx] from a
    pressure lab [..., Ny+2g, Nx+2g]."""
    assert g >= 1
    pfac = -0.5 * dt * h
    dpx = shift(plab, g, 0, 1) - shift(plab, g, 0, -1)
    dpy = shift(plab, g, 1, 0) - shift(plab, g, -1, 0)
    return pfac * jnp.stack([dpx, dpy], axis=-3)
