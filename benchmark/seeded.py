"""Inputs from ``--seed``: the seeded start field of a uniform box and
the seeded jitter of a ``-shapes`` string.

Both are the benchmark's own generators: the program receives only
what they make. Every seed gets the SAME set of modes (same wavenumbers,
same amplitudes, same rms) and differs only in phases. That does not
make every seed the same work: the Poisson iteration count of a step is
a threshold crossing, and the mix of 1-, 2- and 3-iteration steps moves
the step rate by +-3 % from field to field. A fixed base field with
only 2 % or 0.02 % of it drawn from the seed spread the rate just as
widely (PR 23, chip calls 3 and 4), so the spread belongs to the
solver and the seed draws the whole field.

Uniform start (``seeded_start`` group of a configuration file)::

    psi(x, y) = W(x) W(y) * sum_m  a_m sin(2 pi (kx_m x/Lx + px_m))
                                       sin(2 pi (ky_m y/Ly + py_m))
    W(s) = sin^2(pi s),  s in [0, 1]      (W = W' = 0 at both walls)
    u =  d psi / dy,   v = -d psi / dx    (analytic derivatives)

so the field is solenoidal, smooth, and vanishes with the wall: it is
compatible with four no-slip walls (the lid then starts impulsively).
It is scaled to ``rms`` = sqrt(mean(u^2 + v^2)). Why not rest: a box
started from rest spends its first hundreds of steps on a thin layer
under the lid and times the Poisson solver near 0 iterations (the one
sound idea of the old ``bench.bench_state``).
"""

from __future__ import annotations

import math

import numpy as np

_MASK32 = (1 << 32) - 1


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator from any whole-number seed (also above 2**31)."""
    s = int(seed)
    return np.random.default_rng(
        [s & _MASK32, (s >> 32) & _MASK32, int(stream)])


def mode_table(spec: dict, seed: int) -> np.ndarray:
    """[M, 5] rows (kx, ky, amplitude, phase_x, phase_y): every
    wavenumber pair of ``spec['wavenumbers']``^2, amplitude 1/|k|,
    phases uniform in [0, 1) from the seed."""
    ks = [int(k) for k in spec["wavenumbers"]]
    g = rng(seed, 1)
    rows = [(kx, ky, 1.0 / math.hypot(kx, ky), g.random(), g.random())
            for kx in ks for ky in ks]
    return np.asarray(rows, dtype=np.float64)


def seeded_velocity(modes, ny: int, nx: int, extents, rms: float, dtype):
    """[2, ny, nx] device array, one jitted call (the field is built on
    the device; only the few mode numbers come from the host)."""
    import jax
    import jax.numpy as jnp

    lx, ly = float(extents[0]), float(extents[1])
    two_pi = 2.0 * math.pi

    def build(m):
        x = ((jnp.arange(nx, dtype=jnp.float32) + 0.5) / nx)[None, :]
        y = ((jnp.arange(ny, dtype=jnp.float32) + 0.5) / ny)[:, None]
        wx, wy = jnp.sin(math.pi * x) ** 2, jnp.sin(math.pi * y) ** 2
        dwx = math.pi * jnp.sin(two_pi * x) / lx      # dW/dx (physical)
        dwy = math.pi * jnp.sin(two_pi * y) / ly
        s = jnp.zeros((ny, nx), jnp.float32)
        sx = jnp.zeros((ny, nx), jnp.float32)
        sy = jnp.zeros((ny, nx), jnp.float32)
        for i in range(m.shape[0]):
            kx, ky, a, px, py = (m[i, j] for j in range(5))
            ax, ay = two_pi * (kx * x + px), two_pi * (ky * y + py)
            s = s + a * jnp.sin(ax) * jnp.sin(ay)
            sx = sx + a * (two_pi * kx / lx) * jnp.cos(ax) * jnp.sin(ay)
            sy = sy + a * (two_pi * ky / ly) * jnp.sin(ax) * jnp.cos(ay)
        u = wx * (dwy * s + wy * sy)
        v = -wy * (dwx * s + wx * sx)
        vel = jnp.stack([u, v])
        scale = rms / jnp.sqrt(jnp.mean(u * u + v * v))
        return (vel * scale).astype(dtype)

    return jax.jit(build)(jnp.asarray(modes, jnp.float32))


def start_velocity(config: dict, seed: int):
    """The seeded start field of a uniform configuration, from its
    file alone (``grid`` and ``seeded_start`` groups)."""
    g, start = config["grid"], config["seeded_start"]
    ny, nx = int(g["ny"]), int(g["nx"])
    h = float(g["extent"]) / max(ny, nx)
    return seeded_velocity(mode_table(start, seed), ny, nx,
                           (nx * h, ny * h), float(start["rms"]),
                           config["physics"]["dtype"])


def jittered_shapes(shapes: list, jitter: dict, seed: int) -> str:
    """The ``-shapes`` string of a flags-driven configuration: each
    shape line ``key=value ...`` with the keys named in ``jitter``
    moved by a uniform draw in [-j, +j] from the seed."""
    g = rng(seed, 2)
    lines = []
    for shape in shapes:
        toks = []
        for key, val in shape.items():
            if key in jitter:
                val = float(val) + float(jitter[key]) * (2.0 * g.random() - 1.0)
                toks.append(f"{key}={val:.9g}")
            else:
                toks.append(f"{key}={val:g}" if isinstance(val, float)
                            else f"{key}={val}")
        lines.append(" ".join(toks))
    return "\n".join(lines)
