"""Quantitative physics validation: channel flow past a fixed cylinder.

The true inflow-outflow configuration (cases.py ``channel``: Dirichlet
inflow at x_lo, convective outflow at x_hi, free-slip side walls) that
the towed-cylinder case (validation/cylinder.py) only reaches by
Galilean transformation. The body is FIXED and the stream flows past
it — the stream is sustained by the boundary table, which the closed
free-slip box cannot do.

    python -m validation.channel drag      # Re=40 steady drag
    python -m validation.channel strouhal  # Re=200 shedding, ~30+ min

Published references, same as the towed twin: Cd(Re=40) ~ 1.5-1.6
unbounded (Tritton 1959); St(Re=200) ~ 0.19-0.20 (Williamson 1989).
The acceptance bar (ISSUE 12) is St within 5% of the literature band.
"""

from __future__ import annotations

import io
import sys
import time

import numpy as np


def _build(re, level, u_in=0.2, diameter=0.1, xpos=1.0,
           forces_every=4):
    from cup2d_tpu.cache import enable_compilation_cache
    from cup2d_tpu.cases import make_sim

    enable_compilation_cache()
    sim = make_sim("channel", level=level, re=re, u_in=u_in,
                   diameter=diameter, xpos=xpos)
    sim.compute_forces_every = forces_every
    sim.force_log = io.StringIO()
    sim.initialize()
    return sim


def _force_table(sim):
    rows = sim.force_log.getvalue().strip().splitlines()
    return np.array([[float(c) for c in row.split(",")] for row in rows])


def drag(level: int = 5, t_end: float = 30.0):
    """Re = 40: steady drag on the fixed cylinder from the
    surface-traction diagnostics, averaged after the impulsive-start
    transient washes out (one flow-through is extent/u_in = 20)."""
    D, U = 0.1, 0.2
    sim = _build(re=40.0, level=level, u_in=U, diameter=D,
                 forces_every=5)
    t0 = time.perf_counter()
    while sim.time < t_end:
        sim.step_once()
    data = _force_table(sim)
    t, fx = data[:, 0], data[:, 4]
    m = t > 0.7 * t_end
    cd = float(np.mean(fx[m]) / (0.5 * U * U * D))
    print(f"steps={sim.step_count} wall={time.perf_counter()-t0:.0f}s "
          f"Cd={cd:.3f}  (lit unbounded 1.5-1.6; ~10% blockage here)")
    return cd


def strouhal(level: int = 5, t_end: float = 45.0):
    """Re = 200: vortex-shedding frequency from the lift oscillation
    on the fixed cylinder. A small transverse kick just downstream
    breaks symmetry so shedding saturates early; the FFT window skips
    the impulsive-start transient."""
    import jax.numpy as jnp

    D, U, xpos = 0.1, 0.2, 1.0
    sim = _build(re=200.0, level=level, u_in=U, diameter=D, xpos=xpos)
    x, y = sim.grid.cell_centers()
    r2 = ((x - (xpos + 1.2 * D)) ** 2
          + (y - (0.5 + 0.3 * D)) ** 2) / (0.5 * D) ** 2
    vel = np.array(sim.state.vel)   # copy: device views are read-only
    vel[1] += (0.04 * np.exp(-r2)).astype(vel.dtype)
    sim.state = sim.state._replace(
        vel=jnp.asarray(vel, sim.grid.dtype))
    t0 = time.perf_counter()
    while sim.time < t_end:
        sim.step_once()
    data = _force_table(sim)
    t, fy = data[:, 0], data[:, 5]
    m = t > 0.45 * t_end
    fy_w = fy[m] - fy[m].mean()
    dtm = float(np.median(np.diff(t[m])))
    freqs = np.fft.rfftfreq(len(fy_w), dtm)
    amp = np.abs(np.fft.rfft(fy_w * np.hanning(len(fy_w))))
    fpk = float(freqs[1 + np.argmax(amp[1:])])
    st = fpk * D / U
    print(f"steps={sim.step_count} wall={time.perf_counter()-t0:.0f}s "
          f"lift_rms={float(fy_w.std()):.2e} f={fpk:.4f} "
          f"St={st:.4f}  (lit 0.19-0.20, bar: within 5%)")
    return st


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    which = args[0] if args else "drag"
    if which == "drag":
        drag()
    elif which == "strouhal":
        strouhal()
    else:
        print("usage: python -m validation.channel [drag|strouhal]",
              file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
