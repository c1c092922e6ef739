"""Quantitative physics validation: towed cylinder drag + Strouhal.

The reference validates by eye (SURVEY.md §4: smoke runs + rendered
dumps); these runnable cases pin the solver to published numbers
instead. Both tow a rigid disk through still fluid — the closed
free-slip box (the reference's only BC, main.cpp:3126-3256) cannot
sustain a stream, so towing is the Galilean twin of flow past a fixed
body, exactly like the reference's self-propelled fish.

    python -m validation.cylinder drag      # Re=40 steady drag, ~10 min
    python -m validation.cylinder dragwide  # same at half blockage
    python -m validation.cylinder strouhal  # Re=200 shedding, ~30 min

Published references: Cd(Re=40) ~ 1.5-1.6 unbounded (Tritton 1959);
St(Re=200) ~ 0.19-0.20 (Williamson 1989). Blockage inflates both a few
percent.
"""

from __future__ import annotations

import io
import sys
import time

import numpy as np


def _build(D, U, nu, level, xpos, forces_every, bpdy=1):
    # the case registry (cases.py) owns the config/shape recipe now;
    # this probe just adds the force-log plumbing it measures with
    from cup2d_tpu.cache import enable_compilation_cache
    from cup2d_tpu.cases import make_sim

    enable_compilation_cache()
    sim = make_sim("cylinder", D=D, U=U, nu=nu, level=level, xpos=xpos,
                   bpdy=bpdy)
    sim.compute_forces_every = forces_every
    sim.force_log = io.StringIO()
    sim.initialize()
    return sim


def _force_table(sim):
    rows = sim.force_log.getvalue().strip().splitlines()
    return np.array([[float(c) for c in row.split(",")] for row in rows])


def drag(bpdy=1):
    """Re = 40: steady drag coefficient from the surface-traction
    diagnostics, averaged over the quasi-steady window. ``bpdy=2``
    doubles the transverse extent (blockage 10% -> 5%) — the domain-size
    study that pins the blockage correction the round-2 Cd leaned on
    (VERDICT r2 weak #7)."""
    D, U, nu = 0.1, 0.2, 5e-4
    sim = _build(D, U, nu, level=5, xpos=3.2, forces_every=5,
                 bpdy=bpdy)  # 1024 x 256*bpdy
    t0 = time.perf_counter()
    while sim.time < 6.0 and sim.shapes[0].com[0] > 0.5:
        sim.step_once()
    data = _force_table(sim)
    t, fx = data[:, 0], data[:, 4]
    m = (t > 4.5)
    cd = float(np.mean(fx[m]) / (0.5 * U * U * D))
    print(f"steps={sim.step_count} wall={time.perf_counter()-t0:.0f}s "
          f"Cd={cd:.3f}  (lit unbounded 1.5-1.6; ~10% blockage here)")
    return cd


def strouhal():
    """Re = 200: vortex-shedding frequency from the lift oscillation.
    A small transverse vortical kick behind the body breaks symmetry so
    shedding saturates within the tow distance."""
    import jax.numpy as jnp

    D, U, nu = 0.05, 0.2, 5e-5
    sim = _build(D, U, nu, level=6, xpos=3.5, forces_every=4)  # 2048x512
    x, y = sim.grid.cell_centers()
    r2 = ((x - 3.56) ** 2 + (y - 0.515) ** 2) / (0.5 * D) ** 2
    vel = np.array(sim.state.vel)   # copy: device views are read-only
    vel[1] += (0.04 * np.exp(-r2)).astype(vel.dtype)
    sim.state = sim.state._replace(
        vel=jnp.asarray(vel, sim.grid.dtype))
    t0 = time.perf_counter()
    while sim.time < 15.0 and sim.shapes[0].com[0] > 0.4:
        sim.step_once()
    data = _force_table(sim)
    t, fy = data[:, 0], data[:, 5]
    m = t > 5.0
    fy_w = fy[m] - fy[m].mean()
    dtm = float(np.median(np.diff(t[m])))
    freqs = np.fft.rfftfreq(len(fy_w), dtm)
    amp = np.abs(np.fft.rfft(fy_w * np.hanning(len(fy_w))))
    fpk = float(freqs[1 + np.argmax(amp[1:])])
    st = fpk * D / U
    print(f"steps={sim.step_count} wall={time.perf_counter()-t0:.0f}s "
          f"lift_rms={float(fy_w.std()):.2e} f={fpk:.4f} "
          f"St={st:.4f}  (lit 0.19-0.20)")
    return st


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    which = args[0] if args else "drag"
    if which == "drag":
        drag()
    elif which == "dragwide":
        drag(bpdy=2)
    elif which == "strouhal":
        strouhal()
    else:
        print("usage: python -m validation.cylinder [drag|strouhal]",
              file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
