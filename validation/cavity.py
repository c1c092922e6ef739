"""Quantitative physics validation: lid-driven cavity vs Ghia et al.

The canonical wall-bounded benchmark the free-slip-only box could not
express before the BC engine (cup2d_tpu/bc.py): unit box, four no-slip
walls, the top lid translating at U=1, Re = U L / nu = 100. At steady
state the centerline velocity profiles are tabulated to three decimals
in Ghia, Ghia & Shin (J. Comput. Phys. 48, 1982, Table I/II, 129x129
multigrid) — the standard quantitative anchor for incompressible
solvers.

    python -m validation.cavity          # Re=100 at 128^2, ~minutes

Passes when both centerline profiles match Ghia to within 2% of the
lid speed (the acceptance bar in ISSUE 12).
"""

from __future__ import annotations

import sys
import time

import numpy as np

# Ghia, Ghia & Shin (1982), Re=100: u along the vertical centerline
# x = 0.5 (Table I) and v along the horizontal centerline y = 0.5
# (Table II), both on the 129x129 grid, endpoints included.
GHIA_Y = np.array([
    0.0000, 0.0547, 0.0625, 0.0703, 0.1016, 0.1719, 0.2813, 0.4531,
    0.5000, 0.6172, 0.7344, 0.8516, 0.9531, 0.9609, 0.9688, 0.9766,
    1.0000])
GHIA_U = np.array([
    0.00000, -0.03717, -0.04192, -0.04775, -0.06434, -0.10150,
    -0.15662, -0.21090, -0.20581, -0.13641, 0.00332, 0.23151,
    0.68717, 0.73722, 0.78871, 0.84123, 1.00000])
GHIA_X = np.array([
    0.0000, 0.0625, 0.0703, 0.0781, 0.0938, 0.1563, 0.2266, 0.2344,
    0.5000, 0.8047, 0.8594, 0.9063, 0.9453, 0.9531, 0.9609, 0.9688,
    1.0000])
GHIA_V = np.array([
    0.00000, 0.09233, 0.10091, 0.10890, 0.12317, 0.16077, 0.17507,
    0.17527, 0.05454, -0.24533, -0.22445, -0.16914, -0.10313,
    -0.08864, -0.07391, -0.05906, 0.00000])


def centerline_profiles(sim):
    """(y, u(x=0.5)) and (x, v(y=0.5)) with the wall/lid boundary
    values appended, from the cell-centered state. The centerlines sit
    on cell faces, so each profile averages the two adjacent center
    columns/rows."""
    grid = sim.grid
    vel = np.asarray(sim.state.vel)
    ny, nx = grid.ny, grid.nx
    h = grid.h
    bc = grid.bc

    yc = (np.arange(ny) + 0.5) * h
    xc = (np.arange(nx) + 0.5) * h
    u_mid = 0.5 * (vel[0][:, nx // 2 - 1] + vel[0][:, nx // 2])
    v_mid = 0.5 * (vel[1][ny // 2 - 1, :] + vel[1][ny // 2, :])

    lid_u = bc.y_hi.u_wall[0]
    y = np.concatenate([[0.0], yc, [ny * h]])
    u = np.concatenate([[0.0], u_mid, [lid_u]])
    x = np.concatenate([[0.0], xc, [nx * h]])
    v = np.concatenate([[0.0], v_mid, [0.0]])
    return (y, u), (x, v)


def run(level: int = 4, re: float = 100.0, t_end: float = 30.0,
        dtype: str = "float32", quiet: bool = False):
    """Run the cavity case to quasi-steady state and compare both
    centerline profiles against Ghia. Returns (err_u, err_v), each the
    max deviation normalized by the lid speed."""
    from cup2d_tpu.cache import enable_compilation_cache
    from cup2d_tpu.cases import make_sim

    enable_compilation_cache()
    sim = make_sim("cavity", level=level, re=re, dtype=dtype)
    t0 = time.perf_counter()
    while sim.time < t_end:
        sim.step_once()
    (y, u), (x, v) = centerline_profiles(sim)
    err_u = float(np.max(np.abs(np.interp(GHIA_Y, y, u) - GHIA_U)))
    err_v = float(np.max(np.abs(np.interp(GHIA_X, x, v) - GHIA_V)))
    if not quiet:
        n = sim.grid.nx
        print(f"cavity Re={re:g} {n}x{n} steps={sim.step_count} "
              f"wall={time.perf_counter() - t0:.0f}s  "
              f"max|u-Ghia|={err_u:.4f} max|v-Ghia|={err_v:.4f} "
              f"(bar: 0.02 of lid speed)")
    return err_u, err_v


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    level = int(args[0]) if args else 4
    err_u, err_v = run(level=level)
    ok = err_u <= 0.02 and err_v <= 0.02
    print("PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
