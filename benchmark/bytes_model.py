"""Compulsory HBM traffic of one time step of a uniform box, from the
grid's shape and the Poisson iteration count alone.

"Compulsory" = the bytes the ALGORITHM has to move if every field it
needs is read from HBM once and every field it produces is written
once per stage — no implementation's pass count, no ghost-pad copies,
no multigrid hierarchy, no snapshot. A fused tier that really reaches
one read and one write per stage reads 100 % against this; the XLA
chain, which moves several times as much, is still read against the
same work. With N = Ny*Nx cells of B bytes:

advection-diffusion, each of the 2 Heun substages
    read u (2N) and u_old (2N), write u (2N)                      6N
projection
    right-hand side: read u* (2N), p_old (N), write b (N)         4N
    per Krylov iteration of BiCGSTAB on the 5-point operator: two
    operator applications (read N, write N each: 4N) and the
    vector updates of p, s, r, x (each read and written once per
    iteration, r and s twice: 12N)                               16N
    correction: read dp (N), p_old (N), u* (2N); write p, u (3N)  7N
diagnostics (umax, energy, finite): the correction's output is still
    on chip in a fused epilogue                                    0

    bytes = B * N * (2*6 + 4 + 7 + 16*iters) = B * N * (23 + 16*iters)

The preconditioner (multigrid cycles) adds no compulsory term: a
solver that converges in the same iterations without one would move
these bytes. ``iters`` is the MEASURED mean ``poisson_iters`` of the
window, so a solver that needs fewer iterations is not read as slower
kernels. The bound named is HBM bandwidth; the VPU's f32 rate is not a
published peak and gets no share.
"""

from __future__ import annotations

FIXED_FIELDS = 2 * 6 + 4 + 7
FIELDS_PER_ITERATION = 16


def step_bytes(ny: int, nx: int, iters: float, itemsize: int = 4) -> float:
    return float(itemsize) * ny * nx * (
        FIXED_FIELDS + FIELDS_PER_ITERATION * float(iters))
