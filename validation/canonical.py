"""The ONE definition of the canonical two-fish case for validation
tooling (run.sh, /root/reference/run.sh:1-22). golden, scale_proof,
init_compiles and chip_smoke.py all measure/pin THIS case — a flag drifting in one copy
would silently make them describe different physics (ADVICE r3)."""

from __future__ import annotations


def canonical_flags(levelmax: int = 8, levelstart: int = 5,
                    adapt_steps: int = 20, dtype: str = "float32",
                    rtol: float = 2.0, ctol: float = 1.0):
    flags = (
        "-AdaptSteps {a} -bpdx 2 -bpdy 1 -CFL 0.5 -Ctol {ct} -extent 4 "
        "-lambda 1e7 -levelMax {lm} -levelStart {ls} "
        "-maxPoissonIterations 1000 -maxPoissonRestarts 0 -nu 0.00004 "
        "-poissonTol 1e-3 -poissonTolRel 1e-2 -Rtol {rt} -tdump 0 "
        "-tend 10.0 -dtype {dt}"
    ).format(a=adapt_steps, lm=levelmax, ls=levelstart, dt=dtype,
             rt=rtol, ct=ctol).split()
    return flags + [
        "-shapes",
        "angle=0 L=0.2 xpos=1.8 ypos=0.8\n"
        "angle=180 L=0.2 xpos=1.6 ypos=0.8",
    ]


def build_canonical_sim(levelmax: int = 8, levelstart: int = 5,
                        adapt_steps: int = 20, dtype: str = "float32",
                        rtol: float = 2.0, ctol: float = 1.0):
    from cup2d_tpu.amr import AMRSim
    from cup2d_tpu.config import SimConfig
    from cup2d_tpu.sim import make_shapes

    cfg = SimConfig.from_argv(
        canonical_flags(levelmax, levelstart, adapt_steps, dtype,
                        rtol, ctol))
    sim = AMRSim(cfg, shapes=make_shapes(cfg))
    sim.compute_forces_every = 0
    return sim
