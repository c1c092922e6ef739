"""Forest Poisson solve-path A/B: production iters/step per path.

Builds a near-uniform obstacle-free forest at a chosen block count,
seeds a multi-scale velocity field (smooth modes plus seeded noise on
the forest), and measures ONE production solve (cold deltap — the
worst-case production RHS) plus a short warm train under each solve
path:

  jacobi    block-Jacobi only (trigger off — the sub-15-iters default)
  additive  two-level additive (the round-5 production form, forced on)
  mult      two-level multiplicative (coarse first, BJ post)
  mg2       two-grid cycle: BJ pre-smooth + spectral base-level
            correction + BJ post-smooth (the CUP2D_POIS=fft form)
  fas       forest-native FAS multigrid as the FULL solver over the
            forest's own refinement levels (the CUP2D_POIS=fas form —
            iters are mg_solve CYCLES, ~half the per-unit cost of a
            preconditioned Krylov iteration)
  fas-f     same hierarchy, every solve opened base-level-first
            (CUP2D_POIS=fas-f)
  fas-bf16leg
            the memory-tiered cycle (ISSUE 19): same fas hierarchy
            with the window-image ladder legs stored bf16
            (CUP2D_PREC=bf16 + CUP2D_POIS=fas in production; pinned
            directly here like the other arms). mg_solve's outer loop
            keeps the solver-precision true residual, so the
            acceptance claim is iters within +1 of the fas arm at the
            SAME convergence criterion

Iteration counts are platform-independent (the loop is the same XLA
program everywhere), so this probe runs anywhere; ms/step numbers are
only meaningful on the production rig. Usage:

    python -m validation.poisson_ab [--bpd 8] [--steps 4] [--out F]

Prints one JSON line per path: {path, n_blocks, iters (per step),
residual, converged}; ``--out`` additionally records the arms + probe
metadata as one provenance JSON (the round-10 record at the
1e4-block probe is validation/poisson_ab_r10.json).
"""

from __future__ import annotations

import argparse
import json

import numpy as np


def _seed_multiscale(sim):
    """Seed a multi-scale divergence-bearing field, each
    active block sampled analytically at its OWN resolution."""
    import jax.numpy as jnp

    f = sim.forest
    cfg = sim.cfg
    bs = cfg.bs
    vals = np.zeros((f.capacity, 2, bs, bs))
    n1d = cfg.bpdx * bs << cfg.level_start
    m = max(n1d // 64, 8)
    for (l, i, j), s in f.blocks.items():
        h = cfg.h_at(l)
        x = (i * bs + np.arange(bs) + 0.5) * h
        y = (j * bs + np.arange(bs) + 0.5) * h
        X, Y = np.meshgrid(x, y, indexing="xy")
        xs, ys = np.pi * X, np.pi * Y
        vals[s, 0] = (np.sin(xs) * np.cos(ys)
                      + 0.25 * np.sin(8 * xs) * np.cos(8 * ys)
                      + 0.3 * np.sin(m * xs) * np.sin(m * ys))
        vals[s, 1] = (-np.cos(xs) * np.sin(ys)
                      + 0.25 * np.sin(16 * ys) * np.sin(16 * xs)
                      + 0.3 * np.sin(m * ys) * np.sin(m * xs))
    f.fields["vel"] = jnp.asarray(vals, f.dtype)


def build_forest_sim(bpd: int = 8, level_start: int = 2,
                     dtype: str = "float64", tol: float = 1e-3,
                     tol_rel: float = 1e-2):
    """Obstacle-free AMRSim on the uniform level_start grid
    (bpd*2^level_start squared blocks), regridding disabled, seeded
    with a multi-scale divergence-bearing field."""
    from cup2d_tpu.amr import AMRSim
    from cup2d_tpu.config import SimConfig

    cfg = SimConfig(bpdx=bpd, bpdy=bpd, level_max=level_start + 1,
                    level_start=level_start, extent=1.0, nu=4e-5,
                    cfl=0.5, dtype=dtype, rtol=1e9, ctol=-1.0,
                    poisson_tol=tol, poisson_tol_rel=tol_rel,
                    max_poisson_iterations=2000)
    sim = AMRSim(cfg)
    _seed_multiscale(sim)
    sim.step_count = 20          # production regime (no exact override)
    return sim


def _seed_vortex_field(sim):
    """Weak smooth background + two strong localized Gaussian vortices
    (the scale_proof synthetic-vortex recipe at small scale), each
    active block sampled analytically at its OWN resolution — the
    vorticity tagging then refines ONLY the vortex neighborhoods, so
    the resulting forest is genuinely multi-level."""
    import jax.numpy as jnp

    f = sim.forest
    cfg = sim.cfg
    bs = cfg.bs
    vals = np.zeros((f.capacity, 2, bs, bs))
    centers = [(0.31, 0.62, 0.030, 0.8), (0.68, 0.37, 0.045, -0.6)]
    for (l, i, j), s in f.blocks.items():
        h = cfg.h_at(l)
        x = (i * bs + np.arange(bs) + 0.5) * h
        y = (j * bs + np.arange(bs) + 0.5) * h
        X, Y = np.meshgrid(x, y, indexing="xy")
        xs, ys = np.pi * X, np.pi * Y
        u = 0.2 * np.sin(xs) * np.cos(ys)
        v = -0.2 * np.cos(xs) * np.sin(ys)
        for cx, cy, sg, g in centers:
            dx, dy = X - cx, Y - cy
            r2 = dx * dx + dy * dy
            ut = g / (2 * np.pi * np.sqrt(r2 + 1e-8)) \
                * (1 - np.exp(-r2 / (2 * sg ** 2)))
            th = np.arctan2(dy, dx)
            u += -ut * np.sin(th)
            v += ut * np.cos(th)
        vals[s, 0] = u
        vals[s, 1] = v
    f.fields["vel"] = jnp.asarray(vals, f.dtype)


def build_multilevel_sim(bpd: int = 4, level_start: int = 1,
                         level_max: int = 5, dtype: str = "float64",
                         tol: float = 1e-3, tol_rel: float = 1e-2,
                         rtol: float = 30.0, rounds: int = 4,
                         sim_cls=None):
    """Small MULTI-LEVEL forest for the forest-FAS arms and tier-1
    agreement tests: seed the vortex field, let the production
    vorticity tagging refine (re-seeding analytically after each
    round so fine blocks carry their own-resolution content), and
    leave the topology wherever the tagging converged — deterministic
    (same seed field + thresholds => same forest), spanning levels on
    BOTH sides of the coarse base level c (= min(3, level_max-1)).
    The A/B drivers never call adapt(), so all arms solve the
    identical forest."""
    from cup2d_tpu.amr import AMRSim
    from cup2d_tpu.config import SimConfig

    cfg = SimConfig(bpdx=bpd, bpdy=bpd, level_max=level_max,
                    level_start=level_start, extent=1.0, nu=4e-5,
                    cfl=0.5, dtype=dtype, rtol=rtol, ctol=-1.0,
                    poisson_tol=tol, poisson_tol_rel=tol_rel,
                    max_poisson_iterations=2000)
    sim = (sim_cls or AMRSim)(cfg)
    _seed_vortex_field(sim)
    for _ in range(rounds):
        if not sim.adapt():
            break
        _seed_vortex_field(sim)
    sim.step_count = 20
    return sim


def build_synthetic_sim(target: int, levelmax: int = 8):
    """The 1e4-block-regime forest (scale_proof's synthetic
    vortices on the canonical domain, levelStart 6), adapted until
    ``target`` blocks are active — the same topology class the r4/r5
    production-iteration numbers were measured on."""
    from types import SimpleNamespace

    from validation.scale_proof import _synthetic_sim

    sim = _synthetic_sim(SimpleNamespace(levelmax=levelmax, rtol=0.05))
    while len(sim.forest.blocks) < target and sim.adapt():
        pass
    sim.step_count = 20
    return sim


def run_path(path: str, bpd: int, steps: int, synthetic: int = 0,
             levelmax: int = 8, multilevel: bool = False) -> dict:
    """Fresh sim per path so no state leaks between arms."""
    if synthetic:
        sim = build_synthetic_sim(synthetic, levelmax)
    elif multilevel:
        sim = build_multilevel_sim(bpd=bpd)
    else:
        sim = build_forest_sim(bpd=bpd)
    # build tables/maps BEFORE pinning the path: _refresh_impl re-arms
    # the trigger (coarse_on = False), which would silently turn the
    # first measured solve into the jacobi arm on every path
    sim._refresh()
    if path == "jacobi":
        sim._coarse_on = False       # the trigger-off default
        use = False
    elif path in ("fas", "fas-f", "fas-bf16leg"):
        # the forest-FAS full-solve arms: pin the CUP2D_POIS latch
        # slot directly (fresh sim, first trace sees it — the same
        # post-construction pinning discipline as _twolevel_form) and
        # force-engage the hierarchy maps like _use_coarse would.
        # fas-bf16leg additionally pins the ISSUE-19 leg-dtype latch
        # (production: CUP2D_PREC=bf16 at construction)
        sim._pois_mode = "fas" if path == "fas-bf16leg" else path
        if path == "fas-bf16leg":
            import jax.numpy as jnp
            sim._fas_leg_dtype = jnp.bfloat16
        sim._coarse_on = True
        use = True
    else:
        sim._twolevel_form = path    # the latched A/B slot
        sim._coarse_on = True        # force-engage the correction
        use = True
    iters, res, conv = [], [], []
    dt = None
    for _ in range(steps):
        # keep the trigger state pinned: this is an A/B arm, the
        # sticky iters>15 trigger must not flip it mid-train. Pinning
        # _coarse_on alone is NOT enough — _use_coarse re-engages off
        # sim._last_iters (>15 after any rough step), which would
        # silently turn the jacobi arm's steps 2..N into two-level
        # measurements — so the trigger EVIDENCE is zeroed too.
        sim._coarse_on = use
        sim._last_iters = 0
        sim._last_iters_dev = None
        d = sim.step_once(dt)
        iters.append(int(d["poisson_iters"]))
        res.append(float(d["poisson_residual"]))
        conv.append(bool(d["poisson_converged"]))
    return {
        "path": path,
        "n_blocks": int(sim._n_real),
        "smoother_tier": sim.smoother_tier,
        "iters": iters,
        "residual": res,
        "converged": conv,
    }


def main():
    import jax
    jax.config.update("jax_enable_x64", True)
    ap = argparse.ArgumentParser()
    ap.add_argument("--bpd", type=int, default=8)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--paths",
                    default="jacobi,additive,mult,mg2,fas,fas-f,"
                            "fas-bf16leg")
    ap.add_argument("--synthetic", type=int, default=0,
                    help="use the 1e4-regime synthetic forest "
                         "adapted to >= this many blocks")
    ap.add_argument("--levelmax", type=int, default=8)
    ap.add_argument("--multilevel", action="store_true",
                    help="use the small multi-level forest "
                         "(build_multilevel_sim) instead of the "
                         "near-uniform one")
    ap.add_argument("--out", default="",
                    help="also record the arms + probe metadata as one "
                         "provenance JSON file")
    args = ap.parse_args()
    arms = []
    for path in args.paths.split(","):
        rec = run_path(path, args.bpd, args.steps,
                       synthetic=args.synthetic,
                       levelmax=args.levelmax,
                       multilevel=args.multilevel)
        arms.append(rec)
        print(json.dumps(rec), flush=True)
    if args.out:
        import platform
        with open(args.out, "w") as fh:
            json.dump({
                "probe": {"bpd": args.bpd, "steps": args.steps,
                          "synthetic": args.synthetic,
                          "levelmax": args.levelmax,
                          "multilevel": args.multilevel,
                          "machine": platform.machine(),
                          "backend": jax.default_backend()},
                "arms": arms,
            }, fh, indent=1)
            fh.write("\n")


if __name__ == "__main__":
    main()
