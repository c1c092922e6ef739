"""Scale-proof: drive the forest into the >=1e4-active-block regime and
record per-phase costs (VERDICT r2 #4).

The fully developed run.sh case lives at 1e4-1e5 blocks (SURVEY §6);
round 2 only ever measured ~500. Two modes:

* default: the organic two-fish levelMax-8 case with an aggressive
  refinement threshold (--rtol/--ctol override), stopping at --target
  blocks. Measured round 3: block growth is smooth but slow (~1k blocks
  after 300 steps) — wakes need thousands of steps to demand 1e4.
* --synthetic: dense start — uniform levelStart-6 grid (8,192 blocks)
  + strong seeded vortices refining past 1e4 immediately. This is the
  mode that produced the 1e4-regime table; the machinery
  whose scaling is in question (halo-table rebuild, regrid commit,
  pad-bucket growth, step at 16k-pad) doesn't care where blocks came
  from. Compression is disabled there: --ctol is rejected, --target
  is ignored (the run holds the regime for --max-steps).

Prints one JSON line per sampled step plus a final summary.

    python -m validation.scale_proof [--target 10000] [--rtol 0.05]
    python -m validation.scale_proof --synthetic [--rtol 0.1] \
        [--max-steps 30]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _synthetic_sim(args):
    """Obstacle-free canonical-domain forest that STARTS in the 1e4
    regime: uniform levelStart-6 grid (8,192 blocks) seeded with strong
    vortices whose tags refine past the target. The organic two-fish
    wake needs thousands of steps to demand this many blocks; the
    machinery whose scaling VERDICT r2 #4 questions (table rebuild,
    regrid commit, megastep at 16k-pad, bucket crossings) doesn't care
    where the blocks came from. Compression is disabled (ctol < 0) so
    the measured topology stays in-regime."""
    import jax.numpy as jnp

    from cup2d_tpu.amr import AMRSim
    from cup2d_tpu.config import SimConfig

    cfg = SimConfig(bpdx=2, bpdy=1, level_max=args.levelmax,
                    level_start=6, extent=4.0, dtype="float32",
                    nu=4e-5, cfl=0.5, rtol=args.rtol, ctol=-1.0,
                    poisson_tol=1e-3, poisson_tol_rel=1e-2,
                    max_poisson_iterations=1000, adapt_steps=5)
    sim = AMRSim(cfg, shapes=[])
    f = sim.forest
    order = f.order()
    bs = cfg.bs
    rng = np.random.default_rng(7)
    centers = rng.uniform([0.5, 0.3], [3.5, 1.7], size=(8, 2))
    h = cfg.h0 / (1 << f.level[order]).astype(np.float64)
    x0 = f.bi[order].astype(np.float64) * bs * h
    y0 = f.bj[order].astype(np.float64) * bs * h
    ar = np.arange(bs) + 0.5
    X = np.broadcast_to(
        x0[:, None, None] + ar[None, None, :] * h[:, None, None],
        (len(order), bs, bs))
    Y = np.broadcast_to(
        y0[:, None, None] + ar[None, :, None] * h[:, None, None],
        (len(order), bs, bs))
    u = np.zeros(X.shape)
    v = np.zeros(X.shape)
    for cx, cy in centers:
        dx, dy = X - cx, Y - cy
        r2 = dx * dx + dy * dy
        ut = 0.8 / (2 * np.pi * np.sqrt(r2 + 1e-8)) \
            * (1 - np.exp(-r2 / (2 * 0.03 ** 2)))
        th = np.arctan2(dy, dx)
        u += -ut * np.sin(th)
        v += ut * np.cos(th)
    vals = np.zeros((f.capacity, 2, bs, bs), np.float32)
    vals[order, 0] = u
    vals[order, 1] = v
    f.fields["vel"] = jnp.asarray(vals, f.dtype)
    return sim


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", type=int, default=10000)
    ap.add_argument("--rtol", type=float, default=0.05)
    ap.add_argument("--ctol", type=float, default=None)
    ap.add_argument("--max-steps", type=int, default=400)
    ap.add_argument("--levelmax", type=int, default=8)
    ap.add_argument("--synthetic", action="store_true")
    args = ap.parse_args()

    from cup2d_tpu.cache import enable_compilation_cache
    enable_compilation_cache()
    from validation.canonical import build_canonical_sim

    ctol = args.ctol if args.ctol is not None else args.rtol / 5.0
    if args.synthetic:
        if args.ctol is not None:
            ap.error("--ctol has no effect with --synthetic "
                     "(compression is disabled there)")
        sim = _synthetic_sim(args)
    else:
        sim = build_canonical_sim(levelmax=args.levelmax, rtol=args.rtol,
                                  ctol=ctol)
    t0 = time.perf_counter()
    sim.initialize()
    print(json.dumps({"phase": "init", "wall_s": round(
        time.perf_counter() - t0, 1),
        "n_blocks": len(sim.forest.blocks)}), flush=True)

    step_walls, regrid_walls, table_walls = [], [], []
    nb_hist = []
    while sim.step_count < args.max_steps and (
            args.synthetic or len(sim.forest.blocks) < args.target):
        if sim.step_count <= 10 or \
                sim.step_count % sim.cfg.adapt_steps == 0:
            t1 = time.perf_counter()
            sim.adapt()
            t2 = time.perf_counter()
            # table rebuild happens inside the NEXT _refresh; time it
            sim._refresh()
            t3 = time.perf_counter()
            regrid_walls.append(t2 - t1)
            table_walls.append(t3 - t2)
        t1 = time.perf_counter()
        sim.step_once()
        step_walls.append(time.perf_counter() - t1)
        nb_hist.append(len(sim.forest.blocks))
        if sim.step_count % 20 == 0:
            print(json.dumps({
                "step": sim.step_count, "t": round(sim.time, 4),
                "n_blocks": nb_hist[-1], "n_pad": int(sim._npad_hwm),
                "step_ms_median_last20": round(
                    float(np.median(step_walls[-20:]) * 1e3), 1),
            }), flush=True)

    w = np.asarray(step_walls[5:] or step_walls or [0.0])
    print(json.dumps({
        "phase": "summary",
        "final_blocks": len(sim.forest.blocks),
        "final_pad": int(sim._npad_hwm),
        "steps": sim.step_count,
        "step_ms_median": round(float(np.median(w) * 1e3), 1),
        "step_ms_p90": round(float(np.percentile(w, 90) * 1e3), 1),
        "regrid_s_median": round(
            float(np.median(regrid_walls)), 2) if regrid_walls else None,
        "regrid_s_max": round(
            float(np.max(regrid_walls)), 2) if regrid_walls else None,
        "tables_s_median": round(
            float(np.median(table_walls)), 2) if table_walls else None,
        "tables_s_max": round(
            float(np.max(table_walls)), 2) if table_walls else None,
    }), flush=True)


if __name__ == "__main__":
    main()
