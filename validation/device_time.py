"""Device-time probe of the canonical adaptive case.

A driver step's wall time is one megastep dispatch plus one scalar
pull — two host syncs that can swamp device compute on a small
forest. This probe separates the two: after warming the canonical two-fish levelMax-8 case, it re-dispatches
the megastep N times back-to-back with the velocity/pressure outputs
chained into the next call's inputs (raster windows, dt and shape
kinematics frozen — legal: all block-level work including the Poisson
while_loop still runs), fencing ONCE at the end. Wall/N then bounds the
true device time per step; the same chain fenced per-call gives the
sync-bound number for contrast.

    python -m validation.device_time [--steps 60] [--chain 20]

Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _fence(x) -> float:
    return float(x.reshape(-1)[0])


def _probe_scale_step(sim, args):
    """Chained OBSTACLE-FREE step probe for the synthetic >=1e4-block
    forest (the adaptive device time at the reference's own scale,
    as opposed to the scale proof's host wall time). Freezes dt and chains _step_jit with outputs fed
    back, fencing once; optional profiler trace parsed at op level."""
    import jax.numpy as jnp

    cfg = sim.cfg
    f = sim.forest
    sim._refresh()
    ordf = sim._ordered_state()
    dt = jnp.asarray(1e-4, f.dtype)

    def make_step(tcoarse):
        def step(vel, pres):
            return sim._step_jit(
                vel, pres, dt, sim._h, sim._hsq_flat, sim._maskv,
                sim._tables["vec3"], sim._tables["vec1"],
                sim._tables["sca1"], sim._tables["pois"],
                sim._corr, tcoarse, exact_poisson=False)
        return step

    def chain_time(step, vel, pres):
        out = step(vel, pres)
        _fence(out[0])
        lat = []
        for _ in range(3):
            t0 = time.perf_counter()
            _fence(out[0])
            lat.append(time.perf_counter() - t0)
        lat_floor = min(lat)
        best = None
        for _ in range(3):
            v, p = vel, pres
            t0 = time.perf_counter()
            for _ in range(args.chain):
                v, p, _ = step(v, p)
            _fence(v)
            w = time.perf_counter() - t0 - lat_floor
            best = w if best is None else min(best, w)
        it = int(jax.device_get(step(vel, pres)[2]["poisson_iters"]))
        return best / args.chain * 1e3, lat_floor, it

    vel, pres = ordf["vel"], ordf["pres"]
    # A: plain block-Jacobi (what the r3 builds ran in production)
    dev_ms, lat_floor, iters_plain = chain_time(
        make_step(None), vel, pres)
    # B: the production two-level trigger engaged (iters>15 policy)
    if sim._coarse_cw is None:
        sim._build_coarse_maps(sim._npad_hwm, sim._n_real)
    dev_ms_coarse, _, iters_coarse = chain_time(
        make_step(sim._coarse_cw), vel, pres)

    if args.trace_dir:
        step = make_step(sim._coarse_cw)
        with jax.profiler.trace(args.trace_dir):
            v, p = vel, pres
            for _ in range(args.chain):
                v, p, _ = step(v, p)
            _fence(v)
    return (dev_ms, iters_plain, dev_ms_coarse, iters_coarse,
            lat_floor)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60,
                    help="normal warm-up steps before probing")
    ap.add_argument("--chain", type=int, default=20)
    ap.add_argument("--levelmax", type=int, default=8)
    ap.add_argument("--synthetic-scale", type=int, default=0,
                    help="probe the obstacle-free synthetic forest "
                         "grown to >= this many blocks instead of the "
                         "canonical two-fish case")
    ap.add_argument("--trace-dir", default=None,
                    help="also capture a profiler trace of the chain "
                         "(parse with validation.trace_ops "
                         "--parse-only)")
    args = ap.parse_args()

    from cup2d_tpu.cache import enable_compilation_cache
    enable_compilation_cache()

    if args.synthetic_scale:
        from types import SimpleNamespace

        from validation.scale_proof import _synthetic_sim

        sim = _synthetic_sim(SimpleNamespace(
            levelmax=args.levelmax, rtol=0.1))
        cfg = sim.cfg
        t0 = time.perf_counter()
        grow_steps = 0
        while len(sim.forest.blocks) < args.synthetic_scale \
                and grow_steps < 40:
            sim.adapt()
            sim.step_once()
            grow_steps += 1
        t_init = time.perf_counter() - t0
        n_blocks = len(sim.forest.blocks)
        (dev_ms, iters_plain, dev_ms_coarse, iters_coarse,
         lat_floor) = _probe_scale_step(sim, args)
        cells = n_blocks * cfg.bs * cfg.bs
        print(json.dumps({
            "case": f"synthetic vortices levelMax={args.levelmax}, "
                    f">= {args.synthetic_scale} blocks",
            "backend": jax.default_backend(),
            "n_blocks": n_blocks,
            "n_pad": int(sim._npad_hwm),
            "grow_s": round(t_init, 1),
            "device_ms_per_step_blockjacobi": round(dev_ms, 2),
            "poisson_iters_blockjacobi": iters_plain,
            "device_ms_per_step_twolevel": round(dev_ms_coarse, 2),
            "poisson_iters_twolevel": iters_coarse,
            "latency_floor_ms": round(lat_floor * 1e3, 1),
            "cells_steps_per_sec_device": round(
                cells / (min(dev_ms, dev_ms_coarse) / 1e3)),
            "trace_dir": args.trace_dir,
        }))
        sys.stdout.flush()
        return

    from validation.canonical import build_canonical_sim

    sim = build_canonical_sim(levelmax=args.levelmax)
    cfg = sim.cfg

    t0 = time.perf_counter()
    sim.initialize()
    t_init = time.perf_counter() - t0

    # warm run: real driver loop (regrids + megasteps), median wall/step
    walls = []
    for k in range(args.steps):
        if sim.step_count <= 10 or sim.step_count % cfg.adapt_steps == 0:
            sim.adapt()
        t0 = time.perf_counter()
        sim.step_once()
        walls.append(time.perf_counter() - t0)
    n_blocks = len(sim.forest.blocks)
    warm_ms = float(np.median(walls[min(10, len(walls) // 2):]) * 1e3)

    # frozen-input chained dispatches: device time per megastep
    sim._refresh()
    ordf = sim._ordered_state()
    inputs = sim._shape_inputs()
    f = sim.forest
    prescribed = jnp.asarray(
        [[s.u, s.v, s.omega] for s in sim.shapes], dtype=f.dtype)
    dt = jnp.asarray(sim._next_dt or sim.compute_dt(), f.dtype)
    hmin = jnp.asarray(
        cfg.h_at(int(f.level[sim._order].max())), f.dtype)

    def mega(vel, pres):
        return sim._mega_jit(
            vel, pres, inputs, prescribed, dt, hmin,
            sim._h, sim._hsq_flat, sim._maskv, sim._xc, sim._yc,
            sim._tables["vec3"], sim._tables["vec1"],
            sim._tables["sca1"], sim._tables["pois"],
            sim._tables.get("vec4t"), sim._tables.get("sca4t"),
            sim._corr, None, exact_poisson=False, with_forces=False)

    vel, pres = ordf["vel"], ordf["pres"]
    out = mega(vel, pres)          # compile/warm this exact signature
    _fence(out[0])
    # latency floor of one fenced readback
    lat = []
    for _ in range(3):
        t0 = time.perf_counter()
        _fence(out[0])
        lat.append(time.perf_counter() - t0)
    lat_floor = min(lat)

    best = None
    for _ in range(3):
        v, p = vel, pres
        t0 = time.perf_counter()
        for _ in range(args.chain):
            v, p, _, scal, _ = mega(v, p)
        _fence(v)
        w = time.perf_counter() - t0 - lat_floor
        best = w if best is None else min(best, w)
    dev_ms = best / args.chain * 1e3

    # contrast: same chain, fenced every call (the per-step sync cost)
    v, p = vel, pres
    t0 = time.perf_counter()
    for _ in range(args.chain):
        v, p, _, scal, _ = mega(v, p)
        _fence(v)
    per_call_ms = (time.perf_counter() - t0) / args.chain * 1e3

    cells = n_blocks * cfg.bs * cfg.bs
    print(json.dumps({
        "case": f"two-fish levelMax={args.levelmax} (run.sh)",
        "backend": jax.default_backend(),
        "n_blocks": n_blocks,
        "n_pad": int(sim._npad_hwm),
        "init_s": round(t_init, 1),
        "warm_step_wall_ms": round(warm_ms, 1),
        "device_ms_per_megastep": round(dev_ms, 2),
        "fenced_ms_per_megastep": round(per_call_ms, 1),
        "latency_floor_ms": round(lat_floor * 1e3, 1),
        "cells_steps_per_sec_device": round(cells / (dev_ms / 1e3)),
    }))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
