"""Benchmark: cells·timesteps/second of the full projection step.

Runs the uniform-grid solver at the north-star size (8192^2 f32, the
driver target in BASELINE.json: >= 1 step/s on v5e) from an initial
state with O(1) velocity and real divergence content, so the Poisson
solve iterates at the reference's production tolerances every step —
round 1's bench measured a solver at 0 iterations because Taylor-Green keeps the undivided residual under the absolute
tolerance at large N.

Reports, besides cells*steps/s: Poisson iters/step and ms/iter (timed
separately on the captured RHS), advection ms/step, and model-based MFU
and HBM-bandwidth utilization from an explicit per-cell flop/byte count
(only on a device with a row in PEAKS — a v5e peak divided into a CPU
or interpret-mode time is not a measurement).

Runs on whatever platform jax gives it and records platform,
device_kind and device count; a backend that does not initialise kills
the run (no switch to another platform — a CPU smoke sets
JAX_PLATFORMS=cpu itself). Prints ONE JSON line (driver contract) and
exits non-zero if any phase recorded an "error". BENCH_SIZE/
BENCH_STEPS/BENCH_WARMUP env vars override the defaults.
"""

from __future__ import annotations

import json
import os
import sys
import time

# the kernel_curve's sharded-tier arm needs >= 2 devices; on CPU-only
# boxes force 2 virtual host devices BEFORE jax initializes. The flag
# only affects the host (CPU) platform, so a real accelerator's device
# count wins; an existing forcing (e.g. the test harness's 8) is kept.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2").strip()

import jax
import jax.numpy as jnp
import numpy as np


BASELINE_CELLS_STEPS_PER_SEC = 8192.0 * 8192.0  # 1 step/s @ 8192^2 target

# Published peaks of one chip, keyed by jax's ``device_kind``. v5e:
# Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16 (the f32
# figure is half of it, the MXU's f32 rate) and 819 GB/s of HBM. The
# stencil path is VPU/HBM work, so HBM is the roof that matters. A
# device that is not in this table gets NO utilisation figure: a v5e
# peak divided into a CPU or interpret-mode time is not a measurement.
PEAKS = {
    "TPU v5 lite": {"f32_tflops": 98.5, "hbm_gbps": 819.0,
                    "source": "Google Cloud documentation, 'TPU v5e'"},
}


def _util(sec: float, *, flops: float | None = None,
          hbm_bytes: float | None = None, digits: int = 3,
          hbm_key: str = "hbm_util_pct") -> dict:
    """``mfu_pct`` / ``hbm_util_pct`` entries for work done in ``sec``
    seconds on the device that ran it — empty on any device without a
    row in PEAKS."""
    peaks = PEAKS.get(jax.devices()[0].device_kind)
    if peaks is None:
        return {}
    out = {}
    if flops is not None:
        out["mfu_pct"] = round(
            flops / sec / (peaks["f32_tflops"] * 1e12) * 100.0, 3)
    if hbm_bytes is not None:
        out[hbm_key] = round(
            hbm_bytes / sec / (peaks["hbm_gbps"] * 1e9) * 100.0, digits)
    return out

# --- per-cell work model (counted from cup2d_tpu/ops/stencil.py) ---------
# advect_diffuse_rhs per component per direction: WENO5 plus+minus
# (~2x45 flops incl. smoothness indicators) + upwind select + diffusion
# 5-point (~10) -> ~110; x2 directions x2 components x2 Heun stages ~ 880
# plus penalization/projection/divergence epilogue ~ 60.
FLOPS_STEP_PER_CELL = 940.0
# BiCGSTAB iteration: 2 laplacians (6) + 2 block-precond GEMV rows
# (2*BS^2 MAC/cell = 256) + ~8 axpy/dot sweeps (~16) -> ~290.
FLOPS_ITER_PER_CELL = 290.0
# bytes: advection reads vel(2f) x2 stages + writes, penalization, rhs,
# projection: ~22 f32 field sweeps; Krylov iteration touches ~12 arrays.
BYTES_STEP_PER_CELL = 22 * 4.0
BYTES_ITER_PER_CELL = 12 * 4.0
# one Heun SUBSTAGE (the kernel_curve unit, PR 9): half the advection
# work above (~440/cell: WENO5 x2 directions x2 components ~440) plus
# the 3-flop state update — documented estimate, shared by every tier
# so the MFU column is comparable across them.
FLOPS_SUBSTAGE_PER_CELL = 443.0


def bench_state(grid):
    """O(1) velocity with genuine multi-scale divergence: a shear-layer
    pair, a mid-scale mode, and a non-solenoidal mode at a FIXED 64
    cells/wavelength. The last one makes the Poisson load
    resolution-invariant (undivided divergence ~ A^2 * h * k stays
    constant when k grows with N) — with physical-wavenumber-only
    content the absolute 1e-3 tolerance becomes trivially satisfied at
    large N and the bench degenerates to advection-only (round 1's
    failure). Free-slip-compatible normal components
    (sin -> 0 at walls) keep the box BCs consistent."""
    x, y = grid.cell_centers()
    lx, ly = grid.cfg.extents
    xs, ys = np.pi * x / lx, np.pi * y / ly
    m = max(grid.nx // 64, 32)
    u = (np.sin(xs) * np.cos(ys)
         + 0.25 * np.sin(8 * xs) * np.cos(8 * ys)
         + 0.3 * np.sin(m * xs) * np.sin(m * ys))
    v = (-np.cos(xs) * np.sin(ys)
         + 0.25 * np.sin(16 * ys) * np.sin(16 * xs)
         + 0.3 * np.sin(m * ys) * np.sin(m * xs))
    vel = jnp.asarray(np.stack([u, v]), dtype=grid.dtype)
    return grid.zero_state()._replace(vel=vel)


def _fence(x) -> float:
    """Force completion of x's producer chain via a host scalar read:
    a data-dependent transfer cannot return before the value exists,
    whatever the runtime's notion of "ready" is."""
    return float(x.reshape(-1)[0])


def _latency_floor(probe) -> float:
    """Per-readback host<->device round-trip cost, to subtract from
    fenced wall times."""
    _fence(probe)
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        _fence(probe)
        ts.append(time.perf_counter() - t0)
    return min(ts)


def run_size(size: int, n_warmup: int, n_steps: int):
    from cup2d_tpu.config import SimConfig
    from cup2d_tpu.uniform import UniformGrid

    level = int(np.log2(size // 8))
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, nu=4e-5, cfl=0.5, dtype="float32")
    grid = UniformGrid(cfg, level=level)
    state = bench_state(grid)

    # obstacle_terms=False: the bench case has no shapes; the step
    # statically drops the identically-zero penalization/udef terms
    # (see UniformGrid.step; the obstacle-free driver does the same)
    import functools
    step = jax.jit(
        functools.partial(grid.step, obstacle_terms=False),
        donate_argnums=(0,), static_argnames=("exact_poisson",))
    dt = jnp.asarray(0.5 * grid.h, grid.dtype)  # CFL 0.5 at umax ~ 1

    for _ in range(n_warmup):
        state, diag = step(state, dt)
    _fence(state.vel)
    lat = _latency_floor(dt)

    # full-step throughput; one fence (its latency subtracted), no other
    # host syncs inside the timed region. The window auto-extends until
    # it dwarfs the fence latency — a window at or below the latency
    # floor would otherwise report pure jitter as throughput.
    latency_bound = False
    while True:
        diags = []
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, diag = step(state, dt)
            diags.append(diag)
        _fence(state.vel)
        t1 = time.perf_counter()
        if (t1 - t0) >= 5.0 * lat or n_steps >= 640:
            latency_bound = (t1 - t0) < 5.0 * lat
            break
        n_steps *= 4
    wall = max(t1 - t0 - lat, 1e-9)
    # ONE batched pull of every step's whole diag dict, outside the
    # timed window (the per-scalar int() pulls this replaces cost one
    # round trip each)
    diags = jax.device_get(diags)
    iters = [int(d["poisson_iters"]) for d in diags]
    iters_total = sum(iters)

    # advection stage alone (the non-Poisson bulk of the step); extra
    # reps at small sizes so the fence latency stays small against the
    # measured window
    adv = jax.jit(grid.advect_heun)
    _fence(adv(state.vel, dt))
    n_adv = max(3, n_steps, (2048 // max(size // 8, 1)) * n_steps)
    t2 = time.perf_counter()
    out = state.vel
    for _ in range(n_adv):
        out = adv(out, dt)
    _fence(out)
    advect_ms = max(
        (time.perf_counter() - t2 - lat) / n_adv * 1e3, 0.0)

    # Poisson stage alone, on a HARD solve: the t=0 RHS (cold pressure,
    # full divergence content) at a tight relative tolerance, so ms/iter
    # averages over a real iteration train even when the production
    # steps above coast at 0-1 iterations thanks to the MG preconditioner
    from cup2d_tpu.ops.stencil import divergence_rhs
    from cup2d_tpu.poisson import bicgstab
    from cup2d_tpu.uniform import pad_vector
    state0 = bench_state(grid)
    b = divergence_rhs(pad_vector(state0.vel, 1),
                       pad_vector(state0.udef, 1),
                       state0.chi, 1, grid.h, dt)
    psolve = jax.jit(lambda bb: bicgstab(
        grid.laplacian, bb, M=grid.mg, tol=0.0, tol_rel=1e-4,
        max_iter=100))
    res = psolve(b)
    _fence(res.x)
    t3 = time.perf_counter()
    res = psolve(b)
    _fence(res.x)
    psolve_wall = max(time.perf_counter() - t3 - lat, 0.0)
    psolve_iters = int(res.iters)
    poisson_ms_per_iter = psolve_wall / max(psolve_iters, 1) * 1e3

    # the timed steps as run-telemetry records in the SAME schema a
    # production run streams to metrics.jsonl (profiling.METRICS_KEYS)
    # — BENCH_*.json and run telemetry are one trajectory. t/step are
    # synthetic (the bench holds dt fixed and restarts from warmup);
    # wall_ms is the per-step mean of the fenced window.
    from cup2d_tpu.profiling import MetricsRecorder, summarize_metrics
    rec = MetricsRecorder(sink=None)
    step_ms_mean = wall / n_steps * 1e3
    records = [
        rec.record_step(step=i + 1, t=float(dt) * (i + 1),
                        dt=float(dt), diag=d, wall_ms=step_ms_mean)
        for i, d in enumerate(diags)]
    telemetry = {
        "summary": summarize_metrics(records),
        "last_records": records[-8:],
    }

    cells = grid.nx * grid.ny
    cells_steps_per_sec = cells * n_steps / wall
    iters_per_step = iters_total / n_steps
    flops = cells * (FLOPS_STEP_PER_CELL * n_steps
                     + FLOPS_ITER_PER_CELL * iters_total)
    bytes_ = cells * (BYTES_STEP_PER_CELL * n_steps
                      + BYTES_ITER_PER_CELL * iters_total)
    return {
        "telemetry": telemetry,
        "grid": f"{size}x{size}",
        "cells_steps_per_sec": round(cells_steps_per_sec, 1),
        "steps": n_steps,
        "wall_s": round(wall, 3),
        "step_ms": round(wall / n_steps * 1e3, 3),
        "iters_per_step": round(iters_per_step, 2),
        "poisson_iters_total": iters_total,
        "poisson_ms_per_iter": round(poisson_ms_per_iter, 3),
        "poisson_solve_iters": psolve_iters,
        "advect_ms_per_step": round(advect_ms, 3),
        **_util(wall, flops=flops, hbm_bytes=bytes_, digits=1),
        "latency_bound": latency_bound,
        **_profiled_step(step, state, dt, cells),
    }


def _profiled_step(step, state, dt, cells: int) -> dict:
    """Profiler-measured step time (VERDICT r2 #3: measured, not
    modeled): capture a short jax.profiler trace of the warmed step and
    read the XLA-module device time from the xplane dump. The HBM
    figure divides the IDEAL traffic floor (the same per-cell byte
    model) by the MEASURED device time — i.e. it is an upper bound on
    achievable utilization; the gap to 100% is arithmetic (VPU), op
    overhead, or redundant traffic. Skipped silently where the profiler
    or its protobufs are unavailable."""
    import glob
    import shutil
    import tempfile
    d = tempfile.mkdtemp(prefix="cup2d_bench_trace_")
    try:
        reps = 8
        with jax.profiler.trace(d):
            s = state
            for _ in range(reps):
                s, _diag = step(s, dt)
            _fence(s.vel)
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
        paths = glob.glob(os.path.join(
            d, "plugins", "profile", "*", "*.xplane.pb"))
        xs = xplane_pb2.XSpace()
        xs.ParseFromString(open(paths[0], "rb").read())
        plane = next(p for p in xs.planes
                     if p.name.startswith("/device:"))
        durs = sorted(ev.duration_ps for line in plane.lines
                      if line.name == "XLA Modules"
                      for ev in line.events)
        if not durs:
            return {}
        # median execution: per-rep Poisson iteration counts vary
        dev_s = durs[len(durs) // 2] / 1e12
        mean_s = sum(durs) / len(durs) / 1e12
        floor_bytes = cells * BYTES_STEP_PER_CELL
        return {
            "device_step_ms_profiled": round(dev_s * 1e3, 3),
            "device_step_ms_profiled_mean": round(mean_s * 1e3, 3),
            "device_cells_steps_per_sec": round(cells / mean_s, 1),
            **_util(dev_s, hbm_bytes=floor_bytes, digits=1,
                    hbm_key="hbm_util_profiled_pct"),
        }
    except Exception:
        return {}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def run_adaptive(n_warm_steps: int = 40, chain: int = 15):
    """The CANONICAL adaptive case as a first-class bench number
    (VERDICT r4 #2): the reference's own run.sh two-fish configuration
    (levelMax 8, finest cap 2048x1024 — /root/reference/run.sh:1-22),
    warmed through real driver steps + regrids, then timed as chained
    frozen-input megasteps with a profiler trace (device time, not
    host wall). Reports active-cell throughput AND the
    finest-equivalent throughput (steps/s x finest-cap cells — the
    number that says what the AMR compression buys on the case the
    reference exists for)."""
    import glob
    import shutil
    import tempfile

    from validation.canonical import build_canonical_sim

    sim = build_canonical_sim(levelmax=8)
    cfg = sim.cfg
    t0 = time.perf_counter()
    sim.initialize()
    init_s = time.perf_counter() - t0
    for _ in range(n_warm_steps):
        if sim.step_count <= 10 or sim.step_count % cfg.adapt_steps == 0:
            sim.adapt()
        sim.step_once()
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
            sim._corr, sim._use_coarse(False),
            exact_poisson=False, with_forces=False)

    vel, pres = ordf["vel"], ordf["pres"]
    out = mega(vel, pres)
    _fence(out[0])
    lat = _latency_floor(dt)
    best = None
    for _ in range(3):
        v, p = vel, pres
        t1 = time.perf_counter()
        for _ in range(chain):
            v, p = mega(v, p)[:2]
        _fence(v)
        w = time.perf_counter() - t1 - lat
        best = w if best is None else min(best, w)
    wall_ms = best / chain * 1e3

    dev_ms = None
    d = tempfile.mkdtemp(prefix="cup2d_bench_adapt_")
    try:
        with jax.profiler.trace(d):
            v, p = vel, pres
            for _ in range(chain):
                v, p = mega(v, p)[:2]
            _fence(v)
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
        paths = glob.glob(os.path.join(
            d, "plugins", "profile", "*", "*.xplane.pb"))
        xs = xplane_pb2.XSpace()
        xs.ParseFromString(open(paths[0], "rb").read())
        plane = next(p_ for p_ in xs.planes
                     if p_.name.startswith("/device:"))
        mod_ps = sum(ev.duration_ps for line in plane.lines
                     if line.name == "XLA Modules" for ev in line.events)
        if mod_ps:
            dev_ms = mod_ps / 1e9 / chain
    except Exception:
        pass
    finally:
        shutil.rmtree(d, ignore_errors=True)

    # the one megastep pull carries the production iteration count
    scal = jax.device_get(mega(vel, pres)[3])
    diag = scal[5]
    piters = int(diag["poisson_iters"])
    n_blocks = len(f.blocks)
    cells = n_blocks * cfg.bs * cfg.bs
    finest_cells = (cfg.bpdx * cfg.bs << (cfg.level_max - 1)) \
        * (cfg.bpdy * cfg.bs << (cfg.level_max - 1))
    ms = dev_ms if dev_ms is not None else wall_ms
    steps_per_sec = 1e3 / ms
    return {
        "case": "run.sh two-fish levelMax=8 (canonical adaptive)",
        "device_derived": dev_ms is not None,
        "n_blocks": n_blocks,
        "n_pad": int(sim._npad_hwm),
        "init_s": round(init_s, 1),
        "device_ms_per_megastep": (
            round(dev_ms, 3) if dev_ms is not None else None),
        "wall_ms_per_megastep": round(wall_ms, 3),
        "poisson_iters_per_step": piters,
        # UPPER BOUND: whole megastep / iterations (at the canonical
        # case's 1-5 iters/step the solve is a fraction of the step;
        # the uniform hard-solve figure above isolates a real train)
        "poisson_ms_per_iter_upper": (
            round(ms / piters, 3) if piters else None),
        "steps_per_sec_device": round(steps_per_sec, 2),
        "cells_steps_per_sec_active": round(cells * steps_per_sec, 1),
        "cells_steps_per_sec_finest_equiv": round(
            finest_cells * steps_per_sec, 1),
        "finest_cap_cells": finest_cells,
    }


def run_fleet(size: int, members_list, n_steps: int = 40,
              n_warmup: int = 3):
    """Fleet-batching throughput curve (fleet.FleetSim): member-steps/s
    of the DRIVER loop (one fused dispatch + one batched diag pull per
    step — the product-level stepping cost) at B = 1, 2, 4, 8 on one
    small grid. Small grids are dispatch-bound — the regime the fleet
    exists for: stepping B cases in one dispatch amortizes the fixed
    per-step dispatch+pull overhead over B members, so member-steps/s
    climbs with B while a single case leaves the device idle. Each
    member is seeded at its own Taylor-Green amplitude (per-member dt,
    no lockstep); the warmup runs the executable hot and the window is
    fenced once with the readback latency subtracted (same methodology
    as run_size)."""
    from cup2d_tpu.config import SimConfig
    from cup2d_tpu.fleet import FleetSim, taylor_green_fleet

    level = int(np.log2(size // 8))
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, nu=4e-5, cfl=0.5, dtype="float32")
    points = []
    for b in members_list:
        sim = FleetSim(cfg, level=level, members=b)
        sim.state = taylor_green_fleet(sim.grid, b)
        sim.step_count = 20    # production regime (skip the exact-mode
        #                        startup solves — a second executable)
        for _ in range(n_warmup):
            sim.step_once()
        _fence(sim.state.vel)
        lat = _latency_floor(sim.state.pres)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            sim.step_once()
        _fence(sim.state.vel)
        wall = max(time.perf_counter() - t0 - lat, 1e-9)
        points.append({
            "members": b,
            "step_ms": round(wall / n_steps * 1e3, 3),
            "member_steps_per_s": round(b * n_steps / wall, 1),
        })
    # the headline: dispatch amortization at the largest B, against
    # the ACTUAL B=1 point (a BENCH_FLEET spec without 1 must not
    # mislabel a B=2 baseline as B=1 — the field is null then)
    b1 = next((pt for pt in points if pt["members"] == 1), None)
    return {
        "grid": f"{size}x{size}",
        "steps": n_steps,
        "points": points,
        "speedup_vs_b1": (round(
            points[-1]["member_steps_per_s"]
            / b1["member_steps_per_s"], 2) if b1 else None),
        "note": ("member-steps/s of the sync driver loop (one fused "
                 "dispatch + one batched diag pull per step); the "
                 "curve IS the dispatch-amortization win — per-member "
                 "compute is B-invariant"),
    }


def run_fleet_serving(size: int, members: int = 8, n_steps: int = 60,
                      n_warmup: int = 3):
    """Continuous-batching serving curve (fleet.FleetServer, PR 11):
    occupancy-weighted member-steps/s of a CHURN workload — sessions
    with staggered horizons retiring and admitting INSIDE the timed
    window — against the static fixed-B FleetSim loop of run_fleet on
    the same pool size. The ratio is the cost of the serving machinery
    (mask-frozen dead lanes, device-indexed slot scatter on admit,
    host-side queue/retire bookkeeping); the zero-recompile contract is
    measured, not assumed: the warmup exercises every serving
    executable (masked step, admit scatter, retire re-zero, fresh-dt
    reduce), then the jax.monitoring compile counter must stay FLAT
    through the whole churn window (``recompiles_after_warmup`` — the
    CI smoke pins it at 0)."""
    from cup2d_tpu.config import SimConfig
    from cup2d_tpu.fleet import (FleetRequest, FleetServer, FleetSim,
                                 taylor_green_fleet)
    from cup2d_tpu.profiling import HostCounters
    from cup2d_tpu.tracing import ServingLatency
    from cup2d_tpu.uniform import FlowState

    level = int(np.log2(size // 8))
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, nu=4e-5, cfl=0.5, dtype="float32")

    # --- static baseline: the fixed-B fleet loop, full pool, no churn
    sim = FleetSim(cfg, level=level, members=members)
    sim.state = taylor_green_fleet(sim.grid, members)
    sim.step_count = 20    # production regime, as in run_fleet
    for _ in range(n_warmup):
        sim.step_once()
    _fence(sim.state.vel)
    lat = _latency_floor(sim.state.pres)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        sim.step_once()
    _fence(sim.state.vel)
    wall = max(time.perf_counter() - t0 - lat, 1e-9)
    static_msps = members * n_steps / wall

    # --- serving pool: same B, sessions flowing through the queue.
    # No session_dir/clients_dir: the timed window measures stepping +
    # slot churn, not checkpoint I/O (that cost is per-retire and
    # reported by the production run's phase timers instead).
    sim2 = FleetSim(cfg, level=level, members=members)
    sim2.step_count = 20
    # latency histograms (tracing.ServingLatency) ride the server's
    # existing submit/admit/step boundaries — pure host clocks, so the
    # instrument itself costs nothing the timed window can see
    server = FleetServer(sim2, latency=ServingLatency())
    ens = taylor_green_fleet(sim2.grid, members)   # session state bank
    n_req = 0
    queued_msteps = 0

    def submit(horizon_steps: int):
        # amplitude ladder member -> Taylor-Green umax = amp, so the
        # session's CFL dt ~ cfl*h/amp and a t_end of horizon_steps
        # such dts retires it after ~horizon_steps steps (the horizon
        # stagger below is what makes the churn continuous rather than
        # one synchronized retirement wave). queued_msteps accounts the
        # demand in MEMBER-STEPS — dt-invariant, so the window
        # provisioning below holds across the 5x dt spread of the
        # ladder
        nonlocal n_req, queued_msteps
        i = n_req % members
        amp = 0.8 ** i
        dt_est = cfg.cfl * sim2.grid.h / amp
        server.submit(FleetRequest(
            client_id=f"b{n_req:04d}",
            state=FlowState(*(a[i] for a in ens)),
            t_end=horizon_steps * dt_est))
        n_req += 1
        queued_msteps += horizon_steps

    # warmup: every serving executable compiles here — fill the pool,
    # step under the (array-form) mask, retire the short-horizon
    # sessions, admit replacements through the slot scatter
    counters = HostCounters().install()
    try:
        for _ in range(members):
            submit(2)
        for _ in range(max(n_warmup, 6)):
            submit(2)
            server.step()

        # the churn window: enough staggered-horizon demand queued that
        # the pool never idles, retirements interleaving throughout
        # (1.3x over-provision absorbs dt drift as the vortices decay;
        # leftover sessions just stay queued). Sessions average about
        # half the window — roughly one full pool turnover of churn
        # inside the timed region
        span = max(n_steps // 2, 2)
        queued_msteps = 0
        while queued_msteps < 1.3 * n_steps * members:
            submit(span + (n_req % 7))
        # roll the pool ONTO window sessions before the clock starts:
        # the warmup's short-horizon leftovers retire here, outside the
        # timed region, so the window's churn is the staggered-horizon
        # workload itself and not a warmup artifact wave
        for _ in range(4):
            server.step()
        _fence(sim2.state.vel)
        compiles_warm = counters.jit_compiles
        member_steps = 0
        t1 = time.perf_counter()
        for _ in range(n_steps):
            server.step()
            # occupants DURING the fused step (active[] is already
            # post-retire here — a member retiring at the end of this
            # very cycle still did a full step of work)
            member_steps += sum(c is not None
                                for c in server.step_clients)
        _fence(sim2.state.vel)
        wall2 = max(time.perf_counter() - t1 - lat, 1e-9)
        recompiles = counters.jit_compiles - compiles_warm
    finally:
        counters.uninstall()
    serving_msps = member_steps / wall2
    return {
        "grid": f"{size}x{size}",
        "members": members,
        "steps": n_steps,
        "static_member_steps_per_s": round(static_msps, 1),
        "serving_member_steps_per_s": round(serving_msps, 1),
        "throughput_ratio": round(serving_msps / static_msps, 3),
        "occupancy_mean": round(
            member_steps / (n_steps * members), 3),
        "admitted": server.admitted,
        "retired": server.retired,
        "evicted": server.evicted,
        "recompiles_after_warmup": recompiles,
        # pool-wide latency distributions of the whole churn run
        # (warmup included — queue_wait/admit percentiles need the
        # admission waves, not just the steady window)
        "serving_latency": server.latency.report()["pool"],
        "note": ("serving member-steps/s is occupancy-weighted (sum "
                 "of live members over the churn window / wall); the "
                 "ratio vs the static fixed-B loop prices the serving "
                 "machinery, and recompiles_after_warmup pins the "
                 "zero-steady-state-recompile contract; "
                 "serving_latency is the pool-wide queue-wait/"
                 "admit-to-first-step/per-step histogram report "
                 "(log2 buckets, tracing.LatencyHistogram)"),
    }


def run_mirror_overhead(size: int, n_iters: int = 30, n_warmup: int = 3):
    """Host-redundant mirror tier overhead (PR 17): enqueue-side cost
    of capturing a device snapshot WITH the neighbor mirror (one
    shard_map ppermute + on-device per-block checksums, io.py) vs the
    plain snapshot — the per-capture tax the ``-mirror`` flag adds to
    a guarded elastic run. Runs on the full local device set grouped
    into 2 "hosts" (the minimal ring); both loops are fenced with the
    readback latency subtracted (run_size methodology). The number to
    watch is mirror_overhead_ms staying a small fraction of a step —
    the mirror is enqueue-only and overlaps the next dispatch, so the
    exposed cost in a real run is lower still."""
    from cup2d_tpu.config import SimConfig
    from cup2d_tpu.io import (mirror_nbytes, mirror_snapshot,
                              snapshot_nbytes, snapshot_state_device)
    from cup2d_tpu.parallel.mesh import ShardedUniformSim, make_mesh
    from cup2d_tpu.uniform import taylor_green_state

    level = int(np.log2(size // 8))
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, nu=4e-5, cfl=0.5, dtype="float32")
    mesh = make_mesh()
    n_hosts = 2
    sim = ShardedUniformSim(cfg, mesh, level=level)
    sim.set_state(taylor_green_state(sim.grid))
    for _ in range(n_warmup):        # compile ppermute + checksum jits
        snap = snapshot_state_device(sim)
        m = mirror_snapshot(snap, mesh, n_hosts)
        if m is None:
            raise RuntimeError("mirror_snapshot refused the uniform "
                               "payload — bench rig mismatch")
    _fence(m.payload["vel"])
    lat = _latency_floor(sim.state.pres)
    t0 = time.perf_counter()
    for _ in range(n_iters):
        snap = snapshot_state_device(sim)
    _fence(snap.payload["vel"])
    plain = max(time.perf_counter() - t0 - lat, 1e-9)
    t0 = time.perf_counter()
    for _ in range(n_iters):
        snap = snapshot_state_device(sim)
        m = mirror_snapshot(snap, mesh, n_hosts)
    _fence(m.payload["vel"])
    mirrored = max(time.perf_counter() - t0 - lat, 1e-9)
    snap = snap._replace(mirror=m)
    return {
        "grid": f"{size}x{size}",
        "devices": mesh.devices.size,
        "hosts": n_hosts,
        "iters": n_iters,
        "snap_ms": round(plain / n_iters * 1e3, 3),
        "snap_mirror_ms": round(mirrored / n_iters * 1e3, 3),
        "mirror_overhead_ms": round(
            max(mirrored - plain, 0.0) / n_iters * 1e3, 3),
        "snapshot_bytes": int(snapshot_nbytes(snap)),
        "mirror_bytes": int(mirror_nbytes(snap)),
        "note": ("per-capture cost of the neighbor mirror (ppermute + "
                 "device checksums) over the plain device snapshot; "
                 "enqueue-side — in a guarded run the collective "
                 "overlaps the next dispatch"),
    }


def run_poisson_curve(size: int, tol_rel: float = 1e-3,
                      n_rep: int = 3):
    """Poisson solver micro-curve (PR 6): iterations-to-tolerance and
    ms/solve PER SOLVE PATH on one cold RHS at a FIXED relative
    residual target, so the solver trajectory is tracked across rounds
    in the BENCH JSON instead of living only in ad-hoc probes.

    Paths: the reference's block-Jacobi-preconditioned Krylov
    (bicgstab_jacobi — the AMR smoother's scaling baseline), the
    production uniform default (bicgstab_mg), and the FAS multigrid
    full solver in V-cycle and FMG-opening form (fas_v / fas_f,
    poisson.mg_solve — the CUP2D_POIS=fas path). Iteration counts are
    platform-independent; ms figures carry the usual host-fence
    methodology (latency floor subtracted).

    The 1e-3 target is the deepest one every path can HONESTLY reach
    in f32: mg_solve converges on the true residual b - A(x), whose
    f32 evaluation floor on this case is ~2e-4 relative (eps * |x|
    amplified through the undivided Laplacian — measured, f64 cycles
    sail through to any target), while BiCGSTAB's recursive residual
    drifts optimistically below that floor. Comparing at 1e-4 would
    pit an honest residual against a drifted one.

    Memory-tiered arms (ISSUE 19) + the kernel_curve roofline fields:
    fas_v+strip runs the same f32 hierarchy with the sweep chains
    fused to one strip pipeline each; fas_v+bf16leg additionally
    stores the cycle legs bf16 (mg_solve's outer loop keeps the f32
    true residual, so all fas arms converge by the SAME Linf
    criterion). Each arm carries modeled f32-equivalent HBM passes
    per iteration (1 pass = one size^2 f32 field), the modeled bytes,
    and the derived HBM-util% / MFU% against the v5e peaks — the
    kernel_curve r04-anchor methodology.

    Bytes model, per V(2,2) cycle (1 Jacobi sweep = read e + read r +
    write e = 3 passes, 2 when the first sweep starts from zero; one
    level visit = pre-chain + residual 3 + restrict 1.25 + prolong
    2.25 + post-chain; the level ladder sums to 4/3 of the finest;
    mg_solve's outer true-residual + correction add ~4 f32 passes):
      fas_v / fas_f   : (5 + 3 + 1.25 + 2.25 + 6) * 4/3 + 4 ~ 27.3
      fas_v+strip     : chains at 1 read (e, r) + 1 write -> level
                        (2 + 3 + 1.25 + 2.25 + 3) * 4/3 + 4 ~ 19.3
      fas_v+bf16leg   : same strip passes at bf16 width on the legs
                        (x 0.5), f32 outer -> 15.33 * 0.5 + 4 ~ 11.7
      bicgstab_jacobi : per iter, 2 A (3 each) + 2 block-precond
                        (2 each) + ~12 Krylov vector passes ~ 22
      bicgstab_mg     : block-precond -> one bf16 V(2,2) cycle
                        (23.3 * 0.5 each) + 2 A + vectors ~ 41.3
    The flops model is equally coarse (laps/cycle x ~7 flops/cell +
    sweep updates) — the fields track cross-round MOVEMENT, and the
    pinned acceptance is the fas_v : fas_v+bf16leg byte ratio >= 2 at
    iters within +1. util percentages are meaningless in
    interpret_mode (flagged), exactly like run_kernel_curve.

    Direct arms (ISSUE 20): fftd_periodic (doubly-periodic box, pure
    spectral divide) and fftd_channel (periodic-x/no-slip-y, per-mode
    Thomas systems) time poisson.fft_diag_solve on their own periodic
    grids + cold mean-free RHS at the same relative criterion —
    iters == 1 by contract, and the round-14 acceptance pins
    fftd_periodic ms_per_solve below the best fas arm's."""
    from cup2d_tpu.config import SimConfig
    from cup2d_tpu.ops.stencil import divergence_rhs
    from cup2d_tpu.poisson import (MultigridPreconditioner, bicgstab,
                                   mg_solve)
    from cup2d_tpu.uniform import UniformGrid, pad_vector

    level = int(np.log2(size // 8))
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, nu=4e-5, cfl=0.5, dtype="float32")
    grid = UniformGrid(cfg, level=level)
    state0 = bench_state(grid)
    dt = jnp.asarray(0.5 * grid.h, grid.dtype)
    b = divergence_rhs(pad_vector(state0.vel, 1),
                       pad_vector(state0.udef, 1),
                       state0.chi, 1, grid.h, dt)

    # EVERY arm's hierarchy is built EXPLICITLY with its own
    # cycle_dtype/leg_dtype/smoother rather than reusing grid.mg: that
    # one's tier follows the CUP2D_POIS/CUP2D_PREC/CUP2D_PALLAS
    # latches, so a bench run under any env latch would silently time
    # a mislabeled arm and break cross-round curve comparison (the
    # PR-6 contamination fix, extended to the ISSUE-19 tiers).
    from cup2d_tpu.ops.pallas_kernels import _on_accel
    mgp = MultigridPreconditioner(grid.ny, grid.nx, grid.dtype)
    mgf = MultigridPreconditioner(grid.ny, grid.nx, grid.dtype,
                                  cycle_dtype=grid.dtype)
    mgs = MultigridPreconditioner(grid.ny, grid.nx, grid.dtype,
                                  cycle_dtype=grid.dtype,
                                  smoother="strip")
    mgb = MultigridPreconditioner(grid.ny, grid.nx, grid.dtype,
                                  cycle_dtype=grid.dtype,
                                  leg_dtype=jnp.bfloat16,
                                  smoother="strip")
    solvers = {
        "bicgstab_jacobi": lambda bb: bicgstab(
            grid.laplacian, bb, M=grid.precond, tol=0.0,
            tol_rel=tol_rel, max_iter=2000),
        "bicgstab_mg": lambda bb: bicgstab(
            grid.laplacian, bb, M=mgp, tol=0.0,
            tol_rel=tol_rel, max_iter=200),
        "fas_v": lambda bb: mg_solve(
            grid.laplacian, bb, mgf, tol=0.0,
            tol_rel=tol_rel, max_cycles=200),
        "fas_f": lambda bb: mg_solve(
            grid.laplacian, bb, mgf, tol=0.0,
            tol_rel=tol_rel, max_cycles=200, fmg=True),
        "fas_v+strip": lambda bb: mg_solve(
            grid.laplacian, bb, mgs, tol=0.0,
            tol_rel=tol_rel, max_cycles=200),
        "fas_v+bf16leg": lambda bb: mg_solve(
            grid.laplacian, bb, mgb, tol=0.0,
            tol_rel=tol_rel, max_cycles=200),
    }
    # modeled f32-equivalent HBM passes and flops per ITERATION (see
    # docstring; 1 pass = one size^2 f32 field, flops/cell coarse)
    hbm_model = {
        "bicgstab_jacobi": (22.0, 24.0),
        "bicgstab_mg": (41.3, 75.0),
        "fas_v": (27.3, 60.0),
        "fas_f": (27.3, 60.0),
        "fas_v+strip": (19.3, 60.0),
        "fas_v+bf16leg": (11.7, 60.0),
    }
    tier_label = {
        "fas_v": mgf.smoother_tier, "fas_f": mgf.smoother_tier,
        "fas_v+strip": mgs.smoother_tier,
        "fas_v+bf16leg": mgb.smoother_tier,
    }
    fb = float(size * size) * 4.0
    cells = float(size * size)
    lat = None
    paths = {}
    norm0 = float(jnp.max(jnp.abs(b)))
    for name, solve in solvers.items():
        js = jax.jit(solve)
        res = js(b)
        _fence(res.x)
        if lat is None:
            lat = _latency_floor(dt)
        t0 = time.perf_counter()
        for _ in range(n_rep):
            res = js(b)
            _fence(res.x)
        wall = max((time.perf_counter() - t0 - n_rep * lat) / n_rep,
                   1e-9)
        iters = int(res.iters)
        ms_iter = wall / max(iters, 1) * 1e3
        passes, flops_cell = hbm_model[name]
        sec_iter = ms_iter * 1e-3
        paths[name] = {
            "iters": iters,
            "ms_per_solve": round(wall * 1e3, 3),
            "ms_per_iter": round(ms_iter, 3),
            "residual_rel": float(res.residual) / norm0,
            "converged": bool(res.converged),
            "hbm_passes": passes,
            "hbm_bytes": passes * fb,
            **_util(sec_iter, flops=flops_cell * cells,
                    hbm_bytes=passes * fb),
        }
        if name in tier_label:
            paths[name]["smoother_tier"] = tier_label[name]

    # FFT-diagonalized direct arms (ISSUE 20): each gets its OWN
    # periodic grid and cold RHS — the wall-table RHS above belongs to
    # a different operator — under the SAME fence methodology and
    # relative Linf criterion. The plan is constructed EXPLICITLY
    # (not via the CUP2D_POIS latch), the PR-6 contamination rule.
    # iters == 1 is the direct-solve contract; the acceptance compares
    # ms_per_solve against the best fas arm above. The bytes model is
    # as coarse as the others': rfft2+divide+irfft2 ~ 2 passes per 1-D
    # transform stage + the pointwise stage; the tridiag arm swaps one
    # transform pair for the two first-order Thomas scans.
    from cup2d_tpu.cases import periodic_channel_table, periodic_table
    from cup2d_tpu.poisson import FFTDiagPlan, fft_diag_solve

    for name, table in (("fftd_periodic", periodic_table()),
                        ("fftd_channel", periodic_channel_table())):
        gp = UniformGrid(cfg, level=level, bc=table)
        sp = bench_state(gp)
        bp = gp.poisson_rhs(sp.vel, None, sp.udef, dt)
        bp = bp - jnp.mean(bp)       # cold mean-free RHS (the
        #                              projection pipeline's contract)
        px, py = gp._paxes
        plan = FFTDiagPlan(gp.ny, gp.nx, gp.dtype, px, py, gp._psigns)
        solve = lambda bb, gp=gp, plan=plan: fft_diag_solve(
            gp.laplacian, bb, plan, tol=0.0, tol_rel=tol_rel)
        js = jax.jit(solve)
        res = js(bp)
        _fence(res.x)
        t0 = time.perf_counter()
        for _ in range(n_rep):
            res = js(bp)
            _fence(res.x)
        wall = max((time.perf_counter() - t0 - n_rep * lat) / n_rep,
                   1e-9)
        passes, flops_cell = ((10.0, 120.0) if name == "fftd_periodic"
                              else (12.0, 80.0))
        norm0p = float(jnp.max(jnp.abs(bp)))
        sec = max(wall, 1e-12)
        paths[name] = {
            "iters": int(res.iters),
            "ms_per_solve": round(wall * 1e3, 3),
            "ms_per_iter": round(wall * 1e3, 3),
            "residual_rel": float(res.residual) / norm0p,
            "converged": bool(res.converged),
            "bc_table": table.token,
            "hbm_passes": passes,
            "hbm_bytes": passes * fb,
            **_util(sec, flops=flops_cell * cells,
                    hbm_bytes=passes * fb),
        }
    return {"grid": f"{size}x{size}", "tol_rel": tol_rel,
            "interpret_mode": not _on_accel(),
            "paths": paths,
            "forest": run_poisson_forest(n_rep=n_rep),
            "note": ("cold-RHS solves at a fixed relative target; "
                     "iters are platform-independent, ms carries the "
                     "fence methodology of run_size; hbm_passes/bytes "
                     "are MODELED per-iteration f32-equivalent field "
                     "passes (docstring), util/mfu derived against "
                     "the v5e peaks and meaningless in "
                     "interpret_mode")}


def run_poisson_forest(n_rep: int = 3):
    """Composite-forest solve-path micro-curve (PR 13): the SAME
    iters-to-tolerance + ms/solve contract as the uniform curve above,
    but on a genuinely multi-level forest (validation.poisson_ab's
    vortex-tagged topology) and through the REAL production entry
    point — each arm times a jitted AMRSim._pressure_project on the
    cold deltap RHS, so the figure includes the RHS assembly and
    projection every production solve pays. Arms:

      krylov_jacobi  block-Jacobi-preconditioned BiCGSTAB (the
                     trigger-off structured default)
      krylov_fft     mg2-cycle-preconditioned BiCGSTAB (the
                     CUP2D_POIS=fft production form)
      forest_fas     forest-native FAS multigrid as the full solver
                     (CUP2D_POIS=fas; iters are mg_solve CYCLES)

    One fresh sim per arm: _pois_mode is latched and read at trace
    time, so arms must not share a traced callable. Tolerances are the
    forest production defaults (tol 1e-3 / tol_rel 1e-2) rather than
    the uniform curve's 1e-3 relative target — the acceptance claim is
    about PRODUCTION solves."""
    from validation.poisson_ab import build_multilevel_sim

    arms = {
        "krylov_jacobi": (None, False),
        "krylov_fft": ("fft", True),
        "forest_fas": ("fas", True),
    }
    lat = None
    paths = {}
    meta = {}
    for name, (mode, coarse) in arms.items():
        sim = build_multilevel_sim(dtype="float32")
        sim._refresh()
        if mode is not None:
            sim._pois_mode = mode
        sim._coarse_on = coarse
        tc = sim._use_coarse(False) if coarse else None
        t = sim._tables
        ordf = sim._ordered_state()
        dtv = jnp.asarray(sim.compute_dt(), sim.forest.dtype)

        def solve(v, p, sim=sim, t=t, tc=tc, dtv=dtv):
            _, _, res, _ = sim._pressure_project(
                v, p, dtv, sim._h, sim._hsq_flat, t["vec1"],
                t["sca1"], t["pois"], sim._corr, tc, False,
                sim._maskv)
            return res

        js = jax.jit(solve)
        res = js(ordf["vel"], ordf["pres"])
        _fence(res.x)
        if lat is None:
            lat = _latency_floor(dtv)
        t0 = time.perf_counter()
        for _ in range(n_rep):
            res = js(ordf["vel"], ordf["pres"])
            _fence(res.x)
        wall = max((time.perf_counter() - t0 - n_rep * lat) / n_rep,
                   1e-9)
        iters = int(res.iters)
        if not meta:
            meta = {"n_blocks": int(sim._n_real),
                    "tol": sim.cfg.poisson_tol,
                    "tol_rel": sim.cfg.poisson_tol_rel}
        paths[name] = {
            "iters": iters,
            "ms_per_solve": round(wall * 1e3, 3),
            "ms_per_iter": round(wall / max(iters, 1) * 1e3, 3),
            "residual": float(res.residual),
            "converged": bool(res.converged),
        }
    return {**meta, "paths": paths,
            "note": ("cold-RHS _pressure_project solves on the "
                     "multi-level vortex forest at the production "
                     "tolerances; forest_fas iters are mg_solve "
                     "cycles, the Krylov arms' are BiCGSTAB "
                     "iterations")}


def run_kernel_curve(size: int, n_rep: int = 3):
    """Advection kernel-tier micro-curve (PR 9): ms per Heun SUBSTAGE
    for the XLA op chain vs the fused Pallas megakernel (f32 and bf16
    storage), with the MODELED HBM bytes per substage and the derived
    HBM-util% / MFU% against the v5e peaks — so acceptance is roofline
    movement against the r04 anchors (0.95% MFU / 12% HBM util), not
    just wall-clock. Timing covers one full Heun (both substages)
    divided by 2, apples-to-apples across tiers.

    Bytes model (per substage, field = 2 * N^2 * itemsize; the modeled
    pass counts are the asserted ISSUE-9 acceptance — XLA's chain
    re-reads the field >= 3x where the megakernel reads it once):
      xla   : 3 field reads (vel by pad; lab + vold by the fused
              RHS+update kernel) + 2 writes (lab, vel) = 5 f32 passes
      fused : stage 1 reads vel ONCE, writes once (2 passes); stage 2
              adds the vold read (3 passes) -> 2.5 f32 passes/substage
      bf16  : same passes at bf16 width, plus the once-per-step f32
              state <-> bf16 cast (1 f32 read + 1 bf16 write) and the
              stage-2 f32 final write -> 2 f32 + 5 bf16 passes per
              STEP = 2.25 f32-equivalent passes/substage. Halo bytes
              (<0.1% at bench sizes) ignored.

    On non-TPU hosts the fused tiers run in Pallas interpret mode: the
    ms/util columns are then NOT kernel performance (interpret_mode
    says so) but the bytes model and tier plumbing are
    platform-independent, so the smoke can pin the schema."""
    from cup2d_tpu.config import SimConfig
    from cup2d_tpu.ops.pallas_kernels import (_on_accel,
                                              fused_advect_heun,
                                              fused_tier_supported)
    from cup2d_tpu.ops.stencil import advect_diffuse_rhs, heun_substage
    from cup2d_tpu.uniform import UniformGrid, pad_vector

    level = int(np.log2(size // 8))
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, nu=4e-5, cfl=0.5, dtype="float32")
    grid = UniformGrid(cfg, level=level)
    vel0 = bench_state(grid).vel
    h, nu = grid.h, cfg.nu
    ih2 = 1.0 / (h * h)
    dt = jnp.asarray(0.5 * h, jnp.float32)

    def xla_heun(v):
        vold = v
        for c in (0.5, 1.0):
            lab = pad_vector(v, 3)
            rhs = advect_diffuse_rhs(lab, 3, h, nu, dt)
            v = heun_substage(vold, c, rhs, ih2)
        return v

    def measure(fn):
        f = jax.jit(fn)
        out = f(vel0)
        _fence(out)                       # compile + warm
        lat = _latency_floor(dt)
        t0 = time.perf_counter()
        for _ in range(n_rep):
            out = f(out)
        _fence(out)
        wall = max(time.perf_counter() - t0 - lat, 1e-9)
        return wall / n_rep / 2.0 * 1e3   # ms per SUBSTAGE

    fb4 = 2.0 * size * size * 4.0         # one f32 velocity field
    cells = float(size * size)

    def derived(ms, passes_f32_equiv):
        hbm = passes_f32_equiv * fb4
        sec = ms * 1e-3
        return {
            "hbm_passes": passes_f32_equiv,
            "hbm_bytes": hbm,
            **_util(sec, flops=FLOPS_SUBSTAGE_PER_CELL * cells,
                    hbm_bytes=hbm),
        }

    tiers = {}
    ms = measure(xla_heun)
    tiers["xla"] = {
        "ms_per_substage": round(ms, 4),
        "adv_field_reads": 3, "adv_field_writes": 2,
        "storage_dtype": "f32", **derived(ms, 5.0)}
    if fused_tier_supported(grid.ny, grid.nx, prec="f32"):
        ms = measure(lambda v: fused_advect_heun(v, h, nu, dt))
        tiers["pallas_fused"] = {
            "ms_per_substage": round(ms, 4),
            "adv_field_reads": 1, "adv_field_writes": 1,
            "storage_dtype": "f32", **derived(ms, 2.5)}
    if fused_tier_supported(grid.ny, grid.nx, prec="bf16"):
        ms = measure(lambda v: fused_advect_heun(v, h, nu, dt,
                                                 bf16=True))
        tiers["pallas_fused_bf16"] = {
            "ms_per_substage": round(ms, 4),
            "adv_field_reads": 1, "adv_field_writes": 1,
            "storage_dtype": "bf16", **derived(ms, 2.25)}
        # BC'd arms (ISSUE 16): the validation workloads that used to
        # fall back to the XLA chain — lid-driven cavity and parabolic
        # channel tables — now run the same 2.25-pass bf16 tier; the
        # ghost synthesis is in-VMEM affine arithmetic, so the bytes
        # model is UNCHANGED and any ms delta vs pallas_fused_bf16 is
        # pure compute
        from cup2d_tpu.cases import cavity_table, channel_table
        for name, table in (
                ("pallas_fused_cavity", cavity_table(1.0)),
                ("pallas_fused_channel",
                 channel_table(1.0, profile="parabolic"))):
            ms = measure(lambda v, t=table: fused_advect_heun(
                v, h, nu, dt, bc=t, bf16=True))
            tiers[name] = {
                "ms_per_substage": round(ms, 4),
                "adv_field_reads": 1, "adv_field_writes": 1,
                "storage_dtype": "bf16", "bc_token": table.token,
                **derived(ms, 2.25)}
        # sharded-tier point (ISSUE 16): 2-device x-split mesh (virtual
        # host devices on CPU boxes — forced at import, top of file);
        # the 3-wide WENO halo moves by edge-column ppermutes before
        # the strip pipeline, so the per-device bytes model is the same
        # 2.25 passes (halo bytes < 0.1% at bench sizes, ignored as in
        # the bf16 model above)
        if jax.device_count() >= 2 and grid.nx % 2 == 0:
            from cup2d_tpu.parallel.mesh import make_mesh
            from cup2d_tpu.parallel.shard_halo import (
                fused_advect_heun_sharded)
            mesh2 = make_mesh(2)
            ms = measure(lambda v: fused_advect_heun_sharded(
                v, h, nu, dt, mesh2, bc=channel_table(
                    1.0, profile="parabolic"), bf16=True))
            tiers["pallas_fused_sharded"] = {
                "ms_per_substage": round(ms, 4),
                "adv_field_reads": 1, "adv_field_writes": 1,
                "storage_dtype": "bf16",
                "bc_token": channel_table(1.0,
                                          profile="parabolic").token,
                "mesh": "x:2", **derived(ms, 2.25)}
    return {
        "grid": f"{size}x{size}",
        "interpret_mode": not _on_accel(),
        "flops_substage_per_cell": FLOPS_SUBSTAGE_PER_CELL,
        "tiers": tiers,
        "note": ("ms = one full Heun (jit, fenced, latency floor "
                 "subtracted) / 2 substages; reads/writes are MODELED "
                 "full-field HBM passes per substage (see "
                 "run_kernel_curve docstring for the bytes model); "
                 "util percentages use the v5e peak constants and are "
                 "meaningless in interpret_mode"),
    }


def _phase_errors(node, path="") -> list:
    """Paths of every ``"error"`` entry in the result tree: each phase
    is allowed to fail without silencing the others (the JSON line is
    still printed), but a run that carries one must not exit 0."""
    found = []
    if isinstance(node, dict):
        for k, v in node.items():
            where = f"{path}.{k}" if path else str(k)
            if k == "error":
                found.append(where)
            else:
                found += _phase_errors(v, where)
    elif isinstance(node, (list, tuple)):
        for i, v in enumerate(node):
            found += _phase_errors(v, f"{path}[{i}]")
    return found


def main():
    from cup2d_tpu.cache import enable_compilation_cache
    # bench runs on whatever platform jax gives it and says which; a
    # backend that does not initialise raises here and the run dies
    # non-zero — there is no switch to another platform (a CPU smoke
    # sets JAX_PLATFORMS=cpu itself)
    dev = jax.devices()[0]
    enable_compilation_cache()
    size = int(os.environ.get("BENCH_SIZE", "8192"))
    n_warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    n_steps = int(os.environ.get("BENCH_STEPS", "10"))
    extra_sizes = [int(s) for s in
                   os.environ.get("BENCH_EXTRA_SIZES", "").split(",") if s]

    primary = run_size(size, n_warmup, n_steps)
    secondary = {s: run_size(s, n_warmup, n_steps) for s in extra_sizes}
    adaptive = None
    if os.environ.get("BENCH_ADAPTIVE", "1") != "0":
        try:
            adaptive = run_adaptive(
                n_warm_steps=int(os.environ.get("BENCH_ADAPT_WARM", "40")),
                chain=int(os.environ.get("BENCH_ADAPT_CHAIN", "15")))
        except Exception as e:           # noqa: BLE001 - bench must print
            adaptive = {"error": f"{type(e).__name__}: {e}"}
    # fleet-batching curve (BENCH_FLEET="1,2,4,8" default; "0" skips;
    # BENCH_FLEET_SIZE picks the small-grid case — 16^2 default, the
    # dispatch-bound regime on every platform incl. the CPU CI box)
    fleet = None
    fleet_spec = os.environ.get("BENCH_FLEET", "1,2,4,8")
    if fleet_spec not in ("", "0"):
        try:
            fleet = run_fleet(
                int(os.environ.get("BENCH_FLEET_SIZE", "16")),
                [int(b) for b in fleet_spec.split(",") if b],
                n_steps=int(os.environ.get("BENCH_FLEET_STEPS", "40")))
        except Exception as e:           # noqa: BLE001 - bench must print
            fleet = {"error": f"{type(e).__name__}: {e}"}
    # continuous-batching serving curve (BENCH_SERVE=0 skips;
    # BENCH_SERVE_MEMBERS picks the pool size — 8 default, the ISSUE-11
    # acceptance point; BENCH_SERVE_SIZE/BENCH_SERVE_STEPS size the
    # grid and churn window like the fleet knobs above)
    serving = None
    if os.environ.get("BENCH_SERVE", "1") != "0":
        try:
            serving = run_fleet_serving(
                int(os.environ.get("BENCH_SERVE_SIZE", "16")),
                members=int(os.environ.get("BENCH_SERVE_MEMBERS", "8")),
                n_steps=int(os.environ.get("BENCH_SERVE_STEPS", "60")))
        except Exception as e:           # noqa: BLE001 - bench must print
            serving = {"error": f"{type(e).__name__}: {e}"}
    # mirror-overhead point (BENCH_MIRROR=0 skips; BENCH_MIRROR_SIZE
    # picks the grid — 256^2 default keeps the CPU CI point cheap
    # while still big enough that the permute cost is visible)
    mirror = None
    if os.environ.get("BENCH_MIRROR", "1") != "0":
        try:
            mirror = run_mirror_overhead(
                int(os.environ.get("BENCH_MIRROR_SIZE", "256")),
                n_iters=int(os.environ.get("BENCH_MIRROR_ITERS", "30")))
        except Exception as e:           # noqa: BLE001 - bench must print
            mirror = {"error": f"{type(e).__name__}: {e}"}
    # Poisson solve-path micro-curve (BENCH_POISSON=0 skips;
    # BENCH_POISSON_SIZE picks the grid — 1024^2 default keeps the
    # block-Jacobi baseline arm's iteration train bounded)
    poisson = None
    if os.environ.get("BENCH_POISSON", "1") != "0":
        try:
            poisson = run_poisson_curve(
                int(os.environ.get("BENCH_POISSON_SIZE", "1024")))
        except Exception as e:           # noqa: BLE001 - bench must print
            poisson = {"error": f"{type(e).__name__}: {e}"}
    # advection kernel-tier micro-curve (BENCH_KERNEL=0 skips;
    # BENCH_KERNEL_SIZE defaults to the primary size)
    kernel = None
    if os.environ.get("BENCH_KERNEL", "1") != "0":
        try:
            kernel = run_kernel_curve(
                int(os.environ.get("BENCH_KERNEL_SIZE", str(size))),
                n_rep=int(os.environ.get("BENCH_KERNEL_REPS", "3")))
        except Exception as e:           # noqa: BLE001 - bench must print
            kernel = {"error": f"{type(e).__name__}: {e}"}

    # PRIMARY metric: DEVICE-derived throughput (profiler module time
    # over chained steps). The fenced-wall number carries host
    # dispatch overhead that varies with the machine; the device
    # number is what the chip does and reproduces to a few % against
    # device_step_ms_profiled by construction.
    # Wall-clock throughput stays as a secondary field with the
    # wall/device divergence called out explicitly.
    have_device = "device_cells_steps_per_sec" in primary
    uni_value = (primary["device_cells_steps_per_sec"] if have_device
                 else primary["cells_steps_per_sec"])
    wall_ms = primary["step_ms"]
    dev_ms = primary.get("device_step_ms_profiled_mean")
    if adaptive and "error" not in adaptive:
        # PRIMARY metric since round 5: the CANONICAL adaptive case
        # (VERDICT r4 #2 — the uniform 8192^2 number flattered both the
        # advection share and the solver). The value is the
        # finest-equivalent throughput (device steps/s x the case's
        # finest-cap cell count): the driver target of 1 step/s applied
        # to the run.sh case makes the baseline finest_cap_cells
        # cells*steps/s, so vs_baseline is literally the achieved
        # steps/s on the reference's own case.
        value = adaptive["cells_steps_per_sec_finest_equiv"]
        # the wall-fallback must not masquerade as a device measurement
        # (same contract as the uniform metric below)
        metric = ("adaptive_cells_steps_per_sec_finest_equiv"
                  if adaptive["device_derived"]
                  else "adaptive_cells_steps_per_sec_finest_equiv"
                  "_wall_fallback")
        vs_baseline = round(value / adaptive["finest_cap_cells"], 4)
    else:
        value = uni_value
        metric = ("device_cells_steps_per_sec" if have_device
                  else "cells_steps_per_sec_wall_fallback")
        vs_baseline = round(value / BASELINE_CELLS_STEPS_PER_SEC, 4)
    out = {
        # the metric label must say what the number IS: on rigs where
        # the profiler is unavailable the fallback is wall-derived and
        # must not masquerade as a device measurement
        "metric": metric,
        "value": value,
        "unit": "cells*steps/s",
        "vs_baseline": vs_baseline,
        "backend": jax.default_backend(),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "dtype": "float32",
        ("uniform_8192_device_cells_steps_per_sec" if have_device
         else "uniform_8192_cells_steps_per_sec_wall_fallback"): uni_value,
        "uniform_8192_vs_1steps_target": round(
            uni_value / BASELINE_CELLS_STEPS_PER_SEC, 4),
        "wall_minus_device_ms": (
            round(wall_ms - dev_ms, 3) if dev_ms else None),
        "wall_overhead_note": (
            "step_ms(wall) - device_step_ms_profiled_mean is host "
            "dispatch overhead, not solver time; primary value is "
            "device-derived"),
        "peaks": PEAKS.get(dev.device_kind),
        **primary,
    }
    if adaptive:
        out["adaptive_canonical"] = adaptive
    if fleet:
        out["fleet"] = fleet
    if serving:
        out["fleet_serving"] = serving
    if mirror:
        out["mirror"] = mirror
    if poisson:
        out["poisson_curve"] = poisson
    if kernel:
        out["kernel_curve"] = kernel
    if secondary:
        out["secondary"] = secondary
    print(json.dumps(out))
    errors = _phase_errors(out)
    if errors:
        print(f"bench: {len(errors)} phase(s) recorded an error: "
              f"{', '.join(errors)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
