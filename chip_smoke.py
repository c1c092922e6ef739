#!/usr/bin/env python3
"""Chip smoke: the solver's main paths, once, on a TPU, through the CLI.

    python chip_smoke.py              # one chip   (what the driver runs)
    python chip_smoke.py --chips 4    # the sharded path on a 4-chip host
    python chip_smoke.py --tiny       # rehearsal sizes; never a success

One process imports JAX once and calls ``cup2d_tpu.__main__.main(argv)``
for every phase (the ``CUP2D_*`` latches are read at construction, so
setting ``os.environ`` between phases selects the tier). No child
process is started: a chip belongs to one process at a time.

Every phase prints one JSON line (phase, argv, env, steps, set-up and
stepping seconds labelled as such, checks). The LAST stdout line of a
passing full-size run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and nothing else is ever printed there: a failed phase, a non-TPU
platform or ``--tiny`` exits non-zero (or prints a rehearsal line)
without it. Seconds printed here are set-up/compile evidence, not a
speed claim — the benchmark owns those.

Phases (default run, one chip):
  0  platform: versions, device, cache directory, native helper
  1  uniform periodic: tgv_periodic with the solver its table selects
     (the direct solve, PR 35), then the same asked for by CUP2D_POIS=fftd
  2  uniform walls: cavity
  3  canonical adaptive: the reference two-fish case, levelMax 8
  4  fleet server: turb2d pool, 4 slots serving 6 sessions
  5  kernel tiers, compiled: cavity under the four Pallas latches,
     the canonical case under FAS on the XLA tier and then with both
     forest kernels (lab RHS + fused block update)
``--chips 4`` runs ONLY the sharded phase: cavity and the canonical
case over a 4-device mesh, each against the same argv on one device.

What "Poisson converged" means here: the reference's first ten steps
solve at tolerance 0 and exit through the stall detector at the
precision floor BY DESIGN (``poisson_stalled``, benign — see
resilience.health_verdict), so a start-up record (step <= 10) must be
converged OR stalled, and every later record must be converged.
"""

from __future__ import annotations

import argparse
import gc
import io
import json
import os
import re
import shutil
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chip_smoke_out")

# parity bands: bars the tests already hold, never a bar fitted to a
# run. f32: 1e-4 x scale, the trajectory bar of
# tests/test_strip_smoother.py (the per-op bars of
# tests/test_megakernel.py — 2e-6 per Heun, 5e-6 per correction — sit
# well inside it over a dozen steps). It also stands in for the
# sharded-vs-single bars of tests/test_mesh.py and
# tests/test_forest_mesh.py, which are f64 bars (1e-11/1e-12) on
# identical arithmetic and have no f32 twin. The canonical pair of
# ``--chips 4`` is KNOWN TO MISS it (4.1e-4 on four chips; PERF.md,
# Findings PR 21, says what is known about why) and stays failing
# until a four-chip run settles the cause. bf16 storage:
# tests/test_megakernel.py's trajectory band.
F32_BAND = 1e-4
BF16_BAND = 2e-2
# tests/test_fftd.py::test_tgv_periodic_ke_decay_within_1pct
TGV_KE_BAR = 0.01

SIZES = {
    # uniform level L is an (8 << L)^2 grid: 9 -> 4096^2, 6 -> 512^2
    "full": dict(uniform_level=9, uniform_steps=14, fleet_level=6,
                 serve_tend=0.01, serve_max_steps=240,
                 levelmax=8, levelstart=5, canonical_steps=25),
    # 128^2 keeps the fused tiers' lane alignment, so the same
    # rehearsal is a cheap pre-flight on a chip too
    "tiny": dict(uniform_level=4, uniform_steps=14, fleet_level=2,
                 serve_tend=0.05, serve_max_steps=80,
                 levelmax=5, levelstart=3, canonical_steps=25),
}

BAD_EVENTS = {"recovery", "member_aborted", "member_evict",
              "topology_lost", "topology_hang", "remesh",
              "mirror_reject", "checkpoint_fallback_old"}
LATCHES = ("CUP2D_PALLAS", "CUP2D_PREC", "CUP2D_POIS", "CUP2D_TWOLEVEL",
           "CUP2D_FAULTS", "CUP2D_TRACE", "CUP2D_SHARD_EXCHANGE")

_cache_events = {"hits": 0, "misses": 0}
_faults: list = []     # names of the runs/phases that failed


def _emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def _jsonl(path: str) -> list:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def _count_cache_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _cache_events["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _cache_events["misses"] += 1


def _regrids(rec: dict) -> int:
    return (rec["refines"] or 0) + (rec["coarsens"] or 0)


class Run:
    """One ``main(argv)`` call, what it left behind, and its verdict."""

    def __init__(self, name: str, argv: list, env: dict):
        from cup2d_tpu.__main__ import main

        self.name, self.env = name, dict(env)
        self.outdir = os.path.join(OUT, name)
        shutil.rmtree(self.outdir, ignore_errors=True)
        self.argv = list(argv)
        for k in LATCHES:
            os.environ.pop(k, None)
        os.environ.update(env)
        hits0, miss0 = _cache_events["hits"], _cache_events["misses"]
        sims: list = []
        t0 = time.perf_counter()
        try:
            self.rc = main(self.argv + ["-output", self.outdir],
                           sim_out=sims)
        finally:
            for k in env:
                os.environ.pop(k, None)
        self.seconds = time.perf_counter() - t0
        self.cache_hits = _cache_events["hits"] - hits0
        self.cache_misses = _cache_events["misses"] - miss0
        self.sim = sims[-1] if sims else None
        rows = _jsonl(os.path.join(self.outdir, "metrics.jsonl"))
        self.records = [r for r in rows if r.get("event") == "metrics"]
        self.events = _jsonl(os.path.join(self.outdir, "events.jsonl"))
        self.bad_events = [e for e in self.events
                           if e.get("event") in BAD_EVENTS]
        # steady = past both step executables' compiles (the start-up
        # exact-mode one and the production one that takes over at
        # step 10) and past the lagged record that carries the second
        self.steady = [r for r in self.records if r["step"] >= 13]

    def grid(self) -> str:
        g = self.sim.grid
        return f"{g.ny}x{g.nx} {g.dtype.name}"

    def final_vel(self):
        """Uniform drivers: the [.., 2, Ny, Nx] velocity; forest: a
        {(level, i, j): [2, BS, BS]} dict (slot order is an allocator
        detail, the block keys are the comparable identity)."""
        sim = self.sim
        if not hasattr(sim, "forest"):
            return np.asarray(sim.state.vel)
        f = sim.forest
        order = np.asarray(f.order())
        vel = np.asarray(f.fields["vel"][order])
        keys = zip(f.level[order], f.bi[order], f.bj[order])
        return {tuple(int(x) for x in k): vel[n]
                for n, k in enumerate(keys)}

    def parity(self, ref, versus: str, band: float = F32_BAND) -> tuple:
        """(check, line fields): max |final velocity - ref| against
        ``band`` x scale. Forest block dicts must hold the same
        blocks; a missing reference (its phase failed) is infinitely
        far."""
        a, b = self.final_vel(), ref
        if b is None or (isinstance(a, dict) and set(a) != set(b)):
            diff, scale = float("inf"), 1.0
        else:
            if isinstance(a, dict):
                a = np.stack([a[k] for k in sorted(a)])
                b = np.stack([b[k] for k in sorted(b)])
            diff = float(np.max(np.abs(a - b)))
            scale = max(1.0, float(np.max(np.abs(b))))
        return diff <= band * scale, {f"max_abs_diff_vs_{versus}": diff,
                                      "band": band * scale}

    def checks(self, min_steps: int, kernel_tier: str = "xla",
               smoother_tier: str = "xla", bc_table=None) -> dict:
        """What every classic-loop phase is held to. The stamped tiers
        must be the ones asked for (a BC'd fused tier suffixes its
        table token, which ``bc_table`` pins separately)."""
        recs = self.records
        out = {
            "rc_0": self.rc == 0,
            "steps": len(recs) >= min_steps,
            "no_recovery_event": not self.bad_events,
            "finite": all(r["umax"] is not None and np.isfinite(r["umax"])
                          and np.isfinite(r["energy"]) for r in recs),
            "poisson_startup_converged_or_floor": all(
                r["poisson_converged"] or r["poisson_stalled"]
                for r in recs if r["step"] <= 10),
            "poisson_production_converged": any(
                r["step"] > 10 for r in recs) and all(
                r["poisson_converged"] for r in recs if r["step"] > 10),
            # a regrid that changes the topology may legitimately
            # compile (a new bucket, new sharded tables): steady state
            # on the forest is every later record that did NOT regrid
            "steady_no_recompile": bool(self.steady) and all(
                r["jit_compiles"] == 0 for r in self.steady
                if not _regrids(r)),
            "kernel_tier_as_requested": all(
                r["kernel_tier"].split("+bc(")[0] == kernel_tier
                for r in recs),
            "smoother_tier_as_requested": all(
                r["smoother_tier"] == smoother_tier for r in recs),
        }
        if bc_table is not None:
            out["bc_table"] = all(r["bc_table"] == bc_table for r in recs)
        return out

    def finish(self, checks: dict, **extra) -> None:
        """Print the phase line, record a failure, drop the driver
        (device state, snapshot ring) before the next run builds its
        own."""
        last = self.records[-1] if self.records else {}
        steady_s = sum(r["wall_ms"] or 0.0 for r in self.steady) / 1e3
        ok = all(checks.values())
        _emit({
            "phase": self.name, "argv": self.argv, "env": self.env,
            "steps": len(self.records),
            "setup_seconds_incl_compile": round(self.seconds - steady_s, 2),
            "steady_steps": len(self.steady),
            "steady_stepping_seconds": round(steady_s, 3),
            "compile_ms_total": last.get("compile_ms_total"),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "poisson_mode": last.get("poisson_mode"),
            "kernel_tier": last.get("kernel_tier"),
            "smoother_tier": last.get("smoother_tier"),
            # how many levels of a uniform hierarchy run the two fused
            # legs (the strip tier's "does it engage"; None: a forest)
            "fused_levels": getattr(getattr(getattr(
                self.sim, "grid", None), "mg", None),
                "fused_levels", None),
            "bc_table": last.get("bc_table"),
            "poisson_iters": [r["poisson_iters"] for r in self.records],
            "hbm_peak_bytes": last.get("hbm_peak_bytes"),
            "bad_events": self.bad_events[:4],
            **extra,
            "checks": checks, "ok": ok,
        })
        if not ok:
            _faults.append(
                f"{self.name}: {[k for k, v in checks.items() if not v]}")
        self.sim = None
        gc.collect()


def _phase(name: str, fn):
    """Run one phase; a thrown phase is a failed phase with its trace
    on stderr, and the run goes on so one call shows every fault."""
    try:
        return fn()
    except Exception as e:   # noqa: BLE001 — reported, and fails the run
        traceback.print_exc()
        _emit({"phase": name, "ok": False,
               "error": f"{type(e).__name__}: {e}"[:2000]})
        _faults.append(f"{name}: {type(e).__name__}")
        return None


# ---------------------------------------------------------------------
# phase 0
# ---------------------------------------------------------------------

def phase0(chips: int, tiny: bool) -> dict:
    from importlib import metadata

    import jax

    from cup2d_tpu import cache, native

    jax.monitoring.register_event_listener(_count_cache_event)
    dev = jax.devices()[0]
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cdir = env_dir or cache.XLA_CACHE_DIR
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    checks = {"native_available": native.available(),
              "devices_for_mode": device["count"] >= chips}
    if not tiny:
        checks["platform_is_tpu"] = dev.platform == "tpu"
    line = {"phase": "0-platform", "jax": jax.__version__,
            "jaxlib": metadata.version("jaxlib"),
            "libtpu": metadata.version("libtpu"),
            "device": device, "mode": "tiny" if tiny else "full",
            "chips": chips, "cache_dir": cdir,
            "cache_dir_from": ("JAX_COMPILATION_CACHE_DIR" if env_dir
                               else "checkout"),
            "cache_entries_at_start": (len(os.listdir(cdir))
                                       if os.path.isdir(cdir) else 0),
            "checks": checks, "ok": all(checks.values())}
    if not line["ok"]:
        # stdout stays EMPTY: no line there can be read as a result
        sys.exit(f"chip_smoke: phase 0 failed on platform "
                 f"{dev.platform!r} ({dev.device_kind}, "
                 f"{device['count']} device(s)): "
                 f"{[k for k, v in checks.items() if not v]}\n"
                 f"{json.dumps(line)}")
    _emit(line)
    return device


# ---------------------------------------------------------------------
# phases 1-5 (one chip)
# ---------------------------------------------------------------------

def phase1(sz: dict) -> None:
    """Uniform, periodic: the analytic anchor. E(0) of the sampled
    Taylor-Green field is exactly 1/4 (discrete orthogonality), so the
    records' kinetic energy is held to exp(-4 nu k^2 t) directly."""
    nu, k = 1e-3, 2.0 * np.pi
    argv = ["-case", "tgv_periodic", "-level", str(sz["uniform_level"]),
            "-maxSteps", str(sz["uniform_steps"])]
    for name, env in (("1a-tgv_periodic-default", {}),
                      ("1b-tgv_periodic-fftd", {"CUP2D_POIS": "fftd"})):
        run = Run(name, argv, env)
        checks = run.checks(sz["uniform_steps"], bc_table="pd,pd,pd,pd")
        ratio = [r["energy"] / 0.25 for r in run.records]
        exact = [float(np.exp(-4.0 * nu * k * k * r["t"]))
                 for r in run.records]
        ke_err = max(abs(m - e) / e for m, e in zip(ratio, exact))
        # the 1% bar is nearly vacuous over a dozen steps, so the decay
        # ITSELF (1 - E/E0) is held too — a wrong nu, k or dt moves it
        # by its own factor. The scheme's first-order pressure
        # splitting adds u0^2 k^2 dt / 2 to the analytic rate
        # 4 nu k^2 (measured at 64^2..512^2, two CFLs: within 2%), so
        # the expected ratio is 1 + dt/(8 nu): 1.39 at 128^2, 1.002 at
        # 4096^2
        decay_ratio = (1.0 - ratio[-1]) / (1.0 - exact[-1])
        dt_mean = run.records[-1]["t"] / len(run.records)
        decay_model = 1.0 + dt_mean / (8.0 * nu)
        checks["ke_follows_analytic_decay"] = ke_err < TGV_KE_BAR
        checks["ke_decay_rate"] = abs(decay_ratio / decay_model - 1.0) < 0.1
        # the table selects the direct solve (1a) and the latch asks
        # for it (1b): one application a step either way
        checks["fftd_one_application"] = all(
            r["poisson_mode"] == "fftd" and r["poisson_iters"] == 1
            and r["precond_cycles"] == 0 for r in run.records)
        checks["fftd_chosen_by"] = run.sim.grid.fftd_by == (
            "env" if env else "table")
        run.finish(checks, grid=run.grid(), ke_rel_err_max=ke_err,
                   ke_decay_over_analytic=decay_ratio,
                   ke_decay_over_analytic_expected=decay_model)


CAVITY_TABLE = "ns,ns,ns,ns(1,0)"


def _picked(on_chip: str, on_cpu: str = "xla") -> str:
    """The smoother tier a wall-bounded single-device uniform hierarchy
    picks for itself: the fused strip legs on the chip (``strip+bf16``
    under Krylov, whose preconditioner cycle stores bf16), XLA in a
    CPU rehearsal. Periodic tables, mesh runs and the forest stay
    ``xla`` everywhere."""
    import jax
    return on_chip if jax.devices()[0].platform == "tpu" else on_cpu


def _cavity_argv(sz: dict) -> list:
    return ["-case", "cavity", "-level", str(sz["uniform_level"]),
            "-maxSteps", str(sz["uniform_steps"])]


def phase2(sz: dict):
    """Uniform, walls. Returns the XLA-tier final velocity for phase 5."""
    run = Run("2-cavity", _cavity_argv(sz), {})
    checks = run.checks(sz["uniform_steps"],
                        smoother_tier=_picked("strip+bf16"),
                        bc_table=CAVITY_TABLE)
    vel = run.final_vel()
    checks["lid_drives_flow"] = float(np.abs(vel).max()) > 1e-3
    run.finish(checks, grid=run.grid())
    return vel


def _canonical_argv(sz: dict) -> list:
    from validation.canonical import canonical_flags
    return canonical_flags(levelmax=sz["levelmax"],
                           levelstart=sz["levelstart"]) + [
        "-maxSteps", str(sz["canonical_steps"])]


def _canonical_checks(run, sz: dict, **tiers) -> tuple:
    checks = run.checks(sz["canonical_steps"], **tiers)
    rows = np.atleast_2d(np.genfromtxt(
        os.path.join(run.outdir, "forces.csv"), delimiter=",",
        skip_header=1))
    checks["forces_logged_and_finite"] = (
        rows.size > 0 and bool(np.all(np.isfinite(rows))))
    checks["state_gathers_zero_in_steady"] = all(
        r["state_gathers"] == 0 for r in run.steady)
    checks["regridded"] = any(_regrids(r) for r in run.records)
    last = run.records[-1]
    extra = dict(recompiles_on_regrid_records=sum(
                     r["jit_compiles"] for r in run.steady if _regrids(r)),
                 n_blocks=last["n_blocks"],
                 blocks_per_level=last["blocks_per_level"],
                 n_blocks_trail=[r["n_blocks"] for r in run.records],
                 levelmax=sz["levelmax"], levelstart=sz["levelstart"])
    return checks, extra


def phase3(sz: dict):
    """Canonical adaptive two-fish case at full width."""
    run = Run("3-canonical", _canonical_argv(sz), {})
    checks, extra = _canonical_checks(run, sz)
    vel = run.final_vel()
    run.finish(checks, **extra)
    return vel


class _Tee:
    """stderr, with a copy kept. Everything but write/flush/close is
    the real stream's (a logging handler created meanwhile may hold on
    to this object past the phase)."""

    def __init__(self, real, copy):
        self._real, self._copy = real, copy

    def write(self, s):
        self._copy.write(s)
        return self._real.write(s)

    def flush(self):
        self._real.flush()

    def close(self):
        pass

    def __getattr__(self, name):
        return getattr(self._real, name)


def phase4(sz: dict) -> None:
    """Fleet server: 6 staggered sessions through a 4-slot pool."""
    argv = ["-case", "turb2d", "-level", str(sz["fleet_level"]),
            "-fleet", "4", "-serve", "6",
            "-tend", str(sz["serve_tend"]),
            "-maxSteps", str(sz["serve_max_steps"])]
    # the served/retired/evicted summary is the CLI's stderr contract
    err, real = io.StringIO(), sys.stderr
    sys.stderr = _Tee(real, err)
    try:
        run = Run("4-fleet-serve", argv, {})
    finally:
        sys.stderr = real
    recs = run.records
    ev = [e.get("event") for e in run.events]
    cdir = os.path.join(run.outdir, "clients")
    clients = {f[:-len(".jsonl")]: [r for r in _jsonl(os.path.join(cdir, f))
                                    if r.get("event") == "metrics"]
               for f in sorted(os.listdir(cdir))}
    checks = {
        "rc_0": run.rc == 0,
        "served_summary": ("served 6 session(s): 6 retired, 0 evicted"
                           in err.getvalue()),
        "six_admits": ev.count("member_admit") == 6,
        "six_retires": ev.count("member_retire") == 6,
        "no_recovery_event": not run.bad_events,
        "final_record_no_recompile": bool(recs)
        and recs[-1]["jit_compiles"] == 0,
        "poisson_iterates": any(r["poisson_iters"] > 0 for r in recs),
        # one stream per session, holding only that session's rows,
        # and the sessions really are different flows (own dt)
        "client_streams": len(clients) == 6 and all(
            rows and all(r["client"] == c for r in rows)
            for c, rows in clients.items()),
        "sessions_differ": len({rows[0]["dt"] for rows in clients.values()
                                if rows}) > 1,
    }
    run.finish(checks, member_grid=run.grid(), members=run.sim.members,
               admitted=recs[-1]["admitted"] if recs else None)


def phase5(sz: dict, refs: dict) -> None:
    """Kernel tiers, compiled: the stamped tier must be the one asked
    for, and the field must agree with the XLA tier's. ``refs`` holds
    the XLA-tier final velocities of phase 2 ("cavity") and phase 3
    ("canonical"); a part whose reference phase was not selected is
    not run, one whose reference phase FAILED fails here too."""
    pallas, bf16 = {"CUP2D_PALLAS": "1"}, {"CUP2D_PREC": "bf16"}
    fas = {"CUP2D_POIS": "fas"}
    tiers = (
        ("5a-cavity-pallas", pallas, "pallas-fused",
         _picked("strip+bf16"), F32_BAND),
        ("5b-cavity-pallas-bf16", {**pallas, **bf16},
         "pallas-fused-bf16", _picked("strip+bf16"), BF16_BAND),
        ("5c-cavity-pallas-fas", {**pallas, **fas},
         "pallas-fused", _picked("strip"), F32_BAND),
        ("5d-cavity-pallas-fas-bf16", {**pallas, **fas, **bf16},
         "pallas-fused-bf16", _picked("strip+bf16", "xla+bf16"),
         BF16_BAND),
    )
    for name, env, ktier, stier, band in tiers:
        if "cavity" not in refs:
            break
        run = Run(name, _cavity_argv(sz), env)
        checks = run.checks(sz["uniform_steps"], ktier, stier,
                            bc_table=CAVITY_TABLE)
        checks["parity_with_xla_tier"], extra = run.parity(
            refs["cavity"], "xla", band)
        run.finish(checks, grid=run.grid(), **extra)

    # forest kernels: CUP2D_PALLAS=1 arms the lab RHS kernel, and —
    # only under the FAS solver — the fused block-Jacobi update. Both
    # run in ONE canonical run (each canonical variant costs minutes of
    # step compiles, and the smoke has 1200 s), held against the SAME
    # solver on the XLA tier: another solver lands elsewhere inside the
    # case's 1e-3/1e-2 tolerance and regrids differently, which is no
    # statement about a kernel
    if "canonical" in refs:
        run = Run("5e-canonical-fas", _canonical_argv(sz), fas)
        checks, extra = _canonical_checks(run, sz)
        checks["solver_is_fas"] = all(
            r["poisson_mode"] == "fas+forest"
            for r in run.records if r["step"] > 10)
        fas_ref = run.final_vel()
        run.finish(checks, **extra)

        run = Run("5f-canonical-pallas-fas", _canonical_argv(sz),
                  {**pallas, **fas})
        checks, extra = _canonical_checks(
            run, sz, kernel_tier="pallas-fused", smoother_tier="strip")
        checks["parity_with_xla_tier"], par = run.parity(fas_ref, "xla")
        run.finish(checks, **par, **extra)


# ---------------------------------------------------------------------
# --chips 4: the sharded path and what it is compared with, only
# ---------------------------------------------------------------------

def _collectives(sim) -> list:
    """Collective ops named in the compiled production step's text
    (ShardedUniformSim; the persistent cache makes this a hit)."""
    import jax.numpy as jnp
    dt = jnp.asarray(1e-6, sim.grid.dtype)
    txt = sim._step.lower(sim.state, dt, exact_poisson=False,
                          obstacle_terms=False).compile().as_text()
    return sorted(set(re.findall(
        r"\b(collective-permute|all-reduce|all-gather|all-to-all|"
        r"reduce-scatter)", txt)))


def _spans(arr) -> int:
    return len(arr.sharding.device_set)


def mesh_uniform(sz: dict, chips: int) -> None:
    """ShardedUniformSim (XLA tier, then the halo-mode kernel) against
    UniformSim on cavity."""
    solo = Run("m0-cavity-1dev", _cavity_argv(sz), {})
    checks = solo.checks(sz["uniform_steps"],
                         smoother_tier=_picked("strip+bf16"),
                         bc_table=CAVITY_TABLE)
    ref = solo.final_vel()
    solo.finish(checks, grid=solo.grid(),
                devices=_spans(solo.sim.state.vel))
    for name, env, ktier in (
            ("m1-cavity-mesh", {}, "xla"),
            ("m2-cavity-mesh-pallas", {"CUP2D_PALLAS": "1"},
             "pallas-fused")):
        run = Run(name, _cavity_argv(sz) + ["-mesh", str(chips)], env)
        checks = run.checks(sz["uniform_steps"], ktier,
                            bc_table=CAVITY_TABLE)
        state = run.sim.state
        checks["state_spans_all_devices"] = (
            _spans(state.vel) == chips and _spans(state.pres) == chips)
        checks["matches_single_device"], par = run.parity(ref, "1dev")
        colls = _collectives(run.sim)
        checks["collectives_in_step"] = "collective-permute" in colls
        run.finish(checks, grid=run.grid(), devices=_spans(state.vel),
                   collectives=colls, **par)


def mesh_forest(sz: dict, chips: int) -> None:
    """ShardedAMRSim against AMRSim on the canonical case."""
    solo = Run("m3-canonical-1dev", _canonical_argv(sz), {})
    checks, extra = _canonical_checks(solo, sz)
    ref, solo_records = solo.final_vel(), solo.records
    solo.finish(checks, **extra)
    run = Run("m4-canonical-mesh",
              _canonical_argv(sz) + ["-mesh", str(chips)], {})
    checks, extra = _canonical_checks(run, sz)
    n_dev = _spans(run.sim._ordered_state()["vel"])
    checks["blocks_span_all_devices"] = n_dev == chips
    checks["matches_single_device"], par = run.parity(ref, "1dev")
    # where the two trajectories part, for whoever reads a failed
    # parity: the first step whose umax or iteration count differs,
    # and both clocks at the end (dt follows umax)
    parted = [a["step"] for a, b in zip(solo_records, run.records)
              if (a["umax"], a["poisson_iters"])
              != (b["umax"], b["poisson_iters"])]
    last = run.records[-1]
    run.finish(checks, devices=n_dev,
               halo_real_bytes=last["halo_real_bytes"],
               halo_padded_bytes=last["halo_padded_bytes"],
               first_step_apart_from_1dev=parted[0] if parted else None,
               t_final=last["t"], t_final_1dev=solo_records[-1]["t"],
               **par, **extra)


# ---------------------------------------------------------------------

def main(argv=None, phases=None) -> int:
    """``phases``: an in-process caller's subset of "12345" (the CPU
    rehearsal tests split the adaptive phases off); the command line
    always runs them all."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the sharded phase on a 4-chip host")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal sizes; never prints the success line")
    args = ap.parse_args(argv)
    sz = SIZES["tiny" if args.tiny else "full"]
    phases = "12345" if phases is None else phases
    os.makedirs(OUT, exist_ok=True)
    _faults.clear()     # module state: a second in-process call starts clean

    device = phase0(args.chips, args.tiny)
    if args.chips == 4:
        _phase("mesh-uniform", lambda: mesh_uniform(sz, 4))
        _phase("mesh-forest", lambda: mesh_forest(sz, 4))
    else:
        refs = {}
        if "1" in phases:
            _phase("1", lambda: phase1(sz))
        if "2" in phases:
            refs["cavity"] = _phase("2", lambda: phase2(sz))
        if "3" in phases:
            refs["canonical"] = _phase("3", lambda: phase3(sz))
        if "4" in phases:
            _phase("4", lambda: phase4(sz))
        if "5" in phases:
            _phase("5", lambda: phase5(sz, refs))
    if _faults:
        print("chip_smoke: FAILED — " + "; ".join(_faults),
              file=sys.stderr)
        return 1
    if args.tiny or device["platform"] != "tpu":
        # a rehearsal: the phases passed, but this is not a chip run at
        # real size and must never read as one
        _emit({"rehearsal": "tiny", "phases_ok": True, "device": device})
        if device["platform"] != "tpu":
            print(f"chip_smoke: platform is {device['platform']!r}, not "
                  "'tpu' — a rehearsal cannot pass", file=sys.stderr)
            return 3
        return 0
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
