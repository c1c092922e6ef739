"""Tier-1 bench smoke: a bench regression must never land silently.

Runs the REAL bench.py entry point as a subprocess on CPU with a tiny
configuration and pins the driver contract: rc 0, ONE JSON line, the
platform / device_kind / device count recorded, the telemetry block in
the metrics schema, every curve present — and the two things that keep
a run from looking like a chip run when it was not: no
``mfu_pct``/``hbm_util_pct`` on a device without a peak-table row, and
a non-zero exit whenever any phase recorded an ``"error"``.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_cpu_smoke():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)           # no virtual-device forcing
    env.update(
        JAX_PLATFORMS="cpu",
        PYTHONPATH=ROOT,
        BENCH_SIZE="32",                 # level-2 grid: seconds, not minutes
        BENCH_WARMUP="1",
        BENCH_STEPS="2",
        BENCH_ADAPTIVE="0",              # the AMR bench is its own path
        BENCH_FLEET="1,2",
        BENCH_FLEET_SIZE="16",
        BENCH_FLEET_STEPS="5",
        BENCH_SERVE="1",                 # continuous-batching churn curve
        BENCH_SERVE_SIZE="16",
        BENCH_SERVE_MEMBERS="4",
        BENCH_SERVE_STEPS="8",
        BENCH_MIRROR_SIZE="32",          # mirror-overhead point, tiny
        BENCH_MIRROR_ITERS="5",
        BENCH_POISSON_SIZE="32",         # tiny solver micro-curve
        BENCH_KERNEL_SIZE="32",          # kernel-tier curve, interpret mode
        BENCH_KERNEL_REPS="1",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    # driver contract: ONE JSON object on stdout
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout
    out = json.loads(lines[0])
    # the device that ran is RECORDED, as jax reports it
    assert out["platform"] == "cpu"
    assert out["backend"] == "cpu"
    assert out["device_kind"] and out["device_count"] >= 1
    # ... and a CPU has no row in bench.PEAKS: no utilisation figure
    # may appear anywhere in the result, at any depth
    assert out["peaks"] is None

    def keys(node):
        if isinstance(node, dict):
            for k, v in node.items():
                yield k
                yield from keys(v)
        elif isinstance(node, list):
            for v in node:
                yield from keys(v)

    assert not {"mfu_pct", "hbm_util_pct",
                "hbm_util_profiled_pct"} & set(keys(out))
    assert out["metric"] and out["value"] > 0
    # telemetry block rides the run-metrics schema (profiling.py)
    from cup2d_tpu.profiling import METRICS_KEYS
    summary = out["telemetry"]["summary"]
    assert summary["steps"] == 2
    last = out["telemetry"]["last_records"][-1]
    assert set(last) == set(METRICS_KEYS)
    # fleet curve (the -fleet bench mode): every requested B measured
    fleet = out["fleet"]
    assert "error" not in fleet, fleet
    assert [p["members"] for p in fleet["points"]] == [1, 2]
    assert all(p["member_steps_per_s"] > 0 for p in fleet["points"])
    assert fleet["speedup_vs_b1"] > 0
    # continuous-batching serving curve (PR 11): the churn window ran
    # real admit/retire traffic and the zero-recompile contract held —
    # every serving executable (masked step, slot scatter, fresh-dt
    # admit) compiled in warmup, NONE after. The throughput ratio is
    # timing-noise-prone on a shared CI box, so the smoke pins it
    # present-and-positive; the >= 0.9x acceptance is the bench box's
    # claim (BENCH JSON), not the smoke's.
    srv = out["fleet_serving"]
    assert "error" not in srv, srv
    assert srv["members"] == 4 and srv["steps"] == 8
    assert srv["recompiles_after_warmup"] == 0, srv
    assert srv["throughput_ratio"] > 0, srv
    assert 0 < srv["occupancy_mean"] <= 1, srv
    assert srv["admitted"] > srv["retired"] >= 4, srv
    assert srv["evicted"] == 0, srv
    # serving latency histograms (PR 18): the pool-wide block must be
    # present with all three distributions populated by the churn —
    # every fused step observed, percentiles ordered and positive
    slat = srv["serving_latency"]
    for kind in ("queue_wait", "admit_to_first_step", "step"):
        assert slat[kind]["count"] > 0, slat
    assert slat["step"]["p50_ms"] > 0, slat
    assert slat["step"]["p99_ms"] >= slat["step"]["p50_ms"], slat
    # mirror-overhead point (PR 17): the host-redundant snapshot tier
    # measured on the bench's 2 forced virtual devices grouped into 2
    # hosts — present, no error, sane values (non-negative overhead,
    # positive redundancy bytes)
    mr = out["mirror"]
    assert "error" not in mr, mr
    assert mr["devices"] == 2 and mr["hosts"] == 2
    assert mr["snap_ms"] > 0 and mr["snap_mirror_ms"] > 0, mr
    assert mr["mirror_overhead_ms"] >= 0, mr
    assert mr["mirror_bytes"] > 0 and mr["snapshot_bytes"] > 0, mr
    # Poisson solve-path micro-curve (PR 6): every path present with a
    # real converged solve, so the solver trajectory is tracked in the
    # BENCH JSON across rounds
    pc = out["poisson_curve"]
    assert "error" not in pc, pc
    assert set(pc["paths"]) == {"bicgstab_jacobi", "bicgstab_mg",
                                "fas_v", "fas_f",
                                "fas_v+strip", "fas_v+bf16leg",
                                "fftd_periodic", "fftd_channel"}
    for name, p in pc["paths"].items():
        assert p["converged"], (name, p)
        assert p["iters"] >= 1 and p["ms_per_solve"] > 0, (name, p)
        # roofline fields (ISSUE 19, kernel_curve methodology): every
        # arm carries the modeled passes/bytes (util/MFU only on a
        # device with a peak-table row — pinned absent above)
        assert set(p) >= {"hbm_passes", "hbm_bytes"}, (name, p)
    # memory-tiered FAS acceptance (ISSUE 19): the bf16-leg strip arm
    # models >= ~2x fewer bytes/cycle than the XLA f32 chain while
    # converging by the SAME f32 true-residual criterion with iters
    # within +1 of the f32-leg arm; the strip tiers report themselves
    assert (pc["paths"]["fas_v"]["hbm_bytes"]
            >= 2.0 * pc["paths"]["fas_v+bf16leg"]["hbm_bytes"]), pc
    assert (pc["paths"]["fas_v+bf16leg"]["iters"]
            <= pc["paths"]["fas_v"]["iters"] + 1), pc
    assert (pc["paths"]["fas_v+strip"]["iters"]
            <= pc["paths"]["fas_v"]["iters"] + 1), pc
    assert pc["paths"]["fas_v+strip"]["smoother_tier"] == "strip", pc
    assert (pc["paths"]["fas_v+bf16leg"]["smoother_tier"]
            == "strip+bf16"), pc
    # FFT-diagonalized direct arms (ISSUE 20): one application reaches
    # the shared relative criterion on both periodic operators —
    # iters == 1 is the CONTRACT, not a measurement. The
    # beats-best-fas ms/solve claim is a chip measurement's to make,
    # not the smoke's — ms on a shared CI box is noise.
    for name, tok in (("fftd_periodic", "pd,pd,pd,pd"),
                      ("fftd_channel", "pd,pd,ns,ns")):
        p = pc["paths"][name]
        assert p["iters"] == 1, (name, p)
        assert p["converged"], (name, p)
        assert p["bc_table"] == tok, (name, p)
    # composite-forest solve-path block (PR 13): the three forest arms
    # each ran a real converged production solve on the multi-level
    # topology. ms/solve ordering is timing-noise-prone on a shared CI
    # box, so the smoke pins presence + convergence + the CYCLE-count
    # claim (FAS needs no more outer iterations than mg2-Krylov); the
    # ms/solve win is the bench box's claim (BENCH JSON), not the
    # smoke's.
    fc = pc["forest"]
    assert "error" not in fc, fc
    assert set(fc["paths"]) == {"krylov_jacobi", "krylov_fft",
                                "forest_fas"}
    for name, p in fc["paths"].items():
        assert p["converged"], (name, p)
        assert p["iters"] >= 1 and p["ms_per_solve"] > 0, (name, p)
    assert (fc["paths"]["forest_fas"]["iters"]
            <= fc["paths"]["krylov_fft"]["iters"]), fc
    # advection kernel-tier curve (PR 9 + ISSUE 16): every tier
    # present — the three PR-9 arms plus the BC'd cavity/channel arms
    # and the 2-device sharded point (bench.py forces 2 virtual host
    # devices before jax initializes, so the sharded arm runs even
    # though this smoke pops XLA_FLAGS). The fused tiers run the REAL
    # kernels in Pallas interpret mode on the CPU box, so this pins
    # the plumbing, schema, and bytes model.
    kc = out["kernel_curve"]
    assert "error" not in kc, kc
    assert kc["interpret_mode"] is True          # CPU box
    assert set(kc["tiers"]) == {"xla", "pallas_fused",
                                "pallas_fused_bf16",
                                "pallas_fused_cavity",
                                "pallas_fused_channel",
                                "pallas_fused_sharded"}
    for name, tr in kc["tiers"].items():
        assert tr["ms_per_substage"] > 0, (name, tr)
        assert set(tr) >= {"adv_field_reads", "adv_field_writes",
                           "hbm_bytes", "hbm_passes",
                           "storage_dtype"}, (name, tr)
    # the ISSUE-9 acceptance, asserted from the bytes model: the XLA
    # chain re-reads the advected field >= 3x per substage where the
    # megakernel reads it ONCE, and the modeled HBM bytes drop
    assert kc["tiers"]["xla"]["adv_field_reads"] >= 3
    assert kc["tiers"]["pallas_fused"]["adv_field_reads"] == 1
    assert (kc["tiers"]["pallas_fused"]["hbm_bytes"]
            < kc["tiers"]["xla"]["hbm_bytes"])
    assert (kc["tiers"]["pallas_fused_bf16"]["hbm_bytes"]
            < kc["tiers"]["pallas_fused"]["hbm_bytes"])
    # the ISSUE-16 acceptance: ghost synthesis is in-VMEM affine
    # arithmetic, so every BC'd/sharded arm keeps the single-read
    # single-write bytes model with <= 2.25 modeled f32-equiv passes
    # and names its boundary table
    for name in ("pallas_fused_cavity", "pallas_fused_channel",
                 "pallas_fused_sharded"):
        tr = kc["tiers"][name]
        assert tr["adv_field_reads"] == 1, (name, tr)
        assert tr["hbm_passes"] <= 2.25, (name, tr)
        assert tr["bc_token"], (name, tr)
    assert kc["tiers"]["pallas_fused_cavity"]["bc_token"] == \
        "ns,ns,ns,ns(1,0)"
    assert kc["tiers"]["pallas_fused_sharded"]["mesh"] == "x:2"


def test_bench_exits_nonzero_on_phase_error():
    """A phase that throws keeps its ``{"error": ...}`` entry in the
    JSON line (the other phases still report) but the process must not
    exit 0 — an rc-0 run with an errored arm is how a broken sharded
    kernel arm once passed as a dictionary entry. The fleet phase is
    made to fail for real (a 4-cell grid has no level), everything
    else but the tiny primary run is switched off."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(
        JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        BENCH_SIZE="32", BENCH_WARMUP="1", BENCH_STEPS="1",
        BENCH_ADAPTIVE="0", BENCH_SERVE="0", BENCH_MIRROR="0",
        BENCH_POISSON="0", BENCH_KERNEL="0",
        BENCH_FLEET="1", BENCH_FLEET_SIZE="4")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout
    out = json.loads(lines[0])
    assert "error" in out["fleet"], out["fleet"]
    assert proc.returncode != 0, proc.stderr[-2000:]
    assert "fleet.error" in proc.stderr, proc.stderr[-2000:]
