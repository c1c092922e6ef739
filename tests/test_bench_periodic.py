"""The doubly-periodic cell (``turb2d-8192.solo``, ISSUE 34) at sizes a
test run holds:

- the plain reference (benchmark/references/uniform_periodic.py): its
  Fourier solve against a dense solve of the wrap Laplacian, and the
  harness's seeded start on a box whose faces wrap;
- the program against that reference on seeded starts at 64^2, under
  the direct solve its table selects (ISSUE 35) and under the Krylov
  backstop of the supervision ladder, inside the cell's own limits;
- the cell through ``benchmark/run.py --rehearsal``: ``correct`` true
  with all four numbers compared and the solver, smoother tier and
  table of the run in every record; the six planted faults
  (benchmark/checks/periodic_faults.py) each ``correct`` false;
- the control of ``correct``: ``--control bf16_reference`` (the
  reference computed through bfloat16) reads ``correct`` false through
  run.py's own decision — and why the PROGRAM has no bf16 control here;
- the rehearsal's band against the chip's (the configuration's
  ``rehearsal`` group lays a band the 64^2 grid resolves over the
  file's k 32-72), and the case's start field (synthesised on the
  device since ISSUE 34) against its documented construction in numpy
  float64;
- the all-periodic multigrid hierarchy keeps the constant out of its
  coarse levels, so a tol-0 start-up solve comes back at the floor:
  the seeds below come back WRONG on the parent's ``poisson.py``.

ONE file on purpose: every run through ``benchmark/run.py`` writes
``benchmark_out/turb2d-8192.solo/``.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "turb2d-8192.solo"
SEEDS = (11, 2 ** 31 + 5)


def _load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


def _files():
    from benchmark import generator
    cell = _load("workloads", CELL)
    config = _load("configs", cell["config"])
    return (generator.merge(cell, cell["rehearsal"]),
            generator.merge(config, config["rehearsal"]))


def _wrap_laplacian(n):
    """Dense undivided 5-point Laplacian of an n x n box that wraps."""
    one = -2.0 * np.eye(n) + np.roll(np.eye(n), 1, 0) + np.roll(np.eye(n), -1, 0)
    return np.kron(one, np.eye(n)) + np.kron(np.eye(n), one)


def test_reference_fourier_solve_against_dense_wrap_laplacian():
    import jax.numpy as jnp
    from benchmark.references import uniform_periodic as ref
    n = 16
    b = np.random.default_rng(3).standard_normal((n, n))
    b -= b.mean()
    dense = np.linalg.lstsq(_wrap_laplacian(n), b.reshape(-1),
                            rcond=None)[0].reshape(n, n)
    dense -= dense.mean()
    x = np.asarray(ref.periodic_solve(jnp.asarray(b, jnp.float32)),
                   np.float64)
    assert x.dtype == np.float64 and abs(x.mean()) < 1e-6
    assert np.max(np.abs(x - dense)) < 2e-6 * np.max(np.abs(dense))
    back = np.asarray(ref.lap_wrap(jnp.asarray(x, jnp.float32)))
    assert np.max(np.abs(back - b)) < 1e-5 * np.max(np.abs(b))


def test_seeded_start_wraps():
    """The harness's start field on a box with no wall: across both
    seams the value and its first difference go on as between any two
    neighbours, and the wrap divergence is the scheme's O(h^2) with no
    seam outlier (the field is solenoidal analytically, not
    discretely: its wrap divergence falls 4x when h halves)."""
    from benchmark import seeded
    _, config = _files()
    div_max = {}
    for n in (128, 256):
        config["grid"].update(ny=n, nx=n)
        vel = np.asarray(seeded.start_velocity(config, 2 ** 31 + 99),
                         np.float64)
        for axis in (1, 2):
            d1 = np.abs(np.diff(vel, axis=axis))
            d2 = np.abs(np.diff(vel, n=2, axis=axis))
            seam = np.concatenate([vel, vel], axis=axis)   # ... n-1 | 0 ...
            take = [slice(None)] * 3
            take[axis] = slice(n - 2, n + 2)
            s = seam[tuple(take)]
            assert np.abs(np.diff(s, axis=axis)).max() <= d1.max()
            assert np.abs(np.diff(s, n=2, axis=axis)).max() <= d2.max()
        u, v = vel
        div = (np.roll(u, -1, 1) - np.roll(u, 1, 1)
               + np.roll(v, -1, 0) - np.roll(v, 1, 0)) * (0.5 * n)
        inner = np.abs(div[4:-4, 4:-4]).max()
        assert np.abs(div).max() <= inner * (1 + 1e-9)
        div_max[n] = np.abs(div).max()
        assert abs(u.mean()) < 1e-6 and abs(v.mean()) < 1e-6
    assert 3.5 < div_max[128] / div_max[256] < 4.5, div_max


def _program_rows(config, seed, n, krylov=False):
    """``krylov``: every step through the ladder's escalate entry
    (``_force_exact``), which on this table is how the tol-0 Krylov
    solve is reached since the table selects the direct one."""
    from benchmark import seeded
    from cup2d_tpu import cases
    a = config["case"]["args"]
    sim = cases.build_turb2d(level=config["grid"]["level"], nu=a["nu"],
                             dtype=a["dtype"], cfl=a["cfl"])
    sim._force_exact = krylov
    sim.state = sim.state._replace(
        vel=seeded.start_velocity(config, seed))
    rows = []
    for _ in range(n):
        d = sim.step_once()
        rows.append({"t": sim.time, "dt": d["dt"],
                     "umax": float(d["umax"]),
                     "energy": float(d["energy"]),
                     "div_linf": float(d["div_linf"]),
                     "residual": float(d["poisson_residual"]),
                     "poisson_iters": int(d["poisson_iters"])})
    return sim, rows


@pytest.mark.parametrize("arm", ["selected", "krylov"])
@pytest.mark.parametrize("seed", SEEDS)
def test_program_follows_the_periodic_reference(seed, arm, monkeypatch):
    """``UniformSim`` on the all-periodic table, 20 steps from a seeded
    start at 64^2 (ten tol-0 start-up steps and ten production ones),
    against ``uniform_periodic.follow``: every gap inside the cell's
    limits, under the direct solve the table selects and under the
    Krylov solve of the ladder's escalate entry."""
    from benchmark import seeded
    from benchmark.references import uniform_periodic as ref
    monkeypatch.delenv("CUP2D_POIS", raising=False)
    cell, config = _files()
    n, g, ph = 20, config["grid"], config["physics"]
    sim, theirs = _program_rows(config, seed, n, krylov=arm == "krylov")
    assert sim.poisson_mode == "fftd" and sim.grid.fftd_by == "table"
    iters = [r["poisson_iters"] for r in theirs]
    assert (min(iters) > 1) if arm == "krylov" else (set(iters) == {1})
    assert sim.bc_table == config["bc_table"]
    ours = ref.follow(seeded.start_velocity(config, seed), n,
                      h=g["extent"] / g["nx"], nu=ph["nu"], cfl=ph["cfl"])
    got = ref.gaps(theirs, ours)
    assert set(cell["limits"]) == set(ref.COMPARED)
    for k, limit in cell["limits"].items():
        assert got[k] <= limit, (k, got[k], limit)


def test_rehearsal_lays_its_own_band_over_the_chips():
    """The chip's band (k 32-72 per direction: every production step
    solves at 8192^2, ISSUE 34) is under two cells a wavelength at the
    rehearsal's 64^2; the configuration's ``rehearsal`` group gives the
    band that grid resolves, and the generator reads the band from the
    configuration it is handed — same 36 modes, same rms."""
    from benchmark import seeded
    config = _load("configs", _load("workloads", CELL)["config"])
    _, small = _files()
    assert config["seeded_start"]["wavenumbers"] == [32, 40, 48, 56, 64, 72]
    assert small["seeded_start"] == {"wavenumbers": [2, 3, 4, 5, 6, 8],
                                     "rms": config["seeded_start"]["rms"]}
    assert (small["grid"]["ny"], small["grid"]["nx"]) == (64, 64)
    assert max(small["seeded_start"]["wavenumbers"]) * 8 <= 64
    modes = seeded.mode_table(small["seeded_start"], 5)
    assert modes.shape == seeded.mode_table(
        config["seeded_start"], 5).shape == (36, 5)
    vel = np.asarray(seeded.start_velocity(small, 5), np.float64)
    assert np.sqrt(np.mean(vel[0] ** 2 + vel[1] ** 2)) == pytest.approx(
        1.0, rel=1e-5)


@pytest.mark.parametrize("n", [16, 256])
def test_turb2d_synthesis_is_the_documented_field(n):
    """``cases.turb2d_vel`` (the transforms on the device since ISSUE
    33) against the documented construction written out in numpy
    float64: random-phase psi-hat of amplitude sqrt(E(k)/k)/k, E(k) ~
    k/(1+(k/k0)^4), inverse transform, centred wrap differences, rms
    ``urms`` — the same phases from the same seed, so the same flow."""
    import jax.numpy as jnp
    from types import SimpleNamespace
    from cup2d_tpu import cases
    h, k0, seed, m = 1.0 / n, 6.0, 3, 1
    kx = np.fft.fftfreq(n, d=1.0 / n)
    kk = np.sqrt(kx[None, :] ** 2 + kx[:, None] ** 2)
    with np.errstate(divide="ignore", invalid="ignore"):
        amp = np.where(kk > 0, np.sqrt(kk / (1.0 + (kk / k0) ** 4))
                       / kk ** 1.5, 0.0)
    phase = np.exp(2j * np.pi * np.random.default_rng(seed + m).random((n, n)))
    psi = np.fft.ifft2(amp * phase).real
    u = (np.roll(psi, -1, 0) - np.roll(psi, 1, 0)) / (2.0 * h)
    v = -(np.roll(psi, -1, 1) - np.roll(psi, 1, 1)) / (2.0 * h)
    want = np.stack([u, v]) / np.sqrt(np.mean(u * u + v * v))
    grid = SimpleNamespace(ny=n, nx=n, h=h, dtype=jnp.float32)
    got = np.asarray(cases.turb2d_vel(grid, m, seed, k0, 1.0), np.float64)
    assert got.shape == (2, n, n)
    assert np.abs(got - want).max() < 2e-6 * np.abs(want).max()
    div = (np.roll(got[0], -1, 1) - np.roll(got[0], 1, 1)
           + np.roll(got[1], -1, 0) - np.roll(got[1], 1, 0)) / (2.0 * h)
    assert np.abs(div).max() < 4e-7 * np.abs(want).max() / h


_RUN: dict = {}


def _rehearsal(capsys):
    if not _RUN:
        from benchmark import run
        rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 12345),
                       "--seconds", "1", "--trace", "0", "--rehearsal"])
        assert rc == 0
        lines = [ln for ln in capsys.readouterr().out.splitlines()
                 if ln.strip()]
        with open(os.path.join(ROOT, "benchmark_out", CELL,
                               "metrics.jsonl")) as f:
            rows = [json.loads(ln) for ln in f if ln.strip()]
        _RUN["result"] = json.loads(lines[-1])
        _RUN["records"] = [r for r in rows if r.get("event") == "metrics"]
    return _RUN["result"], _RUN["records"]


def test_cell_rehearsal_reads_correct(capsys):
    res, records = _rehearsal(capsys)
    assert res["correct"] is True and res["failed"] == 0, res["compared"]
    assert set(res["compared"]) == {"umax_gap", "energy_gap", "div_gap",
                                    "t_gap"}
    assert {"cell_steps_per_s", "setup_s"} <= set(res["metrics"])


def test_records_say_which_solver_and_tier_were_timed(capsys):
    """What a reader of the cell's output needs to know WHICH path a
    number belongs to is in every record of the run (schema 4, 8, 11):
    the solver, the smoother tier, the advection tier, the table."""
    _, records = _rehearsal(capsys)
    assert len(records) > 40
    for r in records:
        assert (r["poisson_mode"], r["smoother_tier"], r["kernel_tier"],
                r["bc_table"], r["case"]) == (
            "fftd", "xla", "xla", "pd,pd,pd,pd", "turb2d")
        assert (r["poisson_iters"], r["precond_cycles"]) == (1, 0)


# the planted faults (the cavity's five and wall paint for wrap ghosts,
# each ``correct`` false; the sound program ``correct`` true through the
# same path) and the control of ``correct`` (``--control bf16_reference``
# reads false through run.py's own decision): ONE copy of each, beside
# the cavity's in benchmark/checks, collected here
from benchmark.checks.test_faults_periodic import (  # noqa: E402,F401
    test_fault_reads_not_correct)
from benchmark.checks.test_control_periodic import (  # noqa: E402,F401
    test_bf16_reference_reads_not_correct)


def test_program_has_no_bf16_tier_on_a_wrap():
    """Why the cell has no ``bf16`` control of the program's own: the
    bf16-storage advection tier is the fused Pallas tier, whose ghost
    synthesis has no wrap form — a periodic table is refused by name
    at construction (pallas_kernels.kernel_supports), not run wrong."""
    from cup2d_tpu import cases
    from cup2d_tpu.config import SimConfig
    from cup2d_tpu.uniform import UniformGrid
    assert "bf16" not in _load("workloads", CELL)["controls"]
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, dtype="float32", nu=1e-4, cfl=0.4)
    with pytest.raises(ValueError, match="periodic"):
        UniformGrid(cfg, 3, use_pallas=True, bc=cases.periodic_table())


@pytest.mark.parametrize("n", [64, 128])
def test_periodic_hierarchy_keeps_the_constant_out(n):
    """M(1) of the all-periodic hierarchy is what the FINEST level's
    four sweeps alone give, -(nu1 + nu2) omega / 4 = -0.8, at every
    size — not -322 at 64^2 and four times that a level (ISSUE 34)."""
    import jax.numpy as jnp
    from cup2d_tpu import cases
    grid = cases.build_turb2d(level=int(np.log2(n // 8))).grid
    assert (grid.ny, grid.nx) == (n, n)
    out = np.asarray(grid.mg(jnp.ones((n, n), jnp.float32)), np.float64)
    assert np.ptp(out) == 0.0               # still a constant
    assert -1.0 < out.mean() < -0.6, out.mean()
    walls = cases.build_cavity(level=int(np.log2(n // 8))).grid
    out = np.asarray(walls.mg(jnp.ones((n, n), jnp.float32)), np.float64)
    assert np.ptp(out) > 1.0                # a wall breaks the constant


@pytest.mark.parametrize("seed", [5, 3, 7])
def test_startup_solves_come_back_at_the_floor(seed, monkeypatch):
    """Tol-0 Krylov solves on the all-periodic table at 128^2 (through
    the ladder's escalate entry: the table selects the direct solve
    for the start-up steps themselves, ISSUE 35), their
    TRUE residual Linf(b - lap x) taken from the returned x: on the
    parent's ``poisson.py`` these starts came back with 1.6 (seed 5,
    step 1), 2.6 and 8.5e-2 (seed 3, steps 1 and 8), 0.19 and 2e-2
    (seed 7, steps 2 and 3) — the mean of x had drifted through the
    hierarchy's constant M(1); every one now ends at the f32 floor
    (step 1, whose right-hand side is the start field's own
    divergence, 4e-6..8e-6; later steps 1e-7..5e-7)."""
    import jax.numpy as jnp
    from cup2d_tpu.uniform import UniformGrid
    solve = UniformGrid.pressure_solve

    def true_residual(self, rhs, exact=False):
        res = solve(self, rhs, exact=exact)
        return res._replace(residual=jnp.max(jnp.abs(
            rhs - self.laplacian(res.x))).astype(res.residual.dtype))

    monkeypatch.setattr(UniformGrid, "pressure_solve", true_residual)
    _, config = _files()
    config["grid"].update(level=4, ny=128, nx=128)
    monkeypatch.delenv("CUP2D_POIS", raising=False)
    _, rows = _program_rows(config, seed, 8 if seed == 3 else 4,
                            krylov=True)
    assert all(r["poisson_iters"] > 20 for r in rows), rows
    assert all(r["residual"] <= 1e-5 for r in rows), rows


def test_cell_is_in_the_manifest():
    """``BENCHMARK.json`` names the configuration, the cell and every
    per-layer metric the cell reports as the cell's own files give
    them, and ``benchmark/checks/test_manifest.py`` holds the whole."""
    from benchmark.checks import test_manifest as held_to
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = _load("workloads", CELL)
    assert [w for w in manifest["workloads"] if w["name"] == CELL] == [
        {"name": CELL, "config": cell["config"], "traffic": "solo",
         "chips": 1, "why": cell["why"]}]
    named = {m["name"]: m
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    for name in cell["metrics"]:
        assert CELL in named[name].get("workloads", [CELL]), name
    for name in ("advect_ms", "poisson_solve_ms", "mg_cycle_ms",
                 "projection_ms"):
        assert named[name]["workloads"] == [CELL] and name in cell["metrics"]
    assert "fft_diag_ms" not in named and "host_gap_ms" not in cell["metrics"]
    held_to.test_configs_match_their_files(manifest)
    held_to.test_cells_match_their_files(manifest)
    held_to.test_metrics_match_their_files(manifest)
