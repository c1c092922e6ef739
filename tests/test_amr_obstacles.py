"""Obstacles on the adaptive forest: rasterization parity with the
uniform path, chi-driven refinement (GradChiOnTmp, main.cpp:4631-4656),
forest checkpoint round-trip, and mixed-level dumps."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from cup2d_tpu.amr import AMRSim
from cup2d_tpu.config import SimConfig
from cup2d_tpu.io import dump_forest, load_checkpoint, read_dump, \
    save_checkpoint
from cup2d_tpu.models import DiskShape
from cup2d_tpu.sim import Simulation


def _fill_tg(sim):
    """Taylor-Green velocity on every active block."""
    f = sim.forest
    cfg = sim.cfg
    order = f.order()
    bs = cfg.bs
    vals = np.zeros((f.capacity, 2, bs, bs))
    for s in order:
        l = int(f.level[s])
        h = cfg.h_at(l)
        i, j = int(f.bi[s]), int(f.bj[s])
        x = (i * bs + np.arange(bs) + 0.5) * h
        y = (j * bs + np.arange(bs) + 0.5) * h
        X, Y = np.meshgrid(x, y, indexing="xy")
        vals[s, 0] = np.sin(np.pi * X) * np.cos(np.pi * Y)
        vals[s, 1] = -np.cos(np.pi * X) * np.sin(np.pi * Y)
    f.fields["vel"] = jnp.asarray(vals, f.dtype)


def test_disk_forest_matches_uniform():
    """Single-level forest with a disk must reproduce the uniform-grid
    Simulation trajectory to rounding (same algorithms, same
    resolution)."""
    cfg = SimConfig(bpdx=2, bpdy=2, level_max=2, level_start=1,
                    extent=1.0, dtype="float64", nu=1e-3, lam=1e6,
                    rtol=1e9, ctol=-1.0)   # topology frozen
    mk = lambda: DiskShape(0.08, 0.5, 0.55, prescribed=(0.0, 0.0))
    asim = AMRSim(cfg, shapes=[mk()])
    usim = Simulation(cfg, shapes=[mk()], level=1)
    asim.compute_forces_every = 0
    usim.compute_forces_every = 0

    X, Y = usim.grid.cell_centers()
    u = np.sin(np.pi * X) * np.cos(np.pi * Y)
    v = -np.cos(np.pi * X) * np.sin(np.pi * Y)
    usim.state = usim.state._replace(vel=jnp.asarray(np.stack([u, v])))
    _fill_tg(asim)

    for _ in range(3):
        asim.step_once(dt=2e-3)
        usim.step_once(dt=2e-3)

    asim.sync_fields()
    f = asim.forest
    bs = cfg.bs
    gv = np.asarray(usim.state.vel)
    err = 0.0
    for s in f.order():
        i, j = int(f.bi[s]), int(f.bj[s])
        blk = np.asarray(f.fields["vel"][s])
        err = max(err, np.abs(
            blk - gv[:, j * bs:(j + 1) * bs, i * bs:(i + 1) * bs]).max())
    assert err < 1e-10, err


@pytest.mark.slow   # ~23 s; duplicative tier-1 coverage: the canonical
#                     golden (test_golden.py) pins the post-climb block
#                     topology EXACTLY (n_blocks at every CHECK_STEP of
#                     the 2-fish levelStart -> levelMax case), so a chi
#                     tagging regression cannot pass tier-1 — this
#                     drills the same climb in isolation on a disk
def test_chi_tagging_refines_to_finest():
    """Initialization must refine every chi-support block to the finest
    level (the canonical case's levelStart -> levelMax climb,
    main.cpp:6542-6545)."""
    cfg = SimConfig(bpdx=2, bpdy=1, level_max=3, level_start=1,
                    extent=1.0, dtype="float64", nu=4e-5, lam=1e6,
                    rtol=2.0, ctol=1.0)
    sim = AMRSim(cfg, shapes=[DiskShape(0.08, 0.55, 0.25)])
    sim.compute_forces_every = 0
    sim.initialize()
    f = sim.forest
    levels = {int(f.level[s]) for s in f.blocks.values()}
    assert cfg.level_max - 1 in levels
    order = f.order()
    chi = np.asarray(f.fields["chi"][order])
    for k, s in enumerate(order):
        if chi[k].max() > 0.2:
            assert int(f.level[s]) == cfg.level_max - 1

    # and the adaptive run is stable with a disk + quiescent flow
    for _ in range(3):
        diag = sim.step_once()
    assert np.isfinite(float(diag["umax"]))
    # quiescent flow, free disk: nothing should move
    assert abs(sim.shapes[0].u) < 1e-12
    # surface-delta perimeter approximates 2 pi r
    sim.compute_forces_every = 1
    sim.step_once()
    per = sim.shapes[0].forces["perimeter"]
    assert abs(per - 2 * np.pi * 0.08) < 0.15 * 2 * np.pi * 0.08, per


@pytest.mark.slow   # ~32 s; checkpoint bit-exactness stays tier-1 via
#                     test_io (uniform roundtrip + the AMR restore-cache
#                     trio) and test_resilience rung 3
def test_amr_checkpoint_roundtrip(tmp_path):
    """Forest checkpoint restores topology + fields bit-exactly and the
    resumed trajectory matches an uninterrupted run."""
    cfg = SimConfig(bpdx=2, bpdy=1, level_max=3, level_start=1,
                    extent=1.0, dtype="float64", nu=4e-5, lam=1e6,
                    rtol=2.0, ctol=1.0)
    sim = AMRSim(cfg, shapes=[DiskShape(0.08, 0.55, 0.25)])
    sim.compute_forces_every = 0
    sim.initialize()
    _fill_tg(sim)
    sim.step_once(dt=1e-3)
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, sim)

    sim2 = AMRSim(cfg, shapes=[DiskShape(0.08, 0.55, 0.25)])
    sim2.compute_forces_every = 0
    load_checkpoint(path, sim2)
    assert sim2.forest.blocks.keys() == sim.forest.blocks.keys() or \
        set(sim2.forest.blocks) == set(sim.forest.blocks)
    o1, o2 = sim.forest.order(), sim2.forest.order()
    for name in sim.forest.fields:
        a = np.asarray(sim.forest.fields[name][o1])
        b = np.asarray(sim2.forest.fields[name][o2])
        assert np.array_equal(a, b), name

    sim.step_once(dt=1e-3)
    sim2.step_once(dt=1e-3)
    sim.sync_fields()
    sim2.sync_fields()
    a = np.asarray(sim.forest.fields["vel"][sim.forest.order()])
    b = np.asarray(sim2.forest.fields["vel"][sim2.forest.order()])
    assert np.abs(a - b).max() < 1e-12

    # and WITHOUT an explicit dt: the checkpoint persists the cached
    # next-dt state (a restart must take the SAME dt branch as the
    # uninterrupted run — a post-regrid restart would otherwise fork),
    # and a cache-cleared restart exercises the compute_dt fallback,
    # whose shared dt_from_umax arithmetic must keep times in lockstep
    path2 = str(tmp_path / "ckpt2")
    save_checkpoint(path2, sim)
    sim3 = AMRSim(cfg, shapes=[DiskShape(0.08, 0.55, 0.25)])
    sim3.compute_forces_every = 0
    load_checkpoint(path2, sim3)
    assert sim3._next_dt == sim._next_dt      # cache restored
    sim4 = AMRSim(cfg, shapes=[DiskShape(0.08, 0.55, 0.25)])
    sim4.compute_forces_every = 0
    load_checkpoint(path2, sim4)
    sim4._next_dt = None                       # force the fallback
    sim4._next_umax = None
    sim.step_once()                    # cached-dt path
    sim3.step_once()                   # restored-cache path
    sim4.step_once()                   # compute_dt fallback path
    assert sim.time == sim3.time == sim4.time, (
        sim.time, sim3.time, sim4.time)


@pytest.mark.slow   # ~206 s, the tier-1 dominator (PR-3 satellite):
#                     the fast end-to-end CLI smoke retained in tier-1
#                     is tests/test_io.py::test_cli_driver_smoke (+ the
#                     in-process telemetry CLI test)
def test_cli_amr_smoke(tmp_path):
    """`python -m cup2d_tpu` with run.sh-style flags (no -level) runs the
    ADAPTIVE path end-to-end: dumps, forces.csv, checkpoint, restart."""
    from cup2d_tpu.__main__ import main
    out = str(tmp_path / "out")
    argv = ("-bpdx 2 -bpdy 1 -levelMax 3 -levelStart 1 -Rtol 2 -Ctol 1 "
            "-extent 1 -CFL 0.5 -tend 10 -lambda 1e6 -nu 0.00004 "
            "-poissonTol 1e-3 -poissonTolRel 0.01 -maxPoissonRestarts 0 "
            "-maxPoissonIterations 200 -AdaptSteps 5 -tdump 1e-9 "
            "-maxSteps 3 -checkpointEvery 2").split()
    argv += ["-shapes", "angle=0 L=0.16 xpos=0.5 ypos=0.25 kind=disk "
                        "radius=0.08", "-output", out]
    assert main(argv) == 0
    assert os.path.exists(os.path.join(out, "forces.csv"))
    dumps = [p for p in os.listdir(out) if p.endswith(".xdmf2")]
    assert dumps, os.listdir(out)
    assert os.path.exists(os.path.join(out, "checkpoint", "meta.json"))
    # restart continues from the checkpoint without re-blending
    argv2 = argv + ["+maxSteps", "4",
                    "-restart", os.path.join(out, "checkpoint")]
    assert main(argv2) == 0


@pytest.mark.slow   # ~102 s CLI smoke (see test_cli_amr_smoke note)
def test_cli_uniform_smoke(tmp_path):
    """`-level N` forces the single-resolution uniform path through the
    same CLI (dump + forces + exit 0)."""
    from cup2d_tpu.__main__ import main
    out = str(tmp_path / "uout")
    argv = ("-bpdx 2 -bpdy 1 -levelMax 3 -levelStart 1 -Rtol 2 -Ctol 1 "
            "-extent 1 -CFL 0.5 -tend 10 -lambda 1e6 -nu 0.00004 "
            "-poissonTol 1e-3 -poissonTolRel 0.01 -maxPoissonRestarts 0 "
            "-maxPoissonIterations 100 -AdaptSteps 5 -tdump 1e-9 "
            "-maxSteps 2 -level 2").split()
    argv += ["-shapes", "angle=0 L=0.16 xpos=0.5 ypos=0.25 kind=disk "
                        "radius=0.08", "-output", out]
    assert main(argv) == 0
    assert os.path.exists(os.path.join(out, "forces.csv"))
    assert [p for p in os.listdir(out) if p.endswith(".xdmf2")]


def test_dump_forest_mixed_level(tmp_path):
    """Mixed-level dump: one quad per cell, quad areas sum to the domain
    area, and attrs round-trip the velocity."""
    cfg = SimConfig(bpdx=2, bpdy=1, level_max=3, level_start=1,
                    extent=1.0, dtype="float64", nu=4e-5, lam=1e6,
                    rtol=2.0, ctol=1.0)
    sim = AMRSim(cfg, shapes=[DiskShape(0.08, 0.55, 0.25)])
    sim.compute_forces_every = 0
    sim.initialize()
    _fill_tg(sim)
    path = str(tmp_path / "vel.0")
    dump_forest(path, 0.25, sim.forest)
    t, xyz, attr = read_dump(path)
    assert t == 0.25
    f = sim.forest
    bs = cfg.bs
    assert xyz.shape[0] == len(f.blocks) * bs * bs
    # shoelace quad areas sum to extent_x * extent_y
    x = xyz[:, :, 0]
    y = xyz[:, :, 1]
    area = 0.5 * np.abs(
        np.sum(x * np.roll(y, -1, axis=1) - np.roll(x, -1, axis=1) * y,
               axis=1))
    assert abs(area.sum() - cfg.extents[0] * cfg.extents[1]) < 1e-3
    # attr values match the stored field (first block, first cells)
    order = f.order()
    vel = np.asarray(f.fields["vel"][order], np.float32)
    assert np.allclose(attr[:, 0], vel[:, 0].ravel(), atol=1e-6)
    assert np.allclose(attr[:, 1], vel[:, 1].ravel(), atol=1e-6)


# ---------------------------------------------------------------------------
# the surface-force pass over the body's block list (ISSUE 29)
# ---------------------------------------------------------------------------

_FCFG = dict(bpdx=2, bpdy=1, level_max=4, level_start=2, extent=2.0,
             dtype="float32", nu=4e-5, lam=1e6, rtol=2.0, ctol=1.0)


def _two_fish(angle, gap=0.4):
    """Two fish nose to nose as in twofish-amr-l8, the pair turned by
    ``angle`` degrees about its middle."""
    from cup2d_tpu.models import FishShape
    cfg = SimConfig(**_FCFG)
    a = np.deg2rad(angle)
    d = 0.5 * gap * np.array([np.cos(a), np.sin(a)])
    c = np.array([1.0, 0.5])
    return AMRSim(cfg, shapes=[
        FishShape(0.4, *(c + d), angle, cfg.min_h),
        FishShape(0.4, *(c - d), angle + 180.0, cfg.min_h)])


def _half_climbed_fish():
    """One fish whose climb stopped half way: refined to the finest
    level over its head half only, so its surface band crosses
    coarse-fine edges (two levels and their 2:1 rings)."""
    from cup2d_tpu.models import FishShape
    cfg = SimConfig(**_FCFG)
    sim = AMRSim(cfg, shapes=[FishShape(0.5, 1.0, 0.5, 20.0, cfg.min_h)])
    for s in sim.shapes:
        s.advect(0.0, cfg.extents)
        s.midline(0.0)
    lo, hi = sim._shape_bbox(sim.shapes[0])
    sim._shape_bbox = lambda s: (lo, np.array([1.0, hi[1]]))
    while sim._refine_toward_shapes():
        pass
    sim._initialized = True
    return sim


def _force_operands(sim):
    """What _forces_impl reads, on the sim's forest: the rasterised
    bodies, a smooth flow and pressure in the ordered layout."""
    sim._refresh()
    inputs = sim._shape_inputs()
    obs = sim._raster_jit(inputs, sim._xc, sim._yc, sim._h3,
                          sim._hsq_flat, sim._tables["sca1"])
    xc, yc = sim._xc, sim._yc
    vel = jnp.stack([jnp.sin(np.pi * xc) * jnp.cos(np.pi * yc) + 0.3 * yc,
                     -jnp.cos(np.pi * xc) * jnp.sin(np.pi * yc)], axis=1)
    pres = (jnp.cos(2.0 * xc) * jnp.sin(3.0 * yc))[:, None]
    uvw = jnp.asarray([[0.05, -0.02, 0.3]] * len(sim.shapes), vel.dtype)
    return inputs, (vel, pres, obs, uvw, sim._tables["vec4t"],
                    sim._tables["sca4t"], sim._hflat, xc, yc)


def _mask_blocks(sim, obs, k):
    """Ordered block rows where the pass's surface mask (forces.py:
    chi gradient, |D| > eps, own_sdf > -4h) is true in some cell."""
    from cup2d_tpu.halo import assemble_labs_ordered
    t4s = sim._tables["sca4t"]
    chip = np.asarray(assemble_labs_ordered(obs.chi[:, None], t4s)[:, 0])
    sdfp = np.asarray(assemble_labs_ordered(obs.sdf[:, None], t4s)[:, 0])
    h = np.asarray(sim._hflat)[:, None, None]
    c, m, p = slice(4, -4), slice(3, -5), slice(5, -3)
    ghx = chip[:, c, p] - chip[:, c, m]
    ghy = chip[:, p, c] - chip[:, m, c]
    gux = (0.5 / h) * (sdfp[:, c, p] - sdfp[:, c, m])
    guy = (0.5 / h) * (sdfp[:, p, c] - sdfp[:, m, c])
    d_w = (0.5 * h) * (ghx * gux + ghy * guy) \
        / (gux * gux + guy * guy + 2.220446049250313e-16)
    mask = (ghx * ghx + ghy * ghy >= 1e-12) \
        & (np.abs(d_w) > 2.220446049250313e-16) \
        & (np.asarray(obs.sdf_s[k]) > -4.0 * h)
    return set(np.nonzero(mask.any(axis=(1, 2)))[0].tolist())


def _assert_lists_hold(sim):
    """The compacted pass against the pass over all N rows, all 19 keys
    to f32 summation round-off, and every masked block on its list."""
    import jax
    from cup2d_tpu.ops.forces import FORCE_KEYS
    inputs, ops = _force_operands(sim)
    full = jax.jit(sim._forces_impl)(*ops)
    # the lists as the step hands them over: the listed blocks' labs
    # assembled alone ...
    assert all({"fpos", "fsrow", "fgrow"} <= set(inp) for inp in inputs)
    tight = jax.jit(sim._forces_impl)(*ops, lists=inputs)
    # ... and without the table rows (the sharded form): all N labs
    # assembled, the listed rows taken
    taken = jax.jit(sim._forces_impl)(
        *ops, lists=[{"fpos": inp["fpos"]} for inp in inputs])
    levels = set()
    for k in range(len(sim.shapes)):
        rows = np.asarray(inputs[k]["fpos"])
        rows = rows[rows >= 0]
        assert len(rows) == sim._force_blocks[k] <= sim._fcap[k]
        assert len(rows) < sim._n_real          # a list, not the forest
        masked = _mask_blocks(sim, ops[2], k)
        assert masked and masked <= set(rows.tolist()), \
            masked - set(rows.tolist())
        levels |= {int(sim.forest.level[sim._order[r]]) for r in masked}
        # the scale of a key: no cancellation in perimeter (a sum of
        # positive weights), so forces scale with it through nu/h and p
        assert float(full[k]["perimeter"]) > 0.1
        for key in FORCE_KEYS:
            a = float(full[k][key])
            scale = max(abs(a), 1e-3 * float(full[k]["perimeter"]))
            for got in (tight, taken):
                b = float(got[k][key])
                assert abs(a - b) <= 1e-5 * scale, (k, key, a, b)
    return levels


@pytest.mark.parametrize("angle", [0.0, 45.0, 90.0, 180.0])
def test_force_lists_match_the_full_pass_two_fish(angle):
    sim = _two_fish(angle)
    sim.initialize()
    assert len({int(l) for l in sim.forest.level[sim._order]}) > 1  # mixed
    _assert_lists_hold(sim)


def test_force_lists_hold_across_a_coarse_fine_edge():
    sim = _half_climbed_fish()
    levels = _assert_lists_hold(sim)
    assert len(levels) >= 2, levels     # surface cells at two levels


@pytest.mark.parametrize("field", ["vel", "chi_sdf"])
def test_row_labs_equal_the_full_assembly(field):
    """halo.assemble_labs_rows gives the labs of the rows it is asked
    for exactly as the full assembly holds them — paint, copy rows and
    interpolation rows across coarse-fine edges — for the vector set
    and for two scalars through the scalar set at once."""
    from cup2d_tpu.halo import assemble_labs_ordered, \
        assemble_labs_rows, rows_of_blocks
    sim = _half_climbed_fish()
    _, ops = _force_operands(sim)
    vel, obs = ops[0], ops[2]
    blocks = np.arange(sim._n_real)[::2].astype(np.int32)   # every other
    srows, grows = (rows_of_blocks(ix, blocks) for ix in sim._frow_index)
    assert len(srows) and len(grows)        # walls and coarse-fine edges
    pad = lambda a, n: jnp.asarray(np.concatenate(
        [a, np.full(n, -1, np.int32)]))
    lists = (pad(blocks, 7), pad(srows, 5), pad(grows, 11))
    if field == "vel":
        x, t = vel, sim._tables["vec4t"]
        want = assemble_labs_ordered(x, t)
    else:
        x, t = jnp.stack([obs.chi, obs.sdf], axis=1), sim._tables["sca4t"]
        want = jnp.concatenate([assemble_labs_ordered(x[:, :1], t),
                                assemble_labs_ordered(x[:, 1:], t)], 1)
    got = np.asarray(assemble_labs_rows(x, t, *lists))[:len(blocks)]
    want = np.asarray(want)[blocks]
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_force_capacity_grows_before_the_dispatch():
    """A list that outgrows its capacity grows it when the list is
    built — before any dispatch — and says so; nothing is truncated."""
    from cup2d_tpu.resilience import set_event_log

    class Log:
        rows = []

        def emit(self, **row):
            self.rows.append(row)

    sim = _two_fish(0.0)
    sim.initialize()
    caps = list(sim._fcap)
    assert sim._fcap_growths == 0 and all(c & (c - 1) == 0 for c in caps)
    sim._refresh()
    sim._shape_inputs()
    assert sim._fcap == caps and sim._fcap_growths == 0     # pre-sized
    sim._fcap = [4, caps[1]]
    sim._frcap[0] = 1
    set_event_log(Log())
    try:
        _assert_lists_hold(sim)
    finally:
        set_event_log(None)
    n = sim._force_blocks[0]
    assert 4 < n <= sim._fcap[0] and sim._fcap[0] & (sim._fcap[0] - 1) == 0
    assert sim._fcap_growths == 1
    (ev,) = [r for r in Log.rows if r["event"] == "force_cap_grow"]
    assert (ev["shape"], ev["blocks"], ev["cap"], ev["growths"]) \
        == (0, n, sim._fcap[0], 1)
    assert 1 < ev["rows"] <= ev["row_cap"] == sim._frcap[0]


def test_force_lists_null_without_shapes():
    from cup2d_tpu.profiling import MetricsRecorder
    cfg = SimConfig(**{**_FCFG, "level_max": 2, "level_start": 1})
    sim = AMRSim(cfg, shapes=[])
    r = MetricsRecorder().record(sim, sim.step_once(dt=1e-3))
    assert r["n_blocks"] > 0
    assert r["force_blocks"] is None and r["force_cap"] is None
