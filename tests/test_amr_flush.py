"""The flush of the forest's ordered working state into its slot arrays
(AMRSim.sync_fields, and the head of the regrid dispatch) as ONE
shape-stable program (ISSUE 32): a regrid inside its capacities
compiles nothing, and the program leaves the slots what a NumPy scatter
of the live rows leaves them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup2d_tpu.amr import AMRSim
from cup2d_tpu.config import SimConfig
from cup2d_tpu.profiling import HostCounters

from test_amr_obstacles import _two_fish


def _ladder_sim(kind):
    """A forest — the two fish after their climb, or a shapeless 8 x 8
    box — under a solenoidal flow of vorticity 4 (x + 0.3 y): a
    different tag in every block, so ``_next_rung`` can refine the
    forest one block at a time. Compression is off."""
    if kind == "two_fish":
        sim = _two_fish(0.0)
        sim.compute_forces_every = 0
        sim.initialize()
    else:
        sim = AMRSim(SimConfig(bpdx=2, bpdy=2, level_max=4, level_start=2,
                               extent=1.0, nu=1e-4, dtype="float64",
                               rtol=1e9, ctol=-1.0))
    sim.cfg.ctol = -1.0
    sim.sync_fields()
    f, cfg = sim.forest, sim.cfg
    bs = cfg.bs
    vel = np.array(f.fields["vel"])
    for (l, i, j), s in f.blocks.items():
        h = cfg.h_at(l)
        X, Y = np.meshgrid((i * bs + np.arange(bs) + 0.5) * h,
                           (j * bs + np.arange(bs) + 0.5) * h,
                           indexing="xy")
        vel[s, 0] += -0.6 * X * X
        vel[s, 1] += 4.0 * (0.5 * X * X + 0.3 * X * Y)
    f.fields["vel"] = jnp.asarray(vel, f.dtype)
    return sim


def _next_rung(sim):
    """One step (the ordered state is then newer than the slots), and
    Rtol set between the two largest vorticity tags among the blocks
    that can still refine: the next adapt() refines the first of them
    (and what 2:1 balance drags along)."""
    sim.step_once(dt=1e-5)
    w = np.asarray(sim._vorticity_jit(
        sim._ordered_state()["vel"], sim._h,
        sim._tables["vec1"]))[:sim._n_real]
    cand = np.sort(w[sim.forest.level[sim._order] < sim.cfg.level_max - 1])
    sim.cfg.rtol = float(0.5 * (cand[-1] + cand[-2]))
    assert sim._ord_dirty


def _numpy_flush(sim) -> dict:
    """The flush as a NumPy scatter of the LIVE rows of the ordered
    arrays into copies of the slot arrays: the reference of the tests
    below; it reads the sim and writes nothing."""
    out = {}
    for name, x in sim._ord.items():
        slots = np.array(sim.forest.fields[name])
        slots[sim._order] = np.asarray(x)[:sim._n_real]
        out[name] = slots
    return out


@pytest.mark.parametrize("kind", ["two_fish", "shapeless"])
def test_regrids_inside_a_bucket_compile_nothing(kind):
    """A regrid whose capacities did not move compiles NOTHING, however
    the live block count moved: between the top of adapt() and the
    next step's dispatch every shape is a function of the sticky
    capacities (n_pad, the slot capacity, the table capacities). Before
    ISSUE 32 the flush compiled 11 one-op programs at each of them, and
    a table that crossed a power-of-two bucket brought new variants of
    the regrid and the step."""
    sim = _ladder_sim(kind)

    def capacities():
        return str(jax.tree_util.tree_map(
            lambda x: getattr(x, "shape", None),
            (sim._tables, sim._corr, sim._npad_hwm, sim.forest.capacity)))

    c = HostCounters().install()
    try:
        seen, counts, held = set(), set(), []
        for _ in range(12):
            _next_rung(sim)
            n0, before = sim._n_real, capacities()
            c0 = c.jit_compiles
            assert sim.adapt()
            # ... and what precedes the next step's dispatch
            sim._refresh()
            sim._ordered_state()
            if sim.shapes:
                sim._shape_inputs()
            compiles = c.jit_compiles - c0
            key = (before, sim._npad_hwm, sim.forest.capacity)
            assert sim._n_real != n0
            # a regrid is held to zero where its programs have seen
            # these shapes before (a capacity that grew brings its own
            # variants) and the block count is one no earlier regrid had
            if key in seen and n0 not in counts:
                held.append(compiles)
            seen.add(key)
            counts.add(n0)
            if len(held) >= 4:
                break
    finally:
        c.uninstall()
    assert len(held) >= 4 and not any(held), held


@pytest.mark.parametrize("state", ["step", "regrid", "grow"])
@pytest.mark.parametrize("kind", ["two_fish", "shapeless"])
def test_flush_equals_a_numpy_scatter(kind, state):
    """The one flush program (sync_fields, and the head of the regrid
    dispatch) leaves every slot array what a NumPy scatter of the live
    rows leaves it: every inactive slot keeps its bytes."""
    sim = _ladder_sim(kind)
    f = sim.forest
    _next_rung(sim)
    if state == "grow":
        f._grow()           # the slot capacity doubles under a dirty state
    if state == "regrid":
        # a twin flushed the old way before its regrid; this one
        # flushes inside the regrid dispatch
        twin = _ladder_sim(kind)
        _next_rung(twin)
        twin.forest.fields.update(
            {k: jnp.asarray(v) for k, v in _numpy_flush(twin).items()})
        twin._ord_key = (twin.forest.version, twin.forest.fields.wver)
        twin._ord_dirty = False
        n0 = sim._n_real
        assert sim.adapt() and twin.adapt()
        assert len(f.blocks) != n0 and not sim._ord_dirty
        assert set(f.blocks.items()) == set(twin.forest.blocks.items())
        want = dict(twin.forest.fields)
    else:
        want = _numpy_flush(sim)
        ordf = sim._ord
        sim.sync_fields()
        assert not sim._ord_dirty
        assert sim._ord_key == (f.version, f.fields.wver)
        assert sim._ordered_state() is ordf     # the cache still holds
    assert set(want) == set(f.fields)
    assert f.capacity > sim._n_real and not f.active.all()
    for name, w in want.items():
        got = np.asarray(f.fields[name])
        assert got.shape[0] == f.capacity and np.abs(got).max() > 0
        np.testing.assert_array_equal(got, np.asarray(w), err_msg=name)


def test_one_flush_and_one_regrid_executable():
    """Regrids that change the live block count inside their capacities
    run ONE amr.regrid and ONE amr.sync executable (a regrid whose
    tables outgrew a capacity brings one more of the regrid, none of
    the flush), and sync_fields on a clean state dispatches nothing."""
    sim = _ladder_sim("shapeless")
    counts, caps = set(), []
    # the flush is a static function: its jit cache is the process's
    flushes = sim._sync_jit._cache_size()
    for _ in range(5):
        _next_rung(sim)
        counts.add(sim._n_real)
        caps.append((sim._tcap["vec1t"], sim._tcap["sca1t"]))
        assert sim.adapt()
        _next_rung(sim)
        sim.sync_fields()
    assert len(counts) == 5 and sim._npad_hwm == 128
    assert len(set(caps)) < len(caps)
    assert sim._regrid_jit._cache_size() == len(set(caps))
    assert sim._sync_jit._cache_size() <= flushes + 1
    before = dict(sim.forest.fields)
    c = HostCounters().install()
    try:
        sim.sync_fields()
        assert c.jit_compiles == 0
    finally:
        c.uninstall()
    assert all(sim.forest.fields[k] is v for k, v in before.items())
