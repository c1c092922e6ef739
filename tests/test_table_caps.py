"""The forest's halo and flux-correction tables at STICKY capacities
(ISSUE 32): AMRSim keeps one capacity a (set, dimension) beside the
block axis' high-water mark and hands it to halo.pad_tables /
flux.build_flux_corr, so that a rebuild inside its capacities changes
no shape any program sees, and a padded table computes what the
instantaneously padded one does."""

import jax
import numpy as np

from cup2d_tpu.flux import FluxCorrTables
from cup2d_tpu.halo import FastHalo, HaloTables, _bucket, table_buckets
from cup2d_tpu.resilience import set_event_log

from test_amr_flush import _ladder_sim, _next_rung


def _shapes(sim) -> str:
    return str(jax.tree_util.tree_map(lambda x: x.shape,
                                      (sim._tables, sim._corr)))


def _live(sim, name) -> tuple:
    """The live (simple rows, interpolation rows) of a padded halo set:
    the rows that do not write the dead cell."""
    t = sim._tables[name]
    t = t.t if isinstance(t, FastHalo) else t
    dead = sim._n_real * t.L * t.L
    return (int((np.asarray(t.dest_s) != dead).sum()),
            int((np.asarray(t.dest) != dead).sum()))


class _Log:
    def __init__(self):
        self.rows = []

    def emit(self, **row):
        self.rows.append(row)


def _production(sim):
    """Past the ten start-up solves, whose two-level maps are shaped by
    the forest's level windows (amr._build_coarse_maps): the production
    step takes the tables, the lists and the state alone."""
    sim.step_count = 10
    sim._refresh()
    sim._coarse_cw = None
    return sim


def test_rebuilds_inside_the_capacities_change_no_shape():
    """Over regrids of a two-fish forest that change the live block
    count (and every table's live rows) the shape of every leaf of the
    tables and of the flux correction stays what the first build made
    it, no capacity grows, and the step runs ONE executable."""
    sim = _production(_ladder_sim("two_fish"))
    log = _Log()
    set_event_log(log)
    try:
        _next_rung(sim)
        assert sim.adapt()      # the climb's regrids ran at its shapes
        _next_rung(sim)
        shapes, caps = _shapes(sim), dict(sim._tcap)
        counts, rows = {sim._n_real}, {_live(sim, "vec3")}
        held = [j._cache_size() for j in (sim._regrid_jit, sim._tags_jit)]
        for _ in range(3):
            assert sim.adapt()
            _next_rung(sim)
            counts.add(sim._n_real)
            rows.add(_live(sim, "vec3"))
            assert _shapes(sim) == shapes
            assert sim._tcap == caps
    finally:
        set_event_log(None)
    assert len(counts) == 4 and len(rows) == 4
    assert not [r for r in log.rows if r["event"] == "table_cap_grow"]
    assert sim._mega_jit._cache_size() == 1
    assert held == [j._cache_size()
                    for j in (sim._regrid_jit, sim._tags_jit)]
    # every set and the correction hold a capacity: power-of-two
    # buckets with room above the live rows, the interpolation width a
    # multiple of 8
    assert set(caps) == set(sim._tables) - {"pois"} | {"corr"}
    assert all(c & (c - 1) == 0 for cap in caps.values() for c in cap[:2])
    assert all(cap[2] % 8 == 0 for cap in caps.values() if len(cap) == 3)
    gs, gg = _live(sim, "vec3")
    assert gs <= caps["vec3"][0] and gg <= caps["vec3"][1]


def test_over_capacity_rebuild_grows_once_and_says_so():
    """A rebuild whose need exceeds a capacity raises that capacity to
    the bucket of 1.3 x the need BEFORE any dispatch and logs ONE
    table_cap_grow; the next rebuild finds room. The capacities start
    over (from the live rows, by the same rule) only when the block
    bucket steps down."""
    sim = _ladder_sim("two_fish")
    _next_rung(sim)
    caps = dict(sim._tcap)
    need = _live(sim, "vec3")[0]
    sim._tcap["vec3"] = (64,) + caps["vec3"][1:]
    sim._tcap["corr"] = (64,)
    m = int(np.asarray(sim._corr.valid).sum())
    assert need > 64 and m > 64
    log = _Log()
    set_event_log(log)
    try:
        for _ in range(2):
            sim._tables_version = -1
            sim._refresh()
    finally:
        set_event_log(None)
    ev = [r for r in log.rows if r["event"] == "table_cap_grow"]
    assert [(r["set"], r["dim"], r["need"], r["old"], r["new"])
            for r in ev] == [
        ("vec3", "gs", need, 64, _bucket(int(1.3 * need))),
        ("corr", "m", m, 64, _bucket(int(1.3 * m)))]
    assert sim._tcap["vec3"] == (ev[0]["new"],) + caps["vec3"][1:]
    assert sim._tables["vec3"].t.dest_s.shape[0] == ev[0]["new"]
    assert sim._corr.dest.shape[0] == ev[1]["new"] == sim._tcap["corr"][0]
    assert _live(sim, "vec3")[0] == need
    # ten quiet rebuilds at a quarter of the block bucket: it steps
    # down, and the table capacities start over with it
    sim._npad_hwm = 4 * sim._npad_hwm
    sim._tcap["vec1"] = tuple(4 * c for c in caps["vec1"])
    for _ in range(10):
        assert sim._tcap["vec1"] != caps["vec1"]
        sim._tables_version = -1
        sim._refresh()
    gs, gg = _live(sim, "vec1")
    assert sim._tcap["vec1"][:2] == (_bucket(int(1.3 * gs)),
                                     _bucket(int(1.3 * gg)))
    assert sim._tcap["vec1"] <= caps["vec1"]


def test_step_on_capacity_padding_equals_instantaneous_padding():
    """The pad rows carry zero weight and a dead destination whatever
    their number: a step on tables padded to the sticky capacities
    gives the step on pad_tables' instantaneous buckets within float32
    round-off."""
    sims = []
    for sticky in (True, False):
        sim = _ladder_sim("two_fish")
        if not sticky:
            # the padding functions' own rule, as a caller that holds
            # no capacity gets it
            sim._sticky_caps = lambda name, need: table_buckets(need)
        _next_rung(sim)
        assert sim.adapt()
        sim.step_once(dt=1e-4)
        sims.append(sim)
    a, b = sims
    assert a._n_real == b._n_real
    assert _shapes(a) != _shapes(b)
    assert _live(a, "vec3") == _live(b, "vec3")
    for name in ("vel", "pres", "chi"):
        x = np.asarray(a._ord[name])[:a._n_real]
        y = np.asarray(b._ord[name])[:b._n_real]
        assert np.abs(y).max() > 0
        np.testing.assert_allclose(x, y, rtol=0,
                                   atol=2e-6 * np.abs(y).max())


def test_padding_rules_of_the_table_builders():
    """halo.pad_tables / flux.build_flux_corr pad to what their caller's
    ``caps`` gives, refuse a capacity below the need, and fall back to
    the instantaneous buckets for a caller that holds none."""
    import pytest

    from cup2d_tpu.flux import build_flux_corr
    from cup2d_tpu.halo import build_tables, pad_tables

    sim = _ladder_sim("shapeless")
    _next_rung(sim)
    assert sim.adapt()
    sim._refresh()
    f, order, n_pad = sim.forest, sim._order, sim._npad_hwm
    t = build_tables(f, order, 1, True, 2)
    need = (t.dest_s.shape[0], t.dest.shape[0], t.idx.shape[1])
    inst = pad_tables(t, n_pad)
    assert isinstance(inst, HaloTables)
    assert (inst.dest_s.shape[0], inst.dest.shape[0], inst.idx.shape[1]) \
        == table_buckets(need) == (_bucket(need[0]), _bucket(need[1]), 32)
    big = pad_tables(t, n_pad, lambda n: (5000, 3000, 40))
    assert big.sign.shape == (5000, 2) and big.w.shape == (3000, 40, 2)
    assert big.src_ord.shape == (5000,) and big.idx_ord.shape == (3000, 40)
    dead = len(order) * t.L * t.L
    assert (big.dest_s[need[0]:] == dead).all()
    assert (big.dest[need[1]:] == dead).all()
    assert not big.sign[need[0]:].any() and not big.w[need[1]:].any()
    np.testing.assert_array_equal(big.w[:need[1], :need[2]], t.w)
    with pytest.raises(AssertionError):
        pad_tables(t, n_pad, lambda n: (n[0] - 1, n[1], n[2]))
    c0 = build_flux_corr(f, order, n_pad=n_pad)
    m = int(np.asarray(c0.valid).sum())
    assert isinstance(c0, FluxCorrTables) and 0 < m <= c0.dest.shape[0]
    assert c0.dest.shape[0] == _bucket(m)
    c1 = build_flux_corr(f, order, n_pad=n_pad, caps=lambda n: (4 * n[0],))
    assert c1.dest.shape[0] == 4 * m and int(np.asarray(c1.valid).sum()) == m
    assert (np.asarray(c1.dest)[m:] == len(order) * f.bs * f.bs).all()
    with pytest.raises(AssertionError):
        build_flux_corr(f, order, n_pad=n_pad, caps=lambda n: (n[0] - 1,))
