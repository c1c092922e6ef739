"""The Pallas kernels, compiled for a described TPU v5e at real widths.

Interpret mode (every other kernel test in this suite) cannot see what
the chip's compiler refuses: a slice off the (8, 128) tiling, more
scoped VMEM than a kernel may use, a kernel that cannot be partitioned.
The TPU compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (``jax.experimental.topologies``), so each case
below lowers one kernel wrapper with ``interpret=False`` at the width
chip_smoke.py drives it and asserts the Mosaic custom call is in the
executable. Nothing runs: these say nothing about results or speed —
run-time parity lives in chip_smoke.py phase 5.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and every xdist
worker imports every test file), everything built from it is built in
fixtures/tests, and the persistent compilation cache is off around
them (an executable for an unattached chip is written but can never be
read back). ONE file on purpose: a second file could land on another
worker, whose fixture would then skip every case in silence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from cup2d_tpu.cases import cavity_table
from cup2d_tpu.ops import pallas_kernels as pk
from cup2d_tpu.parallel import shard_halo as sh

N = 8192           # the cavity cell's width
NB = 2048          # canonical levelStart-5 block bucket (2 x 32 x 32)
F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("x",))


def _compiles_to_mosaic(fn, *args):
    # Mosaic has no f64: the kernels are an f32 contract, compiled the
    # way the chip runs them (the suite's x64 default is a CPU
    # validation setting)
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _advect(bf16, bc):
    def fn(v, dt):
        return pk.fused_advect_heun(v, 1.0 / N, 1e-3, dt, bc=bc,
                                    bf16=bf16, interpret=False)
    return fn, [((2, N, N), F32), ((), F32)]


def _correction():
    def fn(x, p, v, mx, mp, pf):
        return pk.fused_correction(x, p, v, mx, mp, pf, 1.0,
                                   interpret=False)
    return fn, [((1, N, N), F32), ((1, N, N), F32), ((1, 2, N, N), F32),
                ((1,), F32), ((1,), F32), ((1,), F32)]


def _jacobi(dtype):
    def fn(e, r):
        return pk.fused_jacobi_sweeps(e, r, 0.8, 2, interpret=False)
    return fn, [((N, N), dtype), ((N, N), dtype)]


def _mg_down(dtype):
    def fn(r):
        return pk.fused_mg_down(None, r, 0.8, 2, from_zero=True,
                                interpret=False)
    return fn, [((N, N), dtype)]


def _mg_up(dtype):
    def fn(e, r, ec):
        return pk.fused_mg_up(e, r, ec, 0.8, 2, interpret=False)
    return fn, [((N, N), dtype), ((N, N), dtype),
                ((N // 2, N // 2), dtype)]


def _lab_rhs():
    def fn(lab, h, dt):
        return pk.fused_lab_rhs(lab, h, 4e-5, dt, interpret=False)
    return fn, [((NB, 2, 14, 14), F32), ((NB, 1, 1, 1), F32), ((), F32)]


def _block_update():
    def fn(e, r, lap, p_inv):
        return pk.fused_block_jacobi_update(e, r, lap, p_inv,
                                            interpret=False)
    return fn, [((NB, 8, 8), F32)] * 3 + [((64, 64), F32)]


ONE_CHIP_CASES = {
    "fused_advect_heun-f32": lambda: _advect(False, None),
    "fused_advect_heun-bf16": lambda: _advect(True, None),
    "fused_advect_heun-f32-cavity": lambda: _advect(False,
                                                    cavity_table(1.0)),
    "fused_correction": _correction,
    "fused_jacobi_sweeps-f32": lambda: _jacobi(F32),
    "fused_jacobi_sweeps-bf16": lambda: _jacobi(BF16),
    "fused_mg_down-f32": lambda: _mg_down(F32),
    "fused_mg_down-bf16": lambda: _mg_down(BF16),
    "fused_mg_up-f32": lambda: _mg_up(F32),
    "fused_mg_up-bf16": lambda: _mg_up(BF16),
    "fused_lab_rhs": _lab_rhs,
    "fused_block_jacobi_update": _block_update,
}


@pytest.mark.parametrize("case", sorted(ONE_CHIP_CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = ONE_CHIP_CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    _compiles_to_mosaic(fn, *args)


def test_sharded_substage_compiles_on_4_chip_mesh(mesh4):
    """_fused_substage_sharded through its shard_map wrapper at the
    per-shard width nxl = 8192/4, halo exchange included."""
    def fn(v, dt):
        return sh.fused_advect_heun_sharded(
            v, 1.0 / N, 1e-3, dt, mesh4, bc=cavity_table(1.0),
            interpret=False)
    compiled = _compiles_to_mosaic(
        fn,
        jax.ShapeDtypeStruct((2, N, N), F32,
                             sharding=NamedSharding(mesh4,
                                                    P(None, None, "x"))),
        jax.ShapeDtypeStruct((), F32, sharding=NamedSharding(mesh4, P())))
    assert "collective-permute" in compiled.as_text()


def test_jacobi_halo_sweep_compiles_on_4_chip_mesh(mesh4):
    """fused_jacobi_halo_sweep through the strip-tier overlap wrapper."""
    def fn(e, r):
        return sh._overlap_jacobi_sweeps_strip(e, r, 0.8, 2, mesh4,
                                               interpret=False)
    split = NamedSharding(mesh4, P(None, "x"))
    compiled = _compiles_to_mosaic(
        fn, jax.ShapeDtypeStruct((N, N), F32, sharding=split),
        jax.ShapeDtypeStruct((N, N), F32, sharding=split))
    assert "collective-permute" in compiled.as_text()


def _cavity_step_text(one_chip, **grid_kw):
    """The benchmark cell's step (8192^2 f32 cavity, bicgstab +
    multigrid), compiled for the described v5e: the executable's text."""
    grid, compiled = _step_compiled(one_chip, cavity_table(1.0), **grid_kw)
    return grid, compiled.as_text()


def _step_compiled(one_chip, bc, exact_poisson=False, **grid_kw):
    """One uniform 8192^2 f32 step under table ``bc`` (the production
    one unless ``exact_poisson`` asks otherwise), compiled for the
    described v5e."""
    from cup2d_tpu.config import SimConfig
    from cup2d_tpu.uniform import FlowState, UniformGrid

    with jax.enable_x64(False):
        cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                        extent=1.0, dtype="float32", nu=1e-4, cfl=0.4,
                        poisson_tol=1e-4, poisson_tol_rel=1e-3)
        grid = UniformGrid(cfg, 10, bc=bc, **grid_kw)
        assert (grid.ny, grid.nx) == (N, N)

        def field(*lead):
            return jax.ShapeDtypeStruct(lead + (N, N), F32,
                                        sharding=one_chip)

        state = FlowState(vel=field(2), pres=field(), chi=field(),
                          us=field(2), udef=field(2))

        def step(state, dt):
            return grid.step(state, dt, exact_poisson=exact_poisson,
                             obstacle_terms=False)

        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            state, jax.ShapeDtypeStruct((), F32, sharding=one_chip)
        ).compile()
    return grid, compiled


def test_cavity_step_with_fused_legs_compiles_on_v5e(one_chip,
                                                     monkeypatch):
    """The same step as the chip builds it (PR 26): the hierarchy's
    strip tier with ``_on_accel`` held true, so every level's kernel
    is Mosaic-compiled at its own width — the finest 3 of the 11
    levels run the two fused legs (8192, 4096, 2048 wide), in both
    cycles of the Krylov body, and they sit in the ``mg_smooth``
    scope."""
    import re

    monkeypatch.setattr(pk, "_on_accel", lambda: True)
    grid, text = _cavity_step_text(one_chip)     # the hierarchy's pick
    assert grid.smoother_tier == "strip+bf16"
    assert (grid.mg.fused_levels, len(grid.mg.shapes)) == (3, 11)
    calls = re.findall(
        r'custom-call\(.*custom_call_target="tpu_custom_call".*'
        r'op_name="([^"]*)"', text)
    # two cycles a Krylov iteration, each 3 fused levels x 2 legs
    assert len(calls) == 2 * 3 * 2, len(calls)
    assert all("mg_cycle/mg_smooth" in n for n in calls), calls[:3]


def test_cavity_step_keeps_its_scopes_on_v5e(one_chip):
    """The whole cavity step (the benchmark's cell: 8192^2 f32, XLA
    tier, bicgstab + multigrid), compiled for the described v5e: the
    TPU compiler's fusions keep the step's scope names in ``op_name``
    — what the chip's trace then shows as each operation's ``tf_op``
    and benchmark/xplane_meta.py sums device time by. The no-chip
    evidence that the names survive fusion (PR 24)."""
    import re

    from cup2d_tpu import tracing

    grid, text = _cavity_step_text(one_chip)
    assert grid.smoother_tier == "xla"       # a CPU process picks XLA
    fusions = re.findall(r' fusion\(.*op_name="([^"]*)"', text)
    assert len(fusions) > 50

    def scope_of(name):
        return [p for p in name.split("/") if p in tracing.SCOPES]

    named = [n for n in fusions if scope_of(n)]
    assert len(named) >= 0.95 * len(fusions), sorted(
        set(fusions) - set(named))[:10]
    seen = {s for n in named for s in scope_of(n)}
    assert {"advect", "substage0", "substage1", "poisson_rhs",
            "poisson_solve", "krylov", "mg_cycle", "mg_smooth",
            "mg_transfer", "mg_coarse", "project_correct",
            "diag"} <= seen, seen


@pytest.mark.parametrize("exact", [False, "krylov"],
                         ids=["production", "backstop"])
def test_periodic_step_compiles_on_v5e(one_chip, monkeypatch, exact):
    """The doubly-periodic cell's step (``turb2d-8192.solo``: 8192^2
    f32, all four faces wrap), compiled for the described v5e with
    ``_on_accel`` held true. The production step is the direct solve
    the table selects: the ``fft_diag`` scope, its transform pair as
    eight matmul stages (each 8192 axis split 128 x 64), and no scope
    of the hierarchy. The supervision ladder's backstop variant is the
    Krylov solve: the hierarchy still picks the XLA legs (no strip
    form on a wrap), the multigrid scopes are in the executable, with
    the coarse levels' mean removal among the transfers. Neither holds
    a Mosaic call, and arguments plus temporaries leave room on a
    16 GB chip for the snapshot ring beside them."""
    import re

    from cup2d_tpu.cases import periodic_table

    monkeypatch.setattr(pk, "_on_accel", lambda: True)
    monkeypatch.delenv("CUP2D_POIS", raising=False)
    grid, compiled = _step_compiled(one_chip, periodic_table(),
                                    exact_poisson=exact)
    assert (grid.poisson_mode, grid.fftd_by) == ("fftd", "table")
    assert (grid.smoother_tier, grid.mg.fused_levels) == ("xla", 0)
    assert grid.mg._const_null and len(grid.mg.shapes) == 11
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    names = re.findall(r'op_name="([^"]*)"', text)
    seen = {part for n in names for part in n.split("/")}
    step = {"advect", "substage0", "substage1", "poisson_rhs",
            "poisson_solve", "project_correct", "diag"}
    hierarchy = {"krylov", "mg_cycle", "mg_smooth", "mg_transfer",
                 "mg_coarse"}
    if exact == "krylov":
        assert step | hierarchy <= seen, seen
        assert "fft_diag" not in seen
        assert any("mg_transfer" in n and "reduce" in n for n in names)
    else:
        assert step | {"fft_diag"} <= seen, seen
        assert not hierarchy & seen, hierarchy & seen
        # the transform pair as matmuls: two stages an axis, forward
        # and inverse, and no other convolution in the step
        convs = re.findall(r' convolution\(.*op_name="([^"]*)"', text)
        assert len(convs) == 8, convs
        assert all("fft_diag/dot_general" in n for n in convs), convs
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 10e9
