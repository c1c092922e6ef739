"""The step's parts named where they run (tracing.SCOPES, PR 24).

- Device side: every step core (uniform, fleet, forest) is traced
  under the one scope vocabulary, so the compiled program's ``op_name``
  metadata — what a TPU trace shows as each operation's ``tf_op`` —
  resolves to a scope for (nearly) every instruction; and a scope is
  metadata ONLY: the lowered program is byte-identical with
  ``tracing.scope`` replaced by a null context.
- Host side: inside a ``CUP2D_TRACE`` window the flight recorder's
  spans are in the profiler's trace (``cup2d:<name>``), once per traced
  step; outside it no annotation is ever constructed and the module
  flag is down.
"""

import contextlib
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup2d_tpu import cases, tracing
from cup2d_tpu.config import SimConfig
from cup2d_tpu.profiling import TraceWindow

STEP_SCOPES = ("advect/substage0", "advect/substage1", "poisson_rhs",
               "krylov", "mg_cycle", "mg_smooth", "mg_coarse",
               "project_correct", "diag")


def _abstract(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if isinstance(x, (jax.Array, np.ndarray)) else x, tree)


# -- the three step cores, each as (traceable fn, abstract args) -------

def _uniform_step(exact):
    sim = cases.build_cavity(level=3)           # 64^2, the wall table
    g = sim.grid

    def step(state, dt):
        return g.step(state, dt, exact_poisson=exact,
                      obstacle_terms=False)
    return step, _abstract((sim.state, jnp.asarray(1e-3, g.dtype)))


def _fleet_step(exact=False):
    from cup2d_tpu.fleet import FleetSim
    cfg = SimConfig(bpdx=1, bpdy=1, level_max=1, level_start=0,
                    extent=1.0, nu=1e-3, cfl=0.4, dtype="float64")
    sim = FleetSim(cfg, level=2, members=2)

    def step(state, dt):
        return sim._step_impl(state, dt, None, exact_poisson=exact)
    return step, _abstract((sim.state, jnp.full((2,), 1e-3, jnp.float64)))


def _forest_step(exact=False):
    """One obstacle-free AMRSim step on a fresh two-level forest; the
    arguments are the ones the driver itself hands its step jit."""
    from cup2d_tpu.amr import AMRSim
    cfg = SimConfig(bpdx=2, bpdy=2, level_max=3, level_start=1,
                    extent=1.0, nu=1e-3, cfl=0.4, dtype="float64",
                    rtol=1e9, ctol=-1.0)
    sim = AMRSim(cfg)
    sim.step_count = 20                         # production solve
    seen = {}
    real = sim._step_jit

    def capture(*args, **kwargs):
        seen["args"] = _abstract(args)
        return real(*args, **kwargs)

    sim._step_jit = capture
    sim.step_once(dt=1e-3)
    assert "args" in seen

    def step(*args):
        return sim._step_impl(*args, exact_poisson=exact)
    return step, seen["args"]


def _forest_fish_step():
    """One AMRSim step WITH a body — rasterisation, penalisation, the
    flow step and the surface forces in the one megastep the forest's
    cell drives; the arguments are the driver's own."""
    from cup2d_tpu.amr import AMRSim
    from cup2d_tpu.models import FishShape
    cfg = SimConfig(bpdx=2, bpdy=1, level_max=4, level_start=2,
                    extent=2.0, nu=4e-5, lam=1e7, cfl=0.5, rtol=2.0,
                    ctol=1.0, dtype="float32")
    sim = AMRSim(cfg, shapes=[FishShape(0.4, 1.0, 0.5, 0.0, cfg.min_h)])
    sim.initialize()
    sim.step_count = 20                         # production solve
    seen = {}
    real = sim._mega_jit

    def capture(*args, **kwargs):
        seen["args"], seen["kw"] = _abstract(args), kwargs
        return real(*args, **kwargs)

    sim._mega_jit = capture
    sim.step_once()
    assert seen["kw"] == {"exact_poisson": False, "with_forces": True}

    def step(*args):
        return sim._megastep_impl(*args, **seen["kw"])
    return step, seen["args"]


CORES = {"uniform": lambda: _uniform_step(False),
         "uniform-exact": lambda: _uniform_step(True),
         "fleet": _fleet_step,
         "forest": _forest_step,
         "forest-fish": _forest_fish_step}
BODY_SCOPES = ("rasterize", "penalize", "forces")


def _op_names(step, args):
    text = jax.jit(step).lower(*args).compile().as_text()
    return [n for n in re.findall(r'op_name="([^"]*)"', text)
            if n.startswith("jit(step)/")]


def _scoped(name):
    return any(part in tracing.SCOPES for part in name.split("/")[1:])


# (a) the vocabulary covers the step
@pytest.mark.parametrize("core", sorted(CORES))
def test_scopes_cover_the_step(core):
    step, args = CORES[core]()
    names = _op_names(step, args)
    assert len(names) > 100
    covered = sum(map(_scoped, names)) / len(names)
    assert covered >= 0.95, (covered, sorted(
        {n for n in names if not _scoped(n)})[:20])
    joined = "\n".join(names)
    for scope in STEP_SCOPES:
        if core.startswith("forest") and scope == "mg_coarse":
            continue    # no coarse correction on a forest this small
        assert f"/{scope}/" in joined, scope
    assert "/poisson_solve/" in joined
    for scope in BODY_SCOPES:
        assert (f"/{scope}/" in joined) is (core == "forest-fish"), scope


# (b) a scope is metadata only
@pytest.mark.parametrize("core", sorted(CORES))
def test_scopes_change_no_operation(core, monkeypatch):
    step, args = CORES[core]()

    def lowered():
        # a fresh callable each time: jax caches traces by function
        return jax.jit(lambda *a: step(*a)).lower(*args)

    with_scopes = lowered()
    assert "poisson_solve" in with_scopes.as_text(debug_info=True)
    monkeypatch.setattr(tracing, "scope",
                        lambda name: contextlib.nullcontext())
    without = lowered()
    assert "poisson_solve" not in without.as_text(debug_info=True)
    # locations and metadata stripped (as_text's default)
    assert with_scopes.as_text() == without.as_text()


# (c) the spans on the profiler's clock, inside a window and only there
CLI = ["-bpdx", "1", "-bpdy", "1", "-levelMax", "1", "-levelStart", "0",
       "-Rtol", "2", "-Ctol", "1", "-extent", "1", "-CFL", "0.4",
       "-tend", "1e9", "-lambda", "1e6", "-nu", "0.001",
       "-poissonTol", "1e-3", "-poissonTolRel", "1e-2",
       "-maxPoissonRestarts", "0", "-maxPoissonIterations", "100",
       "-AdaptSteps", "20", "-tdump", "0", "-level", "3",
       "-dtype", "float64", "-case", "cavity", "-maxSteps", "16"]


def _host_spans(logdir):
    """[(line, start ns, end ns, name)] of the cup2d:* events."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(line.name, e.start_ns, e.start_ns + e.duration_ns,
                     e.name) for e in line.events
                    if e.name.startswith("cup2d:")]
    return sorted(out, key=lambda e: e[1])


def test_spans_enter_the_trace_inside_a_window(tmp_path, monkeypatch):
    from cup2d_tpu.__main__ import main
    monkeypatch.delenv("CUP2D_FAULTS", raising=False)
    logdir = str(tmp_path / "trace")
    monkeypatch.setenv("CUP2D_TRACE", f"12:15:{logdir}")
    out = tmp_path / "run"
    assert main(CLI + ["-output", str(out)]) == 0
    assert not tracing.profiling()          # down again after _stop

    spans = _host_spans(logdir)
    assert len({line for line, *_ in spans}) == 1    # one host line
    by_name = {}
    for _, a, b, name in spans:
        by_name.setdefault(name, []).append((a, b))
    for name in ("step", "dispatch", "verdict", "snapshot", "record"):
        assert len(by_name["cup2d:" + name]) == 3, (name, by_name.keys())
    steps = by_name["cup2d:step"]
    for name in ("dispatch", "verdict", "snapshot"):
        for a, b in by_name["cup2d:" + name]:
            assert any(sa <= a and b <= sb for sa, sb in steps), name
    # the loop records a step right after its `step` span closes
    for (_, step_end), (a, _) in zip(steps, by_name["cup2d:record"]):
        assert a >= step_end
    for (a, _), (_, rec_end) in zip(steps[1:], by_name["cup2d:record"]):
        assert rec_end <= a

    # spans.jsonl: a verdict says what it waited for
    rows = [json.loads(ln) for ln in open(out / "spans.jsonl")]
    verdicts = [r for r in rows if r.get("name") == "verdict"]
    assert verdicts and all(
        {"iters", "cycles", "exact"} <= set(r) for r in verdicts)
    assert [r["exact"] for r in verdicts][:10] == [True] * 10
    assert any(r["name"] == "record" for r in rows)


def test_no_annotation_outside_a_window(tmp_path, monkeypatch):
    from cup2d_tpu.__main__ import main
    monkeypatch.delenv("CUP2D_FAULTS", raising=False)
    monkeypatch.delenv("CUP2D_TRACE", raising=False)
    made = []

    def forbidden(*args, **kwargs):
        made.append(args)
        raise AssertionError("an annotation outside a trace window")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", forbidden)
    assert main(CLI[:-1] + ["6", "-output", str(tmp_path / "run")]) == 0
    assert made == [] and not tracing.profiling()


def test_window_flag_follows_the_window(tmp_path):
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.ones(8)
    tw = TraceWindow(1, 2, str(tmp_path / "a"))
    tw.maybe_start(0)
    assert not tracing.profiling()
    tw.maybe_start(1)
    assert tracing.profiling()
    x = f(x)
    tw.maybe_stop(2)
    assert not tracing.profiling() and tw.done
    # a window still open at loop exit: close() lowers the flag too
    tw = TraceWindow(0, 9, str(tmp_path / "b"))
    tw.maybe_start(0)
    assert tracing.profiling()
    f(x)
    tw.close()
    assert not tracing.profiling()
