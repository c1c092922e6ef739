"""Device self time under the program's named scopes, per traced step
or per multigrid cycle: one reader for every metric whose file names
its ``scopes`` (summed, nested scopes included) and its divisor
(``per``: ``step``, or ``loops_of`` a scope — the executions, in the
traced window, of the loops whose operations lie in that scope: the
coarsest level's sweep loop of ``mg_coarse`` runs once per multigrid
cycle). Only operations inside the step
executables count, so the scopes add up to ``device_step_ms``.

The first metric read also writes the whole table — every scope path,
``(unscoped)``, the sum beside ``device_step_ms``, the loops counted
in the trace beside the records' ``precond_cycles`` — to stderr and to
``benchmark_out/<cell>/scopes.json`` (stdout belongs to the contract).
Nothing where there is no device trace or the program has no scope
vocabulary (``cup2d_tpu.tracing.SCOPES``)."""
import functools
import json
import os
import sys

from benchmark import xplane_meta
from benchmark.readers import traced_steps


@functools.lru_cache(maxsize=2)
def _table(path, step_modules, vocabulary, whole_runs=True):
    return xplane_meta.self_ms_by_scope(
        path, step_modules, vocabulary, inside=step_modules,
        whole_runs=whole_runs)


def table(ctx):
    from cup2d_tpu import tracing
    vocabulary = getattr(tracing, "SCOPES", None)
    path = traced_steps.xplane(ctx) if ctx["trace"] is not None else None
    if not vocabulary or path is None:
        return None
    modules = tuple(ctx["cell"]["trace"]["step_modules"])
    t = _table(path, modules, tuple(vocabulary))
    if t is not None and "written" not in t:
        t["written"] = _write(ctx, t, _table(
            path, modules, tuple(vocabulary), whole_runs=False))
    return t


def _write(ctx, t, reduction) -> str:
    cell, steps = ctx["cell"], t["steps"]
    first = int(cell["warmup_steps"]) + int(cell["trace"]["after_warmup"])
    by_step = {r["step"]: r for r in ctx["records"]}
    # a record is stamped with the count AFTER its step; the first
    # whole run of the trace is the step the window opens with
    recorded = [by_step[s] for s in range(first + 1, first + 1 + steps)
                if s in by_step]
    per_step = {k: v / steps for k, v in sorted(
        t["self_ms"].items(), key=lambda kv: -kv[1])}
    out = {
        "steps": steps,
        "first_run_in_flight": t["first_run_in_flight"],
        "self_ms_per_step": per_step,
        "sum_ms_per_step": sum(per_step.values()),
        # the same sum over the reduction's own window, which counts a
        # first run in flight as a step: this one equals device_step_ms
        "reduction_window": {
            "steps": reduction["steps"],
            "sum_ms_per_step": sum(reduction["self_ms"].values())
            / reduction["steps"],
            "device_step_ms": 1e3 * ctx["trace"]["device_step_s"]},
        "loops": t["loops"],
        "module_ms_per_step": {
            k: v / steps for k, v in xplane_meta.module_ms(
                traced_steps.xplane(ctx),
                cell["trace"]["step_modules"]).items() if k != "steps"},
        "poisson_iters_recorded": [r.get("poisson_iters")
                                   for r in recorded],
        "precond_cycles_recorded": sum(r.get("precond_cycles") or 0
                                       for r in recorded),
    }
    path = os.path.join(traced_steps.out_dir(ctx), "scopes.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("scopes " + json.dumps(out), file=sys.stderr, flush=True)
    return path


def read(ctx):
    t, m = table(ctx), ctx["metric"]
    if t is None:
        return None
    ms = sum(xplane_meta.under(t["self_ms"], s) for s in m["scopes"])
    if ms <= 0:
        return None             # no operation carries the scope
    if m["per"] == "step":
        return ms / t["steps"]
    loops = t["loops"].get(m["per"]["loops_of"], 0)
    return ms / loops if loops else None
