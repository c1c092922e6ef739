"""Device time inside the runs of the executables the metric's file
names (``modules``), per traced step: the reduction's own window."""
from benchmark import xplane_meta
from benchmark.readers import traced_steps


def read(ctx):
    path = traced_steps.xplane(ctx) if ctx["trace"] is not None else None
    t = path and xplane_meta.module_ms(
        path, ctx["cell"]["trace"]["step_modules"])
    if not t:
        return None
    return sum(t.get(m, 0.0) for m in ctx["metric"]["modules"]) \
        / t["steps"] or None
