"""What the host does itself each step: the step period less the time
inside that period's ``verdict`` span (the wait for the device), median
over the window's steps outside the profiler's reach."""
from statistics import median

from benchmark.readers import traced_steps


def read(ctx):
    waits = sorted((s["ts_us"], s["dur_us"]) for s in ctx["spans"]
                   if s["name"] == "verdict")
    own = []
    for _, ms, a, b in traced_steps.quiet_steps(ctx):
        inside = [d for t, d in waits if a <= t < b]
        if inside:              # a period with no wait is not a step
            own.append(ms - sum(inside) / 1e3)
    return median(own) if own else None
