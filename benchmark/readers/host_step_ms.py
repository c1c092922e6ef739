"""What the host does itself each step: the step period less the time
inside that period's ``verdict`` and ``pull`` spans, the program's
waits for the device (the lagged verdict's fence on the uniform paths;
the shaped step's one blocking pull, inside ``dispatch``, on the
forest), median over the window's steps outside the profiler's reach.
A period with neither wait is not a step. Nothing where the run has no
such span at all."""
from statistics import median

from benchmark.readers import span_cover, traced_steps

WAITS = ("verdict", "pull")


def waits(ctx) -> span_cover.Cover:
    return span_cover.Cover(span_cover.interval(s) for s in ctx["spans"]
                            if s["name"] in WAITS)


def read(ctx):
    wait = waits(ctx)
    own = []
    for _, ms, a, b in traced_steps.quiet_steps(ctx):
        inside = wait.us(a, b)
        if inside > 0:
            own.append(ms - inside / 1e3)
    return median(own) if own else None
