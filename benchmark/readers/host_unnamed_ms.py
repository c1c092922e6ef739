"""The host's time a step that no span names: the step period less the
union of the LEAF spans in it (spans that hold no other span), median
over the steps ``host_step_ms`` reads. A container's own time (the
part of ``step`` or ``dispatch`` outside its children) and the time
outside every span (the loop's own code) both count as unnamed; nested
spans are counted once, through their leaves."""
from statistics import median

from benchmark.readers import host_step_ms, span_cover, traced_steps


def read(ctx):
    wait = host_step_ms.waits(ctx)
    named = span_cover.Cover(span_cover.interval(s)
                             for s in span_cover.leaves(ctx["spans"]))
    left = [ms - named.us(a, b) / 1e3
            for _, ms, a, b in traced_steps.quiet_steps(ctx)
            if wait.us(a, b) > 0]
    return median(left) if left else None
