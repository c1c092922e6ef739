"""Share of the traced window's device-idle time that lies inside a
``cup2d:*`` host span other than ``cup2d:step`` itself: how much of
the chip's idling the program's own spans name. Nothing where the
trace holds no device plane or none of the program's spans (a program
that does not annotate)."""
from benchmark import reduce, xplane_meta
from benchmark.readers import traced_steps


def read(ctx):
    path = traced_steps.xplane(ctx) if ctx["trace"] is not None else None
    if path is None:
        return None
    spans = reduce._union(
        (a, b) for a, b, name in xplane_meta.host_events(path)
        if name != "cup2d:step")
    wins = xplane_meta.device_windows(
        path, ctx["cell"]["trace"]["step_modules"])
    if not spans or not wins:
        return None
    p, lo, hi = wins[0][:3]             # idle gaps: the first chip's
    busy = reduce._union(
        (a, b) for a, b, _ in reduce._clip(
            xplane_meta._line_events(p, reduce.OPS_LINE), lo, hi))
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    idle = named = 0.0
    for a, b in zip(edges[::2], edges[1::2]):
        idle += b - a
        named += sum(min(b, y) - max(a, x) for x, y in spans
                     if y > a and x < b)
    return 100.0 * named / idle if idle > 0 else None
