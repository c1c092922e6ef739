"""One straight line through the window's step times against their
Poisson iteration counts: step_ms = base + per_iter * iterations. The
metric's file says which term it reports (``term``: ``per_iter`` or
``base``). The line goes through the median time of each iteration
count, weighted by how many steps had it, so it follows wherever a
solver PR moves the histogram. Counts that fewer than 5 steps had are
left out where two or more others remain (one stalled step is then no
median of its own); nothing where the window holds fewer than two
different counts."""
from benchmark.readers import step_groups


def read(ctx):
    groups = step_groups.groups(ctx)
    full = {it: ms for it, ms in groups.items() if len(ms) >= 5}
    if len(full) >= 2:
        groups = full
    if len(groups) < 2:
        return None
    pts = [(it, step_groups.median(ms), len(ms)) for it, ms in groups.items()]
    n = sum(w for _, _, w in pts)
    mx = sum(w * x for x, _, w in pts) / n
    my = sum(w * y for _, y, w in pts) / n
    per_iter = (sum(w * (x - mx) * (y - my) for x, y, w in pts)
                / sum(w * (x - mx) ** 2 for x, _, w in pts))
    return {"per_iter": per_iter,
            "base": my - per_iter * mx}[ctx["metric"]["term"]]
