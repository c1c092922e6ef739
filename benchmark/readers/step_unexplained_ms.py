"""Wall time per step that the solver's own count does not account
for: mean over the window's steps (outside the profiler's reach) of
max(0, period - (base + per_iter * iterations)), the line exactly as
``step_fit`` takes it and the iterations of each step as
``step_groups`` pairs them. About 0 in a clean run; one step of 2 s in
a 440-step window reads 4-5."""
from benchmark.readers import step_fit, traced_steps


def read(ctx):
    base, per_iter = (step_fit.read({**ctx, "metric": {"term": t}})
                      for t in ("base", "per_iter"))
    if base is None or per_iter is None:
        return None
    iters = {r["step"]: r.get("poisson_iters") for r in ctx["records"]}
    over = [max(0.0, ms - (base + per_iter * iters[s]))
            for s, ms, _, _ in traced_steps.quiet_steps(ctx)
            if iters.get(s) is not None]
    return sum(over) / len(over) if over else None
