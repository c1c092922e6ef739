"""The window's step times grouped by Poisson iteration count (shared by
the readers that hold the count still; not a metric itself).

Under the lagged verdict a ``step`` span dispatches its own step and
then waits for the step BEFORE it, so the time from one span's start to
the next is the device time of the previous step: the span stamped
``s`` (the count before its step) is grouped by the record stamped
``s`` (the count after the previous step)."""
from statistics import median  # noqa: F401  (readers take it from here)


def groups(ctx) -> dict:
    """{iteration count: [step ms, ...]} over every step of the window."""
    w = ctx["window"]
    by_step = {r["step"]: r.get("poisson_iters") for r in ctx["records"]}
    out = {}
    for s, ms in zip(w["steps"], w["step_ms"]):
        it = by_step.get(s)
        if it is not None:
            out.setdefault(int(it), []).append(ms)
    return out


def medians(ctx, min_steps: int = 5) -> dict:
    """The median step time of each count with ``min_steps`` steps or
    more (the ``window`` phase line)."""
    return {it: median(ms) for it, ms in groups(ctx).items()
            if len(ms) >= min_steps}
