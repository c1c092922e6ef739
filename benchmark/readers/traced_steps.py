"""Where a cell's run keeps its files, and which of the window's steps
ran while the profiler was starting, tracing or writing its file
(shared by the readers of this PR's metrics; not a metric itself).

A traced run's window still holds the traced steps. Starting a trace
and, far more, stopping it (hundreds of ms of writing) stall the loop,
so the readers that time the loop's own steps leave out every step
from ``trace_start`` to one past ``trace_stop`` (events.jsonl; both are
stamped with the program's step counter)."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def out_dir(ctx) -> str:
    return os.path.join(ROOT, "benchmark_out", ctx["cell"]["name"])


def xplane(ctx):
    """The run's trace file, found as ``run.py`` finds it, or None."""
    from benchmark import reduce
    return reduce.find_xplane(os.path.join(out_dir(ctx), "trace"))


def disturbed(ctx) -> range:
    """The step counts (as the ``step`` spans stamp them) whose period
    holds the profiler's start-up, tracing or shut-down."""
    start = stop = None
    path = os.path.join(out_dir(ctx), "events.jsonl")
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                row = json.loads(line) if line.strip() else {}
                if row.get("event") == "trace_start" and start is None:
                    start = row.get("step")
                elif row.get("event") == "trace_stop":
                    stop = row.get("step")
    if start is None:
        return range(0)
    # an open window (no stop yet) disturbs everything after its start
    return range(int(start), (int(stop) if stop is not None
                              else 2 ** 62) + 1)


def quiet_steps(ctx) -> list:
    """(step count, period ms, start us, end us) of the window's steps
    outside the profiler's reach."""
    w, skip = ctx["window"], disturbed(ctx)
    return [(s, ms, a, b) for s, ms, a, b in zip(
        w["steps"], w["step_ms"], w["ts_us"], w["ts_us"][1:])
        if s not in skip]
