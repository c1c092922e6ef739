"""The step's compulsory HBM time over its measured device time.

Uniform boxes only: the byte count (benchmark/bytes_model.py) knows no
forest. The iteration count is that of the TRACED steps (the records
stamped with the counts after them), since a step's device time moves
by a quarter with each iteration. Returns nothing where there is no
trace, no peak or no grid."""
from benchmark import bytes_model


def read(ctx):
    t, peak, g = ctx["trace"], ctx["peak"], ctx["config"]["grid"]
    if t is None or peak is None or "ny" not in g:
        return None
    cell = ctx["cell"]
    first = int(cell["warmup_steps"]) + int(cell["trace"]["after_warmup"])
    iters = [r["poisson_iters"] for r in ctx["records"]
             if first < r["step"] <= first + t["steps"]
             and r.get("poisson_iters") is not None]
    if not iters:
        return None
    least_s = bytes_model.step_bytes(
        g["ny"], g["nx"], sum(iters) / len(iters)) / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / t["device_step_s"]
