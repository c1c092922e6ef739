"""Traced wall per step less the device time of the step executables."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    return 1e3 * (t["window_s"] / t["steps"] - t["device_step_s"])
