"""Device time inside the step executables per traced step."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None else 1e3 * t["device_step_s"]
