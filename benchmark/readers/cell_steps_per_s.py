"""All cell updates of the window over all its wall time."""


def read(ctx):
    w = ctx["window"]
    return w["cell_steps"] / w["seconds"]
