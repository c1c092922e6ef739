"""Mean Poisson iterations per step over the window's records."""


def read(ctx):
    it = [r["poisson_iters"] for r in ctx["window"]["records"]
          if r.get("poisson_iters") is not None]
    return sum(it) / len(it) if it else None
