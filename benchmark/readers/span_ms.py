"""Time inside the program's spans that a metric's file names
(``spans``), in the window, per window step: 0 where the run has such
spans but none in the window, nothing where it has none at all (a
program from before the span was added)."""


def read(ctx):
    w, names = ctx["window"], set(ctx["metric"]["spans"])
    mine = [s for s in ctx["spans"] if s["name"] in names]
    if not mine:
        return None
    inside = sum(s["dur_us"] for s in mine
                 if w["t_open_us"] <= s["ts_us"] < w["t_close_us"])
    return inside / 1e3 / len(w["steps"])
