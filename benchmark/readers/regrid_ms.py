"""Median duration of the window's regrid spans (forest only)."""
import statistics


def read(ctx):
    w = ctx["window"]
    d = [s["dur_us"] / 1e3 for s in ctx["spans"] if s["name"] == "regrid"
         and w["t_open_us"] <= s["ts_us"] < w["t_close_us"]]
    return statistics.median(d) if d else None
