"""Offline field renderer: per-cell quads colored by |attr|^2 -> PNG.

The reference ships an offline plotter with the same contract
(`/root/reference/post.py`: memmap the .xyz.raw/.attr.raw pair, draw
each cell's rectangle colored by the squared magnitude of its
attribute, save PNG at high dpi). This renderer reads the identical
byte-compatible dump format through `io.read_dump` and draws the quads
as one matplotlib PolyCollection — mixed-level AMR dumps render
naturally because the format is per-cell quads (each cell carries its
own geometry, so resolution can vary freely).

``--metrics`` switches to the telemetry reporter: summarize a run's
``metrics.jsonl`` stream (profiling.MetricsRecorder schema) as one JSON
line per file — solver iteration stats, dt/wall distributions, energy
endpoints, divergence peak, recompile/transfer counters, final AMR
shape, serving latency percentiles and the compile blame ledger.
Truncated/torn rows (a SIGKILL'd run's last line) are counted as
``truncated_records``, never raised.

``--trace`` exports a run's flushed span timeline (``spans.jsonl``
plus its per-process ``.pN`` siblings and rotated segments, the
flight-recorder stream — tracing.py) to Chrome/Perfetto
``trace.json``: one track per process, one per client session. Load at
https://ui.perfetto.dev or chrome://tracing.

Usage:  python -m cup2d_tpu.post out/vel.0000001234.xdmf2 [...]
        python -m cup2d_tpu.post --metrics out/metrics.jsonl [...]
        python -m cup2d_tpu.post --trace out/spans.jsonl [...]
"""

from __future__ import annotations

import json
import sys

import numpy as np

from .io import read_dump


def render(path: str, png_path: str | None = None,
           cmap: str = "viridis", dpi: int = 400) -> str:
    """Render one dump (any of the .xdmf2/.xyz.raw/.attr.raw paths or
    the bare prefix) to PNG; returns the written path."""
    import matplotlib
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt
    from matplotlib.collections import PolyCollection

    for suf in (".xdmf2", ".attr.raw", ".xyz.raw"):
        if path.endswith(suf):
            path = path[: -len(suf)]
    time, xyz, attr = read_dump(path)
    # xyz: [ncell, 4, 2] quad corners; attr: [ncell, 3] (u, v, 0)
    val = np.sum(attr.astype(np.float64) ** 2, axis=1)
    fig, ax = plt.subplots()
    pc = PolyCollection(xyz, array=val, cmap=cmap, edgecolors="none")
    ax.add_collection(pc)
    ax.set_xlim(float(xyz[..., 0].min()), float(xyz[..., 0].max()))
    ax.set_ylim(float(xyz[..., 1].min()), float(xyz[..., 1].max()))
    ax.set_aspect("equal")
    ax.set_title(f"t = {time:g}")
    fig.colorbar(pc, ax=ax, shrink=0.7)
    out = png_path or (path + ".png")
    fig.savefig(out, dpi=dpi, bbox_inches="tight")
    plt.close(fig)
    return out


def metrics_summary(path: str) -> dict:
    """Aggregate one metrics.jsonl stream (profiling.summarize_metrics
    + the source path). A serving run's per-client streams (schema v7:
    a ``clients/`` directory next to the metrics file, one
    ``<client>.jsonl`` each — profiling.ClientStreams) are summarized
    per client under ``clients``."""
    import os

    from .profiling import (load_metrics, load_metrics_report,
                            summarize_client, summarize_metrics)

    records, torn = load_metrics_report(path)
    out = summarize_metrics(records)
    out["truncated_records"] = torn
    out["source"] = path
    cdir = os.path.join(os.path.dirname(os.path.abspath(path)),
                        "clients")
    if os.path.isdir(cdir):
        out["clients"] = {
            fn[:-len(".jsonl")]: summarize_client(
                load_metrics(os.path.join(cdir, fn)))
            for fn in sorted(os.listdir(cdir))
            if fn.endswith(".jsonl")}
    return out


def trace_export(path: str, out_path: str | None = None) -> str:
    """Export a flight-recorder span stream to Perfetto trace JSON.

    ``path`` is the process-0 ``spans.jsonl``; per-process siblings
    (``spans.jsonl.pN`` — EventLog all_writers mode) and rotated
    segments of each are folded in automatically, so one command
    renders a whole multi-process run. Returns the written path."""
    import glob
    import os
    import re

    from .profiling import load_metrics
    from .tracing import spans_to_perfetto

    # exactly the live per-process siblings: rotated segments (.pN.M,
    # or .M on the base path) are folded in by load_metrics itself
    sibs = [p for p in sorted(glob.glob(path + ".p[0-9]*"))
            if re.fullmatch(r"\.p\d+", p[len(path):])]
    rows = []
    for p in [path] + sibs:
        try:
            rows.extend(load_metrics(p))
        except FileNotFoundError:
            continue
    trace = spans_to_perfetto(rows)
    out = out_path or os.path.join(
        os.path.dirname(os.path.abspath(path)) or ".", "trace.json")
    with open(out, "w") as f:
        json.dump(trace, f)
    return out


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    if not args:
        print("usage: python -m cup2d_tpu.post <dump>[.xdmf2] ... | "
              "--metrics <metrics.jsonl> ... | "
              "--trace <spans.jsonl> ...", file=sys.stderr)
        return 2
    if args[0] == "--metrics":
        if not args[1:]:
            print("usage: python -m cup2d_tpu.post --metrics "
                  "<metrics.jsonl> ...", file=sys.stderr)
            return 2
        for a in args[1:]:
            print(json.dumps(metrics_summary(a)))
        return 0
    if args[0] == "--trace":
        if not args[1:]:
            print("usage: python -m cup2d_tpu.post --trace "
                  "<spans.jsonl> ...", file=sys.stderr)
            return 2
        for a in args[1:]:
            print(trace_export(a))
        return 0
    for a in args:
        print(render(a))
    return 0


if __name__ == "__main__":
    sys.exit(main())
