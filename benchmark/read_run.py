"""The per-layer metrics of a FINISHED run, read again from its files:

    python3 benchmark/read_run.py --workload <cell> [--rehearsal] [--metrics a,b]

``benchmark/run.py`` reports the metrics its cell file lists. This
reads the same run's ``benchmark_out/<cell>/`` (spans, records, events,
the trace of a ``--trace 1`` run) with the same window and the same
readers, and evaluates EVERY per-layer metric file under
``benchmark/metrics/`` (or those named), the ones no cell lists yet
included — PR 24's scope and span metrics wait there for the
``benchmark`` PR that appends them to the cell's list (PERF.md §7). No
JAX device is touched; one JSON line on stdout, the scope table on
stderr.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import generator, reduce, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--metrics", default=None)
    args = ap.parse_args(argv)
    cell = run.load("workloads", args.workload)
    config = run.load("configs", cell["config"])
    if args.rehearsal:
        config = generator.merge(config, config.get("rehearsal", {}))
        cell = generator.merge(cell, cell.get("rehearsal", {}))
    out = os.path.join(run.OUT_ROOT, args.workload)
    spans = [r for r in run.read_jsonl(os.path.join(out, "spans.jsonl"))
             if r.get("event") == "span"]
    rows = run.read_jsonl(os.path.join(out, "metrics.jsonl"))
    records = [r for r in rows if r.get("event") == "metrics"]
    win = run.window_of(spans, records, cell, lambda rec: 0)
    if win is None:
        sys.exit(f"read_run: no finished run under {out}")
    path = reduce.find_xplane(os.path.join(out, "trace"))
    ctx = {"config": config, "cell": cell, "window": win, "spans": spans,
           "records": records, "setup_s": None, "peak": None,
           "ledger": next((r for r in rows
                           if r.get("event") == "compile_ledger"), None),
           "trace": path and reduce.reduce_trace(
               path, cell["trace"]["step_modules"])}
    names = args.metrics.split(",") if args.metrics else sorted(
        os.path.basename(p)[:-5]
        for p in glob.glob(os.path.join(HERE, "metrics", "*.json")))
    metrics = {}
    for name in names:
        m = run.load("metrics", name)
        if m["kind"] != "per_layer":
            continue
        value = importlib.import_module(
            "benchmark.readers." + m["reader"]).read({**ctx, "metric": m})
        if value is not None:
            metrics[name] = {"value": value, "unit": m["unit"],
                             "listed": name in cell["metrics"]}
    print(json.dumps({"workload": args.workload, "trace": path,
                      "steps": len(win["steps"]), "metrics": metrics}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
