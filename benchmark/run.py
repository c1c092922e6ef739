"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run drives the program's own entry, ``cup2d_tpu.__main__.main(argv,
sim_out=box)``, in this process, with the CLI's default tier, solver,
supervision and telemetry. Everything that belongs to one
configuration, one cell or one metric is a data file found by name:

    benchmark/configs/<configuration>.json    sizes, seeded start, reference
    benchmark/workloads/<cell>.json           warm-up, trace range, metrics, limits
    benchmark/metrics/<metric>.json           unit, direction, layer, moves, reader
    benchmark/readers/<reader>.py             read(ctx) -> number or None
    benchmark/references/<reference>.py       the configuration's plain reference

Earlier stdout lines are one JSON object per phase; the LAST line is the
contract's result object and nothing else.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()          # set-up is counted from here

import argparse                   # noqa: E402
import importlib                  # noqa: E402
import json                       # noqa: E402
import os                         # noqa: E402
import shutil                     # noqa: E402
import sys                        # noqa: E402
import threading                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_ROOT = os.path.join(ROOT, "benchmark_out")
RECOVERY_ACTIONS = ("retry", "escalate", "disk_restore", "abort")


def load(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, name + ".json")
    if not os.path.exists(path):
        sys.exit(f"benchmark: no {kind}/{name}.json")
    with open(path) as f:
        return json.load(f)


def phase(name: str, **kw) -> None:
    print(json.dumps({"phase": name, **kw}), flush=True)


def read_jsonl(path: str) -> list:
    rows = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    rows.append(json.loads(line))
    return rows


class Monitor(threading.Thread):
    """Opens the window when the program's step counter passes the
    cell's warm-up and, ``seconds`` later, ends the run through the
    program's own stopping condition: ``cfg.end_time = 0`` (the loop
    re-reads it every step, drains the pending verdict and leaves).
    Touches no JAX. The window's edges are NOT taken from this thread's
    polling but from the ``step`` spans' own clocks."""

    def __init__(self, box, warmup_steps, seconds, deadline_s):
        super().__init__(daemon=True)
        self.box, self.warmup, self.seconds = box, warmup_steps, seconds
        self.deadline = time.time() + deadline_s
        self.t_open = None
        self.timed_out = False
        self.done = threading.Event()

    def run(self):
        while not self.done.is_set():
            now = time.time()
            sim = self.box[0] if self.box else None
            if self.t_open is None and sim is not None \
                    and sim.step_count >= self.warmup:
                self.t_open = now
            if sim is not None and (
                    (self.t_open is not None
                     and now >= self.t_open + self.seconds)
                    or now >= self.deadline):
                self.timed_out = self.t_open is None
                sim.cfg.end_time = 0.0
                return
            time.sleep(0.002)


class Listener:
    """jax.monitoring taps of the harness's own: persistent-cache hits
    and misses, and every backend compile with the time it ended."""

    def __init__(self):
        self.hits = self.misses = 0
        self.compiles = []          # (wall time at end, seconds)

    def install(self):
        import jax

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles.append((time.time(), float(duration)))

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        return self


def window_of(spans, records, cell, cells_per_step):
    """The measured window from the ``step`` spans' own clocks: it opens
    at the start of the first step span at or past the warm-up and
    closes at the start of the last one, so it holds whole steps only,
    each from the start of one step span to the start of the next (loop
    work between steps — regrids, verdict pulls, telemetry — inside)."""
    steps = sorted((s for s in spans if s["name"] == "step"),
                   key=lambda s: s["ts_us"])
    first = next((i for i, s in enumerate(steps)
                  if s.get("step", -1) >= cell["warmup_steps"]), None)
    if first is None or len(steps) - first < 3:
        return None
    win = steps[first:]
    ts = [s["ts_us"] for s in win]
    by_step = {r["step"]: r for r in records}
    done = win[:-1]                 # the last span only closes the window
    return {
        "t_open_us": ts[0], "t_close_us": ts[-1], "ts_us": ts,
        "seconds": (ts[-1] - ts[0]) / 1e6,
        "step_ms": [(b - a) / 1e3 for a, b in zip(ts, ts[1:])],
        "steps": [s.get("step") for s in done],
        # a span is stamped with the count BEFORE its step, a record
        # with the count after it
        "cell_steps": sum(cells_per_step(by_step.get(s.get("step", -1) + 1))
                          for s in done),
        "records": [by_step[s["step"] + 1] for s in done
                    if s.get("step", -1) + 1 in by_step],
    }


def parse(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal at the cell's `rehearsal` sizes; "
                         "its numbers are not device numbers")
    ap.add_argument("--control", default=None,
                    help="run the program under the named control of the "
                         "cell (a lower precision); never used by a check")
    return ap.parse_args(argv)


def find_device(cell, rehearsal):
    """(platform, kind, count, peaks row) as JAX reports them; leaves
    with a message where there is no TPU, no row of peaks for it, or
    fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    peaks = load(".", "peaks")["devices"]
    if not rehearsal:
        if platform != "tpu":
            sys.exit(f"benchmark: no accelerator (JAX reports {platform}); "
                     "a CPU run is a rehearsal and has to say so")
        if kind not in peaks:
            sys.exit(f"benchmark: no peaks for device kind {kind!r}")
    if len(devices) < int(cell["chips"]):
        sys.exit(f"benchmark: {len(devices)} device(s), cell needs "
                 f"{cell['chips']}")
    return platform, kind, len(devices), peaks.get(kind)


def report_phases(win, run, spans, records, ledger, listener, t_import,
                  t_exit, setup_s, warmup_steps):
    """The earlier stdout lines: set-up split, the window, the forest's
    block trail — so that the last line carries only what the contract
    names."""
    from benchmark.readers import step_groups

    first_step_us = min(s["ts_us"] for s in spans if s["name"] == "step")
    iters = {}
    for r in win["records"]:
        k = str(r.get("poisson_iters"))
        iters[k] = iters.get(k, 0) + 1
    ms = sorted(win["step_ms"])
    half = len(ms) // 2
    per_step = win["cell_steps"] / len(win["steps"])
    halves = [per_step * len(part) / (sum(part) / 1e3)
              for part in (win["step_ms"][:half], win["step_ms"][half:])]
    # what the program's spans say went on inside the longest step, and
    # the record of the step it waited for (a span stamped s waits for
    # the step whose record is stamped s): a step of 2 s that its
    # iteration count does not explain (PERF.md) names itself here
    worst = max(range(len(win["step_ms"])), key=win["step_ms"].__getitem__)
    a, b = win["ts_us"][worst], win["ts_us"][worst + 1]
    inside_worst = {}
    for s in spans:
        if s["ts_us"] < b and s["ts_us"] + s.get("dur_us", 0) > a:
            inside_worst[s["name"]] = inside_worst.get(s["name"], 0.0) \
                + (min(b, s["ts_us"] + s.get("dur_us", 0))
                   - max(a, s["ts_us"])) / 1e3
    lo, hi = win["t_open_us"] / 1e6, win["t_close_us"] / 1e6
    inside = [sec for at, sec in listener.compiles if lo <= at <= hi]
    phase("setup", import_s=t_import - T_PROCESS, build_s=run.build_s,
          seeded_state_s=run.seed_s,
          to_first_step_s=first_step_us / 1e6 - T_PROCESS,
          warmup_s=(win["t_open_us"] - first_step_us) / 1e6,
          compile_s=(ledger or {}).get("compile_ms_total", 0.0) / 1e3,
          compiles=len(listener.compiles), cache_hits=listener.hits,
          cache_misses=listener.misses,
          warmup_steps=warmup_steps, setup_s=setup_s)
    phase("window", seconds=win["seconds"], steps=len(win["steps"]),
          first_step=win["steps"][0], last_step=win["steps"][-1],
          cell_steps=win["cell_steps"],
          step_ms={"min": ms[0], "p50": ms[half],
                   "p90": ms[int(0.9 * len(ms))], "max": ms[-1]},
          poisson_iters_hist=iters,
          step_ms_by_iters=step_groups.medians(
              {"window": win, "records": records}),
          half_window_rates=halves,
          longest_step={"step": win["steps"][worst],
                        "ms": win["step_ms"][worst],
                        "span_ms": inside_worst,
                        "waited_for": {k: v for k, v in next(
                            (r for r in records
                             if r["step"] == win["steps"][worst]),
                            {}).items() if k.startswith("poisson_")}},
          compiles_inside=len(inside),
          compile_seconds_inside=sum(inside),
          drain_and_exit_s=t_exit - hi)
    trail = run.block_trail(records)
    if trail:
        phase("blocks", trail=trail)


def read_metrics(cell, want, ctx) -> dict:
    """The cell's metrics of one kind, each from its own reader (which
    sees the metric's own file as ``ctx["metric"]``); a reader that
    finds nothing to read leaves its metric out."""
    metrics = {}
    for name in cell["metrics"]:
        m = load("metrics", name)
        if m["kind"] != want:
            continue
        value = importlib.import_module(
            "benchmark.readers." + m["reader"]).read({**ctx, "metric": m})
        if value is not None:
            metrics[name] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "cup2d_tpu")):
        sys.exit("benchmark: the program (cup2d_tpu/) is not in this "
                 "checkout; nothing to measure")
    sys.path.insert(0, ROOT)
    from benchmark import generator, reduce

    cell = load("workloads", args.workload)
    config = load("configs", cell["config"])
    if args.rehearsal:
        config = generator.merge(config, config.get("rehearsal", {}))
        cell = generator.merge(cell, cell.get("rehearsal", {}))
    if args.control:
        os.environ.update(cell["controls"][args.control]["env"])

    out = os.path.join(OUT_ROOT, args.workload)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    trace_dir = os.path.join(out, "trace")
    w = int(cell["warmup_steps"])
    if args.trace:
        t0 = w + int(cell["trace"]["after_warmup"])
        os.environ["CUP2D_TRACE"] = \
            f"{t0}:{t0 + int(cell['trace']['steps'])}:{trace_dir}"

    platform, kind, count, peak = find_device(cell, args.rehearsal)
    listener = Listener().install()
    from cup2d_tpu.__main__ import main as cli_main
    t_import = time.time()

    run = generator.Run(config, cell, args.seed, out)
    box: list = []
    monitor = Monitor(box, w, args.seconds,
                      deadline_s=float(cell.get("deadline_s", 1000)))
    monitor.start()
    try:
        rc = cli_main(run.argv, sim_out=box)
    finally:
        monitor.done.set()
        monitor.join(5.0)
    t_exit = time.time()
    if rc != 0 or monitor.timed_out or not box:
        sys.exit(f"benchmark: the program left with code {rc}"
                 + (", warm-up never ended" if monitor.timed_out else ""))

    spans = [r for r in read_jsonl(os.path.join(out, "spans.jsonl"))
             if r.get("event") == "span"]
    rows = read_jsonl(os.path.join(out, "metrics.jsonl"))
    records = [r for r in rows if r.get("event") == "metrics"]
    ledger = next((r for r in rows if r.get("event") == "compile_ledger"),
                  None)
    events = read_jsonl(os.path.join(out, "events.jsonl"))
    win = window_of(spans, records, cell, run.cells_per_step)
    if win is None:
        sys.exit("benchmark: the window holds fewer than two whole steps")
    setup_s = win["t_open_us"] / 1e6 - T_PROCESS
    report_phases(win, run, spans, records, ledger, listener, t_import,
                  t_exit, setup_s, w)

    failed_steps = {r["step"] for r in win["records"]
                    if r.get("poisson_converged") is False}
    failed_steps |= {e.get("step") for e in events
                     if e.get("action") in RECOVERY_ACTIONS
                     and (e.get("step") or 0) >= win["steps"][0]}

    import jax
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.local_devices())

    trace = None
    if args.trace:
        path = reduce.find_xplane(trace_dir)
        if path is not None:
            trace = reduce.reduce_trace(path, cell["trace"]["step_modules"])
        phase("trace", file=path, found=trace is not None,
              **({k: trace[k] for k in ("window_s", "busy_s", "steps",
                                        "device_step_s", "planes")}
                 if trace else {}))

    # the program's state is freed before the reference runs: a
    # process's memory peak never falls again, and it was read above
    grid = run.grid_of(box[0])
    box.clear()
    ctx = {"config": config, "cell": cell, "window": win, "spans": spans,
           "records": records, "ledger": ledger, "trace": trace,
           "setup_s": setup_s, "peak": peak}
    metrics = read_metrics(
        cell, "per_layer" if args.trace else "end_to_end", ctx)

    t_ref = time.time()
    compared = run.compare(records, grid)
    phase("reference", seconds=time.time() - t_ref,
          steps=cell.get("reference_steps"))

    def holds(c):
        return c["value"] is not None and c["value"] <= c["limit"]

    correct = bool(compared) and all(holds(c) for c in compared.values())
    device = {"platform": platform, "kind": kind, "count": count,
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": correct, "attempted": len(win["steps"]),
              "failed": len(failed_steps), "metrics": metrics,
              "device": device}
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    if args.rehearsal:
        result["rehearsal"] = True
    result["compared"] = compared     # last in the line, by contract
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if holds(c) else 'FAIL'}", file=sys.stderr)
    print(f"correct {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
