"""Reduction of a profiler trace (``*.xplane.pb``) to the few numbers
the per-layer metrics read. ``jax.profiler.ProfileData`` only — no
TensorFlow import.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per run of
a compiled executable (named ``<module>(<fingerprint>)``) and whose line
``XLA Ops`` has one event per HLO operation that ran; host threads sit
on ``/host:CPU``. All on one clock, in nanoseconds.

The traced window runs from the START of the first run of a step
executable to the START of the last one, so it holds whole step periods
only (``steps`` = runs - 1) and no profiler start-up or shut-down:

busy_s         union of the ``XLA Ops`` intervals inside the window,
               averaged over the chips
window_s       the window's length
device_step_s  time inside step-executable runs in the window / steps
device_ops     the ten operations with most SELF time in the window
               (an operation's time less the operations nested in it:
               a ``while`` holds its body's fusions), by short name
idle_gaps      the window's idle time by what the host was doing: each
               gap between device operations goes to the innermost
               event of the program's Python thread at the gap's middle
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE, OPS_LINE = "XLA Modules", "XLA Ops"


def find_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def module_name(event_name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def short(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``; host
    names are cut to 80 characters."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _self_times(events) -> dict:
    """{short name: seconds} with nested events' time taken out of
    the event that holds them (events of one line nest, never cross)."""
    out, stack = defaultdict(float), []      # stack of [end, name, self]

    def close(until):
        while stack and stack[-1][0] <= until:
            _, name, own = stack.pop()
            out[name] += own / 1e9

    for a, b, n in sorted(events, key=lambda e: (e[0], -e[1])):
        close(a)
        if stack:
            stack[-1][2] -= b - a
        stack.append([b, short(n), b - a])
    close(float("inf"))
    return out


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def _union(intervals):
    """Merged, sorted [start, end) list."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(events, lo, hi):
    return [(max(a, lo), min(b, hi), n) for a, b, n in events
            if b > lo and a < hi]


def reduce_trace(path: str, step_modules) -> dict | None:
    """None where the trace holds no device plane or fewer than two
    runs of a step executable (a CPU rehearsal: nothing to read)."""
    planes = {p.name: {ln.name: ln for ln in p.lines}
              for p in load(path).planes}
    devices = sorted(n for n in planes if DEVICE_PLANE.match(n))
    per_chip, ops_time = [], defaultdict(float)
    window = gaps = None
    for name in devices:
        lines = planes[name]
        if MODULE_LINE not in lines or OPS_LINE not in lines:
            continue
        runs = sorted(e for e in _events(lines[MODULE_LINE])
                      if module_name(e[2]) in step_modules)
        if len(runs) < 2:
            continue
        lo, hi = runs[0][0], runs[-1][0]
        ops = _clip(_events(lines[OPS_LINE]), lo, hi)
        busy = _union((a, b) for a, b, _ in ops)
        in_step = sum(b - a for a, b, _ in _clip(runs, lo, hi))
        per_chip.append({"window": hi - lo, "steps": len(runs) - 1,
                         "busy": sum(b - a for a, b in busy),
                         "in_step": in_step})
        for n, sec in _self_times(ops).items():
            ops_time[n] += sec
        if window is None:          # idle gaps: the first chip's
            window = (lo, hi)
            edges = [lo] + [t for iv in busy for t in iv] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    if not per_chip:
        return None
    n = len(per_chip)
    steps = per_chip[0]["steps"]
    return {
        "planes": devices,
        "window_s": sum(c["window"] for c in per_chip) / n / 1e9,
        "busy_s": sum(c["busy"] for c in per_chip) / n / 1e9,
        "steps": steps,
        "device_step_s": sum(c["in_step"] for c in per_chip) / n / 1e9 / steps,
        "device_ops": [[k, v / n] for k, v in sorted(
            ops_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": _attribute(gaps, planes, devices),
    }


def _attribute(gaps, planes, devices) -> list:
    """[[host event, idle seconds], ...], the ten largest."""
    host = planes.get("/host:CPU", {})
    lines = [ln for name, ln in host.items() if name.startswith("python")] \
        or list(host.values())
    threads = [sorted(_events(ln)) for ln in lines]
    starts = [[e[0] for e in ev] for ev in threads]
    out = defaultdict(float)
    for a, b in gaps:
        mid, best = (a + b) // 2, None
        for ev, st in zip(threads, starts):
            # events of one thread nest: walking back from the last one
            # that began before the middle, the first still open is the
            # innermost
            i = bisect.bisect_right(st, mid) - 1
            while i >= 0 and ev[i][1] <= mid:
                i -= 1
            if i >= 0 and (best is None
                           or ev[i][1] - ev[i][0] < best[1] - best[0]):
                best = ev[i]
        out[short(best[2]) if best else "(no host event)"] += (b - a) / 1e9
    return [[k, v] for k, v in sorted(out.items(),
                                      key=lambda kv: -kv[1])[:10]]


def describe(path: str, limit: int = 12) -> dict:
    """What a trace holds, for looking at one by hand."""
    out = {}
    for p in load(path).planes:
        lines = {}
        for ln in p.lines:
            ev = list(ln.events)
            names = defaultdict(lambda: [0, 0.0])
            for e in ev:
                names[e.name][0] += 1
                names[e.name][1] += e.duration_ns / 1e6
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:limit]
            lines[ln.name] = {"events": len(ev),
                              "top_ms": [[k, c, round(ms, 3)]
                                         for k, (c, ms) in top]}
        out[p.name] = lines
    return out


if __name__ == "__main__":
    import json
    import sys
    print(json.dumps(describe(sys.argv[1]), indent=1))
