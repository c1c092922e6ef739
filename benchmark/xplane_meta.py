"""What ``jax.profiler.ProfileData`` does not hand out: the stats of a
trace's *event metadata*. On a TPU plane every ``XLA Ops`` event's
metadata carries ``tf_op``, the operation's JAX name
(``jit(step)/poisson_solve/while/body/krylov/mg_cycle/mg_smooth/add``):
the path of ``jax.named_scope``s it was traced under, which is where
the program's scope vocabulary (``cup2d_tpu.tracing.SCOPES``) lands.
Read from the protobuf's wire format — varints and length-delimited
fields of XSpace / XPlane / XLine / XEvent / XEventMetadata / XStat /
XStatMetadata (tsl/profiler/protobuf/xplane.proto) — with no
TensorFlow and no generated code.

    tf_ops(path)                         {plane: {operation: tf_op}}
    scope_path(tf_op, vocabulary)        the vocabulary names on the path
    self_ms_by_scope(path, step_modules) device self time by scope
    module_ms(path, step_modules)        device time by executable
    host_events(path)                    the program's cup2d:* spans

The window and the self-time rule are the reduction's own
(``reduce.reduce_trace``): first to last start of a step executable,
an operation's time less the operations nested in it — but for a first
run caught in flight, which is left out (``device_windows``).
"""

from __future__ import annotations

import bisect
import functools
import gzip
from collections import defaultdict

from benchmark import reduce

UNSCOPED = "(unscoped)"


def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """(field number, wire type, value) over one message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"xplane: wire type {wire}")
        yield num, wire, val


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    """The value message of one map<int64, Message> entry."""
    return next((v for n, _, v in _fields(entry) if n == 2), b"")


def _plane(buf) -> dict:
    """One XPlane: its name, its lines' events as (line name,
    timestamp_ns, [(metadata id, offset_ps, duration_ps)]), and per
    event-metadata id the name and the string stats by stat name."""
    name, lines, stat_names, raw_meta = "", [], {}, []
    for num, _, val in _fields(buf):
        if num == 2:
            name = _text(val)
        elif num == 3:
            lname, ts, events = "", 0, []
            for n, _, v in _fields(val):
                if n == 2:
                    lname = _text(v)
                elif n == 3:
                    ts = v
                elif n == 4:
                    ev = {1: 0, 2: 0, 3: 0}
                    for k, w, x in _fields(v):
                        if k in ev and w == 0:
                            ev[k] = x
                    events.append((ev[1], ev[2], ev[3]))
            lines.append((lname, ts, events))
        elif num == 4:
            raw_meta.append(_map_value(val))
        elif num == 5:
            sid, sname = 0, ""
            for n, w, v in _fields(_map_value(val)):
                if n == 1 and w == 0:
                    sid = v
                elif n == 2:
                    sname = _text(v)
            stat_names[sid] = sname
    meta = {}
    for m in raw_meta:
        mid, mname, stats = 0, "", {}
        for n, w, v in _fields(m):
            if n == 1 and w == 0:
                mid = v
            elif n == 2:
                mname = _text(v)
            elif n == 5:
                sid = text = None
                for k, w2, x in _fields(v):
                    if k == 1 and w2 == 0:
                        sid = x
                    elif k == 5:
                        text = _text(x)
                    elif k == 7 and w2 == 0:     # a string by reference
                        text = stat_names.get(x, "")
                if sid is not None and text is not None:
                    stats[stat_names.get(sid, str(sid))] = text
        meta[mid] = (mname, stats)
    return {"name": name, "lines": lines, "meta": meta}


@functools.lru_cache(maxsize=4)
def planes(path: str) -> tuple:
    """Every plane of the file, parsed once per file."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = memoryview(f.read())
    return tuple(_plane(v) for n, _, v in _fields(space) if n == 1)


def tf_ops(path: str) -> dict:
    """{device plane: {operation's short name: tf_op}}; an operation
    whose metadata has no ``tf_op`` is left out."""
    out = {}
    for p in planes(path):
        if reduce.DEVICE_PLANE.match(p["name"]):
            out[p["name"]] = {
                reduce.short(n): st["tf_op"]
                for n, st in p["meta"].values() if st.get("tf_op")}
    return out


def scope_path(tf_op: str, vocabulary) -> tuple:
    """The vocabulary names on an operation's path, outermost first;
    ``jit(...)``, ``while``, ``body``, ``cond`` and the operation's own
    name are passed over."""
    return tuple(part for part in (tf_op or "").split("/")
                 if part in vocabulary)


def _span_ns(ts, off_ps, dur_ps):
    """(start, end) in ns exactly as ``ProfileData`` gives them (whole
    nanoseconds), so both readers cut the same window."""
    a = float(ts + off_ps // 1000)
    return a, a + dur_ps // 1000


def _line_events(plane, line_name):
    """(start ns, end ns, metadata id) of one line's events."""
    return [(*_span_ns(ts, off_ps, dur_ps), mid)
            for lname, ts, events in plane["lines"] if lname == line_name
            for mid, off_ps, dur_ps in events]


def host_events(path: str, prefix: str = "cup2d:") -> list:
    """(start ns, end ns, name) of the host events whose name starts
    with ``prefix``: the flight recorder's spans inside a trace."""
    out = []
    for p in planes(path):
        if p["name"] != "/host:CPU":
            continue
        names = {mid: n for mid, (n, _) in p["meta"].items()
                 if n.startswith(prefix)}
        for _, ts, events in p["lines"]:
            for mid, off_ps, dur_ps in events:
                if mid in names:
                    out.append((*_span_ns(ts, off_ps, dur_ps), names[mid]))
    return sorted(out)


def device_windows(path: str, step_modules, whole_runs=True) -> list:
    """Per device plane: (plane, window start ns, window end ns, steps,
    module runs, whether a first run in flight was left out). The window is the reduction's — first to last start
    of a step executable — less a first run that the profiler caught
    in flight: under the lagged verdict the device is still inside the
    step before the window when the trace starts, and that run's event
    begins where the trace does. Such a run lacks operations that
    every later run has (seen on the chip, PR 24: 165 ms of a 208 ms
    step, advection missing) and is no step to average over;
    ``whole_runs=False`` keeps it, which is the reduction's window to
    the nanosecond. Planes left with fewer than two runs are passed
    over."""
    out = []
    for p in planes(path):
        if not reduce.DEVICE_PLANE.match(p["name"]):
            continue
        runs = sorted(
            (a, b, p["meta"].get(mid, ("", {}))[0])
            for a, b, mid in _line_events(p, reduce.MODULE_LINE))
        step = [r for r in runs
                if reduce.module_name(r[2]) in step_modules]
        in_flight = False
        if len(step) >= 3:
            ops = sorted(_line_events(p, reduce.OPS_LINE))
            starts = [e[0] for e in ops]
            ran = [{e[2] for e in ops[bisect.bisect_left(starts, a):
                                      bisect.bisect_left(starts, b)]}
                   for a, b, _ in step]
            in_flight = not set.intersection(*ran[1:]) <= ran[0]
            if in_flight and whole_runs:
                step = step[1:]
        if len(step) >= 2:
            out.append((p, step[0][0], step[-1][0], len(step) - 1, runs,
                        in_flight))
    return out


def module_ms(path: str, step_modules, whole_runs=True) -> dict | None:
    """{executable: ms inside its runs in the traced window}, averaged
    over the chips, and the window's steps under ``"steps"``."""
    wins = device_windows(path, step_modules, whole_runs)
    if not wins:
        return None
    out = defaultdict(float)
    for _, lo, hi, _, mods, _ in wins:
        for a, b, name in reduce._clip(mods, lo, hi):
            out[reduce.module_name(name)] += (b - a) / 1e6 / len(wins)
    return {"steps": wins[0][3], **out}


def _scope_keys(ops, tf_op, vocabulary) -> tuple:
    """Per operation (``ops`` in start order, outer before inner) its
    scope path joined with ``/``, or ``(unscoped)``, and the operations
    it holds. An operation with no ``tf_op`` that holds others — the
    compiler's ``while`` around a loop body — takes the path its
    operations share."""
    paths, kids, stack = [], [[] for _ in ops], []
    for i, (a, b, mid) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            kids[stack[-1]].append(i)
        stack.append(i)
        paths.append(scope_path(tf_op.get(mid), vocabulary))
    for i in range(len(ops) - 1, -1, -1):
        if tf_op.get(ops[i][2]) is None and kids[i]:
            shared = paths[kids[i][0]]
            for k in kids[i][1:]:
                n = 0
                while n < min(len(shared), len(paths[k])) \
                        and shared[n] == paths[k][n]:
                    n += 1
                shared = shared[:n]
            paths[i] = shared
    return ["/".join(path) or UNSCOPED for path in paths], kids


def self_ms_by_scope(path: str, step_modules, vocabulary,
                     inside=None, whole_runs=True) -> dict | None:
    """Device self time of the traced window by scope, averaged over
    the chips: ``{"steps", "first_run_in_flight", "self_ms": {scope
    path: ms}, "loops": {scope: executions}}``. A scope
    path is the vocabulary names of an operation joined with ``/``, or
    ``(unscoped)``. ``inside`` names the executables whose runs bound
    the operations counted (None: every operation of the window).
    ``loops`` counts the executions of operations that hold others
    (loops), by the innermost scope of their path: the coarsest level's
    sweep loop runs once per multigrid cycle. None where no device plane holds two whole runs of a
    step executable."""
    wins = device_windows(path, step_modules, whole_runs)
    if not wins:
        return None
    total, loops = defaultdict(float), defaultdict(float)
    n = len(wins)
    for p, lo, hi, _, mods, _ in wins:
        ops = reduce._clip(_line_events(p, reduce.OPS_LINE), lo, hi)
        if inside is not None:
            bounds = [(a, b) for a, b, name in reduce._clip(mods, lo, hi)
                      if reduce.module_name(name) in inside]
            starts = [a for a, _ in bounds]

            def counted(t):
                i = bisect.bisect_right(starts, t) - 1
                return i >= 0 and t < bounds[i][1]

            ops = [e for e in ops if counted(e[0])]
        ops.sort(key=lambda e: (e[0], -e[1]))
        keys, kids = _scope_keys(
            ops, {mid: st.get("tf_op") for mid, (_, st) in p["meta"].items()},
            vocabulary)
        # the reduction's self-time rule, keyed by scope path
        for key, sec in reduce._self_times(
                [(a, b, key) for (a, b, _), key in zip(ops, keys)]).items():
            total[key] += 1e3 * sec / n
        for key, held in zip(keys, kids):
            if held and key != UNSCOPED:
                loops[key.rsplit("/", 1)[-1]] += 1 / n
    return {"steps": wins[0][3], "first_run_in_flight": wins[0][5],
            "self_ms": dict(total), "loops": dict(loops)}


def under(self_ms: dict, scope: str) -> float:
    """Self time of every scope path that holds ``scope``."""
    return sum(ms for key, ms in self_ms.items()
               if scope in key.split("/"))
