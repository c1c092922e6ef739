"""Flight recorder: run-wide observability riding existing sync points.

Four instruments, one discipline — ZERO new device pulls on the hot
path (the PR-3 contract: a recorder-on run is bit-identical to a
recorder-off run with equal ``HostCounters.device_gets`` AND equal
``jit_compiles``; tests/test_tracing.py pins both on UniformSim and
FleetServer churn):

1. **Span timeline** — hierarchical wall-clock spans (``span("step")``
   nesting ``dispatch``/``verdict``/``snapshot``/``mirror``/
   ``recover``/``remesh``/``admit``/``evict``/``regrid``; :data:`SPANS`
   is the whole vocabulary). The forest's host side has names down to
   its parts: ``tables`` (the lookup-table rebuild after a regrid)
   nests ``tables.topo``, ``tables.halo`` (one a halo set, attribute
   ``set``), ``tables.faces``, ``tables.pad``, ``tables.flux``,
   ``tables.coarse`` and ``tables.geom``; a shaped step's ``dispatch``
   nests ``dt`` (attribute ``pulled`` where it round-trips a scalar),
   ``kinematics``, ``shape_inputs``, ``enqueue``, ``pull`` (the step's
   one ``jax.device_get``: a fence, so the device's wait is in it) and
   ``bodies`` (what the host does with the pulled values). Spans are
   recorded lock-free per process into a bounded ring, flushed through
   the EventLog writer (cold path: shutdown or ring-full), exported to a
   Chrome/Perfetto ``trace.json`` by ``python -m cup2d_tpu.post
   --trace``. Inside a ``CUP2D_TRACE`` window (and only there:
   ``profiling.TraceWindow`` raises :func:`set_profiling`) every span
   additionally opens a ``jax.profiler.TraceAnnotation`` named
   ``cup2d:<name>`` (``step`` a ``StepTraceAnnotation`` with its
   ``step_num``), so the spans sit in the profiler's trace on the
   device's clock and an idle gap of the chip names the span it fell
   in. Spans are host-clock intervals between points the run
   already passes through: where a phase already fences (the verdict's
   batched pull, the snapshot's host gather) the span is
   fence-accurate; a ``dispatch`` span times enqueue cost only — the
   async dispatch pipeline is exactly what it must not perturb.

2. **Compile attribution** — ``profiling._on_compile`` (the
   jax.monitoring listener that counts ``jit_compiles``) forwards each
   backend-compile duration here; :func:`named_jit` wraps the
   package's jit entry points (uniform/fleet/amr/io) with a label
   pushed onto a stack for the duration of the call, so a compile
   fired by tracing inside that call lands on the innermost label.
   The ledger row carries count, total ms, trigger step
   (:func:`note_step`), latch token (:func:`note_token` — the
   dispatch-time poisson-mode/kernel-tier label), and the Poisson-path
   components observed at trace time (:func:`note_component` from
   ``poisson.mg_solve``/``bicgstab``). The ``jit_compiles==0`` CI pin
   thereby fails WITH a blame report instead of a bare count.

3. **HBM memory ledger** — after a call that triggered a compile, the
   executable is re-lowered from the abstract signature (donated
   arrays keep ``.shape``/``.dtype`` after deletion) and
   ``compiled.memory_analysis()`` records argument/output/temp/
   generated-code bytes per label. The re-lower fires one extra
   backend compile (served from the persistent compilation cache when
   armed); :func:`compiles_suppressed` hides it from HostCounters and
   from the ledger itself, preserving the equal-compile-count
   contract.

4. **Serving latency histograms** — :class:`ServingLatency` collects
   per-request queue-wait, admit-to-first-step, and per-step wall
   latency into fixed-bucket log2 :class:`LatencyHistogram`\\ s, per
   client and pool-wide; ``FleetServer`` drives it from its existing
   submit/admit/step boundaries (host clocks only).

5. **Device scopes** — :data:`SCOPES` is the one vocabulary of the
   step program's parts and :func:`scope` the ``jax.named_scope`` that
   writes a name into the ``op_name`` of every operation traced under
   it (uniform/poisson/sim/fleet/amr apply it where the work is
   traced). Metadata only: the lowered program is byte-identical with
   and without it (tests/test_scopes.py), the names reach the TPU
   trace as each operation's ``tf_op``, and ``benchmark/xplane_meta.py``
   sums device time by them.

Import discipline: this module imports nothing from the package at
module level (resilience/fleet/profiling all import it), and jax only
inside the cold-path memory capture, :func:`scope` (trace time) and an
open profiler window's annotations.
"""

from __future__ import annotations

import functools
import math
import os
import time
from collections import deque
from contextlib import nullcontext
from typing import Optional

# ---------------------------------------------------------------------------
# module state: the active recorder + attribution stacks
# ---------------------------------------------------------------------------

_RECORDER: Optional["FlightRecorder"] = None
_LABEL_STACK: list = []     # innermost active named_jit label
_SUPPRESS = [0]             # >0: backend compiles are ledger-internal
_NULL = nullcontext()       # shared, reentrant — the recorder-off span
_PROFILING = False          # a CUP2D_TRACE window is open (TraceWindow)

# the host's parts, as the span timeline and a device trace's
# ``cup2d:<name>`` annotations name them: the run loop and its guard,
# the supervision ladder's rungs (``span(action)``), the fleet server's
# slot changes, the forest's regrid and table rebuild with the parts of
# the rebuild, and the parts of a shaped step
SPANS = ("step", "dispatch", "verdict", "snapshot", "mirror", "record",
         "recover", "retry", "escalate", "disk_restore", "abort",
         "remesh", "admit", "retire", "evict",
         "regrid", "tables", "tables.topo", "tables.halo", "tables.faces",
         "tables.pad", "tables.flux", "tables.coarse", "tables.geom",
         "dt", "kinematics", "shape_inputs", "enqueue", "pull", "bodies")

# the step program's parts, as they read in a device trace: top-level
# scopes in step order (the Heun substages nest in advect), then the
# ones that nest inside poisson_solve, then the bodies' own two: the
# rasterisation before the flow step and the surface forces after it
SCOPES = ("advect", "substage0", "substage1", "penalize", "poisson_rhs",
          "poisson_solve", "project_correct", "diag",
          "krylov", "mg_cycle", "mg_smooth", "mg_transfer", "mg_coarse",
          "fft_diag", "rasterize", "forces")


def scope(name: str):
    """``jax.named_scope`` of a :data:`SCOPES` name (``advect`` also as
    ``advect/substage<k>``): operation metadata only, no operation."""
    import jax
    return jax.named_scope(name)


def scoped(name: str, fn):
    """``fn``, traced under :func:`scope` ``name`` wherever it is
    called (for closures that are handed on as operators)."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with scope(name):
            return fn(*args, **kwargs)
    return run


def in_scope(name: str):
    """:func:`scoped` as a decorator: ``@tracing.in_scope("diag")``."""
    return functools.partial(scoped, name)


def set_profiling(on: bool) -> None:
    """``TraceWindow`` tells the spans that the profiler is running:
    while up, each span also opens a ``cup2d:<name>`` annotation."""
    global _PROFILING
    _PROFILING = bool(on)


def profiling() -> bool:
    return _PROFILING


def recorder() -> Optional["FlightRecorder"]:
    """The active flight recorder, or None (library default)."""
    return _RECORDER


def compiles_suppressed() -> bool:
    """True while a ledger-internal re-lower is compiling — the
    profiling listener must count neither in HostCounters nor here."""
    return _SUPPRESS[0] > 0


def span(name: str, **attrs):
    """A timeline span context. Free when no recorder is installed
    (returns a shared ``nullcontext``); otherwise records one ring
    entry at exit — host clocks only, no device interaction."""
    r = _RECORDER
    if r is None or not r.spans_on:
        return _NULL
    return _SpanCtx(r, name, attrs)


def note_step(n) -> None:
    """Current driver step — stamped onto compiles as the trigger step
    (called from StepGuard's dispatch path; a no-op attribute write)."""
    r = _RECORDER
    if r is not None:
        r._step = int(n)


def note_token(token) -> None:
    """Current latch token (dispatch-time poisson-mode/kernel-tier
    label) — stamped onto compiles whose entry has no static token."""
    r = _RECORDER
    if r is not None:
        r._token = token


def note_component(name: str) -> None:
    """Record a trace-time component (e.g. ``poisson.mg_solve``) onto
    the innermost compiling executable's ledger row. Runs only while a
    jit body is being TRACED — compiled dispatches never re-enter the
    Python body, so this costs nothing in steady state."""
    r = _RECORDER
    if r is None or not r.compile_attr or not _LABEL_STACK:
        return
    ent = r.ledger.get(_LABEL_STACK[-1])
    if ent is not None:
        ent["components"].add(name)


def _note_compile(duration_s: float) -> None:
    """Entry point for profiling._on_compile: attribute one backend
    compile to the innermost active label."""
    r = _RECORDER
    if r is not None and r.compile_attr:
        r._on_compile_event(
            _LABEL_STACK[-1] if _LABEL_STACK else None, duration_s)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class _SpanCtx:
    """One live span frame. Entry/exit are a few host clock reads and
    list ops; the record lands in the recorder's ring at exit (LIFO —
    spans close in nesting order, enforced by ``with`` scoping)."""

    __slots__ = ("_r", "name", "attrs", "_wall", "_t0", "_ann")

    def __init__(self, r: "FlightRecorder", name: str, attrs: dict):
        self._r = r
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self._r._stack.append(self)
        self._ann = None
        if _PROFILING:
            # the span on the profiler's clock, for the window's steps
            # only (the attrs known at entry; later ones reach
            # spans.jsonl alone)
            import jax
            if self.name == "step":
                self._ann = jax.profiler.StepTraceAnnotation(
                    "cup2d:step", step_num=self.attrs.get("step", 0))
            else:
                self._ann = jax.profiler.TraceAnnotation(
                    "cup2d:" + self.name, **self.attrs)
            self._ann.__enter__()
        self._wall = time.time()          # cross-process alignment
        self._t0 = time.perf_counter()    # duration
        return self

    def __exit__(self, etype, _exc, _tb):
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        r = self._r
        r._stack.pop()
        attrs = self.attrs
        if etype is not None:
            # an aborting rung propagates through its spans — keep the
            # interval and mark it, so the timeline shows WHERE it died
            attrs = {**attrs, "error": etype.__name__}
        r._record(self.name, self._wall, dur, len(r._stack), attrs)
        return False


# ---------------------------------------------------------------------------
# compile attribution: the named-jit label registry
# ---------------------------------------------------------------------------

class NamedJit:
    """A jitted callable with a ledger label. ``__call__`` pushes the
    label for the duration of the dispatch (compiles happen
    synchronously inside it, so the monitoring listener attributes the
    duration to the innermost label) and, when a compile fired,
    captures the executable's ``memory_analysis`` from the abstract
    signature. Recorder off: one ``is None`` check, then passthrough.

    ``variant`` names static kwargs whose values split the label
    (``step[exact_poisson=True]`` is a different executable than the
    production solve — the blame report must say which one compiled).
    ``token`` is an optional static latch token; without one the
    recorder's current :func:`note_token` value stamps at compile
    time. All other attribute access (``.lower``, ``.__wrapped__``)
    passes through to the underlying jit."""

    def __init__(self, label: str, fn, *, token=None, variant=()):
        self._label = label
        self._fn = fn
        self._token = token
        self._variant = tuple(variant)

    def __call__(self, *args, **kwargs):
        r = _RECORDER
        if r is None or not r.compile_attr:
            return self._fn(*args, **kwargs)
        label = self._label
        for k in self._variant:
            if k in kwargs:
                label = f"{label}[{k}={kwargs[k]}]"
        ent = r._ledger_entry(label, self._token)
        n0 = ent["count"]
        _LABEL_STACK.append(label)
        try:
            out = self._fn(*args, **kwargs)
        finally:
            _LABEL_STACK.pop()
        if ent["count"] > n0 and r.capture_memory and ent["mem"] is None:
            ent["mem"] = _memory_analysis(self._fn, args, kwargs)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self):
        return f"NamedJit({self._label!r}, {self._fn!r})"


def named_jit(label: str, fn, *, token=None, variant=()) -> NamedJit:
    """Wrap a ``jax.jit`` result with a compile-ledger label (see
    :class:`NamedJit`). graftlint's donation/retrace rules unwrap this
    call to keep seeing the inner jit's donate/static declarations."""
    return NamedJit(label, fn, token=token, variant=variant)


def _memory_analysis(fn, args, kwargs) -> dict:
    """Cold-path HBM ledger capture: re-lower ``fn`` from the abstract
    signature of the call that just compiled and read the executable's
    ``memory_analysis``. Donated operands are already deleted by the
    time this runs — only ``.shape``/``.dtype`` are read, which
    survive deletion. The re-lower's own backend compile is suppressed
    from HostCounters and the ledger (equal-compile-count contract);
    with the persistent compilation cache armed it is a cache hit.
    Sanctioned host-sync scope (policy.HOST_SYNC_SITES)."""
    import jax
    import numpy as np

    def _abstract(x):
        if isinstance(x, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    try:
        aargs, akw = jax.tree_util.tree_map(_abstract, (args, kwargs))
        _SUPPRESS[0] += 1
        try:
            compiled = fn.lower(*aargs, **akw).compile()
        finally:
            _SUPPRESS[0] -= 1
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }
    except Exception as e:         # never let the ledger kill a run
        return {"error": str(e)[:200]}


def _mem_total(mem: Optional[dict]) -> int:
    if not mem or "error" in mem:
        return 0
    return sum(int(v) for v in mem.values())


# ---------------------------------------------------------------------------
# serving latency histograms
# ---------------------------------------------------------------------------

class LatencyHistogram:
    """Fixed-bucket log2 histogram of durations. Bucket ``i`` counts
    samples in ``[2^i, 2^(i+1))`` microseconds (bucket 0 absorbs
    sub-2µs); 40 buckets reach ~18 minutes. O(1) memory and update —
    no per-sample storage on the serving path. Percentiles report the
    upper edge of the bucket holding the rank, clamped to the observed
    max: a conservative (never under-reporting) estimate within one
    bucket (2x) of resolution."""

    NBUCKETS = 40

    __slots__ = ("counts", "n", "sum_us", "max_us")

    def __init__(self):
        self.counts = [0] * self.NBUCKETS
        self.n = 0
        self.sum_us = 0.0
        self.max_us = 0.0

    def add(self, seconds: float) -> None:
        us = seconds * 1e6
        if us < 0.0:
            us = 0.0
        i = max(int(us), 1).bit_length() - 1
        if i >= self.NBUCKETS:
            i = self.NBUCKETS - 1
        self.counts[i] += 1
        self.n += 1
        self.sum_us += us
        if us > self.max_us:
            self.max_us = us

    def percentile(self, q: float) -> Optional[float]:
        """q-quantile in milliseconds (bucket upper edge, clamped to
        the observed max), or None when empty."""
        if self.n == 0:
            return None
        target = max(int(math.ceil(q * self.n)), 1)
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target:
                if i == self.NBUCKETS - 1:
                    # the overflow bucket has no real upper edge — the
                    # observed max is the only honest bound
                    return round(self.max_us / 1e3, 3)
                return round(min(float(1 << (i + 1)),
                                 self.max_us) / 1e3, 3)
        return round(self.max_us / 1e3, 3)

    def report(self) -> dict:
        if self.n == 0:
            return {"count": 0}
        return {"count": self.n,
                "mean_ms": round(self.sum_us / self.n / 1e3, 3),
                "p50_ms": self.percentile(0.50),
                "p90_ms": self.percentile(0.90),
                "p99_ms": self.percentile(0.99),
                "max_ms": round(self.max_us / 1e3, 3)}


class ServingLatency:
    """Per-request latency collector for ``FleetServer`` — host clocks
    at the server's existing submit/admit/step boundaries, so arming
    it adds no device interaction and no extra dispatches.

    Three distributions, pool-wide and per client:

    - ``queue_wait``: submit() -> the admit that seats the request;
    - ``admit_to_first_step``: admit -> end of the first fused step
      that carried the client;
    - ``step``: wall time of each fused step, attributed to every
      client it carried (the slot pool dispatches all occupants
      together — a member's step latency IS the fused latency).

    Per-client tracking caps at ``MAX_CLIENTS`` distinct ids (the
    pool-wide histograms keep counting; dropped ids are reported as
    ``untracked_clients``)."""

    KINDS = ("queue_wait", "admit_to_first_step", "step")
    MAX_CLIENTS = 512

    def __init__(self):
        self.pool = {k: LatencyHistogram() for k in self.KINDS}
        self.clients: dict = {}
        self._submitted: dict = {}
        self._admitted: dict = {}
        self._dropped: set = set()

    def _client(self, cid) -> Optional[dict]:
        h = self.clients.get(cid)
        if h is None:
            if len(self.clients) >= self.MAX_CLIENTS:
                self._dropped.add(cid)
                return None
            h = {k: LatencyHistogram() for k in self.KINDS}
            self.clients[cid] = h
        return h

    def _observe(self, kind: str, cid, seconds: float) -> None:
        self.pool[kind].add(seconds)
        h = self._client(cid)
        if h is not None:
            h[kind].add(seconds)

    def on_submit(self, cid) -> None:
        self._submitted[cid] = time.perf_counter()

    def on_admit(self, cid) -> None:
        now = time.perf_counter()
        t0 = self._submitted.pop(cid, None)
        if t0 is not None:
            self._observe("queue_wait", cid, now - t0)
        self._admitted[cid] = now

    def on_step(self, cids, seconds: float) -> None:
        """One fused step of duration ``seconds`` carried ``cids``."""
        now = time.perf_counter()
        for cid in cids:
            if cid is None:
                continue
            self._observe("step", cid, seconds)
            t0 = self._admitted.pop(cid, None)
            if t0 is not None:
                self._observe("admit_to_first_step", cid, now - t0)

    def report(self) -> dict:
        out = {"pool": {k: self.pool[k].report() for k in self.KINDS}}
        if self.clients:
            out["clients"] = {
                str(cid): {k: h[k].report() for k in self.KINDS}
                for cid, h in self.clients.items()}
        if self._dropped:
            out["untracked_clients"] = len(self._dropped)
        return out


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

class FlightRecorder:
    """Per-process flight recorder: span ring + compile/memory ledger.
    Install exactly one (:meth:`install` registers it module-wide and
    arms the profiling compile listener); ``close()`` flushes and
    deregisters. All state is plain host data — the recorder never
    touches the device outside the sanctioned cold-path scopes."""

    def __init__(self, *, spans: bool = True, compile_attr: bool = True,
                 capture_memory: bool = True, max_spans: int = 65536,
                 sink=None):
        self.spans_on = bool(spans)
        self.compile_attr = bool(compile_attr)
        self.capture_memory = bool(capture_memory)
        self.max_spans = int(max_spans)
        self.sink = sink                  # EventLog-like (.emit(**row))
        self.pid = 0
        self._buf: deque = deque()
        self._stack: list = []
        self.span_count = 0               # cumulative, survives flushes
        self.spans_dropped = 0
        self.ledger: dict = {}            # label -> entry dict
        self.compile_ms_total = 0.0
        self._step = None                 # note_step
        self._token = None                # note_token

    @classmethod
    def from_env(cls, **kw) -> "FlightRecorder":
        """Construction-time latch of ``CUP2D_SPANS`` (the ONE read,
        policy.ENV_LATCH_SITES): ``"0"`` disables the span instrument
        (ledger instruments stay on), an integer overrides the ring
        capacity, unset/empty keeps the caller's settings."""
        raw = os.environ.get("CUP2D_SPANS", "").strip()
        on = kw.pop("spans", True)
        if raw == "0":
            on = False
        elif raw:
            try:
                kw["max_spans"] = max(int(raw), 16)
            except ValueError:
                pass
        return cls(spans=on, **kw)

    # -- lifecycle -----------------------------------------------------
    def install(self) -> "FlightRecorder":
        global _RECORDER
        _RECORDER = self
        from . import profiling
        profiling._install_hooks()    # arm the compile listener
        try:
            import jax
            from .resilience import dist_initialized
            self.pid = (jax.process_index() if dist_initialized()
                        else 0)
        except Exception:
            self.pid = 0
        return self

    def uninstall(self) -> None:
        global _RECORDER
        if _RECORDER is self:
            _RECORDER = None

    def close(self) -> None:
        self.flush()
        self.uninstall()

    # -- span ring -----------------------------------------------------
    def _record(self, name, wall, dur, depth, attrs) -> None:
        self.span_count += 1
        buf = self._buf
        if len(buf) >= self.max_spans:
            if self.sink is not None:
                self.flush()       # cold path: ring-full write burst
            else:
                buf.popleft()
                self.spans_dropped += 1
        buf.append((name, wall, dur, depth, attrs))

    def flush(self) -> None:
        """Drain the span ring into the attached EventLog sink — cold
        path (shutdown / ring-full), one JSONL row per span."""
        sink = self.sink
        if sink is None:
            return
        buf = self._buf
        while buf:
            name, wall, dur, depth, attrs = buf.popleft()
            row = {"event": "span", "name": name,
                   "ts_us": int(wall * 1e6),
                   "dur_us": max(int(dur * 1e6), 1),
                   "depth": depth, "pid": self.pid}
            for k, v in attrs.items():
                if k not in row:
                    row[k] = v
            sink.emit(**row)

    # -- compile / memory ledger ----------------------------------------
    def _ledger_entry(self, label: str, token=None) -> dict:
        ent = self.ledger.get(label)
        if ent is None:
            ent = {"label": label, "count": 0, "ms": 0.0,
                   "first_step": None, "last_step": None,
                   "token": token, "components": set(), "mem": None}
            self.ledger[label] = ent
        elif token is not None and ent["token"] is None:
            ent["token"] = token
        return ent

    def _on_compile_event(self, label: Optional[str],
                          duration_s: float) -> None:
        ent = self._ledger_entry(label or "<unattributed>")
        ent["count"] += 1
        ent["ms"] += duration_s * 1e3
        if ent["first_step"] is None:
            ent["first_step"] = self._step
        ent["last_step"] = self._step
        if ent["token"] is None:
            ent["token"] = self._token
        self.compile_ms_total += duration_s * 1e3

    def hbm_exec_bytes(self) -> int:
        """Summed memory_analysis footprint (argument+output+temp+
        generated code) over every executable with a captured row."""
        return sum(_mem_total(e["mem"]) for e in self.ledger.values())

    def ledger_report(self) -> dict:
        """The compile blame report: one row per named executable."""
        rows = []
        for label in sorted(self.ledger):
            e = self.ledger[label]
            rows.append({
                "label": label,
                "compiles": e["count"],
                "ms": round(e["ms"], 3),
                "first_step": e["first_step"],
                "last_step": e["last_step"],
                "token": e["token"],
                "components": sorted(e["components"]) or None,
                "memory": e["mem"],
            })
        return {
            "compiles": sum(r["compiles"] for r in rows),
            "compile_ms_total": round(self.compile_ms_total, 3),
            "hbm_exec_bytes": self.hbm_exec_bytes() or None,
            "executables": rows,
        }


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------

_CLIENT_PID_BASE = 1 << 20    # client tracks live above any process id


def spans_to_perfetto(rows) -> dict:
    """Chrome/Perfetto trace-event JSON from flushed span rows: one
    track per process (pid = process index) plus one synthesized track
    per client session (spans carrying a ``client`` attr — admit/
    retire/evict — are mirrored onto the client's track under a
    ``session`` envelope spanning first-to-last appearance). Load the
    result at https://ui.perfetto.dev or chrome://tracing."""
    events = []
    pids = set()
    clients: dict = {}
    for r in rows:
        if r.get("event") != "span":
            continue
        pid = int(r.get("pid", 0))
        pids.add(pid)
        args = {k: v for k, v in r.items()
                if k not in ("event", "name", "ts_us", "dur_us",
                             "depth", "pid", "wall")}
        ev = {"name": str(r["name"]), "ph": "X", "ts": int(r["ts_us"]),
              "dur": int(r["dur_us"]), "pid": pid, "tid": 0,
              "args": args}
        events.append(ev)
        cid = r.get("client")
        if cid is not None:
            info = clients.setdefault(
                str(cid), {"first": ev["ts"], "last": ev["ts"],
                           "spans": []})
            info["first"] = min(info["first"], ev["ts"])
            info["last"] = max(info["last"], ev["ts"] + ev["dur"])
            info["spans"].append(ev)
    meta = []
    for pid in sorted(pids):
        meta.append({"name": "process_name", "ph": "M", "pid": pid,
                     "tid": 0, "args": {"name": f"process {pid}"}})
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": 0, "args": {"name": "guard"}})
    for i, cid in enumerate(sorted(clients,
                                   key=lambda c: clients[c]["first"])):
        cpid = _CLIENT_PID_BASE + i
        info = clients[cid]
        meta.append({"name": "process_name", "ph": "M", "pid": cpid,
                     "tid": 0, "args": {"name": f"client {cid}"}})
        events.append({"name": "session", "ph": "X",
                       "ts": info["first"],
                       "dur": max(info["last"] - info["first"], 1),
                       "pid": cpid, "tid": 0, "args": {"client": cid}})
        for ev in info["spans"]:
            events.append({**ev, "pid": cpid})
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}
