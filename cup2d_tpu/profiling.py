"""Telemetry & profiling (SURVEY.md §5: the reference has NO timers,
counters, metrics or traces — a stderr step counter only).

The run-telemetry subsystem (PR 3), layered so every piece rides work
the step ALREADY does — the contract throughout is **zero extra device
syncs**: the per-step scalars arrive in the step's one existing batched
diag pull (`sim.py`/`amr.py`), and everything here is host-side
bookkeeping on top of it (asserted by ``tests/test_telemetry.py``: a
metrics-on run is bit-identical to metrics-off with equal
``device_get`` counts).

- :class:`MetricsRecorder`: one structured record per step — solver
  health (Poisson iters / true residual / converged / stalled),
  timestep state (dt, umax, next dt), fused on-device physics
  invariants (kinetic energy, max |∇·u|), AMR shape (per-level block
  histogram, refine/coarsen counts), comm volume (real/padded halo
  bytes of the shard exchange plan), host counters (jit recompiles,
  ``device_get`` pulls, HBM high-water mark) — streamed as JSONL through the PR-2 ``resilience.EventLog`` machinery
  (process-0 writer on pods). The key set is frozen
  (:data:`METRICS_KEYS`, schema-stability golden test).
- :class:`HostCounters`: process-wide host-side counters — jit
  recompiles via the ``jax.monitoring`` backend-compile event,
  device→host pulls by wrapping ``jax.device_get``, HBM peak bytes via
  ``jax.local_devices()[0].memory_stats()`` (None on backends without
  an allocator report, e.g. CPU).
- :class:`TraceWindow`: windowed device tracing —
  ``CUP2D_TRACE=start:stop[:logdir]`` wraps exactly steps
  ``[start, stop)`` of a production run in ``jax.profiler`` so a
  TensorBoard trace costs only its window, not the whole run.
"""

from __future__ import annotations

import json
import os
from typing import Optional

import numpy as np

import jax

from . import tracing


# ---------------------------------------------------------------------------
# windowed device tracing (CUP2D_TRACE=start:stop[:logdir])
# ---------------------------------------------------------------------------

class TraceWindow:
    """Windowed `jax.profiler` tracing driven by the step counter: the
    trace wraps exactly steps ``[start, stop)``, so a production run
    captures a TensorBoard trace of a few warmed steps without paying
    profiler overhead (or trace-file volume) for the whole run.

    The driver calls :meth:`maybe_start` BEFORE attempting a step and
    :meth:`maybe_stop` AFTER it completes (with the post-step counter);
    ``>=`` comparisons keep a restarted run from arming a window its
    step range already passed. :meth:`close` stops a still-open trace
    at loop exit (a window past ``tend`` must not leave the profiler
    running)."""

    # the profiler's Python tracer is OFF: the flight recorder's spans
    # are in the trace themselves (tracing.set_profiling) and name the
    # host's part of a step. Measured on the chip at 8192^2 (PERF.md,
    # PR 24): with it on a traced step costs 4.0 ms of host against
    # 3.3, starting the trace 96 ms against 48, and every idle gap is
    # named by a Python frame instead of a span or a runtime event
    PYTHON_TRACER_LEVEL = 0

    def __init__(self, start: int, stop: int, logdir: str = "trace"):
        if not (0 <= int(start) < int(stop)):
            raise ValueError(
                f"trace window needs 0 <= start < stop, got "
                f"{start}:{stop}")
        self.start = int(start)
        self.stop = int(stop)
        self.logdir = logdir
        self.active = False
        self.done = False

    @classmethod
    def from_env(cls) -> Optional["TraceWindow"]:
        """Latch CUP2D_TRACE once (the sanctioned read site — see
        tests/test_env_latch.py). A typo'd spec raises instead of
        silently arming nothing (the CUP2D_FAULTS principle: a trace
        window that never fires measures nothing)."""
        spec = os.environ.get("CUP2D_TRACE", "")
        if not spec:
            return None
        parts = spec.split(":", 2)
        if len(parts) < 2:
            raise ValueError(
                f"CUP2D_TRACE={spec!r}: expected start:stop[:logdir]")
        try:
            start, stop = int(parts[0]), int(parts[1])
        except ValueError:
            raise ValueError(
                f"CUP2D_TRACE={spec!r}: start/stop must be integers")
        logdir = parts[2] if len(parts) == 3 and parts[2] else "trace"
        return cls(start, stop, logdir)

    def maybe_start(self, step_count: int) -> None:
        """Arm the trace before stepping ``step_count`` if the window
        opens here."""
        if self.active or self.done or step_count < self.start \
                or step_count >= self.stop:
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = self.PYTHON_TRACER_LEVEL
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self.active = True
        tracing.set_profiling(True)   # spans enter the trace from here
        from .resilience import record_event
        record_event(event="trace_start", step=step_count,
                     logdir=self.logdir)

    def maybe_stop(self, step_count: int) -> None:
        """Close the trace once the post-step counter reaches the
        window end."""
        if self.active and step_count >= self.stop:
            self._stop(step_count)

    def close(self) -> None:
        if self.active:
            self._stop(None)

    def _stop(self, step_count) -> None:
        tracing.set_profiling(False)
        jax.profiler.stop_trace()
        self.active = False
        self.done = True
        from .resilience import record_event
        record_event(event="trace_stop", step=step_count,
                     logdir=self.logdir)


# ---------------------------------------------------------------------------
# host-side counters: jit recompiles, device_get pulls, HBM high-water
# ---------------------------------------------------------------------------

# active counter instances; the jax-level hooks dispatch to whatever is
# active (jax.monitoring has no per-listener deregistration, and
# un-wrapping jax.device_get under someone else's later monkeypatch
# would drop their wrapper — the pass-through hooks are inert while no
# counter is active)
_ACTIVE_COUNTERS: list = []
_LISTENER_ON = False

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_compile(event, duration, **kw):
    if event == _COMPILE_EVENT:
        if tracing.compiles_suppressed():
            # a flight-recorder memory-ledger re-lower is compiling:
            # ledger-internal, invisible to HostCounters AND to the
            # compile ledger itself (equal-compile-count contract)
            return
        for c in _ACTIVE_COUNTERS:
            c.jit_compiles += 1
        tracing._note_compile(float(duration))


def _install_hooks() -> None:
    global _LISTENER_ON
    if not _LISTENER_ON:
        _LISTENER_ON = True
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
    # marker-checked (not a one-shot flag): a test monkeypatch that
    # saved/restored jax.device_get around an install would otherwise
    # silently unwind the wrapper forever
    if not getattr(jax.device_get, "_cup2d_counting", False):
        orig = jax.device_get

        def _counting_device_get(x):
            for c in _ACTIVE_COUNTERS:
                c.device_gets += 1
            return orig(x)

        _counting_device_get._cup2d_counting = True
        jax.device_get = _counting_device_get


def _note_state_gather() -> None:
    """io._gather_state reports each FULL D2H state gather here: the
    device snapshot ring exists so steady-state supervised steps make
    zero of these (disk checkpoints and post-mortems only), and the CI
    sync guard asserts it through this counter."""
    for c in _ACTIVE_COUNTERS:
        c.state_gathers += 1


def hbm_peak_bytes() -> Optional[int]:
    """HBM high-water mark of the first local device, or None where the
    backend reports no allocator stats (CPU)."""
    try:
        ms = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not ms:
        return None
    peak = ms.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None


class HostCounters:
    """Host-side observability counters for one run.

    - ``jit_compiles``: XLA backend compiles since :meth:`install`
      (the `jax.monitoring` backend-compile event — a steady-state step
      must trigger ZERO of these; `tests/test_telemetry.py` guards it).
    - ``device_gets``: explicit device→host pulls (`jax.device_get`
      calls — the drivers' batched per-step pull discipline makes this
      exactly one per step on the hot paths, and under the lagged
      StepGuard verdict that one pull is issued AFTER the next step's
      dispatch, off the critical path).
    - ``state_gathers``: full D2H state gathers (io._gather_state) —
      zero in steady state since the device snapshot ring; nonzero only
      at disk checkpoints/post-mortems.
    - HBM high-water via :func:`hbm_peak_bytes` (absolute, not delta:
      the allocator reports a process-lifetime peak).

    ``install``/``uninstall`` only toggle membership in the active set;
    the underlying hooks are process-wide pass-throughs (see
    ``_install_hooks``) and never removed."""

    def __init__(self):
        self.jit_compiles = 0
        self.device_gets = 0
        self.state_gathers = 0

    def install(self) -> "HostCounters":
        _install_hooks()
        if self not in _ACTIVE_COUNTERS:
            _ACTIVE_COUNTERS.append(self)
        return self

    def uninstall(self) -> None:
        if self in _ACTIVE_COUNTERS:
            _ACTIVE_COUNTERS.remove(self)

    def snapshot(self) -> dict:
        return {"jit_compiles": self.jit_compiles,
                "device_gets": self.device_gets,
                "state_gathers": self.state_gathers}


# ---------------------------------------------------------------------------
# the per-step metrics stream
# ---------------------------------------------------------------------------

# THE frozen record key set (schema-stability golden test). Keys are
# always present; fields that do not apply to a path (AMR shape on a
# uniform run, comm volume on a single device, counters when disabled)
# are null — consumers key on names, never on presence.
METRICS_SCHEMA_VERSION = 15
METRICS_KEYS = (
    "schema", "step", "t", "dt", "wall_ms",
    # solver health + timestep state (the step's existing diag pull).
    # On a FLEET record (schema v3) these scalar slots carry the
    # fleet-conservative aggregate — umax/iters/residual/div_linf max,
    # dt/dt_next min, energy sum, converged all, stalled any — and the
    # per-member vectors live in member_health below.
    "umax", "dt_next",
    "poisson_iters", "poisson_residual",
    "poisson_converged", "poisson_stalled",
    # solve-path attribution (schema v4, PR 6): the ACTIVE Poisson
    # path latch as a string (drivers' .poisson_mode — CUP2D_POIS mode
    # + trigger state) and the per-step preconditioner/MG cycle count
    # (rides the one diag pull), so an A/B run is attributable from
    # metrics.jsonl alone. The VALUE vocabulary grew twice without a
    # schema bump (no keys moved): uniform "bicgstab+mg | fas | fas-f",
    # forest "bicgstab+jacobi | bicgstab+twolevel | bicgstab+fft |
    # fas+forest | fas-f+forest" (PR 13 — forest-native FAS as the
    # full solver; there precond_cycles == poisson_iters, one mg_solve
    # cycle per outer iteration, vs the Krylov arms' 2 M-applies/iter).
    # Schema v12 (ISSUE 20) adds the uniform-family DIRECT tokens:
    # "fftd" (doubly-periodic pure-spectral solve) and "fftd+tridiag"
    # (one periodic axis FFT-diagonalized, per-mode Thomas solves on
    # the wall axis) — both report poisson_iters == 1 by contract and
    # precond_cycles == 0 (no hierarchy runs)
    "poisson_mode", "precond_cycles",
    # kernel-tier attribution (schema v6, PR 9): the ACTIVE advection
    # kernel tier latch (drivers' .kernel_tier — xla | pallas-fused |
    # pallas-fused-bf16) and the hot-loop storage-precision contract
    # (.prec_mode — f32|f64|bf16), so a kernel-tier A/B run is
    # attributable from metrics.jsonl alone, like poisson_mode. The
    # VALUE vocabulary grew without a schema bump (ISSUE 16, no keys
    # moved): BC'd fused tiers suffix the per-face token — e.g.
    # "pallas-fused+bc(in(1,0)[parabolic],out,fs,fs)" — captured at
    # dispatch through the _Pending lagged-commit rule like
    # poisson_mode
    "kernel_tier", "prec_mode",
    # smoother-tier attribution (schema v11, ISSUE 19): the pressure
    # hierarchy's sweep-chain implementation latch (drivers'
    # .smoother_tier — xla | strip | strip+bf16, with "+bf16"
    # suffixing whichever base the shape gate left armed), riding the
    # same diag-then-driver pull as kernel_tier, so a memory-tiered
    # FAS A/B run is attributable from metrics.jsonl alone
    "smoother_tier",
    # boundary-condition attribution (schema v8, ISSUE 12): the
    # driver's compact per-face BCTable token string (.bc_table — e.g.
    # "fs,fs,fs,fs" legacy box, "ns,ns,ns,ns(1,0)" lid-driven cavity;
    # schema v12 adds the "pd" face token — "pd,pd,pd,pd" for the
    # doubly-periodic turbulence catalog, "pd,pd,ns,ns" periodic
    # channels) and the case-registry tag (.case — cavity|channel|
    # cylinder|tgv_periodic|shear_layer|turb2d, null outside -case
    # runs), so a record says WHICH physics scenario it measured, like
    # poisson_mode says which solve path
    "bc_table", "case",
    # fused on-device physics invariants (watchdog inputs)
    "energy", "div_linf",
    # AMR shape; pad_blocks (schema v13) is the bucket the step's
    # block arrays are padded to, so 1 - n_blocks/pad_blocks is the
    # share of the forest step spent on masked rows. Null off the forest
    "n_blocks", "pad_blocks", "blocks_per_level", "refines", "coarsens",
    # the bodies (schema v13): one entry a shape — com [x, y], angle, u,
    # v, omega, mass, inertia — host floats from the step's one existing
    # pull (shapes_host._bodies_record), so a run's body trajectory is
    # in metrics.jsonl and a reference can hold it. Null without shapes
    "bodies",
    # the force pass's block lists (schema v14): per shape, the block
    # rows the surface-force reduction ran over at this step's list
    # build and the sticky power-of-two capacity they are padded to
    # (amr.AMRSim._shape_inputs; a growth is a "force_cap_grow" event).
    # Null without shapes and off the forest
    "force_blocks", "force_cap",
    # comm volume (shard surface-exchange plan, per one vec3 exchange)
    "halo_real_bytes", "halo_padded_bytes",
    # host-side counters (per-step deltas; hbm peak is absolute);
    # state_gathers counts FULL D2H state gathers — zero in guarded
    # steady state since the device snapshot ring (schema v2)
    "jit_compiles", "device_gets", "state_gathers", "hbm_peak_bytes",
    # supervision state (schema v2): device snapshot ring HBM footprint
    # (absolute bytes) + replayed-step delta of the snapshot-cadence
    # recovery path — the D2H win made visible in post --metrics
    "snap_ring_bytes", "replayed_steps",
    # elastic topology (schema v5, PR 7): the current topology epoch
    # (bumped by each survivor re-mesh agreement — 0 for a run that
    # never lost a host), the cumulative re-mesh count, and the wall
    # cost of any re-mesh that landed since the previous record (null
    # on ordinary steps) — a topology loss and its recovery are
    # attributable from metrics.jsonl alone
    "topology_epoch", "remesh_count", "remesh_ms",
    # host-redundant mirror tier (schema v9, PR 17): HBM footprint of
    # the held neighbor-mirror payloads (absolute bytes; null with the
    # tier off), the enqueue-side mirror cost landed since the
    # previous record (delta ms, null when none), and the rung the
    # LAST recovery restored from ("ring"|"mirror"|"disk", null until
    # a recovery happens) — a real-loss resume is attributable from
    # metrics.jsonl alone
    "mirror_bytes", "mirror_ms", "restore_source",
    # fleet batching (schema v3, fleet.py): member count of the fused
    # dispatch, its throughput in member-steps/s (B / wall of the one
    # dispatch — THE dispatch-amortization metric), and per-member
    # solver health folded into the one record as {key: [B values]}
    "fleet_members", "member_steps_per_s", "member_health",
    # fleet serving (schema v7, fleet.FleetServer): slot-pool gauges —
    # live member count, occupancy fraction of the padded pool,
    # cumulative admissions/evictions, and the request-queue depth.
    # Null outside -serve. With per-client streams attached
    # (ClientStreams) the per-member rows move to clients/<id>.jsonl
    # and member_health above is null on serving records.
    "active_members", "occupancy", "admitted", "evicted",
    "queue_depth",
    # flight recorder (schema v10, tracing.FlightRecorder): cumulative
    # span count (absolute — the ring drains to its own spans.jsonl,
    # the record only gauges volume), cumulative attributed compile
    # wall ms, and the summed memory_analysis footprint of every
    # ledgered executable (argument+output+temp+generated-code bytes;
    # null until a capture lands). Null with no recorder attached —
    # all three are host state, same zero-pull discipline as counters
    "span_count", "compile_ms_total", "hbm_exec_bytes",
    # (schema v15 takes out "phase_ms", the per-phase wall times of the
    # fencing timers that went with it: null in every record a run
    # without -profile wrote. Readers ignore the key in older streams)
)

_SERVE_KEYS = ("active_members", "occupancy", "admitted", "evicted",
               "queue_depth")

_DIAG_KEYS = ("umax", "dt_next", "poisson_iters", "poisson_residual",
              "poisson_converged", "poisson_stalled", "energy",
              "div_linf", "precond_cycles")

_INT_KEYS = {"poisson_iters", "precond_cycles"}
_BOOL_KEYS = {"poisson_converged", "poisson_stalled", "finite"}


def _jsonable(key: str, v):
    if v is None:
        return None
    if key in _INT_KEYS:
        return int(v)
    if key in _BOOL_KEYS:
        return bool(v)
    return float(v)


# fleet records (schema v3): how each per-member [B] diag vector folds
# into the record's scalar slot — conservative aggregates (the value an
# alerting consumer should key on), with the full vectors preserved in
# member_health
_FLEET_AGG = {
    "umax": np.max, "dt_next": np.min,
    "poisson_iters": np.max, "poisson_residual": np.max,
    "poisson_converged": np.all, "poisson_stalled": np.any,
    "energy": np.sum, "div_linf": np.max,
    "precond_cycles": np.max,
}

# the per-member vectors folded into member_health (diag keys plus the
# health/clock extras the guard's pull carries)
_MEMBER_KEYS = _DIAG_KEYS + ("finite", "dt")


def _member_list(key: str, v):
    return [_jsonable(key, x) for x in np.asarray(v).ravel()]


class MetricsRecorder:
    """Assembles one :data:`METRICS_KEYS` record per step and streams
    it through ``sink`` (a ``resilience.EventLog`` — process-0 JSONL,
    unified with the PR-2 event stream; ``None`` returns records
    without writing, as the tests do).

    The record costs no device work: every diag scalar arrives in the
    step's one existing batched pull (on library paths that keep diag
    scalars on device, ONE `device_get` fetches the union — same policy
    as ``resilience.health_verdict``), the AMR histogram is host numpy
    cached per topology version, and the counters are host state."""

    def __init__(self, sink=None, counters: Optional[HostCounters] = None,
                 guard=None, server=None, flight=None):
        self.sink = sink
        self.counters = counters
        self.guard = guard          # resilience.StepGuard, opt-in
        self.server = server        # fleet.FleetServer, opt-in (v7)
        self.flight = flight        # tracing.FlightRecorder, opt-in (v10)
        self._last_time: Optional[float] = None
        self._last_counters = counters.snapshot() if counters else None
        self._last_regrid = (0, 0)
        self._last_replayed = 0
        self._last_remesh_ms = 0.0
        self._last_mirror_ms = 0.0
        self._lvl_cache = (None, None, None)   # (version, hist, n)

    def prime(self, sim) -> None:
        """Anchor the dt baseline to the sim's current time (call once
        before the loop; the first record's dt is null otherwise)."""
        self._last_time = float(sim.time)
        if hasattr(sim, "_n_refined"):
            self._last_regrid = (sim._n_refined, sim._n_coarsened)

    # -- assembly ------------------------------------------------------
    def record(self, sim, diag: dict, wall_ms: Optional[float] = None
               ) -> dict:
        """One record from a driver sim (uniform or forest) after a
        completed step; emits into the sink and returns the record."""
        rec = self.record_step(
            step=sim.step_count, t=float(sim.time), diag=diag,
            wall_ms=wall_ms, sim=sim)
        return rec

    def record_step(self, *, step: int, t: float, diag: dict,
                    wall_ms: Optional[float] = None, sim=None,
                    dt: Optional[float] = None) -> dict:
        vals = {k: diag[k] for k in _MEMBER_KEYS if k in diag}
        if any(isinstance(v, jax.Array) for v in vals.values()):
            vals = jax.device_get(vals)   # library-path fallback: 1 pull
        # fleet record (schema v3): [B]-vector diags — fold the
        # per-member detail into member_health, put the conservative
        # aggregate in the scalar slots
        vecs = [np.asarray(v) for v in vals.values() if np.ndim(v) >= 1]
        fleet_b = int(vecs[0].shape[0]) if vecs else 0
        member_health = None
        if fleet_b:
            member_health = {k: _member_list(k, v)
                             for k, v in vals.items()
                             if np.ndim(v) >= 1}
            vals = {k: (_FLEET_AGG[k](np.asarray(v))
                        if np.ndim(v) >= 1 and k in _FLEET_AGG else v)
                    for k, v in vals.items()}
        if dt is not None and np.ndim(dt) >= 1:
            if member_health is not None:
                member_health["dt"] = _member_list("dt", dt)
            dt = float(np.min(dt))    # the pacing (slowest-dt) member
        if dt is None:
            dt = (t - self._last_time) if self._last_time is not None \
                else None
        self._last_time = t
        rec = {
            "schema": METRICS_SCHEMA_VERSION,
            "step": int(step),
            "t": float(t),
            "dt": float(dt) if dt is not None else None,
            "wall_ms": round(wall_ms, 3) if wall_ms is not None else None,
        }
        for k in _DIAG_KEYS:
            rec[k] = _jsonable(k, vals.get(k))
        # the active solve-path latch (schema v4): a host string — from
        # the diag when a producer supplies one, else the
        # driver's .poisson_mode property; never a device value
        pm = diag.get("poisson_mode")
        if pm is None and sim is not None:
            pm = getattr(sim, "poisson_mode", None)
        rec["poisson_mode"] = str(pm) if pm is not None else None
        # kernel-tier attribution (schema v6) and BC/case attribution
        # (schema v8): same diag-then-driver pull as poisson_mode —
        # host strings from constructor latches (.bc_table is the
        # table's token string, .case the case-registry tag)
        for key in ("kernel_tier", "prec_mode", "smoother_tier",
                    "bc_table", "case"):
            kv = diag.get(key)
            if kv is None and sim is not None:
                kv = getattr(sim, key, None)
            rec[key] = str(kv) if kv is not None else None
        rec.update(self._amr_fields(sim))
        rec["bodies"] = diag.get("bodies")
        fb = getattr(sim, "_force_blocks", None)
        rec["force_blocks"] = list(fb) if fb else None
        rec["force_cap"] = list(sim._fcap) if fb else None
        rec.update(self._comm_fields(sim))
        rec.update(self._counter_fields())
        rec.update(self._guard_fields())
        rec["fleet_members"] = fleet_b or None
        rec["member_steps_per_s"] = (
            round(fleet_b * 1e3 / wall_ms, 3)
            if fleet_b and wall_ms else None)
        # fleet serving gauges (schema v7): host state on the server;
        # null slots on every non-serving record
        serve = (self.server.telemetry_fields()
                 if self.server is not None else {})
        for k in _SERVE_KEYS:
            rec[k] = serve.get(k)
        if (self.server is not None and self.server.clients is not None
                and member_health is not None):
            # the per-client split (schema v7): per-member rows ride
            # their own JSONL streams keyed by client id — the
            # aggregate record keeps only the conservative folds
            self._emit_client_rows(rec, member_health)
            member_health = None
        rec["member_health"] = member_health
        rec.update(self._flight_fields())
        if self.sink is not None:
            self.sink.emit(event="metrics", **rec)
        return rec

    def _emit_client_rows(self, rec: dict, member_health: dict) -> None:
        """One JSONL row per slot that was OCCUPIED during the recorded
        step (``server.step_clients`` — a member retiring at the end of
        that very step must still get its final row; ``client_of`` is
        already cleared by then): the member's slice of the pulled diag
        vectors plus its own clock (``sim.times[m]`` — the aggregate
        record's ``t`` is only the pool min; the retiree's final clock
        survives until the next cycle's refill). Slots parked for the
        whole step have no client and emit nothing."""
        srv = self.server
        sim = srv.sim
        nm = len(next(iter(member_health.values())))
        for m in range(nm):
            cid = srv.step_clients[m]
            if cid is None:
                continue
            row = {k: v[m] for k, v in member_health.items()}
            srv.clients.emit(cid, {
                "event": "metrics", "client": str(cid), "member": m,
                "step": rec["step"], "t": float(sim.times[m]), **row})

    def _amr_fields(self, sim) -> dict:
        f = getattr(sim, "forest", None)
        if f is None:
            return {"n_blocks": None, "pad_blocks": None,
                    "blocks_per_level": None,
                    "refines": None, "coarsens": None}
        if self._lvl_cache[0] != f.version:
            order = getattr(sim, "_order", None)
            if order is None:
                order = f.order()
            lv, cnt = np.unique(f.level[order], return_counts=True)
            hist = {str(int(l)): int(c) for l, c in zip(lv, cnt)}
            self._lvl_cache = (f.version, hist, int(len(order)))
        nr = getattr(sim, "_n_refined", 0)
        nc = getattr(sim, "_n_coarsened", 0)
        ref_d = nr - self._last_regrid[0]
        coa_d = nc - self._last_regrid[1]
        self._last_regrid = (nr, nc)
        mask = getattr(sim, "_mask", None)
        return {"n_blocks": self._lvl_cache[2],
                "pad_blocks": int(len(mask)) if mask is not None else None,
                "blocks_per_level": self._lvl_cache[1],
                "refines": ref_d, "coarsens": coa_d}

    def _comm_fields(self, sim) -> dict:
        st = getattr(sim, "_comm_stats", None)
        if not st:
            return {"halo_real_bytes": None, "halo_padded_bytes": None}
        return {"halo_real_bytes": int(st["halo_real_bytes"]),
                "halo_padded_bytes": int(st["halo_padded_bytes"])}

    def _counter_fields(self) -> dict:
        if self.counters is None:
            return {"jit_compiles": None, "device_gets": None,
                    "state_gathers": None, "hbm_peak_bytes": None}
        cur = self.counters.snapshot()
        last = self._last_counters or {k: 0 for k in cur}
        self._last_counters = cur
        return {
            "jit_compiles": cur["jit_compiles"] - last["jit_compiles"],
            "device_gets": cur["device_gets"] - last["device_gets"],
            "state_gathers": (cur["state_gathers"]
                              - last.get("state_gathers", 0)),
            "hbm_peak_bytes": hbm_peak_bytes(),
        }

    def _guard_fields(self) -> dict:
        """Supervision telemetry: the device snapshot ring's HBM bytes
        (absolute — host metadata on the arrays, no sync), the
        replayed-step delta of the snapshot-cadence recovery path, and
        the elastic-topology group (schema v5): epoch / cumulative
        re-mesh count / per-record re-mesh wall cost, the mirror-tier
        group (schema v9): held mirror bytes / per-record mirror
        enqueue cost / last restore rung, all host state on the
        guard."""
        if self.guard is None:
            return {"snap_ring_bytes": None, "replayed_steps": None,
                    "topology_epoch": None, "remesh_count": None,
                    "remesh_ms": None, "mirror_bytes": None,
                    "mirror_ms": None, "restore_source": None}
        cur = int(getattr(self.guard, "replayed_steps", 0))
        delta = cur - self._last_replayed
        self._last_replayed = cur
        ms_total = float(getattr(self.guard, "remesh_ms_total", 0.0))
        ms_delta = ms_total - self._last_remesh_ms
        self._last_remesh_ms = ms_total
        mirroring = getattr(self.guard, "mirror_hosts", None) is not None
        mir_total = float(getattr(self.guard, "mirror_ms_total", 0.0))
        mir_delta = mir_total - self._last_mirror_ms
        self._last_mirror_ms = mir_total
        src = getattr(self.guard, "restore_source", None)
        return {"snap_ring_bytes": int(self.guard.ring_nbytes()),
                "replayed_steps": delta,
                "topology_epoch": int(
                    getattr(self.guard, "topology_epoch", 0)),
                "remesh_count": int(
                    getattr(self.guard, "remesh_count", 0)),
                "remesh_ms": (round(ms_delta, 3)
                              if ms_delta > 0 else None),
                "mirror_bytes": (int(self.guard.mirror_nbytes())
                                 if mirroring else None),
                "mirror_ms": (round(mir_delta, 3)
                              if mir_delta > 0 else None),
                "restore_source": (str(src) if src is not None
                                   else None)}

    def _flight_fields(self) -> dict:
        """Flight-recorder gauges (schema v10): host state on the
        recorder — span volume, attributed compile cost, ledgered
        executable footprint. Null slots with no recorder attached."""
        f = self.flight
        if f is None:
            return {"span_count": None, "compile_ms_total": None,
                    "hbm_exec_bytes": None}
        hbm = f.hbm_exec_bytes()
        return {"span_count": int(f.span_count),
                "compile_ms_total": round(f.compile_ms_total, 3),
                "hbm_exec_bytes": int(hbm) if hbm else None}


class ClientStreams:
    """Per-client JSONL telemetry (schema v7): one append-only stream
    per serving client id under ``dirpath``, written by the
    MetricsRecorder's serving split — the per-member rows that used to
    exist only folded inside the aggregate record's ``member_health``.
    A session's telemetry thereby survives slot reuse (the slot index
    is an allocator detail; the client id is the identity) and is
    readable per client by ``post --metrics``.

    ``rotate_mb`` caps each stream file: a stream crossing the cap is
    renamed to ``<name>.jsonl.N`` (N ascending in rotation order) and
    reopened fresh — same scheme as ``EventLog``; ``load_metrics``
    reads the segments back in order. Off (None) by default: rotation
    exists for long serving runs, not 200-step CI drills."""

    def __init__(self, dirpath: str, rotate_mb: Optional[float] = None):
        self.dir = dirpath
        os.makedirs(dirpath, exist_ok=True)
        self._files: dict = {}
        self.rotate_bytes = (int(rotate_mb * 2 ** 20)
                             if rotate_mb else None)
        self._seq: dict = {}

    @staticmethod
    def _fname(cid) -> str:
        # client ids come from the request queue — sanitize into a flat
        # filename (no separators, no dot-prefix surprises)
        s = "".join(c if c.isalnum() or c in "-_." else "_"
                    for c in str(cid))
        return (s or "client").lstrip(".") + ".jsonl"

    def path_of(self, cid) -> str:
        return os.path.join(self.dir, self._fname(cid))

    def emit(self, cid, rec: dict) -> None:
        f = self._files.get(cid)
        if f is None:
            f = open(self.path_of(cid), "a")
            self._files[cid] = f
        f.write(json.dumps(rec, sort_keys=True, default=float) + "\n")
        f.flush()
        if self.rotate_bytes and f.tell() >= self.rotate_bytes:
            path = self.path_of(cid)
            f.close()
            seq = self._seq.get(cid, _next_segment_seq(path))
            os.replace(path, f"{path}.{seq}")
            self._seq[cid] = seq + 1
            self._files[cid] = open(path, "a")

    def close(self, cid=None) -> None:
        """Close one client's stream (retire/evict) or all of them."""
        files = ([self._files.pop(cid)] if cid in self._files
                 else list(self._files.values()) if cid is None else [])
        if cid is None:
            self._files.clear()
        for f in files:
            if not f.closed:
                f.close()


def summarize_client(records: list) -> dict:
    """Aggregate one client stream (clients/<id>.jsonl rows) into the
    per-client summary ``post --metrics`` reports: session extent,
    clock, dt/solver-health stats — the per-member slice analogue of
    :func:`summarize_metrics`."""
    recs = [r for r in records if r.get("event", "metrics") == "metrics"]

    def col(key):
        return [r[key] for r in recs if r.get(key) is not None]

    def stats(xs):
        if not xs:
            return None
        return {"mean": round(float(np.mean(xs)), 6),
                "max": round(float(np.max(xs)), 6)}

    return {
        "steps": len(recs),
        "t_first": recs[0]["t"] if recs else None,
        "t_final": recs[-1]["t"] if recs else None,
        "dt": stats(col("dt")),
        "umax_max": (max(col("umax")) if col("umax") else None),
        "energy_last": (col("energy")[-1] if col("energy") else None),
        "poisson_iters": stats(col("poisson_iters")),
        "poisson_residual_max": (max(col("poisson_residual"))
                                 if col("poisson_residual") else None),
        "div_linf_max": (max(col("div_linf"))
                         if col("div_linf") else None),
        "finite_all": (all(col("finite")) if col("finite") else None),
    }


def _next_segment_seq(path: str) -> int:
    """1 + the highest existing numeric rotation suffix of ``path``."""
    import glob
    top = 0
    for p in glob.glob(path + ".*"):
        suf = p[len(path) + 1:]
        if suf.isdigit():
            top = max(top, int(suf))
    return top + 1


def _segment_paths(path: str) -> list:
    """Rotated segments of ``path`` in write order (``path.1`` oldest),
    then the live file itself."""
    import glob
    segs = []
    for p in glob.glob(path + ".*"):
        suf = p[len(path) + 1:]
        if suf.isdigit():
            segs.append((int(suf), p))
    return [p for _, p in sorted(segs)] + [path]


def load_metrics(path: str) -> list:
    """All JSONL records from ``path`` and its rotated segments, in
    write order (mixed event streams are fine; `summarize_metrics`
    filters for ``event == "metrics"``). Torn lines are skipped — use
    :func:`load_metrics_report` to count them."""
    return load_metrics_report(path)[0]


def load_metrics_report(path: str) -> tuple:
    """(records, truncated_records) from ``path`` plus rotated
    segments. A SIGKILL'd run leaves a torn last line (and an empty
    file is a run killed before its first record) — both are facts
    about the run, not read errors, so unparseable lines are counted
    and reported instead of raised. A missing path still raises
    ``FileNotFoundError`` unless rotated segments exist for it."""
    out: list = []
    torn = 0
    paths = [p for p in _segment_paths(path) if os.path.exists(p)]
    if not paths:
        open(path).close()     # surface the original FileNotFoundError
    for p in paths:
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    torn += 1
    return out, torn


def summarize_metrics(records: list) -> dict:
    """Aggregate a metrics stream (list of record dicts — from
    :func:`load_metrics` or directly from a recorder) into the summary
    `python -m cup2d_tpu.post --metrics` prints."""
    recs = [r for r in records if r.get("event", "metrics") == "metrics"]

    def col(key):
        return [r[key] for r in recs if r.get(key) is not None]

    def stats(xs):
        if not xs:
            return None
        return {"mean": round(float(np.mean(xs)), 6),
                "max": round(float(np.max(xs)), 6)}

    energy = col("energy")
    out = {
        "schema": METRICS_SCHEMA_VERSION,
        "steps": len(recs),
        "t_first": recs[0]["t"] if recs else None,
        "t_final": recs[-1]["t"] if recs else None,
        "dt": stats(col("dt")),
        "wall_ms": stats(col("wall_ms")),
        "poisson_iters": stats(col("poisson_iters")),
        "poisson_residual_max": (max(col("poisson_residual"))
                                 if col("poisson_residual") else None),
        # solve-path attribution (schema v4): the distinct paths the
        # run's steps took (the trigger can flip mid-run) + cycle cost
        "poisson_modes": (sorted({str(m) for m in col("poisson_mode")})
                          or None),
        # smoother-tier attribution (schema v11): distinct sweep-chain
        # implementations the run's solves used, like poisson_modes
        "smoother_tiers": (sorted({str(m)
                                   for m in col("smoother_tier")})
                           or None),
        "precond_cycles": stats(col("precond_cycles")),
        "energy_first": energy[0] if energy else None,
        "energy_last": energy[-1] if energy else None,
        "div_linf_max": (max(col("div_linf"))
                         if col("div_linf") else None),
        "jit_compiles_total": (sum(col("jit_compiles"))
                               if col("jit_compiles") else None),
        "device_gets_per_step": stats(col("device_gets")),
        "hbm_peak_bytes": (max(col("hbm_peak_bytes"))
                           if col("hbm_peak_bytes") else None),
        "n_blocks_last": (col("n_blocks")[-1]
                          if col("n_blocks") else None),
        "refines_total": (sum(col("refines"))
                          if col("refines") else None),
        "coarsens_total": (sum(col("coarsens"))
                           if col("coarsens") else None),
        # supervision (schema v2): zero steady-state state_gathers is
        # the device-ring win; replays say what recovery cost
        "state_gathers_total": (sum(col("state_gathers"))
                                if col("state_gathers") else None),
        "snap_ring_bytes": (max(col("snap_ring_bytes"))
                            if col("snap_ring_bytes") else None),
        "replayed_steps_total": (sum(col("replayed_steps"))
                                 if col("replayed_steps") else None),
        # elastic topology (schema v5): a run that never lost a host
        # reports epoch 0 / 0 re-meshes
        "topology_epoch": (col("topology_epoch")[-1]
                           if col("topology_epoch") else None),
        "remesh_count": (col("remesh_count")[-1]
                         if col("remesh_count") else None),
        # mirror tier (schema v9): held redundancy bytes, total
        # enqueue-side mirror cost, and the rung the last recovery
        # restored from — mirror-attributed real-loss resumes show
        # "mirror" here
        "mirror_bytes": (max(col("mirror_bytes"))
                         if col("mirror_bytes") else None),
        "mirror_ms_total": (round(sum(col("mirror_ms")), 3)
                            if col("mirror_ms") else None),
        "restore_source": (col("restore_source")[-1]
                           if col("restore_source") else None),
        # fleet batching (schema v3): member count + the
        # dispatch-amortization throughput metric
        "fleet_members": (col("fleet_members")[-1]
                          if col("fleet_members") else None),
        "member_steps_per_s": stats(col("member_steps_per_s")),
        # fleet serving (schema v7): occupancy stats of the slot pool +
        # final lifecycle counters (admitted/evicted are cumulative
        # gauges — the last value is the run total)
        "active_members": stats(col("active_members")),
        "occupancy": stats(col("occupancy")),
        "admitted_total": (col("admitted")[-1]
                           if col("admitted") else None),
        "evicted_total": (col("evicted")[-1]
                          if col("evicted") else None),
        "queue_depth": stats(col("queue_depth")),
        # flight recorder (schema v10): cumulative span count, compile
        # blame total and the memory-ledger footprint are gauges — the
        # last value is the run total
        "span_count": (col("span_count")[-1]
                       if col("span_count") else None),
        "compile_ms_total": (col("compile_ms_total")[-1]
                             if col("compile_ms_total") else None),
        "hbm_exec_bytes": (col("hbm_exec_bytes")[-1]
                           if col("hbm_exec_bytes") else None),
    }
    # run-report event rows (emitted once at exit by the CLI): the
    # serving-latency distributions and the compile blame ledger ride
    # the same stream; surface the last of each verbatim
    for ev in ("serving_latency", "compile_ledger"):
        rows = [r for r in records if r.get("event") == ev]
        if rows:
            out[ev] = {k: v for k, v in rows[-1].items()
                       if k != "event"}
    return out
