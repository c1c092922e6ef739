"""Supervised stepping: health verdicts, rewind-and-retry, clean preemption.

The reference's ``main()`` dies on the first NaN and loses the run; our
CLI inherited that (`__main__.py` pre-PR2 aborted with exit 1, missed
Inf, and left the force log unclosed). Production AMR frameworks treat
solver-failure handling and checkpoint/restart as first-class
subsystems (AMReX, arXiv:2009.12009); the atomic-checkpoint half lives
in ``io.py`` — this module is the supervision half on top of it:

- :func:`health_verdict`: a per-step health check that rides the
  diagnostics the step ALREADY pulls (the fused isfinite reduction over
  vel/pres plus the Poisson ``converged``/``stalled`` flags, which the
  solver has always computed and nothing consumed). On the CLI driver
  paths the scalars arrive host-side in the step's existing batched
  pull, so the verdict adds NO device round trips and NO retraces —
  asserted by ``tests/test_resilience.py``.
- :class:`StepGuard`: keeps a DEVICE-RESIDENT ring of good-state
  snapshots (HBM copies via ``io.snapshot_state_device`` — no D2H
  gather; the host ring of PR 2/3 taxed every good step with a full
  state transfer, the former ROADMAP pod gap (b)) and on a bad verdict
  walks a bounded recovery ladder:

      1. rewind to the last device snapshot, replay the recorded good
         steps since it bit-exactly (``snap_every`` cadence), retry the
         failed step at dt/2
      2. rewind/replay again, retry with the exact Poisson solve
      3. restore from the on-disk checkpoint and resume
      4. abort — post-mortem checkpoint + closed force log

  Every rung emits one JSONL event (step, verdict, action, replayed)
  through :class:`EventLog`.

  The verdict is ONE-STEP-LAGGED on the device-diag drivers (the
  obstacle-free uniform/AMR paths, ``sim.async_diag``): step N's diag
  stays on device, step N+1 is dispatched first, and only then is N's
  scalar set pulled — still exactly one batched ``device_get`` per
  step, now overlapped with N+1's compute instead of idling the
  device. Detection latency is 1 step; the pending post-N snapshot is
  simply discarded when N turns out bad, so the rewind target is still
  the pre-N state. Drivers whose diag arrives host-side at dispatch
  (the shaped paths must pull uvw/CoM for the host kinematics anyway)
  verdict eagerly — the lag would buy nothing there and the host
  kinematics must never consume unverdicted scalars. Callers finish a
  run with :meth:`StepGuard.drain` (the final step's verdict is still
  pending at loop exit).
- :class:`PhysicsWatchdog`: windowed drift bounds on the fused physics
  invariants (kinetic energy, max |∇·u|) the diag pull carries since
  PR 3 — catches wrong-but-FINITE corruption the isfinite verdict
  cannot (the former ROADMAP open item), feeding the same ladder.
- :class:`PreemptionGuard`: SIGTERM latches a flag; the driver loop
  checkpoints at the next step boundary and exits 0 (preemptible-pod
  semantics: the grace window is spent writing the restart point, not
  dying mid-collective).

- :class:`FleetStepGuard`: the per-member generalization for the
  fleet-batched driver (fleet.py) — vectorized verdicts over the [B]
  diag vectors of one fused dispatch; a bad member restores ONLY its
  slice of the device snapshot ring and replays solo, healthy members
  never rewind.

Multi-host note: the verdict scalars are outputs of global reductions
(replicated by SPMD semantics) and the device snapshots are per-shard
local copies (no collective at all — strictly safer than the host
gather they replace), so every process reaches the same ladder
decision in the same order — the determinism contract of
``parallel/launch.py`` extends to recovery. The SIGTERM latch is
per-process but the DECISION is not: :meth:`PreemptionGuard.agree`
min-allreduces the flag at every step boundary, so all hosts enter the
collective checkpoint at the same step (the former ROADMAP pod gap
(a); drilled by the skewed-delivery phase of the multihost harness).

Topology-changing loss (the one failure class the ladder above cannot
touch — a host or process dropping OUT of the SPMD program) is handled
by the elastic subsystem (PR 7):

- :class:`TopologyGuard`: detection + agreement. The heartbeat
  piggybacks on the step-boundary collective the run already pays
  (:meth:`PreemptionGuard.agree`'s one-int allgather grows to a
  three-int payload: SIGTERM latch, topology epoch, exiting flag) and
  is BOUNDED — the collective runs under a deadline, so a peer that
  died mid-step surfaces as a timeout instead of an infinite hang. A
  host that misses ``miss_k`` consecutive beats (or announces a
  graceful exit in its last beat) is DECLARED lost; every survivor
  computes the same new device set from the same allgathered evidence
  and bumps the same epoch counter — the deterministic agreement that
  keeps the re-mesh collective-safe. Single-process runs can stand up
  a SIMULATED topology (``sim_hosts=H`` groups the virtual devices
  into H hosts) whose losses are injected by ``faults.py``
  ``host_exit@N`` / ``host_hang@N`` directives — the tier-1 drill.
- :meth:`StepGuard.elastic_recover`: re-mesh + resume. Survivor
  devices become a fresh mesh (``parallel.mesh.make_mesh``), the sim
  rebuilds its placement/tables/step executable over it
  (``sim.remesh``), and the state comes from the device snapshot ring
  where the surviving shards still cover it (``io.snapshot_covers`` —
  re-sharded onto the new mesh by ``io.restore_snapshot_resharded``),
  falling back to the last disk checkpoint otherwise. No process
  relaunch. Every stage emits one JSONL event (``topology_lost``,
  ``remesh``) and the telemetry stream carries the schema-v5
  ``topology_epoch`` / ``remesh_*`` field group.

Real-pod coverage note: per-shard-local snapshots die with their host
(an x-split state loses the lost host's columns). The host-redundant
MIRRORED ring (PR 17) closes that gap: every capture additionally
ships each host's shard block to its ring neighbor (io.MirroredSnapshot
via parallel.mesh.host_ring_shift, checksummed on device), and
``elastic_recover`` gains a mirrored-ring rung between the plain ring
and disk — reconstruct the lost hosts' blocks from the survivors'
mirrors (io.restore_snapshot_mirrored), re-shard, replay. The ladder
is ring -> mirror -> disk -> abort; the mirror rung degrades to disk
when the anchor carries no mirror (cadence staleness), the checksum
rejects (``mirror_reject`` event), or a lost host's ring neighbor died
with it. Drilled end-to-end on CPU with the destroyed-shard semantics
(``shard_loss@N`` zeroes the dead host's slices first, so the resumed
bytes provably came from the mirror); the 2-process real-runtime
drills remain slow-marked (`tests/_multihost_worker.py`; the harness
is environment-broken in this container, see ROADMAP).
"""

from __future__ import annotations

import json
import os
import sys
import time
from collections import deque
from typing import NamedTuple, Optional

import numpy as np

from . import tracing


# ---------------------------------------------------------------------------
# distributed-runtime probe (no backend touch, no private API)
# ---------------------------------------------------------------------------

def dist_initialized() -> bool:
    """True when the jax distributed runtime is initialized. Never
    probes the backend (safe to call before a later ``initialize()``)."""
    import jax
    return bool(jax.distributed.is_initialized())


# ---------------------------------------------------------------------------
# JSONL event log
# ---------------------------------------------------------------------------

class EventLog:
    """Append-only JSONL log of resilience events (one object per line,
    flushed per event so a dying process keeps its tail).

    Multi-host: once the distributed runtime is up, only process 0
    writes — the recovery decisions are replicated by construction
    (see the module docstring), so N processes appending the same
    lines to one shared-FS file would only duplicate and interleave
    them. Events BEFORE the runtime joins (coordinator connect
    retries) are written by every process: they are genuinely
    per-process and the world membership is unknown at that point.
    ``all_writers=True`` (the span-timeline sink) opts OUT of the
    process-0 gate: spans are genuinely per-process, so every process
    writes — to its own ``<path>.p<idx>`` file past process 0, never
    interleaving on a shared FS (the Perfetto export merges them).

    ``rotate_mb`` caps the file (``-logRotateMB``, default off): on
    crossing the cap the live file is renamed to the next numbered
    segment ``<path>.N`` and reopened fresh; ``profiling.load_metrics``
    reads the segments back in write order. Off by default — rotation
    exists for long serving runs, and a rotated-away segment is no
    longer fsync-reachable for the durable-event tail guarantee."""

    def __init__(self, path: str, rotate_mb=None, all_writers=False):
        self._all_writers = bool(all_writers)
        if self._all_writers:
            try:
                import jax
                if dist_initialized() and jax.process_index() > 0:
                    path = f"{path}.p{jax.process_index()}"
            except Exception:
                pass
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self.rotate_bytes = (int(rotate_mb * 2 ** 20) if rotate_mb
                             else None)
        self._seq = None
        self._f = open(path, "a")

    def _is_writer(self) -> bool:
        # version-safe no-probe check (dist_initialized above): must
        # not touch the XLA backend — EventLog exists before
        # init_distributed runs, and a backend probe would make a
        # later initialize() impossible
        if self._all_writers:
            return True
        import jax
        return (not dist_initialized()) or jax.process_index() == 0

    # recovery-critical events are fsynced at emit: a process that dies
    # right after a remesh (exactly the failure class the elastic path
    # exists for) must not take the event trail post-mortem triage
    # depends on into the page cache with it. Per-step metrics and
    # routine events keep the cheap buffered write+flush path — fsync
    # per step would serialize the dispatch pipeline on disk latency.
    _DURABLE_EVENTS = frozenset({
        "topology_lost", "remesh", "member_abort", "member_aborted",
        "mirror_reject",
    })

    def emit(self, **fields) -> None:
        if not self._is_writer():
            return
        fields.setdefault("wall", time.time())
        self._f.write(json.dumps(fields, sort_keys=True,
                                 default=float) + "\n")
        self._f.flush()
        if fields.get("event") in self._DURABLE_EVENTS:
            try:
                os.fsync(self._f.fileno())
            except OSError:
                pass    # non-seekable sink (pipe/pty): flush is all it has
        if self.rotate_bytes and self._f.tell() >= self.rotate_bytes:
            self._rotate()

    def _rotate(self) -> None:
        self._f.close()
        if self._seq is None:
            from .profiling import _next_segment_seq
            self._seq = _next_segment_seq(self.path)
        os.replace(self.path, f"{self.path}.{self._seq}")
        self._seq += 1
        self._f = open(self.path, "a")

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()


_EVENT_LOG: Optional[EventLog] = None


def set_event_log(log: Optional[EventLog]) -> None:
    """Register the process-wide event sink (io.py's checkpoint-fallback
    warning and launch.py's connect-retry report through it)."""
    global _EVENT_LOG
    _EVENT_LOG = log


def record_event(**fields) -> None:
    """Emit into the registered event log; silently dropped when no run
    log is active (library users without a supervised loop)."""
    if _EVENT_LOG is not None:
        _EVENT_LOG.emit(**fields)


# ---------------------------------------------------------------------------
# per-step health verdict
# ---------------------------------------------------------------------------

class StepVerdict(NamedTuple):
    ok: bool
    reason: str           # "ok" | "nonfinite" | "poisson_nonfinite"
    #                     | "poisson_exhausted" | "poisson_giveup(injected)"
    #                     | "invariant_umax" | "invariant_energy"
    #                     | "invariant_divergence"


_HEALTH_KEYS = ("finite", "umax", "poisson_converged", "poisson_stalled",
                "poisson_residual")

# the fused on-device physics invariants (uniform.step_diag /
# amr._step_impl): watchdog inputs, riding the same batched diag pull
_INVARIANT_KEYS = ("energy", "div_linf")

# everything the guard's ONE batched pull fetches per step: health +
# invariants + the trigger/telemetry scalars + the dt actually used
# (the async drivers put it in the diag — the lagged clock and the
# replay dts come from this same pull)
_PULL_KEYS = _HEALTH_KEYS + _INVARIANT_KEYS + (
    "poisson_iters", "precond_cycles", "dt_next", "dt")


def _waited_for(sp, vals: dict, pend) -> None:
    """What a ``verdict`` span waited for, from the host values its
    pull has just read (no further pull): the solver's counts of the
    step it fenced, so one line of spans.jsonl explains a long wait.
    Fleet pulls are [B] vectors: the slowest member's counts."""
    if sp is None:              # recorder off: the shared nullcontext
        return
    for attr, key in (("iters", "poisson_iters"),
                      ("cycles", "precond_cycles")):
        v = vals.get(key)
        if v is not None:
            sp.attrs[attr] = int(np.max(v))
    sp.attrs["exact"] = bool(pend.exact)


def _host_scalars(diag: dict, keys) -> dict:
    """The named diag entries as host scalars. On the CLI driver paths
    every value is already host-side (batched into the step's one
    existing pull); library paths that keep scalars on device pay ONE
    ``device_get`` for the whole set."""
    import jax

    vals = {k: diag[k] for k in keys if k in diag}
    if any(isinstance(v, jax.Array) for v in vals.values()):
        vals = jax.device_get(vals)
    return vals


def health_verdict(diag: dict,
                   residual_ok: Optional[float] = None) -> StepVerdict:
    """Classify a step's diagnostics dict.

    Policy: a step is BAD when (a) the fused isfinite reduction over
    vel/pres failed (covers the Inf the old ``umax != umax`` check
    missed), (b) the Poisson residual itself is nonfinite, or (c) the
    solve exited neither converged nor stalled — a breakdown give-up
    past the restart budget, or max_iter exhaustion — with a residual
    above ``residual_ok``. A ``stalled`` exit is NOT bad: it is the
    solver's precision floor (exact-mode solves end there by design,
    see poisson.bicgstab). ``residual_ok`` (the StepGuard passes 100x
    the case's poisson_tol) keeps a merely budget-capped solve that
    still sits near its target out of the recovery ladder — the
    reference ran its whole life with unchecked budget exhaustion;
    exhaustion with a residual FAR above target is what recovery is
    for. ``residual_ok=None`` flags every non-converged non-stalled
    exit (strict mode).

    On the CLI driver paths every value here is already host-side
    (batched into the step's one existing pull); if any is still a
    device array (library paths that keep scalars on device, e.g. the
    obstacle-free AMR step), they are fetched in ONE device_get.
    """
    vals = _host_scalars(diag, _HEALTH_KEYS)
    finite = vals.get("finite")
    if finite is None:
        u = float(vals.get("umax", 0.0))
        finite = np.isfinite(u)
    if not bool(finite):
        return StepVerdict(False, "nonfinite")
    resid = vals.get("poisson_residual")
    if resid is not None and not np.isfinite(float(resid)):
        return StepVerdict(False, "poisson_nonfinite")
    conv = vals.get("poisson_converged")
    stall = vals.get("poisson_stalled")
    if conv is not None and not bool(conv) \
            and stall is not None and not bool(stall):
        rf = float(resid) if resid is not None else float("inf")
        if residual_ok is None or not (rf <= residual_ok):
            return StepVerdict(False, "poisson_exhausted")
    return StepVerdict(True, "ok")


# ---------------------------------------------------------------------------
# physics-invariant watchdog (the silent-corruption gap, ROADMAP)
# ---------------------------------------------------------------------------

class PhysicsWatchdog:
    """Windowed drift bounds on the fused physics invariants (umax,
    kinetic energy, max |∇·u|) that every step's diag already carries.

    The health verdict's isfinite reduction catches NaN/Inf, but
    wrong-but-FINITE fields (a bit-flipped exponent, a corrupted halo
    exchange, a stale buffer reinstalled by a bad restore) sail through
    it — the ROADMAP open item this closes. Physics pins them down: a
    viscous box flow cannot multiply its velocity scale or kinetic
    energy inside one step, and advection bounds the divergence
    production, so a step whose invariants jump far outside the recent
    window is corrupt even though every number in it is finite.

    Policy (deliberately loose — a FALSE positive costs a rewind-retry
    and forks the trajectory, so the bounds are orders of magnitude
    above legitimate step-to-step variation):

    - each invariant ARMS itself independently, and only once its
      window is both full and SETTLED (window max/min <= its settle
      ratio). Relative drift bounds are meaningless on an unsettled
      signal: during spin-up from rest the kinetic energy legitimately
      multiplies per step (measured on the deforming-fish case: a dt/2
      retry lands 8x the window max while E is still ~1e-10), so an
      unsettled invariant stays dormant rather than false-positive.
      umax is the invariant that arms FIRST in practice — it is
      body-velocity-dominated and near-constant from the first steps
      even while the energy still ramps — so corruption is caught long
      before the energy bound wakes up;
    - umax: BAD when outside [window min / factor, factor x window max]
      (``umax_factor``, settle ``umax_settle``);
    - energy: same two-sided bound (``energy_factor``/``energy_settle``
      — corruption can deflate as well as inflate; legitimate viscous
      decay is a few % per step, never a 4x cliff inside an 8-step
      window);
    - divergence: BAD when max |∇·u| > ``div_factor`` x the window max
      (one-sided — a too-CLEAN divergence is what the projection aims
      for; settle ``div_settle``).

    Drive it through :class:`StepGuard` (``watchdog=``): a flagged step
    walks the same recovery ladder as a nonfinite one, and only steps
    with an OK final verdict enter the window — a corrupted step can
    never poison its own baseline. ``tests/test_telemetry.py`` injects
    a wrong-but-finite field (``faults.py scale_vel``) and asserts the
    flag + recovery; an unfaulted guarded run stays bit-identical."""

    def __init__(self, window: int = 8,
                 umax_factor: float = 4.0, umax_settle: float = 2.0,
                 energy_factor: float = 4.0, energy_settle: float = 2.0,
                 div_factor: float = 50.0, div_settle: float = 4.0):
        self.window = int(window)
        self.umax_factor = float(umax_factor)
        self.umax_settle = float(umax_settle)
        self.energy_factor = float(energy_factor)
        self.energy_settle = float(energy_settle)
        self.div_factor = float(div_factor)
        self.div_settle = float(div_settle)
        self.umax: deque = deque(maxlen=self.window)
        self.energy: deque = deque(maxlen=self.window)
        self.div: deque = deque(maxlen=self.window)

    @classmethod
    def for_prec(cls, prec_mode: str, **kw) -> "PhysicsWatchdog":
        """Tolerance band matched to the driver's storage-precision
        contract (``sim.prec_mode``, PR 9). The bf16 tier's legitimate
        step-to-step invariant jitter is ~2^-8 relative (bf16 mantissa)
        instead of f32's ~2^-23, so its windows settle later and sit
        wider: the settle ratios and the one-sided divergence factor
        loosen. The CORRUPTION factors stay put where they bound
        corruption scale, not rounding (a 4x energy cliff inside an
        8-step window is corrupt in any precision); div_factor doubles
        because the projection's reachable divergence floor — the
        window baseline the factor multiplies — is itself noisier at
        bf16 storage. Explicit ``**kw`` overrides win."""
        if prec_mode == "bf16":
            kw.setdefault("umax_settle", 2.5)
            kw.setdefault("energy_settle", 2.5)
            kw.setdefault("div_settle", 8.0)
            kw.setdefault("div_factor", 100.0)
        return cls(**kw)

    def _armed(self, hist: deque, settle: float):
        """(hi, lo) when the invariant's window is full and settled,
        else None — drift bounds only mean something against a stable
        baseline."""
        if len(hist) < self.window:
            return None
        hi, lo = max(hist), min(hist)
        if lo <= 0.0 or hi > settle * lo:
            return None
        return hi, lo

    def check(self, vals: dict) -> Optional[str]:
        """Verdict reason for a drifted invariant, or None. ``vals``
        holds host scalars (the guard pre-pulls them with the health
        keys in one batch)."""
        u = vals.get("umax")
        band = self._armed(self.umax, self.umax_settle)
        if u is not None and band is not None:
            hi, lo = band
            if not (lo / self.umax_factor <= float(u)
                    <= self.umax_factor * hi):
                return "invariant_umax"
        e = vals.get("energy")
        band = self._armed(self.energy, self.energy_settle)
        if e is not None and band is not None:
            hi, lo = band
            if not (lo / self.energy_factor <= float(e)
                    <= self.energy_factor * hi):
                return "invariant_energy"
        d = vals.get("div_linf")
        band = self._armed(self.div, self.div_settle)
        if d is not None and band is not None:
            hi, _ = band
            if float(d) > self.div_factor * hi:
                return "invariant_divergence"
        return None

    def observe(self, vals: dict) -> None:
        """Fold a GOOD step's invariants into the window."""
        if vals.get("umax") is not None:
            self.umax.append(float(vals["umax"]))
        if vals.get("energy") is not None:
            self.energy.append(float(vals["energy"]))
        if vals.get("div_linf") is not None:
            self.div.append(float(vals["div_linf"]))

    def reset(self) -> None:
        """Drop the window (after a disk restore the history describes
        steps FORWARD of the restored point)."""
        self.umax.clear()
        self.energy.clear()
        self.div.clear()


# ---------------------------------------------------------------------------
# the supervised stepper
# ---------------------------------------------------------------------------

class ResilienceAbort(RuntimeError):
    """The recovery ladder is exhausted; the run cannot continue. A
    post-mortem checkpoint (if configured) was written before raising."""


class _Pending:
    """One dispatched-but-unverdicted step (the lagged slot)."""

    __slots__ = ("step0", "t0", "diag", "exact", "dt_host", "advanced",
                 "snap", "trig", "fired", "mode", "tier")

    def __init__(self, step0, t0, diag, exact, dt_host, advanced,
                 snap=None, trig=None, fired=(), mode=None, tier=None):
        self.step0 = step0
        self.t0 = t0
        self.diag = diag
        self.exact = exact
        self.dt_host = dt_host       # None on the async (device-dt) paths
        self.advanced = advanced     # driver advanced sim.time at dispatch
        self.snap = snap             # optimistic post-step device snapshot
        self.trig = trig             # (coarse_on, last_iters) at dispatch
        self.fired = fired           # fault entries this dispatch consumed
        self.mode = mode             # sim.poisson_mode at dispatch (v4):
        #                              a lagged commit must label step N
        #                              with the path N actually TOOK, not
        #                              the live mode after N+1's dispatch
        #                              may have flipped the trigger
        self.tier = tier             # sim.kernel_tier at dispatch (v6/
        #                              ISSUE 16): BC-token-suffixed tier
        #                              string, captured under the same
        #                              lagged-commit rule as mode


class StepGuard:
    """Wraps ``sim.step_once`` with verdict + bounded recovery ladder.

    Parameters
    ----------
    sim : Simulation | AMRSim | UniformSim (step_once/time/step_count)
    ring : confirmed device snapshots to keep in HBM (>= 1). The ladder
        consumes only the LATEST anchor; an unconfirmed post-step
        snapshot additionally lives in the pending slot under the
        lagged verdict, so >= 2 snapshots coexist in HBM whenever a
        cadence step is in flight — that pairing is what lets a
        late-detected bad step N still rewind to the pre-N state.
    ckpt_dir : the run's on-disk checkpoint (the disk-restore rung;
        None or missing disables that rung)
    postmortem_dir : where the abort rung writes its final checkpoint
    event_log : EventLog for the JSONL recovery events
    faults : FaultPlan whose pre/post-step hooks this guard drives
        (suspended during replay — replay reproduces verdicted-good
        steps, it is not a fresh attempt)
    recover : False = verdict-only mode (first bad verdict aborts, with
        the same post-mortem/event path)
    watchdog : PhysicsWatchdog consulted after the health verdict (a
        drifted invariant walks the same recovery ladder; None skips
        the invariant check)
    snap_every : device-snapshot cadence in good steps (``-snapEvery``).
        N > 1 amortizes even the HBM copy: the dt/exact sequence since
        the last snapshot is recorded, and a bad verdict restores the
        snapshot and REPLAYS forward bit-exactly (same dts, same solver
        branches, faults suspended) to the failed step before entering
        the ladder.
    lag : one-step-lagged verdict (default on). Device-diag drivers
        (``sim.async_diag``) keep their scalars on device; the guard
        dispatches step N+1, then pulls step N's set — the one batched
        ``device_get`` per step moves off the critical path. Host-diag
        drivers verdict eagerly either way.
    mirror_hosts : host-ring size for the host-redundant mirrored
        snapshot tier (None/<2 disables it — the default, bit-identical
        to the pre-mirror guard). When set, every captured snapshot
        additionally ships each host's shard block to its ring neighbor
        (io.mirror_snapshot: one shard_map ppermute + on-device
        checksums, enqueued before the next dispatch donates its
        buffers — zero host transfers), and ``elastic_recover`` gains
        the mirrored-ring rung between ring and disk.
    mirror_every : mirror cadence in snapshots (``-mirrorEvery``): N > 1
        mirrors every Nth capture — anchors between carry no mirror, so
        a loss there finds the mirror rung stale and degrades to disk.
    """

    def __init__(self, sim, *, ring: int = 1, ckpt_dir: Optional[str] = None,
                 postmortem_dir: Optional[str] = None,
                 event_log: Optional[EventLog] = None,
                 faults=None, recover: bool = True, watchdog=None,
                 snap_every: int = 1, lag: bool = True,
                 mirror_hosts: Optional[int] = None,
                 mirror_every: int = 1):
        self.sim = sim
        self.ring: deque = deque(maxlen=max(1, int(ring)))
        self.ckpt_dir = ckpt_dir
        self.postmortem_dir = postmortem_dir
        self.event_log = event_log
        self.faults = faults
        self.recover = recover
        self.watchdog = watchdog
        self.snap_every = max(1, int(snap_every))
        self.lag = bool(lag)
        self.recoveries = 0       # completed recovery actions (telemetry)
        self.replayed_steps = 0   # cumulative replayed steps (telemetry)
        # elastic-topology state (schema v5 field group; advanced only
        # by elastic_recover — a run that never loses a host reports
        # epoch 0 / count 0 forever)
        self.topology_epoch = 0
        self.remesh_count = 0
        self.remesh_ms_total = 0.0
        # host-redundant mirrored snapshot tier (PR 17, schema v9
        # field group). mirror_hosts None/<2 keeps every mirror code
        # path dormant — bit-identical dispatch stream to the
        # pre-mirror guard, zero extra host syncs.
        self.mirror_hosts = (int(mirror_hosts)
                             if mirror_hosts and int(mirror_hosts) >= 2
                             else None)
        self.mirror_every = max(1, int(mirror_every))
        self.mirror_ms_total = 0.0   # enqueue-side cost (telemetry)
        self.restore_source = None   # last recovery rung: ring|mirror|disk
        self._mirror_tick = 0
        self._pendings: list = []
        self._replay: list = []   # (dt, exact, trig) good steps since anchor
        self._since_snap = 0
        self._last_fired = ()     # fault entries the last _attempt consumed
        # two-level-trigger freshness (PR 6): True from each re-anchor
        # until the first PRODUCTION verdict delivers the new
        # topology's iteration count — the window where the lagged
        # pipeline would otherwise consult stale trigger evidence (see
        # step())
        self._trigger_fresh = False
        if self.lag and hasattr(sim, "async_diag"):
            # device-diag mode: the obstacle-free branches keep their
            # diag (incl. the dt used) on device and leave the clock
            # settlement to the lagged verdict below
            sim.async_diag = True

    # -- snapshot machinery (device-resident, io.py) ------------------
    def _snapshot(self):
        from .io import snapshot_state_device, mirror_snapshot
        with tracing.span("snapshot", step=int(self.sim.step_count)):
            snap = snapshot_state_device(self.sim)
            mh = self.mirror_hosts
            mesh = getattr(self.sim, "mesh", None)
            if mh is not None and mesh is not None:
                self._mirror_tick += 1
                if self._mirror_tick >= self.mirror_every:
                    t0 = time.perf_counter()
                    with tracing.span("mirror",
                                      step=int(self.sim.step_count)):
                        m = mirror_snapshot(snap, mesh, mh)
                    if m is None:
                        # unmirrorable family (forest payloads, odd
                        # divisibility): latch the tier off rather than
                        # re-probing every capture
                        self.mirror_hosts = None
                    else:
                        snap = snap._replace(mirror=m)
                        self._mirror_tick = 0
                    # enqueue-side only — the collective itself overlaps
                    # with the next dispatch (async device execution)
                    self.mirror_ms_total += \
                        (time.perf_counter() - t0) * 1e3
        return snap

    def ring_nbytes(self) -> int:
        """HBM footprint of every live snapshot (anchors + pending)."""
        from .io import snapshot_nbytes
        n = sum(snapshot_nbytes(s) for s in self.ring)
        return n + sum(snapshot_nbytes(p.snap) for p in self._pendings
                       if p.snap is not None)

    def mirror_nbytes(self) -> int:
        """HBM footprint of the held mirror payloads (anchors +
        pending) — the redundancy the host-redundant tier buys."""
        from .io import mirror_nbytes
        n = sum(mirror_nbytes(s) for s in self.ring)
        return n + sum(mirror_nbytes(p.snap) for p in self._pendings
                       if p.snap is not None)

    def _held_mirror_snaps(self) -> list:
        """Every held snapshot carrying a mirror, newest first (the
        mirror_corrupt fault injector targets the newest)."""
        out = [p.snap for p in reversed(self._pendings)
               if p.snap is not None and p.snap.mirror is not None]
        out += [s for s in reversed(self.ring) if s.mirror is not None]
        return out

    @property
    def pending(self) -> bool:
        """True while a dispatched step awaits its lagged verdict."""
        return bool(self._pendings)

    def _disk_available(self) -> bool:
        return bool(self.ckpt_dir) and (
            os.path.exists(os.path.join(self.ckpt_dir, "meta.json"))
            or os.path.exists(os.path.join(
                self.ckpt_dir.rstrip("/") + ".old", "meta.json")))

    # -- one supervised step ------------------------------------------
    def step(self, dt: Optional[float] = None) -> Optional[dict]:
        """Dispatch one step; return the most recently VERDICTED step's
        record (host scalars + ``step``/``t``/``dt``), or None when the
        first lagged dispatch is still in flight."""
        with tracing.span("step", step=int(self.sim.step_count)):
            return self._step_guarded(dt)

    def _step_guarded(self, dt: Optional[float]) -> Optional[dict]:
        self._seed()
        out = None
        # Two-level-trigger freshness window (PR 6): while the trigger
        # is re-armed-but-off after a re-anchor (a regrid, or the run
        # start), resolve the in-flight verdict BEFORE dispatching so
        # the pulled step-N iteration count anchors the trigger that
        # THIS dispatch consults — the preconditioner upgrade then
        # lands at step N+1, same as the eager drivers, instead of the
        # documented one-step-late N+2. The cost is one exposed pull
        # round trip per re-anchor window (the window closes at the
        # first production verdict, _commit); outside it the pull
        # stays overlapped behind the next dispatch as before.
        # Guards on the drain: the upcoming dispatch must be a
        # PRODUCTION solve (exact dispatches neither consult the
        # trigger nor, at run start, exist past step 9 — draining the
        # steps-0..9 exact-startup pipeline would serialize ~10
        # pointless exposed pulls for zero trigger evidence), at
        # least one pending verdict must be production (exact verdicts
        # cannot deliver the count that closes the window, _commit),
        # and the sim must actually CONSULT the trigger — under
        # CUP2D_POIS=fft (and the forest-FAS modes fas/fas-f, whose
        # hierarchy IS the solver) the correction is forced on
        # unconditionally (amr._use_coarse), so the pulled count
        # decides nothing and the drain would just re-tax every
        # post-regrid step.
        if self.lag and self._trigger_fresh \
                and hasattr(self.sim, "_coarse_on") \
                and not self.sim._coarse_on \
                and getattr(self.sim, "_pois_mode", None) not in (
                    "fft", "fas", "fas-f") \
                and not (self.sim.step_count < 10
                         or getattr(self.sim, "_force_exact", False)) \
                and any(not p.exact for p in self._pendings):
            while self._pendings:
                out = self._resolve_oldest()
        self._dispatch(dt)
        while self._pendings:
            if self.lag and len(self._pendings) == 1 \
                    and _on_device(self._pendings[-1].diag):
                break   # leave the newest device-diag step in flight
            out = self._resolve_oldest()
        return out

    def drain(self) -> list:
        """Resolve every pending verdict (call at loop exit and before
        dumps/checkpoints/regrids). Recovery runs as usual; returns the
        resolved records in step order."""
        out = []
        while self._pendings:
            out.append(self._resolve_oldest())
        return out

    def _seed(self) -> None:
        sim = self.sim
        if self.ring:
            if hasattr(sim, "forest") and \
                    self.ring[-1].meta.get("forest_version") \
                    != sim.forest.version:
                # topology moved (a regrid between guarded steps): the
                # ring must never span it — replay cannot reproduce a
                # regrid. Settle any in-flight verdicts against the old
                # anchor, then re-anchor on the new topology.
                self.drain()
                self._reanchor()
            return
        # run the lazy chi-blend initialization BEFORE seeding: a
        # snapshot of the pre-initialize state marks the sim
        # initialized on restore, so a rewind after a FIRST-step
        # failure would silently skip the blend and fork the
        # trajectory from t=0
        if getattr(sim, "shapes", None) \
                and not getattr(sim, "_initialized", False):
            sim.initialize()
        # seed: the pre-first-step state is by definition good
        self._reanchor()

    def _reanchor(self) -> None:
        self.ring.append(self._snapshot())
        self._replay.clear()
        self._since_snap = 0
        self._trigger_fresh = True

    def _trigger_state(self):
        """The two-level-trigger inputs the next dispatch consults —
        recorded per step so replay reproduces the SAME preconditioner
        branch the original trajectory took (replay steps never commit,
        so the trigger would otherwise stay frozen at the anchor's
        value)."""
        sim = self.sim
        if hasattr(sim, "_coarse_on"):
            return (bool(sim._coarse_on), int(sim._last_iters))
        return None

    def _dispatch(self, dt) -> None:
        sim = self.sim
        step0, t0 = sim.step_count, sim.time
        if tracing.recorder() is not None:
            # compile-ledger context (host strings, recorder-on only):
            # the trigger step and the dispatch-time latch token any
            # compile fired by this dispatch gets blamed on
            tracing.note_step(step0)
            mode = getattr(sim, "poisson_mode", None)
            tier = getattr(sim, "kernel_tier", None)
            if mode is not None or tier is not None:
                tracing.note_token("/".join(
                    str(x) for x in (mode, tier) if x is not None))
        trig = self._trigger_state()
        diag = self._attempt(dt, exact=False)
        pend = _Pending(
            step0=step0, t0=t0, diag=diag,
            exact=bool(step0 < 10 or getattr(sim, "_force_exact", False)),
            dt_host=(sim.time - t0 if sim.time != t0 else None),
            advanced=(sim.time != t0), trig=trig,
            fired=self._last_fired,
            mode=getattr(sim, "poisson_mode", None),
            tier=getattr(sim, "kernel_tier", None))
        # optimistic cadence snapshot: the post-step state must be
        # copied BEFORE the next dispatch donates its buffers; if this
        # step's lagged verdict comes back bad, the copy is discarded
        # and the rewind target is the previous (confirmed) anchor
        self._since_snap += 1
        if self._since_snap >= self.snap_every:
            pend.snap = self._snapshot()
            self._since_snap = 0
        self._pendings.append(pend)
        # fault injection: mirror_corrupt@N flips bytes in EVERY held
        # mirror so the recovery-time checksum-reject path is drillable
        # regardless of which anchor the next loss lands on (suspended
        # during replay like every other token; keyed on the pre-step
        # count like apply_pre_step)
        if self.faults is not None \
                and getattr(self.faults, "mirror_corrupt", None) \
                and self.faults.mirror_corrupt_at(step0):
            from .io import corrupt_mirror
            for s in self._held_mirror_snaps():
                corrupt_mirror(s)

    def _resolve_oldest(self) -> dict:
        pend = self._pendings.pop(0)
        with tracing.span("verdict", step=int(pend.step0)) as sp:
            # the ONE batched pull (host-side already on the eager
            # paths) — where the diag is on device this span fences,
            # so its interval is fence-accurate by construction
            vals = _host_scalars(pend.diag, _PULL_KEYS)
            _waited_for(sp, vals, pend)
            v = self._verdict_from(vals, pend.step0)
        if v.ok:
            return self._commit(pend, vals)
        return self._recover(pend, vals, v)

    @staticmethod
    def _dt_of(pend: _Pending, vals: dict) -> float:
        # prefer the dt the driver actually used (stamped into the
        # diag on every path): reconstructing it from the time
        # difference rounds differently by an ulp, and the replay
        # record must be EXACT
        dtv = vals.get("dt")
        if dtv is not None:
            return float(dtv)
        return pend.dt_host if pend.dt_host is not None else float("nan")

    def _commit(self, pend: _Pending, vals: dict) -> dict:
        sim = self.sim
        dt_used = self._dt_of(pend, vals)
        if not pend.advanced:
            # async path: the driver left the clock to the verdict;
            # commits run in step order, so sim.time is settled through
            # the previous step here
            sim.time = sim.time + dt_used
            if hasattr(sim, "_last_iters") and not pend.exact \
                    and vals.get("poisson_iters") is not None:
                # the pulled count IS the drained trigger scalar. The
                # r4-documented one-step hysteresis lag is closed by
                # the freshness window in step(): while the trigger is
                # re-armed, the verdict resolves BEFORE the next
                # dispatch, so the upgrade lands one step earlier.
                # The first production count closes the window — the
                # trigger is sticky, later counts only re-confirm.
                sim._last_iters = int(vals["poisson_iters"])
                sim._last_iters_dev = None
                self._trigger_fresh = False
        if self.watchdog is not None:
            self.watchdog.observe(vals)
        if pend.snap is not None:
            # promote to confirmed anchor; its capture-time clock (and
            # on the async paths the trigger count) was lagged —
            # settle both now
            pend.snap.meta["time"] = sim.time
            if hasattr(sim, "_coarse_on"):
                pend.snap.meta["coarse_on"] = bool(sim._coarse_on)
                pend.snap.meta["last_iters"] = int(sim._last_iters)
            self.ring.append(pend.snap)
            self._replay.clear()
        else:
            self._replay.append((dt_used, pend.exact, pend.trig))
        if self.faults is not None:
            self.faults.fire_post_step(pend.step0 + 1)
        # host scalars replace any device originals: a downstream
        # metrics consumer must never pay a SECOND device_get
        rec = {**pend.diag, **vals, "step": pend.step0 + 1,
               "t": sim.time, "dt": dt_used}
        if pend.mode is not None:
            # dispatch-time solve-path label (see _Pending.mode): the
            # recorder prefers this over the live sim property, which
            # may already reflect a later dispatch's trigger flip
            rec["poisson_mode"] = pend.mode
        if pend.tier is not None:
            # dispatch-time kernel-tier label (BC-token-suffixed,
            # ISSUE 16), same lagged-commit rule
            rec["kernel_tier"] = pend.tier
        return rec

    def _verdict_from(self, vals: dict, step: int) -> StepVerdict:
        tol = float(getattr(self.sim.cfg, "poisson_tol", 0.0))
        v = health_verdict(vals,
                           residual_ok=(100.0 * tol if tol > 0 else None))
        if v.ok and self.watchdog is not None:
            reason = self.watchdog.check(vals)
            if reason is not None:
                v = StepVerdict(False, reason)
        if v.ok and self.faults is not None \
                and self.faults.poisson_giveup_at(step):
            v = StepVerdict(False, "poisson_giveup(injected)")
        return v

    def _discard_pendings(self) -> None:
        """Drop every in-flight dispatch (and its optimistic snapshot)
        and REFUND the fault counts each one consumed, so an injection
        armed for a discarded step still fires at its real re-dispatch.
        Shared by the ladder (garbage dispatched on top of a bad step)
        and the elastic path (dispatches issued against a lost
        topology) — one refund rule, one place."""
        for p in self._pendings:
            for ent in p.fired:
                ent[1] += 1
        self._pendings.clear()

    # -- the recovery ladder ------------------------------------------
    def _recover(self, pend: _Pending, vals: dict,
                 v: StepVerdict) -> dict:
        sim = self.sim
        # any step dispatched on top of the bad one is garbage (the bad
        # step's own fault genuinely fired and is not refunded)
        self._discard_pendings()
        step0 = pend.step0
        dt_used = self._dt_of(pend, vals)
        rung = 0
        retry_dt: Optional[float] = None
        with tracing.span("recover", step=int(step0), verdict=v.reason):
            while True:
                action = self._next_action(rung)
                # one span per ladder rung, named by its action — an
                # aborting rung keeps its interval (error-marked), so
                # the timeline shows where the ladder died
                with tracing.span(action, step=int(step0), rung=rung):
                    if action == "abort":
                        self._abort(step0, v, vals, dt_used)
                    replayed = 0
                    if action in ("retry", "escalate"):
                        replayed = self._rewind_replay()
                        if pend.trig is not None:
                            # the retry consults the trigger with the
                            # same inputs the failed step's dispatch saw
                            self.sim._coarse_on, self.sim._last_iters \
                                = pend.trig
                            self.sim._last_iters_dev = None
                        if action == "retry":
                            # half the failed dt; a nonfinite dt (fault
                            # at a cold-cache step) falls back to a
                            # fresh CFL dt from the restored clean state
                            retry_dt = (0.5 * dt_used
                                        if np.isfinite(dt_used)
                                        and dt_used > 0 else None)
                    else:  # disk_restore: rewind possibly many steps
                        from .io import load_checkpoint
                        load_checkpoint(self.ckpt_dir, sim)
                        self.ring.clear()
                        self._reanchor()
                        if self.watchdog is not None:
                            # the window now describes steps FORWARD of
                            # the restored point — stale as a baseline
                            self.watchdog.reset()
                        retry_dt = None
                    self._emit(step=step0, verdict=v.reason,
                               action=action, dt=dt_used, rung=rung,
                               replayed=replayed)
                    self.recoveries += 1
                    # the retry itself verdicts SYNCHRONOUSLY —
                    # recovery is the cold path, the lag exists for
                    # the steady state
                    t0, s0 = sim.time, sim.step_count
                    exact_retry = action == "escalate"
                    trig = self._trigger_state()
                    diag = self._attempt(retry_dt, exact=exact_retry)
                    advanced = sim.time != t0
                    vals = _host_scalars(diag, _PULL_KEYS)
                    v2 = self._verdict_from(vals, s0)
                    p2 = _Pending(
                        step0=s0, t0=t0, diag=diag,
                        exact=bool(s0 < 10 or exact_retry),
                        dt_host=(sim.time - t0 if advanced else None),
                        advanced=advanced, trig=trig)
                    if v2.ok:
                        # recovered: take a FRESH anchor
                        # unconditionally (the replay list must
                        # restart from a clean base)
                        p2.snap = self._snapshot()
                        self._since_snap = 0
                        return self._commit(p2, vals)
                    v = v2
                    dt_used = self._dt_of(p2, vals)
                    rung += 1

    def _rewind_replay(self) -> int:
        """Restore the latest anchor, then replay the recorded good
        steps bit-exactly (same dts, same exact-solve and trigger
        branches, faults suspended, no verdict pulls) up to the failed
        step."""
        from .io import restore_snapshot_device
        restore_snapshot_device(self.sim, self.ring[-1])
        return self._replay_recorded()

    def _replay_recorded(self) -> int:
        """Replay the recorded good steps since the anchor (the loop
        half of :meth:`_rewind_replay`; the elastic path calls it after
        its own re-sharding restore — there the replay runs on the NEW
        mesh, so it reproduces the committed steps to the sharded-
        equality bound rather than bit-exactly)."""
        import contextlib
        sim = self.sim
        n = len(self._replay)
        if not n:
            return 0
        ctx = (self.faults.suspend() if self.faults is not None
               else contextlib.nullcontext())
        # replayed steps were already force-logged when they first ran
        # good — re-logging them would append duplicate rows with
        # rewound times to the force CSV
        cfe = getattr(sim, "compute_forces_every", None)
        if cfe is not None:
            sim.compute_forces_every = 0
        try:
            with ctx:
                for rdt, rexact, rtrig in self._replay:
                    t0 = sim.time
                    if rtrig is not None:
                        # the trigger inputs as-of this step's ORIGINAL
                        # dispatch: replay must take the same
                        # preconditioner branch
                        sim._coarse_on, sim._last_iters = rtrig
                        sim._last_iters_dev = None
                    # a start-up step is exact by its own count:
                    # forcing it too would name the ladder's Krylov
                    # backstop where the direct solve ran
                    # (UniformGrid.exact_request) and break the
                    # bit-exact replay
                    forced = rexact and sim.step_count >= 10
                    if forced:
                        sim._force_exact = True
                    try:
                        sim.step_once(dt=rdt)
                    finally:
                        if forced:
                            sim._force_exact = False
                    if sim.time == t0:
                        # async driver: settle the clock from the
                        # recorded dt (the same float the original
                        # commit pulled)
                        sim.time = t0 + rdt
        finally:
            if cfe is not None:
                sim.compute_forces_every = cfe
        self.replayed_steps += n
        return n

    def _attempt(self, dt, exact: bool = False) -> dict:
        sim = self.sim
        self._last_fired = (self.faults.apply_pre_step(sim)
                            if self.faults is not None else ())
        if exact:
            sim._force_exact = True
        # enqueue-side span: on the async paths the dispatch returns
        # with the diag still in flight — this times the enqueue, the
        # verdict span times the fence (the pipeline it must not stall)
        with tracing.span("dispatch", step=int(sim.step_count)):
            try:
                return sim.step_once(dt=dt)
            finally:
                if exact:
                    sim._force_exact = False

    def _next_action(self, rung: int) -> str:
        if not self.recover:
            return "abort"
        if rung == 0:
            return "retry"
        if rung == 1:
            return "escalate"
        if rung == 2 and self._disk_available():
            return "disk_restore"
        return "abort"

    def _emit(self, event: str = "recovery", **fields) -> None:
        if self.event_log is not None:
            self.event_log.emit(event=event,
                                sim_time=float(self.sim.time), **fields)

    def _abort(self, step: int, v: StepVerdict, vals: dict,
               dt_used: float) -> None:
        """The last rung: post-mortem checkpoint + diagnostic dump of
        the dead state, force log closed, one final event — then raise.
        A dead run must always leave enough on disk to be diagnosed and
        (where the fault was environmental) resumed."""
        sim = self.sim
        pm = None
        if self.postmortem_dir:
            try:
                from .io import save_checkpoint
                save_checkpoint(self.postmortem_dir, sim)
                pm = self.postmortem_dir
            except Exception as e:   # the abort must not be masked
                print(f"cup2d_tpu: post-mortem checkpoint failed: {e}",
                      file=sys.stderr)
        flog = getattr(sim, "force_log", None)
        if flog is not None and not flog.closed:
            flog.close()
        summary = {k: _as_float(vals[k])
                   for k in ("umax", "poisson_residual", "poisson_iters")
                   if k in vals}
        self._emit(step=step, verdict=v.reason, action="abort",
                   dt=dt_used, postmortem=pm, diag=summary)
        raise ResilienceAbort(
            f"step {step}: {v.reason}; recovery ladder exhausted"
            + (f" (post-mortem checkpoint: {pm})" if pm else ""))

    # -- elastic topology recovery (PR 7) ------------------------------
    def elastic_recover(self, topo: "TopologyGuard") -> None:
        """Re-mesh the survivors and resume in place after ``topo``
        declared a topology loss — no process relaunch.

        Sequence (every stage one JSONL event):

        1. every in-flight dispatch is garbage — it was issued against
           the LOST topology (on a real pod its collectives would hang;
           even verdicted-good pendings are dropped so the resume point
           is a CONFIRMED anchor) — discard + refund its fault counts,
           exactly like the ladder's discard;
        2. survivors (deterministic on every process — same evidence,
           same rule, see TopologyGuard) become a fresh 1-D mesh and
           ``sim.remesh`` rebuilds placement/tables/step executables
           over it (the SFC block partition is device-count-parametric,
           so the forest re-partitions by construction);
        3. state, down a four-rung ladder:

           - **ring** — the latest anchor whose OWN shards still cover
             the survivor set (``io.snapshot_covers`` with the mirror
             tier masked off; a shard_loss drill voids this rung by
             construction — the owner bytes are destroyed) —
             re-sharded onto the new mesh by
             ``io.restore_snapshot_resharded``, then the recorded
             steps since the anchor replayed on the new mesh;
           - **mirror** — the anchor carries a host-redundant mirror
             and every lost host's ring neighbor survived
             (mirror-aware ``snapshot_covers``): the neighbor-held
             blocks are checksum-verified (``io.verify_mirror``; a
             torn/corrupt mirror emits one ``mirror_reject`` event and
             falls through rather than installing bad bytes),
             realigned over the lost columns
             (``io.restore_snapshot_mirrored``), and replayed exactly
             like the ring rung — same trajectory, in-HBM resume;
           - **disk** — the last checkpoint, watchdog baseline reset;
           - **abort** — standard post-mortem machinery.

        The ring is re-anchored on the new topology afterwards (old
        entries carry lost-mesh placement and must never be restored),
        and the mirror tier is resized to the surviving host count
        (disabled when fewer than two hosts remain — no neighbor left
        to hold a mirror).
        """
        with tracing.span("remesh", step=int(self.sim.step_count),
                          epoch=int(topo.epoch)):
            return self._elastic_recover(topo)

    def _elastic_recover(self, topo: "TopologyGuard") -> None:
        import time as _time

        sim = self.sim
        t0 = _time.perf_counter()
        import jax
        if jax.default_backend() == "cpu":
            # recovery fence, CPU ONLY: dispatched-but-unverdicted
            # steps may still be executing, and their halo collectives
            # share devices with the recovery launches (verify sums,
            # mirror realign). The CPU client honors no cross-launch
            # device order, so racing them can deadlock at rendezvous
            # (io.mirror_snapshot documents the capture-side twin).
            # Settle everything in flight before the first recovery
            # launch; TPU's enqueue-ordered streams don't need this.
            for a in jax.tree_util.tree_leaves(
                    [(p.snap, p.diag) for p in self._pendings]
                    + [getattr(sim, "state", None)]):
                if hasattr(a, "block_until_ready"):
                    a.block_until_ready()
        # stage 1: discard + refund (the ladder's garbage-dispatch rule)
        self._discard_pendings()
        survivors = topo.survivor_devices()
        from .io import load_checkpoint, restore_snapshot_resharded, \
            restore_snapshot_mirrored, snapshot_covers, verify_mirror, \
            destroy_shards
        # real-loss honesty (shard_loss drill): zero the destroyed
        # hosts' shard slices — live state, every held snapshot payload
        # AND the physical mirror slices they held — BEFORE choosing a
        # rung, so a successful resume provably sourced the survivors'
        # mirror copies, not the "lost" originals
        destroyed = tuple(topo.destroyed_hosts())
        lost_hosts = tuple(topo.lost_host_indices())
        if destroyed:
            wiped = destroy_shards(sim, list(self.ring), destroyed,
                                   topo.n_hosts)
            self.ring.clear()
            self.ring.extend(wiped)
        anchor = self.ring[-1] if self.ring else None
        lost_p = topo.lost_process_indices()
        use_ring = anchor is not None and not destroyed \
            and snapshot_covers(anchor, lost_p, mirror=False)
        use_mirror = False
        if not use_ring and anchor is not None and snapshot_covers(
                anchor, lost_p, lost_hosts=lost_hosts,
                shards_destroyed=bool(destroyed)):
            dead = tuple(sorted(set(lost_hosts) | set(lost_p)))
            bad = verify_mirror(anchor, dead)
            if bad:
                # torn/corrupt mirror: never install it — reject loudly
                # (durable event) and fall through to disk
                if self.event_log is not None:
                    self.event_log.emit(
                        event="mirror_reject", step=int(sim.step_count),
                        n_rejects=len(bad), rejects=bad[:8])
            else:
                use_mirror = True
        if not use_ring and not use_mirror and not self._disk_available():
            v = StepVerdict(False, "topology_lost")
            self._abort(sim.step_count, v,
                        {}, float("nan"))
        if not survivors:
            raise ResilienceAbort("topology loss left no survivor "
                                  "devices — nothing to re-mesh onto")
        # stage 2: re-mesh (lazy import: resilience must not drag the
        # sharded stack into single-device library users)
        from .parallel.mesh import make_mesh
        mesh = make_mesh(devices=survivors)
        sim.remesh(mesh)
        # stage 3: resume
        replayed = 0
        if use_ring:
            restore_snapshot_resharded(sim, anchor)
            replayed = self._replay_recorded()
            source = "ring"
        elif use_mirror:
            dead = tuple(sorted(set(lost_hosts) | set(lost_p)))
            restore_snapshot_mirrored(sim, anchor, dead)
            replayed = self._replay_recorded()
            source = "mirror"
        else:
            load_checkpoint(self.ckpt_dir, sim)
            if self.watchdog is not None:
                # the window describes steps forward of the restored
                # point — stale as a baseline (same rule as the ladder's
                # disk rung; the ring/mirror paths resume the SAME
                # trajectory, so its window stays valid)
                self.watchdog.reset()
            source = "disk"
        self.restore_source = source
        # resize the mirror tier to the surviving hosts: below two
        # there is no neighbor left to hold a mirror
        if self.mirror_hosts is not None:
            alive = topo.alive_host_count()
            self.mirror_hosts = alive if alive >= 2 else None
        self.ring.clear()
        self._reanchor()
        self.topology_epoch = int(topo.epoch)
        self.remesh_count += 1
        ms = 1e3 * (_time.perf_counter() - t0)
        self.remesh_ms_total += ms
        self.recoveries += 1
        if self.event_log is not None:
            self.event_log.emit(
                event="remesh", epoch=int(topo.epoch), source=source,
                devices=len(survivors), step=int(sim.step_count),
                sim_time=float(sim.time), replayed=replayed,
                ms=round(ms, 3))


# ---------------------------------------------------------------------------
# per-member supervision for the fleet-batched driver (fleet.py)
# ---------------------------------------------------------------------------

class FleetStepGuard(StepGuard):
    """Vectorized verdicts + per-member recovery for ``FleetSim``.

    The fused fleet dispatch is the hot path: ONE batched pull carries
    [B] diag vectors, every member is classified independently (the
    same ``health_verdict`` policy per member, plus an independent
    :class:`PhysicsWatchdog` clone per member — pass one prototype via
    ``watchdog=`` and it is deep-copied B times). Recovery is the cold
    path and PER MEMBER:

    - a bad member restores ONLY its slice of the latest device
      snapshot (``FleetSim.set_member_state`` — every other member's
      values pass through bit-unchanged), replays its recorded
      per-member dts solo through ``member_step_once`` (faults
      suspended, exact-solve branches reproduced), then retries the
      failed step at dt/2 and, on a second failure, with the exact
      Poisson solve;
    - HEALTHY MEMBERS NEVER REWIND: their step-N states from the fused
      dispatch commit as usual, bit-identical to an unfaulted run
      (tests/test_fleet.py pins this with a per-member NaN drill);
    - the per-member ladder has NO disk rung — a disk restore would
      rewind every member (healthy trajectories included), so it goes
      retry -> escalate -> abort, and whole-fleet disk restore remains
      the operator-level restart path.

    Solo replay note: the solo executable deviates from the fused
    member slice by the documented ~1e-16..1e-13 MG FMA-contraction
    noise (fleet.py module docstring), so a replayed member is
    equal-to-solo, not bit-equal-to-fused; the default ``snap_every=1``
    keeps fleet replays at zero steps unless a cadence is requested.

    The fleet verdict is EAGER (``lag`` is forced off): under the
    one-step-lagged verdict a dispatch stacked on an undetected-bad
    step N is discarded wholesale — but a FLEET dispatch of step N+1
    is garbage only in the bad member's slice and a perfectly good
    step N+1 for the other B-1 members, so discarding it would either
    rewind healthy members (recomputing their trajectories — exactly
    what per-member recovery forbids) or fork a per-member step-count
    catch-up. Verdicting eagerly costs NO extra pull: it is the same
    ONE batched device_get per step the sync fleet driver already
    pays for the whole fleet — the fleet's throughput lever is
    dispatch amortization across members, which is orthogonal to the
    lag (a latency lever for the single-case drivers).

    Injected ``poisson_giveup`` faults flag member 0 (the same member
    ``faults.poison_velocity``/``scale_velocity`` target on a fleet).

    Serving mode (``on_member_abort=``, wired by ``fleet.FleetServer``):
    the exhausted ladder EVICTS the one bad member — ``member_aborted``
    event, callback frees the slot, the fleet lives on — instead of
    raising :class:`ResilienceAbort`. Slots masked inactive by the
    server are skipped by the per-member verdicts and watchdogs (their
    lanes are select-frozen identity; classifying a parked slot's
    stale diag would evict ghosts).
    """

    def __init__(self, sim, *, watchdog=None, on_member_abort=None,
                 **kw):
        kw["lag"] = False     # eager by design — see the docstring
        super().__init__(sim, watchdog=None, **kw)
        import copy
        self._watchdog_proto = watchdog
        self.member_watchdogs = (
            [copy.deepcopy(watchdog) for _ in range(sim.members)]
            if watchdog is not None else None)
        self.on_member_abort = on_member_abort
        self.evictions = 0

    def _member_active(self, m: int) -> bool:
        act = getattr(self.sim, "active_mask", None)
        return True if act is None else bool(act[m])

    def reset_member_watchdog(self, m: int) -> None:
        """Fresh watchdog clone for slot ``m`` (server admission: the
        slot's history belongs to the previous occupant)."""
        if self.member_watchdogs is not None:
            import copy
            self.member_watchdogs[m] = copy.deepcopy(self._watchdog_proto)

    def reanchor(self) -> None:
        """Fresh snapshot anchor + clean replay base. The server calls
        this after an admission batch so a later per-member rewind can
        never restore PRE-admit slot contents (the eager fleet verdict
        guarantees no dispatch is in flight between steps)."""
        self.ring.append(self._snapshot())
        self._replay.clear()
        self._since_snap = 0

    # -- vectorized verdict -------------------------------------------
    def _resolve_oldest(self) -> dict:
        pend = self._pendings.pop(0)
        with tracing.span("verdict", step=int(pend.step0)) as sp:
            vals = _host_scalars(pend.diag, _PULL_KEYS)   # [B] vectors
            _waited_for(sp, vals, pend)
            verdicts = self._member_verdicts(vals, pend.step0)
            bad = [m for m, v in enumerate(verdicts) if not v.ok]
        if not bad:
            return self._commit(pend, vals)
        return self._recover_members(pend, vals, verdicts, bad)

    def _one_member_verdict(self, m: int, mv: dict,
                            step: int) -> StepVerdict:
        """THE per-member verdict policy — shared by the fused-dispatch
        classification and the solo retry, so a policy change can never
        drift between them: health -> per-member watchdog -> member-0
        giveup injection."""
        tol = float(getattr(self.sim.cfg, "poisson_tol", 0.0))
        v = health_verdict(mv,
                           residual_ok=(100.0 * tol if tol > 0 else None))
        if v.ok and self.member_watchdogs is not None:
            reason = self.member_watchdogs[m].check(mv)
            if reason is not None:
                v = StepVerdict(False, reason)
        if v.ok and m == 0 and self.faults is not None \
                and self.faults.poisson_giveup_at(step):
            v = StepVerdict(False, "poisson_giveup(injected)")
        return v

    def _member_verdicts(self, vals: dict, step: int) -> list:
        return [
            self._one_member_verdict(
                m, {k: v[m] for k, v in vals.items() if np.ndim(v) >= 1},
                step)
            if self._member_active(m)
            # parked slot: its lane is select-frozen identity — always
            # healthy by construction, never classified
            else StepVerdict(True, "inactive")
            for m in range(self.sim.members)]

    def _commit(self, pend: _Pending, vals: dict) -> dict:
        sim = self.sim
        dts = np.asarray(vals["dt"], np.float64)
        if not pend.advanced:
            # async path: settle every member's clock from the pulled
            # per-member dt vector (commits run in step order; a dead
            # slot's pulled dt is exactly 0.0 — its clock freezes)
            sim.times = sim.times + dts
            sim.time = sim._fleet_time()
        if self.member_watchdogs is not None:
            for m in range(sim.members):
                if self._member_active(m):
                    self.member_watchdogs[m].observe(
                        {k: v[m] for k, v in vals.items()})
        if pend.snap is not None:
            # capture-time clocks were lagged — settle them now
            pend.snap.meta["time"] = sim.time
            pend.snap.meta["times"] = np.array(sim.times)
            self.ring.append(pend.snap)
            self._replay.clear()
        else:
            self._replay.append((dts, pend.exact, None))
        if self.faults is not None:
            self.faults.fire_post_step(pend.step0 + 1)
        rec = {**pend.diag, **vals, "step": pend.step0 + 1,
               "t": sim.time, "dt": dts}
        if pend.mode is not None:
            rec["poisson_mode"] = pend.mode   # dispatch-time label
        if pend.tier is not None:
            rec["kernel_tier"] = pend.tier    # dispatch-time label
        return rec

    # -- per-member recovery ------------------------------------------
    def _recover_members(self, pend: _Pending, vals: dict,
                         verdicts: list, bad: list) -> dict:
        sim = self.sim
        # discard (and refund) any dispatch stacked on the bad step
        self._discard_pendings()
        # the optimistic post-step snapshot contains the bad slices —
        # it must never become an anchor
        pend.snap = None
        vals = {k: np.array(v) for k, v in vals.items()}   # writable
        dts = np.asarray(vals["dt"], np.float64)
        if not pend.advanced:
            # commit the HEALTHY members' step N (their fused results
            # are good; they never rewind)
            for m in range(sim.members):
                if verdicts[m].ok:
                    sim.times[m] += dts[m]
        # the dt cache may hold a discarded garbage dispatch's dt_next
        # (lagged mode dispatched N+1 on top of the bad N): re-anchor
        # EVERY member on step N's pulled dt_next — the same floats the
        # unfaulted run keeps on device, so healthy trajectories stay
        # bit-identical
        import jax.numpy as jnp
        sim._next_dt = jnp.asarray(np.asarray(vals["dt_next"]),
                                   sim.grid.dtype)
        anchor = self.ring[-1]
        for m in bad:
            mv = self._recover_member(m, anchor, pend.step0, vals,
                                      verdicts[m])
            # the record reflects what actually committed for m
            for k, val in mv.items():
                if k in vals and np.ndim(vals[k]) >= 1:
                    vals[k][m] = val
        if self.member_watchdogs is not None:
            for m in range(sim.members):
                if verdicts[m].ok and self._member_active(m):
                    self.member_watchdogs[m].observe(
                        {k: v[m] for k, v in vals.items()})
        sim.time = sim._fleet_time()
        # every member healthy again: fresh anchor, clean replay base
        self.ring.append(self._snapshot())
        self._replay.clear()
        self._since_snap = 0
        if self.faults is not None:
            self.faults.fire_post_step(pend.step0 + 1)
        return {**pend.diag, **vals, "step": pend.step0 + 1,
                "t": sim.time, "dt": np.asarray(vals["dt"])}

    def _recover_member(self, m: int, anchor, step0: int, vals: dict,
                        v: StepVerdict) -> dict:
        sim = self.sim
        dt_used = float(np.asarray(vals["dt"])[m])
        rung = 0
        with tracing.span("recover", step=int(step0), member=m,
                          verdict=v.reason):
            while True:
                if not self.recover or rung >= 2:
                    # serving mode nests the server's client-attributed
                    # "evict" span here (the on_member_abort callback)
                    self._abort_member(m, step0, v, vals, dt_used)
                    # eviction (serving mode): the slot is free, the
                    # fleet lives on — patch the record with an inert
                    # lane so the fold aggregates don't carry the dead
                    # member's NaNs
                    return {"dt": 0.0, "dt_next": 1.0, "finite": True,
                            "umax": 0.0, "energy": 0.0,
                            "div_linf": 0.0, "poisson_iters": 0,
                            "poisson_residual": 0.0,
                            "poisson_stalled": False,
                            "poisson_converged": True,
                            "precond_cycles": 0}
                action = "retry" if rung == 0 else "escalate"
                with tracing.span(action, step=int(step0), member=m,
                                  rung=rung):
                    replayed = self._rewind_member(m, anchor)
                    exact = rung == 1
                    retry_dt = (0.5 * dt_used
                                if rung == 0 and np.isfinite(dt_used)
                                and dt_used > 0 else None)
                    self._emit(step=step0, member=m, verdict=v.reason,
                               action=action, dt=dt_used, rung=rung,
                               replayed=replayed)
                    self.recoveries += 1
                    # the retry is a FRESH attempt of step0: armed *K
                    # faults re-fire (looked up by the step being
                    # retried — the SHARED fleet counter already
                    # advanced past it)
                    self._last_fired = (
                        self.faults.apply_pre_step(sim, step=step0)
                        if self.faults is not None else ())
                    diag = sim.member_step_once(
                        m, dt=retry_dt,
                        exact=sim.grid.exact_request(step0 < 10, exact))
                    mv = _host_scalars(diag, _PULL_KEYS)
                    v2 = self._one_member_verdict(m, mv, step0)
                    if v2.ok:
                        sim.times[m] += float(mv["dt"])
                        sim.time = float(sim.times.min())
                        sim.set_member_next_dt(m, mv["dt_next"])
                        if self.member_watchdogs is not None:
                            self.member_watchdogs[m].observe(mv)
                        return mv
                    v = v2
                    dt_used = float(mv["dt"])
                    rung += 1

    def _rewind_member(self, m: int, anchor) -> int:
        """Restore member ``m``'s slice from the anchor snapshot, then
        replay its recorded per-member dts solo (faults suspended, no
        verdict pulls) up to the failed step."""
        import contextlib
        sim = self.sim
        sim.set_member_state(m, type(sim.state)(
            *(anchor.payload[k][m] for k in sim.state._fields)))
        sim.times[m] = float(np.asarray(anchor.meta["times"])[m])
        n = 0
        ctx = (self.faults.suspend() if self.faults is not None
               else contextlib.nullcontext())
        with ctx:
            for rdts, rexact, _ in self._replay:
                rdt = float(np.asarray(rdts)[m])
                if rdt == 0.0:
                    # the member sat parked (masked dead) for this
                    # recorded step: its lane was frozen identity, so
                    # replay is a no-op for it
                    continue
                sim.member_step_once(m, dt=rdt, exact=rexact)
                sim.times[m] += rdt
                n += 1
        self.replayed_steps += n
        return n

    def _abort_member(self, m: int, step: int, v: StepVerdict,
                      vals: dict, dt_used: float) -> None:
        sim = self.sim
        summary = {k: _as_float(np.asarray(vals[k])[m])
                   for k in ("umax", "poisson_residual", "poisson_iters")
                   if k in vals}
        if self.on_member_abort is not None:
            # serving mode: EVICT the one bad member. The callback
            # (FleetServer._on_member_abort) zeroes the slot and masks
            # it dead; scrubbing the dt cache keeps the evicted lane's
            # NaN out of the next dispatch's operands (the masked step
            # would sanitize it anyway — this keeps the cache clean for
            # the host side too). Healthy members never rewound, and
            # _recover_members re-anchors on the post-eviction state.
            self._emit(event="member_aborted", step=step, member=m,
                       verdict=v.reason, action="evict", dt=dt_used,
                       diag=summary)
            self.evictions += 1
            self.on_member_abort(m, v.reason, step)
            sim.set_member_next_dt(m, 1.0)
            return
        pm = None
        if self.postmortem_dir:
            try:
                from .io import save_checkpoint
                save_checkpoint(self.postmortem_dir, sim)
                pm = self.postmortem_dir
            except Exception as e:   # the abort must not be masked
                print(f"cup2d_tpu: post-mortem checkpoint failed: {e}",
                      file=sys.stderr)
        flog = getattr(sim, "force_log", None)
        if flog is not None and not flog.closed:
            flog.close()
        self._emit(step=step, member=m, verdict=v.reason,
                   action="abort", dt=dt_used, postmortem=pm,
                   diag=summary)
        raise ResilienceAbort(
            f"step {step}, member {m}: {v.reason}; per-member ladder "
            "exhausted"
            + (f" (post-mortem checkpoint: {pm})" if pm else ""))


def _on_device(diag: dict) -> bool:
    import jax
    return any(isinstance(v, jax.Array) for v in diag.values())


def _as_float(x) -> float:
    try:
        return float(np.asarray(x))
    except Exception:
        return float("nan")


# ---------------------------------------------------------------------------
# preemption-safe shutdown
# ---------------------------------------------------------------------------

class PreemptionGuard:
    """Latches SIGTERM (and optionally other signals) into a flag the
    driver loop polls at step boundaries. Installing mid-collective-safe
    shutdown any other way is not possible: the handler must not touch
    device state, so it only sets the flag."""

    def __init__(self):
        self.triggered = False
        self.signum: Optional[int] = None
        self._prev: dict = {}

    def install(self, signums=None) -> "PreemptionGuard":
        import signal
        if signums is None:
            signums = (signal.SIGTERM,)

        def _handler(signum, frame):
            self.triggered = True
            self.signum = signum

        for s in signums:
            self._prev[s] = signal.signal(s, _handler)
        return self

    def agree(self) -> bool:
        """Cross-process agreement on the latch (the former ROADMAP pod
        gap (a)): hosts preempted at different instants must not enter
        MISMATCHED collectives — one stepping while another starts the
        collective checkpoint save hangs the SPMD program out its grace
        window. The flag itself stays per-process (a signal handler
        cannot run collectives); the DECISION is made here: at every
        step boundary each process contributes its local flag to a tiny
        min-allreduce (an allgather of one int32 — the cheap dedicated
        collective; on pods it rides DCN in microseconds against a
        multi-ms step), and the checkpoint fires only once EVERY
        process has latched — so all hosts enter the collective save at
        the SAME step boundary. A lone signal on one host keeps the run
        alive by design: real preemption notifies every worker, and
        stopping on ANY flag would turn a stray operator signal into a
        fleet-wide shutdown. Call it at the same loop point on every
        process — it is a collective on pods. Single-host (or before
        distributed init): just the local flag, no device/collective
        cost. Drilled with skewed sigterm@N delivery by the multihost
        harness (tests/_multihost_worker.py).

        Pre-init / single-process FAST PATH: before the distributed
        runtime is up (or when it was never brought up) this is just
        the local flag — no collective, no device touch, no backend
        probe (the version-safe :func:`dist_initialized` check). Unit-
        tested in tests/test_elastic.py."""
        import jax
        if not dist_initialized() or jax.process_count() == 1:
            return self.triggered
        from jax.experimental import multihost_utils
        flags = multihost_utils.process_allgather(
            np.asarray([1 if self.triggered else 0], np.int32))
        return bool(np.min(flags) > 0)

    def uninstall(self) -> None:
        import signal
        for s, h in self._prev.items():
            signal.signal(s, h)
        self._prev.clear()


# ---------------------------------------------------------------------------
# elastic topology detection + agreement (PR 7)
# ---------------------------------------------------------------------------

def bounded_call(fn, timeout: float):
    """Run ``fn()`` with a deadline: returns ``(True, result)`` when it
    completes within ``timeout`` seconds, ``(False, None)`` when it is
    still blocked at the deadline — the hang watchdog for collectives
    (a peer that died mid-step leaves the survivors' next allgather
    blocked forever; this turns the infinite hang into evidence). The
    worker thread is a daemon: a genuinely hung collective cannot be
    cancelled, only observed — its thread is abandoned with the dying
    world. An exception inside ``fn`` is re-raised here."""
    import threading
    box: dict = {}

    def _run():
        try:
            box["result"] = fn()
        except BaseException as e:   # surfaced to the caller below
            box["error"] = e

    th = threading.Thread(target=_run, daemon=True)
    th.start()
    th.join(timeout)
    if th.is_alive():
        return False, None
    if "error" in box:
        raise box["error"]
    return True, box.get("result")


class Beat(NamedTuple):
    """One step-boundary heartbeat result (TopologyGuard.step_boundary)."""

    stop: bool          # SIGTERM agreement (PreemptionGuard semantics)
    lost: tuple         # hosts DECLARED lost at this beat (may be empty)
    self_lost: bool     # real mode: THIS process was told to die
    hung: bool          # the bounded collective missed its deadline


class TopologyGuard:
    """Detection + agreement half of the elastic recovery subsystem.

    Two modes share one protocol:

    - **Simulated** (``sim_hosts=H``, single process): the device list
      is grouped into H contiguous "hosts" (the same contiguous-range
      layout a real pod has — parallel/launch.global_mesh). Losses are
      injected by ``faults.py`` ``host_exit@N`` / ``host_hang@N``
      directives: the directive marks the highest-index alive host
      dead at step N's boundary, and each subsequent :meth:`poll` is
      one missed beat — after ``miss_k`` consecutive misses the host
      is DECLARED lost and the epoch bumps. This is the tier-1 drill
      mode: the virtual devices all remain addressable, so the
      snapshot-ring resume path runs end-to-end in one process.
    - **Real** (multi-process): the heartbeat piggybacks on the
      step-boundary collective :meth:`PreemptionGuard.agree` already
      pays — ONE allgather of ``[sigterm, epoch, exiting]`` int32s per
      process, run under ``timeout`` via :func:`bounded_call`. A
      graceful loss (``host_exit@N`` on that process) announces itself
      in its final beat (``exiting=1``), so every survivor sees the
      same evidence vector and computes the same survivor set + epoch
      — agreement by construction, no extra round. A hard loss
      (``host_hang@N``, a kill) surfaces as the next beat's deadline
      miss: the world's collectives are unusable from that instant, so
      in-place recovery additionally needs a runtime re-init
      (``parallel.launch.reinit_distributed``) before any further
      collective — the slow-marked 2-process drill's path.

    The DECISION rule is deterministic on identical evidence: survivors
    = alive hosts in original order, epoch += 1 per declaration batch.
    Every declaration emits one ``topology_lost`` JSONL event.
    """

    def __init__(self, devices=None, *, sim_hosts: Optional[int] = None,
                 miss_k: int = 3, timeout: float = 10.0,
                 faults=None, event_log=None):
        import jax
        self.devices = list(devices) if devices is not None \
            else list(jax.devices())
        self.miss_k = max(1, int(miss_k))
        self.timeout = float(timeout)
        self.faults = faults
        self.event_log = event_log
        self.epoch = 0
        self.hung = False
        self._exiting = False
        self._lost_processes: set = set()
        if sim_hosts is not None:
            h = int(sim_hosts)
            if h < 2 or len(self.devices) % h:
                raise ValueError(
                    f"sim_hosts={h}: need >= 2 simulated hosts (losing "
                    "the only host leaves nothing to re-mesh onto) "
                    f"dividing the {len(self.devices)}-device set into "
                    "equal contiguous groups")
            self.sim_hosts = h
        else:
            self.sim_hosts = None
        n = self.n_hosts
        self.alive = [True] * n
        self._dead: dict = {}      # host -> fault kind (not yet declared)
        self._missed: dict = {}    # host -> consecutive missed beats
        # hosts whose shard slices died WITH them (shard_loss@N paired
        # with the loss token — the simulated real-loss semantics; real
        # process losses carry this implicitly via lost_process_indices)
        self._destroyed: set = set()

    # -- topology bookkeeping -----------------------------------------
    @property
    def n_hosts(self) -> int:
        if self.sim_hosts is not None:
            return self.sim_hosts
        import jax
        return jax.process_count() if dist_initialized() else 1

    def _host_of(self, idx: int) -> int:
        """Host owning device index ``idx`` (contiguous groups)."""
        if self.sim_hosts is not None:
            return idx * self.sim_hosts // len(self.devices)
        return int(getattr(self.devices[idx], "process_index", 0))

    def survivor_devices(self) -> list:
        """Devices of the alive hosts, in original (SFC-contiguous)
        order — identical on every survivor by the determinism rule."""
        return [d for i, d in enumerate(self.devices)
                if self.alive[self._host_of(i)]]

    def lost_process_indices(self) -> tuple:
        """Process indices declared lost (REAL mode; empty for
        simulated hosts — the single process survives them all), for
        ``io.snapshot_covers``."""
        return tuple(sorted(self._lost_processes))

    def lost_host_indices(self) -> tuple:
        """Ring indices of every declared-lost host, BOTH modes (the
        mirror-coverage input: simulated hosts and real processes ride
        the same contiguous-block ring)."""
        return tuple(h for h in range(len(self.alive))
                     if not self.alive[h])

    def destroyed_hosts(self) -> tuple:
        """Declared-lost hosts whose shard slices died with them
        (``shard_loss@N`` consumed at the loss boundary) — the
        simulated real-loss set ``elastic_recover`` zeroes via
        ``io.destroy_shards`` before choosing a resume rung."""
        return tuple(sorted(h for h in self._destroyed
                            if not self.alive[h]))

    def alive_host_count(self) -> int:
        return sum(1 for a in self.alive if a)

    # -- detection -----------------------------------------------------
    def poll(self, step: int) -> tuple:
        """One simulated-mode heartbeat at the boundary of ``step``:
        consume any host-loss fault armed for this step, count one
        missed beat per dead-but-undeclared host, and DECLARE the ones
        that reached ``miss_k`` misses. Returns the hosts declared at
        THIS beat (empty tuple almost always)."""
        if self.faults is not None:
            for kind in self.faults.host_loss_at(step):
                h = self._highest_alive_undead()
                if h is not None:
                    self._dead[h] = kind
                    if self.faults.shard_loss_at(step):
                        # the loss takes its shard slices with it (the
                        # simulated real-loss semantics; zeroed by
                        # elastic_recover via io.destroy_shards)
                        self._destroyed.add(h)
        newly = []
        for h, kind in self._dead.items():
            if not self.alive[h]:
                continue
            self._missed[h] = self._missed.get(h, 0) + 1
            if self._missed[h] >= self.miss_k:
                newly.append(h)
        if newly:
            self._declare(newly, step)
        return tuple(newly)

    def _highest_alive_undead(self):
        for h in range(self.n_hosts - 1, -1, -1):
            if self.alive[h] and h not in self._dead:
                return h
        return None

    def _declare(self, hosts, step) -> None:
        for h in hosts:
            self.alive[h] = False
            if self.sim_hosts is None:
                self._lost_processes.add(h)
        self.epoch += 1
        if self.event_log is not None:
            self.event_log.emit(
                event="topology_lost", epoch=self.epoch,
                hosts=[int(h) for h in hosts],
                kinds=[str(self._dead.get(h, "?")) for h in hosts],
                step=int(step), miss_k=self.miss_k,
                survivors=len(self.survivor_devices()))

    # -- the piggybacked step-boundary collective ---------------------
    def step_boundary(self, stop: PreemptionGuard, step: int) -> Beat:
        """The combined step-boundary call: SIGTERM agreement AND
        heartbeat in the ONE small collective the loop already paid for
        ``PreemptionGuard.agree`` (real mode), or the local fast path +
        simulated poll (single process)."""
        import jax
        if self.sim_hosts is not None or not dist_initialized() \
                or jax.process_count() == 1:
            return Beat(stop=stop.agree(), lost=self.poll(step),
                        self_lost=False, hung=False)
        # real mode: host-loss directives are PROCESS-scoped here (the
        # same env-latched plan, a different consumer than the
        # simulated poll — sigterm@N precedent)
        self_kind = None
        if self.faults is not None:
            kinds = self.faults.host_loss_at(step)
            if kinds:
                self_kind = kinds[-1]
                if self_kind == "exit":
                    # announce in this (final) beat so the survivors'
                    # evidence is complete BEFORE the process dies
                    self._exiting = True
        from jax.experimental import multihost_utils
        payload = np.asarray(
            [1 if stop.triggered else 0, self.epoch,
             1 if self._exiting else 0], np.int32)
        done, flags = bounded_call(
            lambda: multihost_utils.process_allgather(payload),
            self.timeout)
        if not done:
            # the collective itself blocked past its deadline: a peer
            # died mid-step. The old world's collectives are unusable;
            # the caller must re-init the runtime before re-meshing.
            self.hung = True
            if self.event_log is not None:
                self.event_log.emit(event="topology_hang", step=int(step),
                                    timeout_s=self.timeout,
                                    epoch=self.epoch)
            return Beat(stop=False, lost=(), self_lost=False, hung=True)
        flags = np.asarray(flags).reshape(-1, 3)
        exiting = [p for p in range(flags.shape[0])
                   if flags[p, 2] and self.alive[p]
                   and p != jax.process_index()]
        if exiting:
            for h in exiting:
                self._dead[h] = "exit"
            self._declare(exiting, step)
        alive_rows = [p for p in range(flags.shape[0]) if self.alive[p]]
        stop_agreed = bool(np.min(flags[alive_rows, 0]) > 0)
        if self_kind == "hang":
            # simulate the hard-loss flavor: stop beating, keep the
            # process (the survivors' NEXT beat hits the deadline)
            time.sleep(1e9)
        return Beat(stop=stop_agreed, lost=tuple(exiting),
                    self_lost=(self_kind == "exit"), hung=False)
