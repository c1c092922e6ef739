"""CLI driver: ``python -m cup2d_tpu <reference flags>``.

Runs the same case the reference's ``main()`` runs with the same flag
names (`/root/reference/main.cpp:6306-6341`, `run.sh:1-22`): e.g.

    python -m cup2d_tpu -bpdx 2 -bpdy 1 -levelMax 8 -levelStart 5 \
        -Rtol 2 -Ctol 1 -extent 4 -CFL 0.5 -tend 10 -lambda 1e7 \
        -nu 0.00004 -poissonTol 1e-3 -poissonTolRel 0.01 \
        -maxPoissonRestarts 0 -maxPoissonIterations 1000 -AdaptSteps 20 \
        -tdump 0.5 -shapes 'angle=0 L=0.2 xpos=1.8 ypos=0.8
                            angle=180 L=0.2 xpos=1.6 ypos=0.8'

By default this executes the adaptive (AMR) path, exactly like the
reference. Extra flags beyond the reference: ``-level N`` (force a
single-resolution uniform run at level N), ``-dtype``, ``-output DIR``,
``-checkpointEvery N``, ``-restart DIR``, ``-maxSteps N``, and ``-fleet B``
(fleet batching, fleet.py: advance B independent obstacle-free uniform
cases in ONE fused dispatch — per-member device dt/clocks, one batched
diag pull for the whole fleet, per-member supervision via
FleetStepGuard, per-member telemetry in schema v3. The t=0 state is an
amplitude-laddered Taylor-Green ensemble so every member runs at its
own CFL dt; dumps write one reference-format triplet per member,
``vel.NNNNNNNN.mK``. Obstacle-free only: ``-shapes`` with ``-fleet``
is an error).

``-serve N`` (with ``-fleet B``, PR 11) switches the fleet to
CONTINUOUS-BATCHING SERVING (fleet.FleetServer): N staggered-horizon
sessions flow through the fixed-B slot pool — each admitted into a
free slot, stepped under the per-slot active mask, retired at its own
``t_end`` with a bit-exact session checkpoint in
``<output>/sessions/<client>/`` (resumable via admit-from-checkpoint),
and the freed slot refilled from the queue, all with ZERO steady-state
recompiles (the mask and slot indices are device operands). A member
whose recovery ladder exhausts is EVICTED (slot freed, ``member_evict``
event) instead of aborting the fleet. Telemetry gains the schema-v7
serving gauges (active_members/occupancy/admitted/evicted/queue_depth)
plus one per-client JSONL stream each under ``<output>/clients/``;
SIGTERM parks every live session as its checkpoint and exits 0.

MULTI-DEVICE & ELASTIC (parallel/, PR 7): ``-mesh N|all`` runs the
sharded drivers (ShardedUniformSim / ShardedAMRSim) over an N-device
(or every-device) 1-D mesh; multi-process bring-up takes
``-coordinator HOST:PORT -meshHosts P -processId I`` (TPU pods
autodetect all three) with the coordinator connect budget on
``-connectAttempts`` / ``-connectBackoff`` (argv-latched at the
init_distributed call — never a scattered env read). ``-elastic`` arms
the TopologyGuard: a bounded-timeout heartbeat piggybacked on the
step-boundary SIGTERM collective (``-heartbeatTimeout S``, a host lost
after ``-heartbeatMissK K`` missed beats), survivors agreeing on the
same shrunk device set from the same evidence. On a SIMULATED topology
(``-simHosts H`` groups the virtual devices of a single-process run
into H hosts; losses injected by CUP2D_FAULTS host_exit@N /
host_hang@N, with shard_loss@N zeroing the lost host's shard bytes for
an honest real-loss drill) recovery is fully in place: re-mesh the
survivors, resume from the device snapshot ring, continue — no
relaunch. With >= 2 hosts the elastic guard also arms the
HOST-REDUNDANT MIRROR TIER (PR 17, default on; ``-noMirror`` off,
``-mirror`` explicit, ``-mirrorEvery N`` thins the cadence): each
snapshot additionally ships every host's shard block to its ring
neighbor via one device-side ppermute, checksummed, so a loss that
DESTROYS the owner's shards still resumes from HBM down the
ring → mirror → disk → abort ladder (a corrupt/stale mirror is
rejected — ``mirror_reject`` event — never installed). On a REAL pod
the CLI today gives bounded detection + an orderly abort (the old
behavior was an indefinite hang); the in-place runtime re-init
(launch.reinit_distributed) is library-level, pending a working
multi-process runtime to validate against (ROADMAP).

CASE CATALOG (cases.py + bc.py, ISSUE 12): ``-case cavity|channel|
cylinder`` runs a named validation workload instead of parsing a
reference config — the case supplies its own SimConfig, per-face
BCTable and initial/obstacle state (``cavity``: lid-driven cavity,
four no-slip walls + moving lid, obstacle-free, also fleet-servable
via ``-fleet B``; ``channel``: Dirichlet inflow / convective outflow
past a fixed cylinder; ``cylinder``: the legacy towed-disk free-slip
case). ``-level N`` overrides the case's validation resolution. The
per-face boundary-condition engine behind it (bc.py BCTable:
free_slip | no_slip(u_wall) | dirichlet_inflow | convective_outflow
per face) is a uniform-family feature; the AMR/forest tier and the
Pallas megakernel tier refuse non-free-slip tables loudly at
construction and the default table is bit-identical to the legacy
free-slip/Neumann box.

The run loop is SUPERVISED (resilience.py): every step's health verdict
rides the diagnostics the step already pulls, a bad step walks the
rewind/escalate/disk-restore/abort ladder, SIGTERM checkpoints at the
next step boundary and exits 0, and every recovery lands in
``<output>/events.jsonl``. Since PR 4 the supervision tax is off the
hot loop: the good-state ring is DEVICE-RESIDENT (HBM copies, no
per-step D2H gather), ``-snapEvery N`` snapshots every N good steps
and replays bit-exactly from the last snapshot on a bad verdict, and
the verdict itself is ONE-STEP-LAGGED on the device-diag paths (step
N+1 dispatches before step N's scalars are pulled — detection latency
1 step, zero blocking per-step syncs; ``-noLag`` restores the eager
verdict). Knobs: ``-noSupervise`` (verdict-only: first bad step aborts
— still with a post-mortem checkpoint, unlike the old inline NaN
check), ``-guardRing K`` (confirmed-snapshot ring depth, default 1),
``-eventLog PATH``. Fault drills: the ``CUP2D_FAULTS`` env var
(faults.py) injects NaNs, wrong-but-finite field corruption, solver
give-ups, mid-save crashes and SIGTERMs on schedule.

TELEMETRY (profiling.py, PR 3) is on by default: one structured record
per step (solver health, dt/umax, kinetic energy + max |∇·u|, AMR
shape, halo comm volume, jit-recompile/device-pull counters, HBM peak,
phase times) streamed to ``<output>/metrics.jsonl`` — zero extra device
syncs, everything rides the step's one existing batched pull. Summarize
with ``python -m cup2d_tpu.post --metrics <path>``. The physics
invariants feed a drift watchdog wired into the recovery ladder
(catches wrong-but-FINITE corruption the isfinite verdict misses).
Knobs: ``-noMetrics``, ``-metricsLog PATH``, ``-noWatchdog``. Windowed
device tracing: ``CUP2D_TRACE=start:stop[:logdir]`` wraps exactly those
steps in a ``jax.profiler`` TensorBoard trace; inside the window the
flight recorder's spans are in the trace too (``cup2d:step``, ...), the
device operations carry the step's scope names (``tracing.SCOPES``),
and the profiler's Python tracer is OFF (``profiling.TraceWindow``:
the spans name the host's part of a step, a frame-by-frame record
cost 0.7 ms of host a traced step at 8192^2 and took the idle gaps'
names — PERF.md, PR 24).

THE FLIGHT RECORDER (tracing.py, PR 18) rides the same zero-extra-sync
discipline: span timeline (``<output>/spans.jsonl``, export with
``python -m cup2d_tpu.post --trace``), compile/HBM ledger and serving
latency histograms (both summarized into ``metrics.jsonl`` at exit).
The spans' names are one vocabulary, ``tracing.SPANS``: the loop's
``step`` > ``dispatch`` / ``snapshot`` / ``verdict`` and ``record``;
on the forest ``regrid`` and ``tables``, the rebuild of the lookup
tables, with its parts ``tables.topo``, ``tables.halo`` (a halo set
each, attribute ``set``), ``tables.faces``, ``tables.pad``,
``tables.flux``, ``tables.coarse`` and ``tables.geom``; and inside a
shaped step's ``dispatch`` its host parts in order, ``dt`` (``pulled``
where it round-trips a scalar), ``kinematics``, ``shape_inputs``,
``enqueue``, ``pull`` (the step's one blocking ``jax.device_get``: the
device's wait is in it) and ``bodies``.
Knobs: ``-noSpans``, ``-spansLog PATH``, ``-noMemLedger``,
``-logRotateMB N`` (size-capped rotation of metrics/clients/spans
JSONL streams — default off), ``CUP2D_SPANS=0|N`` (disable spans /
ring capacity, latched once).
"""

from __future__ import annotations

import os
import sys
import time

from .config import CommandlineParser, SimConfig
from .io import dump_forest, dump_uniform, load_checkpoint, save_checkpoint


from .cache import enable_compilation_cache


def main(argv=None, *, sim_out=None) -> int:
    """Run one case from ``argv`` (default ``sys.argv[1:]``); returns
    the process exit code. ``sim_out``: an in-process caller's list
    that receives the driver object once it is built, so a smoke
    (chip_smoke.py) can inspect the final state and its placement
    without a disk round trip — not a command-line feature."""
    enable_compilation_cache()
    argv = sys.argv[1:] if argv is None else argv
    p = CommandlineParser(argv)
    case_name = p("case").asString() if p.has("case") else None
    # a -case run gets its SimConfig from the catalog (cases.py), so
    # the reference flag set (-bpdx/-tend/...) is not required on the
    # command line; the case branch below sets cfg = sim.cfg
    cfg = None if case_name is not None else SimConfig.from_argv(argv)
    fleet_n = p("fleet").asInt() if p.has("fleet") else 0
    serve_n = p("serve").asInt() if p.has("serve") else 0
    if serve_n and not fleet_n:
        print("cup2d_tpu: -serve N needs -fleet B (the slot pool it "
              "serves through)", file=sys.stderr)
        return 2
    if serve_n and p.has("restart"):
        print("cup2d_tpu: -serve resumes per-session (admit from "
              "<output>/sessions/<client>), not from a whole-fleet "
              "-restart", file=sys.stderr)
        return 2
    uniform = (fleet_n > 0 or p.has("level") or case_name is not None
               or cfg.level_max <= 1)
    outdir = p("output").asString() if p.has("output") else "."
    ckpt_every = p("checkpointEvery").asInt() if p.has("checkpointEvery") \
        else 0
    max_steps = p("maxSteps").asInt() if p.has("maxSteps") else 10**9
    # size-capped JSONL rotation (metrics/clients/spans) — default off;
    # long serving runs cap each stream at N MB per segment
    rotate_mb = p("logRotateMB").asInt() if p.has("logRotateMB") else None
    os.makedirs(outdir, exist_ok=True)

    from . import faults, tracing
    from .profiling import HostCounters, MetricsRecorder, TraceWindow
    from .resilience import EventLog, FleetStepGuard, PhysicsWatchdog, \
        PreemptionGuard, ResilienceAbort, StepGuard, set_event_log

    plan = faults.FaultPlan.from_env()   # CUP2D_FAULTS, latched once
    faults.install(plan)                 # io.py's crash window consults it
    events_path = p("eventLog").asString() if p.has("eventLog") \
        else os.path.join(outdir, "events.jsonl")
    log = EventLog(events_path)
    set_event_log(log)                   # io/launch fallback events
    tracer = TraceWindow.from_env()      # CUP2D_TRACE, latched once

    # multi-device mesh (+ optional multi-process bring-up). The
    # connect budget is argv-latched HERE and passed down — satellite
    # of the elastic work: a scattered env read would be exactly the
    # mid-run-mutation hazard the CUP2D_* latch rule exists for.
    mesh = None
    if p.has("mesh"):
        from .parallel.launch import global_mesh, init_distributed
        from .parallel.mesh import make_mesh
        init_distributed(
            coordinator_address=(p("coordinator").asString()
                                 if p.has("coordinator") else None),
            num_processes=(p("meshHosts").asInt()
                           if p.has("meshHosts") else None),
            process_id=(p("processId").asInt()
                        if p.has("processId") else None),
            expected_processes=(p("meshHosts").asInt()
                                if p.has("meshHosts") else None),
            connect_attempts=(p("connectAttempts").asInt()
                              if p.has("connectAttempts") else 5),
            connect_backoff=(p("connectBackoff").asDouble()
                             if p.has("connectBackoff") else 1.0))
        spec = p("mesh").asString()
        mesh = global_mesh() if spec == "all" else make_mesh(int(spec))
    if p.has("elastic") and (mesh is None or mesh.devices.size < 2):
        print("cup2d_tpu: -elastic needs -mesh with at least 2 devices "
              "(nothing to re-mesh onto otherwise)", file=sys.stderr)
        return 2

    if case_name is not None:
        # validation-case catalog (cases.py): the case supplies its own
        # SimConfig + BCTable + initial/obstacle state; -level overrides
        # the validation resolution, -fleet serves fleet-capable cases
        from .cases import REGISTRY, initial_states, make_sim
        spec = REGISTRY.get(case_name)
        if spec is None:
            names = ", ".join(c for c in REGISTRY)
            print(f"cup2d_tpu: unknown -case {case_name!r} "
                  f"(catalog: {names})", file=sys.stderr)
            return 2
        kw = {}
        if p.has("level"):
            kw["level"] = p("level").asInt()
        if fleet_n:
            if not spec.fleet_ok:
                print(f"cup2d_tpu: -case {case_name} does not ride the "
                      "fleet slot pool (obstacle cases are solo-driver "
                      "only)", file=sys.stderr)
                return 2
            kw["members"] = fleet_n
        if mesh is not None:
            if case_name != "cavity":
                print(f"cup2d_tpu: -case {case_name} does not combine "
                      "with -mesh (the sharded path is obstacle-free "
                      "only)", file=sys.stderr)
                return 2
            kw["mesh"] = mesh
        sim = make_sim(case_name, **kw)
        cfg = sim.cfg   # the case's config drives dt/dump/end-time below
        # -tend/-tdump still override the case's schedule (smoke runs
        # want short horizons without forking the catalog); the grid
        # and operators are already built from cfg, so only the
        # schedule fields may be overridden post hoc
        if p.has("tend"):
            cfg.end_time = p("tend").asDouble()
        if p.has("tdump"):
            cfg.dump_time = p("tdump").asDouble()
    elif fleet_n:
        if cfg.shapes:
            print("cup2d_tpu: -fleet supports obstacle-free uniform "
                  "runs only (shapes given)", file=sys.stderr)
            return 2
        if mesh is not None:
            print("cup2d_tpu: -fleet has its own placement policy "
                  "(fleet.py) and does not combine with -mesh",
                  file=sys.stderr)
            return 2
        from .fleet import FleetSim
        level = p("level").asInt() if p.has("level") else cfg.level_start
        sim = FleetSim(cfg, level=level, members=fleet_n)
        if not p.has("restart") and not serve_n:
            # obstacle-free zero state would be a trivial run: seed the
            # amplitude-laddered Taylor-Green ensemble (per-member umax
            # -> per-member dt, the no-lockstep contract live). A
            # SERVING run starts empty instead — sessions arrive
            # through the FleetServer queue
            sim.seed_taylor_green()
    elif uniform:
        level = p("level").asInt() if p.has("level") else cfg.level_start
        if mesh is not None:
            if cfg.shapes:
                print("cup2d_tpu: -mesh on the uniform path is "
                      "obstacle-free only (ShardedUniformSim)",
                      file=sys.stderr)
                return 2
            from .parallel.mesh import ShardedUniformSim
            from .uniform import taylor_green_state
            sim = ShardedUniformSim(cfg, mesh, level=level)
            if not p.has("restart"):
                # same rationale as the fleet seed: an obstacle-free
                # zero state is a trivial run
                sim.set_state(taylor_green_state(sim.grid))
        else:
            from .sim import Simulation
            sim = Simulation(cfg, level=level)
    else:
        if mesh is not None:
            from .parallel.forest_mesh import ShardedAMRSim
            sim = ShardedAMRSim(cfg, mesh)
        else:
            from .amr import AMRSim
            sim = AMRSim(cfg)
    if sim_out is not None:
        sim_out.append(sim)
    if p.has("restart"):
        load_checkpoint(p("restart").asString(), sim)

    if not fleet_n and hasattr(type(sim), "force_log_header"):
        # the obstacle-free sharded driver (ShardedUniformSim) computes
        # no body forces — there is nothing to log, like the fleet
        force_path = os.path.join(outdir, "forces.csv")
        resuming = p.has("restart") and os.path.exists(force_path)
        sim.force_log = open(force_path, "a" if resuming else "w")
        if not resuming:
            sim.force_log.write(type(sim).force_log_header() + "\n")

    if sim.shapes and not p.has("restart"):
        # t=0 only: the chi-blend vel = vel(1-chi) + udef*chi would
        # discard the rigid-motion part of a RESTORED body-interior
        # velocity and silently fork the resumed trajectory (ADVICE.md
        # r1); load_checkpoint already marks the sim initialized.
        sim.initialize()   # so the t=0 dump sees the blended velocity

    def dump(path):
        if fleet_n:
            # one reference-format triplet per member, at the MEMBER's
            # own clock (sim.time is only the fleet min)
            for m in range(sim.members):
                dump_uniform(f"{path}.m{m}", float(sim.times[m]),
                             sim.state.vel[m], sim.grid.h)
        elif uniform:
            dump_uniform(path, sim.time, sim.state.vel, sim.grid.h)
        else:
            sim.sync_fields()
            dump_forest(path, sim.time, sim.forest)

    ckpt_path = os.path.join(outdir, "checkpoint")
    topo = None
    if p.has("elastic"):
        from .resilience import TopologyGuard, dist_initialized
        if not p.has("simHosts") and not dist_initialized():
            # single process without a simulated topology: the "real"
            # heartbeat would watch a 1-host world whose only possible
            # loss is this process itself — a host_exit fault would be
            # misdiagnosed as a pod losing its host. Usage error, not
            # a runtime misdiagnosis.
            print("cup2d_tpu: -elastic on a single-process run needs "
                  "-simHosts H (H >= 2) to stand up a simulated "
                  "topology; real heartbeats need a multi-process "
                  "bring-up (-coordinator/-meshHosts)", file=sys.stderr)
            return 2
        topo = TopologyGuard(
            devices=list(mesh.devices.flat),
            sim_hosts=(p("simHosts").asInt()
                       if p.has("simHosts") else None),
            miss_k=(p("heartbeatMissK").asInt()
                    if p.has("heartbeatMissK") else 3),
            timeout=(p("heartbeatTimeout").asDouble()
                     if p.has("heartbeatTimeout") else 10.0),
            faults=plan, event_log=log)
    # host-redundant mirror tier (PR 17): defaults ON when the elastic
    # machinery is armed over >= 2 (simulated or real) hosts — that is
    # exactly the regime where a host loss is survivable in HBM.
    # -mirror forces it on (still needs a topology), -noMirror off;
    # -mirrorEvery N thins the cadence.
    mirror_hosts = None
    if topo is not None and not p.has("noMirror"):
        if topo.n_hosts >= 2 and (p.has("mirror") or p.has("elastic")):
            mirror_hosts = topo.n_hosts
    guard_cls = FleetStepGuard if fleet_n else StepGuard
    guard = guard_cls(
        sim,
        ring=p("guardRing").asInt() if p.has("guardRing") else 1,
        ckpt_dir=ckpt_path,
        postmortem_dir=os.path.join(outdir, "postmortem"),
        event_log=log,
        faults=plan,
        recover=not p.has("noSupervise"),
        watchdog=None if p.has("noWatchdog") else PhysicsWatchdog(),
        snap_every=p("snapEvery").asInt() if p.has("snapEvery") else 1,
        lag=not p.has("noLag"),
        mirror_hosts=mirror_hosts,
        mirror_every=(p("mirrorEvery").asInt()
                      if p.has("mirrorEvery") else 1),
    )

    # -serve N: continuous-batching serving — N staggered-horizon
    # sessions flow through the B-slot pool (admit/retire/evict churn,
    # zero steady-state recompiles). The server wires the guard's
    # eviction rung (on_member_abort) in its constructor.
    server = None
    if serve_n:
        from .fleet import (FleetRequest, FleetServer, FlowState,
                            taylor_green_fleet)
        serving_lat = None
        if not p.has("noMetrics"):
            # latency histograms ride the server's existing submit/
            # admit/step boundaries — host clocks only
            from .tracing import ServingLatency
            serving_lat = ServingLatency()
        server = FleetServer(
            sim, guard=guard,
            session_dir=os.path.join(outdir, "sessions"),
            event_log=log,
            clients_dir=os.path.join(outdir, "clients"),
            clients_rotate_mb=rotate_mb, latency=serving_lat)
        # the session ladder: horizons staggered across [tend/2, tend]
        # so retirements interleave with admissions (real churn, not
        # one synchronized wave). A -case pool serves the CASE's own
        # initial states (turb2d: seed + slot, so sessions differ; the
        # free-slip Taylor-Green below is discontinuous across a
        # periodic wrap — served into a periodic pool at 512^2 it went
        # non-finite inside ten steps); plain flags keep Taylor-Green
        # at geometrically decaying amplitudes (per-session umax ->
        # per-session dt)
        if case_name is not None:
            ens = initial_states(case_name, sim.grid, serve_n)
        else:
            ens = taylor_green_fleet(sim.grid, serve_n)
        for i in range(serve_n):
            t_end = cfg.end_time * (0.5 + 0.5 * (i + 1) / serve_n)
            server.submit(FleetRequest(
                client_id=f"s{i:04d}",
                state=FlowState(*(a[i] for a in ens)),
                t_end=t_end))

    # telemetry: on unless -noMetrics; the record rides the step's one
    # existing batched diag pull — under the lagged verdict the record
    # for step N is emitted when its verdict lands (during step N+1's
    # call, or at the final drain), labeled with the step's own
    # step/t/dt from the guard's record. The CALL-scoped host metrics
    # (wall_ms, jit_compiles/device_gets deltas) therefore
    # describe the call that resolved N — i.e. N+1's dispatch plus N's
    # lagged pull — a one-call skew that is CONSISTENT across those
    # fields (a compile spike and its wall cost land on the same row).
    metrics_log = None
    recorder = None
    counters = None
    flight = None
    spans_log = None
    if not p.has("noMetrics"):
        metrics_path = p("metricsLog").asString() if p.has("metricsLog") \
            else os.path.join(outdir, "metrics.jsonl")
        metrics_log = EventLog(metrics_path, rotate_mb=rotate_mb)
        counters = HostCounters().install()
        # flight recorder: span timeline (spans.jsonl, per process),
        # compile/HBM ledger (summarized into metrics.jsonl at exit).
        # Zero new device pulls — the zero-overhead contract is pinned
        # by tests/test_tracing.py (bit-identical, equal device_gets,
        # equal jit_compiles)
        from .tracing import FlightRecorder
        spans_path = p("spansLog").asString() if p.has("spansLog") \
            else os.path.join(outdir, "spans.jsonl")
        spans_log = EventLog(spans_path, rotate_mb=rotate_mb,
                             all_writers=True)
        flight = FlightRecorder.from_env(
            spans=not p.has("noSpans"),
            capture_memory=not p.has("noMemLedger"),
            sink=spans_log).install()
        recorder = MetricsRecorder(sink=metrics_log, counters=counters,
                                   guard=guard, server=server,
                                   flight=flight)
        recorder.prime(sim)

    def record(rec, wall_ms=None):
        if rec is not None and recorder is not None:
            with tracing.span("record", step=int(rec["step"])):
                recorder.record_step(step=rec["step"], t=rec["t"],
                                     dt=rec["dt"], diag=rec, sim=sim,
                                     wall_ms=wall_ms)

    def drain():
        # settle every in-flight verdict (before dumps, regrids,
        # checkpoints, preemption saves and at loop exit — all of them
        # read state + clock, and a checkpoint of an unverdicted step
        # would persist a possibly-bad state); recovery may rewind the
        # clock, so callers re-check the loop condition after
        for rec in guard.drain():
            record(rec)

    # SIGTERM = preemption notice: finish the step in flight, write the
    # restart point, exit 0 (the grace window buys a checkpoint, not a
    # corpse). Installed around the loop only — library users keep
    # their own handlers.
    stop = PreemptionGuard().install()

    rc = 0
    try:
        if server is not None:
            # serving loop: refill / fused step / retire each cycle.
            # No -tdump schedule here — a session's artifact is its
            # save-on-retire checkpoint (sessions/<client>), and its
            # telemetry its per-client stream (clients/<client>.jsonl).
            # FleetStepGuard forces the eager verdict (lag=False), so
            # there is never a pending verdict between cycles and
            # admit/retire/evict always land on settled state.
            while ((server.queue or server.active.any())
                   and sim.step_count < max_steps):
                if stop.agree():
                    n_parked = server.park_all()
                    log.emit(event="sigterm_park", step=sim.step_count,
                             parked=n_parked, queued=len(server.queue),
                             signum=stop.signum)
                    print(f"cup2d_tpu: SIGTERM at step "
                          f"{sim.step_count} — {n_parked} live "
                          f"session(s) parked under "
                          f"{os.path.join(outdir, 'sessions')}, "
                          f"{len(server.queue)} still queued, exiting "
                          "cleanly", file=sys.stderr)
                    return 0
                if sim.step_count % 5 == 0:
                    print(f"cup2d_tpu: {sim.step_count:08d} serving "
                          f"{int(server.active.sum())}/{sim.members} "
                          f"slots, queue={len(server.queue)}, "
                          f"retired={server.retired}, "
                          f"evicted={server.evicted}", file=sys.stderr)
                t_step = time.perf_counter()
                rec = server.step()
                if rec is None:
                    break
                record(rec,
                       wall_ms=1e3 * (time.perf_counter() - t_step))
            print(f"cup2d_tpu: served {server.admitted} session(s): "
                  f"{server.retired} retired, {server.evicted} "
                  f"evicted, {len(server.queue)} unserved",
                  file=sys.stderr)
        next_dump = sim.time if cfg.dump_time > 0 else float("inf")
        while server is None:   # the classic (non-serving) run loop
            if not (sim.time < cfg.end_time
                    and sim.step_count < max_steps):
                # loop end — or a lagged NaN clock; the drain settles
                # pending verdicts (recovery rewinds a poisoned clock),
                # then the condition is re-checked. Note the lag-1
                # semantics: the condition reads the settled clock (one
                # step stale on the device-diag paths), so the run may
                # dispatch-and-commit ONE step more than a -noLag run
                # of the same case before stopping — the reference loop
                # itself overshoots tend by up to one dt, and the
                # per-step states remain bit-identical; only the
                # stopping point shifts by <= 1 step.
                if guard.pending:
                    drain()
                    continue
                break
            # agree() is a min-allreduce of the SIGTERM latch on pods
            # (all hosts enter the collective save at the same step —
            # the former ROADMAP pod gap (a)); single-host it is just
            # the local flag. With -elastic the SAME step-boundary
            # collective carries the heartbeat (TopologyGuard — one
            # bounded allgather instead of two).
            if topo is not None:
                beat = topo.step_boundary(stop, sim.step_count)
                if beat.self_lost:
                    # real-mode host_exit fault: die like a lost host
                    # would — hard, immediately, writing nothing (the
                    # survivors' detection drill)
                    os._exit(17)
                if beat.hung:
                    print("cup2d_tpu: heartbeat collective missed its "
                          f"{topo.timeout:.1f}s deadline at step "
                          f"{sim.step_count} — a peer died mid-step. "
                          "The old world's collectives are unusable; "
                          "in-place resume needs a runtime re-init "
                          "(parallel.launch.reinit_distributed, "
                          "orchestrator-driven). Aborting with the "
                          "last checkpoint intact.", file=sys.stderr)
                    return 1
                if beat.lost:
                    if topo.sim_hosts is None:
                        # real pod: detection is bounded, but in-place
                        # resume additionally needs the runtime re-init
                        # (see the hang branch) — orderly abort beats
                        # the pre-elastic indefinite hang
                        print(f"cup2d_tpu: hosts {list(beat.lost)} "
                              "left the program — aborting (in-place "
                              "pod resume pending a validated "
                              "reinit_distributed path, ROADMAP)",
                              file=sys.stderr)
                        return 1
                    # simulated topology: re-mesh the survivors and
                    # resume from the snapshot ring / disk, in place
                    guard.elastic_recover(topo)
                    continue
                stop_now = beat.stop
            else:
                stop_now = stop.agree()
            if stop_now:
                drain()
                save_checkpoint(ckpt_path, sim)
                log.emit(event="sigterm_checkpoint", step=sim.step_count,
                         sim_time=sim.time, path=ckpt_path,
                         signum=stop.signum)
                print(f"cup2d_tpu: SIGTERM at step {sim.step_count} — "
                      f"checkpoint written to {ckpt_path}, exiting "
                      "cleanly", file=sys.stderr)
                return 0
            if sim.step_count % 5 == 0:
                # the clock is settled-through-verdict: one step stale
                # on the lagged paths (cosmetic here)
                print(f"cup2d_tpu: {sim.step_count:08d} t={sim.time:.6f}",
                      file=sys.stderr)
            if cfg.dump_time > 0 and sim.time >= next_dump:
                # catch the schedule up even when dt > tdump (the
                # reference falls permanently behind there,
                # main.cpp:6597-6602); the lagged clock can trigger
                # this one step late — the dump itself must see a
                # settled, verdicted state, and the drain's recovery
                # may REWIND the clock below the schedule (disk-restore
                # rung), in which case this dump is not due after all
                drain()
                if sim.time >= next_dump:
                    while next_dump <= sim.time:
                        next_dump += cfg.dump_time
                    dump(os.path.join(outdir,
                                      f"vel.{sim.step_count:08d}"))
            if not uniform and (sim.step_count <= 10
                                or sim.step_count % cfg.adapt_steps == 0):
                drain()   # never regrid an unverdicted state
                sim.adapt()
            if tracer is not None:
                tracer.maybe_start(sim.step_count)
            t_step = time.perf_counter()
            rec = guard.step()
            # the record before the window may close: a traced step's
            # `record` span is in the trace it belongs to, and wall_ms
            # does not hold the profiler's shut-down
            record(rec, wall_ms=1e3 * (time.perf_counter() - t_step))
            if tracer is not None:
                tracer.maybe_stop(sim.step_count)
            if ckpt_every and sim.step_count % ckpt_every == 0:
                drain()
                save_checkpoint(ckpt_path, sim)
    except ResilienceAbort as e:
        # the guard already wrote the post-mortem checkpoint, emitted
        # the abort event and closed the force log
        print(f"cup2d_tpu: unrecoverable step failure — {e}",
              file=sys.stderr)
        rc = 1
    finally:
        stop.uninstall()
        if server is not None:
            server.close()   # flush/close the per-client streams
        if tracer is not None:
            tracer.close()   # a window past tend must not leak a trace
        if sim.force_log is not None and not sim.force_log.closed:
            sim.force_log.close()
        if counters is not None:
            counters.uninstall()
        if metrics_log is not None:
            # run-report rows: the serving latency distributions and
            # the compile blame ledger ride the metrics stream so
            # ``post --metrics`` summarizes them with the records
            if server is not None and server.latency is not None:
                metrics_log.emit(event="serving_latency",
                                 **server.latency.report())
            if flight is not None:
                metrics_log.emit(event="compile_ledger",
                                 **flight.ledger_report())
        if flight is not None:
            flight.close()      # flushes the span ring into spans_log
        if spans_log is not None:
            spans_log.close()
        if metrics_log is not None:
            metrics_log.close()
        set_event_log(None)
        log.close()
    if rc:
        return rc

    if not uniform:
        sim.sync_fields()   # leave the slot fields dict current
    print(f"cup2d_tpu: done at t={sim.time:.6f} "
          f"after {sim.step_count} steps", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
