"""Multi-host launch: the reference's cluster scripts, TPU-style.

The reference ships per-cluster srun recipes (`/root/reference/rc.sh`,
`todi.sh`, `glados.sh`: clone + make + `srun -n2` with one GPU and a
time limit) and bootstraps its communicator with `MPI_Init`
(main.cpp:6307). On TPU pods the launcher is whatever starts one
Python process per host (GKE, `gcloud compute tpus tpu-vm ssh --worker=all`,
or a queued-resource runtime); inside the process the entire "comm
runtime" is `jax.distributed.initialize` + one global device mesh —
XLA routes intra-slice collectives over ICI and cross-slice traffic
over DCN with no code changes here.

Typical pod run (v5e-16, 4 hosts x 4 chips):

    gcloud compute tpus tpu-vm ssh $TPU --worker=all --command='
        cd cup-tpu && python -m cup2d_tpu ... -mesh all'

Each process calls `init_distributed()` (TPU environments autodetect
coordinator/process_id from the pod metadata), then `global_mesh()`
returns the mesh over every chip of every host; `ShardedUniformSim`
/ `ShardedAMRSim` take it unchanged. Single-host runs (and the CPU
virtual-device CI mesh) skip initialize and get the local mesh.

Multi-host AMR determinism (the reference's update_boundary /
update_blocks contract, main.cpp:1410-1970): the host-side regrid
bookkeeping — tag thresholding, 2:1 state fixing, slot allocation, SFC
ordering, gather-table builds — runs INDEPENDENTLY on every process,
and the SPMD program diverges (hangs or corrupts) if any process
reaches a different decision. The design makes that impossible by
construction:

1. every regrid decision derives from ONE tag vector that every
   process holds in full — `AMRSim._pull_blockwise` turns the
   device-side tag pull into a `process_allgather` when
   `jax.process_count() > 1` (single global collective, then identical
   host numpy on every process);
2. everything downstream of the tags is deterministic pure-python/numpy
   on identical inputs (no hash-order iteration on data that differs
   per process: the state machine iterates SFC-sorted arrays);
3. scalar diagnostics (dt, umax, residuals) are outputs of global
   reductions — fully replicated across processes by SPMD semantics,
   so plain device_get agrees everywhere.

`tests/test_multihost.py` enforces this with two real jax.distributed
processes: three regrid+step cycles must produce identical topology +
gather-table digests on both, then the run writes a dump and a
checkpoint, restores, and continues identically.

Pod-safe I/O (io.py, the reference's collective MPI-IO dump
main.cpp:3367-3467): dump_forest/save_checkpoint are COLLECTIVE on
pods — every process joins one field all-gather, process 0 alone
writes (to shared storage, MPI-IO's own assumption), and a barrier
keeps the others from racing past an incomplete save. load_checkpoint
reads the same bytes on every process; everything downstream is the
deterministic replicated-host machinery above.
"""

from __future__ import annotations

import sys
import time
from typing import Callable, Optional

import jax

from .mesh import make_mesh


def _dist_initialized() -> bool:
    """Version-safe, public-API-only check that the distributed runtime
    is up (resilience.dist_initialized): the public
    ``jax.distributed.is_initialized`` accessor where the build has it,
    else the latch ``init_distributed`` sets below. Never touches the
    XLA backend, preserving this module's no-probe contract. (The
    former fallback read ``jax._src.distributed.global_state.client``
    — a private attribute that moves between versions.)"""
    from ..resilience import dist_initialized
    return dist_initialized()


def _connect_with_retry(connect: Callable[[], None],
                        attempts: int = 5,
                        backoff: float = 1.0) -> None:
    """Bounded exponential-backoff retry around the coordinator connect.

    ``jax.distributed.initialize`` makes ONE attempt; on a preemptible
    pod the coordinator process routinely comes up seconds after the
    workers (re-scheduled onto a fresh VM), and a single-shot connect
    kills the whole bring-up for a transient. Retries are bounded
    (``attempts``, delays backoff * 2^k) and LOGGED — to stderr and the
    resilience event log — so a flaky fabric is visible, not silent.
    The final failure propagates: a pod run degrading to independent
    single-host runs computes wrong answers with no error."""
    attempts = max(1, int(attempts))
    for attempt in range(1, attempts + 1):
        try:
            return connect()
        except Exception as e:
            if attempt >= attempts:
                raise
            delay = backoff * (2.0 ** (attempt - 1))
            print(f"cup2d_tpu: coordinator connect failed (attempt "
                  f"{attempt}/{attempts}): {e}; retrying in "
                  f"{delay:.1f}s", file=sys.stderr)
            from ..resilience import record_event
            record_event(event="coordinator_retry", attempt=attempt,
                         max_attempts=attempts, delay_s=delay,
                         error=str(e))
            time.sleep(delay)


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     expected_processes: Optional[int] = None,
                     connect_attempts: int = 5,
                     connect_backoff: float = 1.0) -> int:
    """Bring up the JAX distributed runtime for a multi-host run (the
    reference's MPI_Init moment, main.cpp:6307).

    On TPU pods all three arguments autodetect from the environment;
    pass them explicitly for CPU/GPU clusters. Safe to call on
    single-host runs: with nothing to join (no coordinator argument,
    no pod environment) it returns without touching the backend — the
    decision must not probe jax.process_count(), which would initialize
    XLA and make a later initialize() impossible. Init failures (e.g.
    unreachable coordinator) propagate: a pod run silently degrading to
    independent single-host runs computes wrong answers with no error.

    ``expected_processes`` is the belt-and-braces guard against exactly
    that degradation on launchers whose environment the pod heuristics
    don't recognize (ADVICE r2): pass the known world size (e.g. the
    `-mesh-hosts` flag / slurm's SLURM_NPROCS) and the call aborts
    unless that many processes actually joined. Returns this process's
    index.

    The connect itself retries with bounded exponential backoff
    (``connect_attempts`` tries, ``connect_backoff`` * 2^k seconds
    apart, logged) — see :func:`_connect_with_retry`. Both knobs are
    plumbed from the CLI (``-connectAttempts`` / ``-connectBackoff``,
    latched once from argv at this call — never a scattered env read),
    and the elastic re-init path (:func:`reinit_distributed`) takes its
    OWN budget rather than inheriting this first-launch one.
    """
    if _dist_initialized():
        rank = jax.process_index()
    else:
        explicit = (coordinator_address is not None
                    or num_processes is not None)
        if not explicit and not _in_tpu_pod():
            if expected_processes and expected_processes > 1:
                raise RuntimeError(
                    f"expected {expected_processes} processes but no "
                    "pod environment was detected and no coordinator "
                    "was given — refusing to run single-host silently")
            return 0
        _connect_with_retry(
            lambda: jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id),
            attempts=connect_attempts, backoff=connect_backoff)
        rank = jax.process_index()
    if expected_processes and jax.process_count() != expected_processes:
        raise RuntimeError(
            f"distributed runtime has {jax.process_count()} processes, "
            f"expected {expected_processes} — partial pod bring-up")
    return rank


def reinit_distributed(coordinator_address: str,
                       num_processes: int,
                       process_id: int,
                       connect_attempts: int = 10,
                       connect_backoff: float = 0.5) -> int:
    """Tear down and re-initialize the distributed runtime over the
    SURVIVOR world after a topology loss (resilience.TopologyGuard) —
    the runtime half of elastic recovery: once a peer is gone, every
    collective of the OLD world hangs, so the survivors must agree on a
    new (smaller) world before the re-meshed step can run.

    The connect budget is deliberately separate from
    :func:`init_distributed`'s first-launch one: a re-init races only
    the other survivors (already up, already agreed on the new world
    from the same beat evidence), so it wants more attempts at shorter
    backoff than a cold pod bring-up waiting on a scheduler. The
    coordinator address must name a SURVIVOR (by the determinism rule
    the new process 0 — survivors renumber by rank order), on a fresh
    port: the old coordinator service may be gone, or its port still
    parked in TIME_WAIT.

    Exercised by the slow-marked 2-process drill
    (tests/_multihost_worker.py); environment-broken in this container
    like the rest of the multi-process harness (ROADMAP)."""
    from ..resilience import record_event
    if _dist_initialized():
        try:
            jax.distributed.shutdown()
        except Exception as e:
            # a shutdown against a world with a dead member can itself
            # fail — log and proceed: initialize() below is the
            # authority on whether the new world comes up
            print(f"cup2d_tpu: distributed shutdown during re-init "
                  f"failed: {e}", file=sys.stderr)
            record_event(event="reinit_shutdown_failed", error=str(e))
    _connect_with_retry(
        lambda: jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id),
        attempts=connect_attempts, backoff=connect_backoff)
    record_event(event="reinit_distributed",
                 num_processes=num_processes, process_id=process_id)
    return jax.process_index()


def _in_tpu_pod() -> bool:
    """True when this process is one worker of a multi-host TPU slice
    (the autodetection case for jax.distributed.initialize). A
    single-entry TPU_WORKER_HOSTNAMES means a single-host slice — the
    runtime also sets it there, so only a multi-hostname list counts.
    Several launcher generations are covered (ADVICE r2: relying on one
    env var silently degrades on the others): classic TPU_WORKER_
    HOSTNAMES, megascale coordinators, and GKE/queued-resource runtimes
    that export per-worker ids with a >1 worker count."""
    import os
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if "," in hosts:
        return True
    if os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
        return True
    for nvar in ("TPU_WORKER_COUNT", "NUM_TPU_WORKERS",
                 "CLOUD_TPU_NUM_WORKERS"):
        try:
            if int(os.environ.get(nvar, "1")) > 1:
                return True
        except ValueError:
            pass
    return False


def global_mesh():
    """1-D mesh over every addressable chip of every host, in process
    order — contiguous SFC/x ranges per host, so halo traffic between
    chips of one host rides ICI and only the two range boundaries per
    host cross DCN (the layout rule from the scaling playbook: shard
    the contiguous spatial axis over the slowest network last)."""
    return make_mesh(devices=jax.devices())
