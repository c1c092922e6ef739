"""graftlint policy — the sanctioned-site tables, as DATA.

Every table row is a deliberate, reasoned exception to a rule; adding
a row is a review-visible act (this file is the single source of
truth — the tests are thin wrappers over it, per ISSUE 15 there is no
second copy anywhere). Rows that stop matching reality are themselves
findings (`stale policy row`, emitted by each rule's finalize pass),
so the tables cannot rot silently.
"""

from __future__ import annotations


# ---------------------------------------------------------------------------
# env-latch — CUP2D_* env gates must be read ONCE at a sanctioned
# construction/enable point and stored, never consulted mid-run: a read
# inside a jitted body or a per-refresh helper means a mid-run env
# mutation silently flips an operator/preconditioner form at the next
# retrace or regrid (the hazard class CUP2D_SHARD_EXCHANGE and
# CUP2D_POIS/CUP2D_TWOLEVEL were each fixed for, ADVICE r5 / PR 1).
# Migrated verbatim from tests/test_env_latch.py (PR 2-13); the test is
# now a thin wrapper over this table.
# ---------------------------------------------------------------------------

# files where ANY CUP2D_* read is a sanctioned latch:
#   config.py — the typed-config construction point
ENV_LATCH_FILES = frozenset({"config.py"})

# (file, enclosing scope) -> allowed vars. Each is a construct-once /
# enable-once latch, grandfathered with its reason:
ENV_LATCH_SITES = {
    # A/B gates latched per-sim in the constructor (ADVICE r5).
    # CUP2D_POIS mode values: structured|tables|fft|fas|fas-f on the
    # forest (AMRSim validates; fas/fas-f select the forest-native FAS
    # full solver since PR 13, and fftd refuses by name — uniform-only),
    # and fas|fas-f|fftd on the uniform family (fftd, ISSUE 20: the
    # FFT-diagonalized direct solve is a VALUE of the existing latch,
    # NOT a new read site — tests/test_analysis.py pins that) —
    # the UniformGrid constructor is the ONE uniform-side latch;
    # fleet.py and the parallel/ modules read the GRID's stored latch
    # and stay env-read-free (the package walk enforces it).
    # CUP2D_PALLAS (PR 9): the forest's own fused-tier latch — the
    # lab-mode megakernel dispatch in _advect_rk2 reads the stored
    # self._kernel_tier, never the env.
    # CUP2D_PREC (ISSUE 19): the forest's SOLVER-side read — the
    # bf16-leg FAS tier (stored self._fas_leg_dtype; the cycle and
    # smoother consume the stored dtype, never the env)
    ("amr.py", "AMRSim.__init__"): {"CUP2D_POIS", "CUP2D_TWOLEVEL",
                                    "CUP2D_PALLAS", "CUP2D_PREC"},
    # per-grid constructor latches (stored as self._kernel_tier /
    # self.solver_mode+self.fas_fmg). CUP2D_PREC (PR 9) is the
    # storage-precision contract of the fused tier: ONE read site in
    # the whole package — fleet and mesh consume the grid's stored
    # tier string, so a mid-run env mutation can never flip the
    # precision of a compiled step
    ("uniform.py", "UniformGrid.__init__"): {"CUP2D_PALLAS",
                                             "CUP2D_POIS",
                                             "CUP2D_PREC"},
    # the fault-injection latch (PR 7 tightened faults.py from a
    # whole-file sanction to this one scope): every injector —
    # including the elastic host_exit/host_hang tokens — parses from
    # the ONE plan FaultPlan.from_env constructs; consumers (StepGuard,
    # TopologyGuard, io's crash window) read the plan object, never the
    # env
    ("faults.py", "FaultPlan.from_env"): {"CUP2D_FAULTS"},
    # read once from ShardedAMRSim.__init__, stored as self._exchange
    ("parallel/forest_mesh.py", "_exchange_mode"):
        {"CUP2D_SHARD_EXCHANGE"},
    # windowed device tracing: latched once by the CLI before the run
    # loop (a mid-run mutation must not re-arm a finished window)
    ("profiling.py", "TraceWindow.from_env"): {"CUP2D_TRACE"},
    # flight-recorder span-ring latch (ISSUE 18): read once at
    # construction; the installed recorder stores spans_on/max_spans,
    # so a mid-run env mutation can never flip the span instrument of
    # a live run
    ("tracing.py", "FlightRecorder.from_env"): {"CUP2D_SPANS"},
}


# ---------------------------------------------------------------------------
# host-sync — the zero-extra-syncs contract (PR 3/4): the hot loop pays
# exactly ONE batched ``jax.device_get`` per step (the diag pull); every
# other device->host transfer lives on a cold path (checkpoint gather,
# post-mortem, restore) or inside the counting wrapper itself. A stray
# per-scalar pull (``float(jnp_scalar)``, ``np.asarray(tracer)``,
# ``.item()``) in a driver serializes the dispatch pipeline and used to
# be caught only AFTER the fact by the equal-device_get-count runtime
# tests. (file, scope) rows below are the sanctioned pull sites.
# ---------------------------------------------------------------------------

HOST_SYNC_SITES = {
    # THE batched scalar pull: library paths that keep diag scalars on
    # device pay one device_get for the whole set (PR 2)
    "resilience.py": {"_host_scalars"},
    # per-driver step pulls — each is the step's ONE existing batched
    # diag transfer (PR 3 folded the whole diag dict into what used to
    # fetch dt_next alone); the cold-start dt bootstrap in the same
    # scope is a once-per-run pull by design
    "sim.py": {"Simulation.step_once"},
    "uniform.py": {"UniformSim.step_once"},
    # fleet: the fused dispatch's one pull; member_step_once is the
    # guard's solo replay/retry executable — recovery is the cold path
    "fleet.py": {"FleetSim.step_once", "FleetSim.member_step_once"},
    # the forest driver's one pull per step, and _float_pull — the
    # trigger-drain helper that folds the pending poisson-iters scalar
    # into the SAME transfer precisely so no second round trip exists.
    # _pull_blockwise is the regrid path's tag-vector gather (per
    # regrid, not per step): on pods it MUST all-gather so every
    # process reaches the same host-side regrid decision
    "amr.py": {"AMRSim.step_once", "AMRSim._float_pull",
               "AMRSim._pull_blockwise"},
    # io's gather path: checkpoint/post-mortem state gathers and the
    # topology-mismatch restore fallback are cold paths that NEED the
    # transfer (HostCounters.state_gathers meters them at runtime).
    # _to_host_global is the owning-copy pull under them all — a
    # collective on pods, and deliberately np.array (not a view): the
    # snapshot ring holds its results across donated-buffer steps.
    # verify_mirror is the mirror tier's checksum pull (PR 17): ONE
    # batched device_get of the uint32 block sums, on the cold
    # elastic-recovery path only — capture-side mirroring is pure
    # device collectives and never syncs
    "io.py": {"_gather_state", "restore_snapshot_device",
              "_to_host_global", "verify_mirror"},
    # the counting wrapper itself (wraps jax.device_get to meter pulls)
    # and the recorder's library-path fallback (one pull, documented)
    "profiling.py": {"_install_hooks", "MetricsRecorder.record_step"},
    # shaped drivers: one batched device_get for all S x 19 shape
    # scalars / force rows (separate np.asarray pulls each paid a
    # blocking transfer — PR 3)
    "shapes_host.py": {"ShapeHostMixin._sync_shape_scalars",
                       "ShapeHostMixin._record_forces"},
    # flight recorder (ISSUE 18): both scopes are cold paths by
    # construction — _memory_analysis re-lowers at compile time only
    # (a run that compiles nothing never enters it) and flush drains
    # the span ring at shutdown/ring-full; neither runs per step, and
    # the zero-overhead runtime pin (equal device_gets, equal
    # jit_compiles) holds with both armed
    "tracing.py": {"_memory_analysis", "FlightRecorder.flush"},
}


# ---------------------------------------------------------------------------
# leading-dim-agnostic — the contract FleetSim (PR 5), the Pallas
# megakernel (PR 9) and the fleet server (PR 11) silently depend on:
# field operators address the trailing [-2]=y / [-1]=x axes via ``...``
# slicing and negative axis numbers ONLY, so one kernel serves uniform
# [Ny,Nx], member-batched [B,Ny,Nx] and forest-lab [N,2,H,W] operands.
# file -> checked scopes ("*" = whole file). Files/scopes NOT listed
# (e.g. the DCT base solves, forest FAS window images, Pallas kernel
# bodies with fixed block shapes) are 2-D by documented design.
# ---------------------------------------------------------------------------

LEADING_DIM_SCOPES = {
    # the stencil library is the contract's origin: every op was made
    # leading-dim agnostic in PR 5 and the megakernel shares the code
    "ops/stencil.py": ("*",),
    # the MG cycle runs member-batched (one V-cycle over [B, Ny, Nx]);
    # mg_solve is the fused fleet cycle loop; project_correct is the
    # shared epilogue over any leading shape; bicgstab carries the
    # member axis through its Krylov state
    # fft_diag_solve / FFTDiagPlan (ISSUE 20): the FFT-diagonalized
    # direct solve batches B fleet systems through the one transform
    # ([B, Ny, Nx] — trailing-axes rffts, the Thomas scans broadcast
    # the precomputed [n_s, nk] elimination constants over any lead)
    "poisson.py": ("MultigridPreconditioner", "mg_solve",
                   "project_correct", "bicgstab",
                   "fft_diag_solve", "FFTDiagPlan"),
    # host-side wrappers of the fused tier: normalize ANY leading shape
    # to the kernel's flat [L, ...] layout — the flattening itself must
    # not assume a rank (kernel bodies below them see fixed block
    # shapes and are exempt by design). _fused_substage_sharded rides
    # the same flat layout from the shard_map body (ISSUE 16)
    # fused_jacobi_sweeps / fused_block_jacobi_update (ISSUE 19): the
    # strip-smoother wrappers flatten any leading shape to the same
    # [L, ...] layout before dispatch (uniform [ny,nx], fleet
    # [B,ny,nx], forest-lab [B,bs,bs] callers share one executable);
    # fused_mg_down / fused_mg_up (ISSUE 26) ride the same pipeline
    "ops/pallas_kernels.py": ("fused_advect_heun", "fused_lab_rhs",
                              "fused_correction", "_per_member",
                              "_fused_substage_sharded",
                              "fused_jacobi_sweeps", "fused_mg_down",
                              "fused_mg_up", "_strip_pipeline",
                              "fused_block_jacobi_update"),
    # the sharded megakernel wrapper (ISSUE 16): flattens any leading
    # shape before entering shard_map, so fleet spatial pools (L=B) and
    # the solo sharded sim (L=1) share one executable per BC token.
    # _overlap_jacobi_sweeps_strip (ISSUE 19): the halo strip-smoother
    # form behind overlap_jacobi_sweeps' tier switch
    "parallel/shard_halo.py": ("fused_advect_heun_sharded",
                               "_overlap_jacobi_sweeps_strip"),
}


# ---------------------------------------------------------------------------
# donation-safety / retrace-hazard carry no sanctioned-site tables:
# there is never a good reason to feed a numpy buffer into a donated
# jit (the PR-2 heap-corruption class) or an f-string into a static
# operand (the zero-steady-state-recompile discipline FleetServer pins
# at runtime via jit_compiles==0). Exceptional cases use an in-line
# allow comment, so the written reason is auditable next to the code.
# ---------------------------------------------------------------------------
