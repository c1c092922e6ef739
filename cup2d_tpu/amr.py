"""AMR simulation: the reference's adaptive solver on the block forest.

Reproduces the reference's adaptive time loop (`/root/reference/main.cpp`
adapt() 4657-5440 + the hot loop 6576-7290) with the TPU split:

host (numpy, per regrid)         device (jit, per step)
------------------------------   --------------------------------------
tagging decisions + 2:1 sweeps   vorticity + chi tags (lab + kernel)
slot alloc/release, SFC order    WENO5 advection-diffusion RK2 over all
halo gather-table rebuild          blocks at once (per-block h arrays)
window block selection           SDF/udef rasterization into blocks,
                                   chi, integrals, penalization solve,
                                   collisions (ops/obstacle, collision)
                                 prolongation / restriction batches
                                 matrix-free BiCGSTAB on the forest
                                   (makeFlux variable-resolution rows +
                                   block-Jacobi GEMM)

Level interfaces are discretely conservative: the Poisson operator uses
the makeFlux variable-resolution closure and the stencil kernels carry
coarse-fine flux correction (both in flux.py).

Obstacles live on the forest exactly as the reference's ongrid() does
(main.cpp:3991-4630): blocks intersecting a body's bounding box are
selected on the host (padded to a static capacity so the moving body
never retriggers compilation), the device rasterizes SDF/udef per block
at that block's own resolution, chi comes from the combined-SDF lab, and
the chi field drives GradChiOnTmp-style refinement (main.cpp:4631-4656)
so the body is always surrounded by finest-level blocks.

Jitted functions take tables/order/h as arguments, so regrids that
reproduce previously-seen shapes hit the XLA compile cache.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .config import SimConfig
from .flux import apply_flux_corr, build_flux_corr, \
    build_poisson_structured, build_poisson_tables, diffusive_deposits, \
    divergence_deposits, gradient_deposits, poisson_apply_structured
from .forest import Forest
from .halo import _TopoIndex, _bucket, assemble_labs, \
    FastHalo, assemble_labs_ordered, assemble_labs_rows, block_rows, \
    build_face_copy, build_tables, make_fast_tables, pad_tables, \
    rows_of_blocks
from . import native, tracing
from .ops.collision import merged_overlap_integrals, \
    pairwise_collision_update
from .ops.forces import surface_forces_blocks
from .ops.obstacle import (
    chi_from_sdf,
    midline_udef_packed,
    pack_midline,
    pack_polygon_segments,
    penalization_integrals,
    polygon_sdf_seg,
    shape_integrals,
    solve_rigid_momentum,
)
from .ops.stencil import advect_diffuse_rhs, divergence, dt_from_umax, \
    heun_substage, laplacian5, pressure_gradient_update, vorticity
from .poisson import ForestFASCycle, _down2_mean, _up2_bilinear, \
    apply_block_precond_blocks, bicgstab, block_precond_matrix, \
    coarse_neumann_solve_dct, mg_solve
from .shapes_host import ShapeHostMixin


class ObstacleForestFields(NamedTuple):
    """Per-step obstacle state on the forest, in SFC-ordered block layout
    (the reference's per-shape Obstacle blocks + global chi/tmp grids,
    main.cpp:3283-3342). Vector fields are component-first so the shared
    penalization/collision kernels (which index [0]/[1]) apply
    unchanged."""

    chi: jnp.ndarray      # [N, BS, BS] combined (max over shapes)
    sdf: jnp.ndarray      # [N, BS, BS] combined signed distance
    chi_s: jnp.ndarray    # [S, N, BS, BS]
    sdf_s: jnp.ndarray    # [S, N, BS, BS]
    udef_s: jnp.ndarray   # [S, 2, N, BS, BS] de-meaned deformation vel
    com: jnp.ndarray      # [S, 2] chi-corrected centers of mass
    mass: jnp.ndarray     # [S]
    inertia: jnp.ndarray  # [S]


def _tiles_img(entry, rp, bs: int):
    """Paint one level's uniform image from ordered block rows by ONE
    block-row gather (the round-5 structured transfer primitive; see
    _build_coarse_maps). Shared by the two-level preconditioner
    transfers (_coarse_transfers) and the forest FAS hierarchy's
    per-level deposits (_fas_transfers)."""
    own, ownm, _, _ = entry
    nty, ntx = own.shape
    img = rp[own.reshape(-1)] * ownm.reshape(-1)[:, None, None]
    return img.reshape(nty, ntx, bs, bs) \
              .transpose(0, 2, 1, 3) \
              .reshape(nty * bs, ntx * bs)


def _extract_tiles(a, entry, e, bs: int):
    """Adjoint of _tiles_img: gather each active block's tile out of a
    level image and add into the ordered-block accumulator ``e``."""
    own, _, tid, selp = entry
    nty, ntx = own.shape
    tiles = a.reshape(nty, bs, ntx, bs) \
             .transpose(0, 2, 1, 3) \
             .reshape(nty * ntx, bs, bs)
    return e + tiles[tid] * selp[:, None, None]


def _raster_neg(cfg, dtype):
    """The "far outside" SDF sentinel, ONE definition: the sharded
    window raster cannot receive it as an argument (shard_map bodies
    must not close over tracers), so both paths construct it from the
    config through this function and provably agree."""
    return jnp.asarray(-float(cfg.extent), dtype)


def _padded_list(a, cap: int) -> np.ndarray:
    """An index list, -1 padded to its capacity."""
    out = np.full(cap, -1, np.int32)
    out[:len(a)] = a
    return out


def _window_sdf_udef(inp, bs: int, dtype):
    """Evaluate one shape's SDF + deformation velocity over its window
    blocks ([P, BS, BS] / [2, P, BS, BS]) from the window-block origins
    shipped in ``inp`` (PutFishOnBlocks, main.cpp:3774-3990). The ONE
    definition shared by the single-device scatter and the shard-local
    scatter (forest_mesh.ShardedAMRSim._window_raster) — the sharded ==
    single-device equality tests assume bit-identical evaluation.
    Consumes the host-packed segment/midline tables (ops.obstacle.pack_*
    — built in body frame, com already subtracted): the op-level trace
    showed the unpacked form spending ~40% of megastep device time
    staging tiny per-field shape arrays through scratch."""
    ar = jnp.arange(bs, dtype=dtype) + 0.5
    wh = inp["wh"][:, None, None]
    xw = inp["wx0"][:, None, None] + ar[None, None, :] * wh
    yw = inp["wy0"][:, None, None] + ar[None, :, None] * wh
    com = inp["com"]
    px = xw - com[0]
    py = yw - com[1]
    d = polygon_sdf_seg(px, py, inp["seg"])
    ud = midline_udef_packed(px, py, inp["mid"])
    return d, ud


class AMRSim(ShapeHostMixin):
    """Adaptive flow solver on the block forest, with or without
    immersed obstacles (the reference's only mode is 'with')."""

    def __init__(self, cfg: SimConfig, shapes: Optional[Sequence] = None,
                 bc=None):
        self.cfg = cfg
        # Per-face BC tables (bc.py, ISSUE 12) are a UNIFORM-FAMILY
        # contract: the forest's gather-table ghost exchange encodes
        # boundary rows as linear sign-flip expressions (flux.py), with
        # no slot for the inhomogeneous (moving-wall / inflow) or
        # state-dependent (convective outflow) ghosts a non-default
        # table needs — and the DCT-II spectral base solve assumes
        # all-Neumann walls. Refuse loudly instead of silently running
        # free-slip physics under a different label.
        if bc is not None and not bc.is_free_slip:
            raise ValueError(
                f"AMRSim does not support non-free-slip BCTables "
                f"({bc.token}): the forest gather-table ghost rows are "
                "linear sign-flips (free-slip/Neumann only). Run this "
                "case on the uniform family (UniformSim / Simulation / "
                "ShardedUniformSim / FleetSim).")
        self.case: Optional[str] = None  # case-registry tag (cases.py)
        # A/B env gates latched ONCE per sim, matching the
        # ShardedAMRSim._exchange pattern (ADVICE r5): a mid-run env
        # mutation must not silently flip the operator/preconditioner
        # form at the next retrace or regrid
        import os
        self._pois_mode = os.environ.get("CUP2D_POIS", "structured")
        self._twolevel_form = os.environ.get("CUP2D_TWOLEVEL")
        # a typo'd A/B gate must not silently fall back and measure
        # the same form on both arms. "fft" (PR 6): the forest-FFT
        # preconditioned production solve — structured operator +
        # ALWAYS-ON two-level coarse correction in the two-grid "mg2"
        # form (pre-smooth, spectral base-level correction, post-
        # smooth; see _pressure_project) instead of waiting for the
        # iters>15 trigger with the weaker additive form.
        # "fas"/"fas-f" (PR 13, formerly uniform-only): multigrid over
        # the forest's OWN refinement levels as the FULL production
        # solver (poisson.ForestFASCycle under mg_solve — block-Jacobi
        # composite smoothing, per-level window-image ladder, exact
        # DCT-II base solve; fas-f opens every solve base-level-first).
        # Exact/escalation solves keep Krylov as the robustness
        # backstop, exactly like the uniform path.
        # "fftd" (ISSUE 20) is a UNIFORM-FAMILY token: the FFT
        # diagonalization needs a periodic single-level box, and the
        # forest refuses every non-free-slip table above anyway —
        # name the token explicitly so a mixed-process env latch
        # fails with the reason, not a generic typo message.
        if self._pois_mode == "fftd":
            raise ValueError(
                "CUP2D_POIS=fftd is a uniform-family solve (FFT "
                "diagonalization over a periodic single-level box); "
                "AMRSim's forest has no periodic gather-table ghosts "
                "— run periodic cases on UniformSim/FleetSim")
        if self._pois_mode not in ("structured", "tables", "fft",
                                   "fas", "fas-f"):
            raise ValueError(
                f"CUP2D_POIS={self._pois_mode!r}: "
                "expected structured|tables|fft|fas|fas-f")
        if self._twolevel_form not in (None, "additive", "mult", "mg2"):
            raise ValueError(
                f"CUP2D_TWOLEVEL={self._twolevel_form!r}: "
                "expected additive|mult|mg2")
        # fused advection-kernel tier latch (PR 9; same construct-once
        # discipline). The forest's fusable unit is lab -> RHS (flux
        # corrections interleave before the Heun update), served by the
        # block-batched ops/pallas_kernels.fused_lab_rhs. f32 only —
        # Mosaic has no f64. The ADVECTION bf16 storage tier stays a
        # uniform/fleet contract (UniformGrid's CUP2D_PREC read); the
        # SOLVER-side read below is the forest's one sanctioned
        # CUP2D_PREC site and touches only the FAS cycle legs.
        self._kernel_tier = "xla"
        if os.environ.get("CUP2D_PALLAS", "") == "1":
            from .ops.pallas_kernels import lab_tier_supported
            if lab_tier_supported(cfg.dtype):
                self._kernel_tier = "pallas-fused"
        # Memory-tiered FAS (ISSUE 19): CUP2D_PREC=bf16 composes with
        # the fas latch as a bf16-storage/f32-accumulate tier on the
        # ForestFASCycle window-image ladder legs ONLY — mg_solve's
        # outer loop keeps the f32 true residual, the block composite
        # smoother and the DCT-II base solve stay at solver precision
        # (bf16 floors a FULL solver at ~2e-4 rel). Any
        # other composition refuses loudly: the forest has no bf16
        # advection tier to fall back to, so an un-routed latch would
        # silently run f32 under a bf16 label.
        prec = os.environ.get("CUP2D_PREC", "") or "f32"
        if prec not in ("f32", "bf16"):
            raise ValueError(
                f"CUP2D_PREC={prec!r}: expected f32|bf16")
        self._fas_leg_dtype = None
        if prec == "bf16":
            if self._pois_mode not in ("fas", "fas-f"):
                raise ValueError(
                    "CUP2D_PREC=bf16 on the forest selects the "
                    "bf16-leg FAS tier and requires CUP2D_POIS=fas|"
                    f"fas-f (got CUP2D_POIS={self._pois_mode!r}): "
                    "the forest has no bf16 advection tier, so the "
                    "latch would otherwise relabel an f32 run.")
            if jnp.dtype(cfg.dtype) != jnp.float32:
                raise ValueError(
                    "CUP2D_PREC=bf16 needs f32 solver state (got "
                    f"{jnp.dtype(cfg.dtype).name}): the bf16 legs "
                    "accumulate in f32; an f64 outer loop would cast "
                    "through f32 silently.")
            self._fas_leg_dtype = jnp.bfloat16
        if shapes is None:
            from .sim import make_shapes
            shapes = make_shapes(cfg)
        self.shapes = list(shapes)
        self.forest = Forest(cfg)
        self.forest.add_field("vel", 2)
        self.forest.add_field("pres", 1)
        if self.shapes:
            self.forest.add_field("chi", 1)
        self.time = 0.0
        self.step_count = 0
        self.p_inv = jnp.asarray(
            block_precond_matrix(cfg.bs), dtype=self.forest.dtype)
        # f64 Krylov-scalar accumulation for f32 fields (same rationale
        # as UniformGrid, uniform.py)
        self.sum_dtype = (
            jnp.float64
            if (self.forest.dtype == jnp.float32
                and jax.config.jax_enable_x64)
            else None
        )
        self._tables_version = -1
        self._tables = {}
        self._order = None
        # SFC-ordered compact working state ([n_pad, dim, BS, BS] per
        # field) — the device-resident truth between regrids. The
        # slot-layout fields dict is synced lazily (sync_fields); _ord_key
        # tracks (topology version, fields write-version) so external
        # slot writes invalidate the cache (forest._FieldsDict.wver)
        self._ord = None
        self._ord_key = None
        self._ord_dirty = False
        self._wcap = [16] * len(self.shapes)
        # the force pass's block lists (_shape_inputs): sticky
        # capacities, rows selected at the last build, growths since
        # construction
        self._fcap = [16] * len(self.shapes)
        self._frcap = [512] * len(self.shapes)   # their g=4 table rows
        self._frow_index = None                  # (_finalize_tables)
        self._force_blocks = None
        self._fcap_growths = 0
        # sticky block-axis padding (see _refresh_impl) and, beside it,
        # the sticky row capacities of every table set (_sticky_caps)
        self._npad_hwm = 128
        self._npad_floor = 128    # reserve_blocks raises this
        self._npad_quiet = 0
        self._tcap = {}     # set -> its capacities
        self._tneed = {}    # set -> the live counts of its last build
        self.compute_forces_every = 1   # 0 disables the diagnostics pass
        self.force_log = None           # file-like, CSV rows
        # cumulative regrid activity + shard comm-volume stats for the
        # telemetry stream (profiling.MetricsRecorder reports per-step
        # deltas; _comm_stats is populated by ShardedAMRSim)
        self._n_refined = 0
        self._n_coarsened = 0
        self._comm_stats = None
        # jitted ONCE; tables/order/h are arguments, so regrids that
        # reproduce previously-seen shapes hit the XLA compile cache
        self._step_jit = tracing.named_jit(
            "amr.step", jax.jit(
                self._step_impl, static_argnames=("exact_poisson",)),
            variant=("exact_poisson",))
        self._mega_jit = tracing.named_jit(
            "amr.megastep", jax.jit(
                self._megastep_impl,
                static_argnames=("exact_poisson", "with_forces")),
            variant=("exact_poisson",))
        self._next_dt = None
        self._next_dt_version = -1
        self._dt_jit = None
        self._umax_jit = None
        self._next_umax = None   # survives regrids (see step_once)
        self._next_umax_version = -1
        # production two-level trigger (VERDICT r3 #9): when the last
        # production solve burned > 15 iterations (block-Jacobi's
        # block-count scaling law on near-uniform forests — ~200/step
        # at 1e4 blocks, r4 scale trace), engage the coarse correction
        # and keep it until the next topology change. _last_iters rides
        # host pulls that already happen (the megastep scalar pull /
        # the obstacle-free dt float) — no extra round trip.
        self._last_iters = 0
        self._last_iters_dev = None
        self._coarse_on = False
        # StepGuard's escalation rung forces the exact (tol-0 + coarse
        # correction) Poisson solve on a retried step (resilience.py)
        self._force_exact = False
        # lagged-verdict mode (resilience.StepGuard, lag=True): the
        # obstacle-free branch derives dt on DEVICE from the cached
        # end-state umax, keeps the diag (incl. the dt used) on device
        # and leaves clock settlement + the iters-trigger drain to the
        # guard's lagged pull — zero blocking host syncs per steady
        # step. The former side effect (the two-level iters>15 trigger
        # seeing the count one step LATE) is closed by the guard's
        # trigger-freshness window (resilience.StepGuard.step, PR 6):
        # while the trigger is re-armed-but-off the in-flight verdict
        # resolves BEFORE the next dispatch, so engagement lands at
        # the same step as the eager path. The shaped branch ignores
        # the flag (its uvw/CoM pull feeds the host kinematics).
        self.async_diag = False
        self._raster_jit = tracing.named_jit(
            "amr.rasterize", jax.jit(self._rasterize_impl))
        self._vorticity_jit = tracing.named_jit(
            "amr.vorticity", jax.jit(self._vorticity_impl))
        self._tags_jit = tracing.named_jit(
            "amr.tags", jax.jit(self._tags_impl))
        # fields are dead after _apply_regrid replaces them — donate so
        # XLA aliases the buffers instead of holding old + new field
        # sets live at once during the fused regrid dispatch
        self._regrid_jit = tracing.named_jit(
            "amr.regrid", jax.jit(
                self._regrid_apply_impl, donate_argnums=0))
        # not donated: a caller may still hold the slot arrays it read
        self._sync_jit = tracing.named_jit(
            "amr.sync", jax.jit(self._flush_impl))

    def reserve_blocks(self, n: int):
        """Pre-size the padded block axis so every jitted executable
        compiles once for a bucket that already fits ``n`` active blocks
        (call before initialize(); the init climb then never crosses a
        bucket). Padding above the reserve still grows automatically."""
        self._npad_floor = max(
            self._npad_floor, 1 << max(0, int(n)).bit_length())
        self._npad_hwm = max(self._npad_hwm, self._npad_floor)

    # ------------------------------------------------------------------
    # topology-dependent cached state
    # ------------------------------------------------------------------
    def _refresh(self):
        f = self.forest
        if self._tables_version == f.version:
            return
        with tracing.span("tables", step=int(self.step_count)):
            self._refresh_impl()

    def _block_axis(self):
        """The ordered block axis of the forest as it stands: sets
        ``_order``, ``_n_real`` and ``_mask``; returns the padded row
        count and the step's and the flush's padded block indices."""
        f = self.forest
        self._order = f.order()
        n_real = len(self._order)
        # block axis padded to power-of-two buckets so a regrid that
        # changes n_active reuses the compiled step (SURVEY §7: padded
        # capacity + masking discipline; the r1 per-count retrace made
        # every regrid recompile a Krylov loop). Strictly > n_real so
        # pad-row lab/cell slots exist as dead scatter targets for the
        # shape-stable table padding (halo.pad_tables). Pad rows point
        # at an inactive slot: gathers see stale-but-finite data that
        # the mask zeroes, scatters write garbage only to that slot.
        #
        # The bucket is a sticky HIGH-WATER MARK, not the instantaneous
        # bucket: the levelMax init climb starts from the full uniform
        # levelStart grid and compresses the background away, so the
        # instantaneous bucket would cross several powers of two
        # downward — each crossing a full executable-set recompile.
        # Keeping the peak bucket trades masked-out compute for compile
        # reuse; if the forest stays a quarter of the bucket for 10
        # consecutive rebuilds (a genuinely decayed run, not a
        # transient), the bucket steps down one power of two.
        #
        # Every OTHER dimension a program of the regrid cycle (step,
        # tags, regrid, flush) sees is sticky the same way, so that a
        # run compiles each of them once: the two row counts and the
        # interpolation width of each halo set and the flux-correction
        # rows (``_tcap``, _sticky_caps — handed to halo.pad_tables and
        # flux.build_flux_corr as their ``caps``), the raster windows
        # and force lists (``_wcap``, ``_fcap``, ``_frcap``), the
        # regrid's refine/compress rows (_apply_regrid) and the flush
        # index (``sync_p`` below). The table capacities start over
        # only when the block bucket itself steps down.
        n_bucket = max(128, 1 << n_real.bit_length())
        if n_bucket >= self._npad_hwm:
            self._npad_hwm = n_bucket
            self._npad_quiet = 0
        elif 4 * n_bucket <= self._npad_hwm \
                and self._npad_hwm > self._npad_floor:
            self._npad_quiet += 1
            if self._npad_quiet >= 10:
                self._npad_hwm //= 2
                self._npad_quiet = 0
                self._tcap.clear()
        else:
            self._npad_quiet = 0
        n_pad = self._npad_hwm
        if not f._free:
            f._grow()
        pad_slot = f._free[-1]
        order_p = np.concatenate([
            self._order,
            np.full(n_pad - n_real, pad_slot, np.int32)])
        self._n_real = n_real
        self._mask = np.arange(n_pad) < n_real
        # the flush's index (_flush_impl): the same rows, but its pad
        # rows point past every slot capacity and are DROPPED, so the
        # flush is one shape per (n_pad, capacity) like everything else
        # on the block axis and leaves every inactive slot as it was
        sync_p = np.where(self._mask, order_p, np.iinfo(np.int32).max)
        return n_pad, order_p, sync_p

    def _refresh_impl(self):
        # the rebuild's parts as spans nested in ``tables`` (tracing.SPANS;
        # the benchmark's tables_halo_ms and tables_flux_ms read them)
        f = self.forest
        with tracing.span("tables.topo"):
            n_pad, order_p, sync_p = self._block_axis()
            n_real = self._n_real
            # one dense topology index shared by all 6-8 table builds
            topo = _TopoIndex(f, self._order)
        # (set, g, tensorial, dim): chi tagging (g=4 scalar) and the
        # forces (g=4 vector) only where there are shapes
        sets = [("vec3", 3, True, 2), ("vec1", 1, False, 2),
                ("sca1", 1, False, 1), ("vec1t", 1, True, 2),
                ("sca1t", 1, True, 1)]
        if self.shapes:
            sets += [("sca4t", 4, True, 1), ("vec4t", 4, True, 2)]
        raw = {}
        for name, g, tensorial, dim in sets:
            with tracing.span("tables.halo", set=name):
                raw[name] = build_tables(f, self._order, g, tensorial,
                                         dim, topo=topo)
        with tracing.span("tables.faces"):
            fc = build_face_copy(f, self._order, n_pad, topo)
        # one async transfer for every table leaf (pad_tables returns
        # numpy on purpose; per-leaf jnp.asarray would synchronize per
        # array, one host sync per leaf on every regrid)
        with tracing.span("tables.pad"):
            self._tables = self._finalize_tables(raw, n_pad, fc)
        # makeFlux variable-resolution Poisson operator (flux.py):
        # structured per-face form on a single device; the sharded
        # subclass overrides with the lab-table + ppermute-exchange
        # form (_build_pois)
        with tracing.span("tables.flux"):
            self._tables["pois"] = self._build_pois(topo, n_pad)
            self._corr = self._finalize_corr(topo, n_pad)
        # two-level preconditioner maps: every cell's coarse cell on
        # the uniform level-c grid + its area weight (cells coarser
        # than c deposit into the coarse cell under their center —
        # approximate, but it is only a preconditioner). Built
        # vectorized and passed through the jit boundary as arguments.
        # Startup (steps < 10) always consumes them; production builds
        # them LAZILY on the iters>15 trigger (_use_coarse) — the
        # [cells, 4] arrays are ~50 MB at 1e4-block pads, dead regrid
        # latency for the compressed forests that never trigger.
        # Topology changed: the trigger re-arms from scratch — including
        # the iteration-count evidence, which described the OLD forest
        # (a stale 400-iteration count from a pre-compression topology
        # must not engage the correction on the new one)
        self._coarse_on = False
        self._last_iters = 0
        self._last_iters_dev = None
        if self.step_count >= 10:
            self._coarse_cw = None
        else:
            with tracing.span("tables.coarse"):
                self._build_coarse_maps(n_pad, n_real)
        with tracing.span("tables.geom"):
            self._put_geometry(n_pad, n_real, order_p, sync_p)
        self._tables_version = f.version

    def _put_geometry(self, n_pad: int, n_real: int, order_p, sync_p):
        """The per-block geometry the step programs take, on the device:
        spacings, the mask, the ordered and flush indices, cell centres."""
        f = self.forest
        h = f.h_per_block(self._order)
        hp = np.concatenate([h, np.ones(n_pad - n_real)])
        hsqp = np.concatenate([h * h, np.zeros(n_pad - n_real)])
        # shape on the HOST (numpy reshapes), transfer once: the eager
        # [:, None] slicing of device arrays compiled a one-op
        # executable per distinct shape — 38 of the 62 warm-init
        # executables were such one-op jits (init_compiles probe),
        # each paying a compile and a dispatch of its own
        fdt = np.dtype(jnp.dtype(f.dtype).name)
        self._h = jnp.asarray(hp.reshape(-1, 1, 1, 1).astype(fdt))
        self._h3 = jnp.asarray(hp.reshape(-1, 1, 1).astype(fdt))
        self._hflat = jnp.asarray(hp.astype(fdt))
        self._hsq_flat = jnp.asarray(hsqp.reshape(-1, 1, 1).astype(fdt))
        self._maskv = jnp.asarray(
            self._mask.reshape(-1, 1, 1, 1).astype(fdt))
        self._order_j = jnp.asarray(order_p)
        self._sync_j = jnp.asarray(sync_p)
        # cell centers per active block (device, for obstacle kernels)
        bs = f.bs
        ar = np.arange(bs) + 0.5
        x0 = f.bi[self._order].astype(np.float64) * bs * h
        y0 = f.bj[self._order].astype(np.float64) * bs * h
        xc = np.zeros((n_pad, bs, bs))
        yc = np.zeros((n_pad, bs, bs))
        xc[:n_real] = x0[:, None, None] + ar[None, None, :] * h[:, None, None]
        yc[:n_real] = y0[:, None, None] + ar[None, :, None] * h[:, None, None]
        self._xc = jnp.asarray(xc, f.dtype)
        self._yc = jnp.asarray(yc, f.dtype)

    def _build_coarse_maps(self, n_pad: int, n_real: int):
        """Host build of the two-level transfer structure (see
        _refresh_impl).

        Round-5 re-design: the round-3/4 form was a generic per-cell
        map ([cells, 4] bilinear indices + weights applied as one
        scatter-add deposit and one gather interpolation). On TPU that
        lowering is the adaptive path's single worst cost: the r5 op
        trace of the 1e4-block probe showed ~36 ms PER 4.2M-row
        scatter-add and ~30 ms per gather — ~630 ms of every 1163 ms
        step inside the Krylov loop. The replacement is structured:
        blocks are tile-aligned at their own level by construction, so
        each level's blocks paint a uniform level-l image via ONE
        block-row gather (embedding-style, 256 B rows), images walk to
        the coarse level by 2x2 mean / bilinear 2x ladder steps (pure
        reshape/slice arithmetic at full lane utilization), and the
        per-level tile extraction on the way back is again one
        block-row gather. No per-cell indices exist anywhere.

        The pytree is a dict keyed by active level, so the jit
        executable is keyed on the LEVEL SET (changes rarely, and only
        at regrids) instead of per-cell map contents.

        Levels FINER than the coarse level (l > c, the O(4^l) cells)
        are CROPPED to one shared active-tile bounding-box window
        (``levf`` + the dynamic ``crop`` origin): a deep refinement
        spot no longer paints a full-domain image at its own
        resolution per M application (the former ROADMAP cliff). The
        window is the union of the fine levels' tile bboxes in
        coarse-cell units, padded by 2 coarse cells (the bilinear
        up-ladder's influence radius is < 2, so every ACTIVE cell's
        dependence set stays inside the window and the cropped
        transfers are BIT-IDENTICAL to the full-domain form —
        tests/test_amr.py::test_two_level_crop_matches_full_domain)
        and snapped to an alignment grid that keeps every fine level's
        window tile-aligned. The window ORIGIN crosses the jit
        boundary as an int32 array (lax.dynamic_slice), so a regrid
        that moves the active spot without resizing the window reuses
        the compiled step. Levels <= c keep full-domain images — they
        are at most coarse-image-sized."""
        import math
        f = self.forest
        c = self._coarse_level = max(0, min(3, f.cfg.level_max - 1))
        bs_ = f.bs
        ncx = f.cfg.bpdx * bs_ << c
        ncy = f.cfg.bpdy * bs_ << c
        self._coarse_shape = (ncy, ncx)
        self._coarse_h2 = float(f.cfg.h_at(c)) ** 2
        fdt = jnp.dtype(f.dtype).name
        lvo = f.level[self._order].astype(np.int64)
        bio = f.bi[self._order].astype(np.int64)
        bjo = f.bj[self._order].astype(np.int64)
        active = sorted(int(v) for v in np.unique(lvo))
        # shared coarse-cell window over the fine levels' active tiles
        fine_act = [l for l in active if l > c]
        crop = None
        if fine_act:
            align = 1
            for l in fine_act:
                align = math.lcm(
                    align, bs_ // math.gcd(bs_, 1 << (l - c)))
            cj0 = ci0 = 1 << 30
            cj1 = ci1 = -1
            for l in fine_act:
                sel = lvo == l
                den = 1 << (l - c)       # level-l cells per coarse cell
                cj0 = min(cj0, int(bjo[sel].min()) * bs_ // den)
                ci0 = min(ci0, int(bio[sel].min()) * bs_ // den)
                cj1 = max(cj1, -(-(int(bjo[sel].max()) + 1) * bs_ // den))
                ci1 = max(ci1, -(-(int(bio[sel].max()) + 1) * bs_ // den))
            # 2-coarse-cell margin: the bilinear chain's dependence
            # reach (see docstring); snap outward to the alignment grid
            # (domain dims are multiples of it, so clamping is safe)
            cj0 = max(0, cj0 - 2) // align * align
            ci0 = max(0, ci0 - 2) // align * align
            cj1 = -(-min(ncy, cj1 + 2) // align) * align
            ci1 = -(-min(ncx, ci1 + 2) // align) * align
            crop = (cj0, cj1, ci0, ci1)
        per_level = {}
        fine = {}
        for l in active:
            ntx = f.cfg.bpdx << l
            nty = f.cfg.bpdy << l
            sel = lvo == l
            if not np.any(sel):
                # empty ladder level: never emit an entry — the
                # _deposit/_interp chains in _pressure_project bound
                # their image ladders by min/max of THESE dicts, so an
                # empty level above the finest active one would force
                # needless ladder steps (ADVICE r5). np.unique of the
                # active levels cannot produce one today; this guard
                # keeps the invariant explicit for future callers.
                continue
            if l <= c:
                tix = bjo[sel] * ntx + bio[sel]
                # tiles owned by no level-l block gather the first pad
                # row (index n_real points into the pad range:
                # n_pad > n_real) and are zeroed by ownm — pad-row
                # data is stale, not NaN
                own = np.full(nty * ntx, n_real, np.int32)
                own[tix] = np.nonzero(sel)[0].astype(np.int32)
                ownm = np.zeros(nty * ntx, fdt)
                ownm[tix] = 1.0
                tid = np.zeros(n_pad, np.int32)
                tid[:n_real][sel] = tix.astype(np.int32)
                selp = np.zeros(n_pad, fdt)
                selp[:n_real][sel] = 1.0
                per_level[l] = (own.reshape(nty, ntx),
                                ownm.reshape(nty, ntx), tid, selp)
            else:
                cj0, cj1, ci0, ci1 = crop
                sc = 1 << (l - c)
                tj0 = cj0 * sc // bs_
                ti0 = ci0 * sc // bs_
                ntyw = (cj1 - cj0) * sc // bs_
                ntxw = (ci1 - ci0) * sc // bs_
                tjr = bjo[sel] - tj0
                tir = bio[sel] - ti0
                tix = tjr * ntxw + tir
                own = np.full(ntyw * ntxw, n_real, np.int32)
                own[tix] = np.nonzero(sel)[0].astype(np.int32)
                ownm = np.zeros(ntyw * ntxw, fdt)
                ownm[tix] = 1.0
                tid = np.zeros(n_pad, np.int32)
                tid[:n_real][sel] = tix.astype(np.int32)
                selp = np.zeros(n_pad, fdt)
                selp[:n_real][sel] = 1.0
                fine[l] = (own.reshape(ntyw, ntxw),
                           ownm.reshape(ntyw, ntxw), tid, selp)
        from .poisson import dct_neumann_operators
        cw = {
            "lev": per_level,
            "dct": dct_neumann_operators(ncy, ncx, dtype=fdt),
        }
        if fine:
            cw["levf"] = fine
            # window ORIGIN (coarse cells) — dynamic, so same-shape
            # windows at different spots share one executable
            cw["crop"] = np.asarray([crop[0], crop[2]], np.int32)
        self._coarse_cw = jax.device_put(cw)


    # the hot-loop table sets that take the same-level face-copy fast
    # path (halo.make_fast_tables); vec1t/sca1t are regrid-only and
    # stay plain. Non-tensorial g=1 sets never fill lab corners, so
    # their paint is face-only.
    _FAST_SETS = {"vec3": True, "vec1": False, "sca1": False,
                  "sca4t": True, "vec4t": True}

    def _sticky_caps(self, name: str, need: tuple) -> tuple:
        """The ``caps`` rule this sim hands to halo.pad_tables and
        flux.build_flux_corr for table set ``name``: one sticky
        capacity per dimension of ``need`` — (simple rows,
        interpolation rows, interpolation width K) of a halo set,
        (rows,) of the flux correction — a high-water mark like the
        block axis' ``_npad_hwm``, so that a table's shape is the same
        after every rebuild. A row count above its capacity raises it
        to the bucket of 1.3 x the need (the rule of ``_fcap`` /
        ``_frcap``; power-of-two buckets also let the runs of one
        configuration share executables whatever their seed) and, past
        the first build, says so: each growth is a new variant of every
        program that takes the set. K is a stencil's width, not a
        count that wanders: its mark is rounded up to a multiple of 8
        and given no headroom — a lab assembly costs 3 ns a (row x K)
        element on the chip, pad or live (PERF.md section 6, PR 32)."""
        rows, old = need[:2], self._tcap.get(name, (0,) * len(need))
        new = tuple(c if 0 < c >= n else _bucket(int(1.3 * n))
                    for n, c in zip(rows, old)) \
            + tuple(max(c, -(-k // 8) * 8)
                    for k, c in zip(need[2:], old[2:]))
        self._tneed[name] = need
        if new != old:
            self._tcap[name] = new
            if any(old):
                from .resilience import record_event
                dims = ("gs", "gg", "k") if len(need) == 3 else ("m",)
                for d, n, o, c in zip(dims, need, old, new):
                    if c != o:
                        record_event(event="table_cap_grow",
                                     step=int(self.step_count), set=name,
                                     dim=d, need=n, old=o, new=c)
        return new

    def _reserve_table_rows(self, factor: int):
        """Raise the sticky row capacities to the buckets of ``factor``
        times the rows of the tables as built last (the interpolation
        width is a stencil's, not a count: left alone); the next
        _refresh() pads to them. initialize() calls it on the climbed
        forest."""
        for name, need in self._tneed.items():
            cap = self._tcap[name]
            self._tcap[name] = tuple(
                max(c, _bucket(factor * n))
                for n, c in zip(need[:2], cap)) + cap[2:]
        self._tables_version = -1

    # table placement hooks (ShardedAMRSim splits the hot-loop sets
    # into per-device rows + a surface-exchange plan)
    def _finalize_tables(self, raw: dict, n_pad: int, fc=None) -> dict:
        out = {}
        for k, t in raw.items():
            caps = functools.partial(self._sticky_caps, k)
            if fc is not None and k in self._FAST_SETS:
                out[k] = make_fast_tables(t, fc[0], fc[1], n_pad,
                                          corners=self._FAST_SETS[k],
                                          caps=caps)
            else:
                out[k] = pad_tables(t, n_pad, caps)
        self._frow_index = self._force_row_index(out)
        return jax.device_put(out)

    def _force_row_index(self, tables: dict):
        """Host index of the g=4 sets' table rows by block (simple rows,
        interpolation rows), from which _shape_inputs lists the rows of
        a body's blocks so that the force pass assembles their labs
        alone. One index serves the vector and the scalar set — the two
        are built alike and differ in signs and weights only; None
        (the pass then assembles all N labs and takes its rows) if they
        ever do not, or without the single-device fast form."""
        tv, ts = tables.get("vec4t"), tables.get("sca4t")
        if not (isinstance(tv, FastHalo) and isinstance(ts, FastHalo)):
            return None
        names = ("dest_s", "src_ord", "dest", "idx_ord")
        if not all(np.array_equal(getattr(tv.t, a), getattr(ts.t, a))
                   for a in names):
            return None
        L2 = tv.t.L * tv.t.L
        return (block_rows(tv.t.dest_s, L2, self._n_real),
                block_rows(tv.t.dest, L2, self._n_real))

    def _build_pois(self, topo, n_pad: int):
        """Poisson operator build hook: the structured per-face form
        (build_poisson_structured) on a single device — its 2 block-row
        gathers per face replace the lab scatter whose TPU lowering
        serialized inside the Krylov loop (r5 trace). The sharded
        subclass overrides with per-device rows behind the ppermute
        surface-exchange plan. CUP2D_POIS=tables (latched in __init__)
        forces the table form for A/B measurements."""
        if self._pois_mode == "tables":
            t = build_poisson_tables(self.forest, self._order, topo=topo)
            return jax.device_put(pad_tables(
                t, n_pad, functools.partial(self._sticky_caps, "pois")))
        return jax.device_put(build_poisson_structured(
            self.forest, self._order, n_pad, topo=topo))

    def _finalize_corr(self, topo, n_pad: int):
        return build_flux_corr(
            self.forest, self._order, n_pad=n_pad, topo=topo,
            caps=functools.partial(self._sticky_caps, "corr"))

    # ------------------------------------------------------------------
    # ordered working state
    # ------------------------------------------------------------------
    # The reference's hot loop reads/writes blocks through per-rank
    # `infos` vectors kept in SFC order (main.cpp:1550-1562); the slot
    # map is bookkeeping. Same inversion here: between regrids the
    # device state IS the ordered compact array set, so no step pays a
    # slot<->ordered permutation (under a device mesh that permutation
    # is a volume-sized collective; the ordered arrays are sharded in
    # contiguous SFC ranges exactly like the reference's rank ranges).
    def _ordered_state(self) -> dict:
        f = self.forest
        self._refresh()
        key = (f.version, f.fields.wver)
        if self._ord_key == key and self._ord is not None:
            # (_ord None with a matching key = checkpoint restore just
            # re-anchored the wver trail; fall through and rebuild)
            return self._ord
        if self._ord_dirty:
            # a hard error (not an assert: must survive python -O) —
            # rebuilding from the stale slot arrays here would silently
            # discard the last completed step's fields
            raise RuntimeError(
                "slot fields were written while the ordered working "
                "state held newer data; call sync_fields() before "
                "writing forest.fields")
        if self._ord_key is not None and self._ord_key[0] == f.version \
                and self._ord_key != key:
            # same topology but the fields dict was rewritten
            # externally (wver moved): the cached end-state umax/dt
            # describe the overwritten field — drop them (a regrid, by
            # contrast, keeps them for the 1.05-guarded branch). The
            # key-inequality guard matters: a checkpoint restore lands
            # here with _ord=None and an UNmoved key, and must keep its
            # restored dt cache (the restart takes the same dt branch
            # as the uninterrupted run).
            self._next_dt = None
            self._next_umax = None
        self._ord = {name: self._put_ordered(fld[self._order_j])
                     for name, fld in f.fields.items()}
        self._ord_key = key
        return self._ord

    def _put_ordered(self, x):
        """Placement hook: ShardedAMRSim pins the ordered block axis to
        the device mesh here."""
        return x

    def sync_fields(self):
        """Write the ordered working state back into the slot-layout
        fields dict (regrid prolongation, dumps, checkpoints and tests
        read slots). No-op when already in sync."""
        if not self._ord_dirty:
            return
        f = self.forest
        f.fields.update(
            self._sync_jit(dict(f.fields), self._ord, self._sync_j))
        self._ord_key = (f.version, f.fields.wver)
        self._ord_dirty = False

    @staticmethod
    def _flush_impl(fields, ordf, sync):
        """THE flush (sync_fields' program, and the head of the regrid's):
        every ordered array [n_pad, dim, BS, BS] scattered to its slots
        through the padded index ``sync`` [n_pad] (_refresh_impl). No
        shape holds the live block count — a flush sliced to it
        compiled eleven one-op programs at every regrid, 0.6 s on the
        chip, each too small for the persistent cache. The pad rows are
        out of range and dropped: no inactive slot is written."""
        return {**fields, **{
            name: fields[name].at[sync].set(x, mode="drop")
            for name, x in ordf.items()}}

    def fields(self) -> dict:
        """Slot-layout fields, guaranteed current.

        The supported read path for external/analysis consumers: syncs
        the ordered working state back into ``forest.fields`` first, so
        a reader can never observe pre-step data (reading
        ``forest.fields`` directly between steps silently returns the
        state as of the last sync — ADVICE r3)."""
        self.sync_fields()
        return self.forest.fields

    def _set_ordered(self, **updates):
        """Adopt step outputs as the new ordered truth."""
        self._ord = {**self._ord, **updates}
        self._ord_dirty = True

    @staticmethod
    def _pull_blockwise(x) -> np.ndarray:
        """Pull a block-axis-sharded device array to host numpy.

        Multi-host pods can't np.asarray a sharded global array (shards
        live on other processes) — every process must reach the SAME
        host-side regrid decision from the SAME full tag vector (the
        reference's update_boundary contract, main.cpp:1850-1970), so
        the pull becomes an all-gather across processes there. Scalar
        diagnostics stay plain device_get (reduction outputs are fully
        replicated)."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            return np.asarray(
                multihost_utils.process_allgather(x, tiled=True))
        return np.asarray(x)

    # ------------------------------------------------------------------
    # shared device stages
    # ------------------------------------------------------------------
    def _advect_rk2(self, vel, h, dt, t3, corr, maskv):
        """Heun RK2 advection-diffusion (per-block h); diffusive face
        fluxes flux-corrected at level interfaces (fillcases after each
        stage, main.cpp:6607-6642). ``vel`` and the result are ordered
        compact [N,2,BS,BS]. ``maskv`` zeroes the padded rows each stage
        (pad-row data is stale, never NaN — see _refresh)."""
        cfg = self.cfg
        ih2 = 1.0 / (h * h)
        vold = vel * maskv               # [N,2,BS,BS]
        v = vold
        for k, c in enumerate((0.5, 1.0)):
            with tracing.scope(f"advect/substage{k}"):
                lab = assemble_labs_ordered(v if c == 1.0 else vel, t3)
                if self._kernel_tier != "xla":
                    # forest-block-batched fused RHS: one HBM read of the
                    # lab batch per stage, per-block h rides the kernel's
                    # (afac, dfac) scale rows
                    from .ops.pallas_kernels import fused_lab_rhs
                    rhs = fused_lab_rhs(lab, h, cfg.nu, dt)
                else:
                    rhs = advect_diffuse_rhs(lab, 3, h, cfg.nu, dt)
                rhs = apply_flux_corr(
                    rhs, diffusive_deposits(lab, 3, cfg.nu * dt), corr)
                v = heun_substage(vold, c, rhs, ih2) * maskv
        return v

    def _pressure_project(self, v, pres, dt, h, hsq,
                          t1v, t1s, tpois, corr, tcoarse,
                          exact_poisson, maskv,
                          chi=None, udef_b=None):
        """deltap Poisson solve + projection (main.cpp:7007-7187). The
        RHS divergence is flux-corrected; the operator (also applied to
        the initial guess p_old) is the makeFlux variable-resolution
        closure — conservative on both sides of every interface.
        ``chi``/``udef_b`` add the -chi div(u_def) obstacle term.
        All operands ordered compact; returns
        (v_new, p_new, res, div_linf)."""
        cfg = self.cfg
        ih2 = 1.0 / (h * h)
        with tracing.scope("poisson_rhs"):
            pord = pres[:, 0] * maskv[:, 0]          # [N,BS,BS]
            vlab = assemble_labs_ordered(v, t1v)
            fac = 0.5 * h[:, 0] / dt
            b = fac * divergence(vlab, 1)
            ulab = None
            if udef_b is not None:
                ulab = assemble_labs_ordered(udef_b, t1v)
                b = b - fac * chi * divergence(ulab, 1)
            b = apply_flux_corr(
                b, divergence_deposits(vlab, ulab, chi, fac[:, 0, 0]), corr)
            # physics invariant for the telemetry watchdog: max |∇·u| of
            # the pre-projection velocity, read off the (flux-corrected)
            # Poisson RHS the step already forms — |b| = fac * |undivided
            # div| with fac = h/2dt, physical div = undivided/(2h), so the
            # rescale is dt/h^2 per block. Zero extra lab assemblies (an
            # honest post-projection divergence would cost one more halo
            # exchange per step under the sharded mesh). Pad rows carry
            # stale-but-finite lab data — masked.
            div_linf = jnp.max(
                jnp.abs(b) * maskv[:, 0] * (dt / (h[:, 0] * h[:, 0])))

        if hasattr(tpois, "nba"):
            # structured per-face operator (flux.poisson_apply_structured)
            def A(x):
                return poisson_apply_structured(x, tpois)
        else:
            def A(x):
                lab = assemble_labs_ordered(x[:, None], tpois)
                return laplacian5(lab, 1)[:, 0]

        # initial-guess subtraction via A itself (the reference uses the
        # lab Laplacian + flux correction, pressure_rhs1; using A keeps
        # A(dp + p_old) = div-rhs exactly)
        with tracing.scope("poisson_rhs"):
            b = b - A(pord)

        smooth = tracing.scoped("mg_smooth", apply_block_precond_blocks)
        coarse = tracing.scoped("mg_coarse", coarse_neumann_solve_dct)

        def M(r):
            return smooth(r, self.p_inv)

        if tcoarse is not None:
            # two-level preconditioner (VERDICT r2 #6): block-Jacobi
            # leaves the global pressure modes to the Krylov iteration
            # (hundreds of iterations on a cold RHS); a coarse
            # uniform-grid correction (exact Neumann solve) deflates
            # them multiplicatively. Used for the cold startup solves
            # and, since round 4, for PRODUCTION solves behind the
            # driver's iters>15 trigger (step_once). Round-5 re-design
            # of the transfers: per-level images painted by block-row
            # gathers + 2x mean/bilinear ladder steps, and a DCT-matmul
            # coarse solve — the r4 per-cell scatter/gather maps and
            # the FFT's operand staging were ~630 of 1163 ms/step at
            # 1e4 blocks (r5 trace; see _build_coarse_maps).
            dctops = tcoarse["dct"]
            ncy, ncx = self._coarse_shape
            cih2 = jnp.where(hsq > 0,
                             1.0 / jnp.where(hsq > 0, hsq, 1.0), 0.0)
            _deposit, _interp = (
                tracing.scoped("mg_transfer", f)
                for f in self._coarse_transfers(tcoarse))

            # form selection: PRODUCTION solves use the ADDITIVE
            # two-level (coarse correction + block-Jacobi on the same
            # residual — no embedded A-apply). It saves 2 A-applies
            # per iteration at an unchanged iteration count (8 at 1e4
            # blocks); what that buys on the current chip is not
            # measured.
            # STARTUP (exact) solves keep the multiplicative form —
            # their 2-26-iteration convergence pedigree (r4) was
            # established with it, and 10 solves/run don't pay the
            # hot-loop price. CUP2D_TWOLEVEL={additive,mult} (latched
            # in __init__, validated there) forces one form for A/B
            # probes.
            # "mg2" (PR 6, the CUP2D_POIS=fft production form): a full
            # two-grid cycle — block-Jacobi PRE-smooth, spectral
            # base-level correction of the smoothed residual,
            # block-Jacobi POST-smooth — i.e. the multiplicative
            # composition symmetrized. Costs 2 A-applies + 3 GEMM
            # smooths per application where additive pays 0 + 1, but
            # contracts both the local high-frequency error AND the
            # coarse modes each application, which is what cuts the
            # Krylov train itself (additive 10/9/8 -> mg2 4/4/4
            # iters/step at the 1e4-block probe, poisson_ab_r6.json)
            # instead of shaving per-iter cost.
            form = self._twolevel_form or (
                "mult" if exact_poisson else
                ("mg2" if self._pois_mode == "fft" else "additive"))
            if form == "additive":
                def M(r):
                    rc = _deposit(r * cih2)
                    ec = coarse(rc, dctops, self._coarse_h2)
                    return _interp(ec, r) + smooth(r, self.p_inv)
            elif form == "mg2":
                def M(r):
                    e = smooth(r, self.p_inv)
                    r1 = r - A(e)
                    rc = _deposit(r1 * cih2)
                    ec = coarse(rc, dctops, self._coarse_h2)
                    e = e + _interp(ec, r)
                    return e + smooth(r - A(e), self.p_inv)
            else:
                def M(r):
                    rc = _deposit(r * cih2)
                    ec = coarse(rc, dctops, self._coarse_h2)
                    e = _interp(ec, r)
                    return e + smooth(r - A(e), self.p_inv)

        with tracing.scope("poisson_solve"):
            if self._pois_mode in ("fas", "fas-f") and not exact_poisson:
                # forest-native FAS production solve (PR 13): multigrid
                # over the forest's OWN refinement levels as the FULL
                # solver — mg_solve's true-residual cycle loop (the same
                # result/stall contract every driver already reads) around
                # one ForestFASCycle per cycle. _use_coarse guarantees
                # tcoarse for these modes; exact/escalation solves fall
                # through to the Krylov backstop below, mirroring the
                # uniform path (UniformGrid.pressure_solve).
                paint_fine, base_solve, extract_all = \
                    self._fas_transfers(tcoarse)
                mgc = ForestFASCycle(
                    A, self._fas_block_smoother(A, tpois),
                    paint_fine, base_solve, extract_all, cih2,
                    leg_dtype=self._fas_leg_dtype)
                res = mg_solve(
                    A, b, mgc,
                    tol=cfg.poisson_tol, tol_rel=cfg.poisson_tol_rel,
                    max_cycles=cfg.max_poisson_iterations,
                    fmg=self._pois_mode == "fas-f",
                )
            else:
                # the cold startup solves start from x0 = M(b): one
                # two-level application removes the global pressure modes
                # from r0 before the Krylov iteration begins — the
                # zero-pressure first solve was the 71-iteration outlier of
                # the round-3 probe precisely because those modes dominated
                # its RHS (VERDICT r3 #9)
                M = tracing.scoped("mg_cycle", M)
                x0 = None
                if exact_poisson and tcoarse is not None:
                    x0 = M(b)
                # exact mode converges THREE ORDERS past the case's own
                # production target (max(1e-3*tol, 1e-3*tol_rel*|r0|)) —
                # deep enough that the startup pressure transient is
                # converged for any consumer of the production tolerances,
                # and anchored to the case instead of the r2 builds'
                # grid-dependent empirical f32 floors (VERDICT r2 #8). The
                # stall detector remains the backstop when that target sits
                # below the precision floor. Chasing the literal-0 floor
                # instead spent up to 71 iterations grinding to 1e-8 on the
                # first canonical solve (r3 probe) for depth nothing reads;
                # this exits at <= 40 (measured).
                res = bicgstab(
                    A, b, M=M, x0=x0,
                    tol=1e-3 * cfg.poisson_tol if exact_poisson
                    else cfg.poisson_tol,
                    tol_rel=1e-3 * cfg.poisson_tol_rel if exact_poisson
                    else cfg.poisson_tol_rel,
                    max_iter=cfg.max_poisson_iterations,
                    max_restarts=100 if exact_poisson
                    else cfg.max_poisson_restarts,
                    sum_dtype=self.sum_dtype,
                    refresh_every=10 if exact_poisson else 50,
                    stall_iters=15 if exact_poisson else 120,
                    stall_rtol=0.99 if exact_poisson else 0.999,
                )

        with tracing.scope("project_correct"):
            # volume-weighted mean removal (main.cpp:7120-7173)
            wsum = jnp.sum(hsq) * cfg.bs ** 2
            dp = res.x - jnp.sum(res.x * hsq) / wsum
            p_new = dp + pord - jnp.sum(pord * hsq) / wsum

            # projection (shared kernel, per-block h broadcast), gradient
            # fluxes corrected (pressureCorrectionKernel + fillcases,
            # main.cpp:7174-7187)
            plab = assemble_labs_ordered(p_new[:, None], t1s)
            dv = pressure_gradient_update(plab[:, 0], 1, h, dt)
            pfac = -0.5 * dt * h[:, 0, 0, 0]
            dv = apply_flux_corr(
                dv, gradient_deposits(plab[:, 0], pfac), corr)
            v = (v + dv * ih2) * maskv
        return v, p_new[:, None], res, div_linf

    def _coarse_transfers(self, tcoarse):
        """The two-level transfer pair (deposit: ordered blocks ->
        coarse image; interp: coarse image -> ordered blocks) for one
        ``_build_coarse_maps`` pytree. Factored out of
        _pressure_project so the cropped-vs-full-domain equivalence is
        directly testable (tests/test_amr.py).

        Ladder bounds: ``lev``/``levf`` hold ONLY levels with active
        blocks (_build_coarse_maps filters empty ones), so the image
        chains stop at the finest/coarsest ACTIVE level (ADVICE r5).
        Levels FINER than c live in ``levf`` and are CROPPED to the
        shared active-tile window — the former full-domain O(4^level)
        cliff is closed: a fine level pays window-sized images, not
        domain-sized ones, and the 2-coarse-cell margin keeps the
        cropped bilinear chain bit-identical to the full-domain form
        on every active cell (see _build_coarse_maps)."""
        lev = tcoarse["lev"]
        levf = tcoarse.get("levf", {})
        crop = tcoarse.get("crop")
        ncy, ncx = self._coarse_shape
        c = self._coarse_level
        bs = self.cfg.bs
        if levf:
            l0 = min(levf)
            sc0 = 1 << (l0 - c)
            hw, ww = levf[l0][0].shape
            wHc = hw * bs // sc0        # window size, coarse cells
            wWc = ww * bs // sc0
            oy, ox = crop[0], crop[1]   # dynamic origin

        def _deposit(rp):
            rc = jnp.zeros((ncy, ncx), rp.dtype)
            for l in sorted(lev):               # levels <= c
                img = _tiles_img(lev[l], rp, bs)
                # coarser than c: spread the cell's unit deposit
                # uniformly over its coarse footprint
                for _ in range(c - l):
                    img = jnp.repeat(
                        jnp.repeat(img, 2, 0), 2, 1) * 0.25
                rc = rc + img
            for l in sorted(levf):              # levels > c, cropped
                img = _tiles_img(levf[l], rp, bs)
                # mean ladder: each fine cell deposits its area
                # fraction 4^(c-l) (the r4 wq weight)
                for _ in range(l - c):
                    img = _down2_mean(img)
                cur = jax.lax.dynamic_slice(rc, (oy, ox), (wHc, wWc))
                rc = jax.lax.dynamic_update_slice(
                    rc, cur + img, (oy, ox))
            return rc

        def _extract(a, entry, e):
            return _extract_tiles(a, entry, e, bs)

        def _interp(ec, like):
            # images are kept ONLY for levels with active blocks; gap
            # levels still pay their ladder step (the 2x chain is how
            # level l+1 is built from l) but are never stored or
            # extracted
            e = jnp.zeros_like(like)
            if c in lev:
                e = _extract(ec, lev[c], e)
            a = ec
            for l in range(c - 1, (min(lev) if lev else c) - 1, -1):
                a = _down2_mean(a)
                if l in lev:
                    e = _extract(a, lev[l], e)
            if levf:
                a = jax.lax.dynamic_slice(ec, (oy, ox), (wHc, wWc))
                for l in range(c + 1, max(levf) + 1):
                    a = _up2_bilinear(a)
                    if l in levf:
                        e = _extract(a, levf[l], e)
            return e

        return _deposit, _interp

    def _fas_transfers(self, tcoarse):
        """Transfer closures of the forest FAS hierarchy
        (poisson.ForestFASCycle), built from the SAME
        ``_build_coarse_maps`` pytree as the two-level preconditioner —
        per-level block-row paints, 2x ladder steps, the cropped
        active-tile window for levels above c. Returns
        (paint_fine, base_solve, extract_all):

        * ``paint_fine(rdiv)``: the DIVIDED residual painted as one
          UNDIVIDED window image per ladder level above c (finest
          first, gap levels zero) — R_l = rdiv * h_l^2, each block
          depositing at its OWN level (the composite-forest analog of
          per-level FAS restriction, arXiv:2510.11152);
        * ``base_solve(rdiv, racc)``: the full-domain level-c RHS (the
          <= c block deposits of rdiv plus the restricted fine-level
          residual ``racc``, undivided -> divided at the window),
          solved exactly by the DCT-II spectral Neumann solve; returns
          (ec, window slice of ec);
        * ``extract_all(ec, es)``: per-level tile extraction of the
          corrected error back onto the ordered blocks — levels <= c
          down-laddered from ec, fine levels from their own corrected
          window images ``es``."""
        lev = tcoarse["lev"]
        levf = tcoarse.get("levf", {})
        crop = tcoarse.get("crop")
        ncy, ncx = self._coarse_shape
        c = self._coarse_level
        bs = self.cfg.bs
        ch2 = self._coarse_h2
        dctops = tcoarse["dct"]
        lf = max(levf) if levf else c
        if levf:
            l0 = min(levf)
            sc0 = 1 << (l0 - c)
            hw, ww = levf[l0][0].shape
            wHc = hw * bs // sc0        # window size, coarse cells
            wWc = ww * bs // sc0
            oy, ox = crop[0], crop[1]   # dynamic origin

        def paint_fine(rdiv):
            imgs = []
            for l in range(lf, c, -1):  # finest ladder level first
                if l in levf:
                    img = _tiles_img(levf[l], rdiv, bs) \
                        * (ch2 / 4 ** (l - c))
                else:
                    sc = 1 << (l - c)
                    img = jnp.zeros((wHc * sc, wWc * sc), rdiv.dtype)
                imgs.append(img)
            return imgs

        def base_solve(rdiv, racc):
            rc = jnp.zeros((ncy, ncx), rdiv.dtype)
            for l in sorted(lev):       # levels <= c, full domain
                img = _tiles_img(lev[l], rdiv, bs)
                # rdiv is POINTWISE (the divided residual ~ lap e), so
                # a cell coarser than c REPLICATES its value over the
                # footprint — unlike the preconditioner's 0.25-spread
                # (_coarse_transfers), which conserves the integral and
                # underweights sub-base levels by 4^(c-l); Krylov
                # absorbs that miscalibration, a plain cycle cannot
                for _ in range(c - l):
                    img = jnp.repeat(jnp.repeat(img, 2, 0), 2, 1)
                rc = rc + img
            awin = None
            if racc is not None:
                cur = jax.lax.dynamic_slice(rc, (oy, ox), (wHc, wWc))
                rc = jax.lax.dynamic_update_slice(
                    rc, cur + racc / ch2, (oy, ox))
            ec = coarse_neumann_solve_dct(rc, dctops, ch2)
            if levf:
                awin = jax.lax.dynamic_slice(ec, (oy, ox), (wHc, wWc))
            return ec, awin

        def extract_all(ec, es):
            e = None
            for i, l in enumerate(range(lf, c, -1)):
                if l in levf:
                    base = jnp.zeros(
                        (self._npad_hwm, bs, bs), ec.dtype) \
                        if e is None else e
                    e = _extract_tiles(es[i], levf[l], base, bs)
            if e is None:
                e = jnp.zeros((self._npad_hwm, bs, bs), ec.dtype)
            if c in lev:
                e = _extract_tiles(ec, lev[c], e, bs)
            a = ec
            for l in range(c - 1, (min(lev) if lev else c) - 1, -1):
                a = _down2_mean(a)
                if l in lev:
                    e = _extract_tiles(a, lev[l], e, bs)
            return e

        return paint_fine, base_solve, extract_all

    def _fas_block_smoother(self, A, tpois):
        """Composite-level smoother of the forest FAS cycle: damped
        block-Jacobi sweeps e += P_inv (r - A e) with the exact
        single-block inverse (the same GEMM as the Krylov
        preconditioner). The sharded subclass overrides with the
        comm/compute-overlapped block-surface form
        (shard_halo.overlap_block_jacobi_sweeps)."""
        p_inv = self.p_inv
        # strip tier (ISSUE 19): each sweep's residual-precondition-
        # update tail (r - lap, the P_inv GEMM and the add) fuses into
        # one Pallas pass over the block batch; the A-apply stays XLA
        # (it IS the forest operator — gather tables + flux rows). The
        # from_zero head is a bare GEMM (lap = 0) and stays XLA too.
        use_fused = False
        if self._kernel_tier != "xla":
            from .ops import pallas_kernels as pk
            use_fused = pk.block_update_supported(self.forest.dtype)

        def smooth(e, r, n, from_zero=False):
            if from_zero and n > 0:
                e = apply_block_precond_blocks(r, p_inv)
                n -= 1
            if use_fused:
                from .ops.pallas_kernels import fused_block_jacobi_update
                for _ in range(n):
                    e = fused_block_jacobi_update(e, r, A(e), p_inv)
                return e
            for _ in range(n):
                e = e + apply_block_precond_blocks(r - A(e), p_inv)
            return e

        return smooth

    @staticmethod
    def _precond_cycles_static(res, tcoarse, exact_poisson):
        if tcoarse is None:
            return jnp.zeros_like(res.iters)
        return 2 * res.iters + (1 if exact_poisson else 0)

    def _precond_cycles(self, res, tcoarse, exact_poisson):
        """Coarse-correction cycle count of one solve (telemetry schema
        v4): flexible BiCGSTAB applies M twice per iteration, plus the
        one x0 = M(b) application of exact-mode cold starts; solves
        without the two-level operand report 0. Forest-FAS production
        solves (CUP2D_POIS=fas|fas-f) run mg_solve, whose iterations
        ARE cycles — same convention as the uniform FAS path
        (UniformGrid.precond_cycles). ``tcoarse is None`` is a
        trace-time (pytree-structure) branch, so this costs nothing
        on device."""
        if self._pois_mode in ("fas", "fas-f") and not exact_poisson:
            return res.iters
        return self._precond_cycles_static(res, tcoarse, exact_poisson)

    @property
    def poisson_mode(self) -> str:
        """Active production solve-path latch (telemetry schema v4 —
        the value vocabulary grew in PR 13, the KEY set did not): the
        CUP2D_POIS mode plus the two-level trigger state, so an A/B
        run's metrics.jsonl alone says which path each step took.
        Forest values: bicgstab+jacobi | bicgstab+twolevel |
        bicgstab+fft | fas+forest | fas-f+forest (the "+forest" suffix
        keeps the forest FAS hierarchy distinguishable from the
        uniform path's plain "fas"/"fas-f" in merged fleet streams)."""
        if self._pois_mode == "fft":
            return "bicgstab+fft"
        if self._pois_mode in ("fas", "fas-f"):
            return self._pois_mode + "+forest"
        return ("bicgstab+twolevel" if self._coarse_on
                else "bicgstab+jacobi")

    @property
    def kernel_tier(self) -> str:
        """Active advection-kernel tier (telemetry schema v6)."""
        return self._kernel_tier

    @property
    def prec_mode(self) -> str:
        """Hot-loop storage precision (telemetry schema v6). The forest
        has no bf16 ADVECTION storage tier (that CUP2D_PREC reading is
        a uniform/fleet contract), so this is always the field dtype;
        the solver-side bf16-leg tier is carried by smoother_tier."""
        return {"float32": "f32", "float64": "f64"}.get(
            self.forest.dtype.name, self.forest.dtype.name)

    @property
    def smoother_tier(self) -> str:
        """Smoother tier of the FAS pressure hierarchy (telemetry
        schema v11): "xla" | "strip" (fused block-Jacobi update pass) |
        "+bf16" suffix when the window-image ladder legs store bf16.
        Non-FAS poisson modes have no cycle legs and report "xla"."""
        base = "xla"
        if (self._pois_mode in ("fas", "fas-f")
                and self._kernel_tier != "xla"):
            from .ops.pallas_kernels import block_update_supported
            if block_update_supported(self.forest.dtype):
                base = "strip"
        if self._fas_leg_dtype is not None:
            return base + "+bf16"
        return base

    @property
    def bc_table(self) -> str:
        """Per-face BC token string (telemetry schema v8). The forest
        tier is free-slip-only by construction (see __init__'s
        refusal), so this is the constant default token."""
        from .bc import FREE_SLIP
        return FREE_SLIP.token

    def _energy(self, v, hsq):
        """Kinetic energy of the masked ordered velocity — the
        telemetry watchdog's first invariant, one fused reduction
        riding the step's existing diag (pad rows carry hsq = 0).
        Accumulated in sum_dtype like the Krylov dots."""
        vv = v.astype(self.sum_dtype) if self.sum_dtype is not None else v
        return 0.5 * jnp.sum(vv * vv * hsq[:, None].astype(vv.dtype))

    @staticmethod
    def _finite_flag(v, p_new, maskv):
        """Fused isfinite reduction over velocity + pressure — the
        health verdict's NaN/Inf detector (resilience.health_verdict),
        riding the step's existing diag pull. ``v`` is already masked
        (pad rows zeroed by the step); the pressure's pad rows hold
        stale-but-finite garbage by the padding invariant, masked here
        through a where (a multiply would turn a hypothetical pad Inf
        into NaN and false-positive)."""
        return jnp.all(jnp.isfinite(v)) & jnp.all(
            jnp.isfinite(jnp.where(maskv > 0, p_new, 0.0)))

    @tracing.in_scope("diag")
    def _diag(self, v, p_new, res, div_linf, hsq, maskv, tcoarse,
              exact_poisson) -> dict:
        """The step's device-side scalars (one batched pull)."""
        return {
            "poisson_iters": res.iters,
            "poisson_residual": res.residual,
            "poisson_stalled": res.stalled,
            "poisson_converged": res.converged,
            "finite": self._finite_flag(v, p_new, maskv),
            "umax": jnp.max(jnp.abs(v)),
            "energy": self._energy(v, hsq),
            "div_linf": div_linf,
            "precond_cycles": self._precond_cycles(
                res, tcoarse, exact_poisson),
        }

    # ------------------------------------------------------------------
    # device step: obstacle-free (the oracle path)
    # ------------------------------------------------------------------
    def _step_impl(self, vel, pres, dt, h, hsq, maskv,
                   t3, t1v, t1s, tpois, corr, tcoarse,
                   exact_poisson=False):
        v = self._advect_rk2(vel, h, dt, t3, corr, maskv)
        v, p_new, res, div_linf = self._pressure_project(
            v, pres, dt, h, hsq, t1v, t1s, tpois, corr, tcoarse,
            exact_poisson, maskv)
        diag = self._diag(v, p_new, res, div_linf, hsq, maskv, tcoarse,
                          exact_poisson)
        return v, p_new, diag

    # ------------------------------------------------------------------
    # device step: with obstacles (the reference hot loop 6607-7187)
    # ------------------------------------------------------------------
    def _flow_impl(self, vel, pres, obs, prescribed, dt, h, hsq,
                   maskv, xc, yc, t3, t1v, t1s, tpois, corr, tcoarse,
                   exact_poisson=False):
        cfg = self.cfg
        S = len(self.shapes)
        v = self._advect_rk2(vel, h, dt, t3, corr, maskv)
        with tracing.scope("penalize"):
            v_cf = v.transpose(1, 0, 2, 3)   # component-first [2,N,BS,BS]

            # rigid momentum solve per shape (main.cpp:6643-6704)
            uvw = []
            for k in range(S):
                if self.shapes[k].free:
                    xr = xc - obs.com[k, 0]
                    yr = yc - obs.com[k, 1]
                    sums = penalization_integrals(
                        v_cf, obs.chi_s[k], obs.udef_s[k], xr, yr,
                        cfg.lam * dt, hsq)
                    uvw.append(solve_rigid_momentum(*sums))
                else:
                    uvw.append(prescribed[k])
            uvw = jnp.stack(uvw)

            # shape-shape collisions (main.cpp:6705-6943): opponent-merged
            # integrals in one field pass, impulses via lax.fori_loop —
            # O(S*N) + O(1)-compile in the pair count (many-body ready)
            if S > 1:
                colls = merged_overlap_integrals(
                    obs.chi_s, obs.sdf_s, obs.udef_s, uvw, obs.com, xc, yc)
                lengths = jnp.asarray(
                    [s.length for s in self.shapes], v.dtype)
                uvw = pairwise_collision_update(
                    colls, uvw, obs.mass, obs.inertia, obs.com, lengths)
                for k in range(S):
                    if not self.shapes[k].free:
                        uvw = uvw.at[k].set(prescribed[k])

            # implicit penalization update, winner shape per cell
            # (main.cpp:6944-6979)
            win = jnp.argmax(obs.chi_s, axis=0)
            us = jnp.zeros_like(v_cf)
            for k in range(S):
                xr = xc - obs.com[k, 0]
                yr = yc - obs.com[k, 1]
                usk = jnp.stack([
                    uvw[k, 0] - uvw[k, 2] * yr + obs.udef_s[k, 0],
                    uvw[k, 1] + uvw[k, 2] * xr + obs.udef_s[k, 1],
                ])
                us = jnp.where(win == k, usk, us)
            alpha = jnp.where(obs.chi > 0.5, 1.0 / (1.0 + cfg.lam * dt), 1.0)
            v_cf = alpha * v_cf + (1.0 - alpha) * us
            v = v_cf.transpose(1, 0, 2, 3)

        udef = self._combined_udef(obs)  # [2,N,BS,BS]
        v, p_new, res, div_linf = self._pressure_project(
            v, pres, dt, h, hsq, t1v, t1s, tpois, corr, tcoarse,
            exact_poisson, maskv,
            chi=obs.chi, udef_b=udef.transpose(1, 0, 2, 3))
        diag = self._diag(v, p_new, res, div_linf, hsq, maskv, tcoarse,
                          exact_poisson)
        return v, p_new, uvw, diag

    # ------------------------------------------------------------------
    # device: the fused per-step megacall — rasterize + flow (+ forces)
    # + next-dt in ONE dispatch, so a step costs one host->device launch
    # and one batched device->host pull (each pull is a host sync
    # that drains the dispatch queue; the unfused chain paid ~6)
    # ------------------------------------------------------------------
    def _megastep_impl(self, vel, pres, inputs, prescribed,
                       dt, hmin, h, hsq, maskv, xc, yc,
                       t3, t1v, t1s, tpois, t4v, t4s, corr, tcoarse,
                       exact_poisson=False, with_forces=False):
        cfg = self.cfg
        obs = self._rasterize_impl(inputs, xc, yc, h[:, 0], hsq, t1s)
        vel, pres, uvw, diag = self._flow_impl(
            vel, pres, obs, prescribed, dt, h, hsq, maskv,
            xc, yc, t3, t1v, t1s, tpois, corr, tcoarse,
            exact_poisson=exact_poisson)
        # next step's dt from THIS step's end-state umax, same shared
        # arithmetic as compute_dt so restarts can't fork the trajectory
        dt_next = self._dt_from_umax(diag["umax"], hmin)
        forces = None
        if with_forces:
            forces = self._forces_impl(
                vel, pres, obs, uvw, t4v, t4s,
                h[:, 0, 0, 0], xc, yc, lists=inputs)
        scalars = (uvw, obs.com, obs.mass, obs.inertia, dt_next, diag)
        return vel, pres, obs.chi[:, None], scalars, forces

    @staticmethod
    def _combined_udef(obs: ObstacleForestFields) -> jnp.ndarray:
        """Deformation-velocity field for the pressure RHS and the
        initial blend (main.cpp:6980-7006; ties sum)."""
        return jnp.sum(
            jnp.where((obs.chi_s >= obs.chi)[:, None], obs.udef_s, 0.0),
            axis=0)

    # ------------------------------------------------------------------
    # device: rasterization + chi + integrals (ongrid, main.cpp:4208-4630)
    # ------------------------------------------------------------------
    @tracing.in_scope("rasterize")
    def _rasterize_impl(self, inputs, xc, yc, h3, hsq, t1s):
        cfg = self.cfg
        bs = cfg.bs
        dtype = self.forest.dtype
        N = xc.shape[0]
        neg = _raster_neg(cfg, dtype)
        S = len(self.shapes)

        # per-shape window rasterization, scattered into block layout
        # (pad rows target the dropped N-th slot)
        sdf = jnp.full((N, bs, bs), neg, dtype)
        per = []
        for k in range(S):
            inp = inputs[k]
            sdf_k, udef_k, wm_k = self._window_raster(inp, N)
            sdf = jnp.maximum(sdf, sdf_k)
            per.append((sdf_k, udef_k, wm_k, inp["com"]))

        # chi from the COMBINED sdf lab at each block's own h
        # (PutChiOnGrid, main.cpp:3911-3969)
        slab = assemble_labs_ordered(sdf[:, None], t1s)[:, 0]
        chi = jnp.zeros((N, bs, bs), dtype)
        chi_s, sdf_s, udef_s = [], [], []
        coms, masses, inertias = [], [], []
        for k in range(S):
            sdf_k, udef_k, wm_k, com = per[k]
            chi_k = chi_from_sdf(slab, sdf_k, h3)

            # CoM correction (main.cpp:4468-4487); zero-mass guard
            m0 = jnp.sum(chi_k * hsq)
            dcx = jnp.sum(chi_k * hsq * (xc - com[0]))
            dcy = jnp.sum(chi_k * hsq * (yc - com[1]))
            safe = jnp.where(m0 > 0, m0, 1.0)
            com_n = com + jnp.where(
                m0 > 0, jnp.stack([dcx, dcy]) / safe, 0.0)

            # integrals + udef de-meaning (main.cpp:4488-4560),
            # window-masked like the uniform path
            xr = xc - com_n[0]
            yr = yc - com_n[1]
            _, _, m, j, iu, iv, ia = shape_integrals(
                chi_k, udef_k, xr, yr, hsq)
            corr = jnp.stack([iu - ia * yr, iv + ia * xr])
            ud = wm_k[None, :, None, None] * (udef_k - corr)

            chi = jnp.maximum(chi, chi_k)
            chi_s.append(chi_k)
            sdf_s.append(sdf_k)
            udef_s.append(ud)
            coms.append(com_n)
            masses.append(m)
            inertias.append(j)

        return ObstacleForestFields(
            chi=chi, sdf=sdf,
            chi_s=jnp.stack(chi_s), sdf_s=jnp.stack(sdf_s),
            udef_s=jnp.stack(udef_s),
            com=jnp.stack(coms), mass=jnp.stack(masses),
            inertia=jnp.stack(inertias),
        )

    def _window_raster(self, inp, N):
        """SDF + deformation velocity of one shape over its window
        blocks, scattered into the ordered block layout (the PutFish-
        OnBlocks gather form, main.cpp:3774-3990). ShardedAMRSim
        overrides the SCATTER with a per-device split (shard-local
        writes); the evaluation itself is the shared _window_sdf_udef,
        so the two paths cannot drift apart numerically."""
        bs = self.cfg.bs
        dtype = self.forest.dtype
        neg = _raster_neg(self.cfg, dtype)
        pos = inp["pos"]                 # [P], -1 = padding
        wmask = pos >= 0
        d, ud = _window_sdf_udef(inp, bs, dtype)
        spos = jnp.where(wmask, pos, N)
        wm3 = wmask[:, None, None]
        sdf_k = jnp.full((N + 1, bs, bs), neg, dtype).at[spos].set(
            jnp.where(wm3, d, neg))[:N]
        udef_k = jnp.zeros((2, N + 1, bs, bs), dtype).at[:, spos].set(
            jnp.where(wm3[None], ud, 0.0))[:, :N]
        wm_k = jnp.zeros((N + 1,), dtype).at[spos].set(
            wmask.astype(dtype))[:N]
        return sdf_k, udef_k, wm_k

    # ------------------------------------------------------------------
    # device: tagging kernels
    # ------------------------------------------------------------------
    def _vorticity_impl(self, vel, h, t1v):
        """Per-block Linf of vorticity (the refinement tag,
        main.cpp:4671-4688). ``vel`` ordered compact."""
        lab = assemble_labs_ordered(vel, t1v)
        w = vorticity(lab, 1, h[:, 0])             # [N, BS, BS]
        return jnp.max(jnp.abs(w), axis=(-1, -2))  # [N]

    def _chi_tag_impl(self, chi_o, t4s, finest):
        """GradChiOnTmp (main.cpp:4631-4656): any positive chi in the
        block's padded window forces refinement (offset 4 at the finest
        level — where it only blocks compression — else 2)."""
        lab = assemble_labs_ordered(chi_o, t4s)[:, 0]      # [N, L, L]
        c = jnp.clip(lab, 0.0, 1.0)
        has4 = jnp.max(c, axis=(-1, -2)) > 0.0
        has2 = jnp.max(c[:, 2:-2, 2:-2], axis=(-1, -2)) > 0.0
        return jnp.where(finest, has4, has2)

    def _tags_impl(self, vel, chi_o, h, t1v, t4s, finest):
        """Fused refinement tags: max of the vorticity Linf and the
        GradChiOnTmp marker (2*Rtol where chi is present) — the two
        computeA passes the reference runs back to back (adapt(),
        main.cpp:4659-4661), one dispatch here."""
        w = self._vorticity_impl(vel, h, t1v)
        has = self._chi_tag_impl(chi_o, t4s, finest)
        return jnp.maximum(w, jnp.where(has, 2.0 * self.cfg.rtol, 0.0))

    def _prolong_impl(self, field, parents, order, t):
        """[R] parent block labs -> [R, 4, dim, BS, BS] children via the
        reference's 2nd-order Taylor prolongation (main.cpp:5002-5028);
        tensorial g=1 labs supply the corner ghosts the xy term needs."""
        labs = assemble_labs(field, order, t)           # [N, dim, L, L]
        plabs = labs[parents]                           # [R, dim, L, L]
        bs = self.cfg.bs

        def children(lab):
            # lab [dim, BS+2, BS+2]; coarse cell (i0, j0) = lab[1+i, 1+j]
            l00 = lab[:, 1:bs + 1, 1:bs + 1]
            lp0 = lab[:, 1:bs + 1, 2:bs + 2]
            lm0 = lab[:, 1:bs + 1, 0:bs]
            l0p = lab[:, 2:bs + 2, 1:bs + 1]
            l0m = lab[:, 0:bs, 1:bs + 1]
            lpp = lab[:, 2:bs + 2, 2:bs + 2]
            lmm = lab[:, 0:bs, 0:bs]
            lpm = lab[:, 0:bs, 2:bs + 2]
            lmp = lab[:, 2:bs + 2, 0:bs]
            x = 0.5 * (lp0 - lm0)
            y = 0.5 * (l0p - l0m)
            x2 = (lp0 + lm0) - 2.0 * l00
            y2 = (l0p + l0m) - 2.0 * l00
            xy = 0.25 * ((lpp + lmm) - (lpm + lmp))
            base = l00 + 0.03125 * (x2 + y2)
            q00 = base - 0.25 * x - 0.25 * y + 0.0625 * xy
            q10 = base + 0.25 * x - 0.25 * y - 0.0625 * xy
            q01 = base - 0.25 * x + 0.25 * y - 0.0625 * xy
            q11 = base + 0.25 * x + 0.25 * y + 0.0625 * xy

            def interleave(a, b, c, d):
                # fine block for child (I, J): rows 2j(+1), cols 2i(+1)
                fine = jnp.zeros(
                    (a.shape[0], 2 * bs, 2 * bs), dtype=a.dtype)
                fine = fine.at[:, 0::2, 0::2].set(a)
                fine = fine.at[:, 0::2, 1::2].set(b)
                fine = fine.at[:, 1::2, 0::2].set(c)
                fine = fine.at[:, 1::2, 1::2].set(d)
                return fine

            fine = interleave(q00, q10, q01, q11)  # [dim, 2BS, 2BS]
            return jnp.stack([
                fine[:, :bs, :bs], fine[:, :bs, bs:],
                fine[:, bs:, :bs], fine[:, bs:, bs:],
            ])  # [4(child J*2+I... ordered (I,J)=(0,0),(1,0),(0,1),(1,1)), dim, BS, BS]

        return jax.vmap(children)(plabs)

    # ------------------------------------------------------------------
    # device: surface force diagnostics (main.cpp:7188-7284)
    # ------------------------------------------------------------------
    @tracing.in_scope("forces")
    def _forces_impl(self, vel, pres, obs, uvw, t4v, t4s,
                     hflat, xc, yc, lists=None):
        """The 19 sums per shape. ``lists[k]`` (_shape_inputs) names
        the only block rows that can hold a surface cell of shape k —
        ``fpos`` [C], -1 padded — and the reduction runs over those C
        rows, the reference's per-obstacle-block loop (main.cpp:5573);
        with ``fsrow`` / ``fgrow``, the table rows of those blocks,
        their g=4 labs are assembled alone too. Without lists: all N."""
        tight = lists is not None and "fgrow" in lists[0]
        if tight:
            # both scalars through ONE pass over the scalar set's rows
            chisdf = jnp.stack([obs.chi, obs.sdf], axis=1)     # [N,2,..]
        else:
            labs = (assemble_labs_ordered(vel, t4v),           # [N,2,L,L]
                    assemble_labs_ordered(obs.chi[:, None], t4s)[:, 0],
                    assemble_labs_ordered(obs.sdf[:, None], t4s)[:, 0])
        neg = _raster_neg(self.cfg, self.forest.dtype)
        out = []
        for k in range(len(self.shapes)):
            rows = [*(() if tight else labs), pres[:, 0],
                    obs.udef_s[k].transpose(1, 0, 2, 3), obs.sdf_s[k],
                    xc, yc, hflat]
            if lists is not None:
                fpos = lists[k]["fpos"]
                ok = fpos >= 0
                rows = [a[jnp.where(ok, fpos, 0)] for a in rows]
                # a pad entry reads row 0 as "far outside": no surface
                rows[-4] = jnp.where(ok[:, None, None], rows[-4], neg)
            if tight:
                trows = (fpos, lists[k]["fsrow"], lists[k]["fgrow"])
                cs = assemble_labs_rows(chisdf, t4s, *trows)
                rows = [assemble_labs_rows(vel, t4v, *trows),
                        cs[:, 0], cs[:, 1], *rows]
            velp, chip, sdfp, pord, udef, own, xc_k, yc_k, h_k = rows
            out.append(surface_forces_blocks(
                velp, pord, chip, sdfp, udef, own, xc_k, yc_k,
                obs.com[k], uvw[k], self.cfg.nu, h_k, G=4))
        return out

    # ------------------------------------------------------------------
    # host: obstacle bookkeeping
    # ------------------------------------------------------------------
    def _shape_inputs(self):
        """Select blocks intersecting each body's padded bounding box and
        build the device rasterization inputs (the reference's
        AreaSegment-AABB block intersection, main.cpp:4208-4269). Window
        capacities are padded powers of two, so a moving body only
        recompiles when it grows past the current capacity.

        ``fpos`` is the second, tight list: the blocks the force pass
        reduces over (_forces_impl). A surface cell of body k has
        own_sdf > -4h, so it lies within 4 cells of the body, and the
        body lies inside its segments' boxes: every block whose box,
        grown by 5 of its own cells, meets one of those boxes is
        listed — a superset of the blocks where the pass's mask can be
        true, whose size does not depend on the heading."""
        cfg = self.cfg
        f = self.forest
        order = self._order
        bs = cfg.bs
        h = cfg.h0 / (1 << f.level[order]).astype(np.float64)
        x0 = f.bi[order] * bs * h
        y0 = f.bj[order] * bs * h
        x1 = x0 + bs * h
        y1 = y0 + bs * h
        reach = 5.0 * h
        dt_ = np.dtype(jnp.dtype(f.dtype).name)
        out = []
        self._force_blocks = []
        for k, s in enumerate(self.shapes):
            r = self._raster_radius(s)
            cx, cy = s.com
            hit = (x1 > cx - r) & (x0 < cx + r) \
                & (y1 > cy - r) & (y0 < cy + r)
            idx = np.nonzero(hit)[0].astype(np.int32)
            if len(idx) > self._wcap[k]:
                self._wcap[k] = max(
                    16, 1 << int(np.ceil(np.log2(len(idx) * 1.3))))
            pos = _padded_list(idx, self._wcap[k])
            # window-block origins/spacings ride along so the raster
            # kernel computes its cell coordinates instead of gathering
            # them from the (possibly sharded) per-block arrays
            wx0 = np.zeros(self._wcap[k])
            wy0 = np.zeros(self._wcap[k])
            wh = np.ones(self._wcap[k])
            wx0[:len(idx)] = x0[idx]
            wy0[:len(idx)] = y0[idx]
            wh[:len(idx)] = h[idx]
            poly = s.surface_polygon()
            lo, hi = self._segment_boxes(s, poly)
            near = ((x1 + reach)[:, None] > lo[:, 0]) \
                & ((x0 - reach)[:, None] < hi[:, 0]) \
                & ((y1 + reach)[:, None] > lo[:, 1]) \
                & ((y0 - reach)[:, None] < hi[:, 1])
            fidx = np.nonzero(near.any(axis=1))[0].astype(np.int32)
            # ... and the g=4 table rows that fill those blocks' ghosts
            trows = [rows_of_blocks(ix, fidx)
                     for ix in self._frow_index or ()]
            need = max(map(len, trows), default=0)
            if len(fidx) > self._fcap[k] or need > self._frcap[k]:
                # grown BEFORE the dispatch: no step reduces over a
                # truncated list (it costs a recompile, so it is counted)
                if len(fidx) > self._fcap[k]:
                    self._fcap[k] = _bucket(int(1.3 * len(fidx)), lo=16)
                if need > self._frcap[k]:
                    self._frcap[k] = _bucket(int(1.3 * need), lo=512)
                self._fcap_growths += 1
                from .resilience import record_event
                record_event(event="force_cap_grow",
                             step=int(self.step_count), shape=k,
                             blocks=len(fidx), cap=self._fcap[k],
                             rows=need, row_cap=self._frcap[k],
                             growths=self._fcap_growths)
            flist = {"fpos": _padded_list(fidx, self._fcap[k])}
            if trows:
                flist["fsrow"], flist["fgrow"] = (
                    _padded_list(a, self._frcap[k]) for a in trows)
            self._force_blocks.append(len(fidx))
            mid_r, mid_v, mid_nor, mid_vnor = s.midline_comp_frame()
            com = np.asarray(s.com, np.float64)
            # packed body-frame tables (see _window_sdf_udef): com is
            # subtracted host-side in f64 so the device sees two large
            # operands instead of ~13 tiny derived arrays
            seg = pack_polygon_segments(poly - com)
            mid = pack_midline(mid_r - com, mid_v, mid_nor, mid_vnor,
                               s.width)
            out.append({
                **flist,
                "pos": pos,
                "wx0": wx0.astype(dt_), "wy0": wy0.astype(dt_),
                "wh": wh.astype(dt_),
                "seg": seg.astype(dt_), "mid": mid.astype(dt_),
                "com": com.astype(dt_),
            })
        # ONE transfer for every leaf of every body, cast on the host:
        # a jnp.asarray per leaf is a dispatch each (and a convert on
        # the device where it casts), 20 a step between two dispatches
        # of a loop in which the host sets the pace
        return jax.device_put(out)

    def _segment_boxes(self, s, poly):
        """Axis-aligned boxes ([n, 2] low and high corners) that together
        hold the body: its surface polygon cut across into segments
        about one finest block long (the reference's AreaSegments,
        main.cpp:4208-4236). Vertex i and vertex P-1-i face each other
        across the body — the two skins of one midline node on a fish,
        the ends of a chord on a disk — so a segment is both sides'
        vertices between two such cuts, and its box holds their hull."""
        half = (len(poly) + 1) // 2
        sides = np.stack([poly[:half], poly[::-1][:half]])  # [2, half, 2]
        plo = sides.min(axis=0)
        phi = sides.max(axis=0)
        n = int(np.ceil(s.length / (self.cfg.bs * self.cfg.min_h)))
        cuts = np.linspace(0, half - 1, min(max(n, 1), half - 1) + 1
                           ).astype(int)
        # each segment runs up to AND including the next one's first cut
        lo = np.minimum(np.minimum.reduceat(plo, cuts[:-1]), plo[cuts[1:]])
        hi = np.maximum(np.maximum.reduceat(phi, cuts[:-1]), phi[cuts[1:]])
        return lo, hi

    def _rasterize(self) -> ObstacleForestFields:
        self._refresh()
        return self._raster_jit(
            self._shape_inputs(), self._xc, self._yc, self._h3,
            self._hsq_flat, self._tables["sca1"])

    def _write_chi(self, obs: ObstacleForestFields):
        f = self.forest
        f.fields["chi"] = f.fields["chi"].at[self._order_j].set(
            obs.chi[:, None])

    def _raster_radius(self, s) -> float:
        """Half-extent of a shape's rasterization window (the
        AreaSegment AABB padding policy, main.cpp:4237) — the ONE
        definition shared by window selection and capacity sizing."""
        return 0.625 * s.length + 12.0 * self.cfg.min_h

    @staticmethod
    def _shape_bbox(s):
        """Axis-aligned bbox of the shape's surface polygon (valid after
        advect/midline)."""
        poly = s.surface_polygon()
        return poly.min(axis=0), poly.max(axis=0)

    def _window_blocks_estimate(self, s) -> int:
        """Finest-level blocks covering shape ``s``'s rasterization
        window (sizes the static raster window capacity)."""
        cfg = self.cfg
        h_fin = cfg.h_at(cfg.level_max - 1)
        r = self._raster_radius(s)
        return int(np.ceil(2.0 * r / (cfg.bs * h_fin))) ** 2

    def _body_blocks_estimate(self, s) -> int:
        """Finest-level blocks the chi-tag region around shape ``s``
        actually occupies: the axis-aligned bbox of its surface polygon
        (orientation included — the caller runs advect/midline first)
        padded by the tag's 4-cell ghost window."""
        cfg = self.cfg
        bh = cfg.bs * cfg.h_at(cfg.level_max - 1)
        pad = 8.0 * cfg.min_h
        lo, hi = self._shape_bbox(s)
        lb = int(np.ceil((float(hi[0] - lo[0]) + pad) / bh)) + 1
        wb = int(np.ceil((float(hi[1] - lo[1]) + pad) / bh)) + 1
        return lb * wb

    def _force_blocks_estimate(self, s) -> int:
        """Finest-level blocks the force pass's list can hold for shape
        ``s`` at ANY heading (sizes the static capacity ``_fcap``): a
        box of the body's length by its thickness, grown by the list's
        reach of 5 cells, covers at most this many blocks however it
        is turned; coarser blocks during the climb are fewer."""
        cfg = self.cfg
        h_fin = cfg.h_at(cfg.level_max - 1)
        bh = cfg.bs * h_fin
        grow = 10.0 * h_fin + np.sqrt(2.0) * bh
        thick = 2.0 * float(np.max(s.width))
        return int(np.ceil((s.length + grow) * (thick + grow) / bh ** 2))

    def _estimate_blocks(self, coarse_start: bool) -> int:
        """Upper-ish estimate of the peak active block count of the init
        climb. Climbing UP from the coarsest grid (coarse_start), the
        peak is near the final adapted count: background + per-shape
        body blocks with a 2.5x margin for the coarser-level pyramid and
        the 2:1 rings. Climbing DOWN from levelStart, the starting
        uniform grid itself is the peak."""
        cfg = self.cfg
        est = cfg.bpdx * cfg.bpdy
        if not coarse_start:
            est += cfg.bpdx * cfg.bpdy << (2 * cfg.level_start)
        for s in self.shapes:
            est += int(2.5 * self._body_blocks_estimate(s))
        return est

    def _refine_toward_shapes(self) -> bool:
        """Bootstrap refinement for the init climb: refine every block
        whose footprint, padded by the chi tag's 4-cell ghost window,
        intersects a shape's bounding box and sits below level_max-1.
        Host-geometric — equivalent to GradChiOnTmp tagging once chi is
        resolvable, but works from grids so coarse the body is thinner
        than one cell (where chi rasterizes to nothing). The normal
        chi-driven adapt() immediately after the climb compresses the
        few bbox-corner blocks the tighter chi window wouldn't keep."""
        f = self.forest
        cfg = self.cfg
        self._refresh()
        order = self._order
        lv = f.level[order].astype(np.int64)
        biv = f.bi[order].astype(np.int64)
        bjv = f.bj[order].astype(np.int64)
        h = cfg.h0 / (1 << lv).astype(np.float64)
        bs = cfg.bs
        pad = 8.0 * h   # 4 ghost cells x one-level-finer margin
        x0 = biv * bs * h - pad
        x1 = (biv + 1) * bs * h + pad
        y0 = bjv * bs * h - pad
        y1 = (bjv + 1) * bs * h + pad
        hit = np.zeros(len(order), bool)
        for s in self.shapes:
            (bx0, by0), (bx1, by1) = self._shape_bbox(s)
            hit |= (x1 > bx0) & (x0 < bx1) & (y1 > by0) & (y0 < by1)
        st = np.where(hit & (lv < cfg.level_max - 1), 1, 0).astype(np.int8)
        return self._commit_states(lv, biv, bjv, st)

    def initialize(self):
        """The reference's startup (main.cpp:6542-6575): levelMax rounds
        of {rasterize; adapt} refine the grid around the bodies, then
        the initial velocity is the chi-blended deformation velocity.

        Two compile/throughput measures:
        the padded block axis and raster windows are pre-sized from
        block estimates so the climb compiles one executable set; and
        when the fields are still identically zero, the climb starts
        from the COARSEST grid and refines up toward the bodies
        (host-geometric bootstrap tags) instead of starting from the
        full levelStart grid and compressing the background away — the
        tag rules have the same fixed point (zero fields carry no
        vorticity), but the peak block count is the final adapted count
        rather than 4^levelStart x base, so the pad bucket the whole
        run inherits is several powers of two smaller."""
        if not self.shapes:
            self._initialized = True
            return
        cfg = self.cfg
        f = self.forest
        for s in self.shapes:
            s.advect(0.0, cfg.extents)
            s.midline(0.0)
        # one fused device query + one pull (a per-field pull is a
        # host sync each)
        allzero = not bool(jnp.any(jnp.stack(
            [jnp.any(v != 0) for v in f.fields.values()])))
        # ctol <= 0 disables compression: the from-above climb then
        # keeps the levelStart background forever, so coarse start would
        # genuinely change the grid, not just its construction order
        coarse = allzero and cfg.level_start > 0 and cfg.ctol > 0
        self.reserve_blocks(self._estimate_blocks(coarse))
        # pre-size the per-shape rasterization windows the same way:
        # every window-capacity crossing during the climb recompiles the
        # megastep (the biggest executable in the repo)
        for k, s in enumerate(self.shapes):
            want = int(2.6 * self._window_blocks_estimate(s)) + 16
            self._wcap[k] = max(self._wcap[k], _bucket(want, lo=16))
            want = int(1.5 * self._force_blocks_estimate(s)) + 8
            self._fcap[k] = max(self._fcap[k], _bucket(want, lo=16))
            # a rim block has up to 192 ghost rows, a block among its
            # like none: 32 a block holds the canonical climb's 49 a
            # LISTED block (54 of them) with half again to spare
            self._frcap[k] = max(self._frcap[k], 32 * self._fcap[k])
        if coarse:
            for key in list(f.blocks):
                f.release(*key)
            for j in range(cfg.bpdy):
                for i in range(cfg.bpdx):
                    f.allocate(0, i, j)
            for _ in range(cfg.level_max + 2):
                if not self._refine_toward_shapes():
                    break
        for _ in range(cfg.level_max):
            obs = self._rasterize()
            self._write_chi(obs)
            if not self.adapt():
                break
        # the climbed forest is the smallest of the run: the wake behind
        # the bodies brings 1.2-1.9 x its table rows within 1000 steps
        # of the reference case (PERF.md section 6, PR 32). Room for
        # twice the rows is made HERE, in set-up, so that no step a few
        # hundred into the run grows a capacity and compiles for it
        self._refresh()
        self._reserve_table_rows(2)
        obs = self._rasterize()
        self._write_chi(obs)
        self._sync_shape_scalars(obs)
        f = self.forest
        vel = f.fields["vel"]
        vord = vel[self._order_j]
        udef = self._combined_udef(obs).transpose(1, 0, 2, 3)
        chi_b = obs.chi[:, None]
        f.fields["vel"] = vel.at[self._order_j].set(
            vord * (1.0 - chi_b) + udef * chi_b)
        self._initialized = True

    # ------------------------------------------------------------------
    # host driver
    # ------------------------------------------------------------------
    def _dt_from_umax(self, umax, hmin):
        """ops.stencil.dt_from_umax in the forest dtype — the device
        path (_megastep_impl's cached next-dt) and the host fallback
        (compute_dt) must agree bit-for-bit or a restart forks the
        trajectory the checkpoint machinery promises to preserve.

        Jitted when called from host driver code (traced callers hit
        the isinstance-of-Tracer branch and inline it): the eager form
        compiled 6+ one-op executables (abs/max/divide/minimum/...)
        whose per-executable compile and dispatch are a real slice of
        warm init (init_compiles probe: 38 of 62 init executables
        were such one-op jits)."""
        if isinstance(umax, jax.core.Tracer) or \
                isinstance(hmin, jax.core.Tracer):
            return dt_from_umax(umax, hmin, self.cfg.nu, self.cfg.cfl)
        if self._dt_jit is None:
            self._dt_jit = tracing.named_jit(
                "amr.dt", jax.jit(
                    lambda u, h: dt_from_umax(u, h, self.cfg.nu,
                                              self.cfg.cfl)))
        return self._dt_jit(jnp.asarray(umax, self.forest.dtype), hmin)

    def _hmin(self):
        """Finest active spacing as a device scalar — the ONE
        definition every dt path (compute_dt, both cached-umax branches,
        the megastep argument) must share, or the restart/lockstep
        contracts silently desynchronize."""
        return jnp.asarray(
            self.cfg.h_at(int(self.forest.level[self._order].max())),
            self.forest.dtype)

    def compute_dt(self) -> float:
        # masked: ordered pad rows carry stale (finite) data.
        # _float_pull (not float): this is the obstacle-free dt
        # fallback after external field writes — a plain float() here
        # would discard the pending poisson-iters scalar and disarm
        # the two-level trigger exactly on such drivers (code-review r4)
        if self._umax_jit is None:
            self._umax_jit = tracing.named_jit(
                "amr.umax", jax.jit(
                    lambda v, m: jnp.max(jnp.abs(v) * m)))
        umax = self._umax_jit(self._ordered_state()["vel"], self._maskv)
        return self._float_pull(self._dt_from_umax(umax, self._hmin()))

    def _use_coarse(self, exact: bool):
        """Coarse-correction operand for the next solve: always for the
        startup (exact) solves; for production, engaged when the last
        solve burned > 15 iterations and sticky until the next topology
        change (block-Jacobi alone follows the uniform path's
        block-count scaling law on near-uniform forests — ~200
        iterations/step at 1e4 blocks).
        Maps build lazily on first engagement. CUP2D_POIS=fft keeps
        the correction ALWAYS on for production solves — cutting
        iterations is the point of that mode, so it never waits for
        the trigger's evidence (``_coarse_on`` is still set, so the
        guard's replay trigger-state record stays truthful). The
        forest-FAS modes (fas/fas-f) likewise: the hierarchy IS the
        solver, so its maps are unconditionally engaged."""
        if not exact:
            if self._pois_mode in ("fft", "fas", "fas-f"):
                self._coarse_on = True
            if not self._coarse_on and self._last_iters > 15:
                self._coarse_on = True
            if not self._coarse_on:
                return None
        if self._coarse_cw is None:
            self._build_coarse_maps(self._npad_hwm, self._n_real)
        return self._coarse_cw

    def _float_pull(self, x) -> float:
        """float(x) that also drains the pending poisson-iters scalar
        in the SAME host transfer (the trigger must not add a host
        sync to the obstacle-free step)."""
        if self._last_iters_dev is not None:
            v, it = jax.device_get((x, self._last_iters_dev))
            self._last_iters = int(it)
            self._last_iters_dev = None
            return float(v)
        return float(x)

    def step_once(self, dt: Optional[float] = None):
        self._refresh()
        f = self.forest
        if not self.shapes:
            ordf = self._ordered_state()
            if dt is None:
                # same cached-umax policy as the obstacle path: the
                # previous step's end-state umax (kept ON DEVICE) feeds
                # the shared dt arithmetic — one scalar round trip
                # instead of a full field reduction (and its sync)
                # per step. Under async_diag even that one
                # scalar round trip goes: dt STAYS a device scalar fed
                # straight into the dispatch (identical arithmetic, so
                # the trajectory is bit-identical to the eager path —
                # float()ing a device scalar and re-putting it is a
                # lossless round trip).
                if self._next_umax is not None:
                    # post-regrid: same 1.05 prolongation-overshoot
                    # guard as the obstacle path (ADVICE r2)
                    fac = (1.0 if self._next_umax_version
                           == f.version else 1.05)
                    dt_dev = self._dt_from_umax(
                        fac * jnp.asarray(self._next_umax, f.dtype),
                        self._hmin())
                    dt = (dt_dev if self.async_diag
                          else self._float_pull(dt_dev))
                else:
                    dt = self.compute_dt()
            elif self._last_iters_dev is not None and not self.async_diag:
                # explicit-dt callers still drain the iters scalar
                # (async mode keeps it on device: the guard's lagged
                # pull IS the drain — replay must not add pulls)
                self._float_pull(jnp.zeros((), f.dtype))
            exact = self.step_count < 10 or self._force_exact
            dt_dev = jnp.asarray(dt, f.dtype)
            vel, pres, diag = self._step_jit(
                ordf["vel"], ordf["pres"], dt_dev,
                self._h, self._hsq_flat, self._maskv,
                self._tables["vec3"], self._tables["vec1"],
                self._tables["sca1"], self._tables["pois"],
                self._corr, self._use_coarse(exact),
                exact_poisson=exact)
            self._set_ordered(vel=vel, pres=pres)
            # end-state umax stays a DEVICE scalar — the next
            # step's dt derives from it without an extra field
            # reduction, and only its one-scalar pull touches host
            self._next_umax = diag["umax"]
            self._next_umax_version = f.version
            if not exact:
                # iters ride the NEXT dt pull (see _float_pull).
                # Exact-startup counts are excluded: they converge
                # 3 orders deeper with a different M, and would
                # spuriously trip the production trigger on
                # compressed forests (code-review r4)
                self._last_iters_dev = diag["poisson_iters"]
            diag = dict(diag)
            if self.async_diag:
                diag["dt"] = dt_dev      # the lagged clock's source
                self.step_count += 1
                return diag              # no host sync
            # the EXACT dt used (host float here), for the guard's
            # replay record — a time-difference reconstruction is
            # off by an ulp (review PR 4)
            diag["dt"] = float(dt)
            self.time += dt
            self.step_count += 1
            return diag

        if not getattr(self, "_initialized", False):
            self.initialize()
            self._refresh()
        step = int(self.step_count)
        # the step's host parts as spans nested in ``dispatch``
        # (tracing.SPANS): dt, kinematics, shape_inputs, enqueue, pull,
        # bodies
        with tracing.span("dt", step=step) as sp:
            # run the external-write invalidation BEFORE the dt branch:
            # an external forest.fields write between steps (wver moved)
            # must drop the cached _next_dt/_next_umax here exactly as
            # on the obstacle-free path, or one step runs at the stale
            # dt — a silent CFL violation (ADVICE r3 medium)
            self._ordered_state()
            # a scalar round trip: a pending drain, or no dt cached from
            # the previous megastep on this forest
            pulled = self._last_iters_dev is not None or (
                dt is None and (self._next_dt is None
                                or self._next_dt_version != f.version))
            if self._last_iters_dev is not None:
                # a pending obstacle-free iters scalar must be drained
                # on entry to the shaped path (shapes appended mid-run):
                # left pending, a later _float_pull would overwrite the
                # fresher megastep-set _last_iters with this stale count
                # and perturb the two-level trigger (ADVICE r4)
                self._float_pull(jnp.zeros((), f.dtype))
            if dt is None:
                # prefer the dt the PREVIOUS megastep computed on device
                # — a fresh compute_dt() is a full host<->device round
                # trip
                if self._next_dt is not None and \
                        self._next_dt_version == f.version:
                    dt = min(self._next_dt, self._kinematic_dt_cap())
                elif self._next_umax is not None:
                    # a regrid invalidated the layout, not the physics:
                    # the velocity field is the same water, re-gridded
                    # (2nd-order prolongation can overshoot umax by a
                    # few %, well inside the CFL-0.5 slack). Only hmin
                    # can change; recompute dt from the cached end-state
                    # umax through the SAME shared arithmetic — one
                    # scalar round trip instead of a full field
                    # reduction + compile after every adapt.
                    # The 1.05 factor turns the prolongation-overshoot
                    # argument from an asserted comment into an enforced
                    # bound (ADVICE r2): any overshoot up to 5% now
                    # tightens dt instead of silently stretching CFL.
                    dt = min(float(self._dt_from_umax(
                        jnp.asarray(1.05 * self._next_umax, f.dtype),
                        self._hmin())),
                        self._kinematic_dt_cap())
                else:
                    dt = min(self.compute_dt(), self._kinematic_dt_cap())
            if sp is not None:
                sp.attrs["pulled"] = pulled

        # ongrid host part (main.cpp:3992-4207)
        cfg = self.cfg
        with tracing.span("kinematics", step=step):
            for s in self.shapes:
                s.advect(dt, cfg.extents)
                s.midline(self.time)
        with tracing.span("shape_inputs", step=step):
            inputs = self._shape_inputs()

        exact = self.step_count < 10 or self._force_exact
        with_forces = bool(
            self.compute_forces_every
            and self.step_count % self.compute_forces_every == 0)
        with tracing.span("enqueue", step=step):
            prescribed = jnp.asarray(
                [[s.u, s.v, s.omega] for s in self.shapes], dtype=f.dtype)
            hmin = self._hmin()
            ordf = self._ordered_state()
            vel, pres, chi_new, scalars, forces = self._mega_jit(
                ordf["vel"], ordf["pres"],
                inputs, prescribed, jnp.asarray(dt, f.dtype), hmin,
                self._h, self._hsq_flat, self._maskv,
                self._xc, self._yc,
                self._tables["vec3"], self._tables["vec1"],
                self._tables["sca1"], self._tables["pois"],
                self._tables.get("vec4t"), self._tables.get("sca4t"),
                self._corr, self._use_coarse(exact),
                exact_poisson=exact,
                with_forces=with_forces)
            self._set_ordered(vel=vel, pres=pres, chi=chi_new)
        # the ONE host pull of the step
        with tracing.span("pull", step=step):
            uvw, com, mass, inertia, dt_next, diag, forces = \
                jax.device_get((*scalars, forces))
        with tracing.span("bodies", step=step):
            diag["dt"] = float(dt)    # exact replay record (see above)
            self._sync_shape_scalars_np(com, mass, inertia)
            uvw_np = np.asarray(uvw, dtype=np.float64)
            for k, s in enumerate(self.shapes):
                if s.free:
                    s.u, s.v, s.omega = uvw_np[k]
            diag["bodies"] = self._bodies_record()
            self._next_dt = float(dt_next)
            self._next_dt_version = f.version
            self._next_umax = float(diag["umax"])
            self._next_umax_version = f.version
            if not exact:
                # the megastep's single pull already carried the
                # iteration count — feed the production two-level
                # trigger directly (exact-startup counts excluded, see
                # the step_jit path)
                self._last_iters = int(diag["poisson_iters"])
            if with_forces:
                self._record_forces(forces)
            # the step's device temporaries are released here, inside
            # the span, and not on return, where no span holds the time
            del scalars, inputs, prescribed, hmin

        self.time += dt
        self.step_count += 1
        return diag

    # -- regrid --------------------------------------------------------
    def adapt(self):
        """Tag / 2:1-balance / refine / coarsen (main.cpp:4657-5440)."""
        # refresh BEFORE opening the span: table time always lands in
        # the top-level "tables" span, never nested under "regrid" (the
        # benchmark's tables_ms and regrid_ms read them apart)
        self._refresh()
        with tracing.span("regrid", step=int(self.step_count)):
            return self._adapt_impl()

    def _adapt_impl(self):
        f = self.forest
        cfg = self.cfg
        # one fused dispatch + one pull for both tag kernels (each extra
        # sync stalls the dispatch pipeline)
        ordf = self._ordered_state()
        if self.shapes and "chi" in f.fields:
            finest = np.zeros(len(self._mask), bool)
            finest[:self._n_real] = \
                f.level[self._order] == cfg.level_max - 1
            tags = self._pull_blockwise(self._tags_jit(
                ordf["vel"], ordf["chi"],
                self._h, self._tables["vec1"], self._tables["sca4t"],
                jnp.asarray(finest)))[:self._n_real]
        else:
            tags = self._pull_blockwise(self._vorticity_jit(
                ordf["vel"], self._h,
                self._tables["vec1"]))[:self._n_real]
        order = self._order

        # 1 = refine, -1 = compress, 0 = leave — vectorized over the
        # ordered block arrays (a per-block Python dict was O(n) host
        # time per adapt; only the final refine/group LISTS — small —
        # are materialized for the regrid bookkeeping)
        lv = f.level[order].astype(np.int64)
        biv = f.bi[order].astype(np.int64)
        bjv = f.bj[order].astype(np.int64)
        st = np.where(
            (tags > cfg.rtol) & (lv < cfg.level_max - 1), 1,
            np.where((tags < cfg.ctol) & (lv > 0), -1, 0)
        ).astype(np.int8)
        return self._commit_states(lv, biv, bjv, st)

    def _commit_states(self, lv, biv, bjv, st) -> bool:
        """Shared tail of every regrid decision (chi/vorticity adapts
        AND the init-climb bootstrap): 2:1 state fixing, refine/compress
        extraction, one fused regrid dispatch. Returns whether anything
        changed."""
        if not st.any():
            return False
        self._fix_states(lv, biv, bjv, st)
        refine = [(int(lv[k]), int(biv[k]), int(bjv[k]))
                  for k in np.nonzero(st == 1)[0]]
        groups = self._compress_groups(lv, biv, bjv, st)
        if not refine and not groups:
            return False
        self._apply_regrid(refine, groups)
        return True

    def _fix_states(self, lv, biv, bjv, st):
        """2:1 balance sweeps, finest level first (main.cpp:4734-4861):
        a block with a refining finer neighbor must refine; compressing
        next to a finer or refining neighbor must stay. ``st`` is
        mutated in place. Runs the native C kernel when available
        (cup2d_tpu/native — the reference's equivalent bookkeeping is
        C++ inside adapt()); the Python body below is the semantically
        identical fallback, asserted equal by tests/test_native.py."""
        cfg = self.cfg
        # the native wrapper does its own contiguous-int32 conversion
        if native.available() and native.fix_states(
                lv, biv, bjv, st, cfg.level_max, cfg.bpdx, cfg.bpdy):
            return
        state = {(int(lv[k]), int(biv[k]), int(bjv[k])): int(st[k])
                 for k in range(len(st))}
        self._fix_states_py(state)
        for k in range(len(st)):
            st[k] = state[(int(lv[k]), int(biv[k]), int(bjv[k]))]

    def _fix_states_py(self, state):
        f = self.forest
        cfg = self.cfg
        for m in range(cfg.level_max - 1, -1, -1):
            for key in list(state.keys()):
                l, i, j = key
                if l != m or state[key] == 1 or l == cfg.level_max - 1:
                    continue
                nbx, nby = f.nblocks_at(l)
                for cx in (-1, 0, 1):
                    for cy in (-1, 0, 1):
                        if cx == 0 and cy == 0:
                            continue
                        ni, nj = i + cx, j + cy
                        if not (0 <= ni < nbx and 0 <= nj < nby):
                            continue
                        if f.owner_relation(l, ni, nj) != -1:
                            continue
                        if state[key] == -1:
                            state[key] = 0
                        # any refining finer neighbor forces refinement
                        for (a, b) in [(0, 0), (0, 1), (1, 0), (1, 1)]:
                            ck = (l + 1, 2 * ni + a, 2 * nj + b)
                            if state.get(ck, 0) == 1:
                                state[key] = 1
                                break
                        if state[key] == 1:
                            break
                    if state[key] == 1:
                        break
            # compressing next to a same-level refining neighbor
            for key in list(state.keys()):
                l, i, j = key
                if l != m or state[key] != -1:
                    continue
                nbx, nby = f.nblocks_at(l)
                for cx in (-1, 0, 1):
                    for cy in (-1, 0, 1):
                        if cx == 0 and cy == 0:
                            continue
                        nk = (l, i + cx, j + cy)
                        if nk in state and state[nk] == 1:
                            state[key] = 0
                            break
                    if state[key] == 0:
                        break

    def _compress_groups(self, lv, biv, bjv, st):
        """Sibling groups where all 4 children exist and want compression
        (main.cpp:4826-4861). Vectorized: each compressing block hashes
        to its parent key; a parent with FOUR compressing children is a
        group (each (l, i, j) occurs once, and a compressing block is by
        definition active, so count == 4 implies the quad exists)."""
        cand = np.nonzero(st == -1)[0]
        if len(cand) == 0:
            return []
        # row-unique instead of bit-packing: no coordinate-width limit
        parents = np.stack(
            [lv[cand], biv[cand] >> 1, bjv[cand] >> 1], axis=1)
        uniq, counts = np.unique(parents, axis=0, return_counts=True)
        groups = []
        for l, pi, pj in uniq[counts == 4]:
            i0, j0 = 2 * int(pi), 2 * int(pj)
            groups.append([(int(l), i0 + a, j0 + b)
                           for a in (0, 1) for b in (0, 1)])
        return groups

    def _apply_regrid(self, refine_keys, groups):
        """Refinement + compression as ONE device dispatch over ALL
        fields, with the refine/compress counts padded to power-of-two
        buckets. The r2 per-field path issued 2 prolongation calls + 5
        scatters per adapt AND retraced for every distinct refine count
        (a fresh XLA compile nearly every regrid while the vortex
        grows); bucketed counts + one fused executable make steady-state
        regrids pure cache hits. All gathers read the PRE-regrid field
        arrays (functional semantics), so refine writes can't corrupt
        compress reads; pad rows read/write a dead (inactive) slot.
        Reference: refinement main.cpp:4960-5033, compression 5055-5194.
        """
        f = self.forest
        # the prolongation/restriction gathers read the slot-layout
        # fields — the dispatch flushes the ordered working state into
        # them first (the pre-regrid order and flush index are still
        # valid there). Clean state is flushed too (its own slots'
        # values, written back): one executable, not one a case
        ordf = self._ord if self._ord_dirty else self._ordered_state()
        ordpos = {int(s): k for k, s in enumerate(self._order)}
        R, G = len(refine_keys), len(groups)
        # one executable per pad bucket: padding refine/compress rows to
        # n_pad/4 (G can never exceed it — 4 siblings per group; R can
        # only during mass refinement, which falls back to its own
        # bucket) keeps steady-state regrids on a single compiled
        # executable instead of one per (Rp, Gp) combination — each
        # extra combination cost a full XLA compile of the fused
        # prolong+restrict program
        cap = max(32, self._npad_hwm // 4)
        Rp = cap if R <= cap else _bucket(R, lo=4)
        Gp = cap if G <= cap else _bucket(G, lo=4)

        # host bookkeeping first: parents/siblings resolved BEFORE any
        # release; all allocations done (possibly growing the slot
        # arrays + device fields) before the jitted call captures them
        parents = np.full(Rp, self._n_real, np.int64)   # pad -> pad lab row
        for n, k in enumerate(refine_keys):
            parents[n] = ordpos[f.blocks[k]]
        sib_slots = np.empty((Gp, 4), np.int32)
        for g, sibs in enumerate(groups):
            l, i0, j0 = sibs[0]
            for ci, (a, b) in enumerate([(0, 0), (1, 0), (0, 1), (1, 1)]):
                sib_slots[g, ci] = f.blocks[(l, i0 + a, j0 + b)]

        child_slots = np.empty((Rp, 4), np.int32)
        for n, (l, i, j) in enumerate(refine_keys):
            f.release(l, i, j)
            for ci, (a, b) in enumerate([(0, 0), (1, 0), (0, 1), (1, 1)]):
                child_slots[n, ci] = f.allocate(l + 1, 2 * i + a, 2 * j + b)
        parent_slots = np.empty(Gp, np.int32)
        for g, sibs in enumerate(groups):
            l, i0, j0 = sibs[0]
            for (a, b) in [(0, 0), (1, 0), (0, 1), (1, 1)]:
                f.release(l, i0 + a, j0 + b)
            parent_slots[g] = f.allocate(l - 1, i0 // 2, j0 // 2)
        if not f._free:
            f._grow()
        dead = f._free[-1]
        child_slots[R:] = dead
        sib_slots[G:] = dead
        parent_slots[G:] = dead

        f.fields.update(self._regrid_jit(
            dict(f.fields), ordf, self._sync_j, self._order_j,
            jnp.asarray(parents), jnp.asarray(child_slots.reshape(-1)),
            jnp.asarray(sib_slots), jnp.asarray(parent_slots),
            self._tables["vec1t"], self._tables["sca1t"]))
        # flushed; _ord / _ord_key describe the old topology and the
        # next _ordered_state() gathers anew
        self._ord_dirty = False
        self._n_refined += R
        self._n_coarsened += G

    def _regrid_apply_impl(self, fields, ordf, sync, order, parents,
                           child_slots, sib_slots, parent_slots, tv, ts):
        """Device half of _apply_regrid: the flush of the ordered
        working state (_flush_impl), then per field, Taylor prolongation
        of the refined parents (2nd-order, tensorial g=1 labs) scattered
        to the 4 child slots, then 4->1 averaging restriction of the
        compression groups scattered to the parent slot. Pad rows source
        a pad lab row / the dead slot — finite garbage, never read
        unmasked."""
        fields = self._flush_impl(fields, ordf, sync)
        out = {}
        for name, field in fields.items():
            t = tv if field.shape[1] == 2 else ts
            p = self._prolong_impl(field, parents, order, t)
            new = field.at[child_slots].set(
                p.reshape((-1,) + p.shape[2:]))
            d = field[sib_slots]   # [G, 4, dim, BS, BS]
            restr = 0.25 * (
                d[..., 0::2, 0::2] + d[..., 1::2, 0::2]
                + d[..., 0::2, 1::2] + d[..., 1::2, 1::2])
            row0 = jnp.concatenate([restr[:, 0], restr[:, 1]], axis=-1)
            row1 = jnp.concatenate([restr[:, 2], restr[:, 3]], axis=-1)
            parent = jnp.concatenate([row0, row1], axis=-2)
            out[name] = new.at[parent_slots].set(parent)
        return out

    def run(self, tend: float, max_steps: int = 10**9):
        diag = {}
        while self.time < tend and self.step_count < max_steps:
            if (self.step_count <= 10
                    or self.step_count % self.cfg.adapt_steps == 0):
                self.adapt()
            diag = self.step_once()
        return diag
