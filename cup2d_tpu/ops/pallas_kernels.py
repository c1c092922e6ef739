"""Pallas TPU kernels for the advection hot loop.

The PR-9 **megakernel tier** (``fused_advect_heun`` /
``fused_lab_rhs`` / ``fused_correction``): one kernel per RK
substage that reads the velocity from HBM ONCE, synthesizes the
free-slip ghost halo in VMEM, runs the whole WENO5 + diffusion +
Heun-update chain on double-buffered row strips, and writes the
substage result once — attacking the per-op dispatch chain and
its re-reads of the velocity. The divide-free
WENO weight normalization (single reciprocal of the summed alpha
per component, bit-trick reciprocal for the scale-invariant
normalizer) is shared VERBATIM from ops/stencil._weno5_weights, so
the kernel and the XLA chain cannot drift numerically.

Strip DMA scheme: strips are DMA'd whole (sublane-aligned, each HBM
row read exactly once) into a ring of FOUR VMEM slots; the halo
rows of strip i are taken from the resident neighbor strips i-1 and
i+1, and strip i+2 prefetches while i computes (4 slots because
{i-1, i, i+1, i+2} must be distinct mod the ring size — a ring of 3
lets the prefetch overwrite the live top-halo strip). Scratch and
DMA semaphores persist across sequential grid steps on TPU and in
interpret mode (probed), which is what makes the cross-program ring
legal.

The kernels are leading-dim agnostic like ops/stencil.py: operands
are flattened to one leading batch axis L with per-batch
(afac, dfac) scale rows, so the SAME kernel serves the solo
UniformSim (L=1), member-batched FleetSim (L=B, per-member dt), and
— in lab form — forest-block batches (L=N, per-block h). On a TPU
the kernels are always Mosaic-compiled; interpret mode exists only
on a CPU run (JAX_PLATFORMS=cpu, the tests — validation, not
performance; see _on_accel). ISSUE 16 closed the last two refusals:
non-free-slip BC tables ride the in-VMEM affine ghost synthesis
(one executable per BC token), and the sharded x-split rides the
halo-mode kernel (_fused_substage_sharded) behind shard_halo.
fused_advect_heun_sharded's ppermute-before-interior exchange.

bf16 storage tier: operands stored bf16 in HBM, every VMEM
accumulation in f32 (strips are upcast on entry, the final substage
result is written back f32). Storage halves the bytes the roofline
charges for the dominant reads; the f32 path stays bit-pinned by
the goldens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .stencil import advect_diffuse_core, heun_substage, shift, weno_derivative


_G = 3    # WENO5 halo
_GX = 64  # x halo rounded up to lane alignment (128-multiple DMA widths)

# fused-tier strip heights: the strip DMA slices [k*by, by) must be
# sublane-aligned, so by is the storage dtype's sublane tile (f32: 8,
# bf16: 16) — every uniform grid in the repo (bs=8 blocks) divides it
_BY_F32 = 8
_BY_BF16 = 16

# Scoped-VMEM budget of the full-row strip kernels. Mosaic's default is
# 16 MiB of the v5e core's 128 MiB, and the substage kernel's live WENO
# temporaries span whole [by+6, nx+6] rows: the chip's compiler asks
# 3127 B per column for the f32 strip (24.43 MiB at nx=8192) and
# 4659-4841 B per column for the bf16 one (by=16: 36.40 MiB at 8192,
# 18.91 MiB at 4096) — both refused under the default. The kernels
# therefore raise their limit to _VMEM_LIMIT, and the shape gate
# (fused_tier_supported) refuses rows whose demand, by the per-column
# rule below (the compiler's figures rounded up), would not fit it.
_VMEM_LIMIT = 64 * 1024 * 1024
_SUBSTAGE_VMEM_PER_COL = {"f32": 3328, "bf16": 5120}
_STRIP_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _rem(k, m):
    """``k % m`` with both operands pinned i32: program_id is i32, and
    under x64 (the CPU test harness) a bare Python modulus constant
    promotes to i64 — interpret-mode stablehlo rejects the mix."""
    return jax.lax.rem(jnp.asarray(k, jnp.int32), jnp.int32(m))


def _on_accel() -> bool:
    """True on a TPU, where the kernels compile through Mosaic; False
    on a CPU run (``JAX_PLATFORMS=cpu``, the test harness), the only
    place a wrapper called without ``interpret=`` interprets —
    validation speed, not performance. Any other platform offers
    neither and raises, and so does a backend that fails to
    initialise: it must never read as "no chip", which would silently
    put every kernel in interpret mode."""
    platform = jax.devices()[0].platform
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas tiers compile for TPU and interpret on CPU; "
            f"platform {platform!r} offers neither")
    return platform == "tpu"


def _core_seq(lab, afac, dfac):
    """advect_diffuse_core evaluated one velocity component at a time:
    same arithmetic (the correctness tests compare against the shared
    core bit-for-bit), but the live temporaries are [BY, BX] instead of
    [2, BY, BX], which lets Mosaic fit twice the tile in VMEM stack —
    fewer, larger chunks amortize the per-chunk DMA/loop overhead."""
    g = _G
    wind_u = shift(lab, g, 0, 0)[0]
    wind_v = shift(lab, g, 0, 0)[1]
    outs = []
    for c in (0, 1):
        q = lab[c]
        dx = weno_derivative(
            wind_u,
            shift(q, g, 0, -3), shift(q, g, 0, -2), shift(q, g, 0, -1),
            shift(q, g, 0, 0),
            shift(q, g, 0, 1), shift(q, g, 0, 2), shift(q, g, 0, 3))
        dy = weno_derivative(
            wind_v,
            shift(q, g, -3, 0), shift(q, g, -2, 0), shift(q, g, -1, 0),
            shift(q, g, 0, 0),
            shift(q, g, 1, 0), shift(q, g, 2, 0), shift(q, g, 3, 0))
        lap = (shift(q, g, 0, 1) + shift(q, g, 0, -1)
               + shift(q, g, 1, 0) + shift(q, g, -1, 0)
               - 4.0 * shift(q, g, 0, 0))
        outs.append(afac * (wind_u * dx + wind_v * dy) + dfac * lap)
    return jnp.stack(outs)


def _pick(n: int, pref) -> int:
    for b in pref:
        if n % b == 0:
            return b
    return 0


# ===========================================================================
# PR-9 megakernel tier
# ===========================================================================

def fused_tier_supported(ny: int, nx: int, prec: str = "f32") -> bool:
    """Shape-level gate for the fused substage/correction kernels:
    sublane-aligned strips, and a row whose scoped-VMEM demand (the
    compiler's per-column figure, _SUBSTAGE_VMEM_PER_COL) fits the
    limit the kernels request — 8192-wide rows pass in both
    precisions, a 16384-wide bf16 row is refused here exactly as the
    chip's compiler refuses it. A CPU run takes the SAME kernels in
    interpret mode (the tier is opt-in via CUP2D_PALLAS, so a CPU user
    who latches it gets correctness-validation speed on purpose). On a
    compiled TPU the strip DMA additionally needs lane-aligned rows."""
    by = _BY_BF16 if prec == "bf16" else _BY_F32
    if ny < by or ny % by:
        return False
    if _on_accel() and nx % 128:
        return False
    per_col = _SUBSTAGE_VMEM_PER_COL["bf16" if prec == "bf16" else "f32"]
    return nx * per_col <= _VMEM_LIMIT


def lab_tier_supported(dtype) -> bool:
    """Gate for the forest-lab RHS kernel: f32 storage only (Mosaic has
    no f64; the f64 forest validation path stays on XLA)."""
    return jnp.dtype(dtype) == jnp.float32


# ghost kinds the megakernel synthesizes in VMEM (all of bc.py's
# current vocabulary; a future kind — e.g. periodic — must be added
# here WITH its in-kernel ghost form, or the tier refuses loudly)
_KERNEL_BC_KINDS = ("free_slip", "no_slip", "inflow", "outflow")


def kernel_supports(bc) -> None:
    """Kernel-tier capability check for the per-face BC tables (bc.py):
    every ghost kind in bc.py's current vocabulary (free-slip mirror,
    no-slip antireflection, Dirichlet inflow incl. the parabolic
    profile, convective outflow) reduces to an affine combination of
    the edge/inner lines and is synthesized in VMEM from global
    position plus static per-face coefficients — one executable per BC
    token, no in-kernel branching. Only a genuinely unsupported kind
    (a future ``periodic``) refuses, loudly and naming the token, so a
    silent wrong-physics fallback is impossible."""
    if bc is None:
        return
    for name, f in zip(("x_lo", "x_hi", "y_lo", "y_hi"), bc):
        if f.kind not in _KERNEL_BC_KINDS:
            raise ValueError(
                f"CUP2D_PALLAS=1: BCTable ({bc.token}) face {name} has "
                f"kind {f.kind!r}, which has no in-VMEM ghost synthesis "
                f"in the fused kernel (supported: "
                f"{', '.join(_KERNEL_BC_KINDS)}). Unset CUP2D_PALLAS "
                "for this table; it runs on the XLA tier.")


# ---------------------------------------------------------------------------
# in-VMEM BC ghost synthesis (tentpole, ISSUE 16): the staged twin of
# bc.pad_vector_bc's ghost() closure. The FaceBC is STATIC at trace
# time (the BCTable is latched per grid and already the executable's
# hash/admit key), so each helper emits only the selected face's
# affine arithmetic — the kernel never branches on table kind.
# ---------------------------------------------------------------------------

def _bc_uw_y(face, w, nx_tot, col0):
    """Wall velocity (u, v) of a y face over ``w`` columns whose global
    interior column index starts at ``col0`` (0 solo; the shard/halo
    offset under the x-split). Mirrors bc._face_wall/_profile_1d:
    scalars, or val * 4s(1-s) lines with s = (col + 0.5)/nx."""
    if face.kind not in ("no_slip", "inflow"):
        return (0.0, 0.0)
    prof = None
    if face.kind == "inflow" and face.profile != "uniform":
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, w), 2) + col0
        s = (col.astype(jnp.float32) + 0.5) / nx_tot
        prof = 4.0 * s * (1.0 - s)
    return tuple((v if prof is None or v == 0.0 else v * prof)
                 for v in face.u_wall)


def _bc_uw_x(face, rows, row0, ny_tot):
    """Wall velocity of an x face over ``rows`` PADDED rows starting at
    global padded row ``row0`` (= strip_index * by; the x strips paint
    full rows so corners compose with the y ghosts). Mirrors
    bc._x_face_wall_padded: profile coordinates clamped to the face, so
    a parabolic inflow closes to 0 at the wall corners."""
    if face.kind not in ("no_slip", "inflow"):
        return (0.0, 0.0)
    if face.profile == "uniform" or face.kind == "no_slip":
        return face.u_wall
    row = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1) + row0
    s = (row.astype(jnp.float32) - _G + 0.5) / ny_tot
    s = jnp.clip(s, 0.0, 1.0)
    prof = 4.0 * s * (1.0 - s)
    return tuple((v * prof if v != 0.0 else 0.0) for v in face.u_wall)


def _bc_ghost(face, edge, inner, normal_comp, outward_sign, uw, dtf, h):
    """One painted ghost line per bc.pad_vector_bc's ghost() closure,
    identical arithmetic and evaluation order (the ~1-ulp equivalence
    contract): edge/inner are [2, 1, W] (y faces) or [2, R, 1] (x
    faces) f32 line pairs; ``uw`` the (possibly profiled) wall
    velocity; ``dtf`` the per-member dt scalar feeding the convective-
    outflow speed c = clip(sign * edge_n * dt/h, 0, 1)."""
    eu, ev = edge[0:1], edge[1:2]
    if face.kind == "free_slip":
        return (jnp.concatenate([-eu, ev], axis=0) if normal_comp == 0
                else jnp.concatenate([eu, -ev], axis=0))
    if face.kind in ("no_slip", "inflow"):
        return jnp.concatenate(
            [2.0 * uw[0] - eu, 2.0 * uw[1] - ev], axis=0)
    en = eu if normal_comp == 0 else ev
    c = jnp.clip(outward_sign * en * dtf / h, 0.0, 1.0)
    return edge + c * (edge - inner)


def _substage_kernel(by, n, nx, cfac, ih2, has_vold, out_dtype, bc, hh,
                     facs_ref, vel_ref, *rest):
    """One Heun substage on one row strip of one batch member.

    Grid (L, n): batch-major, strips sequential within a member. The
    velocity is read from HBM exactly once per substage: whole strips
    (no halo overlap) DMA into a 4-slot ring; strip i's WENO halo rows
    come from the resident strips i-1 / i+1, or from the wall ghosts
    synthesized in VMEM (``bc is None``: the PR-9 free-slip mirror,
    kept verbatim so the default table stays bit-identical; else the
    BC'd affine ghost forms of _bc_ghost, with the per-member dt in
    facs column 2 feeding convective outflow). Strip i+2 prefetches
    during strip i's compute (the double-buffering; ring of 4 because
    strips {i-1..i+2} must occupy distinct slots). The lab tile is
    assembled as VALUES (concatenates), not scratch stores — no
    unaligned vector stores for Mosaic to choke on."""
    if has_vold:
        vold_ref, out_ref, ring, sems, vring, vsems = rest
    else:
        out_ref, ring, sems = rest

    l = pl.program_id(0)
    i = pl.program_id(1)
    g = _G

    def dma(k):
        slot = _rem(k, 4)
        return pltpu.make_async_copy(
            vel_ref.at[l, :, pl.ds(k * by, by), :],
            ring.at[slot], sems.at[slot])

    # exactly-once start/wait discipline: dma(k) starts at program
    # max(0, k-2) and is waited at program max(0, k-1), one program
    # before its data is first consumed as a bottom halo
    @pl.when(i == 0)
    def _():
        dma(0).start()
        if n > 1:
            dma(1).start()

    @pl.when(i + 2 < n)
    def _():
        dma(i + 2).start()

    if has_vold:
        def vdma(k):
            slot = _rem(k, 2)
            return pltpu.make_async_copy(
                vold_ref.at[l, :, pl.ds(k * by, by), :],
                vring.at[slot], vsems.at[slot])

        @pl.when(i == 0)
        def _():
            vdma(0).start()

        @pl.when(i + 1 < n)
        def _():
            vdma(i + 1).start()

    @pl.when(i == 0)
    def _():
        dma(0).wait()
        if n > 1:
            dma(1).wait()

    @pl.when((i > 0) & (i + 1 < n))
    def _():
        dma(i + 1).wait()

    if has_vold:
        vdma(i).wait()

    f32 = jnp.float32
    cur = ring[_rem(i, 4)].astype(f32)               # [2, by, nx]
    # neighbor-strip halo rows; the untaken wall branch reads a ring
    # slot that may be uninitialized — jnp.where only selects, never
    # computes on the discarded operand
    prev_t = ring[_rem(i + 3, 4)][:, by - g:, :].astype(f32)
    next_h = ring[_rem(i + 1, 4)][:, :g, :].astype(f32)
    if bc is None:
        # free-slip mirror ghosts (uniform.pad_vector, zeroth-order):
        # all g ghost rows equal the edge row — u copied, v negated at
        # y walls. PR-9 path, verbatim (bit-identity contract).
        top_m = jnp.concatenate(
            [cur[0:1, 0:1, :], -cur[1:2, 0:1, :]], axis=0)
        bot_m = jnp.concatenate(
            [cur[0:1, by - 1:by, :], -cur[1:2, by - 1:by, :]], axis=0)
        top = jnp.where(i > 0, prev_t,
                        jnp.broadcast_to(top_m, (2, g, nx)))
        bot = jnp.where(i + 1 < n, next_h,
                        jnp.broadcast_to(bot_m, (2, g, nx)))
        ycol = jnp.concatenate([top, cur, bot], axis=1)     # [2, by+6, nx]
        # x ghosts read the y-completed columns so corners compose both
        # flips, exactly like pad_vector's two-pass sweep: u negated,
        # v copied at x walls
        left = jnp.concatenate(
            [-ycol[0:1, :, 0:1], ycol[1:2, :, 0:1]], axis=0)
        right = jnp.concatenate(
            [-ycol[0:1, :, nx - 1:nx], ycol[1:2, :, nx - 1:nx]], axis=0)
        lab = jnp.concatenate(
            [jnp.broadcast_to(left, (2, by + 2 * g, g)), ycol,
             jnp.broadcast_to(right, (2, by + 2 * g, g))], axis=2)
    else:
        # BC'd wall ghosts (bc.pad_vector_bc, staged): y faces first
        # over interior columns, then x faces over the y-completed
        # columns so corners compose in the same order
        dtf = facs_ref[l, 2]
        glo = _bc_ghost(bc.y_lo, cur[:, 0:1, :], cur[:, 1:2, :],
                        1, -1.0, _bc_uw_y(bc.y_lo, nx, nx, 0), dtf, hh)
        ghi = _bc_ghost(bc.y_hi, cur[:, by - 1:by, :],
                        cur[:, by - 2:by - 1, :],
                        1, 1.0, _bc_uw_y(bc.y_hi, nx, nx, 0), dtf, hh)
        top = jnp.where(i > 0, prev_t,
                        jnp.broadcast_to(glo, (2, g, nx)))
        bot = jnp.where(i + 1 < n, next_h,
                        jnp.broadcast_to(ghi, (2, g, nx)))
        ycol = jnp.concatenate([top, cur, bot], axis=1)     # [2, by+6, nx]
        rows = by + 2 * g
        gl = _bc_ghost(bc.x_lo, ycol[:, :, 0:1], ycol[:, :, 1:2],
                       0, -1.0, _bc_uw_x(bc.x_lo, rows, i * by, n * by),
                       dtf, hh)
        gr = _bc_ghost(bc.x_hi, ycol[:, :, nx - 1:nx],
                       ycol[:, :, nx - 2:nx - 1],
                       0, 1.0, _bc_uw_x(bc.x_hi, rows, i * by, n * by),
                       dtf, hh)
        lab = jnp.concatenate(
            [jnp.broadcast_to(gl, (2, rows, g)), ycol,
             jnp.broadcast_to(gr, (2, rows, g))], axis=2)

    af = facs_ref[l, 0]
    df = facs_ref[l, 1]
    rhs = _core_seq(lab, af, df)
    if has_vold:
        vold = vring[_rem(i, 2)].astype(f32)
    else:
        vold = cur  # substage 1: vold IS vel — zero extra HBM reads
    out_ref[0] = heun_substage(vold, cfac, rhs, ih2).astype(out_dtype)


def _fused_substage(v, vold, facs, cfac, ih2, out_dtype, interpret,
                    bc=None, hh=None):
    """One megakernel substage over flattened operands.
    v: [L, 2, ny, nx] (storage dtype); vold: same or None (substage 1,
    where vold==vel and the ring strip is reused); facs: [L, 2] f32
    (afac, dfac) per batch member, widened to [L, 3] with the raw dt
    in column 2 when a BC table rides along."""
    L, _, ny, nx = v.shape
    by = _BY_BF16 if v.dtype == jnp.bfloat16 else _BY_F32
    n = ny // by
    has_vold = vold is not None
    kern = functools.partial(_substage_kernel, by, n, nx,
                             cfac, ih2, has_vold, jnp.dtype(out_dtype),
                             bc, hh)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    ops = [facs, v]
    scratch = [pltpu.VMEM((4, 2, by, nx), v.dtype),
               pltpu.SemaphoreType.DMA((4,))]
    if has_vold:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        ops.append(vold)
        scratch += [pltpu.VMEM((2, 2, by, nx), vold.dtype),
                    pltpu.SemaphoreType.DMA((2,))]
    return pl.pallas_call(
        kern,
        grid=(L, n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 2, by, nx), lambda l, i: (l, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((L, 2, ny, nx), out_dtype),
        scratch_shapes=scratch,
        compiler_params=_STRIP_PARAMS,
        interpret=interpret,
    )(*ops)


def _flatten_lead(shape_lead):
    L = 1
    for d in shape_lead:
        L *= int(d)
    return max(L, 1)


def _per_member(x, lead, L, dtype=jnp.float32):
    """Normalize a scale factor to a flat [L] row: accepts a scalar, a
    leading-shaped vector (fleet per-member dt), or the leading shape
    with trailing singleton broadcast dims (the forest's [N,1,1,1]
    per-block h)."""
    x = jnp.asarray(x, dtype)
    if x.ndim > len(lead):
        x = x.reshape(x.shape[:len(lead)] + (-1,))[..., 0]
    return jnp.broadcast_to(x, lead).reshape((L,))


def fused_advect_heun(vel, h, nu, dt, *, bc=None, bf16: bool = False,
                      interpret=None):
    """Both Heun substages through the fused megakernel — the drop-in
    tier for ``UniformGrid.advect_heun`` and the fleet's inlined chain.

    vel: [..., 2, Ny, Nx] (any leading dims); dt: scalar or shaped like
    the leading dims (per-member fleet dt). f32 path: documented-ulp
    equivalent to the XLA chain (identical op sequence; the only
    deviation source is compiler FMA contraction, bound asserted in
    tests/test_megakernel.py). bf16: storage-precision tier — one
    upcast-free bf16 read per substage, f32 VMEM accumulation, f32
    final state. bc: optional BCTable (bc.py); a free-slip table is
    normalized to None so the default path stays bit-identical to
    PR 9, any other table rides the in-VMEM BC ghost synthesis (one
    executable per token — the FaceBCs are static trace constants)."""
    if bc is not None and bc.is_free_slip:
        bc = None
    kernel_supports(bc)
    lead = vel.shape[:-3]
    L = _flatten_lead(lead)
    v = vel.reshape((L,) + vel.shape[-3:])
    dtv = _per_member(dt, lead, L)
    hh = float(h)
    if bc is None:
        facs = jnp.stack([-dtv * hh, nu * dtv], axis=-1)    # [L, 2] f32
    else:
        # raw per-member dt rides column 2 (convective-outflow speed)
        facs = jnp.stack([-dtv * hh, nu * dtv, dtv], axis=-1)
    ih2 = 1.0 / (hh * hh)
    if interpret is None:
        interpret = not _on_accel()
    if bf16:
        vb = v.astype(jnp.bfloat16)
        v1 = _fused_substage(vb, None, facs, 0.5, ih2,
                             jnp.bfloat16, interpret, bc, hh)
        v2 = _fused_substage(v1, vb, facs, 1.0, ih2, v.dtype, interpret,
                             bc, hh)
    else:
        v1 = _fused_substage(v, None, facs, 0.5, ih2, v.dtype,
                             interpret, bc, hh)
        v2 = _fused_substage(v1, v, facs, 1.0, ih2, v.dtype, interpret,
                             bc, hh)
    return v2.reshape(vel.shape)


# ---------------------------------------------------------------------------
# sharded-x-split substage (tentpole, ISSUE 16): the halo-mode twin of
# _substage_kernel. The shard_map wrapper (parallel/shard_halo.
# fused_advect_heun_sharded) ppermutes the 3-wide WENO edge columns
# BEFORE dispatching this kernel, so the exchange latency hides behind
# the strip pipeline; the received columns arrive as a lane-padded
# ``aux`` operand and are fused as the boundary strips' ghost source —
# termwise-identical to the GSPMD chain.
# ---------------------------------------------------------------------------

def _sharded_substage_kernel(by, n, nxl, nx_tot, cfac, ih2, has_vold,
                             out_dtype, bc, hh, facs_ref, info_ref,
                             vel_ref, aux_ref, *rest):
    """Per-shard substage over the local x slab [2, ny, nxl].

    aux: [L, 2, ny, 2*_GX] halo operand — received left-neighbor edge
    columns in [:, :, :, 0:g], right-neighbor in [g:2g], zero elsewhere
    (lane padding, and zeros at the mesh walls where no neighbor
    sends). info (SMEM i32 [1, 3]): (is_lo, is_hi, col0 = idx*nxl) from
    the traced axis index — the ONLY per-shard values; everything else
    is static, so all shards share one executable. Both rings follow
    the solo kernel's exactly-once DMA discipline; the extended strip
    (halo + local + halo) runs the y-ghost pass at global column
    coordinates, then wall shards where-select the x-face BC paint over
    the (zero) non-received halo columns — the same corner composition
    order as bc.pad_vector_bc."""
    if has_vold:
        vold_ref, out_ref, ring, sems, aring, asems, vring, vsems = rest
    else:
        out_ref, ring, sems, aring, asems = rest

    l = pl.program_id(0)
    i = pl.program_id(1)
    g = _G

    def dma(k):
        slot = _rem(k, 4)
        return pltpu.make_async_copy(
            vel_ref.at[l, :, pl.ds(k * by, by), :],
            ring.at[slot], sems.at[slot])

    def adma(k):
        slot = _rem(k, 4)
        return pltpu.make_async_copy(
            aux_ref.at[l, :, pl.ds(k * by, by), :],
            aring.at[slot], asems.at[slot])

    @pl.when(i == 0)
    def _():
        dma(0).start()
        adma(0).start()
        if n > 1:
            dma(1).start()
            adma(1).start()

    @pl.when(i + 2 < n)
    def _():
        dma(i + 2).start()
        adma(i + 2).start()

    if has_vold:
        def vdma(k):
            slot = _rem(k, 2)
            return pltpu.make_async_copy(
                vold_ref.at[l, :, pl.ds(k * by, by), :],
                vring.at[slot], vsems.at[slot])

        @pl.when(i == 0)
        def _():
            vdma(0).start()

        @pl.when(i + 1 < n)
        def _():
            vdma(i + 1).start()

    @pl.when(i == 0)
    def _():
        dma(0).wait()
        adma(0).wait()
        if n > 1:
            dma(1).wait()
            adma(1).wait()

    @pl.when((i > 0) & (i + 1 < n))
    def _():
        dma(i + 1).wait()
        adma(i + 1).wait()

    if has_vold:
        vdma(i).wait()

    f32 = jnp.float32
    is_lo = info_ref[0, 0]
    is_hi = info_ref[0, 1]
    col0 = info_ref[0, 2]
    we = nxl + 2 * g

    def ext(k, rows):
        """Extended-width rows of strip k: received halo columns glued
        onto the local slab (value concatenate, f32 upcast)."""
        a = aring[_rem(k, 4)][:, rows, :].astype(f32)
        v = ring[_rem(k, 4)][:, rows, :].astype(f32)
        return jnp.concatenate(
            [a[:, :, 0:g], v, a[:, :, g:2 * g]], axis=2)

    cur = ext(i, slice(None))                        # [2, by, we]
    prev_t = ext(i + 3, slice(by - g, by))
    next_h = ext(i + 1, slice(0, g))
    dtf = facs_ref[l, 2]
    # y ghosts over the EXTENDED width at global column coordinates:
    # halo columns get the same paint the neighbor's own y pass gives
    # them (identical formula, identical edge/inner data); the unsent
    # wall-shard halo columns are junk here and are overwritten by the
    # x-face where-select below — corners compose in bc.py's order
    glo = _bc_ghost(bc.y_lo, cur[:, 0:1, :], cur[:, 1:2, :],
                    1, -1.0, _bc_uw_y(bc.y_lo, we, nx_tot, col0 - g),
                    dtf, hh)
    ghi = _bc_ghost(bc.y_hi, cur[:, by - 1:by, :], cur[:, by - 2:by - 1, :],
                    1, 1.0, _bc_uw_y(bc.y_hi, we, nx_tot, col0 - g),
                    dtf, hh)
    top = jnp.where(i > 0, prev_t, jnp.broadcast_to(glo, (2, g, we)))
    bot = jnp.where(i + 1 < n, next_h,
                    jnp.broadcast_to(ghi, (2, g, we)))
    ycol = jnp.concatenate([top, cur, bot], axis=1)  # [2, by+2g, we]
    rows = by + 2 * g
    gl = _bc_ghost(bc.x_lo, ycol[:, :, g:g + 1], ycol[:, :, g + 1:g + 2],
                   0, -1.0, _bc_uw_x(bc.x_lo, rows, i * by, n * by),
                   dtf, hh)
    gr = _bc_ghost(bc.x_hi, ycol[:, :, g + nxl - 1:g + nxl],
                   ycol[:, :, g + nxl - 2:g + nxl - 1],
                   0, 1.0, _bc_uw_x(bc.x_hi, rows, i * by, n * by),
                   dtf, hh)
    left = jnp.where(is_lo > 0, jnp.broadcast_to(gl, (2, rows, g)),
                     ycol[:, :, 0:g])
    right = jnp.where(is_hi > 0, jnp.broadcast_to(gr, (2, rows, g)),
                      ycol[:, :, g + nxl:])
    lab = jnp.concatenate([left, ycol[:, :, g:g + nxl], right], axis=2)

    af = facs_ref[l, 0]
    df = facs_ref[l, 1]
    rhs = _core_seq(lab, af, df)
    if has_vold:
        vold = vring[_rem(i, 2)].astype(f32)
    else:
        vold = cur[:, :, g:g + nxl]
    out_ref[0] = heun_substage(vold, cfac, rhs, ih2).astype(out_dtype)


def _fused_substage_sharded(v, vold, aux, info, facs, cfac, ih2,
                            out_dtype, bc, hh, nx_tot, interpret):
    """One halo-mode substage over flattened per-shard operands.
    v: [L, 2, ny, nxl]; aux: [L, 2, ny, 2*_GX] received halo columns;
    info: [1, 3] i32 (is_lo, is_hi, col0); facs: [L, 3]."""
    L, _, ny, nxl = v.shape
    by = _BY_BF16 if v.dtype == jnp.bfloat16 else _BY_F32
    n = ny // by
    has_vold = vold is not None
    kern = functools.partial(_sharded_substage_kernel, by, n, nxl,
                             nx_tot, cfac, ih2, has_vold,
                             jnp.dtype(out_dtype), bc, hh)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    ops = [facs, info, v, aux]
    scratch = [pltpu.VMEM((4, 2, by, nxl), v.dtype),
               pltpu.SemaphoreType.DMA((4,)),
               pltpu.VMEM((4, 2, by, 2 * _GX), aux.dtype),
               pltpu.SemaphoreType.DMA((4,))]
    if has_vold:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        ops.append(vold)
        scratch += [pltpu.VMEM((2, 2, by, nxl), vold.dtype),
                    pltpu.SemaphoreType.DMA((2,))]
    return pl.pallas_call(
        kern,
        grid=(L, n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 2, by, nxl), lambda l, i: (l, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((L, 2, ny, nxl), out_dtype),
        scratch_shapes=scratch,
        compiler_params=_STRIP_PARAMS,
        interpret=interpret,
    )(*ops)


# ---------------------------------------------------------------------------
# lab-mode RHS (forest blocks): the AMR stages interleave flux
# corrections between RHS and update, so the fusable unit is lab -> rhs
# ---------------------------------------------------------------------------

def _lab_kernel(g, facs_ref, lab_ref, out_ref):
    af = facs_ref[:, :, :, 0:1]                 # [cb, 1, 1, 1]
    df = facs_ref[:, :, :, 1:2]
    out_ref[...] = advect_diffuse_core(lab_ref[...], g, af, df)


def fused_lab_rhs(lab, h, nu, dt, *, interpret=None):
    """Fused advect-diffuse RHS over pre-assembled ghost labs
    [..., 2, H+2g, W+2g] -> [..., 2, H, W]; ``h`` may be per-block
    ([N, 1, 1] on the forest) and ``dt`` scalar. One HBM read of the
    lab per evaluation; block chunks ride the standard Pallas
    double-buffered pipeline (BlockSpec grid over the leading axis).
    Shares advect_diffuse_core verbatim with the XLA path."""
    g = _G
    lead = lab.shape[:-3]
    L = _flatten_lead(lead)
    Hp, Wp = lab.shape[-2:]
    lab2 = lab.reshape((L, 2, Hp, Wp))
    a = _per_member(-dt * h, lead, L, lab.dtype).reshape((L, 1, 1))
    d = _per_member(nu * dt, lead, L, lab.dtype).reshape((L, 1, 1))
    facs = jnp.stack([a, d], axis=-1)           # [L, 1, 1, 2]
    # 16-block chunks: the [14, 14] lab tiles pad to (16, 128) VMEM
    # tiles, so the core's temporaries cost ~0.58 MiB per block — the
    # chip's compiler asks 37.41 MiB for 64-block chunks against the
    # 16 MiB default scoped limit, 9.4 MiB for 16
    cb = _pick(L, (16, 8, 4, 2, 1))
    if interpret is None:
        interpret = not _on_accel()
    kern = functools.partial(_lab_kernel, g)
    out = pl.pallas_call(
        kern,
        grid=(L // cb,),
        in_specs=[
            pl.BlockSpec((cb, 1, 1, 2), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((cb, 2, Hp, Wp), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((cb, 2, Hp - 2 * g, Wp - 2 * g),
                               lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (L, 2, Hp - 2 * g, Wp - 2 * g), lab.dtype),
        interpret=interpret,
    )(facs, lab2)
    return out.reshape(lead + out.shape[1:])


# ---------------------------------------------------------------------------
# fused projection correction: pres = ((x - mean x) + pold - mean pold),
# vel += pfac * grad_neumann(pres) * ih2 — one read of x/pold/vel, one
# write of pres/vel, replacing the XLA mean-free + gradient + update
# chain's separate passes (poisson.project_correct dispatches here)
# ---------------------------------------------------------------------------

def _correct_kernel(by, n, ny, nx, ih2, gs, scal_ref, x_ref, p_ref,
                    v_ref, pres_out, vel_out, xr, xs, pr, ps, vr, vs):
    l = pl.program_id(0)
    i = pl.program_id(1)

    def dstrip(ref, ring, sem, k, slots):
        slot = _rem(k, slots)
        return pltpu.make_async_copy(
            ref.at[l, pl.ds(k * by, by), :]
            if ref is not v_ref else
            ref.at[l, :, pl.ds(k * by, by), :],
            ring.at[slot], sem.at[slot])

    def dx(k):
        return dstrip(x_ref, xr, xs, k, 4)

    def dp(k):
        return dstrip(p_ref, pr, ps, k, 4)

    def dv(k):
        return dstrip(v_ref, vr, vs, k, 2)

    @pl.when(i == 0)
    def _():
        dx(0).start()
        dp(0).start()
        dv(0).start()
        if n > 1:
            dx(1).start()
            dp(1).start()

    @pl.when(i + 2 < n)
    def _():
        dx(i + 2).start()
        dp(i + 2).start()

    @pl.when(i + 1 < n)
    def _():
        dv(i + 1).start()

    @pl.when(i == 0)
    def _():
        dx(0).wait()
        dp(0).wait()
        if n > 1:
            dx(1).wait()
            dp(1).wait()

    @pl.when((i > 0) & (i + 1 < n))
    def _():
        dx(i + 1).wait()
        dp(i + 1).wait()

    dv(i).wait()

    f32 = jnp.float32
    mx = scal_ref[l, 0]
    mp = scal_ref[l, 1]
    pfac = scal_ref[l, 2]

    def pt(k, rows):
        """Mean-free pressure values of strip k's given rows — the
        exact XLA expression ((x - mx) + pold) - mp."""
        xv = xr[_rem(k, 4)][rows, :]
        pv = pr[_rem(k, 4)][rows, :]
        return ((xv - mx) + pv) - mp

    cur = pt(i, slice(None))                                # [by, nx]
    # zero-ghost shift rows (the fused-BC zero-shift form): wall rows
    # are zeros, interior rows come from the neighbor strips
    top = jnp.where(i > 0, pt(i + 3, slice(by - 1, by)), 0.0)
    bot = jnp.where(i + 1 < n, pt(i + 1, slice(0, 1)), 0.0)
    pcol = jnp.concatenate([top, cur, bot], axis=0)         # [by+2, nx]
    z = jnp.zeros((by + 2, 1), f32)
    pw = jnp.concatenate([z, pcol, z], axis=1)              # [by+2, nx+2]
    # rank-1 BC edge corrections from GLOBAL indices: -s at the low
    # wall, +s at the high wall with s the per-face pressure-row sign
    # (bc.BCTable.pressure_signs; +1 Neumann, -1 Dirichlet outflow) —
    # gs=(1,1,1,1) reproduces stencil._edge_ones' Neumann constants
    # bitwise (2-D iota — Mosaic has no 1-D iota)
    sx_lo, sx_hi, sy_lo, sy_hi = gs
    col = jax.lax.broadcasted_iota(jnp.int32, (by, nx), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (by, nx), 0) + i * by
    zero = jnp.zeros((), f32)
    gx = jnp.where(col == 0, jnp.asarray(-sx_lo, f32),
                   jnp.where(col == nx - 1, jnp.asarray(sx_hi, f32),
                             zero))
    gy = jnp.where(row == 0, jnp.asarray(-sy_lo, f32),
                   jnp.where(row == ny - 1, jnp.asarray(sy_hi, f32),
                             zero))
    dpx = (pw[1:-1, 2:] - pw[1:-1, :-2]) + cur * gx
    dpy = (pw[2:, 1:-1] - pw[:-2, 1:-1]) + cur * gy
    dv_ = pfac * jnp.stack([dpx, dpy], axis=0)              # [2, by, nx]
    pres_out[0] = cur
    vel_out[0] = vr[_rem(i, 2)] + dv_ * ih2


def fused_correction(x, pres_old, vel, mx, mp, pfac, ih2, *,
                     grad_signs=None, interpret=None):
    """x, pres_old: [L, Ny, Nx]; vel: [L, 2, Ny, Nx]; mx/mp/pfac: [L]
    (means and -0.5*dt*h per batch member). grad_signs: optional
    static (sx_lo, sx_hi, sy_lo, sy_hi) per-face pressure-row signs
    (bc.BCTable.pressure_signs); None = all-Neumann, bit-identical to
    the PR-9 kernel. Returns (pres, vel)."""
    L, ny, nx = x.shape
    by = _BY_F32
    n = ny // by
    if interpret is None:
        interpret = not _on_accel()
    gs = ((1.0, 1.0, 1.0, 1.0) if grad_signs is None
          else tuple(float(s) for s in grad_signs))
    scal = jnp.stack([mx, mp, pfac], axis=-1).astype(jnp.float32)
    kern = functools.partial(_correct_kernel, by, n, ny, nx, ih2, gs)
    f32 = jnp.float32
    return pl.pallas_call(
        kern,
        grid=(L, n),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec((1, by, nx), lambda l, i: (l, i, 0)),
            pl.BlockSpec((1, 2, by, nx), lambda l, i: (l, 0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, ny, nx), x.dtype),
            jax.ShapeDtypeStruct((L, 2, ny, nx), vel.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((4, by, nx), f32), pltpu.SemaphoreType.DMA((4,)),
            pltpu.VMEM((4, by, nx), f32), pltpu.SemaphoreType.DMA((4,)),
            pltpu.VMEM((2, 2, by, nx), f32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(scal, x, pres_old, vel)


# ---------------------------------------------------------------------------
# memory-tiered FAS strip smoothers (ISSUE 19): the whole n-sweep
# damped-Jacobi chain of one MG smooth as ONE time-skewed strip
# pipeline — the XLA fori_loop form reads e and r from HBM once per
# sweep (2n+1 field passes for n sweeps); here sweep k of strip j runs
# as soon as sweep k-1 has produced strips j-1..j+1, so the chain is
# one HBM read of (e, r) and one write of the result regardless of n.
# ---------------------------------------------------------------------------

# sweep-chain depth cap: each extra sweep costs one [4, by, nx]
# intermediate VMEM ring plus one r-ring slot; 6 keeps the 8192-wide
# f32 worst case under the 16M scoped-vmem budget (asked of the chip's
# compiler: 6 sweeps at 8192 compile, f32 and bf16). The nu1/nu2/nu_img
# chains are 1-3 sweeps; the 24-sweep coarsest chain (tiny grids, XLA
# does fine) falls back on purpose.
_JACOBI_MAX_SWEEPS = 6


def jacobi_strip_supported(ny: int, nx: int, dtype, n: int) -> bool:
    """Gate for ``fused_jacobi_sweeps`` at one MG level: f32/bf16
    storage, sublane-aligned strip heights, lane-aligned rows on a
    compiled TPU, and a bounded sweep depth (see _JACOBI_MAX_SWEEPS).
    A False here is a silent fall-back to the identical-result XLA
    sweep chain — an optimization gate, not a capability refusal."""
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    by = _BY_BF16 if dt == jnp.bfloat16 else _BY_F32
    if ny < by or ny % by:
        return False
    if _on_accel() and nx % 128:
        return False
    return 1 <= n <= _JACOBI_MAX_SWEEPS


# fused multigrid legs (ISSUE 26): strip height of the down/up-leg
# pipelines. The restricted strip is by/2 rows and must itself be a
# whole sublane tile of the storage dtype (bf16: 16), so one height
# serves both precisions.
_BY_LEG = 32
# lane width of one column-pair chunk: 256 fine lanes <-> 128 coarse
_LEG_CHUNK = 256


def mg_leg_supported(ny: int, nx: int, dtype, n: int) -> bool:
    """Gate for ``fused_mg_down`` / ``fused_mg_up`` at one MG level:
    the sweep-chain gate plus whole 32-row strips and whole 256-lane
    column-pair chunks (the coarse strip is then a full (16, 128)
    tile in either storage dtype). Like ``jacobi_strip_supported`` a
    False is a silent fall-back to the XLA legs."""
    return (jacobi_strip_supported(ny, nx, dtype, n)
            and ny % _BY_LEG == 0 and nx % _LEG_CHUNK == 0)


def _pair_matrix(dtype=jnp.bfloat16):
    """The constant 0/1 [256, 128] tile S with S[i, i // 2] = 1:
    ``x @ S`` sums adjacent lane pairs (full-weighting restriction
    along x), ``y @ S.T`` replicates every lane twice (nearest
    prolongation along x). Exact on the MXU: every product is x * 1
    and the accumulator is f32."""
    i = np.arange(_LEG_CHUNK)[:, None]
    k = np.arange(_LEG_CHUNK // 2)[None, :]
    # a numpy constant: baked into the program, not recomputed a call
    return jnp.asarray((i // 2 == k).astype(np.float32), dtype)


def _dot_split(x, sel, npass):
    """``x @ sel`` for a 0/1 ``sel`` with the f32 operand split into
    ``npass`` bf16 terms (hi, mid, lo): each pass is exact, so three
    passes carry all 24 mantissa bits of an f32 and one carries a
    bf16 operand whole. The MXU has no other work in this program."""
    f32 = jnp.float32
    acc = None
    rem = x
    for p in range(npass):
        part = rem.astype(jnp.bfloat16)
        d = jnp.dot(part, sel, preferred_element_type=f32)
        acc = d if acc is None else acc + d
        if p + 1 < npass:
            rem = rem - part.astype(f32)
    return acc


def _lane_chunks_matmul(x, sel, npass):
    """Apply ``sel`` ([cin, cout]) to every ``cin``-lane chunk of
    ``x`` [m, n]: the chunks are stacked along sublanes (whole vregs
    relabelled, nothing moves), multiplied in ONE MXU call with M =
    m * n / cin, and unstacked along lanes the same way."""
    m, n = x.shape
    cin, cout = sel.shape
    nch = n // cin
    if nch == 1:
        return _dot_split(x, sel, npass)
    xs = jnp.concatenate(
        [x[:, c * cin:(c + 1) * cin] for c in range(nch)], axis=0)
    ys = _dot_split(xs, sel, npass)
    return jnp.concatenate(
        [ys[c * m:(c + 1) * m, :] for c in range(nch)], axis=1)


def _jacobi_strips_kernel(by, nstr, ny, nx, nsw, omega, gs, from_zero,
                          prolong, restrict, store_dtype, r_ref, *rest):
    """Time-skewed n-sweep Jacobi chain over row strips of one member,
    optionally with a prolong-add head and a residual-restrict tail
    (the two fused legs of a V-cycle level, ISSUE 26).

    Grid (L, nstr + nsw - 1 + head + tail): at step i, sweep level k
    (k = 1..nsw) computes strip j = i - (k - 1) - head — level k-1's
    strip j+1 is produced earlier in the SAME program (the Python
    level loop emits them in order), so every neighbor a sweep needs
    is resident by the time it runs. Input rings follow the
    megakernel's exactly-once DMA discipline; intermediate sweeps live
    in plain 4-slot VMEM rings (compute writes, no DMA); the final
    sweep writes the out block, whose index map revisits block
    j = max(i - nsw + 1 - head, 0) so Pallas flushes it exactly once,
    after the level-nsw write. All arithmetic is f32 (accumulate
    tier); strips store at ``store_dtype`` — for bf16 legs that is the
    one rounding per sweep the XLA bf16 chain also pays, for f32 the
    chain is term-for-term the XLA expression (the ~1-ulp parity
    contract, tests/test_strip_smoother.py).

    ``prolong`` (the up-leg's head, level 0 at strip j = i): the
    coarse correction's strip [by/2, nx/2] is replicated 2x2 (lanes on
    the MXU against the constant pair matrix, rows by strided stores)
    and added to e before the first sweep reads it. ``restrict`` (the
    down-leg's tail, one more level after sweep nsw at strip
    j = i - nsw - head): the residual r - lap(e) of the smoothed e,
    summed over 2x2 cells (rows by strided loads, lanes on the MXU) —
    the x4 of the undivided coarse operator is that plain sum."""
    rest = list(rest)
    # e comes in as pipelined blocks under the prolong head (no halo
    # is read before the head has written its ring), through the
    # manual HBM ring otherwise, and not at all from zero
    ring_e = not prolong and not from_zero
    if prolong:
        e_ref, ec_ref = rest.pop(0), rest.pop(0)
    elif ring_e:
        e_ref = rest.pop(0)
    if restrict:
        sel_ref = rest.pop(0)
    if prolong:
        selt_ref = rest.pop(0)
    out_ref = rest.pop(0)
    if restrict:
        rc_ref = rest.pop(0)
    if ring_e:
        ering, esems = rest.pop(0), rest.pop(0)
    head = 1 if prolong else 0
    tail = 1 if restrict else 0
    rslots = nsw + 2 + head + tail
    rring, rsems = rest.pop(0), rest.pop(0)
    nrings = nsw - 1 + head + tail
    lvls = [rest.pop(0) for _ in range(nrings)]
    # ring of level k's output (k = 0 the head, nsw the last sweep)
    ring_of = {k: lvls[k - 1 + head] for k in range(1 - head,
                                                   nsw + tail)}
    buf = rest.pop(0) if (prolong or restrict) else None

    l = pl.program_id(0)
    i = pl.program_id(1)
    f32 = jnp.float32
    hb = by // 2

    def rdma(k):
        slot = _rem(k, rslots)
        return pltpu.make_async_copy(
            r_ref.at[l, pl.ds(k * by, by), :],
            rring.at[slot], rsems.at[slot])

    # r strip j arrives at step j, is first consumed by sweep 1 at
    # step j + head and last by sweep nsw (or the tail) at step
    # j + nsw - 1 + head + tail; the slots keep that window plus a
    # one-step prefetch live
    @pl.when(i == 0)
    def _():
        rdma(0).start()

    @pl.when(i + 1 < nstr)
    def _():
        rdma(i + 1).start()

    @pl.when(i < nstr)
    def _():
        rdma(i).wait()

    if ring_e:
        def edma(k):
            slot = _rem(k, 4)
            return pltpu.make_async_copy(
                e_ref.at[l, pl.ds(k * by, by), :],
                ering.at[slot], esems.at[slot])

        @pl.when(i == 0)
        def _():
            edma(0).start()
            if nstr > 1:
                edma(1).start()

        @pl.when(i + 2 < nstr)
        def _():
            edma(i + 2).start()

        @pl.when(i == 0)
        def _():
            edma(0).wait()
            if nstr > 1:
                edma(1).wait()

        @pl.when((i > 0) & (i + 1 < nstr))
        def _():
            edma(i + 1).wait()

    sx_lo, sx_hi, sy_lo, sy_hi = gs
    zero = jnp.zeros((), f32)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, nx), 1)
    rowl = jax.lax.broadcasted_iota(jnp.int32, (by, 1), 0)
    first_col, last_col = col == 0, col == nx - 1

    def corr_inv(j):
        """The level's signed wall-diagonal row, from GLOBAL indices —
        the exact values (and groupings) of stencil._edge_ones /
        MultigridPreconditioner._inv_diag (2-D iota: Mosaic has no
        1-D iota). The reciprocal is taken on the three [by, 1]
        columns the diagonal can hold (interior, low wall, high wall)
        and selected per lane: the same quotients as 1 / corr without
        a divide per cell."""
        row = rowl + j * by
        ey = jnp.where(row == 0, jnp.asarray(sy_lo, f32),
                       jnp.where(row == ny - 1, jnp.asarray(sy_hi, f32),
                                 zero))
        c_mid = (ey + zero) - 4.0
        c_lo = (ey + jnp.asarray(sx_lo, f32)) - 4.0
        c_hi = (ey + jnp.asarray(sx_hi, f32)) - 4.0

        def pick(lo, hi, mid):
            return jnp.where(first_col, lo, jnp.where(last_col, hi, mid))

        return (pick(c_lo, c_hi, c_mid),
                pick(1.0 / c_lo, 1.0 / c_hi, 1.0 / c_mid))

    def lap_of(cur, top, bot, corr):
        """Zero-ghost 5-point Laplacian of one strip (term order of
        stencil.laplacian5_neumann/_bc): neighbours by rotation, the
        wrapped lane/row replaced by the ghost (0 in x, the resident
        neighbour strip's edge row in y)."""
        xp = jnp.where(last_col, zero, pltpu.roll(cur, nx - 1, 1))
        xm = jnp.where(first_col, zero, pltpu.roll(cur, 1, 1))
        yp = jnp.where(rowl == by - 1, bot, pltpu.roll(cur, by - 1, 0))
        ym = jnp.where(rowl == 0, top, pltpu.roll(cur, 1, 0))
        return (xp + xm + yp + ym) + cur * corr

    def strips(ring, j):
        """Strip j of a 4-slot ring with its two halo rows (zero at the
        walls). Untaken wall branches may read an uninitialized ring
        slot — jnp.where only selects, never computes on the discarded
        operand."""
        def src(m, rows):
            return ring[_rem(m, 4)][rows, :].astype(f32)

        cur = src(j, slice(None))
        top = jnp.where(j > 0, src(j + 3, slice(by - 1, by)), zero)
        bot = jnp.where(j + 1 < nstr, src(j + 1, slice(0, 1)), zero)
        return cur, top, bot

    def put(ring, j, new):
        ring[_rem(j, 4)] = new.astype(store_dtype)

    if prolong:
        @pl.when(i < nstr)
        def _():
            # nearest prolongation (2x2 replicate) of the coarse strip,
            # added to e: lanes doubled on the MXU, rows by two strided
            # stores into the f32 scratch
            npass = 1 if store_dtype == jnp.bfloat16 else 3
            wide = _lane_chunks_matmul(ec_ref[0], selt_ref[...], npass)
            for c in range(nx // 128):
                piece = wide[:, c * 128:(c + 1) * 128]
                buf[c, pl.ds(0, hb, stride=2), :] = piece
                buf[c, pl.ds(1, hb, stride=2), :] = piece
            up = jnp.concatenate([buf[c] for c in range(nx // 128)],
                                 axis=1)
            put(ring_of[0], i, e_ref[0].astype(f32) + up)

    for k in range(1, nsw + 1):
        j = i - (k - 1) - head

        @pl.when((j >= 0) & (j < nstr))
        def _(k=k, j=j):
            rv = rring[_rem(j, rslots)].astype(f32)
            corr, inv_d = corr_inv(j)
            if k == 1 and from_zero:
                # first sweep from e=0: e = omega r / d (the _smooth
                # from_zero shortcut, same grouping)
                new = omega * rv * inv_d
            else:
                ring = ering if (k == 1 and not prolong) \
                    else ring_of[k - 1]
                cur, top, bot = strips(ring, j)
                # the _smooth fori-body grouping
                new = cur + omega * (rv - lap_of(cur, top, bot, corr)) \
                    * inv_d
            if k == nsw:
                out_ref[0] = new.astype(store_dtype)
            if k < nsw or restrict:
                put(ring_of[k], j, new)

    if restrict:
        j = i - nsw - head

        @pl.when((j >= 0) & (j < nstr))
        def _(j=j):
            rv = rring[_rem(j, rslots)].astype(f32)
            corr, _ = corr_inv(j)
            cur, top, bot = strips(ring_of[nsw], j)
            res = rv - lap_of(cur, top, bot, corr)
            for c in range(nx // 128):
                buf[c] = res[:, c * 128:(c + 1) * 128]
            rows = jnp.concatenate(
                [buf[c, pl.ds(0, hb, stride=2), :]
                 + buf[c, pl.ds(1, hb, stride=2), :]
                 for c in range(nx // 128)], axis=1)
            npass = 2 if store_dtype == jnp.bfloat16 else 3
            rc_ref[0] = _lane_chunks_matmul(
                rows, sel_ref[...], npass).astype(store_dtype)


def _strip_pipeline(r, e, ec, omega, n, edge_signs, from_zero,
                    restrict, by, interpret):
    """The one ``pallas_call`` behind ``fused_jacobi_sweeps`` (plain
    chain), ``fused_mg_down`` (restrict tail) and ``fused_mg_up``
    (prolong head, ``ec`` given). Jitted on its configuration, so a
    kernel body is traced once a process however many cycles, Krylov
    applications and step executables call it (on the chip's host a
    trace and lowering of one body costs about a second)."""
    if interpret is None:
        interpret = not _on_accel()
    gs = ((1.0, 1.0, 1.0, 1.0) if edge_signs is None
          else tuple(float(s) for s in edge_signs))
    return _strip_pipeline_jit(
        r, None if from_zero else e, ec, omega=float(omega), nsw=int(n),
        gs=gs,
        from_zero=bool(from_zero), restrict=bool(restrict), by=int(by),
        interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=(
    "omega", "nsw", "gs", "from_zero", "restrict", "by", "interpret"))
def _strip_pipeline_jit(r, e, ec, *, omega, nsw, gs, from_zero,
                        restrict, by, interpret):
    lead = r.shape[:-2]
    L = _flatten_lead(lead)
    ny, nx = r.shape[-2:]
    store = jnp.dtype(r.dtype)
    nstr = ny // by
    prolong = ec is not None
    head, tail = int(prolong), int(restrict)
    hb, hx = by // 2, nx // 2
    ops = [r.reshape((L, ny, nx))]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    scratch = []
    if prolong:
        ops += [e.astype(store).reshape((L, ny, nx)),
                ec.astype(store).reshape((L, ny // 2, hx))]
        in_specs += [
            pl.BlockSpec((1, by, nx),
                         lambda l, i: (l, jnp.minimum(i, nstr - 1), 0)),
            pl.BlockSpec((1, hb, hx),
                         lambda l, i: (l, jnp.minimum(i, nstr - 1), 0))]
    elif not from_zero:
        ops.append(e.astype(store).reshape((L, ny, nx)))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch += [pltpu.VMEM((4, by, nx), store),
                    pltpu.SemaphoreType.DMA((4,))]
    if restrict:
        ops.append(_pair_matrix())
        in_specs.append(pl.BlockSpec((_LEG_CHUNK, _LEG_CHUNK // 2),
                                     lambda l, i: (0, 0)))
    if prolong:
        ops.append(_pair_matrix().T)
        in_specs.append(pl.BlockSpec((_LEG_CHUNK // 2, _LEG_CHUNK),
                                     lambda l, i: (0, 0)))
    rslots = nsw + 2 + head + tail
    scratch += [pltpu.VMEM((rslots, by, nx), store),
                pltpu.SemaphoreType.DMA((rslots,))]
    for _ in range(nsw - 1 + head + tail):
        scratch.append(pltpu.VMEM((4, by, nx), store))
    if prolong or restrict:
        # the row-pair scratch, one [by, 128] tile a lane chunk: a
        # sublane-strided access needs a 128-lane base
        scratch.append(pltpu.VMEM((nx // 128, by, 128), jnp.float32))

    def clip(j):
        return jnp.minimum(jnp.maximum(j, 0), nstr - 1)

    # block j revisited (unwritten) by the skew's fill steps, then
    # written by sweep nsw at step j + nsw - 1 + head and flushed on
    # the next index change — exactly once per strip
    out_specs = [pl.BlockSpec(
        (1, by, nx), lambda l, i: (l, clip(i - (nsw - 1) - head), 0))]
    out_shape = [jax.ShapeDtypeStruct((L, ny, nx), store)]
    if restrict:
        out_specs.append(pl.BlockSpec(
            (1, hb, hx), lambda l, i: (l, clip(i - nsw - head), 0)))
        out_shape.append(jax.ShapeDtypeStruct((L, ny // 2, hx), store))
    kern = functools.partial(_jacobi_strips_kernel, by, nstr, ny, nx,
                             nsw, omega, gs, from_zero, prolong,
                             restrict, store)
    outs = pl.pallas_call(
        kern,
        grid=(L, nstr + nsw - 1 + head + tail),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_STRIP_PARAMS,
        interpret=interpret,
    )(*ops)
    e_out = outs[0].reshape(r.shape)
    if restrict:
        return e_out, outs[1].reshape(lead + (ny // 2, hx))
    return e_out


def fused_jacobi_sweeps(e, r, omega, n, *, edge_signs=None,
                        from_zero=False, interpret=None):
    """n damped-Jacobi sweeps of the undivided 5-point zero-ghost
    Laplacian in ONE strip pipeline: one HBM read of (e, r), one write
    of the smoothed e — the fused form of the
    MultigridPreconditioner._smooth sweep chain (which pays ~2n+1
    field passes through XLA's fori_loop). Leading-dim agnostic like
    the megakernel: [..., Ny, Nx] operands flatten to [L, Ny, Nx], so
    one kernel serves the solo grid (L=1), fleet member batches (L=B)
    and forest window stacks. ``edge_signs`` = the BC table's
    (sx_lo, sx_hi, sy_lo, sy_hi) pressure-ghost signs; None =
    all-Neumann. ``from_zero``: first sweep is the e = omega r / d
    shortcut and ``e`` is ignored (may be None). Storage dtype follows
    ``r`` (f32 or bf16); accumulation is always f32."""
    by = _BY_BF16 if jnp.dtype(r.dtype) == jnp.bfloat16 else _BY_F32
    return _strip_pipeline(r, e, None, omega, n, edge_signs, from_zero,
                           False, by, interpret)


def fused_mg_down(e, r, omega, n, *, edge_signs=None, from_zero=False,
                  interpret=None):
    """The down-leg of one V-cycle level in one strip pipeline: ``n``
    pre-smoothing sweeps (as ``fused_jacobi_sweeps``), then the
    residual r - lap(e) and its 2x2 full-weighting restriction (plain
    sum: the x4 of the undivided coarse operator). Reads r (and e
    unless ``from_zero``) once, writes e and the restricted residual
    [..., Ny/2, Nx/2] once. Returns ``(e, rc)``."""
    return _strip_pipeline(r, e, None, omega, n, edge_signs, from_zero,
                           True, _BY_LEG, interpret)


def fused_mg_up(e, r, ec, omega, n, *, edge_signs=None, interpret=None):
    """The up-leg of one V-cycle level in one strip pipeline: the
    coarse correction ``ec`` [..., Ny/2, Nx/2] prolonged (nearest, 2x2
    replicate) and added to e, then ``n`` post-smoothing sweeps. Reads
    e, r, ec once, writes e once."""
    return _strip_pipeline(r, e, ec, omega, n, edge_signs, False,
                           False, _BY_LEG, interpret)


# ---------------------------------------------------------------------------
# sharded single-sweep halo strip (ISSUE 19): the strip-tier twin of
# shard_halo.overlap_jacobi_sweeps' per-sweep body. The shard_map
# wrapper ppermutes the 1-wide edge columns BEFORE dispatching (the
# PR-16 pattern), each sweep then runs as one strip pipeline over the
# local slab with the received columns riding a lane-padded ``aux``
# operand — the chain cannot time-skew across sweeps (each sweep needs
# fresh neighbor columns), so the sharded win is per-sweep fusion:
# one read of (e, r) and one write per sweep instead of the GSPMD
# chain's separate stencil/AXPY passes.
# ---------------------------------------------------------------------------

def _jacobi_halo_kernel(by, nstr, ny, nxl, omega, info_ref, r_ref,
                        e_ref, aux_ref, out_ref, ering, esems, rring,
                        rsems, aring, asems):
    """One damped-Jacobi sweep over the local x slab [ny, nxl]; aux
    [ny, 2*_GX] carries the received left edge column in [:, 0:1] and
    right in [:, 1:2] (zeros at mesh walls = the zero ghost). info
    (SMEM i32 [1, 2]) = (is_lo, is_hi) — the only per-shard values, so
    all shards share one executable; the wall-diagonal x indicators
    are masked by them exactly like the shard_map body's
    device-index masks."""
    i = pl.program_id(0)
    f32 = jnp.float32

    def edma(k):
        slot = _rem(k, 4)
        return pltpu.make_async_copy(
            e_ref.at[pl.ds(k * by, by), :], ering.at[slot],
            esems.at[slot])

    def rdma(k):
        slot = _rem(k, 2)
        return pltpu.make_async_copy(
            r_ref.at[pl.ds(k * by, by), :], rring.at[slot],
            rsems.at[slot])

    def adma(k):
        slot = _rem(k, 2)
        return pltpu.make_async_copy(
            aux_ref.at[pl.ds(k * by, by), :], aring.at[slot],
            asems.at[slot])

    @pl.when(i == 0)
    def _():
        edma(0).start()
        rdma(0).start()
        adma(0).start()
        if nstr > 1:
            edma(1).start()

    @pl.when(i + 2 < nstr)
    def _():
        edma(i + 2).start()

    @pl.when(i + 1 < nstr)
    def _():
        rdma(i + 1).start()
        adma(i + 1).start()

    @pl.when(i == 0)
    def _():
        edma(0).wait()
        if nstr > 1:
            edma(1).wait()

    @pl.when((i > 0) & (i + 1 < nstr))
    def _():
        edma(i + 1).wait()

    rdma(i).wait()
    adma(i).wait()

    cur = ering[_rem(i, 4)].astype(f32)                  # [by, nxl]
    prev_t = ering[_rem(i + 3, 4)][by - 1:by, :].astype(f32)
    next_h = ering[_rem(i + 1, 4)][0:1, :].astype(f32)
    zero = jnp.zeros((), f32)
    top = jnp.where(i > 0, prev_t, zero)
    bot = jnp.where(i + 1 < nstr, next_h, zero)
    a = aring[_rem(i, 2)].astype(f32)
    gl, gr = a[:, 0:1], a[:, 1:2]
    xp = jnp.concatenate([cur[:, 1:], gr], axis=1)
    xm = jnp.concatenate([gl, cur[:, :-1]], axis=1)
    ecol = jnp.concatenate([top, cur, bot], axis=0)      # [by+2, nxl]
    yp = ecol[2:, :]
    ym = ecol[:-2, :]
    is_lo = info_ref[0, 0]
    is_hi = info_ref[0, 1]
    col = jax.lax.broadcasted_iota(jnp.int32, (by, nxl), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (by, nxl), 0) + i * by
    one = jnp.ones((), f32)
    ix = jnp.where((col == 0) & (is_lo > 0), one,
                   jnp.where((col == nxl - 1) & (is_hi > 0), one, zero))
    iy = jnp.where(row == 0, one,
                   jnp.where(row == ny - 1, one, zero))
    corr = (iy + ix) - 4.0
    inv_d = 1.0 / corr
    rv = rring[_rem(i, 2)].astype(f32)
    lap = xp + xm + yp + ym + cur * corr
    out_ref[...] = (cur + omega * (rv - lap) * inv_d).astype(
        out_ref.dtype)


def fused_jacobi_halo_sweep(e, r, aux, info, omega, *, interpret=None):
    """One sharded-slab Jacobi sweep (shard_map body helper): e, r
    [ny, nxl] local slabs; aux [ny, 2*_GX] received edge columns;
    info [1, 2] i32 (is_lo, is_hi). Neumann-only (the overlapped
    sharded smoother is free-slip-specific, see
    MultigridPreconditioner.__init__)."""
    ny, nxl = e.shape
    store = jnp.dtype(e.dtype)
    by = _BY_BF16 if store == jnp.bfloat16 else _BY_F32
    nstr = ny // by
    if interpret is None:
        interpret = not _on_accel()
    kern = functools.partial(_jacobi_halo_kernel, by, nstr, ny, nxl,
                             float(omega))
    return pl.pallas_call(
        kern,
        grid=(nstr,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((by, nxl), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ny, nxl), store),
        scratch_shapes=[
            pltpu.VMEM((4, by, nxl), store),
            pltpu.SemaphoreType.DMA((4,)),
            pltpu.VMEM((2, by, nxl), store),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((2, by, 2 * _GX), aux.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(info, r, e, aux)


# ---------------------------------------------------------------------------
# fused forest block-Jacobi update (ISSUE 19): one sweep of the
# composite smoother is e + P_inv (r - A e); the composite A-apply
# stays XLA (it walks the level maps), but the smoother's OWN traffic
# — residual subtract, the [N, bs^2] x [bs^2, bs^2] P_inv GEMM and the
# update add, three separate XLA passes over the block stack — fuses
# to one read of (e, r, Ae) and one write. Honest scope note: the
# forest form is one-fused-pass-PER-SWEEP; only the uniform chain
# above gets n-sweeps-one-pass.
# ---------------------------------------------------------------------------

def block_update_supported(dtype) -> bool:
    """f32 block stacks only (Mosaic has no f64; the f64 forest
    validation path stays on the XLA composition)."""
    return jnp.dtype(dtype) == jnp.float32


def _block_jacobi_kernel(p_ref, e_ref, r_ref, lap_ref, out_ref):
    d = r_ref[...] - lap_ref[...]                   # [cb, bs*bs]
    z = jax.lax.dot_general(
        d, p_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)         # d @ p_inv.T
    out_ref[...] = e_ref[...] + z


def fused_block_jacobi_update(e, r, lap, p_inv, *, interpret=None):
    """e + P_inv (r - lap) over a [N, bs, bs] block stack in one fused
    pass (the apply_block_precond_blocks composition, term-for-term:
    (r - lap).reshape(N, bs^2) @ p_inv.T + e). The [N, bs, bs] ->
    [N, bs^2] reshapes happen OUTSIDE the kernel (XLA bitcasts), so
    the kernel body is pure 2-D MXU work riding the standard chunked
    BlockSpec pipeline."""
    n, bs, _ = e.shape
    m = bs * bs
    cb = _pick(n, (64, 32, 16, 8, 4, 2, 1))
    if interpret is None:
        interpret = not _on_accel()
    out = pl.pallas_call(
        _block_jacobi_kernel,
        grid=(n // cb,),
        in_specs=[
            pl.BlockSpec((m, m), lambda i: (0, 0)),     # replicated
            pl.BlockSpec((cb, m), lambda i: (i, 0)),
            pl.BlockSpec((cb, m), lambda i: (i, 0)),
            pl.BlockSpec((cb, m), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((cb, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, m), e.dtype),
        interpret=interpret,
    )(p_inv, e.reshape(n, m), r.reshape(n, m), lap.reshape(n, m))
    return out.reshape(n, bs, bs)
