"""Shard-local ghost assembly: the explicit halo exchange.

GSPMD lowers `halo.assemble_labs_ordered`'s data-dependent gather from
a block-sharded operand to an **all-gather of the entire field** —
traffic proportional to shard volume, several times per step and once
per Krylov iteration (measured: validation/comm_audit.py). The
reference's comm layer exists precisely to avoid that: it ships only
halo slabs between neighbor ranks (/root/reference/main.cpp:909-2142,
Setup/sync1), so per-rank traffic scales with the shard *boundary*.

This module restores that scaling law on the device mesh:

* gather tables are split per device — rows whose destination block
  lives in shard d become d's rows, with every gather source remapped
  into a local index space = [d's own B blocks] ++ [an all-gathered
  SURFACE buffer];
* the surface buffer packs only blocks some OTHER shard references —
  the shard-boundary halo (SFC-contiguous shards keep it thin, the
  same locality argument as the reference's SFC rank ranges);
* assembly runs under `shard_map`: pack own surface blocks, one
  `lax.ppermute` per shard offset over the sparse pairs that actually
  send (or one mesh-wide surface all-gather in audit mode), then purely
  local gathers/scatters — including the shard-local FastHalo paint of
  same-level strips whose neighbor lives on the same shard.

The flux-correction fix-up (fine-face deposits added into coarse rows,
main.cpp:1392-1849) gets the identical treatment with face-deposit rows
as the exchanged payload, and the structured per-face Poisson operator
(flux.PoissonOp) rides the same plan with neighbor block rows as the
payload (ShardPoissonOp).

Per-device row counts and surface sizes are padded to power-of-two
buckets so regrids reuse compiled executables (same rationale as
halo.pad_tables); surface buckets are per offset so pod-scale meshes
don't pay the worst pair's bucket on every pair.

Elastic re-mesh contract (PR 7): every plan here is a pure function of
(raw host tables, n_pad, mesh), and the table pytrees carry the mesh —
with every mesh-derived static (offsets, perms, B, S) — as STATIC aux
data (the register_pytree_node below). A survivor re-mesh after a
topology loss (forest_mesh.ShardedAMRSim.remesh) therefore just
rebuilds the plans against the shrunk mesh and the jitted stages
retrace on the new treedef — no stale-mesh executable can ever be
reused, by construction. When the survivor count no longer divides the
pad bucket, the callers fall back to replicated tables exactly as they
would at construction.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..halo import HaloTables, _bucket, _paint_regions, filter_face_rows

from jax import shard_map as _shard_map


class ShardTables(NamedTuple):
    """Per-device halo tables (leaves stacked [D, ...], sharded on the
    mesh axis so each device reads only its own rows inside shard_map).

    Index spaces (per device d owning ordered blocks [dB, dB+B)):
      gather sources: flat cells of [B own blocks ++ received surface
                      blocks (per-offset ppermute buckets, or the D*S
                      all-gather buffer in mode="allgather")]
      scatter dests:  flat cells of [B labs] ++ 1 trailing scratch cell
                      (pad rows write zeros there; dropped on return)

    Neighbor-wise exchange (default): ``offsets`` lists the nonzero
    consumer-minus-owner shard distances that actually occur;
    ``pack[o]`` holds, per owner device, the own-block indices to send
    to owner+offsets[o] (one ``lax.ppermute`` per offset). Per-device
    traffic is sum_o S_o — the device's own shard boundary — instead of
    the all-gather's D*S_max (the GLOBAL boundary), restoring the
    reference's per-neighbor-send scaling law (main.cpp:1971-2142).
    SFC-contiguous shards keep the offset set small (almost always
    {-1, +1}).

    Rows are SPLIT by surface dependence (comm/compute overlap,
    VERDICT r3 missing #3): the *_l row sets read only the device's own
    x_loc and are scattered while the surface exchange is still in
    flight; the *_r sets (every row with at least one remote source)
    consume the received buffer afterwards. The reference overlaps the
    same way — inner blocks compute while halo messages fly
    (main.cpp:864-893 avail_next + computeA 3024-3061).

    Shard-local FastHalo paint (round-5 fast path on the mesh): when
    built with the face-copy structure, ``fc_nb``/``fc_mask`` carry,
    per device and per neighbor offset, the OWN-shard same-level
    neighbor of each own block; the assembly paints those strips with
    structured block-row gathers + static-slice writes (halo._fast_paint
    on the shard), and the covered rows are filtered out of the tables
    host-side (halo.filter_face_rows). Only faces whose same-level
    neighbor lives on ANOTHER shard keep their gather rows — they ride
    the surface exchange like any remote row. The paint reads x_loc
    only, so it is part of the exchange-independent work the scheduler
    can hide the collective behind. ``n_regions`` is 0 (no paint), 4
    (faces only) or 8 (tensorial sets paint corners too).
    """

    pack: tuple           # per-offset [D, S_o] int32 own blocks to export
    src_l: jnp.ndarray    # [D, Gsl] int32 (local-only simple rows)
    sign_l: jnp.ndarray   # [D, Gsl, dim]
    dest_sl: jnp.ndarray  # [D, Gsl] int32
    idx_l: jnp.ndarray    # [D, Ggl, K] int32 (local-only general rows)
    w_l: jnp.ndarray      # [D, Ggl, K, dim]
    dest_l: jnp.ndarray   # [D, Ggl] int32
    src_r: jnp.ndarray    # [D, Gsr] int32 (surface-dependent rows)
    sign_r: jnp.ndarray   # [D, Gsr, dim]
    dest_sr: jnp.ndarray  # [D, Gsr] int32
    idx_r: jnp.ndarray    # [D, Ggr, K] int32
    w_r: jnp.ndarray      # [D, Ggr, K, dim]
    dest_r: jnp.ndarray   # [D, Ggr] int32
    fc_nb: jnp.ndarray    # [D, n_regions, B] int32 own-shard positions
    fc_mask: jnp.ndarray  # [D, n_regions, B] field-dtype 1.0/0.0
    mesh: Mesh
    B: int                # blocks per device
    S: int                # WORST per-offset surface bucket (metadata)
    L: int
    g: int
    dim: int
    offsets: tuple        # static nonzero shard offsets (ppermute mode)
    mode: str             # "ppermute" | "allgather"
    n_regions: int        # 0 = no paint, 4 = faces, 8 = faces+corners
    perms: tuple          # per-offset static (src, dst) sending pairs

    def assemble(self, x: jnp.ndarray) -> jnp.ndarray:
        return _assemble_sharded(x, self)


jax.tree_util.register_pytree_node(
    ShardTables,
    lambda t: ((t.pack, t.src_l, t.sign_l, t.dest_sl, t.idx_l, t.w_l,
                t.dest_l, t.src_r, t.sign_r, t.dest_sr, t.idx_r, t.w_r,
                t.dest_r, t.fc_nb, t.fc_mask),
               (t.mesh, t.B, t.S, t.L, t.g, t.dim, t.offsets, t.mode,
                t.n_regions, t.perms)),
    lambda aux, ch: ShardTables(*ch, *aux),
)


def _build_exchange_plan(remote_by_d, D: int, B: int, n_pad: int,
                         mode: str):
    """Common surface-exchange plan from the per-consumer remote-block
    sets: returns (offsets, S, pack, perms, g2surf) where ``pack`` is a
    TUPLE of per-offset [D, S_o] own-block index arrays (one element
    [D, S] in allgather mode), ``perms`` the per-offset static
    (src, dst) pair lists restricted to pairs that actually send, and
    g2surf[d, gblk] the position of remote block gblk in consumer d's
    received-surface space (-1 if not received). Shared by the halo
    gather, the flux-correction deposit exchange and the structured
    Poisson operator so the plans can never drift (code-review r4).

    Buckets are PER OFFSET and the ppermute perm lists are sparse
    (round 6, VERDICT r5 weak #5): the earlier plan shipped one shared
    power-of-two bucket for every (device, offset) pair, so pod-scale
    meshes — whose SFC shard adjacency has many rare offsets with 1-2
    blocks each — paid bucket-width wire traffic on all of them
    (measured 2.64 -> 4.05 MB/device over 8 -> 64 devices at 1e4
    blocks; 36x padded/real on the 16x16 probe). Per-offset buckets +
    real-pair perms keep padded bytes within a small factor of the
    payload at any device count (tests/test_comm_volume.py bounds it).
    ``S`` stays the WORST per-offset bucket — the shape metadata the
    boundary-proportionality tests key on."""
    if mode == "allgather":
        # one shared surface set per owner, broadcast to every device
        surf_lists: list[list[int]] = [[] for _ in range(D)]
        surf_pos: dict[int, int] = {}
        for d in range(D):
            for gblk in remote_by_d[d].tolist():
                if gblk not in surf_pos:
                    surf_pos[gblk] = len(surf_lists[gblk // B])
                    surf_lists[gblk // B].append(gblk)
        S = _bucket(max((len(x) for x in surf_lists), default=1), lo=4)
        pack0 = np.zeros((D, S), np.int32)
        for e, lst in enumerate(surf_lists):
            pack0[e, :len(lst)] = np.asarray(lst, np.int64) - e * B
        g2surf = np.full((D, n_pad), -1, np.int64)
        for gblk, p in surf_pos.items():
            g2surf[:, gblk] = (gblk // B) * S + p
        return (), S, (pack0,), (), g2surf
    # per (owner, offset) send lists; offset = consumer - owner
    send: dict = {}
    for d in range(D):
        for gblk in remote_by_d[d].tolist():
            e = gblk // B
            send.setdefault((e, d - e), []).append(gblk)
    offsets = tuple(sorted({o for (_, o) in send}))
    S_per = [_bucket(max((len(v) for (e, o), v in send.items()
                          if o == off), default=1), lo=4)
             for off in offsets]
    off_base = np.concatenate([[0], np.cumsum(S_per)]).astype(np.int64)
    pack = tuple(np.zeros((D, s), np.int32) for s in S_per)
    perms = tuple(
        tuple(sorted(e for (e, o) in send if o == off))
        for off in offsets)
    g2surf = np.full((D, n_pad), -1, np.int64)
    for (e, o), lst in send.items():
        oi = offsets.index(o)
        pack[oi][e, :len(lst)] = np.asarray(lst, np.int64) - e * B
        for p, gblk in enumerate(lst):
            g2surf[e + o, gblk] = off_base[oi] + p
    perms = tuple(tuple((e, e + offsets[oi]) for e in srcs)
                  for oi, srcs in enumerate(perms))
    return offsets, max(S_per, default=0), pack, perms, g2surf


def _halo_remote_by_d(t: HaloTables, n_pad: int, D: int):
    """Per-consumer-device remote-block demand of a halo table set —
    the ONE derivation behind both the shipped exchange plan
    (shard_tables) and the host-only padding audit
    (exchange_padding_stats), so the CI padding guard can never audit
    a different plan than the one in production. Also returns the
    derived row/device arrays so shard_tables reuses them instead of
    recomputing per regrid: (remote_by_d, zmask, dev_s, dev_g,
    src_blk, idx_blk)."""
    B = n_pad // D
    bs = t.L - 2 * t.g
    bs2 = bs * bs
    LL = t.L * t.L
    src = np.asarray(t.src_ord, np.int64)
    idx = np.asarray(t.idx_ord, np.int64)
    # zero-weight K-padding entries must not create surface demand
    zmask = (np.asarray(t.w) == 0).all(axis=2)
    dev_s = (np.asarray(t.dest_s, np.int64) // LL) // B
    dev_g = (np.asarray(t.dest, np.int64) // LL) // B
    src_blk = src // bs2
    idx_blk = idx // bs2
    remote_by_d = []
    for d in range(D):
        ref = np.concatenate([
            src_blk[dev_s == d],
            idx_blk[dev_g == d][~zmask[dev_g == d]],
        ])
        remote_by_d.append(
            np.unique(ref[(ref < d * B) | (ref >= (d + 1) * B)]))
    return remote_by_d, zmask, dev_s, dev_g, src_blk, idx_blk


def shard_tables(t: HaloTables, n_pad: int, mesh: Mesh,
                 mode: str = "ppermute", fc=None,
                 corners: bool = True) -> ShardTables:
    """Split (unpadded, numpy-leaf) tables into per-device rows with a
    surface-buffer exchange plan. ``n_pad`` must divide by the mesh
    size (amr buckets are powers of two >= 128).

    mode="ppermute" (default): per-offset neighbor sends; traffic per
    device scales with its OWN shard boundary. mode="allgather": the
    round-3 mesh-wide surface all-gather, kept for the comm-scaling
    audit (validation/comm_audit.py measures both).

    ``fc`` = (nb, mask) from halo.build_face_copy enables the
    shard-local FastHalo paint: the global same-level face-copy mask is
    restricted to pairs living on the SAME shard, the covered rows are
    dropped from the tables (halo.filter_face_rows), and the per-device
    neighbor positions ride along for the structured strip writes.
    Cross-shard same-level faces keep their gather rows (they need the
    surface exchange anyway). ``corners`` follows the table set's
    tensoriality exactly as on the single-device path."""
    D = mesh.devices.size
    assert n_pad % D == 0, (n_pad, D)
    B = n_pad // D
    L, g, dim = t.L, t.g, t.dim
    bs = L - 2 * g
    bs2 = bs * bs
    LL = L * L

    n_regions = 0
    if fc is not None:
        nb_g, mask_g = np.asarray(fc[0]), np.asarray(fc[1])
        n_regions = 8 if corners else 4
        # paint only pairs whose blocks share a shard; nb rows of
        # masked-out entries are dead (gathered then zeroed), so 0 is a
        # safe in-range index
        own_dev = np.arange(n_pad, dtype=np.int64) // B
        same_shard = (nb_g.astype(np.int64) // B) == own_dev[None, :]
        mask_loc = np.where(same_shard, mask_g, 0)
        t = filter_face_rows(t, mask_loc, corners)
        fc_nb_ = np.where(mask_loc > 0, nb_g - (own_dev * B)[None, :],
                          0).astype(np.int32)
        fc_nb_ = fc_nb_[:n_regions].T.reshape(D, B, n_regions) \
            .transpose(0, 2, 1).copy()
        fc_mask_ = mask_loc[:n_regions].T.reshape(D, B, n_regions) \
            .transpose(0, 2, 1).copy()
    else:
        fdt = np.asarray(t.sign).dtype
        fc_nb_ = np.zeros((D, 0, B), np.int32)
        fc_mask_ = np.zeros((D, 0, B), fdt)

    dest_s = np.asarray(t.dest_s, np.int64)
    src = np.asarray(t.src_ord, np.int64)
    sign = np.asarray(t.sign)
    dest = np.asarray(t.dest, np.int64)
    idx = np.asarray(t.idx_ord, np.int64)
    w = np.asarray(t.w)
    K = idx.shape[1]

    # remote blocks referenced by each consumer device, plus the
    # derived row/device arrays (the shared derivation — the padding
    # audit reads the same one)
    (remote_by_d, zmask, dev_s, dev_g,
     src_blk, idx_blk) = _halo_remote_by_d(t, n_pad, D)

    offsets, S, pack, perms, g2surf = _build_exchange_plan(
        remote_by_d, D, B, n_pad, mode)

    def remap_cells(cells, d, dead_local=None):
        blk = cells // bs2
        off = cells % bs2
        local = (blk >= d * B) & (blk < (d + 1) * B)
        sidx = g2surf[d, np.clip(blk, 0, n_pad - 1)]
        out = np.where(local, (blk - d * B) * bs2 + off,
                       (B + sidx) * bs2 + off)
        if dead_local is not None:
            out = np.where(dead_local, 0, out)
        bad = (~local) & (sidx < 0)
        if dead_local is not None:
            bad &= ~dead_local
        assert not bad.any(), "gather source missing from surface set"
        return out

    # -- per-device rows, split local/remote, bucketed -------------------
    # a row is LOCAL iff every (live) gather source is an own block:
    # local rows scatter while the surface exchange is in flight
    def local_s(rows, d):
        blk = src_blk[rows]
        return (blk >= d * B) & (blk < (d + 1) * B)

    def local_g(rows, d):
        blk = idx_blk[rows]                       # [n, K]
        own = (blk >= d * B) & (blk < (d + 1) * B)
        return (own | zmask[rows]).all(axis=1)

    rs_by_d = [np.nonzero(dev_s == d)[0] for d in range(D)]
    rg_by_d = [np.nonzero(dev_g == d)[0] for d in range(D)]
    rs_l = [r[local_s(r, d)] for d, r in enumerate(rs_by_d)]
    rs_r = [r[~local_s(r, d)] for d, r in enumerate(rs_by_d)]
    rg_l = [r[local_g(r, d)] for d, r in enumerate(rg_by_d)]
    rg_r = [r[~local_g(r, d)] for d, r in enumerate(rg_by_d)]

    scratch = B * LL
    f32 = sign.dtype

    def pack_rows(rows_by_d, kind):
        G = _bucket(max(len(r) for r in rows_by_d), lo=4)
        pk_src = np.zeros((D, G) + ((K,) if kind == "g" else ()),
                          np.int32)
        pk_wgt = np.zeros(
            (D, G) + ((K, dim) if kind == "g" else (dim,)), f32)
        pk_dst = np.full((D, G), scratch, np.int32)
        for d, r in enumerate(rows_by_d):
            n = len(r)
            if kind == "s":
                pk_src[d, :n] = remap_cells(src[r], d)
                pk_wgt[d, :n] = sign[r]
                pk_dst[d, :n] = dest_s[r] - d * B * LL
            else:
                pk_src[d, :n] = remap_cells(
                    idx[r], d, dead_local=zmask[r]).reshape(n, K)
                pk_wgt[d, :n] = w[r]
                pk_dst[d, :n] = dest[r] - d * B * LL
        return pk_src, pk_wgt, pk_dst

    src_l_, sign_l_, dest_sl_ = pack_rows(rs_l, "s")
    src_r_, sign_r_, dest_sr_ = pack_rows(rs_r, "s")
    idx_l_, w_l_, dest_l_ = pack_rows(rg_l, "g")
    idx_r_, w_r_, dest_r_ = pack_rows(rg_r, "g")

    return _put_shard_tables(mesh, ShardTables(
        pack=pack,
        src_l=src_l_, sign_l=sign_l_, dest_sl=dest_sl_,
        idx_l=idx_l_, w_l=w_l_, dest_l=dest_l_,
        src_r=src_r_, sign_r=sign_r_, dest_sr=dest_sr_,
        idx_r=idx_r_, w_r=w_r_, dest_r=dest_r_,
        fc_nb=fc_nb_, fc_mask=fc_mask_,
        mesh=mesh, B=B, S=S, L=L, g=g, dim=dim,
        offsets=offsets, mode=mode, n_regions=n_regions, perms=perms,
    ))


def _put_shard_tables(mesh: Mesh, t):
    leaves, treedef = jax.tree_util.tree_flatten(t)
    put = jax.device_put(
        leaves, [NamedSharding(mesh, P("x"))] * len(leaves))
    return jax.tree_util.tree_unflatten(treedef, put)


def _exchange_surface(x_loc, pack, t):
    """Surface-block exchange inside shard_map: per-offset ppermute
    sends (default) or the mesh-wide all-gather (audit mode). ``pack``
    is the per-device tuple of per-offset send indices ([S_o] each).
    Returns the received surface blocks [R, ...] (R = sum_o S_o) to
    append after the B own blocks. Each offset's perm list names only
    the pairs that actually send (devices outside it receive zeros in
    that slot); the issue order matters for overlap: all sends start
    before any consumer indexes the results, so XLA can overlap them
    with the local lab initialization below."""
    D = t.mesh.devices.size
    if t.mode == "allgather":
        surf = x_loc[pack[0]]                       # [S, dim, bs, bs]
        asurf = jax.lax.all_gather(surf, "x")       # [D, S, ...]
        return asurf.reshape((D * t.S,) + x_loc.shape[1:])
    parts = []
    for oi in range(len(t.offsets)):
        buf = x_loc[pack[oi]]                       # [S_o, ...]
        parts.append(jax.lax.ppermute(buf, "x", perm=list(t.perms[oi])))
    if not parts:
        return jnp.zeros((0,) + x_loc.shape[1:], x_loc.dtype)
    return jnp.concatenate(parts, axis=0)           # [sum S_o, ...]


def _assemble_sharded(x: jnp.ndarray, t: ShardTables) -> jnp.ndarray:
    """[n_pad, dim, BS, BS] ordered field -> [n_pad, dim, L, L] labs,
    sharded on the block axis; comm = per-offset neighbor ppermutes
    (or one surface all-gather in audit mode).

    Overlap structure (main.cpp:864-893): the exchange is ISSUED first;
    the lab initialization and every local-only ghost row (the vast
    majority) depend only on x_loc and sit between the collective's
    start and its first consumer in the dependence graph, so the
    scheduler can hide the exchange latency behind them; only the *_r
    rows wait for the received buffer. validation/overlap_check.py
    verifies the compiled schedule actually interleaves."""
    B, L, g, dim = t.B, t.L, t.g, t.dim
    bs = L - 2 * g

    @partial(_shard_map, mesh=t.mesh,
             in_specs=(P("x"),) * 16, out_specs=P("x"))
    def run(x_loc, pack, src_l, sign_l, dest_sl, idx_l, w_l, dest_l,
            src_r, sign_r, dest_sr, idx_r, w_r, dest_r, fc_nb, fc_mask):
        pack = tuple(p[0] for p in pack)
        (src_l, sign_l, dest_sl, idx_l, w_l, dest_l,
         src_r, sign_r, dest_sr, idx_r, w_r, dest_r,
         fc_nb, fc_mask) = (
            a[0] for a in (src_l, sign_l, dest_sl, idx_l, w_l,
                           dest_l, src_r, sign_r, dest_sr, idx_r, w_r,
                           dest_r, fc_nb, fc_mask))
        # 1. exchange in flight
        recv = _exchange_surface(x_loc, pack, t)
        # 2. local work: lab init + the shard-local face-copy paint +
        #    all local-only rows (everything here reads x_loc only, so
        #    it all sits in the exchange's latency-hiding window)
        flat_l = x_loc.transpose(1, 0, 2, 3).reshape(dim, -1)
        simple_l = flat_l[:, src_l].T * sign_l
        general_l = jnp.einsum("dgk,gkd->gd", flat_l[:, idx_l], w_l)
        labs = jnp.zeros((B, dim, L, L), x_loc.dtype)
        labs = labs.at[:, :, g:g + bs, g:g + bs].set(x_loc)
        if t.n_regions:
            # structured same-level strips — the same paint body as the
            # single-device FastHalo path (halo._paint_regions), over
            # the own-shard neighbor indices; uncovered blocks write
            # zeros there and their rows remain in the (filtered)
            # tables below
            labs = _paint_regions(x_loc, labs, fc_nb, fc_mask, g, bs,
                                  t.n_regions == 8)
        lf = labs.transpose(1, 0, 2, 3).reshape(dim, -1)
        lf = jnp.concatenate(
            [lf, jnp.zeros((dim, 1), x_loc.dtype)], axis=1)
        lf = lf.at[:, dest_sl].set(simple_l.T.astype(lf.dtype))
        lf = lf.at[:, dest_l].set(general_l.T.astype(lf.dtype))
        # 3. consume the exchange: surface-dependent rows only
        blocks = jnp.concatenate([x_loc, recv], axis=0)
        flat = blocks.transpose(1, 0, 2, 3).reshape(dim, -1)
        simple_r = flat[:, src_r].T * sign_r
        general_r = jnp.einsum("dgk,gkd->gd", flat[:, idx_r], w_r)
        lf = lf.at[:, dest_sr].set(simple_r.T.astype(lf.dtype))
        lf = lf.at[:, dest_r].set(general_r.T.astype(lf.dtype))
        return lf[:, :-1].reshape(dim, B, L, L).transpose(1, 0, 2, 3)

    return run(x, t.pack, t.src_l, t.sign_l, t.dest_sl, t.idx_l, t.w_l,
               t.dest_l, t.src_r, t.sign_r, t.dest_sr, t.idx_r, t.w_r,
               t.dest_r, t.fc_nb, t.fc_mask)


def exchange_padding_stats(t: HaloTables, n_pad: int, D: int,
                           mode: str = "ppermute") -> dict:
    """Host-only audit of the surface-exchange plan at an ARBITRARY
    simulated device count (no mesh, no devices): how many blocks the
    per-offset ppermute buffers actually carry over the wire (the
    sparse perm pairs x their per-offset buckets) vs the distinct real
    sends. The old shared-bucket plan grew padding with device count
    (VERDICT r5 weak #5: 2.64 -> 4.05 MB/device over 8 -> 64 devices
    on the 1e4-block probe); tests/test_comm_volume.py bounds the
    ratio so a pod-scale padding regression fails CI instead of
    passing silently."""
    assert n_pad % D == 0, (n_pad, D)
    B = n_pad // D
    remote_by_d = _halo_remote_by_d(t, n_pad, D)[0]
    offsets, S, pack, perms, _ = _build_exchange_plan(
        remote_by_d, D, B, n_pad, mode)
    # real payload: each (consumer, remote block) demand is exactly
    # one (owner, offset) send entry in the plan (the plan is BUILT
    # from remote_by_d, whose per-consumer sets are unique), so the
    # count needs no re-derivation that could drift from the plan
    real_blocks = sum(len(r) for r in remote_by_d)
    if mode == "allgather":
        padded_blocks = D * S
    else:
        padded_blocks = sum(
            len(perms[oi]) * pack[oi].shape[1]
            for oi in range(len(offsets)))
    return {
        "D": D, "B": B, "S": S, "offsets": offsets,
        "real_blocks": real_blocks,
        "padded_blocks": padded_blocks,
        "ratio": padded_blocks / max(real_blocks, 1),
    }


# ---------------------------------------------------------------------------
# comm/compute-overlapped Jacobi smoothing on x-split uniform fields
# ---------------------------------------------------------------------------

def overlap_jacobi_sweeps(e: jnp.ndarray, r: jnp.ndarray,
                          inv_d: jnp.ndarray, omega: float, n: int,
                          mesh: Mesh, tier: str = "xla") -> jnp.ndarray:
    """``n`` damped-Jacobi sweeps of the undivided zero-Neumann 5-point
    Laplacian, ``e += omega (r - lap e) inv_d``, on [Ny, Nx] fields
    x-split over ``mesh`` — the smoothing kernel of the FAS multigrid
    solver's sharded path (poisson.MultigridPreconditioner(mesh=...)).

    GSPMD lowers the stencil's shifted slices correctly but owns the
    schedule; this form makes the arXiv:1309.7128 overlap structural:
    each sweep ISSUES the two edge-column ``lax.ppermute``s first (the
    sparse interior pairs — boundary devices receive zeros, exactly the
    zero-ghost the wall stencil wants), then computes the y-direction
    terms and the interior x-columns from purely local data inside the
    exchange's latency-hiding window; only the two ghost-adjacent
    columns consume the received buffers. Same dependence idiom as
    ``_assemble_sharded``/``_poisson_apply_sharded``.

    Arithmetic matches ``ops.stencil.laplacian5_neumann`` termwise
    (xp + xm + yp + ym + p*(edges - 4), ghosts zero, rank-1 edge
    correction), so the sharded sweep agrees with the single-device
    sweep to reordering roundoff (tests/test_poisson.py pins the
    equivalence).

    ``tier`` (ISSUE 19): the grid's smoother tier. "strip" routes each
    sweep through the fused halo strip kernel
    (pallas_kernels.fused_jacobi_halo_sweep) with the SAME
    ppermute-before-dispatch structure — halo columns ride a
    lane-padded aux operand into the kernel, per the PR-16
    fused_advect_heun_sharded pattern; unsupported shapes fall back to
    the GSPMD body below (identical result, an optimization gate)."""
    D = mesh.devices.size
    if tier == "strip":
        from ..ops import pallas_kernels as pk
        nxl = int(e.shape[-1]) // int(D)
        if pk.jacobi_strip_supported(int(e.shape[-2]), nxl, e.dtype, 1):
            return _overlap_jacobi_sweeps_strip(e, r, omega, n, mesh)

    @partial(_shard_map, mesh=mesh,
             in_specs=(P(None, "x"),) * 3, out_specs=P(None, "x"))
    def run(e_loc, r_loc, inv_loc):
        ny, w = e_loc.shape
        idx = jax.lax.axis_index("x")
        dt_ = e_loc.dtype
        iy = jnp.arange(ny)
        ix = jnp.arange(w)
        one = jnp.ones((), dt_)
        zero = jnp.zeros((), dt_)
        ey = jnp.where((iy == 0) | (iy == ny - 1), one, zero)
        # x walls exist only on the boundary devices of the split axis
        ex = (jnp.where(ix == 0, one, zero) * (idx == 0).astype(dt_)
              + jnp.where(ix == w - 1, one, zero)
              * (idx == D - 1).astype(dt_))
        corr = (ey[:, None] + ex[None, :]) - 4.0
        zrow = jnp.zeros((1, w), dt_)

        def sweep(_, ee):
            # 1. exchange in flight: my left ghost is my left
            #    neighbor's last column, my right ghost the right
            #    neighbor's first; devices with no sender get zeros
            gl = jax.lax.ppermute(
                ee[:, -1:], "x", perm=[(d, d + 1) for d in range(D - 1)])
            gr = jax.lax.ppermute(
                ee[:, :1], "x", perm=[(d + 1, d) for d in range(D - 1)])
            # 2. local terms (the latency-hiding window): y shifts and
            #    the x contributions of interior columns read ee only
            yp = jnp.concatenate([ee[1:, :], zrow], axis=0)
            ym = jnp.concatenate([zrow, ee[:-1, :]], axis=0)
            # 3. ghost-adjacent columns consume the received buffers
            xp = jnp.concatenate([ee[:, 1:], gr], axis=1)
            xm = jnp.concatenate([gl, ee[:, :-1]], axis=1)
            lap = xp + xm + yp + ym + ee * corr
            return ee + omega * (r_loc - lap) * inv_loc

        return jax.lax.fori_loop(0, n, sweep, e_loc)

    return run(e, r, inv_d)


def _overlap_jacobi_sweeps_strip(e: jnp.ndarray, r: jnp.ndarray,
                                 omega: float, n: int,
                                 mesh: Mesh, interpret=None) -> jnp.ndarray:
    """Strip-tier body of ``overlap_jacobi_sweeps``: per sweep, issue
    the two edge-column ppermutes FIRST, then dispatch the fused halo
    strip kernel over the local slab (one read of (e, r), one write per
    sweep — the sharded chain cannot time-skew across sweeps because
    each needs fresh neighbor columns). The wall-diagonal corr/inv_d
    are rebuilt in-kernel from the (is_lo, is_hi) SMEM row, the same
    values as the GSPMD body's device-index-masked indicators, so both
    tiers agree to reordering roundoff."""
    from ..ops import pallas_kernels as pk
    D = mesh.devices.size
    if interpret is None:
        interpret = not pk._on_accel()
    pad_w = 2 * pk._GX - 2

    # check_vma=False: shard_map has no replication rule for
    # pallas_call (the fused_advect_heun_sharded precedent)
    @partial(_shard_map, mesh=mesh,
             in_specs=(P(None, "x"),) * 2, out_specs=P(None, "x"),
             check_vma=False)
    def run(e_loc, r_loc):
        idx = jax.lax.axis_index("x")
        i32 = jnp.int32
        info = jnp.stack([(idx == 0).astype(i32),
                          (idx == D - 1).astype(i32)])[None, :]

        def sweep(_, ee):
            gl = jax.lax.ppermute(
                ee[..., -1:], "x",
                perm=[(d, d + 1) for d in range(D - 1)])
            gr = jax.lax.ppermute(
                ee[..., :1], "x",
                perm=[(d + 1, d) for d in range(D - 1)])
            aux = jnp.pad(jnp.concatenate([gl, gr], axis=-1),
                          ((0, 0), (0, pad_w)))
            return pk.fused_jacobi_halo_sweep(ee, r_loc, aux, info,
                                              omega,
                                              interpret=interpret)

        return jax.lax.fori_loop(0, n, sweep, e_loc)

    return run(e, r)


# ---------------------------------------------------------------------------
# comm/compute-overlapped megakernel substages on x-split velocity
# (tentpole, ISSUE 16)
# ---------------------------------------------------------------------------

def fused_advect_heun_sharded(vel, h, nu, dt, mesh: Mesh, *, bc=None,
                              bf16: bool = False, interpret=None):
    """Both Heun substages of the fused megakernel tier on an x-split
    velocity — the mesh-aware twin of
    ``ops.pallas_kernels.fused_advect_heun``.

    Each substage ISSUES the two 3-wide edge-column ``lax.ppermute``s
    for the WENO halo FIRST (the ``overlap_jacobi_sweeps`` idiom,
    arXiv:1309.7128 — boundary devices receive zeros), then dispatches
    the interior strip pipeline; the received columns are fused inside
    the kernel as the boundary strips' ghost source
    (``_fused_substage_sharded``), so the exchange latency hides behind
    the kernel body. The halo is exchanged in the STORAGE dtype (bf16
    on the bf16 tier) — exactly the columns the solo kernel reads from
    its own ring — so the sharded trajectory is termwise-identical to
    the GSPMD chain. The BCTable (default free-slip) is static; wall
    shards where-select the x-face ghost paint over the non-received
    halo columns, interior shards never branch.

    vel: [..., 2, Ny, Nx] with Nx divisible by the mesh size; dt:
    scalar or leading-shaped (per-member). Returns the substage-2
    velocity in vel's shape/dtype."""
    from ..bc import BCTable
    from ..ops import pallas_kernels as pk

    if bc is None or bc.is_free_slip:
        bc = BCTable()
    # capability gate (names face/kind/token): periodic ('pd') tables
    # refuse here — the halo exchange is a 3-wide NEIGHBOR ppermute
    # with zero-filled boundary shards, and a wrap ghost would need a
    # ring permute plus a wrap-aware strip pipeline neither kernel
    # has. The same x-split is why CUP2D_POIS=fftd refuses
    # attach_mesh: it would shard the FFT transform axis (periodic x)
    # or the tridiagonal scan axis (periodic y). Run sharded periodic
    # cases under the XLA tier with bicgstab/fas.
    pk.kernel_supports(bc)
    lead = vel.shape[:-3]
    L = pk._flatten_lead(lead)
    v = vel.reshape((L,) + vel.shape[-3:])
    dtv = pk._per_member(dt, lead, L)
    hh = float(h)
    facs = jnp.stack([-dtv * hh, nu * dtv, dtv], axis=-1)   # [L, 3] f32
    ih2 = 1.0 / (hh * hh)
    if interpret is None:
        interpret = not pk._on_accel()
    D = int(mesh.devices.size)
    nx = v.shape[-1]
    if nx % D:
        raise ValueError(
            f"fused_advect_heun_sharded: Nx={nx} not divisible by the "
            f"mesh size D={D}")
    nxl = nx // D
    g = pk._G
    pad_w = 2 * pk._GX - 2 * g   # halo operand lane-padded to 128

    # check_vma=False: shard_map has no replication rule for
    # pallas_call; every output is explicitly sharded on "x" anyway
    @partial(_shard_map, mesh=mesh,
             in_specs=(P(None, None, None, "x"), P(None, None)),
             out_specs=P(None, None, None, "x"), check_vma=False)
    def run(vb, facsb):
        idx = jax.lax.axis_index("x")
        i32 = jnp.int32
        info = jnp.stack([(idx == 0).astype(i32),
                          (idx == D - 1).astype(i32),
                          (idx * nxl).astype(i32)])[None, :]

        def halo(a):
            # exchange first (storage dtype): my left halo is my left
            # neighbor's last g columns, my right halo the right
            # neighbor's first g; wall devices receive zeros (replaced
            # in-kernel by the x-face BC paint)
            hl = jax.lax.ppermute(
                a[..., -g:], "x", perm=[(d, d + 1) for d in range(D - 1)])
            hr = jax.lax.ppermute(
                a[..., :g], "x", perm=[(d + 1, d) for d in range(D - 1)])
            aux = jnp.concatenate([hl, hr], axis=-1)        # [L,2,ny,2g]
            return jnp.pad(aux, ((0, 0), (0, 0), (0, 0), (0, pad_w)))

        def sub(stage_v, vold, cfac, out_dtype):
            return pk._fused_substage_sharded(
                stage_v, vold, halo(stage_v), info, facsb, cfac, ih2,
                out_dtype, bc, hh, nx, interpret)

        if bf16:
            v0 = vb.astype(jnp.bfloat16)
            v1 = sub(v0, None, 0.5, jnp.bfloat16)
            return sub(v1, v0, 1.0, vb.dtype)
        v1 = sub(vb, None, 0.5, vb.dtype)
        return sub(v1, vb, 1.0, vb.dtype)

    return run(v, facs).reshape(vel.shape)


# ---------------------------------------------------------------------------
# structured per-face Poisson operator across shards (round 5 on the mesh)
# ---------------------------------------------------------------------------

class ShardPoissonOp(NamedTuple):
    """Per-device rows of flux.PoissonOp behind the surface exchange.

    The structured operator's only data-dependent reads are its 2
    block-row gathers per face (``nba``/``nbb``); on the mesh those are
    remapped into the [B own blocks ++ received surface blocks] space
    of the SAME per-offset ppermute plan the halo gather uses
    (_build_exchange_plan), so the Krylov loop's per-iteration traffic
    stays shard-boundary-proportional — no whole-field GSPMD
    collectives (the reason forest_mesh kept the round-4 lab-table
    operator until now). The strip math is flux._structured_lap, the
    ONE body shared with the single-device apply: every tangential
    matmul reduces over BS only, so per-block-row results are
    bit-identical across device counts. Exposes ``nba`` so
    amr._pressure_project's structured-operator dispatch works
    unchanged on one device and on eight."""

    pack: tuple            # per-offset [D, S_o] int32 own blocks to export
    nba: jnp.ndarray       # [D, 4, B] int32 into [B own ++ received]
    nbb: jnp.ndarray       # [D, 4, B]
    m_same: jnp.ndarray    # [D, 4, B] case one-hots
    m_coarse: jnp.ndarray  # [D, 4, B]
    m_fine: jnp.ndarray    # [D, 4, B]
    m_wall: jnp.ndarray    # [D, 4, B]
    par: jnp.ndarray       # [D, 4, B]
    wc0: jnp.ndarray       # [BS, BS] static tangential maps, replicated
    wc1: jnp.ndarray
    mcl: jnp.ndarray       # [2, BS, BS]
    mfr: jnp.ndarray       # [2, BS, BS]
    d2own: jnp.ndarray     # [BS, BS]
    mesh: Mesh
    B: int
    S: int
    bs: int
    offsets: tuple
    mode: str
    perms: tuple

    def apply(self, x: jnp.ndarray) -> jnp.ndarray:
        return _poisson_apply_sharded(x, self)


jax.tree_util.register_pytree_node(
    ShardPoissonOp,
    lambda t: ((t.pack, t.nba, t.nbb, t.m_same, t.m_coarse, t.m_fine,
                t.m_wall, t.par, t.wc0, t.wc1, t.mcl, t.mfr, t.d2own),
               (t.mesh, t.B, t.S, t.bs, t.offsets, t.mode, t.perms)),
    lambda aux, ch: ShardPoissonOp(*ch, *aux),
)


def shard_poisson_op(op, n_pad: int, mesh: Mesh,
                     mode: str = "ppermute") -> ShardPoissonOp:
    """Split a (numpy-leaf) flux.PoissonOp into per-device rows + a
    surface exchange plan. Surface demand = the live (non-wall,
    non-pad) neighbor positions of each device's own rows that fall
    outside its shard — the face-neighbor subset of the halo sets'
    demand, so S is bounded by the same shard boundary."""
    D = mesh.devices.size
    assert n_pad % D == 0, (n_pad, D)
    B = n_pad // D
    nba = np.asarray(op.nba, np.int64)          # [4, n_pad]
    nbb = np.asarray(op.nbb, np.int64)
    m_same = np.asarray(op.m_same)
    m_coarse = np.asarray(op.m_coarse)
    m_fine = np.asarray(op.m_fine)
    m_wall = np.asarray(op.m_wall)
    par = np.asarray(op.par)
    # a gather index is live iff some case mask actually consumes it
    # (wall/pad faces keep the n_real sentinel — dead, remapped to 0)
    live_a = (m_same + m_coarse + m_fine) > 0   # [4, n_pad]
    live_b = m_fine > 0                         # nbb only feeds g_fine

    remote_by_d = []
    for d in range(D):
        sl = slice(d * B, (d + 1) * B)
        refs = np.concatenate([nba[:, sl][live_a[:, sl]],
                               nbb[:, sl][live_b[:, sl]]])
        remote_by_d.append(
            np.unique(refs[(refs < d * B) | (refs >= (d + 1) * B)]))

    offsets, S, pack, perms, g2surf = _build_exchange_plan(
        remote_by_d, D, B, n_pad, mode)

    def remap(pos, live, d):
        local = (pos >= d * B) & (pos < (d + 1) * B)
        sidx = g2surf[d, np.clip(pos, 0, n_pad - 1)]
        out = np.where(local, pos - d * B, B + sidx)
        out = np.where(live, out, 0)
        assert not (live & ~local & (sidx < 0)).any(), \
            "gather source missing from surface set"
        return out

    nba_l = np.zeros((D, 4, B), np.int32)
    nbb_l = np.zeros((D, 4, B), np.int32)
    for d in range(D):
        sl = slice(d * B, (d + 1) * B)
        nba_l[d] = remap(nba[:, sl], live_a[:, sl], d)
        nbb_l[d] = remap(nbb[:, sl], live_b[:, sl], d)

    def per_dev(a):
        return np.ascontiguousarray(
            np.asarray(a).reshape(4, D, B).transpose(1, 0, 2))

    shard = NamedSharding(mesh, P("x"))
    repl = NamedSharding(mesh, P())
    pack = jax.device_put(list(pack), [shard] * len(pack))
    rows = jax.device_put(
        [nba_l, nbb_l, per_dev(m_same), per_dev(m_coarse),
         per_dev(m_fine), per_dev(m_wall), per_dev(par)], [shard] * 7)
    mats = jax.device_put(
        [np.asarray(op.wc0), np.asarray(op.wc1), np.asarray(op.mcl),
         np.asarray(op.mfr), np.asarray(op.d2own)], [repl] * 5)
    return ShardPoissonOp(tuple(pack), *rows, *mats, mesh=mesh, B=B,
                          S=S, bs=int(np.asarray(op.wc0).shape[0]),
                          offsets=offsets, mode=mode, perms=perms)


def overlap_block_jacobi_sweeps(e: jnp.ndarray, r: jnp.ndarray,
                                p_inv: jnp.ndarray, t: ShardPoissonOp,
                                n: int, tier: str = "xla") -> jnp.ndarray:
    """``n`` composite block-Jacobi sweeps ``e += P_inv (r - A e)`` on
    the block-sharded forest — the finest-level smoother of the forest
    FAS solver (poisson.ForestFASCycle via
    forest_mesh.ShardedAMRSim._fas_block_smoother), with the
    block-surface exchange latency made hideable: ONE shard_map whose
    per-sweep body ISSUES the per-offset surface ppermutes first
    (_exchange_surface), computes the within-block 5-point part and
    every own-neighbor strip from purely local data inside the
    collective's latency window, and consumes the received buffer only
    in the remote-neighbor gathers; the P_inv GEMM is shard-local.
    Extends ``overlap_jacobi_sweeps``'s issue-comms-first structure
    (arXiv:1309.7128) from the uniform x-split to the forest's
    block-surface exchange.

    Arithmetic is TERMWISE identical to the unoverlapped composition
    ``e + apply_block_precond_blocks(r - A(e), p_inv)`` with
    A = ``_poisson_apply_sharded``: the sweep body runs the same
    flux._structured_lap strip math over the same [own ++ received]
    gather space and the same GEMM, so sweeps agree with the
    single-shard_map-per-sweep form to the last bit
    (tests/test_forest_mesh.py pins <= 1e-12).

    ``tier`` (ISSUE 19): "strip"/"fused" fuses the smoother's own
    traffic — residual subtract, P_inv GEMM, update add — into one
    Pallas pass per sweep (pallas_kernels.fused_block_jacobi_update),
    dispatched AFTER the same exchange-first _structured_lap window;
    f64 (and Pallas-less hosts) keep the XLA composition."""
    from ..flux import _structured_lap
    use_fused = False
    if tier != "xla":
        from ..ops import pallas_kernels as pk
        use_fused = pk.block_update_supported(e.dtype)
        interpret = not pk._on_accel()

    @partial(_shard_map, mesh=t.mesh,
             in_specs=(P("x"),) * 10 + (P(),) * 6, out_specs=P("x"),
             check_vma=not use_fused)
    def run(e0, r_loc, pack, nba, nbb, ms, mc, mf, mw, par,
            p_inv_r, wc0, wc1, mcl, mfr, d2own):
        pack = tuple(p[0] for p in pack)
        nba, nbb, ms, mc, mf, mw, par = (
            a[0] for a in (nba, nbb, ms, mc, mf, mw, par))
        B, bs_, _ = e0.shape

        def sweep(_, ee):
            # 1. exchange in flight
            recv = _exchange_surface(ee, pack, t)
            # 2. local window: own-block stencil + own-neighbor strips
            #    (blocks[:B] = ee) — _structured_lap's gathers of local
            #    sources depend only on ee; 3. remote-sourced strips
            #    consume recv
            blocks = jnp.concatenate([ee, recv], axis=0)
            lap = _structured_lap(ee, blocks, nba, nbb, ms, mc, mf,
                                  mw, par, (wc0, wc1, mcl, mfr, d2own))
            if use_fused:
                return pk.fused_block_jacobi_update(
                    ee, r_loc, lap, p_inv_r, interpret=interpret)
            z = ((r_loc - lap).reshape(B, bs_ * bs_)
                 @ p_inv_r.T).reshape(B, bs_, bs_)
            return ee + z

        return jax.lax.fori_loop(0, n, sweep, e0)

    return run(e, r, t.pack, t.nba, t.nbb, t.m_same, t.m_coarse,
               t.m_fine, t.m_wall, t.par, p_inv, t.wc0, t.wc1,
               t.mcl, t.mfr, t.d2own)


def _poisson_apply_sharded(x: jnp.ndarray, t: ShardPoissonOp):
    """A(x) for [n_pad, BS, BS] ordered x sharded on the block axis:
    issue the surface exchange, then run the shared structured strip
    math over [own ++ received] gather space. The own-edge strips and
    the within-block 5-point part read x_loc only, so they sit in the
    exchange's latency-hiding window exactly like the halo assembly's
    local rows."""
    from ..flux import _structured_lap

    @partial(_shard_map, mesh=t.mesh,
             in_specs=(P("x"),) * 9 + (P(),) * 5, out_specs=P("x"))
    def run(x_loc, pack, nba, nbb, ms, mc, mf, mw, par,
            wc0, wc1, mcl, mfr, d2own):
        pack = tuple(p[0] for p in pack)
        nba, nbb, ms, mc, mf, mw, par = (
            a[0] for a in (nba, nbb, ms, mc, mf, mw, par))
        recv = _exchange_surface(x_loc, pack, t)
        blocks = jnp.concatenate([x_loc, recv], axis=0)
        return _structured_lap(x_loc, blocks, nba, nbb, ms, mc, mf, mw,
                               par, (wc0, wc1, mcl, mfr, d2own))

    return run(x, t.pack, t.nba, t.nbb, t.m_same, t.m_coarse, t.m_fine,
               t.m_wall, t.par, t.wc0, t.wc1, t.mcl, t.mfr, t.d2own)


# ---------------------------------------------------------------------------
# flux correction (fine-face deposits -> coarse rows) across shards
# ---------------------------------------------------------------------------

class ShardFluxCorr(NamedTuple):
    """Per-device flux-correction rows. Deposit index space per device:
    [B own blocks ++ received surface blocks] x 4 faces x BS; value
    dests are local cells [B*BS*BS] ++ 1 scratch. Exchange modes as in
    ShardTables."""

    pack: tuple          # per-offset [D, S_o] blocks whose deposits export
    dest: jnp.ndarray    # [D, M]
    cidx: jnp.ndarray    # [D, M]
    fidx1: jnp.ndarray   # [D, M]
    fidx2: jnp.ndarray   # [D, M]
    valid: jnp.ndarray   # [D, M]
    mesh: Mesh
    B: int
    S: int
    bs: int
    offsets: tuple
    mode: str
    perms: tuple

    def apply(self, values, deposits):
        return _apply_corr_sharded(values, deposits, self)


jax.tree_util.register_pytree_node(
    ShardFluxCorr,
    lambda t: ((t.pack, t.dest, t.cidx, t.fidx1, t.fidx2, t.valid),
               (t.mesh, t.B, t.S, t.bs, t.offsets, t.mode, t.perms)),
    lambda aux, ch: ShardFluxCorr(*ch, *aux),
)


def shard_flux_corr(corr, n_pad: int, mesh: Mesh, bs: int,
                    dtype=np.float32,
                    mode: str = "ppermute") -> ShardFluxCorr:
    """Split (unpadded) FluxCorrTables by owning coarse block."""
    D = mesh.devices.size
    assert n_pad % D == 0
    B = n_pad // D
    bs2 = bs * bs
    fb = 4 * bs                                   # deposit cells/block
    dest = np.asarray(corr.dest, np.int64)
    cidx = np.asarray(corr.cidx, np.int64)
    f1 = np.asarray(corr.fidx1, np.int64)
    f2 = np.asarray(corr.fidx2, np.int64)
    dev = (dest // bs2) // B

    remote_by_d = []
    for d in range(D):
        ref = np.concatenate([a[dev == d] // fb for a in (cidx, f1, f2)])
        remote_by_d.append(
            np.unique(ref[(ref < d * B) | (ref >= (d + 1) * B)]))

    offsets, S, pack, perms, g2surf = _build_exchange_plan(
        remote_by_d, D, B, n_pad, mode)

    def remap_dep(cells, d):
        blk = cells // fb
        off = cells % fb
        local = (blk >= d * B) & (blk < (d + 1) * B)
        sidx = g2surf[d, np.clip(blk, 0, n_pad - 1)]
        assert not ((~local) & (sidx < 0)).any()
        return np.where(local, (blk - d * B) * fb + off,
                        (B + sidx) * fb + off)

    M = _bucket(max(int((dev == d).sum()) for d in range(D)), lo=4)
    scratch = B * bs2
    pk_dest = np.full((D, M), scratch, np.int32)
    pk_c = np.zeros((D, M), np.int32)
    pk_f1 = np.zeros((D, M), np.int32)
    pk_f2 = np.zeros((D, M), np.int32)
    pk_v = np.zeros((D, M), dtype)
    for d in range(D):
        r = np.nonzero(dev == d)[0]
        n = len(r)
        pk_dest[d, :n] = dest[r] - d * B * bs2
        pk_c[d, :n] = remap_dep(cidx[r], d)
        pk_f1[d, :n] = remap_dep(f1[r], d)
        pk_f2[d, :n] = remap_dep(f2[r], d)
        pk_v[d, :n] = 1.0
    return _put_shard_tables(mesh, ShardFluxCorr(
        pack=pack, dest=pk_dest, cidx=pk_c, fidx1=pk_f1, fidx2=pk_f2,
        valid=pk_v, mesh=mesh, B=B, S=S, bs=bs,
        offsets=offsets, mode=mode, perms=perms,
    ))


def _apply_corr_sharded(values, deposits, t: ShardFluxCorr):
    B, bs = t.B, t.bs
    vec = values.ndim == 4

    @partial(_shard_map, mesh=t.mesh,
             in_specs=(P("x"),) * 8, out_specs=P("x"))
    def run(v_loc, d_loc, pack, dest, cidx, f1, f2, valid):
        pack = tuple(p[0] for p in pack)
        dest, cidx, f1, f2, valid = (
            a[0] for a in (dest, cidx, f1, f2, valid))
        recv = _exchange_surface(d_loc, pack, t)
        dep = jnp.concatenate([d_loc, recv], axis=0)
        if vec:
            dim = v_loc.shape[1]
            df = dep.reshape(-1, dim)
            corr = valid[:, None].astype(v_loc.dtype) * (
                df[cidx] + df[f1] + df[f2])
            flat = v_loc.transpose(0, 2, 3, 1).reshape(-1, dim)
            flat = jnp.concatenate(
                [flat, jnp.zeros((1, dim), v_loc.dtype)], axis=0)
            out = flat.at[dest].add(corr)[:-1]
            return out.reshape(B, bs, bs, dim).transpose(0, 3, 1, 2)
        df = dep.reshape(-1)
        corr = valid.astype(v_loc.dtype) * (df[cidx] + df[f1] + df[f2])
        flat = jnp.concatenate(
            [v_loc.reshape(-1), jnp.zeros((1,), v_loc.dtype)])
        return flat.at[dest].add(corr)[:-1].reshape(B, bs, bs)

    return run(values, deposits, t.pack, t.dest, t.cidx, t.fidx1,
               t.fidx2, t.valid)
