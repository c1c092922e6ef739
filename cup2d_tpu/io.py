"""Field dumps (reference-format), checkpoint/restore, diagnostics.

Dump writes the exact on-disk triplet of the reference's `dump()`
(`/root/reference/main.cpp:3367-3467`): per-cell quad geometry as float32
``.xyz.raw`` (4 corners x 2 coords, (x0,y0),(x0,y1),(x1,y1),(x1,y0)),
cell attributes as float32 ``.attr.raw`` (u, v, 0 triplets), and an
``.xdmf2`` XML index — byte-compatible with the reference's `post.py`
renderer and any XDMF2 reader (ParaView).

Checkpoint/restore is a capability the reference lacks entirely
(SURVEY.md §5: `dump()` has no reader, no restart path): the full
simulation state — fields, time/step counters, shape objects including
scheduler state — round-trips through one ``.npz`` + pickle pair.
"""

from __future__ import annotations

import json
import os
import pickle
from typing import NamedTuple

import numpy as np


# ---------------------------------------------------------------------------
# multi-host (pod) support: the reference dumps collectively from every
# rank via MPI-IO (main.cpp:3367-3467, MPI_File_write_at_all with
# MPI_Exscan offsets; the XDMF index written by the last rank). The pod
# equivalent here: every process joins ONE gather collective that
# replicates the sharded field on hosts, then process 0 alone writes
# the files (which must live on shared storage, the same assumption
# MPI-IO makes). Single-host runs take the plain np.asarray path.
# ---------------------------------------------------------------------------

def _multihost() -> bool:
    import jax
    return jax.process_count() > 1


def _to_host_global(x) -> np.ndarray:
    """Full host copy of a (possibly cross-host-sharded) array. A
    COLLECTIVE on pods: every process must call it, in the same order.

    Must be an OWNING copy, never a view: np.asarray of a CPU-backend
    jax array is zero-copy, and the StepGuard's snapshot ring
    (resilience.py) holds these arrays across steps whose jits DONATE
    the state buffers — a view into a donated buffer dangles once XLA
    reuses the memory (observed as heap corruption in the supervised
    CLI loop)."""
    if _multihost():
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.array(x)


def _is_writer() -> bool:
    import jax
    return jax.process_index() == 0


def _sync_processes(tag: str) -> None:
    """Barrier so non-writer processes cannot race past an incomplete
    checkpoint/dump (no-op single-host)."""
    if _multihost():
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(tag)

_XDMF_TEMPLATE = """<Xdmf
    Version="2.0">
  <Domain>
    <Grid>
      <Time Value="{time:.16e}"/>
      <Topology
          Dimensions="{ncell}"
          TopologyType="Quadrilateral"/>
     <Geometry
         GeometryType="XY">
       <DataItem
           Dimensions="{npoint} 2"
           Format="Binary">
         {xyz_base}
       </DataItem>
     </Geometry>
       <Attribute
           AttributeType="Vector"
           Name="vort"
           Center="Cell">
         <DataItem
             Dimensions="3 {ncell}"
             Format="Binary">
           {attr_base}
         </DataItem>
       </Attribute>
    </Grid>
  </Domain>
</Xdmf>
"""


def _write_quads(path: str, time: float, xg, yg, x1, y1, u, v) -> None:
    """Emit the reference dump triplet from per-cell corner/value arrays
    (any shape; raveled in C order). (x0,y0),(x0,y1),(x1,y1),(x1,y0)
    corner order, (u, v, 0) attr triplets — main.cpp:3367-3467."""
    ncell = int(np.prod(np.shape(u)))
    xyz = np.empty((ncell, 4, 2), dtype=np.float32)
    xyz[:, 0, 0] = np.ravel(xg); xyz[:, 0, 1] = np.ravel(yg)
    xyz[:, 1, 0] = np.ravel(xg); xyz[:, 1, 1] = np.ravel(y1)
    xyz[:, 2, 0] = np.ravel(x1); xyz[:, 2, 1] = np.ravel(y1)
    xyz[:, 3, 0] = np.ravel(x1); xyz[:, 3, 1] = np.ravel(yg)

    attr = np.zeros((ncell, 3), dtype=np.float32)
    attr[:, 0] = np.ravel(u)
    attr[:, 1] = np.ravel(v)

    xyz.tofile(path + ".xyz.raw")
    attr.tofile(path + ".attr.raw")
    with open(path + ".xdmf2", "w") as f:
        f.write(_XDMF_TEMPLATE.format(
            time=time, ncell=ncell, npoint=4 * ncell,
            xyz_base=os.path.basename(path) + ".xyz.raw",
            attr_base=os.path.basename(path) + ".attr.raw",
        ))


def dump_uniform(path: str, time: float, vel, h: float,
                 origin=(0.0, 0.0)) -> None:
    """Write a uniform-grid velocity field in the reference dump format.

    vel: [2, Ny, Nx] (numpy or jax array). Cells are emitted in row-major
    (y-outer) order, like the reference's per-block x-inner loop.
    """
    vel = np.asarray(vel, dtype=np.float64)
    _, ny, nx = vel.shape
    x0 = origin[0] + np.arange(nx) * h
    y0 = origin[1] + np.arange(ny) * h
    xg, yg = np.meshgrid(x0, y0, indexing="xy")   # [ny, nx]
    _write_quads(path, time, xg, yg, xg + h, yg + h, vel[0], vel[1])


def dump_forest(path: str, time: float, forest, order=None) -> None:
    """Write an adaptive forest's velocity in the reference dump format.

    The format is per-cell quads precisely so resolution can vary
    (main.cpp:3367-3467 writes one quad per cell of every block); blocks
    are emitted in SFC order, cells y-outer/x-inner within each block —
    the reference's own emission order."""
    order = forest.order() if order is None else order
    bs = forest.bs
    n = len(order)
    # collective on pods (every process calls; process 0 writes below).
    # The [order] gather runs on DEVICE before the host transfer —
    # identical order arrays on every process keep it SPMD-valid, and
    # the host only ever sees the active blocks, not the padded bucket.
    vel = _to_host_global(forest.fields["vel"][np.asarray(order)])
    if not _is_writer():
        _sync_processes("dump_forest")
        return
    vel = vel.astype(np.float64)

    h = forest.cfg.h0 / (1 << forest.level[order]).astype(np.float64)
    ar = np.arange(bs, dtype=np.float64)
    x0b = forest.bi[order].astype(np.float64) * bs * h
    y0b = forest.bj[order].astype(np.float64) * bs * h
    shape = (n, bs, bs)
    xg = np.broadcast_to(
        x0b[:, None, None] + ar[None, None, :] * h[:, None, None], shape)
    yg = np.broadcast_to(
        y0b[:, None, None] + ar[None, :, None] * h[:, None, None], shape)
    x1 = xg + h[:, None, None]
    y1 = yg + h[:, None, None]
    _write_quads(path, time, xg, yg, x1, y1, vel[:, 0], vel[:, 1])
    _sync_processes("dump_forest")


def read_dump(path: str):
    """Read back a dump triplet -> (time, xyz [ncell,4,2], attr [ncell,3]).
    The reader the reference never had."""
    import xml.etree.ElementTree as ET

    time = float(ET.parse(path + ".xdmf2").find("Domain/Grid/Time")
                 .get("Value"))
    xyz = np.fromfile(path + ".xyz.raw", dtype=np.float32).reshape(-1, 4, 2)
    attr = np.fromfile(path + ".attr.raw", dtype=np.float32).reshape(-1, 3)
    return time, xyz, attr


# ---------------------------------------------------------------------------
# checkpoint / restore (beyond-parity, SURVEY.md §5)
# ---------------------------------------------------------------------------

def _gather_state(sim):
    """Collect the full checkpoint payload (host numpy fields) + meta
    dict. The shared gather half of ``save_checkpoint`` and the host
    :class:`Snapshot` machinery — one machinery, so a restore installs
    EXACTLY what a disk restore would. COLLECTIVE on pods (field
    all-gathers); every process must call it in the same order.

    This is the FULL D2H state gather — since the device snapshot ring
    (:func:`snapshot_state_device`) took over the StepGuard's rewind
    path, it runs only for disk checkpoints and post-mortems, never per
    step; ``profiling.HostCounters.state_gathers`` counts invocations
    so the CI sync guard can assert exactly that."""
    from .profiling import _note_state_gather
    _note_state_gather()
    if hasattr(sim, "sync_fields"):
        # the adaptive driver's per-step truth is its ordered working
        # state; flush it into the slot-layout dict read below
        sim.sync_fields()
    # collectives FIRST, identical order on every process
    if hasattr(sim, "forest"):
        # adaptive: topology as (level, i, j) keys + fields in SFC order
        # (slot numbering is an allocator detail that need not survive)
        f = sim.forest
        order = f.order()
        keys = np.stack([f.level[order], f.bi[order], f.bj[order]],
                        axis=1).astype(np.int32)
        # device-side [order] gather before the host transfer (active
        # blocks only; identical order on all processes keeps the
        # collective valid)
        oj = np.asarray(order)
        fields = {k: _to_host_global(v[oj])
                  for k, v in sorted(f.fields.items())}
        payload = {"__forest_keys": keys, **fields}
    else:
        payload = {k: _to_host_global(v)
                   for k, v in sim.state._asdict().items()}
    meta = {
        "time": sim.time,
        "step_count": sim.step_count,
        "config": {k: v for k, v in vars(sim.cfg).items()
                   if not k.startswith("_")},
    }
    if hasattr(sim, "times"):
        # fleet driver (fleet.FleetSim): per-member clocks must survive
        # the checkpoint — sim.time is only their min
        meta["fleet"] = {"members": int(sim.members),
                         "times": [float(t) for t in sim.times]}
    if hasattr(sim, "forest") and hasattr(sim, "_next_dt"):
        # the cached next-dt state must SURVIVE the checkpoint, or a
        # restart right after a regrid takes compute_dt's post-regrid
        # umax while the uninterrupted run takes 1.05x the cached
        # pre-regrid umax — a dt fork the bit-exact-resume contract
        # forbids. 'current' records whether each cache matched the
        # topology at save time (version counters don't survive a
        # rebuild, the boolean does).
        fver = sim.forest.version
        meta["dt_cache"] = {
            "next_dt": sim._next_dt,
            "next_dt_current": bool(
                sim._next_dt is not None
                and sim._next_dt_version == fver),
            "next_umax": (float(sim._next_umax)
                          if sim._next_umax is not None else None),
            "next_umax_current": bool(
                sim._next_umax is not None
                and getattr(sim, "_next_umax_version", -1) == fver),
        }
    if hasattr(sim, "_coarse_on"):
        # the production two-level trigger state must survive too, for
        # the same same-branch contract: a restore that re-arms the
        # trigger would run its first production solve on plain
        # block-Jacobi (up to hundreds of iterations at 1e4 blocks)
        # with a DIFFERENT preconditioner than the uninterrupted run
        # (ADVICE r4 medium). The coarse maps themselves rebuild
        # lazily (_use_coarse). A pending device iters scalar is
        # drained first so the persisted count is the latest one.
        if getattr(sim, "_last_iters_dev", None) is not None:
            import jax
            sim._last_iters = int(jax.device_get(sim._last_iters_dev))
            sim._last_iters_dev = None
        meta["poisson_trigger"] = {
            "coarse_on": bool(sim._coarse_on),
            "last_iters": int(sim._last_iters),
        }
    return payload, meta


def save_checkpoint(dirpath: str, sim) -> None:
    """Serialize a Simulation (or UniformSim) to ``dirpath``.

    Written to a sibling temp dir and renamed into place so a crash
    mid-save (the very event checkpointing exists for) can't destroy the
    previous restart point. On a multi-host pod this is a COLLECTIVE:
    every process must call it (the field gathers are all-gathers);
    process 0 alone writes, to storage all processes can read back
    (the reference's MPI-IO dump makes the same shared-FS assumption),
    and a barrier keeps the others from racing past an incomplete
    save."""
    payload, meta = _gather_state(sim)
    if not _is_writer():
        _sync_processes("save_checkpoint")
        return
    tmp = dirpath.rstrip("/") + ".tmp"
    if os.path.exists(tmp):
        import shutil
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    np.savez(os.path.join(tmp, "fields.npz"), **payload)
    shapes = getattr(sim, "shapes", [])
    with open(os.path.join(tmp, "shapes.pkl"), "wb") as f:
        pickle.dump(shapes, f)
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f, indent=1)
    # swap order matters for crash safety: park the old checkpoint aside,
    # move the new one into place, THEN delete the old — at every instant
    # either dirpath or dirpath+'.old' holds a complete checkpoint (a
    # rmtree-before-replace window would leave neither; ADVICE.md r1)
    old = dirpath.rstrip("/") + ".old"
    import shutil
    if os.path.exists(old):
        shutil.rmtree(old)
    if os.path.exists(dirpath):
        os.replace(dirpath, old)
    # fault-injection window (faults.crash_point is a no-op unless a
    # FaultPlan armed crash_in_save): the instant where NEITHER rename
    # has completed — dirpath absent, dirpath.old complete — which
    # load_checkpoint's .old fallback must cover (tested in
    # tests/test_resilience.py::test_crash_mid_save_restores_old)
    from . import faults
    faults.crash_point("checkpoint_install")
    os.replace(tmp, dirpath)
    if os.path.exists(old):
        shutil.rmtree(old)
    _sync_processes("save_checkpoint")


def load_checkpoint(dirpath: str, sim) -> None:
    """Restore state saved by save_checkpoint into ``sim`` (built with a
    matching config/grid). Falls back to ``dirpath.old`` when a save
    crashed between parking the previous checkpoint and installing the
    new one — loudly: the fallback means the run lost its newest
    restart point, which the operator (and the resilience event log)
    must know about."""
    import sys

    if not os.path.exists(os.path.join(dirpath, "meta.json")):
        old = dirpath.rstrip("/") + ".old"
        if os.path.exists(os.path.join(old, "meta.json")):
            print(f"cup2d_tpu: checkpoint {dirpath!r} is missing or "
                  f"incomplete; falling back to parked copy {old!r} "
                  "(a save crashed between park and install)",
                  file=sys.stderr)
            from .resilience import record_event
            record_event(event="checkpoint_fallback_old",
                         requested=dirpath, used=old)
            dirpath = old
    with open(os.path.join(dirpath, "meta.json")) as f:
        meta = json.load(f)
    shapes = None
    shapes_path = os.path.join(dirpath, "shapes.pkl")
    if os.path.exists(shapes_path):
        with open(shapes_path, "rb") as f:
            shapes = pickle.load(f)
    with np.load(os.path.join(dirpath, "fields.npz")) as data:
        _install_state(sim, data, meta, shapes)


def _install_state(sim, data, meta: dict, shapes) -> None:
    """Install a gathered payload (``data``: name -> array mapping, an
    open npz or a snapshot dict) + meta + shapes into ``sim``. The
    shared install half of ``load_checkpoint`` and the StepGuard's
    in-RAM rewind (resilience.py)."""
    import jax.numpy as jnp

    # counters BEFORE the field restore: the _refresh() below branches
    # on step_count (a production-stage restore with the counter still
    # at 0 would eagerly build the ~50 MB two-level coarse maps the
    # lazy-trigger design defers — code-review r5)
    sim.time = float(meta["time"])
    sim.step_count = int(meta["step_count"])
    if "__forest_keys" in data:
        f = sim.forest
        for key in list(f.blocks):
            f.release(*key)
        keys = data["__forest_keys"]
        slots = np.asarray(
            [f.allocate(int(l), int(i), int(j)) for (l, i, j) in keys],
            np.int32)
        for name in f.fields:
            vals = jnp.asarray(data[name], dtype=f.dtype)
            f.fields[name] = jnp.zeros(
                (f.capacity,) + vals.shape[1:], f.dtype
            ).at[jnp.asarray(slots)].set(vals)
        if hasattr(sim, "_ord"):
            # the restored slot fields are now the truth — discard
            # the ordered-state cache outright. Leaving _ord_dirty
            # set would make the next _ordered_state() raise, and
            # its advice (sync_fields) would overwrite the freshly
            # restored fields with pre-restore data (ADVICE r3).
            # The key is re-anchored (not None-ed) at the restored
            # (version, wver) so a field write BETWEEN restore and
            # the first step still trips the wver-moved branch that
            # drops the restored dt cache — _ordered_state()'s
            # invalidation is guarded by _ord_key being non-None.
            sim._ord = None
            sim._ord_dirty = False
            if hasattr(sim, "_refresh"):
                # refresh BEFORE anchoring: an exactly-full forest
                # makes the first _refresh_impl call _grow(), whose
                # field reassignments move wver — anchoring at the
                # pre-refresh wver would then spuriously drop the
                # restored dt cache below
                sim._refresh()
            sim._ord_key = (f.version, f.fields.wver)
    else:
        # jnp.array (copy=True), NOT jnp.asarray: asarray zero-copies a
        # matching-dtype numpy buffer on the CPU backend, and these
        # arrays become the state the stepping jits DONATE — a donated
        # alias of numpy-owned (npz-extracted, not XLA-aligned) memory
        # intermittently corrupts the heap (pre-PR2 the restart CLI
        # path crashed ~50% of runs with SIGSEGV/"corrupted
        # double-linked list"). The forest branch is safe as-is: its
        # gathered values land in fresh .at[].set outputs.
        sim.state = type(sim.state)(**{
            k: jnp.array(data[k], dtype=sim.grid.dtype)
            for k in sim.state._fields
        })
    # restore the cached next-dt state (or clear it for checkpoints
    # predating dt_cache): the restart must take the SAME dt branch as
    # the uninterrupted run (see save_checkpoint)
    for attr, cleared in (("_next_dt", None), ("_next_umax", None),
                          ("_next_dt_version", -1),
                          ("_next_umax_version", -1)):
        if hasattr(sim, attr):
            setattr(sim, attr, cleared)
    dtc = meta.get("dt_cache")
    if dtc and hasattr(sim, "forest") and hasattr(sim, "_next_dt"):
        fver = sim.forest.version
        if dtc.get("next_dt") is not None:
            sim._next_dt = float(dtc["next_dt"])
            sim._next_dt_version = fver if dtc["next_dt_current"] else -1
        if dtc.get("next_umax") is not None:
            sim._next_umax = float(dtc["next_umax"])
            sim._next_umax_version = (
                fver if dtc["next_umax_current"] else -1)
    # restored AFTER the _refresh() above (which re-arms the trigger
    # from scratch): the restart's first production solve must take the
    # same preconditioner branch as the uninterrupted run (ADVICE r4)
    trig = meta.get("poisson_trigger")
    if trig and hasattr(sim, "_coarse_on"):
        sim._coarse_on = bool(trig["coarse_on"])
        sim._last_iters = int(trig["last_iters"])
        sim._last_iters_dev = None
    fl = meta.get("fleet")
    if hasattr(sim, "times"):
        if fl is not None:
            if int(fl["members"]) != int(sim.members):
                raise ValueError(
                    f"checkpoint holds {fl['members']} fleet members, "
                    f"sim has {sim.members}")
            sim.times = np.asarray(fl["times"], np.float64)
        else:
            # pre-fleet checkpoint restored into a fleet: every member
            # inherits the shared clock
            sim.times = np.full(sim.members, float(meta["time"]))
        sim.time = float(sim.times.min())
    if hasattr(sim, "shapes") and shapes is not None:
        sim.shapes[:] = shapes
        sim._initialized = True  # fields already hold the blended state


# ---------------------------------------------------------------------------
# per-member session checkpoints (fleet serving, PR 11)
# ---------------------------------------------------------------------------
# A serving client's session must survive its slot: the FleetServer
# saves one of these on retire and a FleetRequest(checkpoint=...) admits
# from it — into ANY live fleet, any slot, bit-exact (state, the
# member's own clock, and its chained dt all round-trip losslessly; the
# f64 JSON repr round-trip is exact, and a float of an f32 lane is
# exact in double both ways). Layout mirrors save_checkpoint
# (fields.npz + meta.json, tmp -> park -> replace install) but holds
# ONE member's solo-shaped slice, so the same session can also be
# resumed standalone.

def save_member_checkpoint(dirpath: str, sim, m: int) -> None:
    """Serialize fleet member ``m``'s session to ``dirpath``."""
    st = sim.member_state(m)
    payload = {k: _to_host_global(v)
               for k, v in st._asdict().items()}
    meta = {
        "kind": "member",
        "time": float(sim.times[m]),
        "step_count": int(sim.step_count),
        "config": {k: v for k, v in vars(sim.cfg).items()
                   if not k.startswith("_")},
        "next_dt": (float(np.asarray(sim._next_dt)[m])
                    if sim._next_dt is not None else None),
    }
    if not _is_writer():
        _sync_processes("save_member_checkpoint")
        return
    import shutil
    tmp = dirpath.rstrip("/") + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    np.savez(os.path.join(tmp, "fields.npz"), **payload)
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f, indent=1)
    # same crash-safe swap order as save_checkpoint: at every instant
    # either dirpath or dirpath+'.old' holds a complete session
    old = dirpath.rstrip("/") + ".old"
    if os.path.exists(old):
        shutil.rmtree(old)
    if os.path.exists(dirpath):
        os.replace(dirpath, old)
    os.replace(tmp, dirpath)
    if os.path.exists(old):
        shutil.rmtree(old)
    _sync_processes("save_member_checkpoint")


def load_member_checkpoint(dirpath: str, grid):
    """Read a member session -> (solo FlowState, meta dict). ``grid``
    supplies the dtype (the state is cast exactly as a fleet admission
    would install it); falls back to ``dirpath.old`` like
    load_checkpoint."""
    import sys

    import jax.numpy as jnp

    if not os.path.exists(os.path.join(dirpath, "meta.json")):
        old = dirpath.rstrip("/") + ".old"
        if os.path.exists(os.path.join(old, "meta.json")):
            print(f"cup2d_tpu: member checkpoint {dirpath!r} missing or "
                  f"incomplete; falling back to parked copy {old!r}",
                  file=sys.stderr)
            from .resilience import record_event
            record_event(event="checkpoint_fallback_old",
                         requested=dirpath, used=old)
            dirpath = old
    with open(os.path.join(dirpath, "meta.json")) as f:
        meta = json.load(f)
    if meta.get("kind") != "member":
        raise ValueError(
            f"{dirpath!r} is not a member session checkpoint "
            f"(kind={meta.get('kind')!r})")
    from .uniform import FlowState
    with np.load(os.path.join(dirpath, "fields.npz")) as data:
        # jnp.array (copy) for the same donation-safety reason as
        # _install_state: the admitted slice feeds executables that
        # donate their operands
        st = FlowState(**{k: jnp.array(data[k], dtype=grid.dtype)
                          for k in FlowState._fields})
    return st, meta


# ---------------------------------------------------------------------------
# device-resident snapshots (the StepGuard's HBM ring, resilience.py)
# ---------------------------------------------------------------------------
# The PR-2 host ring gathered the full state to host RAM per good step
# — a real per-step D2H tax and host sync (the former ROADMAP
# pod gap (b)). The device snapshots keep the ring IN HBM: entries are
# donation-safe jnp copies of the state pytree (no transfer — the copy
# is enqueued on the device stream before the next step's jit donates
# the source buffers, so stream order guarantees it reads pre-donation
# data), restore is a device-to-device copy back, and the host only
# ever sees the small meta scalars. Host gathers remain exactly where
# they belong: disk checkpoints and post-mortems (_gather_state).
#
# Multi-host: jnp copies of sharded arrays are per-shard local (no
# collective, unlike the host gather) and restores reinstall the same
# sharding — the ring is pod-safe by construction.


def device_copy(x):
    """Donation-safe device-to-device copy of a jax array (host leaves
    pass through as numpy copies). ``copy=True`` guarantees a fresh XLA
    buffer: the stepping jits DONATE the state, so a ring entry must
    never alias a buffer a later dispatch invalidates — and a restored
    entry must survive being donated itself."""
    import jax
    import jax.numpy as jnp

    if isinstance(x, jax.Array):
        return jnp.array(x, copy=True)
    return np.array(x)


class DeviceSnapshot(NamedTuple):
    """One state in HBM: device copies of the field pytree + host meta.

    ``dev`` carries the dt-cache entries that are device scalars at
    capture time (the async/lagged drivers keep ``_next_dt`` /
    ``_next_umax`` / ``_last_iters_dev`` on device — float()ing them
    here would be exactly the blocking sync the ring exists to kill).
    ``meta['time']`` is patched by the StepGuard at verdict time on the
    lagged paths (the host clock is settled one step behind capture)."""

    payload: dict        # field name -> device array copy
    meta: dict           # host scalars (+ forest keys for topo restore)
    dev: dict            # dt-cache entries still on device
    shapes_pkl: object   # bytes | None
    mirror: object = None  # MirroredSnapshot | None (host-redundant tier)


def _split_cache(meta: dict, dev: dict, name: str, val) -> None:
    """File a dt-cache value under meta (host) or dev (device copy)."""
    import jax

    if isinstance(val, jax.Array):
        dev[name] = device_copy(val)
    elif val is not None:
        meta[name] = float(val)


def snapshot_state_device(sim) -> "DeviceSnapshot":
    """Capture ``sim`` into an HBM-resident snapshot: zero host
    transfers, zero collectives. The forest topology metadata is host
    numpy already (level/bi/bj); the ordered device fields are copied
    in place."""
    meta = {"time": sim.time, "step_count": sim.step_count}
    dev: dict = {}
    if hasattr(sim, "forest"):
        f = sim.forest
        ordf = sim._ordered_state()
        payload = {k: device_copy(v) for k, v in ordf.items()}
        order = sim._order
        meta.update(
            kind="forest",
            forest_version=f.version,
            n_real=int(sim._n_real),
            keys=np.stack([f.level[order], f.bi[order], f.bj[order]],
                          axis=1).astype(np.int32),
            next_dt_current=bool(sim._next_dt is not None
                                 and sim._next_dt_version == f.version),
            next_umax_current=bool(
                sim._next_umax is not None
                and getattr(sim, "_next_umax_version", -1) == f.version),
            last_iters=int(sim._last_iters),
            coarse_on=bool(sim._coarse_on),
        )
        _split_cache(meta, dev, "next_dt", sim._next_dt)
        _split_cache(meta, dev, "next_umax", sim._next_umax)
        if sim._last_iters_dev is not None:
            dev["last_iters_dev"] = device_copy(sim._last_iters_dev)
    else:
        payload = {k: device_copy(v)
                   for k, v in sim.state._asdict().items()}
        meta["kind"] = "uniform"
        if hasattr(sim, "times"):
            # fleet: per-member clocks ride the snapshot (host numpy —
            # the FleetStepGuard settles them at verdict time exactly
            # like the scalar clock)
            meta["times"] = np.array(sim.times)
        _split_cache(meta, dev, "next_dt", getattr(sim, "_next_dt", None))
    shapes = getattr(sim, "shapes", None)
    return DeviceSnapshot(
        payload=payload, meta=meta, dev=dev,
        shapes_pkl=pickle.dumps(list(shapes)) if shapes else None)


def snapshot_nbytes(snap) -> int:
    """HBM footprint of one snapshot's field payload (host metadata on
    the arrays — no sync)."""
    return int(sum(getattr(v, "nbytes", 0)
                   for v in snap.payload.values()))


def _restore_cache(sim, snap: DeviceSnapshot, fver=None) -> None:
    meta, dev = snap.meta, snap.dev
    if hasattr(sim, "_next_dt"):
        nd = dev.get("next_dt")
        sim._next_dt = (device_copy(nd) if nd is not None
                        else meta.get("next_dt"))
        if hasattr(sim, "_next_dt_version"):
            sim._next_dt_version = (
                fver if meta.get("next_dt_current") else -1)
    if hasattr(sim, "_next_umax"):
        nu = dev.get("next_umax")
        sim._next_umax = (device_copy(nu) if nu is not None
                          else meta.get("next_umax"))
        sim._next_umax_version = (
            fver if meta.get("next_umax_current") else -1)
    if hasattr(sim, "_last_iters"):
        sim._last_iters = int(meta.get("last_iters", 0))
        li = dev.get("last_iters_dev")
        sim._last_iters_dev = device_copy(li) if li is not None else None
    if hasattr(sim, "_coarse_on"):
        sim._coarse_on = bool(meta.get("coarse_on", False))


def restore_snapshot_device(sim, snap: DeviceSnapshot) -> None:
    """Install a device snapshot back into ``sim``, device-to-device.

    Same-topology restores (the only ones the StepGuard's ladder issues
    — the guard re-anchors its ring after every regrid) install copies
    of the ordered working state directly. A topology-mismatched
    snapshot falls back to the full reinstall path (_install_state fed
    device arrays — still device-to-device for the fields; only the
    dt-cache scalars are resolved to host floats there)."""
    meta = snap.meta
    if meta["kind"] == "forest":
        f = sim.forest
        if meta["forest_version"] == f.version and sim._ord is not None \
                and next(iter(snap.payload.values())).shape[0] \
                == next(iter(sim._ord.values())).shape[0]:
            sim.time = float(meta["time"])
            sim.step_count = int(meta["step_count"])
            sim._ord = {k: device_copy(v)
                        for k, v in snap.payload.items()}
            # the restored ordered state is now the truth; the slot
            # fields are stale until the next sync_fields()
            sim._ord_key = (f.version, f.fields.wver)
            sim._ord_dirty = True
            _restore_cache(sim, snap, fver=f.version)
        else:
            # topology moved since capture: rebuild through the shared
            # install half (counters-before-refresh ordering and the
            # _ord re-anchor live there). Device scalars in the cache
            # must become floats for the meta dict — one cold pull.
            import jax
            dev = {k: float(np.asarray(v))
                   for k, v in jax.device_get(snap.dev).items()}
            m2 = {
                "time": meta["time"], "step_count": meta["step_count"],
                "dt_cache": {
                    "next_dt": dev.get("next_dt", meta.get("next_dt")),
                    "next_dt_current": meta["next_dt_current"],
                    "next_umax": dev.get("next_umax",
                                         meta.get("next_umax")),
                    "next_umax_current": meta["next_umax_current"],
                },
                "poisson_trigger": {
                    "coarse_on": meta["coarse_on"],
                    "last_iters": int(dev.get("last_iters_dev",
                                              meta["last_iters"])),
                },
            }
            n_real = meta["n_real"]
            data = {"__forest_keys": meta["keys"],
                    **{k: v[:n_real] for k, v in snap.payload.items()}}
            shapes = (pickle.loads(snap.shapes_pkl)
                      if snap.shapes_pkl is not None else None)
            _install_state(sim, data, m2, shapes)
            return
    else:
        sim.time = float(meta["time"])
        sim.step_count = int(meta["step_count"])
        if hasattr(sim, "times") and "times" in meta:
            sim.times = np.array(meta["times"])
            sim.time = float(sim.times.min())
        sim.state = type(sim.state)(
            **{k: device_copy(v) for k, v in snap.payload.items()})
        _restore_cache(sim, snap)
    if getattr(sim, "shapes", None) and snap.shapes_pkl is not None:
        sim.shapes[:] = pickle.loads(snap.shapes_pkl)
        sim._initialized = True


# ---------------------------------------------------------------------------
# elastic topology resume (PR 7): snapshot coverage + re-sharding restore
# ---------------------------------------------------------------------------

def snapshot_covers(snap, lost_processes=(), *, lost_hosts=(),
                    shards_destroyed=False, mirror=True) -> bool:
    """True iff a :class:`DeviceSnapshot` can seed an elastic resume
    after a topology loss: every payload shard must still be readable
    from its OWNER, or (mirror-aware coverage, the host-redundant tier)
    from the ring neighbor that holds its mirror.

    The snapshot payload is per-shard-local device copies (the module
    note above), so the owner rule is addressability: a shard held only
    by a LOST process died with it, and on a multi-host pod each
    process only ever addresses its own shards — a real host loss
    therefore fails the owner check for any cross-host-sharded state.
    SIMULATED topologies (a single process whose virtual devices are
    grouped into fake hosts, resilience.TopologyGuard(sim_hosts=...))
    keep every shard addressable; ``shards_destroyed=True`` is the
    simulated real-loss semantics (the ``shard_loss@N`` injector zeroed
    the lost hosts' slices), voiding owner coverage the way a real loss
    would.

    Mirror coverage (``mirror=True``, the default): a shard whose owner
    died is still covered when the snapshot carries a
    :class:`MirroredSnapshot` and the lost host's ring neighbor — the
    holder of its mirror block — is itself alive. ``lost_hosts`` names
    the dead hosts by ring index (simulated hosts or real processes;
    both ride the same contiguous-block ring). Pass ``mirror=False`` to
    ask about the owner-only (plain ring) rung."""
    import jax

    lost = set(lost_processes)
    dead = set(lost_hosts) | lost
    owner_ok = not (shards_destroyed and dead)
    if owner_ok:
        for v in snap.payload.values():
            if isinstance(v, jax.Array):
                if not v.is_fully_addressable:
                    owner_ok = False
                    break
                if lost and any(d.process_index in lost
                                for d in v.sharding.device_set):
                    owner_ok = False
                    break
    if owner_ok:
        return True
    m = getattr(snap, "mirror", None)
    if not mirror or m is None or not dead:
        return False
    # every dead host's mirror holder (ring neighbor) must be alive —
    # two adjacent losses take a block AND its only mirror
    for h in dead:
        if (h + 1) % m.n_hosts in dead:
            return False
    # and the holders' mirror slices must be readable from here. On the
    # simulated drill every shard stays addressable; after a REAL
    # process loss cross-host arrays stop being fully addressable until
    # the survivor runtime re-inits (launch.reinit_distributed — the
    # ROADMAP real-pod remainder), so real-mode mirror coverage is
    # honest about that prerequisite rather than promising a read that
    # would hang
    for v in m.payload.values():
        if isinstance(v, jax.Array) and not v.is_fully_addressable:
            return False
    return True


def restore_snapshot_resharded(sim, snap: "DeviceSnapshot") -> None:
    """Install a device snapshot into a sim whose MESH changed since
    capture (resilience.StepGuard.elastic_recover, after
    ``sim.remesh``): the standard device-to-device install first (its
    copies land with the capture-time placement), then every field is
    re-placed onto the sim's current mesh — the re-shard the elastic
    resume owes the rebuilt step executable. Works for both families:
    the uniform state goes back through ``set_state`` (the placement
    authority), the forest's ordered working state through
    ``_put_ordered``. Only valid where :func:`snapshot_covers` said so
    — re-sharding reads every source shard."""
    restore_snapshot_device(sim, snap)
    if hasattr(sim, "forest"):
        if sim._ord is not None:
            sim._ord = {k: sim._put_ordered(v)
                        for k, v in sim._ord.items()}
    elif hasattr(sim, "set_state"):
        sim.set_state(sim.state)
    nd = getattr(sim, "_next_dt", None)
    if nd is not None and hasattr(nd, "sharding"):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = getattr(sim, "mesh", None)
        if mesh is not None:
            sim._next_dt = jax.device_put(
                nd, NamedSharding(mesh, PartitionSpec()))


# ---------------------------------------------------------------------------
# host-redundant mirrored snapshot tier (PR 17): in-HBM recovery from
# REAL host loss. A per-shard-local ring entry dies with its host; the
# mirror tier additionally ships every host's contiguous shard block to
# its ring neighbor at capture time (the host-granular shard_map
# ppermute of parallel.mesh.host_ring_shift, fused with the checksums
# into ONE launch — _mirror_capture_fn — and enqueued off the critical
# path before the next dispatch donates the source buffers),
# checksummed per host block ON DEVICE so the capture stays
# transfer-free and a torn/corrupt mirror is detected at restore time
# rather than installed. Lose host
# h: its block still lives (physically) on host h+1, realigned at
# restore by the global-roll identity the exchange satisfies.
# ---------------------------------------------------------------------------


class MirroredSnapshot(NamedTuple):
    """The neighbor-held redundancy of one :class:`DeviceSnapshot`.

    ``payload[k]`` has the SAME global shape and sharding as the
    snapshot field it mirrors, but globally rolled by one host-block
    width (+Nx/H columns): the slice physically resident on host h's
    devices is host h-1's data. ``sums[k]`` is the capture-time [H]
    uint32 bitwise checksum vector, one entry per physical host block,
    kept on device until a restore actually needs the comparison."""

    payload: dict        # field name -> ring-shifted device array
    sums: dict           # field name -> [H] uint32 device checksums
    n_hosts: int         # ring size at capture time


def _block_sums(v, h: int):
    """Trace body of the per-host-block checksum: reshape the x axis
    into (H, W) blocks, bitcast to uint32, wrap-sum every axis but the
    block one (mod 2**32) — any byte flip in a block moves its entry."""
    import jax
    import jax.numpy as jnp

    w = v.shape[-1] // h
    vr = v.reshape(v.shape[:-1] + (h, w))
    bits = jax.lax.bitcast_convert_type(vr, jnp.uint32)
    axes = tuple(i for i in range(bits.ndim) if i != vr.ndim - 2)
    return jnp.sum(bits, axis=axes, dtype=jnp.uint32)


_mirror_sums_jit = None


def _mirror_block_sums(x, n_hosts: int):
    """[H] uint32 bitwise checksum of ONE x-split field (see
    :func:`_block_sums`) — a device-side reduction, zero host
    transfers. Single-field unit/test entry point; multi-field callers
    must use :func:`_mirror_block_sums_tree` so all fields share one
    launch (collective-ordering contract below)."""
    global _mirror_sums_jit
    import jax

    if _mirror_sums_jit is None:
        from . import tracing
        _mirror_sums_jit = tracing.named_jit(
            "io.mirror_sums",
            jax.jit(_block_sums, static_argnums=(1,)))
    return _mirror_sums_jit(x, n_hosts)


_mirror_sums_tree_jit = None


def _mirror_block_sums_tree(payload: dict, n_hosts: int) -> dict:
    """Block checksums of EVERY field in ONE jitted launch. The sum of
    a sharded block spans that host's device pair, so each field's
    checksum compiles to per-host-group collectives; fusing the fields
    into one executable keeps those collectives in one consistent
    per-device schedule (the CPU client runs independent launches out
    of order — see :func:`mirror_snapshot`)."""
    global _mirror_sums_tree_jit
    import jax

    if _mirror_sums_tree_jit is None:
        from . import tracing
        _mirror_sums_tree_jit = tracing.named_jit(
            "io.mirror_sums_tree", jax.jit(
                lambda pl, h: {k: _block_sums(v, h)
                               for k, v in pl.items()},
                static_argnums=(1,)))
    return _mirror_sums_tree_jit(payload, n_hosts)


# fused capture executables: one per (mesh, host count, field-rank
# signature) — the capture runs per snapshot, so the jit must be
# reused, never rebuilt (same rule as mesh._RING_SHIFT_CACHE)
_MIRROR_CAPTURE_CACHE: dict = {}


def _mirror_capture_fn(mesh, n_hosts: int, sig: tuple):
    """Build (or fetch) the ONE-launch capture executable: every
    field's ring shift (a single shard_map issuing the host-granular
    ppermute per field — the same (i, i+D/H) perm as
    parallel.mesh.host_ring_shift) and every field's block checksums,
    inside one jitted program. One launch is a CORRECTNESS requirement,
    not a dispatch micro-optimisation: the shift's CollectivePermute
    and the checksums' per-host-group reductions are collectives, and
    the PJRT CPU client may execute independent launches out of order
    per device — per-field launches can interleave into a cross-launch
    rendezvous deadlock (observed in the CLI drill). Inside one
    program every device runs the same collective schedule."""
    import jax
    from jax.sharding import PartitionSpec as P
    from .parallel.mesh import _shard_map

    key = (mesh, int(n_hosts), sig)
    fn = _MIRROR_CAPTURE_CACHE.get(key)
    if fn is None:
        n_dev = mesh.devices.size
        dph = n_dev // n_hosts
        perm = [(i, (i + dph) % n_dev) for i in range(n_dev)]
        specs = {k: P(*([None] * (nd - 1) + ["x"])) for k, nd in sig}

        def _shift_all(pl):
            return {k: jax.lax.ppermute(v, "x", perm=perm)
                    for k, v in pl.items()}

        shift = _shard_map(_shift_all, mesh=mesh,
                           in_specs=(specs,), out_specs=specs)

        def impl(pl):
            mirrored = shift(pl)
            sums = {k: _block_sums(v, n_hosts)
                    for k, v in mirrored.items()}
            return mirrored, sums

        from . import tracing
        fn = tracing.named_jit("io.mirror_capture", jax.jit(impl))
        _MIRROR_CAPTURE_CACHE[key] = fn
    return fn


def mirror_snapshot(snap: DeviceSnapshot, mesh, n_hosts: int):
    """Capture the host-redundant mirror of ``snap``: ring-shift every
    payload field one host block to the right + per-block checksums,
    all in ONE launch (:func:`_mirror_capture_fn`). Device-only (no
    pulls, no host staging); returns None for payloads the tier does
    not cover (the forest family's padded block-leading layout keeps
    its disk rung for real losses — documented in the README
    recoverability matrix)."""
    import jax

    if snap.meta.get("kind") != "uniform":
        return None
    for v in snap.payload.values():
        if not (isinstance(v, jax.Array) and v.ndim >= 2
                and v.shape[-1] % n_hosts == 0):
            return None
    sig = tuple(sorted((k, v.ndim) for k, v in snap.payload.items()))
    fn = _mirror_capture_fn(mesh, int(n_hosts), sig)
    payload, sums = fn(dict(snap.payload))
    if jax.default_backend() == "cpu":
        # capture fence, CPU ONLY: the next step's dispatch reads the
        # same state arrays this capture read, so its halo collectives
        # are launch-order independent of ours — and the CPU client
        # honors no cross-launch device order, so the two can deadlock
        # at rendezvous. Settling the (tiny, [H] uint32) checksum
        # outputs settles the whole capture program before anything
        # else is enqueued. TPU streams execute launches in enqueue
        # order per device, so the fence (and the hazard) don't exist
        # there and the capture stays off the critical path.
        for s in sums.values():
            s.block_until_ready()
    return MirroredSnapshot(payload=payload, sums=sums,
                            n_hosts=int(n_hosts))


def mirror_nbytes(snap) -> int:
    """HBM footprint of a snapshot's mirror payload (0 when absent) —
    host metadata on the arrays, no sync."""
    m = getattr(snap, "mirror", None)
    if m is None:
        return 0
    return int(sum(getattr(v, "nbytes", 0) for v in m.payload.values()))


def _lost_col_mask(nx: int, lost_hosts, n_hosts: int) -> np.ndarray:
    """Boolean [nx] mask of the x-columns owned by the lost hosts
    (contiguous block h*Nx/H .. (h+1)*Nx/H, the TopologyGuard's host
    grouping)."""
    w = nx // n_hosts
    mask = np.zeros(nx, bool)
    for h in lost_hosts:
        mask[h * w:(h + 1) * w] = True
    return mask


def verify_mirror(snap: DeviceSnapshot, lost_hosts) -> list:
    """Checksum the mirror blocks an elastic restore would install (the
    lost hosts' holder blocks) against their capture-time sums. Returns
    the rejects — [] means the mirror is installable. ONE batched
    device_get of the small checksum vectors; runs only on the cold
    restore path, never per step."""
    import jax

    m = snap.mirror
    holders = sorted({(h + 1) % m.n_hosts for h in lost_hosts})
    current = _mirror_block_sums_tree(dict(m.payload), m.n_hosts)
    expect, actual = jax.device_get((m.sums, current))
    bad = []
    for k in sorted(m.payload):
        for h in holders:
            if int(expect[k][h]) != int(actual[k][h]):
                bad.append({"field": k, "block": int(h),
                            "expected": int(expect[k][h]),
                            "actual": int(actual[k][h])})
    return bad


def corrupt_mirror(snap: DeviceSnapshot) -> bool:
    """Fault injector (faults.py ``mirror_corrupt@N``): flip one
    element's bit pattern in EVERY host block of every mirror field —
    sign-flip of the block's first element, which moves that block's
    uint32 word sum whatever the value (the ±0.0 patterns differ too) —
    WITHOUT touching the stored checksums, so the next verify_mirror
    rejects whichever holder block a restore asks about. Returns False
    when the snapshot carries no mirror."""
    m = snap.mirror
    if m is None:
        return False
    payload = {}
    for k, v in m.payload.items():
        w = v.shape[-1] // m.n_hosts
        idx = (0,) * (v.ndim - 1) + (slice(None, None, w),)
        payload[k] = v.at[idx].multiply(-1.0)
    snap.mirror.payload.clear()
    snap.mirror.payload.update(payload)
    return True


def destroy_shards(sim, snaps, lost_hosts, n_hosts: int) -> list:
    """The simulated real-loss semantics (faults.py ``shard_loss@N``):
    ZERO the lost hosts' x-column blocks in the live state and in every
    snapshot — payloads AND the mirror slices physically resident on
    the dead hosts — exactly what a real host loss takes to the grave.
    This is what makes the CPU drill honest: after it runs, a resumed
    trajectory can only have come from surviving mirror blocks (or
    disk), never from the "lost" originals. Returns the replaced
    snapshot list (DeviceSnapshot is immutable); mutates ``sim.state``
    in place."""
    import jax.numpy as jnp

    def wipe(v):
        mask = jnp.asarray(_lost_col_mask(v.shape[-1], lost_hosts,
                                          n_hosts))
        return jnp.where(mask, 0, v)

    if hasattr(sim, "state") and not hasattr(sim, "forest"):
        sim.state = type(sim.state)(
            **{k: wipe(v) for k, v in sim.state._asdict().items()})
    out = []
    for s in snaps:
        payload = {k: wipe(v) for k, v in s.payload.items()}
        m = s.mirror
        if m is not None:
            m = m._replace(payload={k: wipe(v)
                                    for k, v in m.payload.items()})
        out.append(s._replace(payload=payload, mirror=m))
    return out


def restore_snapshot_mirrored(sim, snap: DeviceSnapshot,
                              lost_hosts) -> None:
    """The mirrored-ring rung: reconstruct the lost hosts' shard blocks
    from their ring neighbors' mirror slices, then install through the
    standard re-sharding restore. The mirror is globally
    roll(x, +Nx/H), so roll(mirror, -Nx/H) realigns it; the lost
    columns are taken from the realigned mirror, everything else from
    the (still-owned) primary payload. Only valid where
    :func:`snapshot_covers` said so with ``mirror=True`` and
    :func:`verify_mirror` returned no rejects."""
    import jax
    import jax.numpy as jnp

    m = snap.mirror
    # the eager jnp.roll on a still-sharded mirror compiles to
    # collective permutes; on the CPU client independent per-field
    # launches can reorder into a rendezvous deadlock (the capture-side
    # story, mirror_snapshot), so serialize them there. Cold path —
    # runs once per recovery.
    fence = jax.default_backend() == "cpu"
    payload = {}
    for k, v in snap.payload.items():
        nx = v.shape[-1]
        mask = jnp.asarray(_lost_col_mask(nx, lost_hosts, m.n_hosts))
        realigned = jnp.roll(m.payload[k], -(nx // m.n_hosts), axis=-1)
        payload[k] = jnp.where(mask, realigned, v)
        if fence:
            payload[k].block_until_ready()
    restore_snapshot_resharded(
        sim, snap._replace(payload=payload, mirror=None))
