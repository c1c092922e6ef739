"""Device mesh + sharded uniform-grid execution.

The reference decomposes space over MPI ranks along the SFC and hand-plans
point-to-point halo messages (`/root/reference/main.cpp:909-2142`). The
TPU-native equivalent is declarative: fields carry a `NamedSharding` that
splits the x-axis of the domain across the mesh, and XLA's SPMD partitioner
inserts the halo collective-permutes for every shifted-slice stencil read,
plus `all-reduce`s for the dt/residual reductions — the entire §2.2 comm
runtime of the reference collapses into sharding annotations.

The mesh axis is named ``"x"``: for a 2-D incompressible flow the natural
"data-parallel" axis is space itself (SURVEY.md §2.8 — spatial domain
decomposition is this code's DP; there is no batch/tensor/pipeline axis in
a single simulation). Multi-host TPU slices extend the same mesh over DCN
transparently.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import SimConfig
from ..uniform import FlowState, UniformSim


def make_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """1-D device mesh over the spatial x-axis.

    On a real v5e-8 slice this is the 8-chip ICI ring; in tests it is the
    CPU-forced virtual device set (conftest.py).
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devices)}"
            )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), ("x",))


def scalar_spec() -> P:
    """[Ny, Nx] fields: split columns across the mesh."""
    return P(None, "x")


def vector_spec() -> P:
    """[2, Ny, Nx] fields."""
    return P(None, None, "x")


def shard_state(state: FlowState, mesh: Mesh) -> FlowState:
    """Place a FlowState with x-split shardings on the mesh."""
    sv = NamedSharding(mesh, vector_spec())
    ss = NamedSharding(mesh, scalar_spec())
    return FlowState(
        vel=jax.device_put(state.vel, sv),
        pres=jax.device_put(state.pres, ss),
        chi=jax.device_put(state.chi, ss),
        us=jax.device_put(state.us, sv),
        udef=jax.device_put(state.udef, sv),
    )


class ShardedUniformSim(UniformSim):
    """Uniform-grid solver executing SPMD over a device mesh.

    Same numerics and driver loop as `UniformSim`; the only difference is
    placement: the state lives x-split across devices and the jitted step
    is compiled with those shardings, so stencil halos ride ICI
    collective-permutes and reductions are cross-device all-reduces —
    the reference's `sync1` + `MPI_Allreduce` pattern with zero
    hand-written communication code.
    """

    def __init__(self, cfg: SimConfig, mesh: Mesh,
                 level: Optional[int] = None, bc=None):
        # spmd_safe: the sharded axes go through the GSPMD partitioner,
        # which miscompiles the fast pad+slice zero-shift form
        # (ops/stencil._zshift)
        super().__init__(cfg, level, spmd_safe=True, bc=bc)
        self._bind_mesh(mesh)

    def _bind_mesh(self, mesh: Mesh) -> None:
        """Point every mesh-derived artifact at ``mesh`` and rebuild
        the step executable: shared by construction and by the elastic
        :meth:`remesh`."""
        if self.grid.nx % mesh.devices.size != 0:
            raise ValueError(
                f"Nx={self.grid.nx} not divisible by mesh size "
                f"{mesh.devices.size}"
            )
        self.mesh = mesh
        # Point the grid at the mesh BEFORE the step re-jit below so
        # the compiled step captures the mesh-aware forms: the fused
        # advection tier (CUP2D_PALLAS=1) dispatches through the
        # halo-mode megakernel (shard_halo.fused_advect_heun_sharded,
        # edge-column ppermutes issued before the strip pipeline), and
        # the FAS solve path (CUP2D_POIS=fas) rebuilds its MG
        # hierarchy so the finest-level smoothing sweeps run the
        # comm/compute-overlapped shard_map form
        # (shard_halo.overlap_jacobi_sweeps) instead of leaving the
        # halo schedule to GSPMD.
        self.grid.attach_mesh(mesh)
        state_shardings = FlowState(
            vel=NamedSharding(mesh, vector_spec()),
            pres=NamedSharding(mesh, scalar_spec()),
            chi=NamedSharding(mesh, scalar_spec()),
            us=NamedSharding(mesh, vector_spec()),
            udef=NamedSharding(mesh, vector_spec()),
        )
        self.state = shard_state(self.state, mesh)
        self._step = jax.jit(
            self.grid.step,
            donate_argnums=(0,),
            static_argnames=("exact_poisson", "obstacle_terms"),
            out_shardings=(state_shardings, None),
        )

    def remesh(self, mesh: Mesh) -> None:
        """Elastic re-mesh (resilience.StepGuard.elastic_recover):
        rebuild placement + the step executable over a new — typically
        shrunk — device set, in place, without relaunch. The current
        state is re-placed onto the new mesh (an XLA reshard); the
        elastic path immediately overwrites it from the snapshot ring /
        disk checkpoint, so its value never matters there. Cached
        device scalars (the async drivers' ``_next_dt``) are re-placed
        too — a replicated scalar pinned to a LOST device must not leak
        into the rebuilt executable's argument stream.

        Real-loss guard: when the current state's shards are no longer
        fully addressable (a peer process died and took them — the
        disk-rung path), re-sharding would try to READ them; the state
        is zeroed instead, since the restore that follows overwrites it
        wholesale."""
        if not all(getattr(v, "is_fully_addressable", True)
                   for v in self.state):
            self.state = self.grid.zero_state()
            self._next_dt = None
        self._bind_mesh(mesh)
        if isinstance(self._next_dt, jax.Array):
            self._next_dt = jax.device_put(
                self._next_dt, NamedSharding(mesh, P()))

    def set_state(self, state: FlowState):
        self.state = shard_state(state, self.mesh)


# ---------------------------------------------------------------------------
# host-ring mirror exchange (the host-redundant snapshot tier, io.py):
# one collective that sends every host's contiguous shard block to its
# ring neighbor. Same machinery class as the shard_halo surface
# exchange — a shard_map body issuing a single lax.ppermute over sparse
# (src, dst) pairs — but host-granular: with D devices grouped into H
# contiguous simulated/real hosts (D/H devices each), device i sends
# its whole shard to device (i + D/H) % D, so host h's x-columns land
# physically on host h+1. Globally the result is exactly
# roll(x, +Nx/H, axis=-1); the restore side (io.py) relies on that
# identity to realign the mirror.
# ---------------------------------------------------------------------------

from jax import shard_map as _shard_map

# executable cache: one compiled shift per (mesh, host count, rank) —
# the capture path runs per snapshot, so the jit must be reused, never
# rebuilt (a fresh lambda per call would recompile every capture)
_RING_SHIFT_CACHE: dict = {}


def host_ring_shift(x, mesh: Mesh, n_hosts: int):
    """Ring-neighbor mirror of an x-split array: each host's contiguous
    column block moves one host to the right (wrapping), as a single
    per-device ``lax.ppermute``. The output is a FRESH buffer with the
    input's sharding — donation-safe for the snapshot ring by the same
    stream-order argument as :func:`cup2d_tpu.io.device_copy` (the
    permute is enqueued before the next step's jit donates its
    sources). Pure device collective: zero host transfers."""
    n_dev = mesh.devices.size
    if n_hosts < 2 or n_dev % n_hosts != 0:
        raise ValueError(
            f"host ring needs >=2 hosts dividing the mesh "
            f"(n_hosts={n_hosts}, devices={n_dev})")
    key = (mesh, int(n_hosts), x.ndim)
    fn = _RING_SHIFT_CACHE.get(key)
    if fn is None:
        dph = n_dev // n_hosts
        perm = [(i, (i + dph) % n_dev) for i in range(n_dev)]
        spec = P(*([None] * (x.ndim - 1) + ["x"]))

        def _shift(s):
            return jax.lax.ppermute(s, "x", perm=perm)

        fn = jax.jit(_shard_map(_shift, mesh=mesh,
                                in_specs=(spec,), out_specs=spec))
        _RING_SHIFT_CACHE[key] = fn
    return fn(x)
