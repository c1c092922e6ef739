"""cup2d_tpu — a TPU-native 2D incompressible Navier–Stokes framework.

Brand-new JAX/XLA/Pallas implementation with the capabilities of
slitvinov/CUP2D (block-structured AMR, self-propelled swimmers via Brinkman
penalization, pressure projection with block-preconditioned BiCGSTAB, WENO5
advection, flux-corrected coarse–fine coupling, collisions, force/power
diagnostics), re-designed TPU-first:

* fields live in dense structure-of-arrays block forests (or a single dense
  grid on uniform runs) sharded over the device mesh with `jax.sharding`;
* ghost-cell assembly is batched gathers planned on host per regrid, not
  per-message MPI scheduling;
* stencil operators are fused XLA/Pallas kernels over all blocks at once;
* the Poisson solve is matrix-free BiCGSTAB inside `lax.while_loop` with the
  block-Cholesky preconditioner applied as one batched BS^2 x BS^2 GEMM
  (MXU work), instead of host-assembled COO + cuSPARSE;
* collectives (dt reduction, rigid-body integrals, residual norms) are XLA
  `psum`/`pmax` over ICI instead of MPI_Allreduce.

See SURVEY.md for the reference layer map this framework covers.
"""

__version__ = "0.2.0"

from .config import SimConfig, CommandlineParser, LineParser  # noqa: F401
from .curve import SpaceCurve  # noqa: F401

# heavier modules (jax-importing) are exposed lazily so `import cup2d_tpu`
# stays cheap for config-only consumers
_LAZY = {
    "AMRSim": ("cup2d_tpu.amr", "AMRSim"),
    "Simulation": ("cup2d_tpu.sim", "Simulation"),
    "UniformSim": ("cup2d_tpu.uniform", "UniformSim"),
    "UniformGrid": ("cup2d_tpu.uniform", "UniformGrid"),
    "Forest": ("cup2d_tpu.forest", "Forest"),
    "ShardedUniformSim": ("cup2d_tpu.parallel.mesh", "ShardedUniformSim"),
    "ShardedAMRSim": ("cup2d_tpu.parallel.forest_mesh", "ShardedAMRSim"),
    "enable_compilation_cache": ("cup2d_tpu.cache",
                                 "enable_compilation_cache"),
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod, attr = _LAZY[name]
        return getattr(importlib.import_module(mod), attr)
    raise AttributeError(f"module 'cup2d_tpu' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY))
