"""Comm-scaling audit at REALISTIC occupancy (VERDICT r4 #6).

The r4 ppermute-vs-allgather table was measured on the
~20-block disk case — under one block per shard at 32 devices, so the
"near-flat per-device bytes" row was dominated by fragmentation, not a
real boundary-to-volume ratio. This audit re-measures on the 1e4-block
synthetic vortex forest (hundreds of blocks per shard), adding 64
devices:

  phase A (TPU or CPU, once):  grow the synthetic forest to >= 1e4
      blocks exactly like validation/scale_proof.py, then checkpoint it
      (topology + fields) to --state DIR.
  phase B (CPU, per device count / exchange mode): restore the
      checkpoint into a ShardedAMRSim on an N-virtual-device mesh and
      STATICALLY compile the production step with XLA HLO dumping on
      (jit .lower().compile() — no execution, so 64-device audits don't
      need to run a 64-way step on one core), then sum the collective
      bytes per optimized module exactly like validation/comm_audit.py.
      SPMD-lowered HLO shapes are per-device, so the reported MB are
      per-device directly.

  python -m validation.comm_audit_scale --grow            # phase A
  python -m validation.comm_audit_scale --devices 8 16 32 64  # phase B

Prints one JSON line (phase B) with per-device collective MB per mode.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

STATE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "_comm_scale_state")


def grow(target: int, levelmax: int):
    from types import SimpleNamespace

    from cup2d_tpu.cache import enable_compilation_cache
    enable_compilation_cache()
    from cup2d_tpu.io import save_checkpoint
    from validation.scale_proof import _synthetic_sim

    sim = _synthetic_sim(SimpleNamespace(levelmax=levelmax, rtol=0.1))
    steps = 0
    while len(sim.forest.blocks) < target and steps < 40:
        sim.adapt()
        sim.step_once()
        steps += 1
    save_checkpoint(STATE_DIR, sim)
    print(json.dumps({"grown_blocks": len(sim.forest.blocks),
                      "steps": steps, "state": STATE_DIR}))


def audit_one(n_dev: int, mode: str, levelmax: int,
              two_level: bool) -> dict:
    """Run in a SUBPROCESS (backend flags must be set pre-init). The
    child is CPU-forced (env AND config), so it never needs a chip the
    parent might hold; phase B's parent does not touch jax itself."""
    code = f"""
import os, json
os.environ["CUP2D_SHARD_EXCHANGE"] = {mode!r}
dump = os.environ["AUDIT_DUMP"]
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count={n_dev}"
    + " --xla_dump_to=" + dump
    + " --xla_dump_hlo_pass_re=").strip()
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from types import SimpleNamespace
from cup2d_tpu.io import load_checkpoint
from cup2d_tpu.parallel.forest_mesh import ShardedAMRSim
from cup2d_tpu.parallel.mesh import make_mesh
from validation.scale_proof import _synthetic_sim
from validation.comm_audit_scale import STATE_DIR

base = _synthetic_sim(SimpleNamespace(levelmax={levelmax}, rtol=0.1))
sim = ShardedAMRSim(base.cfg, make_mesh({n_dev}), shapes=[])
load_checkpoint(STATE_DIR, sim)
sim._refresh()
ordf = sim._ordered_state()
f = sim.forest
dt = jnp.asarray(1e-4, f.dtype)
tc = None
if {two_level!r}:
    sim._build_coarse_maps(sim._npad_hwm, sim._n_real)
    tc = sim._coarse_cw
lowered = sim._step_jit.lower(
    ordf["vel"], ordf["pres"], dt, sim._h, sim._hsq_flat,
    sim._maskv, sim._tables["vec3"], sim._tables["vec1"],
    sim._tables["sca1"], sim._tables["pois"], sim._corr, tc,
    exact_poisson=False)
lowered.compile()
print(json.dumps({{"n_blocks": len(f.blocks),
                   "n_pad": int(sim._npad_hwm)}}))
"""
    with tempfile.TemporaryDirectory(prefix="hlo_scale_") as dump:
        env = dict(os.environ)
        env["AUDIT_DUMP"] = dump
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True,
                           cwd="/root/repo", timeout=3600)
        if r.returncode != 0:
            return {"error": r.stderr[-2000:]}
        meta = json.loads(r.stdout.strip().splitlines()[-1])
        from validation.comm_audit import audit_dump_dir
        mods = audit_dump_dir(dump)
    # only the STEP module matters (the audit compiles exactly one)
    step_mod = {}
    for label, entry in mods.items():
        if "_step_impl" in label:
            step_mod = entry
    total = {"bytes": 0, "count": 0}
    per_op = {}
    for op, e in step_mod.items():
        per_op[op] = {"count": e["count"],
                      "mb": round(e["bytes"] / 1e6, 4)}
        total["bytes"] += e["bytes"]
        total["count"] += e["count"]
    return {**meta, "per_device_mb": round(total["bytes"] / 1e6, 4),
            "collectives": per_op}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grow", action="store_true")
    ap.add_argument("--target", type=int, default=10000)
    ap.add_argument("--levelmax", type=int, default=8)
    ap.add_argument("--devices", type=int, nargs="+",
                    default=[8, 16, 32, 64])
    ap.add_argument("--two-level", action="store_true",
                    help="audit with the coarse correction engaged")
    args = ap.parse_args()
    if args.grow:
        grow(args.target, args.levelmax)
        return
    out = {}
    for n in args.devices:
        for mode in ("ppermute", "allgather"):
            key = f"{n}dev_{mode}"
            out[key] = audit_one(n, mode, args.levelmax, args.two_level)
            print(f"{key}: {out[key].get('per_device_mb', 'ERR')} "
                  f"MB/device", file=sys.stderr)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
