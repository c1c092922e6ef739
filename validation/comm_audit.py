"""Collective-traffic audit of the sharded forest step.

The reference's comm layer (/root/reference/main.cpp:909-2142) exists to
move ONLY halo slabs between neighbor ranks; its per-step traffic is
proportional to the shard *surface*. Our sharded path delegates comm to
GSPMD, which for a data-dependent gather from a sharded operand may
legally lower to an all-gather of the whole field — traffic proportional
to *volume*. This tool measures which one we actually got: it runs one
adaptive step of ShardedAMRSim on an 8-virtual-device CPU mesh with XLA
HLO dumping enabled, then parses every optimized module for collective
ops (all-gather / all-reduce / collective-permute / all-to-all) and sums
their bytes.

Run:  python validation/comm_audit.py [--devices 8]
Prints one line per executable and a JSON summary; exits 0 always (it is
a measurement, not a test — tests/test_comm_volume.py asserts the
bound).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import tempfile

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

# e.g.  %ag = f64[8,512,2,8,8]{4,3,2,1,0} all-gather(%p), ...
_COLL_RE = re.compile(
    r"=\s*(?:\(\s*)?([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"\b(all-gather|all-reduce|collective-permute|all-to-all|"
    r"reduce-scatter|collective-broadcast)\b")


def shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def audit_dump_dir(dump_dir: str) -> dict:
    """Parse every optimized HLO module in dump_dir; return per-module
    and total collective byte counts."""
    mods = {}
    for path in sorted(glob.glob(
            os.path.join(dump_dir, "*after_optimizations.txt"))):
        name = os.path.basename(path)
        # module name: module_NNNN.jit_foo.sm_8... -> jit_foo
        m = re.search(r"module_\d+\.([^.]+)", name)
        label = m.group(1) if m else name
        per_op: dict[str, list] = {}
        with open(path) as f:
            for line in f:
                cm = _COLL_RE.search(line)
                if not cm:
                    continue
                dt, dims, op = cm.groups()
                per_op.setdefault(op, []).append(
                    (shape_bytes(dt, dims), f"{dt}[{dims}]"))
        if per_op:
            entry = mods.setdefault(label, {})
            for op, items in per_op.items():
                e = entry.setdefault(op, {"count": 0, "bytes": 0,
                                          "largest": "", "_max": 0})
                e.setdefault("_max", 0)
                for b, shp in items:
                    e["count"] += 1
                    e["bytes"] += b
                    if b > e["_max"]:
                        e["largest"], e["_max"] = shp, b
            for e in entry.values():
                e.pop("_max", None)
    return mods


def run_step_with_dump(n_dev: int, dump_dir: str) -> dict:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_dev}"
        + f" --xla_dump_to={dump_dir}"
        + " --xla_dump_hlo_pass_re=").strip()
    # jax may already be imported by the caller: config.update before
    # first backend use still wins (tests/conftest.py)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np  # noqa: F401

    from cup2d_tpu.config import SimConfig
    from cup2d_tpu.models import DiskShape
    from cup2d_tpu.parallel.forest_mesh import ShardedAMRSim
    from cup2d_tpu.parallel.mesh import make_mesh

    cfg = SimConfig(bpdx=2, bpdy=1, level_max=3, level_start=1,
                    extent=1.0, dtype="float32", nu=4e-5, lam=1e6,
                    rtol=2.0, ctol=1.0)
    mesh = make_mesh(n_dev)
    sim = ShardedAMRSim(cfg, mesh, shapes=[DiskShape(0.08, 0.55, 0.25)])
    sim.compute_forces_every = 0
    sim.initialize()
    for _ in range(2):
        sim.step_once(dt=1e-3)
    # field stats for the proportionality check
    f = sim.forest
    n_act = len(f.order())
    return {
        "n_devices": n_dev,
        "n_active_blocks": int(n_act),
        "n_pad": int(sim._npad_hwm),
        "bs": int(cfg.bs),
        "field_bytes_vel": int(
            sim._npad_hwm * 2 * cfg.bs * cfg.bs
            * np.dtype(f.dtype).itemsize),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--dump-dir", default=None)
    args = ap.parse_args()
    dump_dir = args.dump_dir or tempfile.mkdtemp(prefix="hlo_comm_")

    meta = run_step_with_dump(args.devices, dump_dir)
    mods = audit_dump_dir(dump_dir)

    grand = {}
    for label, entry in sorted(mods.items()):
        for op, e in sorted(entry.items()):
            g = grand.setdefault(op, {"count": 0, "bytes": 0})
            g["count"] += e["count"]
            g["bytes"] += e["bytes"]
            print(f"{label:50s} {op:20s} x{e['count']:<4d} "
                  f"{e['bytes']/1e6:10.3f} MB   largest {e['largest']}",
                  file=sys.stderr)
    print(json.dumps({"meta": meta, "dump_dir": dump_dir,
                      "modules": mods, "total": grand}))


if __name__ == "__main__":
    main()
