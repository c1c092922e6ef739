"""The one general generator: turns a configuration file, a cell file
and a seed into the argv the program's CLI is called with, and owns the
arithmetic that belongs to a configuration's DRIVER kind (how many
cells a step updates, where its block counts are).

Two driver kinds, chosen by the configuration's ``driver`` key:

``case``   a uniform box from the program's case catalog. The catalog is
           a plain dict (``cases.REGISTRY``; "adding a case is one
           CaseSpec entry"), so the harness registers an entry built
           from the configuration file — the named builder with the
           file's arguments, wrapped so that the seeded start field is
           installed on ``sim.state`` as ``cases._install_vel`` does —
           and passes its name to ``-case``. No program file changes.
``flags``  reference-style flags (the forest path). The seed moves the
           bodies inside the ``-shapes`` string.
"""

from __future__ import annotations

import importlib
import time

from benchmark import seeded


def merge(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, nested groups merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


class Run:
    """One run's argv and the arithmetic of its driver kind."""

    def __init__(self, config, cell, seed, out):
        self.config, self.cell, self.seed = config, cell, seed
        self.build_s = self.seed_s = None
        self.argv = list(config.get("argv", []))
        if config["driver"] == "case":
            self.argv += ["-case", self._register_case(),
                          "-level", str(config["grid"]["level"])]
        elif config["driver"] == "flags":
            self.argv += ["-shapes", seeded.jittered_shapes(
                config["shapes"], config.get("seed_jitter", {}), seed)]
        else:
            raise SystemExit(f"benchmark: driver {config['driver']!r}?")
        self.argv += list(cell.get("argv", [])) + ["-output", out]

    # -- driver "case" --------------------------------------------------
    def _register_case(self) -> str:
        from cup2d_tpu import cases

        case = self.config["case"]
        mod, fn = case["builder"].split(":")
        builder = getattr(importlib.import_module(mod), fn)
        name = "bench." + self.config["name"]

        def build(level=None, **kw):
            t0 = time.time()
            sim = builder(level=level, **case["args"], **kw)
            self.build_s = time.time() - t0
            start = self.config.get("seeded_start")
            if start is not None:
                t0 = time.time()
                vel = seeded.start_velocity(self.config, self.seed)
                vel.block_until_ready()
                sim.state = sim.state._replace(vel=vel)
                self.seed_s = time.time() - t0
            return sim

        cases.REGISTRY[name] = cases.CaseSpec(
            name, "benchmark configuration " + self.config["name"],
            build, default_level=int(self.config["grid"]["level"]))
        return name

    # -- arithmetic of the driver kind ----------------------------------
    def cells_per_step(self, record) -> int:
        """Cells one step updates: Ny*Nx on a uniform box; on the
        forest, block^2 times the active blocks of THAT step (the
        record of the step; 0 if the step left no record)."""
        g = self.config["grid"]
        if self.config["driver"] == "case":
            return int(g["ny"]) * int(g["nx"])
        if record is None or record.get("n_blocks") is None:
            return 0
        return int(g["block"]) ** 2 * int(record["n_blocks"])

    def block_trail(self, records) -> list:
        """[[step, n_blocks], ...] at every change (forest only)."""
        trail, last = [], None
        for r in records:
            n = r.get("n_blocks")
            if n is not None and n != last:
                trail.append([r["step"], n])
                last = n
        return trail if self.config["driver"] == "flags" else []

    def grid_of(self, sim) -> dict:
        """What the program says its grid is, kept only to CHECK the
        configuration file against (the reference is built from the
        file, never from this)."""
        g = getattr(sim, "grid", None)
        if g is None:
            return {}
        return {"ny": int(g.ny), "nx": int(g.nx), "h": float(g.h),
                "nu": float(sim.cfg.nu), "cfl": float(sim.cfg.cfl)}

    def compare(self, records, grid) -> dict:
        """{name: {"value", "limit"}} from the configuration's plain
        reference; empty (never correct) where it has none."""
        name = self.config.get("reference")
        if not name:
            return {}
        ref = importlib.import_module("benchmark.references." + name)
        return ref.compare(self.config, self.cell, self.seed, records, grid)
