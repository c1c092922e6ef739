"""CPU rehearsal of ``twofish-amr-l8.wake`` alone, one command:

    JAX_PLATFORMS=cpu python3 benchmark/checks/rehearsal_twofish.py

``rehearsal.py`` runs every cell file; this runs the forest's cell at
its rehearsal size (levelMax 5, one fish of L = 0.4), ``--trace 0`` and
``--trace 1``, each in a process of its own, holds the last line to the
contract as ``rehearsal.py`` does, and besides: ``correct`` true with a
value under every limit of the cell, and in the traced line every
per-layer metric of the cell that needs no device trace — the spans,
counters and records this PR added (``kinematics_ms``, ``tables_ms``,
``pad_share_pct``) with ``regrid_ms``, ``poisson_iters``, ``compile_s``.
Its timings are a CPU's and mean nothing.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import rehearsal  # noqa: E402

CELL = "twofish-amr-l8.wake"
WITHOUT_DEVICE = {"kinematics_ms", "tables_ms", "pad_share_pct",
                  "regrid_ms", "poisson_iters", "compile_s"}


def main() -> int:
    failures = 0
    with open(os.path.join(rehearsal.ROOT, "benchmark", "workloads",
                           CELL + ".json")) as f:
        limits = json.load(f)["limits"]
    for trace in (0, 1):
        # long enough for the 12 wake steps that `correct` compares and
        # the regrid at step 40 to fall inside the window (the
        # rehearsal's warm-up is the cell's own 25 steps)
        p = rehearsal.run(CELL, trace, seconds=8)
        bad = rehearsal.check_result(CELL, trace, p)
        if not bad:
            res = json.loads([ln for ln in p.stdout.splitlines()
                              if ln.strip()][-1])
            if set(res["compared"]) != set(limits):
                bad.append(f"compared {sorted(res['compared'])}")
            if trace and not WITHOUT_DEVICE <= set(res["metrics"]):
                bad.append(f"missing: {WITHOUT_DEVICE - set(res['metrics'])}")
        print(f"{CELL} --trace {trace}: "
              + ("ok" if not bad else "; ".join(bad)), flush=True)
        failures += bool(bad)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
