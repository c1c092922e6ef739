"""CPU rehearsal of the harness, one command:

    JAX_PLATFORMS=cpu python3 benchmark/checks/rehearsal.py

Runs every cell file under ``benchmark/workloads/`` at its ``rehearsal``
sizes, ``--trace 0`` and ``--trace 1``, each in a process of its own,
and checks that the last stdout line is the contract's object; then
that a run that is NOT told it is a rehearsal fails on a machine with
no TPU and prints no result. Kept out of ``tests/`` so the tier-1 count
is untouched. Its timings are a CPU's and mean nothing.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(cell, trace, rehearsal=True, seconds=2, seed=2**31 + 12345):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", cell, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    if rehearsal:
        cmd.append("--rehearsal")
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)


def check_result(cell, trace, p) -> list:
    bad = []
    if p.returncode != 0:
        return [f"exit code {p.returncode}: {p.stderr[-400:]}"]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    try:
        res = json.loads(lines[-1])
    except (IndexError, ValueError):
        return ["last line is not JSON"]
    if not RESULT_KEYS <= set(res):
        bad.append(f"keys missing: {RESULT_KEYS - set(res)}")
    dev = res.get("device", {})
    if not {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev):
        bad.append("device keys missing")
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           cell + ".json")) as f:
        wl = json.load(f)
    kinds = {}
    for m in wl["metrics"]:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               m + ".json")) as f:
            kinds[m] = json.load(f)["kind"]
    want = "per_layer" if trace else "end_to_end"
    for name, val in res.get("metrics", {}).items():
        if kinds.get(name) != want:
            bad.append(f"{name} is not a {want} metric of this cell")
        if not isinstance(val.get("value"), (int, float)) or "unit" not in val:
            bad.append(f"{name}: no value or unit")
    if not trace:
        for name, k in kinds.items():
            if k == "end_to_end" and name not in res["metrics"]:
                bad.append(f"{name} not reported")
    if list(res)[-1] != "compared":
        bad.append("`compared` is not the last key")
    for ln in lines[:-1]:
        try:
            json.loads(ln)
        except ValueError:
            bad.append(f"an earlier line is not JSON: {ln[:80]}")
    if wl.get("limits") and not res["correct"]:
        bad.append(f"correct is false: {res['compared']}")
    return bad


def main() -> int:
    failures = 0
    cells = sorted(os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(ROOT, "benchmark", "workloads", "*.json")))
    for cell in cells:
        for trace in (0, 1):
            bad = check_result(cell, trace, run(cell, trace))
            print(f"{cell} --trace {trace}: "
                  + ("ok" if not bad else "; ".join(bad)), flush=True)
            failures += bool(bad)
    p = run(cells[0], 0, rehearsal=False)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    printed = bool(lines) and lines[-1].startswith('{"correct"')
    ok = p.returncode != 0 and not printed
    print(f"{cells[0]} without --rehearsal on a CPU: "
          + ("fails, as it must" if ok else
             f"exit code {p.returncode}, result printed: {printed}"))
    failures += not ok
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
