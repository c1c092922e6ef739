"""The control of ``correct`` for the doubly-periodic cell, through the
harness's own decision: ``run.py --control bf16_reference`` holds the
sound program against the plain reference computed in the nearest
precision below the configuration's float32 — its advection operands
rounded through bfloat16 (``BENCHMARK_REFERENCE_CAST``, read by
``uniform_periodic.compare``) — and has to print ``correct`` false by at
least one of the cell's limits; the same run against the float32
reference has to print true. CPU, at the cell's rehearsal size; the
same command without ``--rehearsal`` is the control at 8192^2 (PERF.md
has its readings). Run: ``python3 -m pytest benchmark/checks``.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "turb2d-8192.solo"


@pytest.mark.parametrize("control", [None, "bf16_reference"])
@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5])
def test_bf16_reference_reads_not_correct(seed, control, monkeypatch,
                                          capsys):
    from benchmark import run
    monkeypatch.setenv("BENCHMARK_REFERENCE_CAST", "")   # put back after
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.5", "--trace", "0", "--rehearsal"]
                  + (["--control", control] if control else []))
    assert rc == 0
    res = json.loads([ln for ln in capsys.readouterr().out.splitlines()
                      if ln.strip()][-1])
    over = [k for k, c in res["compared"].items()
            if c["value"] is None or c["value"] > c["limit"]]
    assert res["correct"] is (control is None), res["compared"]
    assert bool(over) is (control is not None), res["compared"]
