"""``test_faults.py`` for the doubly-periodic cell: the rest of a run —
everything but the harness's look for a chip — with the timed path
broken underneath (``periodic_faults.py``: the cavity's five faults and
wall paint in place of wrap ghosts) has to read ``correct`` false, and
the sound program has to come out correct through the same path. CPU,
at the cell's rehearsal size. Run: ``python3 -m pytest benchmark/checks``.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.checks.periodic_faults import FAULTS, plant  # noqa: E402


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_fault_reads_not_correct(fault, monkeypatch, capsys):
    from benchmark import run
    plant(monkeypatch, fault)
    rc = run.main(["--workload", "turb2d-8192.solo", "--seed", "77",
                   "--seconds", "1", "--trace", "0", "--rehearsal"])
    assert rc == 0
    last = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.strip()][-1]
    res = json.loads(last)
    assert res["compared"], "nothing was compared"
    assert res["correct"] is (fault is None), res["compared"]
