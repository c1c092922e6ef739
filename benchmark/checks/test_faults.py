"""Drives the rest of a run — everything but the harness's look for a
chip — with the timed path broken underneath, and sees ``correct`` come
out false, once for each fault the one-box cell can have:

unchanged  the step returns its state as it got it
half       half of the box (the upper rows) is left out of the update
altered    the velocity is altered where it is produced (x 1.001)
late       the same alteration, but only in the production executable
           (tolerance solves, from step 11 on): the one the window drives
no_solve   the production Poisson solve leaves early with a zero
           pressure increment (a 0-iteration early-out gone wrong)

(The exchange between chips does not exist in a one-chip cell.) The
sound program has to come out correct through the same path. CPU, at
the cell's rehearsal size. Run: ``python3 -m pytest benchmark/checks``.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _plant(monkeypatch, fault):
    import jax.numpy as jnp
    from cup2d_tpu.uniform import UniformGrid

    sound = UniformGrid.step

    solve = UniformGrid.pressure_solve

    def pressure_solve(self, rhs, exact=False):
        res = solve(self, rhs, exact=exact)
        return res if exact else res._replace(x=jnp.zeros_like(res.x))

    def step(self, state, dt, **kw):
        new, diag = sound(self, state, dt, **kw)
        if fault == "late" and kw.get("exact_poisson"):
            return new, diag
        if fault == "unchanged":
            new = state
        elif fault == "half":
            half = self.ny // 2
            new = new._replace(vel=jnp.concatenate(
                [new.vel[:, :half], state.vel[:, half:]], axis=1))
        elif fault in ("altered", "late"):
            new = new._replace(vel=new.vel * 1.001)
        umax = jnp.max(jnp.abs(new.vel))
        diag = dict(diag, umax=umax, dt_next=self.dt_from_umax(umax),
                    energy=0.5 * self.h * self.h * jnp.sum(new.vel ** 2))
        return new, diag

    if fault == "no_solve":
        monkeypatch.setattr(UniformGrid, "pressure_solve", pressure_solve)
    elif fault is not None:
        monkeypatch.setattr(UniformGrid, "step", step)


@pytest.mark.parametrize("fault", [None, "unchanged", "half", "altered",
                                   "late", "no_solve"])
def test_fault_reads_not_correct(fault, monkeypatch, capsys):
    from benchmark import run
    _plant(monkeypatch, fault)
    rc = run.main(["--workload", "cavity-re10k-8192.solo", "--seed", "77",
                   "--seconds", "1", "--trace", "0", "--rehearsal"])
    assert rc == 0
    last = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.strip()][-1]
    res = json.loads(last)
    assert res["compared"], "nothing was compared"
    assert res["correct"] is (fault is None), res["compared"]
