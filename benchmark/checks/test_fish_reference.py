"""Drives a whole run of ``twofish-amr-l8.wake`` — everything but the
harness's look for a chip — at the rehearsal size with the program
broken underneath, and sees ``correct`` come out false by at least one
of the cell's own limits, once for each fault:

unchanged  the flow step returns velocity and pressure as it got them
frozen     the fish's curvature wave stands still (the midline of t = 0)
stale      one body's velocity update is skipped (its u, v, omega stay)
mass       the recorded body mass is 1.0001 times the rasterised one
no_climb   the regrids of the climb are skipped: the forest starts where
           the coarse start left it and gains one level a step
no_solve   every Poisson solve leaves with a zero pressure increment

and two that strike the measured window only, which the start-up
numbers cannot see and the ``wake_`` numbers have to:

late_unchanged  past the warm-up every step's flow is thrown away again
late_no_solve   the production solves (past the ten start-up steps)
                leave with a zero pressure increment: faster, and wrong

The sound program has to come out correct through the same path, and so
have two that are faults in name only. ``healed``: ONE regrid of the
climb dropped, which the next regrid makes good before a step has run.
``one_iter``: every Poisson solve leaves after one iteration — with the
two-level first guess that leaves residuals of 5e-7..1.6e-4 in the
start-up and under 1e-2 later, which IS the configuration's tolerance
(1e-3 absolute, 1e-2 relative; its production solves stop after 0-2
iterations themselves). No compared number can see either, and none
pretends to (PERF.md, PR 28). CPU, at the cell's rehearsal size
(levelMax 5, one fish of L = 0.4). Run: ``python3 -m pytest
benchmark/checks``.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "twofish-amr-l8.wake"
LATE_FROM = 25      # the warm-up: a late fault strikes window steps only
# fault -> the limits it has to break (at least these)
FAULTS = {
    None: (), "healed": (), "one_iter": (),
    "unchanged": ("energy_gap",), "frozen": ("energy_gap", "vel_gap"),
    "stale": ("vel_gap", "spin_gap", "wake_vel_gap"),
    "mass": ("mass_gap",),
    "no_climb": ("cover_gap",), "no_solve": ("energy_gap", "vel_gap"),
    "late_unchanged": ("wake_energy_gap", "wake_vel_gap"),
    "late_no_solve": ("wake_vel_gap",),
}
# the window a late fault's run is given: a flow gone rough grows the
# forest, and a step executable is compiled again inside the window
SECONDS = {"late_no_solve": 25, "late_unchanged": 25}


def plant(monkeypatch, fault):
    """Break the program in one place (``monkeypatch``: pytest's, or a
    ``pytest.MonkeyPatch()`` of a script that runs a fault on the chip)."""
    import jax.numpy as jnp
    from cup2d_tpu import amr
    from cup2d_tpu.amr import AMRSim
    from cup2d_tpu.models.fish import FishShape
    from cup2d_tpu.shapes_host import ShapeHostMixin

    if fault == "unchanged":
        sound = AMRSim._megastep_impl

        def megastep(self, vel, pres, *args, **kw):
            _, _, chi, scalars, forces = sound(self, vel, pres, *args, **kw)
            uvw, com, mass, inertia, dt_next, diag = scalars
            hsq = args[5]              # (inputs, prescribed, dt, hmin, h, hsq)
            diag = dict(diag, umax=jnp.max(jnp.abs(vel)),
                        energy=self._energy(vel, hsq))
            return vel, pres, chi, (uvw, com, mass, inertia, dt_next,
                                    diag), forces
        monkeypatch.setattr(AMRSim, "_megastep_impl", megastep)
    elif fault == "late_unchanged":
        sound = AMRSim.step_once

        def step_once(self, dt=None):
            # past the warm-up every step's flow is thrown away again
            # (the megastep donates its operands: keep copies)
            late = self.step_count >= LATE_FROM
            if late:
                was = self._ordered_state()
                was = {k: jnp.copy(was[k]) for k in ("vel", "pres")}
            diag = sound(self, dt)
            if late:
                self._set_ordered(**was)
            return diag
        monkeypatch.setattr(AMRSim, "step_once", step_once)
    elif fault == "frozen":
        sound = FishShape.midline
        monkeypatch.setattr(FishShape, "midline",
                            lambda self, time: sound(self, 0.0))
    elif fault == "stale":
        sound = AMRSim.step_once

        def step_once(self, dt=None):
            s = self.shapes[0]
            keep = (s.u, s.v, s.omega)
            diag = sound(self, dt)
            s.u, s.v, s.omega = keep
            diag["bodies"] = self._bodies_record()
            return diag
        monkeypatch.setattr(AMRSim, "step_once", step_once)
    elif fault == "mass":
        sound = ShapeHostMixin._bodies_record
        monkeypatch.setattr(
            ShapeHostMixin, "_bodies_record",
            lambda self: [dict(b, mass=b["mass"] * 1.0001)
                          for b in sound(self)])
    elif fault in ("no_climb", "healed"):
        sound = AMRSim._apply_regrid
        dropped = []

        def apply_regrid(self, refine_keys, groups):
            climbing = not getattr(self, "_initialized", False)
            if climbing and (fault == "no_climb" or not dropped):
                dropped.append(1)
                return None
            return sound(self, refine_keys, groups)
        monkeypatch.setattr(AMRSim, "_apply_regrid", apply_regrid)
    elif fault == "one_iter":
        sound = amr.bicgstab
        monkeypatch.setattr(
            amr, "bicgstab",
            lambda A, b, **kw: sound(A, b, **dict(
                kw, max_iter=1, max_restarts=0)))
    elif fault in ("no_solve", "late_no_solve"):
        sound = amr.bicgstab

        def no_solve(A, b, **kw):
            res = sound(A, b, **kw)
            # the start-up's exact solves stall-exit after 15 iterations
            # without progress, the production solves after 120
            if fault == "late_no_solve" and kw.get("stall_iters") == 15:
                return res
            return res._replace(x=jnp.zeros_like(res.x))
        monkeypatch.setattr(amr, "bicgstab", no_solve)
    elif fault is not None:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_reads_not_correct(fault, monkeypatch, capsys):
    from benchmark import run
    plant(monkeypatch, fault)
    rc = run.main(["--workload", CELL, "--seed", "77", "--seconds",
                   str(SECONDS.get(fault, 6)), "--trace", "0",
                   "--rehearsal"])
    assert rc == 0
    last = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.strip()][-1]
    res = json.loads(last)
    compared = res["compared"]
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".json")) as f:
        assert set(compared) == set(json.load(f)["limits"])
    broken = {k for k, c in compared.items()
              if c["value"] is None or c["value"] > c["limit"]}
    assert res["correct"] is (not FAULTS[fault]), compared
    assert set(FAULTS[fault]) <= broken, (broken, compared)
    if str(fault).startswith("late_"):
        # the start-up stretch ends before a late fault strikes: only
        # the numbers of the window's own steps can see it
        assert all(k.startswith("wake_") for k in broken), broken
    # a fault is meant to be caught with room: twice the limit at least
    for k in FAULTS[fault]:
        assert compared[k]["value"] >= 2.0 * compared[k]["limit"], \
            (k, compared[k])
