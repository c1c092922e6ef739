"""The faults a doubly-periodic one-box cell can have, planted under
the timed path, for ``test_faults_periodic.py`` here and for the tier-1
cases in ``tests/test_bench_periodic.py``: the cavity's five
(``test_faults.py``: unchanged, half, altered, late, no_solve — the same
plants, they know no boundary) and the one that belongs to this box:

walls      wall paint in place of wrap ghosts: the advection's three
           ghost layers are painted as four no-slip walls at rest
           (ghost = -edge) instead of the far side's cells

Not a test file: ``plant(monkeypatch, fault)`` patches the program's
``UniformGrid``; ``None`` plants nothing.
"""

from benchmark.checks.test_faults import _plant

FAULTS = ("unchanged", "half", "altered", "late", "no_solve", "walls")


def plant(monkeypatch, fault):
    if fault != "walls":
        return _plant(monkeypatch, fault)
    from cup2d_tpu.bc import BCTable, no_slip, pad_vector_bc
    from cup2d_tpu.uniform import UniformGrid

    at_rest = BCTable(no_slip(), no_slip(), no_slip(), no_slip())

    def pad_vector_field(self, v, g, dt=None):
        return pad_vector_bc(v, g, at_rest, self.h, dt)

    monkeypatch.setattr(UniformGrid, "pad_vector_field", pad_vector_field)
