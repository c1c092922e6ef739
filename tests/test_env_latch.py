"""Env gates are latched ONCE at sanctioned sites (thin wrapper).

The bespoke AST walk that lived here since PR 2 moved into the
graftlint framework (``cup2d_tpu.analysis``): the sanctioned-site
table is now ``analysis/policy.py`` data (the single source of truth
— there is deliberately no second copy in this file), the walk is the
``env-latch`` rule, and this test just asserts the rule runs clean on
the package. The old reality check (every allowlist row still names a
real latch) is the rule's finalize pass: a stale row IS a finding, so
the clean assertion covers it; the monkeypatch test below proves the
detector actually fires.
"""

from cup2d_tpu.analysis import lint_package, policy


def test_cup2d_env_reads_only_at_latch_points():
    report = lint_package(only=["env-latch"])
    assert report.clean, "\n".join(str(f) for f in report.findings)


def test_latch_allowlist_matches_reality(monkeypatch):
    # the finalize pass flags policy rows that stopped matching the
    # code — prove it by planting a row for a latch that doesn't exist
    bogus = dict(policy.ENV_LATCH_SITES)
    bogus[("parallel/forest_mesh.py", "_exchange_mode")] = (
        bogus[("parallel/forest_mesh.py", "_exchange_mode")]
        | {"CUP2D_NO_SUCH_GATE"})
    monkeypatch.setattr(policy, "ENV_LATCH_SITES", bogus)
    report = lint_package(only=["env-latch"])
    stale = [f for f in report.findings if "stale policy row" in f.message]
    assert stale, "planted stale allowlist row was not detected"
    assert any("CUP2D_NO_SUCH_GATE" in f.message for f in stale)
