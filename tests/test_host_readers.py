"""The benchmark's readers of the host's own time a step, on hand-made
span rows: ``host_step_ms`` (the period less the waits for the device,
``verdict`` and ``pull``) and ``host_unnamed_ms`` (the period less the
union of the leaf spans), both through the window the harness builds
from the ``step`` spans."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.readers import (host_step_ms, host_unnamed_ms,  # noqa: E402
                               span_cover)


def _span(name, ts, dur, depth, **attrs):
    return {"event": "span", "name": name, "ts_us": ts, "dur_us": dur,
            "depth": depth, "pid": 0, **attrs}


def _ctx(spans):
    """The readers' context for a run with these spans: no events file
    (no profiler window to leave out), the window from the step spans."""
    cell = {"name": "hand-made-cell-without-files", "warmup_steps": 0}
    win = run.window_of(spans, [], cell, lambda rec: 0)
    assert win is not None
    return {"cell": cell, "window": win, "spans": spans}


def _forest_step(t0, step, *, pull=20_000, gap=3_000):
    """One shaped forest step of 40 ms starting at ``t0`` (us): ``step``
    holding ``dispatch`` (dt, kinematics, shape_inputs, enqueue, a pull
    of ``pull`` us, bodies) and a host-side ``verdict``, then ``record``
    at the top level and ``gap`` us of the loop's own code."""
    body = 40_000 - gap - 500
    spans = [_span("step", t0, body, 0, step=step),
             _span("dispatch", t0 + 100, body - 600, 1, step=step)]
    t = t0 + 200
    parts = [("dt", 100), ("kinematics", 3_000), ("shape_inputs", 3_000),
             ("enqueue", 1_000), ("pull", pull)]
    for name, dur in parts:
        spans.append(_span(name, t, dur, 2, step=step))
        t += dur
    spans.append(_span("bodies", t, t0 + body - 600 - t, 2, step=step))
    spans.append(_span("verdict", t0 + body - 400, 300, 1, step=step))
    spans.append(_span("record", t0 + body, 500, 0, step=step + 1))
    return spans


def _forest(n=6, **kw):
    spans = []
    for k in range(n):
        spans += _forest_step(k * 40_000, 30 + k, **kw)
    spans.append(_span("step", n * 40_000, 1_000, 0, step=30 + n))
    return spans


def test_host_step_ms_takes_off_the_pull_and_the_verdict():
    ctx = _ctx(_forest())
    # 40 ms a period less the 20 ms pull and the 0.3 ms verdict
    assert host_step_ms.read(ctx) == pytest.approx(40.0 - 20.0 - 0.3)
    # the same loop with a longer wait: the host's own time is the same
    ctx = _ctx(_forest(pull=25_000))
    assert host_step_ms.read(ctx) == pytest.approx(40.0 - 25.0 - 0.3)


def test_host_step_ms_counts_a_wait_once_and_within_its_period():
    spans = _forest(n=3)
    # a verdict nested in the pull (a fence inside a fence) is not
    # taken off twice
    pull = next(s for s in spans if s["name"] == "pull")
    spans.append(_span("verdict", pull["ts_us"] + 10, 5_000, 3))
    ctx = _ctx(spans)
    assert host_step_ms.read(ctx) == pytest.approx(40.0 - 20.0 - 0.3)
    # a period with no wait in it is not a step
    bare = [_span("step", k * 10_000, 9_000, 0, step=k) for k in range(5)]
    assert host_step_ms.read(_ctx(bare)) is None


def test_host_unnamed_ms_counts_container_self_time_and_gaps():
    ctx = _ctx(_forest(gap=3_000))
    # unnamed a period: the loop's own 3 ms between record and the next
    # step, the step's own 0.3 ms around dispatch and verdict and
    # dispatch's own 0.2 ms around its parts; the rest is in a leaf
    assert host_unnamed_ms.read(ctx) == pytest.approx(3.0 + 0.5)
    ctx = _ctx(_forest(gap=9_000))
    assert host_unnamed_ms.read(ctx) == pytest.approx(9.0 + 0.5)


def test_host_unnamed_ms_does_not_count_nested_leaves_twice():
    spans = _forest(n=3)
    # the pull split into two nested parts: the leaves are the parts,
    # the pull becomes a container whose own time between them is
    # unnamed (0.5 ms), and nothing is counted twice
    pulls = [s for s in spans if s["name"] == "pull"]
    for p in pulls:
        half = (p["dur_us"] - 500) // 2
        spans += [_span("wait", p["ts_us"], half, 3),
                  _span("copy", p["ts_us"] + half + 500, half, 3)]
    ctx = _ctx(spans)
    assert host_unnamed_ms.read(ctx) == pytest.approx(3.0 + 0.5 + 0.5)
    leaves = {s["name"] for s in span_cover.leaves(spans)}
    assert "pull" not in leaves and {"wait", "copy", "record"} <= leaves
    assert "dispatch" not in leaves
    # the one step that holds nothing is the one that closes the window
    assert [s["step"] for s in span_cover.leaves(spans)
            if s["name"] == "step"] == [33]


def test_cover_clips_to_the_period_and_merges_overlaps():
    cover = span_cover.Cover([(0, 10), (5, 20), (30, 40), (100, 300)])
    assert cover.us(0, 50) == 30          # [0, 20) and [30, 40)
    assert cover.us(15, 35) == 10         # [15, 20) and [30, 35)
    assert cover.us(150, 200) == 50       # inside the long interval
    assert cover.us(50, 90) == 0
    assert span_cover.Cover([]).us(0, 10) == 0
