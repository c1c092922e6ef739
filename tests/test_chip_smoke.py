"""CPU rehearsal of chip_smoke.py: the pin that the script runs end to
end and can NEVER report success from a CPU.

The script is driven as a subprocess at its ``--tiny`` sizes (sizes
only — same phases, same checks, Pallas tiers in interpret mode) with
``JAX_PLATFORMS=cpu``: every phase line must be valid JSON and pass its
checks, and the process must still exit non-zero, naming the platform,
without the contract's ``{"ok": true, ...}`` line. The adaptive phases'
compiles do not fit a tier-1 budget next to the uniform ones, so the
tier-1 rehearsal covers phases 1, 2, 4 and the uniform kernel tiers of
5, and a slow-marked twin covers phase 3 and the forest kernels of 5.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rehearse(argv, phases=None, devices=1, timeout=600):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = ("import sys, chip_smoke; "
            f"sys.exit(chip_smoke.main({argv!r}, phases={phases!r}))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.strip()]          # every stdout line is JSON
    return proc, lines


def _assert_rehearsal(proc, lines, want_phases):
    tail = proc.stderr[-3000:]
    by_phase = {ln["phase"]: ln for ln in lines if "phase" in ln}
    for name in want_phases:
        assert name in by_phase, (name, sorted(by_phase), tail)
    for name, ln in by_phase.items():
        assert ln["ok"], (name, ln.get("checks"), ln.get("error"), tail)
    # the phases passed, and STILL no success: non-zero exit with the
    # platform named, no {"ok": true} contract line anywhere
    assert proc.returncode != 0, tail
    assert "platform is 'cpu'" in proc.stderr, tail
    assert not any(ln.get("ok") is True and "device" in ln
                   and "phase" not in ln for ln in lines), lines
    assert lines[-1] == {"rehearsal": "tiny", "phases_ok": True,
                         "device": lines[0]["device"]}, lines[-1]
    assert lines[0]["device"]["platform"] == "cpu"


def test_chip_smoke_rehearsal_uniform_and_fleet():
    proc, lines = _rehearse(["--tiny"], phases="1245")
    _assert_rehearsal(proc, lines, (
        "0-platform", "1a-tgv_periodic-default", "1b-tgv_periodic-fftd",
        "2-cavity", "4-fleet-serve", "5a-cavity-pallas",
        "5b-cavity-pallas-bf16", "5c-cavity-pallas-fas",
        "5d-cavity-pallas-fas-bf16"))
    by_phase = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert by_phase["0-platform"]["checks"]["native_available"] is True
    assert by_phase["0-platform"]["cache_dir_from"] == "checkout"
    # a CPU rehearsal keeps the XLA smoother (the hierarchy picks the
    # strip legs on the chip only); the fas bf16 legs show in the label
    assert by_phase["5d-cavity-pallas-fas-bf16"]["smoother_tier"] == \
        "xla+bf16"
    assert by_phase["2-cavity"]["fused_levels"] == 0
    assert by_phase["5a-cavity-pallas"]["kernel_tier"].startswith(
        "pallas-fused+bc(")


def test_chip_smoke_full_size_refuses_cpu_at_phase_0():
    """Without --tiny the platform check comes FIRST: nothing runs,
    stdout stays empty, the exit code is non-zero."""
    proc, lines = _rehearse([])
    assert proc.returncode != 0
    assert lines == [] and proc.stdout.strip() == ""
    assert "phase 0 failed on platform 'cpu'" in proc.stderr
    assert "['platform_is_tpu']" in proc.stderr


@pytest.mark.slow   # ~2-3 min of forest compiles: phase 3 plus the
#                     two FAS runs of phase 5
def test_chip_smoke_rehearsal_adaptive():
    proc, lines = _rehearse(["--tiny"], phases="35", timeout=1500)
    _assert_rehearsal(proc, lines, (
        "3-canonical", "5e-canonical-fas", "5f-canonical-pallas-fas"))


@pytest.mark.slow   # ~3 min: four sharded-step compiles on 4 virtual
#                     devices plus their single-device twins
def test_chip_smoke_rehearsal_four_chips():
    proc, lines = _rehearse(["--tiny", "--chips", "4"], devices=4,
                            timeout=1500)
    _assert_rehearsal(proc, lines, (
        "m0-cavity-1dev", "m1-cavity-mesh", "m2-cavity-mesh-pallas",
        "m3-canonical-1dev", "m4-canonical-mesh"))
    by_phase = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert by_phase["m1-cavity-mesh"]["devices"] == 4
    assert by_phase["m4-canonical-mesh"]["devices"] == 4
    assert "collective-permute" in by_phase["m1-cavity-mesh"][
        "collectives"]
