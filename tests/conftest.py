"""Test harness: run every test on CPU with 8 virtual devices.

This is the mechanism the reference never had for testing "multi-node
without a cluster" (SURVEY.md §4): XLA's forced host platform device count
stands in for a TPU v5e-8 slice, so `shard_map`/`pjit` paths are exercised
for real (collectives and all) on any machine.

Must run before `import jax` — hence top of conftest.
"""

import os

# Assignment, not setdefault: a JAX_PLATFORMS inherited from the shell
# must not send the suite to an accelerator. The config.update below
# also covers a pytest plugin that imported jax before this file ran
# (the env var is read at import; the config wins until first use).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# float64 on CPU: validates discretization order of accuracy at reference
# precision (the reference is float64 throughout, main.cpp:24). The TPU
# production path runs float32 — precision-sensitive tests assert both.
jax.config.update("jax_enable_x64", True)
