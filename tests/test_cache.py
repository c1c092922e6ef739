"""cache.enable_compilation_cache(): a compile cache that can be placed
from outside and otherwise never moves.

The directory is part of how a cached executable is found again, so
(a) where ``JAX_COMPILATION_CACHE_DIR`` is set the function sets NO
directory in code — jax reads the variable itself — and (b) unset, it
resolves to ONE fixed path inside the checkout: the same across calls
and across processes, never under the home directory, a temporary
name, a pid or a time.
"""

import os
import subprocess
import sys

import jax
import pytest

from cup2d_tpu import cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_env_var_placement_is_left_to_jax(monkeypatch,
                                          restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/outside/dir")
    jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
    cache.enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch, tmp_path,
                                                   restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache", "xla")
    cache.enable_compilation_cache()
    first = jax.config.jax_compilation_cache_dir
    cache.enable_compilation_cache()
    assert first == jax.config.jax_compilation_cache_dir == want
    # ... and the same in two other processes, whatever their home,
    # temp dir, pid or start time
    code = ("import jax; from cup2d_tpu import cache; "
            "cache.enable_compilation_cache(); "
            "print(jax.config.jax_compilation_cache_dir)")
    seen = set()
    for name in ("a", "b"):
        home, tmp = tmp_path / f"home-{name}", tmp_path / f"tmp-{name}"
        home.mkdir()
        tmp.mkdir()
        env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
                   HOME=str(home), TMPDIR=str(tmp))
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             cwd=tmp_path, capture_output=True, text=True,
                             timeout=120, check=True)
        seen.add(out.stdout.strip().splitlines()[-1])
    assert seen == {want}


def test_native_helper_builds_under_the_same_root():
    from cup2d_tpu import native
    assert cache.CACHE_ROOT == os.path.join(ROOT, ".jax_cache")
    if native.available():
        built = os.listdir(os.path.join(cache.CACHE_ROOT, "native"))
        assert any(f.startswith("amr_host_") and f.endswith(".so")
                   for f in built), built
