"""Persistent XLA compilation cache for every entry point.

Adaptive runs compile one executable per (bucket, window-capacity)
combination — tens of multi-second TPU compiles that are identical
across process restarts of the same case. The CLI and driver
entry points all funnel through here; library users can call it once
before building a sim. Safe to call repeatedly.

The key includes operation metadata
(``jax_compilation_cache_include_metadata_in_key``; JAX leaves it out
by default): the step's scope names (``tracing.SCOPES``) live in that
metadata, and an executable compiled before a scope was added or
renamed and served from a shared directory would otherwise trace under
its old names. Within one checkout the source, and so the key, is the
same from run to run.

Placement: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
itself and this module sets no directory. Otherwise the cache lives at
ONE fixed path inside the checkout (``<repo>/.jax_cache/xla``, listed
in .gitignore) — the directory is part of the cache key's lookup, so a
path that moved with the home directory, a pid or a time would never
hit. The native helper's ``.so`` (native/__init__.py) builds under the
same root.
"""

from __future__ import annotations

import os

CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")
XLA_CACHE_DIR = os.path.join(CACHE_ROOT, "xla")


def enable_compilation_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", XLA_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
