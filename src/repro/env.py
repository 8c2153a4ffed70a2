"""Process environment helpers: one-time warnings for unparsable
``REPRO_*`` values, and the persistent compile cache.

Every routing/config env var in the stack parses through
:func:`warn_env_once` instead of silently falling back (the PR 7
satellite that started with the ``REPRO_SERVE_*`` family, extended to
the whole ``REPRO_*`` namespace): an invalid value warns exactly once
per (variable, value) pair and names the fallback it resolved to, so a
typo in CI or a shell profile shows up in the logs instead of silently
running the default engine.

This module is a dependency leaf (stdlib only) so the kernel dispatchers
(``kernels/ops.py``), the core dispatchers (``popshard``/``dcoarsen``/
``mutate``/``scheduler``) and the serving layer can all share the same
helper without import cycles.  ``serve.faults.warn_env_once`` re-exports
it for the existing call sites.
"""
from __future__ import annotations

import os
import warnings

#: Fixed persistent compile-cache directory used when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: the path is part of the
#: cache key, so it must not move between runs.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_WARNED: set = set()


def warn_env_once(var: str, raw: str, fallback: str) -> None:
    """``warnings.warn`` exactly once per (variable, value) that a
    ``REPRO_*`` value could not be parsed and what it fell back to —
    instead of the silent default the early parsers used."""
    key = (var, raw)
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(f"{var}={raw!r} is not a valid value; "
                  f"falling back to {fallback}", stacklevel=3)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache for an entry point and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
    JAX's own setting and is left alone; otherwise the cache lives at
    the fixed ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
