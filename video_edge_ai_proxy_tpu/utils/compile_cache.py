"""One rule for where the XLA persistent compile cache lives.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax binds the cache to it at
import and this process keeps it there: no code path sets another
directory, so whoever launches the process (an operator, a chip-run
tool whose only surviving directory is one it names) decides where
compiled programs go. Where it is not set, the caller's directory is
used — an operator's ``engine.compile_cache_dir`` / ``aot_cache_dir``,
or :func:`checkout_dir` for ``chip_smoke.py``, ``bench.py`` and the
tests. Callers pass fixed paths only: a directory that moves between
runs (a temp name, a pid) never hits.

Every site that used to call
``jax.config.update("jax_compilation_cache_dir", ...)`` calls
:func:`configure` instead.
"""

from __future__ import annotations

import os

from .logging import get_logger

log = get_logger("utils.compile_cache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def checkout_dir() -> str:
    """``<checkout>/.jax_cache`` — the fixed default for code that runs
    from a checkout (listed in ``.gitignore``)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def configure(cache_dir: str = "") -> str:
    """Bind the persistent compile cache and return the directory in
    effect ("" = none). ``cache_dir`` is used only when the environment
    does not name one."""
    import jax

    if jax.config.jax_persistent_cache_min_compile_time_secs == 1.0:
        # Lower the jax default so mid-size serving programs persist too,
        # but never clobber a value the operator set before boot.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    env = os.environ.get(ENV_VAR, "")
    if env:
        if cache_dir and os.path.abspath(cache_dir) != os.path.abspath(env):
            log.info("%s=%s set; compile cache stays there, not %s",
                     ENV_VAR, env, cache_dir)
        return env
    current = jax.config.jax_compilation_cache_dir or ""
    if not cache_dir or cache_dir == current:
        return current
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # The cache object binds its directory on first use; if anything
    # compiled before this call the config change alone is ignored.
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
    return cache_dir
