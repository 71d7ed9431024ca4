"""utils/compile_cache.py — the one rule for where compiled programs go.

With ``JAX_COMPILATION_CACHE_DIR`` set the process keeps its cache there
and no code sets another directory; unset, callers name a fixed one
(``<checkout>/.jax_cache`` for chip_smoke.py, bench.py and these tests).
"""

import os

import jax
import pytest

from video_edge_ai_proxy_tpu.bus import MemoryFrameBus
from video_edge_ai_proxy_tpu.engine import InferenceEngine
from video_edge_ai_proxy_tpu.utils import compile_cache
from video_edge_ai_proxy_tpu.utils.config import EngineConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cache_config():
    """Save/restore the process-wide cache binding around a test."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    cc.reset_cache()


@pytest.fixture()
def env_dir(tmp_path, monkeypatch, cache_config):
    """As if the process had been started with the variable set: jax reads
    it into its config when it is imported, so set both."""
    d = str(tmp_path / "from_env")
    os.makedirs(d)
    monkeypatch.setenv(compile_cache.ENV_VAR, d)
    jax.config.update("jax_compilation_cache_dir", d)
    return d


def test_checkout_default_is_fixed_and_gitignored():
    d = compile_cache.checkout_dir()
    assert d == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_unset_binds_the_callers_directory(tmp_path, monkeypatch,
                                           cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    d = str(tmp_path / "operator" / "compile_cache")
    assert compile_cache.configure(d) == d
    assert jax.config.jax_compilation_cache_dir == d
    assert os.path.isdir(d)
    # no directory named: whatever is bound stays bound
    assert compile_cache.configure("") == d


def test_env_wins_and_nothing_else_is_set(env_dir, tmp_path):
    other = str(tmp_path / "other")
    assert compile_cache.configure(other) == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert not os.path.exists(other)


@pytest.mark.parametrize("field", ["compile_cache_dir", "aot_cache_dir"])
def test_engine_warmup_leaves_the_env_directory_bound(env_dir, tmp_path,
                                                      field):
    """engine.compile_cache_dir and engine.aot_cache_dir both yield to the
    variable: after warmup + one compile the config still names the
    variable's directory and no XLA payload sits in the configured one
    (the AOT manifest may — it is not the compile cache)."""
    from video_edge_ai_proxy_tpu.engine import aot_cache

    configured = str(tmp_path / "configured")
    kwargs = {field: configured}
    if field == "aot_cache_dir":
        kwargs["aot_cache"] = True
    bus = MemoryFrameBus()
    try:
        eng = InferenceEngine(bus, EngineConfig(
            model="tiny_mobilenet_v2", batch_buckets=(1,), tick_ms=5,
            **kwargs))
        eng.warmup()
        assert jax.config.jax_compilation_cache_dir == env_dir
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        eng.compile_for((44, 60), 1)   # a geometry no other test compiles
    finally:
        bus.close()
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert os.listdir(env_dir)          # the payload went where env says
    leftovers = (os.listdir(configured)
                 if os.path.isdir(configured) else [])
    assert [f for f in leftovers if f != aot_cache.MANIFEST_NAME] == []
