"""Persistent compilation cache (core/compile_cache.py).

The reference's serving pods go ready on weight-load; the TPU
equivalent requires compiled programs to survive restarts. These tests
pin where the cache goes: ``JAX_COMPILATION_CACHE_DIR`` (JAX's own
reading, never re-set by the helper), else one fixed path inside the
checkout on an accelerator, else off on the CPU backend.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from llm_in_practise_tpu.core import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _restore_jax_cache_config():
    """Leave the session's jax config untouched: an enabled persistent
    cache leaking past these tests would serialize every later test's
    programs and flood the CPU AOT-loader warnings the module guards
    against."""
    from jax.experimental.compilation_cache.compilation_cache import (
        reset_cache,
    )

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs,
             jax.config.jax_persistent_cache_min_entry_size_bytes)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", saved[2])
    reset_cache()


def test_env_variable_is_the_directory_and_is_never_reset(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: JAX's own reading of it is the
    directory — the helper issues no ``jax.config.update`` of the
    directory (only the two thresholds) and compiled programs land
    there. Needs a fresh interpreter: JAX reads the variable at
    import."""
    d = str(tmp_path / "from-env")
    script = (
        "import jax, jax.numpy as jnp\n"
        "from llm_in_practise_tpu.core import compile_cache\n"
        "seen = []\n"
        "real = jax.config.update\n"
        "jax.config.update = lambda k, v: (seen.append(k), real(k, v))[1]\n"
        "got = compile_cache.enable_compilation_cache()\n"
        "jax.jit(lambda x: (x @ x.T).sum())("
        "jnp.ones((64, 64), jnp.float32)).block_until_ready()\n"
        "print(got); print(sorted(set(seen)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=d,
               PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    got, seen = out.stdout.strip().splitlines()[-2:]
    assert got == d
    assert "jax_compilation_cache_dir" not in seen
    assert "jax_persistent_cache_min_compile_time_secs" in seen
    assert any(f.endswith("-cache") for f in os.listdir(d))


def test_unset_on_accelerator_is_the_fixed_checkout_path(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = compile_cache.enable_compilation_cache()
    assert got == os.path.join(REPO, ".jax_cache")
    assert os.path.isdir(got)
    assert jax.config.jax_compilation_cache_dir == got
    # cache-everything thresholds: engines compile many small programs
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    # not from $HOME, a temporary name, a pid or the time
    assert got == compile_cache.CHECKOUT_CACHE_DIR
    assert not got.startswith(os.path.expanduser("~") + os.sep + ".cache")


def test_unset_on_cpu_stays_off():
    assert jax.config.jax_compilation_cache_dir is None
    assert compile_cache.enable_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir is None


def test_idempotent(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    first = compile_cache.enable_compilation_cache()
    assert compile_cache.enable_compilation_cache() == first
    assert jax.config.jax_compilation_cache_dir == first


def test_user_configured_directory_is_never_clobbered(tmp_path, monkeypatch):
    """A ``jax_compilation_cache_dir`` the user already set through
    ``jax.config`` is reported and left alone, on any backend."""
    theirs = str(tmp_path / "user-dir")
    jax.config.update("jax_compilation_cache_dir", theirs)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.enable_compilation_cache() == theirs
    assert jax.config.jax_compilation_cache_dir == theirs


def test_engine_enables_cache(tmp_path):
    """InferenceEngine construction calls the helper (restart story):
    with a directory configured, the engine leaves it in place and
    lowers the thresholds so its small programs are kept."""
    from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
    from llm_in_practise_tpu.serve.engine import InferenceEngine

    d = str(tmp_path / "engine-cache")
    jax.config.update("jax_compilation_cache_dir", d)
    cfg = GPTConfig(vocab_size=64, seq_len=64, n_layer=1, n_head=2,
                    embed_dim=32, dropout=0.0)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    InferenceEngine(model, params, max_slots=1, cache_len=32).stop()
    assert jax.config.jax_compilation_cache_dir == d
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
