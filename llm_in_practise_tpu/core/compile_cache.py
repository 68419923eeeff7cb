"""Persistent XLA compilation cache — cold-start control.

The reference's serving pods go ready on weight-load: vLLM CUDA-graph
capture takes seconds, so an engine restart costs little
(``LLM_on_Kubernetes/Inference_Platfrom/README.md`` readiness probes).
On TPU the equivalent tax is XLA compilation — an engine compiles one
program per (phase, shape bucket) and a full-depth model pays seconds to
minutes for each — so a restart without a cache pays it all again.

JAX ships a persistent compilation cache (serialized executables keyed
by HLO fingerprint, compile options and the cache path). This module is
the one switch that turns it on for the serving, training and bench
entry points, under one placement rule:

- ``JAX_COMPILATION_CACHE_DIR`` set (or a directory already configured
  through ``jax.config``): JAX's own reading of it is the directory;
  this helper sets none.
- not set, accelerator backend: ``<checkout>/.jax_cache``, derived from
  the package's own location. The path is part of the cache key, so it
  must not move between runs: never ``$HOME``, a temporary name, a pid
  or the time.
- not set, CPU backend: off. XLA:CPU's AOT loader re-checks recorded
  machine features on every cache load and warns (possible SIGILL) per
  program, so CPU runs (the test suite) stay uncached.
"""

from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; idempotent.

    Returns the active cache directory, or ``None`` where the cache
    stays off (CPU backend with no directory configured, or an
    unwritable checkout). Thresholds are dropped to cache every
    program: engines compile many small programs (decode step, insert
    variants, chunked-prefill buckets) and JAX's default 1 s
    minimum-compile-time would skip most of them.
    """
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        if jax.default_backend() == "cpu":
            return None
        cache_dir = CHECKOUT_CACHE_DIR
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError:
            # read-only install (a non-root pod): serve uncached rather
            # than take the engine down
            return None
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # Any compile that ran BEFORE this call memoized the disabled
        # cache state process-wide (importing the serve package is
        # enough); reset_cache() drops that memo so the directory takes
        # effect for every later compile.
        from jax.experimental.compilation_cache.compilation_cache import (
            reset_cache,
        )

        reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
