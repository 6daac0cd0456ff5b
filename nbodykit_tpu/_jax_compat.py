"""Process-level jax configuration, for the one installation there is
(jax 0.9): how many virtual CPU devices a CPU run gets, and where the
persistent compile cache lives.  Both must be called before the first
backend initializes (the first ``jax.devices()``)."""

import os

import jax


def set_cpu_devices(n):
    """Request ``n`` virtual CPU devices (the multi-device rehearsal
    mesh of the tests and the ``--devices`` CLIs)."""
    jax.config.update('jax_num_cpu_devices', int(n))


def enable_compile_cache():
    """Turn on jax's persistent compile cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax itself reads it
    and no path is set in code; otherwise the cache is
    ``<checkout>/.jax_cache`` (a fixed path: the path is part of the
    cache's key).  Every entry point — chip_smoke.py, bench.py, the
    serve CLI, tests/conftest.py — calls this one helper."""
    cache = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if not cache:
        cache = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), '.jax_cache')
        jax.config.update('jax_compilation_cache_dir', cache)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    return cache
