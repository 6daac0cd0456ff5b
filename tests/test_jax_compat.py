"""The process-level jax configuration (``_jax_compat``) and the jax
surface the package leans on, on the one jax that is installed."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from nbodykit_tpu import _jax_compat


def test_modern_names_exist():
    # the whole codebase uses ONE spelling of each
    assert callable(jax.shard_map)
    assert callable(jax.lax.pcast)
    assert callable(jax.typeof)
    assert callable(jax.core.trace_ctx.is_top_level)


def test_shard_map_psum_roundtrip(cpu8):
    # jax.shard_map must run a real collective: replicated sum over
    # the 8-device mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    from nbodykit_tpu.parallel.runtime import AXIS
    ndev = cpu8.shape[AXIS]
    x = jax.device_put(np.arange(ndev, dtype='f4'),
                       NamedSharding(cpu8, P(AXIS)))
    total = jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(jnp.sum(v), AXIS), mesh=cpu8,
        in_specs=P(AXIS), out_specs=P()))(x)
    assert float(total) == float(np.arange(ndev).sum())


def test_shard_map_while_loop_carry(cpu8):
    # a while_loop carry inside shard_map that starts from a constant
    # and folds in device-local data (the sort/paint/pairblock kernels
    # depend on this): the varying-manual-axes check refuses it unless
    # the carry is cast the way the library's helper does
    from jax.sharding import NamedSharding, PartitionSpec as P
    from nbodykit_tpu.parallel.runtime import AXIS, vary_like
    ndev = cpu8.shape[AXIS]
    x = jax.device_put(np.ones(ndev, 'f4'),
                       NamedSharding(cpu8, P(AXIS)))

    def body(v):
        def step(state):
            i, acc = state
            return i + 1, acc + jnp.sum(v)
        _, acc = jax.lax.while_loop(
            lambda s: s[0] < 3, step,
            (jnp.int32(0), vary_like(jnp.float32(0), v)))
        return jax.lax.psum(acc, AXIS)

    total = jax.jit(jax.shard_map(body, mesh=cpu8, in_specs=P(AXIS),
                                  out_specs=P()))(x)
    assert float(total) == 3.0 * ndev


def test_vary_like_is_identity_outside_shard_map():
    from nbodykit_tpu.parallel.runtime import vary_like
    x = jnp.arange(3)
    assert vary_like(x, jnp.ones(2)) is x
    assert jax.jit(lambda a: vary_like(a, a * 2))(x).tolist() == [0, 1, 2]


def test_typeof_returns_aval():
    aval = jax.typeof(jnp.zeros((2, 3), jnp.float32))
    assert tuple(aval.shape) == (2, 3)
    assert aval.dtype == jnp.float32


def test_threefry_partitionable_enabled():
    # rng.py's device-count-invariant draw contract depends on it
    assert jax.config.jax_threefry_partitionable


class _Config:
    def __init__(self):
        self.calls = []

    def update(self, name, value):
        self.calls.append((name, value))


def test_set_cpu_devices_config_path(monkeypatch):
    cfg = _Config()
    monkeypatch.setattr(_jax_compat.jax, 'config', cfg)
    _jax_compat.set_cpu_devices(5)
    assert cfg.calls == [('jax_num_cpu_devices', 5)]


def test_compile_cache_follows_the_environment(monkeypatch):
    # placed from outside: the env var wins and NO path is set in code
    cfg = _Config()
    monkeypatch.setattr(_jax_compat.jax, 'config', cfg)
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/some/dir')
    assert _jax_compat.enable_compile_cache() == '/some/dir'
    assert 'jax_compilation_cache_dir' not in dict(cfg.calls)


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    cfg = _Config()
    monkeypatch.setattr(_jax_compat.jax, 'config', cfg)
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(here, '.jax_cache')
    assert _jax_compat.enable_compile_cache() == want
    assert dict(cfg.calls)['jax_compilation_cache_dir'] == want
