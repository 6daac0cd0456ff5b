"""Two-pass counted exchange: eager count (pass 1) feeding a traced
paint's static all_to_all capacity (pass 2). Reference analog: the MPI
all-to-allv counts in pmesh.domain.GridND.decompose, consumed at
nbodykit/source/catalog ... mesh/catalog.py:271-284."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from nbodykit_tpu.pmesh import ParticleMesh, memory_plan
from nbodykit_tpu.parallel.runtime import cpu_mesh
from nbodykit_tpu.parallel.exchange import RUNG, counted_capacity


def test_counted_capacity_is_exact_bound():
    nproc = 8
    rng = np.random.RandomState(5)
    dest = jnp.asarray(rng.randint(0, nproc, 10000), jnp.int32)
    cap = counted_capacity(nproc, dest, slack=1.0)
    # recompute the true max per (src, dst) pair under even sharding
    per = -(-10000 // nproc)
    src = np.arange(10000) // per
    pair_counts = np.bincount(src * nproc + np.asarray(dest),
                              minlength=nproc * nproc)
    # slack=1.0 + headroom, rounded up to the capacity ladder: at
    # least the exact bound, at most one rung (17/16) above it
    exact = int(pair_counts.max()) + 8
    assert exact <= cap <= exact * RUNG[0] // RUNG[1] + 1


def test_traced_paint_with_counted_capacity_matches_eager():
    comm = cpu_mesh()
    pm = ParticleMesh(32, 100.0, dtype='f4', comm=comm)
    rng = np.random.RandomState(3)
    pos = jnp.asarray(rng.uniform(0, 100.0, (5000, 3)).astype('f4'))
    cap = pm.exchange_capacity(pos)
    # the counted bound must beat the traced ceil(N/P) fallback
    assert cap < 5000 // pm.nproc

    f_eager = pm.paint(pos, 1.0, resampler='cic')

    @jax.jit
    def step(p):
        return pm.paint(p, 1.0, resampler='cic', capacity=cap,
                        return_dropped=True)

    f_traced, dropped = step(pos)
    assert int(dropped) == 0
    np.testing.assert_allclose(np.asarray(f_traced),
                               np.asarray(f_eager), rtol=1e-6,
                               atol=1e-6)


def test_shifted_routing_counts_differently():
    """Interlaced paints route by the half-cell-shifted grid; the
    count must honor the same shift (round-5 review finding)."""
    comm = cpu_mesh()
    pm = ParticleMesh(32, 32.0, dtype='f4', comm=comm)
    # one source shard (40 slots) holds 20 particles at x=4.25 (slab 1)
    # and 20 at x=3.9 (slab 0): under shift=0.5 the first group routes
    # by x-0.5=3.75 -> slab 0 too, merging both into ONE (src, dst)
    # pair of 40 — the count must see it
    pos = np.zeros((320, 3), 'f4')
    pos[:20, 0] = 4.25
    pos[20:40, 0] = 3.9
    pos[40:, 0] = np.random.RandomState(0).uniform(8.0, 31.9, 280)
    pos[:, 1:] = np.random.RandomState(1).uniform(0, 32, (320, 2))
    cap0 = pm.exchange_capacity(jnp.asarray(pos), slack=1.0, shift=0.0)
    cap5 = pm.exchange_capacity(jnp.asarray(pos), slack=1.0, shift=0.5)
    assert cap5 >= 40 + 8
    assert cap5 > cap0  # merged routing -> strictly larger count


def test_memory_plan_counted_vs_ceil():
    pc = memory_plan(2048, int(1e9), 16, hbm_bytes=16e9)
    pf = memory_plan(2048, int(1e9), 16, exchange='ceil',
                     hbm_bytes=16e9)
    assert pc['fits'] and not pf['fits']
    assert pc['exchange_buffers'] < pf['exchange_buffers'] / 5


def test_mxu_traced_needs_no_dropped_count():
    """The tile paint has no capacity: under a trace it runs without
    ``return_dropped``, and with it the count is the exchange's."""
    from nbodykit_tpu import set_options
    pm = ParticleMesh(16, 16.0, dtype='f4', comm=None)
    pos = jnp.asarray(np.random.RandomState(1)
                      .uniform(0, 16.0, (100, 3)).astype('f4'))
    with set_options(paint_method='mxu'):
        f = jax.jit(lambda p: pm.paint(p, 1.0))(pos)
        assert abs(float(f.sum()) - 100) < 1e-3
        f, dropped = jax.jit(
            lambda p: pm.paint(p, 1.0, return_dropped=True))(pos)
        assert int(dropped) == 0
        assert abs(float(f.sum()) - 100) < 1e-3
