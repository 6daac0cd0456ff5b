"""Pallas deposit kernel == XLA deposit engine (interpret mode on CPU),
incl. slab blocks with origin offsets (the shard_map case) and the
end-to-end option plumbing."""

import numpy as np
import jax.numpy as jnp
import pytest

from nbodykit_tpu.ops.paint import (paint_local, paint_local_mxu)


def _pos(rng, n, shape):
    scale = np.asarray(shape, 'f4')
    return jnp.asarray(rng.uniform(0, 1, (n, 3)).astype('f4') * scale)


@pytest.mark.parametrize("res", ['cic', 'tsc', 'pcs'])
def test_pallas_deposit_matches_xla(res):
    rng = np.random.RandomState(11)
    shape = (32, 32, 32)
    pos = _pos(rng, 4000, shape)
    ref = paint_local_mxu(pos, 1.0, shape, resampler=res, deposit='xla')
    got = paint_local_mxu(pos, 1.0, shape, resampler=res,
                          deposit='pallas')
    # one f32 product in the kernel, three bf16 parts outside it
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6)
    # and both agree with the scatter oracle
    sc = paint_local(pos, 1.0, shape, resampler=res)
    np.testing.assert_allclose(np.asarray(got), np.asarray(sc),
                               atol=1e-3)


def test_pallas_deposit_slab_block():
    """Slab block with origin offset + periodic wrap strip, weighted."""
    rng = np.random.RandomState(3)
    period = (32, 32, 32)
    n0l, origin = 8, 24          # top slab; rows wrap through 0
    shape = (n0l, 32, 32)
    pos = _pos(rng, 3000, period)
    w = jnp.asarray(rng.uniform(0.5, 2.0, 3000).astype('f4'))
    ref = paint_local_mxu(pos, w, shape, resampler='tsc',
                          period=period, origin=origin, deposit='xla')
    got = paint_local_mxu(pos, w, shape, resampler='tsc',
                          period=period, origin=origin, deposit='pallas')
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6)
    sc = paint_local(pos, w, shape, resampler='tsc', period=period,
                     origin=origin)
    np.testing.assert_allclose(np.asarray(got), np.asarray(sc),
                               atol=1e-3)


def test_pallas_deposit_via_options():
    """set_options(paint_deposit='pallas') reaches the kernel through
    ParticleMesh.paint."""
    import nbodykit_tpu
    from nbodykit_tpu.pmesh import ParticleMesh

    rng = np.random.RandomState(5)
    pm = ParticleMesh(Nmesh=16, BoxSize=100.0, dtype='f4')
    pos = jnp.asarray(rng.uniform(0, 100.0, (2000, 3)).astype('f4'))
    with nbodykit_tpu.set_options(paint_method='mxu',
                                  paint_deposit='pallas'):
        f_pal = pm.paint(pos, 1.0, resampler='cic')
    with nbodykit_tpu.set_options(paint_method='mxu',
                                  paint_deposit='xla'):
        f_xla = pm.paint(pos, 1.0, resampler='cic')
    np.testing.assert_allclose(np.asarray(f_pal), np.asarray(f_xla),
                               atol=2e-6)
    assert abs(float(jnp.sum(f_pal)) - 2000.0) < 0.1
