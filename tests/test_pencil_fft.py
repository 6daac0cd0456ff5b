"""Pencil-decomposed distributed FFT (parallel/dfft.py pencil path).

Equivalence oracles: the slab path and single-device jnp.fft on the
same 8-device CPU mesh, at every factorization of 8 — including the
degenerate 8x1 (== slab) — plus ragged shapes (exact fallback, never
zero-padded), r2c/c2r/c2c roundtrips, composition under an outer jit,
and bit-identical determinism.  Also units for the runtime helpers
(pencil_mesh / default_pencil_factor), dispatch-time decomp resolution
(resolve_decomp / dist_fft_plan / set_options) and the memory_plan
pencil branch.

x64 is on (conftest), so the jnp.fft oracle comparisons run at double
precision and the 1e-10 acceptance bar is meaningful.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nbodykit_tpu
from nbodykit_tpu.parallel import dfft
from nbodykit_tpu.parallel.runtime import (cpu_mesh,
                                           default_pencil_factor,
                                           is_pencil, mesh_shape2d,
                                           pencil_mesh)

FACTORIZATIONS = [(4, 2), (2, 4), (8, 1), (1, 8)]


def _real(shape, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float64)


def _cplx(shape, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.standard_normal(shape)
                       + 1j * rng.standard_normal(shape),
                       jnp.complex128)


def _ref_rfftn(x):
    return np.transpose(np.fft.rfftn(np.asarray(x)), (1, 0, 2))


# ---------------------------------------------------------------- r2c

@pytest.mark.parametrize('pxpy', FACTORIZATIONS,
                         ids=['%dx%d' % f for f in FACTORIZATIONS])
def test_pencil_rfftn_matches_jnp_and_slab(pxpy):
    # N2=10 -> Nc=6: indivisible by py for 4 of the runs, so the
    # z-axis zero-pad + output slice path is exercised, not just the
    # pad=0 degenerate case
    x = _real((16, 16, 10), seed=1)
    pm = pencil_mesh(*pxpy)
    got = np.asarray(dfft.dist_rfftn(x, pm))
    np.testing.assert_allclose(got, _ref_rfftn(x), atol=1e-10)
    slab = np.asarray(dfft.dist_rfftn(x, cpu_mesh()))
    np.testing.assert_allclose(got, slab, atol=1e-10)


def test_pencil_rfftn_ortho_norm():
    x = _real((8, 8, 8), seed=2)
    pm = pencil_mesh(2, 4)
    got = np.asarray(dfft.dist_rfftn(x, pm, norm='ortho'))
    want = np.transpose(np.fft.rfftn(np.asarray(x), norm='ortho'),
                        (1, 0, 2))
    np.testing.assert_allclose(got, want, atol=1e-10)


@pytest.mark.parametrize('pxpy', FACTORIZATIONS,
                         ids=['%dx%d' % f for f in FACTORIZATIONS])
def test_pencil_roundtrip_r2c_c2r(pxpy):
    x = _real((16, 8, 12), seed=3)
    pm = pencil_mesh(*pxpy)
    y = dfft.dist_rfftn(x, pm)
    back = np.asarray(dfft.dist_irfftn(y, 12, pm))
    np.testing.assert_allclose(back, np.asarray(x), atol=1e-10)


def test_pencil_c2r_matches_slab():
    x = _real((16, 16, 10), seed=4)
    y = dfft.dist_rfftn(x, cpu_mesh())      # slab-produced spectrum
    pm = pencil_mesh(4, 2)
    got = np.asarray(dfft.dist_irfftn(y, 10, pm))
    want = np.asarray(dfft.dist_irfftn(y, 10, cpu_mesh()))
    np.testing.assert_allclose(got, want, atol=1e-10)
    np.testing.assert_allclose(got, np.asarray(x), atol=1e-10)


# ---------------------------------------------------------------- c2c

@pytest.mark.parametrize('pxpy', [(4, 2), (2, 4)],
                         ids=['4x2', '2x4'])
def test_pencil_c2c_forward_and_inverse(pxpy):
    x = _cplx((16, 16, 6), seed=5)
    pm = pencil_mesh(*pxpy)
    y = dfft.dist_fftn_c2c(x, pm)
    want = np.transpose(np.fft.fftn(np.asarray(x)), (1, 0, 2))
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-10)
    back = dfft.dist_fftn_c2c(y, pm, inverse=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=1e-10)


# ------------------------------------------------------- ragged shapes

def test_pencil_ragged_shape_is_exact():
    """A shape that does not factor into pencils falls back to exact
    semantics (never a zero-padded transform)."""
    from nbodykit_tpu.diagnostics import counter
    x = _real((10, 12, 8), seed=6)          # 10 % 4 != 0 on a 4x2 mesh
    pm = pencil_mesh(4, 2)
    before = counter('fft.pencil.fallback').value
    got = np.asarray(dfft.dist_rfftn(x, pm))
    assert counter('fft.pencil.fallback').value > before
    np.testing.assert_allclose(got, _ref_rfftn(x), atol=1e-10)
    back = np.asarray(dfft.dist_irfftn(jnp.asarray(got), 8, pm))
    np.testing.assert_allclose(back, np.asarray(x), atol=1e-10)


def test_pencil_ragged_n1_is_exact():
    x = _real((16, 10, 8), seed=7)          # 10 % 4 != 0 on a 2x4 mesh
    got = np.asarray(dfft.dist_rfftn(x, pencil_mesh(2, 4)))
    np.testing.assert_allclose(got, _ref_rfftn(x), atol=1e-10)


# --------------------------------------------- composition + determinism

def test_pencil_composes_under_jit():
    x = _real((16, 16, 10), seed=8)
    pm = pencil_mesh(2, 4)
    f = jax.jit(lambda v: dfft.dist_rfftn(v, pm))
    np.testing.assert_allclose(np.asarray(f(x)),
                               np.asarray(dfft.dist_rfftn(x, pm)),
                               atol=1e-10)


def test_pencil_bit_identical_determinism():
    x = _real((16, 16, 10), seed=9)
    pm = pencil_mesh(4, 2)
    a = np.asarray(dfft.dist_rfftn(x, pm))
    b = np.asarray(dfft.dist_rfftn(x, pm))
    assert np.array_equal(a, b)             # exact, not allclose
    rt1 = np.asarray(dfft.dist_irfftn(dfft.dist_rfftn(x, pm), 10, pm))
    rt2 = np.asarray(dfft.dist_irfftn(dfft.dist_rfftn(x, pm), 10, pm))
    assert np.array_equal(rt1, rt2)


# ----------------------------------------------------- runtime helpers

def test_default_pencil_factor():
    assert default_pencil_factor(8) == (2, 4)
    assert default_pencil_factor(4) == (2, 2)
    assert default_pencil_factor(6) == (2, 3)
    assert default_pencil_factor(12) == (3, 4)
    assert default_pencil_factor(7) == (1, 7)   # prime: degenerate
    assert default_pencil_factor(1) == (1, 1)


def test_pencil_mesh_construction():
    pm = pencil_mesh()                      # near-square default
    assert is_pencil(pm)
    assert mesh_shape2d(pm) == (2, 4)
    assert pm.axis_names == ('x', 'y')
    pm2 = pencil_mesh(4)                    # inferred py
    assert mesh_shape2d(pm2) == (4, 2)
    pm3 = pencil_mesh(py=8)
    assert mesh_shape2d(pm3) == (1, 8)
    with pytest.raises(ValueError):
        pencil_mesh(3, 2)                   # 6 != 8 devices
    assert not is_pencil(cpu_mesh())
    # flattened pencil device order == the 1-D slab mesh order, so
    # slab- and pencil-sharded fields interconvert without movement
    assert list(pm.devices.reshape(-1)) == \
        list(cpu_mesh().devices.reshape(-1))


# ------------------------------------------------- dispatch resolution

def test_resolve_decomp_defaults_and_overrides():
    # default options -> slab, near-square factorization
    assert dfft.resolve_decomp(1) == ('slab', None)
    decomp, pxpy = dfft.resolve_decomp(8)
    assert decomp == 'slab' and pxpy == (2, 4)
    # explicit arguments win
    assert dfft.resolve_decomp(8, decomp='pencil') == ('pencil', (2, 4))
    assert dfft.resolve_decomp(8, pencil='8x1') == ('slab', (8, 1))
    # options drive the resolution when no explicit argument is given
    with nbodykit_tpu.set_options(fft_decomp='pencil',
                                  fft_pencil='4x2'):
        assert dfft.resolve_decomp(8) == ('pencil', (4, 2))
    with pytest.raises(ValueError):
        dfft.resolve_decomp(8, pencil='3x2')    # does not cover 8
    for bad in ('banana', 'auto'):
        with pytest.raises(ValueError):
            dfft.resolve_decomp(8, decomp=bad)


def test_plan_dispatches_pencil_via_options():
    x = _real((16, 16, 12), seed=10)
    plan = dfft.dist_fft_plan((16, 16, 12), cpu_mesh())
    slab = np.asarray(plan.r2c(x))
    with nbodykit_tpu.set_options(fft_decomp='pencil'):
        pen = plan.r2c(x)
        np.testing.assert_allclose(np.asarray(pen), slab, atol=1e-10)
        back = np.asarray(plan.c2r(pen))
    np.testing.assert_allclose(back, np.asarray(x), atol=1e-10)


def test_plan_explicit_2d_mesh_wins():
    x = _real((16, 16, 12), seed=11)
    plan = dfft.dist_fft_plan((16, 16, 12), pencil_mesh(4, 2))
    np.testing.assert_allclose(np.asarray(plan.r2c(x)), _ref_rfftn(x),
                               atol=1e-10)


def test_memory_plan_pencil_branch():
    from nbodykit_tpu.parallel.dfft import PENCIL_BUFFERS
    from nbodykit_tpu.pmesh import memory_plan
    plan = memory_plan(1024, int(1e8), ndevices=8,
                       fft_decomp='pencil')
    assert plan['fft_pencil'] == '2x4'
    assert plan['fft_pencil_buffers'] == PENCIL_BUFFERS == 2
    assert plan['fft_pencil_pad'] >= 1.0
    slab = memory_plan(1024, int(1e8), ndevices=8)
    # the pencil staging is the slab's 2 complex units scaled by the
    # z pad — never cheaper than slab, only padded
    assert plan['fft_workspace'] >= slab['fft_workspace']
    assert 'fft_pencil' not in slab
    # single device: the knob is meaningless, the slab model applies
    single = memory_plan(1024, int(1e8), fft_decomp='pencil')
    assert 'fft_pencil' not in single
