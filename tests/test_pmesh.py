"""Core ParticleMesh tests: FFT round-trip/correctness vs numpy, paint
mass conservation + cross-device-count invariance, readout consistency.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from nbodykit_tpu.pmesh import ParticleMesh
from nbodykit_tpu.parallel import dfft
from nbodykit_tpu.parallel.runtime import cpu_mesh


def test_dist_rfftn_matches_numpy(cpu8):
    rng = np.random.RandomState(42)
    x = rng.standard_normal((16, 24, 20))
    want = np.fft.rfftn(x).transpose(1, 0, 2)

    got1 = dfft.dist_rfftn(jnp.asarray(x), None)
    np.testing.assert_allclose(np.asarray(got1), want, rtol=1e-9, atol=1e-8)

    got8 = dfft.dist_rfftn(jnp.asarray(x), cpu8)
    np.testing.assert_allclose(np.asarray(got8), want, rtol=1e-9, atol=1e-8)


def test_dist_irfftn_roundtrip(cpu8):
    rng = np.random.RandomState(1)
    x = rng.standard_normal((16, 8, 12))
    y = dfft.dist_rfftn(jnp.asarray(x), cpu8)
    back = dfft.dist_irfftn(y, 12, cpu8)
    np.testing.assert_allclose(np.asarray(back), x, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize('shape', [(16, 24, 20), (12, 12, 12)])
@pytest.mark.parametrize('traced', [False, True])
def test_chunked_single_device_fft_matches_plain(shape, traced):
    # force the slab-chunked path on a tiny mesh and compare against
    # the one-shot rfftn (and the exact round-trip back). Eager calls
    # route through the Python-chunked lowmem driver; traced calls
    # through the in-jit fori_loop version — both must agree.
    import nbodykit_tpu
    rng = np.random.RandomState(7)
    x = rng.standard_normal(shape)
    want = np.fft.rfftn(x).transpose(1, 0, 2)
    fwd = (jax.jit(lambda v: dfft.dist_rfftn(v, None)) if traced
           else (lambda v: dfft.dist_rfftn(v, None)))
    inv = (jax.jit(lambda v: dfft.dist_irfftn(v, shape[2], None))
           if traced else (lambda v: dfft.dist_irfftn(v, shape[2], None)))
    with nbodykit_tpu.set_options(fft_chunk_bytes=1024):
        got = fwd(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-9, atol=1e-8)
        back = inv(got)
    np.testing.assert_allclose(np.asarray(back), x, rtol=1e-9, atol=1e-9)


def test_rfftn_single_lowmem_matches_plain():
    # the eager Python-chunked low-memory driver (bench >=1024 staged
    # path) must match the one-shot transform, and must consume its
    # one-element input box (ownership transfer)
    import nbodykit_tpu
    rng = np.random.RandomState(11)
    x = rng.standard_normal((8, 10, 12)).astype(np.float32)
    want = np.fft.rfftn(x.astype(np.float64)).transpose(1, 0, 2)
    with nbodykit_tpu.set_options(fft_chunk_bytes=1024):
        box = [jnp.asarray(x)]
        got = dfft.rfftn_single_lowmem(box)
    assert box == []
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=1e-4)


def test_irfftn_single_lowmem_roundtrip():
    import nbodykit_tpu
    rng = np.random.RandomState(13)
    x = rng.standard_normal((8, 10, 12)).astype(np.float32)
    with nbodykit_tpu.set_options(fft_chunk_bytes=1024):
        y = dfft.rfftn_single_lowmem([jnp.asarray(x)])
        box = [y]
        back = dfft.irfftn_single_lowmem(box, 12)
    assert box == []
    np.testing.assert_allclose(np.asarray(back), x, rtol=2e-4, atol=1e-4)


def test_chunked_c2c_matches_plain_and_roundtrips():
    import nbodykit_tpu
    rng = np.random.RandomState(5)
    x = (rng.standard_normal((10, 8, 6))
         + 1j * rng.standard_normal((10, 8, 6)))
    want = np.fft.fftn(x).transpose(1, 0, 2)
    with nbodykit_tpu.set_options(fft_chunk_bytes=512):
        got = dfft.dist_fftn_c2c(jnp.asarray(x), None)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-9, atol=1e-8)
        back = dfft.dist_fftn_c2c(got, None, inverse=True)
    np.testing.assert_allclose(np.asarray(back), x, rtol=1e-9, atol=1e-9)


def test_chunked_fft_norm_ortho_and_odd_rows():
    # odd leading axis exercises the divisor fallback; 'ortho' must
    # compose across the per-axis passes exactly like the one-shot
    import nbodykit_tpu
    rng = np.random.RandomState(3)
    x = rng.standard_normal((9, 6, 10))
    want = np.fft.rfftn(x, norm='ortho').transpose(1, 0, 2)
    with nbodykit_tpu.set_options(fft_chunk_bytes=512):
        got = dfft.dist_rfftn(jnp.asarray(x), None, norm='ortho')
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-9, atol=1e-8)


def test_r2c_normalization(comm):
    # pmesh convention: r2c divides by Ntot, so DC mode = mean of field
    pm = ParticleMesh(8, 1.0, dtype='f8', comm=comm)
    field = pm.create('real', value=3.0)
    c = pm.r2c(field)
    np.testing.assert_allclose(complex(c[0, 0, 0]), 3.0, rtol=1e-12)
    back = pm.c2r(c)
    np.testing.assert_allclose(np.asarray(back), 3.0, rtol=1e-6)


@pytest.mark.parametrize("resampler", ['nnb', 'cic', 'tsc', 'pcs'])
def test_paint_mass_conservation(comm, resampler):
    pm = ParticleMesh(32, 100.0, dtype='f8', comm=comm)
    rng = np.random.RandomState(7)
    pos = jnp.asarray(rng.uniform(0, 100.0, size=(1000, 3)))
    mass = jnp.asarray(rng.uniform(0.5, 1.5, size=1000))
    field = pm.paint(pos, mass, resampler=resampler)
    np.testing.assert_allclose(float(field.sum()), float(mass.sum()),
                               rtol=1e-10)


@pytest.mark.parametrize("resampler", ['cic', 'tsc'])
def test_paint_device_count_invariance(resampler):
    rng = np.random.RandomState(3)
    pos_np = rng.uniform(0, 50.0, size=(4096, 3))
    fields = []
    for comm in [cpu_mesh(1), cpu_mesh(2), cpu_mesh()]:
        pm = ParticleMesh(32, 50.0, dtype='f8', comm=comm)
        field = pm.paint(jnp.asarray(pos_np), 1.0, resampler=resampler)
        fields.append(np.asarray(field))
    np.testing.assert_allclose(fields[0], fields[1], rtol=1e-10, atol=1e-12)
    np.testing.assert_allclose(fields[0], fields[2], rtol=1e-10, atol=1e-12)


def test_paint_nnb_is_histogram(comm):
    pm = ParticleMesh(8, 8.0, dtype='f8', comm=comm)
    rng = np.random.RandomState(11)
    pos_np = rng.uniform(0, 8.0, size=(500, 3))
    field = np.asarray(pm.paint(jnp.asarray(pos_np), 1.0, resampler='nnb'))
    # nnb: each particle deposits into the nearest cell center => cell
    # index = floor(x + 0.5) mod N at unit cell size
    idx = np.floor(pos_np + 0.5).astype(int) % 8
    want = np.zeros((8, 8, 8))
    np.add.at(want, (idx[:, 0], idx[:, 1], idx[:, 2]), 1.0)
    np.testing.assert_array_equal(field, want)


def test_readout_constant_field(comm):
    # interpolating a constant field returns the constant for any window
    pm = ParticleMesh(32, 32.0, dtype='f8', comm=comm)
    field = pm.create('real', value=5.0)
    rng = np.random.RandomState(5)
    pos = jnp.asarray(rng.uniform(0, 32.0, size=(300, 3)))
    for resampler in ['cic', 'tsc', 'pcs']:
        vals = pm.readout(field, pos, resampler=resampler)
        np.testing.assert_allclose(np.asarray(vals), 5.0, rtol=1e-10)


def test_readout_linear_gradient(comm):
    # CIC exactly interpolates a linear function away from wrap edges
    pm = ParticleMesh(16, 16.0, dtype='f8', comm=comm)
    x = np.arange(16)
    field = jnp.asarray(np.broadcast_to(
        x[:, None, None], (16, 16, 16)).astype('f8'))
    rng = np.random.RandomState(9)
    pos_np = np.column_stack([
        rng.uniform(2, 13, 200),      # away from the periodic seam
        rng.uniform(0, 16, 200),
        rng.uniform(0, 16, 200)])
    vals = pm.readout(field, jnp.asarray(pos_np), resampler='cic')
    np.testing.assert_allclose(np.asarray(vals), pos_np[:, 0], rtol=1e-9)


def test_whitenoise_invariance_and_variance():
    for N in [16]:
        pms = [ParticleMesh(N, 1.0, dtype='f8', comm=c)
               for c in [cpu_mesh(1), cpu_mesh()]]
        etas = [np.asarray(pm.generate_whitenoise(seed=99)) for pm in pms]
        np.testing.assert_allclose(etas[0], etas[1], rtol=1e-10)
        # unit variance per complex mode (hermitian-sum over all modes)
        eta = etas[0]
        # total power sum_k |eta|^2 over the full (uncompressed) cube
        w = np.ones(N // 2 + 1) * 2.0
        w[0] = 1.0
        w[-1] = 1.0
        total = np.sum(np.abs(eta) ** 2 * w)
        assert abs(total / N ** 3 - 1.0) < 0.05


def test_whitenoise_unitary():
    pm = ParticleMesh(8, 1.0, dtype='f8')
    eta = np.asarray(pm.generate_whitenoise(seed=1, unitary=True))
    np.testing.assert_allclose(np.abs(eta), 1.0, rtol=1e-10)


def test_uniform_particle_grid(comm):
    pm = ParticleMesh(8, 16.0, dtype='f8', comm=comm)
    pos = np.asarray(pm.generate_uniform_particle_grid(shift=0.5))
    assert pos.shape == (512, 3)
    assert pos.min() == 1.0 and pos.max() == 15.0
    # paint back with nnb: exactly one particle per cell
    field = np.asarray(pm.paint(jnp.asarray(pos), 1.0, resampler='nnb'))
    np.testing.assert_array_equal(field, np.ones((8, 8, 8)))


def test_paint_clustered_no_mass_loss():
    # all particles in one slab: auto-capacity must still not drop any
    from nbodykit_tpu.parallel.runtime import cpu_mesh
    pm = ParticleMesh(16, 16.0, dtype='f8', comm=cpu_mesh())
    rng = np.random.RandomState(0)
    pos_np = rng.uniform(0, 16.0, size=(4096, 3))
    pos_np[:, 0] = rng.uniform(0.0, 1.5, size=4096)  # clustered in x
    field = pm.paint(jnp.asarray(pos_np), 1.0, resampler='cic')
    np.testing.assert_allclose(float(field.sum()), 4096.0, rtol=1e-10)


def test_paint_non_divisible_N(comm):
    # N=501 not divisible by 8 devices: padding path
    pm = ParticleMesh(16, 16.0, dtype='f8', comm=comm)
    rng = np.random.RandomState(2)
    pos = jnp.asarray(rng.uniform(0, 16.0, size=(501, 3)))
    field = pm.paint(pos, 1.0, resampler='cic')
    np.testing.assert_allclose(float(field.sum()), 501.0, rtol=1e-10)


def test_halo_too_wide_raises():
    from nbodykit_tpu.parallel.runtime import cpu_mesh
    pm = ParticleMesh(16, 16.0, dtype='f8', comm=cpu_mesh())  # n0 = 2
    pos = jnp.asarray(np.random.RandomState(1).uniform(0, 16.0, (64, 3)))
    with pytest.raises(ValueError, match="support"):
        pm.paint(pos, 1.0, resampler='tsc')  # support 3 > n0 2


def test_paint_sorted_max_collision_exact():
    """All particles in one cell: the sorted paint's doubling
    reduction must sum arbitrarily long runs exactly (f32-roundoff
    close to the f64 truth), and unused compaction slots must not
    corrupt neighboring cells."""
    from nbodykit_tpu.ops.paint import paint_local, paint_local_sorted

    pos = jnp.asarray(np.full((5000, 3), 3.3, dtype='f4'))
    for rs in ('cic', 'tsc', 'pcs'):
        truth = paint_local(pos.astype(jnp.float64), jnp.float64(1.0),
                            (8, 8, 8), resampler=rs)
        got = paint_local_sorted(pos, jnp.float32(1.0), (8, 8, 8),
                                 resampler=rs)
        scale = float(np.abs(np.asarray(truth)).max())
        err = np.abs(np.asarray(got, 'f8')
                     - np.asarray(truth)).max() / scale
        assert err < 1e-5, (rs, err)
        # total mass conserved
        assert abs(float(np.asarray(got, 'f8').sum()) - 5000) < 1.0


def test_paint_method_device_count_invariance(method='sort'):
    """The sort kernel produces device-count-invariant fields through
    the full exchange + halo path (the scatter kernel's invariance is
    test_paint_device_count_invariance above)."""
    from nbodykit_tpu import set_options

    rng = np.random.RandomState(13)
    pos_np = rng.uniform(0, 50.0, size=(3000, 3))
    fields = []
    with set_options(paint_method=method):
        for comm in [cpu_mesh(1), cpu_mesh()]:
            pm = ParticleMesh(32, 50.0, dtype='f8', comm=comm)
            field = pm.paint(jnp.asarray(pos_np), 1.0, resampler='tsc')
            fields.append(np.asarray(field))
    np.testing.assert_allclose(fields[0], fields[1], rtol=1e-10,
                               atol=1e-12)
    np.testing.assert_allclose(fields[0].sum(), 3000.0, rtol=1e-9)


def test_memory_plan_scale_claims():
    """The HBM arithmetic behind BASELINE.md: the v5e-16 stretch config
    fits per device, the single-chip 2048 does not, and small configs
    are comfortable (pmesh.memory_plan)."""
    from nbodykit_tpu.pmesh import memory_plan

    v5e = 16e9      # one chip's published HBM
    assert memory_plan(512, int(1e7), 1, hbm_bytes=v5e)['fits']
    assert not memory_plan(2048, int(1e9), 1, hbm_bytes=v5e)['fits']
    p16 = memory_plan(2048, int(1e9), 16, hbm_bytes=v5e)
    # without a device's memory the plan is arithmetic only: no verdict
    assert 'fits' not in memory_plan(512, int(1e7), 1)
    # the tile paint's padded mesh and sorted payload beside the field
    # (9.35 GB with the scatter's chunk)
    assert p16['fits'] and p16['peak_bytes'] < 12.5e9
    # monotonic in devices
    assert (memory_plan(1024, int(1e8), 8)['peak_bytes']
            < memory_plan(1024, int(1e8), 1)['peak_bytes'])
    # sort paint costs more than chunked scatter at large npart
    assert (memory_plan(1024, int(1e8), 1, paint_method='sort')
            ['paint_temporaries']
            > memory_plan(1024, int(1e8), 1)['paint_temporaries'])
