"""The tile paint (ops/paint.py::paint_local_mxu: one payload-carrying
sort, contiguous bucket slices, per-tile matrix products) against an
f64 numpy deposit and against the scatter kernel.

Its semantics must match ``paint_local`` on every geometry class: full
mesh, periodic wrap, halo-extended slab block (origin != 0, n0l <
period), the wrapped-to-valid boundary strip, and zero-mass slots at
garbage positions (the exchange's padding).  In f32 its products run
as three bf16 parts: the field itself is held to the scatter's own
error against f64, since a cell's 64^3 oracle would let an 8-bit paint
through.  Reference behavior being reproduced: pmesh's C paint
consumed at nbodykit/source/mesh/catalog.py:287-296.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from nbodykit_tpu.ops.paint import paint_local, paint_local_mxu
from nbodykit_tpu.pmesh import ParticleMesh
from nbodykit_tpu.parallel.runtime import cpu_mesh

GEOMETRIES = [
    # (n0l, N1, N2, period0, origin): full, non-cubic, slab, far-wrap
    (16, 16, 16, 16, 0),
    (32, 16, 8, 32, 0),
    (12, 16, 16, 32, 5),
    (10, 24, 16, 64, 59),
]


def _random_particles(n, p0, N1, N2, seed=1):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, p0, (n, 3))
    pos[:, 1] %= N1
    pos[:, 2] %= N2
    return jnp.asarray(pos), jnp.asarray(rng.uniform(0.5, 2.0, n))


@pytest.mark.parametrize('resampler', ['nnb', 'cic', 'tsc', 'pcs'])
def test_matches_scatter_all_geometries(resampler):
    for (n0l, N1, N2, p0, origin) in GEOMETRIES:
        pos, mass = _random_particles(3000, p0, N1, N2)
        ref = paint_local(pos, mass, (n0l, N1, N2), resampler=resampler,
                          period=(p0, N1, N2), origin=origin)
        got = paint_local_mxu(
            pos, mass, (n0l, N1, N2), resampler=resampler,
            period=(p0, N1, N2), origin=origin, rb=4, cb=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-10, atol=1e-12)


def test_default_tiles_and_out_accumulate():
    pos, mass = _random_particles(5000, 32, 32, 32, seed=3)
    base = jnp.full((32, 32, 32), 0.5, jnp.float64)
    ref = paint_local(pos, mass, (32, 32, 32), resampler='cic', out=base)
    got = paint_local_mxu(pos, mass, (32, 32, 32), resampler='cic',
                          out=base)  # default rb=cb=8
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-10, atol=1e-12)


# ---------------------------------------------------------------------------
# the f32 field against an f64 numpy deposit

N = 32
NPART = 20000
WINDOWS = {'cic': 2, 'tsc': 3}
#: (shape, period0, origin): the full block; a slab block whose rows
#: wrap through 0, with rows of the period outside it
BLOCKS = {'full': ((N, N, N), N, 0), 'slab': ((12, N, N), N, 27)}


def _f64_deposit(pos, mass, shape, resampler, p0, origin):
    """Plain numpy, f64: every window offset with ``np.add.at``."""
    s = WINDOWS[resampler]
    n0l, N1, N2 = shape
    pos = np.asarray(pos, 'f8')
    mass = np.asarray(mass, 'f8')
    if s % 2 == 0:
        base = np.floor(pos).astype('i8') - (s // 2 - 1)
    else:
        base = np.floor(pos + 0.5).astype('i8') - (s - 1) // 2
    out = np.zeros(shape, 'f8')

    def weight(d):
        d = np.abs(d)
        if s == 2:
            return np.maximum(1.0 - d, 0.0)
        return np.where(d <= 0.5, 0.75 - d * d,
                        0.5 * np.maximum(1.5 - d, 0.0) ** 2)

    for a in range(s):
        row = np.mod(base[:, 0] + a - origin, p0)
        ok = row < n0l
        w0 = weight(pos[:, 0] - (base[:, 0] + a))
        for b in range(s):
            w1 = weight(pos[:, 1] - (base[:, 1] + b))
            for c in range(s):
                w2 = weight(pos[:, 2] - (base[:, 2] + c))
                np.add.at(out, (row[ok], np.mod(base[ok, 1] + b, N1),
                                np.mod(base[ok, 2] + c, N2)),
                          (w0 * w1 * w2 * mass)[ok])
    return out


def _catalog(layout, weights, block, seed):
    """f32 positions and masses.  On a slab block a tenth of the slots
    is the exchange's padding: mass 0 at garbage positions."""
    rng = np.random.default_rng(seed)
    if layout == 'uniform':
        pos = rng.uniform(0, N, (NPART, 3))
    elif layout == 'one_cell':
        # every particle in one cell: one bucket holds the catalog
        pos = rng.uniform(0.05, 0.95, (NPART, 3)) + [29.0, 13.0, 7.0]
    else:
        # on the periodic faces: exact 0, just under N, and a shell
        # half a cell thick around both
        pos = rng.uniform(-0.5, 0.5, (NPART, 3)) % N
        pos[:200] = 0.0
        pos[200:400] = np.nextafter(np.float32(N), np.float32(0))
    mass = np.ones(NPART) if weights == 'unit' \
        else rng.uniform(-1.0, 2.0, NPART)
    pos = pos.astype('f4')
    pos[pos >= N] = 0.0
    mass = mass.astype('f4')
    if block == 'slab':
        pad = rng.random(NPART) < 0.1
        mass[pad] = 0.0
        pos[pad] = rng.uniform(-1e4, 1e4, (int(pad.sum()), 3))
    return pos, mass


@pytest.mark.parametrize('staged', [False, True], ids=['eager', 'jit'])
@pytest.mark.parametrize('block', sorted(BLOCKS))
@pytest.mark.parametrize('weights', ['unit', 'signed'])
@pytest.mark.parametrize('layout', ['uniform', 'one_cell', 'faces'])
@pytest.mark.parametrize('resampler', sorted(WINDOWS))
def test_field_against_f64_deposit(resampler, layout, weights, block,
                                   staged):
    """Per cell no farther from the f64 field than twice the scatter's
    own error plus 1e-6 of the largest cell, the total mass to 1e-6,
    and the same bytes from two calls.  No product may run at less
    than three bf16 parts; nothing can drop, whatever the occupancy."""
    shape, p0, origin = BLOCKS[block]
    pos, mass = _catalog(layout, weights, block, seed=7)
    truth = _f64_deposit(pos, mass, shape, resampler, p0, origin)
    kw = dict(resampler=resampler, period=(p0, N, N), origin=origin)

    def tile(p, m):
        return paint_local_mxu(p, m, shape, **kw)

    run = jax.jit(tile) if staged else tile
    got = np.asarray(run(jnp.asarray(pos), jnp.asarray(mass)))
    assert got.dtype == np.float32
    ref = np.asarray(paint_local(jnp.asarray(pos), jnp.asarray(mass),
                                 shape, **kw))
    top = np.abs(truth).max()
    assert np.abs(got - truth).max() <= \
        2 * np.abs(ref - truth).max() + 1e-6 * top
    assert abs(got.sum(dtype='f8') - truth.sum()) <= \
        1e-6 * np.abs(np.asarray(mass, 'f8')).sum()
    again = np.asarray(run(jnp.asarray(pos), jnp.asarray(mass)))
    assert again.tobytes() == got.tobytes()


def test_vmap_takes_catalogs_in_turn():
    """Under ``vmap`` (the served program batches seeds) each catalog
    is painted by the unbatched program: the same bytes."""
    rng = np.random.default_rng(5)
    pos = jnp.asarray(rng.uniform(0, N, (2, 3000, 3)).astype('f4'))
    mass = jnp.asarray(rng.uniform(0.5, 2.0, (2, 3000)).astype('f4'))

    def tile(p, m):
        return paint_local_mxu(p, m, (N, N, N), resampler='cic')

    both = np.asarray(jax.jit(jax.vmap(tile))(pos, mass))
    for i in range(2):
        one = np.asarray(jax.jit(tile)(pos[i], mass[i]))
        assert both[i].tobytes() == one.tobytes()


def test_piece_size_does_not_change_the_field():
    """Many small pieces against few large ones: a bucket is read in
    as many pieces as it needs, in f64 to roundoff."""
    pos, mass = _random_particles(6000, 32, 32, 32, seed=11)
    few = paint_local_mxu(pos, mass, (32, 32, 32), resampler='cic',
                          ck=1024)
    many = paint_local_mxu(pos, mass, (32, 32, 32), resampler='cic',
                           ck=1)    # one row of 128 a piece
    np.testing.assert_allclose(np.asarray(many), np.asarray(few),
                               rtol=1e-12, atol=1e-13)
    ref = paint_local(pos, mass, (32, 32, 32), resampler='cic')
    np.testing.assert_allclose(np.asarray(many), np.asarray(ref),
                               rtol=1e-10, atol=1e-12)


def test_f32_precision_close_to_f64():
    pos64, mass64 = _random_particles(20000, 32, 32, 32, seed=5)
    truth = paint_local(pos64, mass64, (32, 32, 32), resampler='cic')
    got = paint_local_mxu(pos64.astype(jnp.float32),
                          mass64.astype(jnp.float32), (32, 32, 32),
                          resampler='cic')
    scale = float(jnp.abs(truth).max())
    assert float(jnp.abs(got.astype(jnp.float64) - truth).max()) \
        < 1e-5 * scale


def test_tiny_mesh_falls_back():
    """Meshes smaller than the wrap arithmetic allows delegate to the
    scatter kernel rather than mis-painting."""
    pos, mass = _random_particles(200, 4, 4, 4, seed=7)
    ref = paint_local(pos, mass, (4, 4, 4), resampler='pcs')
    got = paint_local_mxu(pos, mass, (4, 4, 4), resampler='pcs')
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-10, atol=1e-12)


def test_one_cell_through_pmesh_under_jit():
    """The whole catalog in one cell through ``ParticleMesh.paint``
    inside ``jit`` with no dropped count asked for: there is no
    capacity to overflow."""
    pm = ParticleMesh(N, float(N), dtype='f4', comm=cpu_mesh(1))
    pos = jnp.full((5000, 3), 3.3, jnp.float32)
    field = jax.jit(lambda p: pm.paint(p, 1.0, resampler='cic'))(pos)
    assert abs(float(field.sum(dtype=jnp.float64)) - 5000.0) < 5e-3
    assert int((np.asarray(field) != 0).sum()) == 8


@pytest.mark.slow
def test_pmesh_device_count_invariance_mxu():
    """The tile paint through the full exchange + halo + shard_map
    path: 1-device and 8-device paints agree to f64 roundoff."""
    rng = np.random.RandomState(13)
    pos_np = rng.uniform(0, 50.0, size=(3000, 3))
    fields = []
    for comm in [cpu_mesh(1), cpu_mesh()]:
        pm = ParticleMesh(32, 50.0, dtype='f8', comm=comm)
        field = pm.paint(jnp.asarray(pos_np), 1.0, resampler='tsc')
        fields.append(np.asarray(field))
    np.testing.assert_allclose(fields[0], fields[1], rtol=1e-10,
                               atol=1e-12)
    np.testing.assert_allclose(fields[0].sum(), 3000.0, rtol=1e-9)
