"""The lognormal mock as the benchmark's ``desi_like_lognormal_n512``
cell runs it (ISSUE 39), at small sizes on the CPU: the sample written
into a rung of rows, the data phase as two cached programs, the plain
f8 reference (``perf/reference/lab_lognormal.py``) against the system,
and the algorithms that read a catalog's rows directly."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from nbodykit_tpu import mockmaker                         # noqa: E402
from nbodykit_tpu.diagnostics.metrics import REGISTRY       # noqa: E402
from nbodykit_tpu.lab import (FFTPower, LinearPower,        # noqa: E402
                              LogNormalCatalog, Planck15,
                              SimulationBoxPairCount)
from nbodykit_tpu.parallel.runtime import cpu_mesh, use_mesh  # noqa: E402
from perf.reference import lab_lognormal as plain           # noqa: E402

#: the cell's rate per cell: nbar 8e-5 in cells of 5000 / 512
NBAR, CELL = 8e-5, 5000.0 / 512


@pytest.fixture(scope='module')
def plin():
    return LinearPower(Planck15, redshift=0.55, transfer='EisensteinHu')


@pytest.fixture(autouse=True)
def no_program_kept():
    """Every test starts and leaves with no data-phase program kept:
    the ones that count compiles meet no other test's."""
    kept = (mockmaker._amplitude, mockmaker._fields_program,
            mockmaker._sample_program)
    for prog in kept:
        prog.cache_clear()
    yield
    for prog in kept:
        prog.cache_clear()


def counted(prefix):
    return sum(int(v['value']) for k, v in REGISTRY.snapshot().items()
               if k.startswith(prefix) and v.get('type') == 'counter')


def mock(plin, n, seed, **kw):
    return LogNormalCatalog(Plin=plin, nbar=NBAR, BoxSize=n * CELL,
                            Nmesh=n, bias=2.0, seed=seed, **kw)


def drawn_rows(cat, *cols):
    sel = np.asarray(cat['Selection'])
    return [np.asarray(cat[c])[sel] for c in cols]


@pytest.mark.parametrize('nmesh, nmu', [(32, 4), (64, 10)])
def test_system_against_the_f8_reference(plin, nmesh, nmu):
    # the cell's call at a small mesh, held to the limits of the cell's
    # oracle (its traffic file): counts equal but in 1e-6 of the cells
    # + 3, by one; positions to 2e-4 of a cell; P(k, mu) to 1e-4 in
    # every bin, only the modes on a mu edge free to fall either side
    box = nmesh * CELL
    seed = 20260 + nmesh
    cat = mock(plin, nmesh, seed)
    r = FFTPower(cat, mode='2d', Nmesh=nmesh, kmin=0.001, Nmu=nmu)
    pos, voff = drawn_rows(cat, 'Position', 'VelocityOffset')
    f = float(Planck15.scale_independent_growth_rate(0.55))
    power, modes = np.asarray(r.power['power']), np.asarray(r.power['modes'])

    def held(low=None):
        ref = plain.reference_mock_power(
            seed, nmesh, box, plin, NBAR, 2.0, nmu, 0.001, pos, voff, f,
            low=low, backend='cpu')
        why, err, _ = plain.check_binned(power, modes, ref, 1e-4)
        return ref, why, err, \
            plain.periodic_offset(pos, ref['positions'], box) / CELL

    ref, why, err, off = held()
    assert ref['raster_order'] and not why
    assert ref['count_flips'] <= 1e-6 * nmesh ** 3 + 3
    assert ref['count_worst'] <= 1
    assert off < 2e-4
    assert np.array_equal(modes.sum(axis=1), ref['modes'].sum(axis=1))
    # bfloat16 fields and density miss the positions' limit by far, and
    # at 64^3 the counts' and P's
    ref, why, err, off = held('bfloat16')
    assert off > 10 * 2e-4
    if nmesh == 64:
        assert ref['count_flips'] > 1e-6 * nmesh ** 3 + 3
        assert err > 1e-4


def test_binned_check_tolerates_only_the_modes_on_mu_edges():
    # one k shell, four mu bins, an edge mode of weight 2 between bins
    # 1 and 2 (power 5); the system may put it either side, nowhere else
    ref = {'modes_off': np.array([[2., 2., 2., 2.]]),
           'sums_off': np.array([[2., 4., 6., 8.]]),
           'edge_modes': np.array([[0., 0., 2., 0., 0.]]),
           'edge_min': np.array([[0., 0., 5., 0., 0.]]),
           'edge_max': np.array([[0., 0., 5., 0., 0.]])}
    ref['modes'] = ref['modes_off'] + [[0, 0, 2, 0]]
    up = (np.array([[1., 2., 4., 4.]]), np.array([[2., 2., 4., 2.]]))
    down = (np.array([[1., 3.5, 3., 4.]]), np.array([[2., 4., 2., 2.]]))
    for power, modes in (up, down):
        assert plain.check_binned(power, modes, ref, 1e-6)[0] == []
    moved = (np.array([[1., 2., 3., 4.5]]), np.array([[2., 2., 2., 4.]]))
    assert 'mode counts' in plain.check_binned(*moved, ref, 1e-6)[0][0]
    off = (np.array([[1., 2., 4. * 1.01, 4.]]), up[1])
    why, err, edged = plain.check_binned(*off, ref, 1e-6)
    assert why and abs(err - 0.01) < 1e-9 and edged == 1


def test_seeds_share_one_rung_and_compile_once(plin):
    # a campaign of mocks: after the first seed nothing is traced or
    # compiled again, the data phase's programs nor the paint's
    misses, traces, rungs = [], [], []
    for seed in range(4):
        cat = mock(plin, 32, 300 + seed)
        FFTPower(cat, mode='2d', Nmesh=32, kmin=0.001, Nmu=10).power
        misses.append([counted('compile.lognormal.%s.misses' % p)
                       for p in ('fields', 'sample')])
        traces.append(counted('paint.trace.'))
        rungs.append(cat.rung)
        assert cat.csize == cat.rung > cat.drawn
    assert len(set(rungs)) == 1
    assert misses[0] == misses[-1] and traces[0] == traces[-1]
    assert counted('compile.lognormal.fields.hits') >= 3
    assert mockmaker._fields_program.cache_info().misses == 1
    assert mockmaker._sample_program.cache_info().misses == 1
    assert 0.5 < REGISTRY.snapshot()['lognormal.fill']['value'] < 1.0


def test_a_forced_small_rung_climbs_and_loses_nothing(plin, monkeypatch):
    want = mock(plin, 32, 7)
    before = counted('lognormal.overflow')
    monkeypatch.setattr(mockmaker, 'RUNG_SIGMAS', -30.0)
    got = mock(plin, 32, 7)
    assert got.rung < want.rung and got.drawn == want.drawn
    assert counted('lognormal.overflow') > before
    for a, b in zip(drawn_rows(want, 'Position', 'Velocity'),
                    drawn_rows(got, 'Position', 'Velocity')):
        assert a.tobytes() == b.tobytes()
    assert mockmaker.sample_rung(1e7, 10 ** 7)[1] > 0
    monkeypatch.undo()
    rung, climbs = mockmaker.sample_rung(1e7, 10 ** 7)
    assert (rung, climbs) == (10019840, 0)
    assert mockmaker.sample_rung(1e7, rung - 8) == (rung, 0)
    assert mockmaker.sample_rung(1e7, rung - 7) == (-(-rung * 17 // 16), 1)


def parent_catalog(pm, plin, seed, vfac, f):
    """The parent's ``LogNormalCatalog`` (PR 38), op by op: the eager
    fields, the lognormal rate, the Poisson counts, the count fetched,
    a ``jnp.repeat`` of the cell index, three gathers of psi, the
    Zel'dovich update.  Its sqrt(P/V) is this PR's
    (``power_amplitude``, Plin's f8 path where the parent read Plin's
    device table), and its Poisson draw (``poisson_counts``, Knuth's
    product where the parent summed a backend's logs): the rest of the
    recipe is the parent's."""
    delta, psi = mockmaker.gaussian_real_fields(pm, plin, seed,
                                                compute_displacement=True)
    key = jax.random.key(seed)
    k_pois, k_shift = jax.random.split(key)
    overdensity = mockmaker.lognormal_transform(delta, bias=2.0 - 1.0)
    lam = (NBAR * float(np.prod(pm.cellsize))) * overdensity.value
    counts = mockmaker.poisson_counts(k_pois, lam)
    n = int(counts.sum())
    flat = counts.reshape(-1)
    cell_ids = jnp.repeat(jnp.arange(flat.shape[0]), flat,
                          total_repeat_length=n)
    N0, N1, N2 = pm.shape_real
    H = pm.cellsize
    corner = jnp.stack([cell_ids // (N1 * N2), (cell_ids // N2) % N1,
                        cell_ids % N2], axis=-1).astype(jnp.float32) \
        * jnp.asarray(H, jnp.float32)
    pos = corner + jax.random.uniform(k_shift, (n, 3), jnp.float32) \
        * jnp.asarray(H, jnp.float32)
    disp = jnp.stack([d.value.reshape(-1)[cell_ids] for d in psi],
                     axis=-1).astype(jnp.float32)
    pos = jnp.mod(pos + disp, jnp.asarray(pm.BoxSize, pos.dtype))
    return pos, (disp * vfac).astype(pos.dtype), \
        (disp * f).astype(pos.dtype)


@pytest.mark.parametrize('x64', [True, False])
@pytest.mark.parametrize('seed', [7, 2 ** 31 - 2])
def test_drawn_rows_equal_the_parents_catalog(plin, seed, x64):
    # the parent's catalog op by op and the two cached programs with the
    # rung: the drawn rows to the bit, in raster order
    from nbodykit_tpu.pmesh import ParticleMesh
    pm = ParticleMesh(32, 32 * CELL)
    z = 0.55
    f = float(Planck15.scale_independent_growth_rate(z))
    vfac = f * 100.0 * float(Planck15.efunc(z)) / (1.0 + z)
    with jax.enable_x64(x64):
        want = parent_catalog(pm, plin, seed, vfac, f)
        cat = LogNormalCatalog(Plin=plin, nbar=NBAR, BoxSize=32 * CELL,
                               Nmesh=32, bias=2.0, seed=seed,
                               cosmo=Planck15, redshift=z)
    got = drawn_rows(cat, 'Position', 'Velocity', 'VelocityOffset')
    for a, b in zip(want, got):
        assert np.asarray(a).tobytes() == b.tobytes()
    assert cat.drawn == len(want[0]) and cat.csize == cat.rung
    sub = cat[cat['Selection']]
    assert sub.csize == cat.drawn
    assert np.asarray(sub['Position']).tobytes() == got[0].tobytes()
    # the padding is zeros, and paints nothing
    pad = ~np.asarray(cat['Selection'])
    assert pad.sum() == cat.rung - cat.drawn > 0
    assert not np.asarray(cat['Position'])[pad].any()
    assert repr(cat) == 'LogNormalCatalog(drawn=%d, rung=%d, seed=%d)' % (
        cat.drawn, cat.rung, seed)


def test_exp_neg_within_an_ulp():
    lam = np.linspace(0.0, mockmaker.KNUTH_MAX, 200001, dtype='f4')[:-1]
    want = np.exp(-lam.astype('f8'))
    got = np.asarray(jax.jit(mockmaker._exp_neg)(jnp.asarray(lam)), 'f8')
    ulp = np.spacing(want.astype('f4')).astype('f8')
    assert (np.abs(got - want) <= 1.01 * ulp).all()


def test_poisson_counts_are_knuths_and_jaxs():
    # jax's draw but for rounding (its sum of logs, the product here),
    # the plain f8 replay's but for f4 against f8 (a boundary flips),
    # and jax's own rejection for a rate of 10 or more
    rng = np.random.RandomState(3)
    lam = (0.07 * np.exp(rng.standard_normal((32, 32, 32)))).astype('f4')
    lam[0, 0, :4] = [0.0, 12.0, 40.0, 9.99]
    key = jax.random.split(jax.random.key(41))[0]
    got = np.asarray(jax.jit(mockmaker.poisson_counts)(key, lam))
    want = np.asarray(jax.random.poisson(key, jnp.asarray(lam)))
    assert got.dtype == np.int32 and got[0, 0, 0] == 0
    assert (got != want).sum() <= 2
    assert got[0, 0, 1] == want[0, 0, 1] and got[0, 0, 2] == want[0, 0, 2]
    ref = plain.poisson_counts(41, lam)
    assert (got != ref).sum() <= 2 and np.abs(got - ref).max() <= 1
    assert got.sum() > 0.5 * lam.sum()


@pytest.mark.parametrize('shape, step, case', [
    ((4, 4, 16), None, None), ((3, 5, 13), None, None),
    ((8, 8, 32), 64, None), ((4, 3, 100), None, None),
    ((3, 4, 128), None, None), ((5, 4, 16), None, 'empty plane'),
    ((4, 4, 64), None, 'full cell'), ((4, 4, 64), 16, 'full cell')])
def test_rows_to_cells_is_a_repeat(monkeypatch, shape, step, case):
    # any last axis (13 and 100 are no multiple of a segment, 128 is
    # two), dense and sparse cells, a cell over a segment's worth of
    # rows (200 of 64 cells), a plane of empty cells, and rows in
    # several steps, down to steps shorter than a segment's rows
    if step:
        monkeypatch.setattr(mockmaker, 'ROWS_PER_STEP', step)
    rng = np.random.RandomState(sum(shape))
    counts = rng.poisson(rng.exponential(0.5, shape) ** 2).astype('i4')
    counts.reshape(-1)[5] = 40
    if case == 'empty plane':
        counts[2] = 0
    elif case == 'full cell':
        counts.reshape(-1)[70] = 200
    counts.reshape(-1)[3] += 1
    psi = [rng.standard_normal(shape).astype('f4') for _ in range(3)]
    psi[0].reshape(-1)[3] = -0.0
    n = int(counts.sum())
    cell, disp = jax.jit(lambda c, p: mockmaker.rows_to_cells(
        c, p, n + 100))(jnp.asarray(counts), [jnp.asarray(p) for p in psi])
    want = np.repeat(np.arange(counts.size), counts.reshape(-1))
    assert np.array_equal(np.asarray(cell)[:n], want)
    for k in range(3):
        assert np.asarray(disp)[:n, k].tobytes() \
            == psi[k].reshape(-1)[want].tobytes()


def test_sample_span_and_counter_name_the_placement(plin, tmp_path,
                                                    monkeypatch):
    # each lognormal.sample span says how its rows were placed: segments
    # of 64 cells, in so many steps of the loop; the trace-time counter
    # lognormal.sample.trace.segment counts one a compiled program
    from nbodykit_tpu import set_options
    from nbodykit_tpu.diagnostics.trace import read_trace
    monkeypatch.setattr(mockmaker, 'ROWS_PER_STEP', 1024)
    before = counted('lognormal.sample.trace.segment')
    with set_options(diagnostics=str(tmp_path)):
        cats = [mock(plin, 32, seed) for seed in (1, 2)]
    spans = [r for r in read_trace(str(tmp_path))[0]
             if r.get('t') == 'span' and r['name'] == 'lognormal.sample']
    assert cats[0].rung == cats[1].rung > 2048
    assert [(s['attrs']['segment'], s['attrs']['steps']) for s in spans] \
        == [(64, -(-cats[0].rung // 1024))] * 2
    assert counted('lognormal.sample.trace.segment') - before == 1


@pytest.mark.parametrize('ndev', [1, 4])
def test_device_count_invariance_on_a_mesh(plin, ndev):
    with use_mesh(cpu_mesh(1)):
        one = drawn_rows(mock(plin, 32, 11), 'Position')[0]
    with use_mesh(cpu_mesh(ndev)):
        cat = mock(plin, 32, 11)
        got = drawn_rows(cat, 'Position')[0]
    assert one.shape == got.shape
    np.testing.assert_allclose(one, got, rtol=1e-5, atol=1e-4)


def _sky(cat):
    """RA, DEC and Redshift columns from the box positions (a survey
    algorithm's input; padding rows get the origin's)."""
    L = float(cat.attrs['BoxSize'][0])
    pos = jnp.asarray(cat['Position']) / L
    cat['RA'] = 360.0 * pos[:, 0]
    cat['DEC'] = 170.0 * pos[:, 1] - 85.0
    cat['Redshift'] = 0.05 + 0.1 * pos[:, 2]
    return cat


def _row_reader(name):
    """Each algorithm that reads a catalog's rows itself, as a function
    of the catalog returning its result's arrays."""
    from nbodykit_tpu import lab
    edges = np.linspace(10.0, 40.0, 4)

    def box(cat):
        return cat.attrs['BoxSize']
    return {
        'SimulationBoxPairCount': lambda c: lab.SimulationBoxPairCount(
            '1d', c, edges, BoxSize=box(c)).pairs['npairs'],
        'Bispectrum.direct': lambda c: lab.Bispectrum(
            c, nbins=3, BoxSize=box(c), method='direct').B['B'],
        'SurveyDataPairCount': lambda c: lab.SurveyDataPairCount(
            'angular', c, np.linspace(1.0, 10.0, 4)).pairs['npairs'],
        'SurveyData3PCF': lambda c: lab.SurveyData3PCF(
            c, [0, 1], edges, Planck15).poles['corr_0'],
        'SimulationBox3PCF': lambda c: lab.SimulationBox3PCF(
            c, [0, 1], edges, BoxSize=box(c)).poles['corr_0'],
        'RedshiftHistogram': lambda c: lab.RedshiftHistogram(
            c, 0.1, Planck15, bins=np.linspace(0.05, 0.15, 5)).nbar,
        'FOF': lambda c: np.bincount(lab.FOF(c, 0.2, 2).labels),
        'KDDensity': lambda c: lab.KDDensity(c).density,
        'SubVolumesCatalog': lambda c: lab.SubVolumesCatalog(
            c, domain=[2, 2, 2])['SubVolumeIndex'],
    }[name]


@pytest.mark.parametrize('name', [
    'SimulationBoxPairCount', 'Bispectrum.direct', 'SurveyDataPairCount',
    'SurveyData3PCF', 'SimulationBox3PCF', 'RedshiftHistogram', 'FOF',
    'KDDensity', 'SubVolumesCatalog'])
def test_algorithms_that_read_rows_skip_the_padding(plin, name):
    # these know no Selection: on a catalog drawn into a rung they read
    # its drawn rows alone, as on cat[cat['Selection']]
    cat = _sky(mock(plin, 32, 5))
    assert cat.drawn < cat.csize
    sub = cat[cat['Selection']]
    got, want = (np.asarray(_row_reader(name)(c)) for c in (cat, sub))
    assert np.array_equal(got, want, equal_nan=True)
