"""ConvolvedFFTPower against ``tests/plain_convpower.py``: the
estimator written out in plain numpy from its definition, on seeded
survey catalogs with an anisotropic signal.

The data carry a completeness weight ``1 + 0.5 cos(k0 . x)`` with
``k0`` off every axis, seen from an observer at the corner of the box:
``P_2`` stands as high as ``P_0`` and ``P_4`` at half of it, so a
wrong sign, normalisation or ``m`` in the ``Y_lm`` sum is a difference
of order one, not a change in the noise.  Also here: the spans and
counters of the survey path, the bounding box reduced on the device,
and the reference's slabs held to the answer of one.
"""

import numpy as np
import pytest

import plain_convpower as plain
from nbodykit_tpu import diagnostics
from nbodykit_tpu.diagnostics import REGISTRY, read_trace
from nbodykit_tpu.lab import (ArrayCatalog, ConvolvedFFTPower, FKPCatalog,
                              set_options)

#: x64 on both sides: what is left is the order of the sums
TOLERANCE = 1e-10
BOX, DK = 1000.0, 0.02


def survey(seed, ndata, P0=None, wave=0.5):
    """Data and randoms (ten times as many) uniform in the box, as the
    reference takes them.  With ``P0`` the ``nbar`` column rises across
    the box, so the FKP weights differ from particle to particle."""
    rng = np.random.RandomState(seed)
    k0 = 2 * np.pi / BOX * np.array([2.0, 1.0, 3.0])
    out = []
    for n, amplitude in ((ndata, wave), (10 * ndata, 0.0)):
        pos = rng.uniform(size=(n, 3)) * BOX
        nbar = np.full(n, ndata / BOX ** 3)
        if P0:
            nbar = nbar * (1 + 0.3 * pos[:, 0] / BOX)
        out.append({'pos': pos, 'nbar': nbar,
                    'comp': 1 + amplitude * np.cos(pos @ k0),
                    'fkp': 1 / (1 + P0 * nbar) if P0 else np.ones(n)})
    return out


def system(data, randoms, nmesh, poles, P0=None):
    cats = [ArrayCatalog({'Position': s['pos'], 'Weight': s['comp'],
                          'NZ': s['nbar']}) for s in (data, randoms)]
    mesh = FKPCatalog(*cats, P0=P0).to_mesh(Nmesh=nmesh, resampler='tsc')
    return ConvolvedFFTPower(mesh, poles=poles, dk=DK)


def worst(result, ref, poles):
    """Largest difference of any multipole, real or imaginary part,
    over the largest monopole."""
    scale = np.abs(ref['power_0']).max()
    return max(np.abs(result.poles['power_%d' % ell]
                      - ref['power_%d' % ell]).max()
               for ell in poles) / scale


@pytest.fixture(scope='module')
def case_32():
    data, randoms = survey(5, 5000)
    return data, randoms, system(data, randoms, 32, [0, 2, 4])


@pytest.mark.parametrize('nmesh, ndata, P0', [
    (32, 5000, None), (64, 20000, None), (32, 5000, 1e5)])
def test_multipoles_and_attrs_against_the_plain_estimator(
        nmesh, ndata, P0, case_32):
    if (nmesh, P0) == (32, None):
        data, randoms, r = case_32
    else:
        data, randoms = survey(5, ndata, P0)
        r = system(data, randoms, nmesh, [0, 2, 4], P0)
    ref = plain.reference_convpower(data, randoms, nmesh, [0, 2, 4], DK)
    # the signal is there: P_2 of the order of P_0, P_4 a third of it
    scale = np.abs(ref['power_0']).max()
    assert np.abs(ref['power_2']).max() > 0.9 * scale
    assert np.abs(ref['power_4']).max() > 0.3 * scale
    assert scale > 1.3 * ref['shotnoise']
    assert np.array_equal(r.poles['modes'], ref['modes'])
    assert worst(r, ref, [0, 2, 4]) < TOLERANCE
    np.testing.assert_allclose(r.poles['k'], ref['k'], rtol=1e-12)
    for name in ('alpha', 'data.norm', 'randoms.norm', 'shotnoise',
                 'BoxSize', 'BoxCenter'):
        np.testing.assert_allclose(r.attrs[name], ref[name], rtol=1e-6)
    if P0:      # the weights did differ from particle to particle
        assert np.ptp(data["fkp"]) > 0.05 * data["fkp"].mean()


@pytest.mark.parametrize('fault, misses_by', [
    ('one m dropped from ell = 2', 1e-2),
    ('bfloat16 fields', 2e-4)])
def test_the_tolerance_is_tight(fault, misses_by, case_32, monkeypatch):
    data, randoms, r = case_32
    kwargs = {}
    if fault.startswith('one m'):
        monkeypatch.setitem(plain.REAL_YLM, 2, plain.REAL_YLM[2][:-1])
    else:
        kwargs['quantize'] = plain.round_to_bfloat16
    ref = plain.reference_convpower(data, randoms, 32, [0, 2, 4], DK,
                                    **kwargs)
    assert np.array_equal(r.poles['modes'], ref['modes'])
    # five orders over the tolerance, and more
    assert worst(r, ref, [0, 2, 4]) > misses_by > 1e5 * TOLERANCE


@pytest.mark.parametrize('ell', [0, 2, 4])
def test_the_plain_harmonics_obey_the_addition_theorem(ell):
    from numpy.polynomial.legendre import legval
    rng = np.random.RandomState(ell)
    a, b = rng.standard_normal((2, 3, 50))
    a, b = a / np.sqrt((a * a).sum(0)), b / np.sqrt((b * b).sum(0))
    assert len(plain.REAL_YLM[ell]) == 2 * ell + 1
    total = sum(Y(*a) * Y(*b) for Y in plain.REAL_YLM[ell])
    want = (2 * ell + 1) / (4 * np.pi) * legval(
        (a * b).sum(0), np.eye(ell + 1)[ell])
    np.testing.assert_allclose(total, want, rtol=1e-12, atol=1e-14)


def test_the_slabs_do_not_show(case_32, monkeypatch):
    # the reference works through the mesh a few planes at a time, the
    # deposit with a plane of halo either side: one slab of the whole
    # mesh (its halo wraps onto itself) gives the same answer
    import perf.reference.lab_convpower as reference
    assert plain.reference_convpower is reference.reference_convpower
    data, randoms, _ = case_32
    a = plain.reference_convpower(data, randoms, 32, [0, 2, 4], DK)
    slabs = []
    monkeypatch.setattr(
        reference, 'in_slabs', lambda fn, n, rows=None: slabs.append(n)
        or plain.in_slabs(fn, n, rows=n))
    b = plain.reference_convpower(data, randoms, 32, [0, 2, 4], DK)
    assert slabs and sorted(a) == sorted(b)
    assert np.array_equal(a['modes'], b['modes'])
    scale = np.abs(a['power_0']).max()
    for name in a:
        assert np.allclose(a[name], b[name], rtol=1e-12,
                           atol=1e-12 * scale, equal_nan=True), name


def test_bfloat16_rounding_is_to_nearest_even():
    got = plain.round_to_bfloat16(
        [1.0, 1 + 2.0 ** -8, 1 + 3 * 2.0 ** -8, -3.14159, 0.0])
    assert got.tolist() == [1.0, 1.0, 1 + 2.0 ** -6, -3.140625, 0.0]


@pytest.mark.parametrize('selected', ['all', 'some'])
def test_bounding_box_from_the_device_to_the_bit(selected):
    rng = np.random.RandomState(3)
    pos = rng.uniform(size=(5000, 3)) * [900.0, 700.0, 1100.0] + 50.0
    cats = [ArrayCatalog({'Position': p, 'NZ': np.ones(len(p))})
            for p in (pos[:500], pos)]
    keep = np.ones(len(pos), bool)
    if selected == 'some':
        keep = pos[:, 1] < 600.0
        cats[1]['Selection'] = keep
    fkp = FKPCatalog(*cats)
    size, centre = fkp._define_bbox('Position', 'Selection', 'randoms')
    lo, hi = pos[keep].min(axis=0), pos[keep].max(axis=0)
    assert np.array_equal(size, np.ceil(np.abs(hi - lo) * 1.02))
    assert np.array_equal(centre, 0.5 * (lo + hi))
    cats[1]['Selection'] = np.zeros(len(pos), bool)
    with pytest.raises(ValueError, match='no selected objects'):
        fkp._define_bbox('Position', 'Selection', 'randoms')


@pytest.mark.parametrize('column', ['equal', 'spread', 'short', 'empty'])
def test_column_total_of_f4_rows_is_good_to_a_rounding(column):
    # the sums P(k) is divided by: 2e5 equal f4 values read 1.5e-6 off
    # in a plain device sum on the chip (PERF.md, PR 32)
    from nbodykit_tpu.algorithms.convpower.catalogmesh import column_total
    rng = np.random.RandomState(1)
    x = {'equal': np.full(200000, 2e-5), 'short': np.arange(5.0),
         'spread': rng.lognormal(size=1000003), 'empty': np.zeros(0)
         }[column].astype('f4')
    got, want = column_total(x), x.astype('f8').sum()
    assert abs(got - want) <= 1.2e-7 * want


def test_spans_and_counters_of_the_survey_path(tmp_path):
    data, randoms = survey(7, 2000)
    cats = [ArrayCatalog({'Position': s['pos'], 'Weight': s['comp'],
                          'NZ': s['nbar']}) for s in (data, randoms)]
    mesh = FKPCatalog(*cats).to_mesh(Nmesh=32, resampler='tsc')
    ConvolvedFFTPower(mesh, poles=[0, 2, 4], dk=DK)      # warm
    before = REGISTRY.snapshot()

    def counted(name):
        now = REGISTRY.snapshot().get(name)
        return (now['value'] if now else 0) - (
            before[name]['value'] if name in before else 0)

    try:
        with set_options(diagnostics=str(tmp_path)):
            ConvolvedFFTPower(mesh, poles=[0, 2, 4], dk=DK)
    finally:
        diagnostics.configure(None)
    # a warm call traces nothing: every per-ell program is fetched
    assert counted('convpower.ffts') == 15
    assert counted('compile.convpower.ell.misses') == 0
    assert counted('compile.convpower.ell.hits') == 3
    assert counted('compile.convpower.p3d.misses') == 0
    assert counted('compile.convpower.combine.misses') == 0

    records, bad = read_trace(str(tmp_path))
    assert bad == 0
    spans = [r for r in records if r.get('t') == 'span'
             and not r['name'].startswith('compile.')]
    names = {s['id']: s['name'] for s in spans}

    def named(name):
        return [s for s in spans if s['name'] == name]

    run, = named('convpower.run')
    assert run['attrs']['poles'] == [0, 2, 4]
    density, = named('convpower.density')
    assert density['attrs'] == {'species': ['data', 'randoms'],
                                'npart': 22000, 'resampler': 'tsc'}
    paints = named('paint')
    assert [names[p['par']] for p in paints] == ['convpower.density'] * 2
    assert [(p['attrs']['npart'], p['attrs']['resampler'])
            for p in paints] == [(2000, 'tsc'), (20000, 'tsc')]
    assert [(s['attrs']['ell'], s['attrs']['nfft'])
            for s in named('convpower.ylm')] == [(0, 1), (2, 5), (4, 9)]
    assert len(named('fftpower.transfer')) == 3
    binnings = named('fftpower.binning')
    assert len(binnings) == 3
    assert binnings[0]['attrs']['nmu_edges'] == 2
    assert len(named('convpower.stats')) == 3
    # everything a call does sits under its root
    assert all(s is run or s['par'] in names for s in spans)
