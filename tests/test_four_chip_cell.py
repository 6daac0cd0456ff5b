"""The four-chip deployment (``desi_like_n1024.lab_x4``) rehearsed on
four virtual CPU devices, with the TPU branch of every
``is_mxu_backend()`` dispatch as ``tests/test_chip_smoke.py`` takes it:

(a) ``FFTPower(mode='2d', kmin, Nmu)`` under a four-device slab mesh
    against a plain numpy f8 P(k, mu) written here, sharing no code
    with ``nbodykit_tpu/algorithms`` or ``perf/``, and against the
    one-device call;
(b) the exchange capacity comes from a ladder: catalogs of one (N, P)
    that are balanced alike share one capacity and so one set of
    static shapes, never below the exact count; an unbalanced catalog
    climbs the ladder and loses nothing;
(c) the trace of one four-device call: ``exchange`` spans with
    ``capacity``, ``capacity_exact`` and ``fill``, the ``fft.a2a.*``
    scopes in the slab r2c's HLO, ``exchange.dropped`` at 0;
(d) the exchange, the slab paint and the slab r2c run eagerly as one
    cached jitted program each (``compile.exchange``, ``.paint.slab``,
    ``.fft.slab.r2c``): a warm call re-traces none of them, whatever
    they read is in their key, and their results are those of the
    traced forms to the bit."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import nbodykit_tpu.utils
from nbodykit_tpu import diagnostics
from nbodykit_tpu.diagnostics import REGISTRY, read_trace
from nbodykit_tpu.lab import (ArrayCatalog, FFTPower, cpu_mesh,
                              set_options, use_mesh)
from nbodykit_tpu.parallel.exchange import (RUNG, auto_capacity,
                                            exchange_by_dest,
                                            ladder_capacity,
                                            pair_count_max)
from nbodykit_tpu.parallel.runtime import shard_leading
from nbodykit_tpu.pmesh import ParticleMesh, memory_plan

BOX = 1000.0
KMIN = 0.001


@pytest.fixture
def tpu_branch(monkeypatch):
    # without x64, as on the chip (the suite turns it on)
    monkeypatch.setattr(nbodykit_tpu.utils, 'is_mxu_backend',
                        lambda: True)
    with jax.enable_x64(False):
        yield


@pytest.fixture
def clean_registry():
    REGISTRY.reset()
    yield
    REGISTRY.reset()
    diagnostics.configure(None)


# ---------------------------------------------------------------------------
# (a) the system against the plain reference

def plain_pkmu(pos, box, nmesh, nmu, kmin):
    """P(k, mu) of unit-weight particles from the estimator's
    definition, in f8: cloud-in-cell density, its Fourier transform
    with the CIC window and first-order aliasing divided out, the DC
    mode dropped, |delta_k|^2 V averaged over the modes of each
    (k, mu) bin on the half lattice with Hermitian pairs counted
    twice.  k edges from ``kmin`` in steps of 2 pi / box up to past the
    Nyquist frequency, ``nmu`` bins over -1 <= mu <= 1 (line of sight
    z), mu = 1 in the last."""
    n = int(nmesh)
    x = np.asarray(pos, 'f8') / box * n
    cell = np.floor(x).astype(int)
    frac = x - cell
    rho = np.zeros((n, n, n))
    for shift in np.ndindex(2, 2, 2):
        w = np.prod(np.where(shift, frac, 1 - frac), axis=1)
        i, j, k = ((cell + shift) % n).T
        np.add.at(rho, (i, j, k), w)
    delta = rho / rho.mean()
    dk = np.fft.rfftn(delta) / n ** 3
    freq = [np.fft.fftfreq(n, 1.0 / n), np.fft.fftfreq(n, 1.0 / n),
            np.arange(n // 2 + 1.0)]
    grid = np.meshgrid(*freq, indexing='ij')
    for m in grid:
        dk /= np.sqrt(1 - 2.0 / 3 * np.sin(np.pi * m / n) ** 2)
    p3 = np.abs(dk) ** 2 * box ** 3
    kf = 2 * np.pi / box
    kk = kf * np.sqrt(sum(m ** 2 for m in grid))
    with np.errstate(invalid='ignore'):
        mu = np.where(kk > 0, kf * grid[2] / kk, 0.0)
    twice = np.where((grid[2] == 0) | (grid[2] == n // 2), 1.0, 2.0)
    twice[0, 0, 0] = 0.0        # the DC mode carries no power
    kedges = np.arange(kmin, np.pi * n / box + kf / 2, kf)
    muedges = np.linspace(-1, 1, nmu + 1)
    ik = np.digitize(kk, kedges) - 1
    imu = np.minimum(np.digitize(mu, muedges) - 1, nmu - 1)
    nk = len(kedges) - 1
    keep = (ik >= 0) & (ik < nk)
    modes = np.zeros((nk, nmu))
    power = np.zeros((nk, nmu))
    np.add.at(modes, (ik[keep], imu[keep]), twice[keep])
    np.add.at(power, (ik[keep], imu[keep]), (twice * p3)[keep])
    with np.errstate(invalid='ignore', divide='ignore'):
        return power / modes, modes


def fftpower_2d(pos, nmesh, nmu, mesh):
    with use_mesh(mesh):
        cat = ArrayCatalog({'Position': jnp.asarray(pos)},
                           BoxSize=BOX)
        r = FFTPower(cat, mode='2d', Nmesh=nmesh, kmin=KMIN, Nmu=nmu)
        return (np.asarray(r.power['power']).real,
                np.asarray(r.power['modes']))


@pytest.mark.parametrize('nmesh,npart', [(32, 50000), (64, 200000)])
def test_fftpower_2d_on_four_devices_against_plain_numpy(
        tpu_branch, nmesh, npart):
    # Nmu = 4: no lattice mode lies on an interior mu edge (mu = 1/2
    # needs kx^2 + ky^2 = 3 kz^2, which no integers solve), so the
    # mode counts compare exactly
    pos = np.random.RandomState(nmesh).uniform(
        0, BOX, (npart, 3)).astype('f4')
    want, want_modes = plain_pkmu(pos, BOX, nmesh, 4, KMIN)
    got, modes = fftpower_2d(pos, nmesh, 4, cpu_mesh(4))
    one, one_modes = fftpower_2d(pos, nmesh, 4, cpu_mesh(1))
    assert np.array_equal(modes, want_modes)
    assert np.array_equal(modes, one_modes)
    # the DC mode sits in a bin of its own count and no power: skip
    ok = (want_modes > 0) & (want > 0)
    assert ok.sum() > 0.4 * ok.size
    assert np.max(np.abs(got[ok] / want[ok] - 1)) < 1e-3
    assert np.max(np.abs(got[ok] / one[ok] - 1)) < 1e-5


# ---------------------------------------------------------------------------
# (b) the ladder

def uniform_dest(n, nproc, seed):
    """Slab owners of ``n`` uniform positions, and the exact largest
    (src, dst) count under the even sharding, in numpy."""
    dest = (np.random.RandomState(seed).uniform(size=n)
            * nproc).astype('i8')
    src = np.arange(n) // -(-n // nproc)
    return dest, int(np.bincount(src * nproc + dest,
                                 minlength=nproc * nproc).max())


def exact_bound(exact, slack=1.05):
    """What ``auto_capacity`` returned before the ladder."""
    return int(np.ceil(exact * slack)) + 8


def test_twenty_seeds_of_the_published_size_share_one_capacity():
    n, nproc = 10 ** 7, 4
    caps = set()
    for seed in range(20):
        exact = uniform_dest(n, nproc, 4000 + seed)[1]
        cap = ladder_capacity(exact, n, nproc)
        assert exact_bound(exact) <= cap <= 1.08 * exact_bound(exact)
        caps.add(cap)
    # the rung at 17/16 of N / P^2; the exact bounds sit 1% below it
    assert caps == {664063}


def test_unbalanced_catalog_takes_a_higher_rung():
    n, nproc = 10 ** 6, 4
    balanced = uniform_dest(n, nproc, 1)[1]
    rng = np.random.RandomState(2)
    dest = np.where(rng.uniform(size=n) < 0.5, 0,
                    (rng.uniform(size=n) * nproc).astype('i8'))
    src = np.arange(n) // (n // nproc)
    skewed = int(np.bincount(src * nproc + dest).max())
    assert skewed > 2 * balanced
    cap = ladder_capacity(skewed, n, nproc)
    assert cap > ladder_capacity(balanced, n, nproc)
    assert exact_bound(skewed) <= cap \
        <= exact_bound(skewed) * RUNG[0] / RUNG[1] + 1


@pytest.mark.parametrize('n,nproc,exact,slack', [
    (10 ** 7, 4, 625000, 1.05),     # perfectly balanced
    (10 ** 7, 4, 2500000, 1.05),    # one source sends all to one slab
    (10 ** 9, 16, 3921000, 1.25),
    (320, 8, 40, 1.0), (7, 4, 2, 1.05), (0, 4, 0, 1.05)])
def test_rung_is_at_least_the_exact_bound_and_at_most_one_above(
        n, nproc, exact, slack):
    cap = ladder_capacity(exact, n, nproc, slack=slack)
    bound = exact_bound(exact, slack)
    base = max(-(-n // nproc ** 2), 1)
    assert bound <= cap <= max(bound * RUNG[0] / RUNG[1] + 1, base)
    # a rung, whatever count led to it
    assert cap in [-(-base * RUNG[0] ** k // RUNG[1] ** k)
                   for k in range(120)]


def test_counted_capacity_of_an_array_is_its_rung():
    nproc = 4
    dest = uniform_dest(100000, nproc, 3)[0]
    exact = pair_count_max(jnp.asarray(dest, jnp.int32), nproc)
    assert exact == uniform_dest(100000, nproc, 3)[1]
    assert auto_capacity(jnp.asarray(dest, jnp.int32), nproc) \
        == ladder_capacity(exact, 100000, nproc)
    pm = ParticleMesh(32, 32.0, dtype='f4', comm=cpu_mesh(4))
    pos = np.random.RandomState(4).uniform(0, 32.0, (100000, 3))
    owner = jnp.asarray(np.floor(pos[:, 0]).astype('i4') // 8)
    assert pm.exchange_capacity(jnp.asarray(pos, jnp.float32)) \
        == auto_capacity(owner, nproc)


def half_in_one_slab(npart, box, seed):
    rng = np.random.RandomState(seed)
    pos = rng.uniform(0, box, (npart, 3))
    pos[:npart // 2, 0] *= 0.25     # the first slab of four
    return pos[rng.permutation(npart)].astype('f4')


def test_exchange_on_the_rung_drops_nothing(tpu_branch):
    mesh = cpu_mesh(4)
    pos = half_in_one_slab(40000, 32.0, 5)
    dest = shard_leading(mesh, jnp.asarray(
        np.floor(pos[:, 0]).astype('i4') // 8))
    tag = shard_leading(mesh, jnp.arange(40000, dtype=jnp.int32))
    cap = auto_capacity(dest, 4)
    assert cap > ladder_capacity(2500, 40000, 4)    # climbed
    for capacity in (None, cap):
        (got,), valid, dropped = exchange_by_dest(dest, [tag], mesh,
                                                  capacity)
        assert int(dropped) == 0
        assert got.shape == (4 * 4 * cap,)
        valid = np.asarray(valid)
        assert valid.sum() == 40000
        # every particle arrived once, on its slab's device
        got = np.asarray(got)
        assert np.array_equal(np.sort(got[valid]), np.arange(40000))
        owner = np.repeat(np.arange(4), 4 * cap)
        assert np.array_equal(np.asarray(dest)[got[valid]],
                              owner[valid])


@pytest.mark.parametrize('skewed', [False, True])
def test_paint_on_the_rung_conserves_mass_to_the_last_particle(
        tpu_branch, clean_registry, skewed):
    npart = 60000
    pos = half_in_one_slab(npart, 32.0, 6) if skewed else \
        np.random.RandomState(6).uniform(0, 32.0, (npart, 3)).astype('f4')
    pm4 = ParticleMesh(32, 32.0, dtype='f4', comm=cpu_mesh(4))
    pm1 = ParticleMesh(32, 32.0, dtype='f4', comm=cpu_mesh(1))
    field = np.asarray(pm4.paint(jnp.asarray(pos), 1.0, resampler='cic'))
    assert abs(float(field.sum(dtype='f8')) - npart) < 0.5
    np.testing.assert_allclose(
        field, np.asarray(pm1.paint(jnp.asarray(pos), 1.0,
                                    resampler='cic')),
        rtol=1e-5, atol=1e-5)
    # the eager paint read its exchange's count: nothing was lost
    assert REGISTRY.snapshot()['exchange.dropped']['value'] == 0


def test_memory_plan_prices_the_rung():
    # the 'counted' branch assumes an imbalance of 1.5, not the exact
    # count: a balanced catalog's rung (17/16 of N / P^2) is inside it
    n, nproc = 10 ** 7, 4
    plan = memory_plan(1024, n, nproc)
    cap = ladder_capacity(uniform_dest(n, nproc, 7)[1], n, nproc)
    payload = 3 * 4 + 4 + 1 + 4     # as memory_plan counts a slot
    assert plan['exchange_buffers'] >= 2 * nproc * cap * payload


# ---------------------------------------------------------------------------
# (c) the trace

def test_exchange_span_says_capacity_exact_count_and_fill(
        tpu_branch, clean_registry, tmp_path):
    npart = 50000
    pos = np.random.RandomState(8).uniform(0, BOX, (npart, 3)) \
        .astype('f4')
    with set_options(diagnostics=str(tmp_path)):
        fftpower_2d(pos, 32, 4, cpu_mesh(4))
    records, bad = read_trace(str(tmp_path))
    assert bad == 0
    spans = [r for r in records if r.get('t') == 'span']
    exch = [s for s in spans if s['name'] == 'exchange']
    assert exch
    by_id = {s['id']: s for s in spans}
    for s in exch:
        a = s['attrs']
        assert a['nproc'] == 4 and a['npart'] == npart
        assert a['capacity'] == ladder_capacity(
            a['capacity_exact'], npart, 4)
        assert a['capacity_exact'] <= a['capacity']
        assert a['fill'] == pytest.approx(npart / 16.0 / a['capacity'])
        assert 0.8 < a['fill'] <= 1.0
        assert by_id[s['par']]['name'] == 'paint'
    snap = REGISTRY.snapshot()
    assert snap['exchange.dropped']['value'] == 0
    assert snap['exchange.fill']['value'] == exch[-1]['attrs']['fill']
    assert snap['exchange.capacity']['value'] \
        == exch[-1]['attrs']['capacity']


def test_slab_r2c_names_its_all_to_all(tpu_branch):
    from nbodykit_tpu.parallel.dfft import dist_rfftn
    mesh = cpu_mesh(4)
    x = shard_leading(mesh, jnp.zeros((32, 32, 32), jnp.float32))
    text = jax.jit(lambda v: dist_rfftn(v, mesh)).lower(x).as_text(
        debug_info=True)
    assert 'nbk.fft.r2c' in text
    assert 'nbk.fft.a2a.' in text


# ---------------------------------------------------------------------------
# what stopped the cell's 64^3 oracle on four chips (PERF.md, PR 27)

def test_mxu_histogram_rounds_its_hi_part_where_no_pass_can_elide_it():
    # hi = w.astype(bf16).astype(f32) inside one fusion is excess
    # precision to the TPU compiler: with one chunk a device it kept
    # hi = w, lo = 0, and the sums were 8 bits wide (1e-3 against plain
    # numpy where the CPU reads 1e-5).  reduce_precision is an op of its
    # own that no simplifier removes; the CPU shows only that it is
    # there and that the sums are 16 bits wide
    from nbodykit_tpu.ops.histogram import hist2d_mxu
    rng = np.random.RandomState(9)
    m, na, nb = 33792, 36, 6        # 16 x 64 x 33: the oracle's block
    a = rng.randint(0, na, m).astype('i4')
    b = rng.randint(0, nb, m).astype('i4')
    w = (rng.exponential(size=m) * 5000).astype('f4')

    def hist(a, b, w):
        return hist2d_mxu(a, b, [w], na, nb, acc_dtype=jnp.float32)[0]
    assert 'reduce_precision' in jax.jit(hist).lower(a, b, w).as_text()
    want = np.bincount(a * nb + b, weights=w.astype('f8'),
                       minlength=na * nb).reshape(na, nb)
    got = np.asarray(jax.jit(hist)(a, b, w)).astype('f8')
    assert np.max(np.abs(got / want - 1)) < 2e-6


def test_eager_slab_r2c_is_one_launch_with_its_all_to_all_named_inside(
        tpu_branch, tmp_path):
    # the eager slab r2c is one cached program: on the profiler's host
    # line ``nbk.fft.r2c`` stands around one launch, and the
    # all_to_all is named where the benchmark reads a staged program,
    # in the launched program's own op names (an eager shard_map ran
    # one primitive a program with no name stack, and only the host
    # annotation ``nbk.fft.a2a.dev`` could name the all_to_all)
    import glob
    import os
    from jax.profiler import ProfileData
    from nbodykit_tpu.parallel.dfft import _slab_programs, dist_rfftn
    mesh = cpu_mesh(4)
    x = shard_leading(mesh, jnp.ones((16, 16, 16), jnp.float32))
    jax.block_until_ready(dist_rfftn(x, mesh))      # traced here
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        jax.block_until_ready(dist_rfftn(x, mesh))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), 'plugins', 'profile',
                                   '*', '*.xplane.pb'))
    host = ProfileData.from_file(path).find_plane_with_name('/host:CPU')
    marks, launches = {}, []
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith('nbk.'):
                marks.setdefault(ev.name, []).append(
                    (line.name, ev.start_ns, ev.end_ns))
            elif ev.name.endswith('Executable::Execute'):
                launches.append((line.name, ev.start_ns, ev.end_ns))
    assert set(marks) == {'nbk.fft.r2c'}
    (line, r0, r1), = marks['nbk.fft.r2c']
    assert [(ln, r0 <= a0 and a1 <= r1) for ln, a0, a1 in launches] \
        == [(line, True)]
    hits = _slab_programs.cache_info().hits
    program = _slab_programs(mesh, None, 'r2c', None, 'none', False, 0)[1]
    assert _slab_programs.cache_info().hits == hits + 1   # the call's own
    text = program._jitted.lower(x).as_text(debug_info=True)
    # the passes name their layer themselves: a nameless instruction
    # takes its users' scopes, the all_to_all's for the first two passes
    assert 'nbk.fft.r2c' in text and 'nbk.fft.a2a.dev' in text


# ---------------------------------------------------------------------------
# (d) the three shard_map sites as cached programs

SITES = ('exchange', 'paint.slab', 'fft.slab.r2c')
#: every cached program of a lab call: the sites', the 3-D power and
#: its binning
PROGRAMS = SITES + ('fftpower.p3d', 'fftpower.binning')


def program_counts():
    """The process's compile-cache requests and each site's
    ``(hits, misses)``."""
    snap = REGISTRY.snapshot()

    def value(name):
        return snap.get(name, {}).get('value', 0)
    out = {s: (value('compile.%s.hits' % s),
               value('compile.%s.misses' % s)) for s in PROGRAMS}
    out['requests'] = value('xla.cache.requests')
    return out


def counts_added(before):
    after = program_counts()
    out = {s: (after[s][0] - before[s][0], after[s][1] - before[s][1])
           for s in PROGRAMS}
    out['requests'] = after['requests'] - before['requests']
    return out


@pytest.fixture(scope='module')
def warm_calls():
    """What the second and third ``FFTPower(mode='2d')`` calls on four
    devices, each on a new seed of one N, add to the counters."""
    added = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(nbodykit_tpu.utils, 'is_mxu_backend', lambda: True)
        with jax.enable_x64(False):
            for seed in (11, 12, 13):
                pos = np.random.RandomState(seed).uniform(
                    0, BOX, (50000, 3)).astype('f4')
                before = program_counts()
                fftpower_2d(pos, 32, 4, cpu_mesh(4))
                added.append(counts_added(before))
    return added[1:]


def test_warm_four_device_call_asks_the_compile_cache_almost_nothing(
        warm_calls):
    # 549 a call while the three sites were eager shard_maps, one
    # primitive a program and each traced, lowered and looked up again;
    # 8 while the binning program was built in every call; with it kept
    # per geometry (``fftpower._binning_program``) nothing is left
    for added in warm_calls:
        assert added['requests'] == 0


@pytest.mark.parametrize('site', PROGRAMS)
def test_warm_four_device_call_reuses_the_sites_program(warm_calls, site):
    for added in warm_calls:
        assert added[site] == (1, 0)


def uniform_positions(npart, seed, box=32.0):
    return np.random.RandomState(seed).uniform(
        0, box, (npart, 3)).astype('f4')


def paint_then_r2c(pm, pos, **kw):
    from nbodykit_tpu.parallel.dfft import dist_rfftn
    before = program_counts()
    field = pm.paint(jnp.asarray(pos), 1.0, resampler='cic', **kw)
    spectrum = dist_rfftn(field, pm.comm)
    return (np.asarray(field), np.asarray(spectrum),
            counts_added(before))


#: what changes between two calls -> the programs it costs one miss
KEYED = {
    'capacity': ({}, {'capacity': 5000}, {'exchange', 'paint.slab'}),
    'paint_chunk_size': ({'paint_chunk_size': 4096}, {}, {'paint.slab'}),
    'integrity': ({'integrity': 'cheap'}, {}, {'fft.slab.r2c'}),
    'a2a.payload': ({'faults': 'a2a.payload@1:corrupt'}, {},
                    {'fft.slab.r2c'}),
    # flipped on the painted block, after the program
    'paint.accum': ({'faults': 'paint.accum@1:corrupt'}, {}, set()),
}


@pytest.mark.parametrize('what', sorted(KEYED))
def test_what_a_site_reads_is_in_its_programs_key(tpu_branch, what):
    from nbodykit_tpu.resilience.faults import reset_faults
    options, paint_kw, missed = KEYED[what]
    pm = ParticleMesh(32, 32.0, dtype='f4', comm=cpu_mesh(4))
    pos = uniform_positions(60000, 21)
    paint_then_r2c(pm, pos)
    field, spectrum, added = paint_then_r2c(pm, pos)
    assert all(added[s] == (1, 0) for s in SITES)
    with set_options(**options):
        reset_faults()
        changed = paint_then_r2c(pm, pos, **paint_kw)
        again = paint_then_r2c(pm, pos, **paint_kw)
    # one miss of each program that reads the change, none of the rest
    assert {s for s in SITES if changed[2][s] == (0, 1)} == missed
    assert all(changed[2][s] == (1, 0) for s in SITES if s not in missed)
    # compiled once: the same call again hits (a fault's rule fires
    # once, so ``again`` is the clean call)
    assert all(again[2][s] == (1, 0) for s in SITES)
    if 'faults' in options:
        assert not (np.array_equal(changed[0], field)
                    and np.array_equal(changed[1], spectrum))
        assert np.array_equal(again[0], field)
        assert np.array_equal(again[1], spectrum)
    # and the clean programs were never perturbed
    clean = paint_then_r2c(pm, pos)
    assert all(clean[2][s] == (1, 0) for s in SITES)
    assert np.array_equal(clean[0], field)
    assert np.array_equal(clean[1], spectrum)


def traced_paint(pm, pos, capacity, **kw):
    field, dropped = jax.jit(lambda p: pm.paint(
        p, 1.0, capacity=capacity, return_dropped=True, **kw))(pos)
    assert int(dropped) == 0
    return np.asarray(field)


@pytest.mark.parametrize('case', ['cic', 'tsc-shifted', 'unbalanced',
                                  'capacity-retry'])
def test_eager_paint_equals_the_traced_paint_to_the_bit(
        tpu_branch, clean_registry, case):
    npart = 40001                   # not a multiple of four
    kw = {'resampler': 'cic'}
    pos = uniform_positions(npart, 31)
    if case == 'tsc-shifted':
        kw = {'resampler': 'tsc', 'shift': 0.5}
    elif case != 'cic':
        pos = half_in_one_slab(npart, 32.0, 32)
    pos = jnp.asarray(pos)
    pm4 = ParticleMesh(32, 32.0, dtype='f4', comm=cpu_mesh(4))
    pm1 = ParticleMesh(32, 32.0, dtype='f4', comm=cpu_mesh(1))
    rung = pm4.exchange_capacity(pos, shift=kw.get('shift', 0.0))
    uniform = pm4.exchange_capacity(jnp.asarray(uniform_positions(npart, 31)))
    if case == 'capacity-retry':
        # too small by half: the eager call doubles it once
        eager = np.asarray(pm4.paint(pos, 1.0, capacity=rung // 2, **kw))
        assert REGISTRY.snapshot()['exchange.dropped']['value'] > 0
        capacity = 2 * (rung // 2)
    else:
        eager = np.asarray(pm4.paint(pos, 1.0, **kw))
        assert REGISTRY.snapshot()['exchange.dropped']['value'] == 0
        capacity = rung
    # half the particles in one slab climb the ladder
    assert (rung > uniform) == (case in ('unbalanced', 'capacity-retry'))
    assert np.array_equal(eager, traced_paint(pm4, pos, capacity, **kw))
    np.testing.assert_allclose(
        eager, np.asarray(pm1.paint(pos, 1.0, **kw)), rtol=1e-5,
        atol=1e-5)


def test_eager_exchange_equals_the_traced_exchange_to_the_bit(
        tpu_branch):
    mesh = cpu_mesh(4)
    npart = 40001
    pos = half_in_one_slab(npart, 32.0, 33)
    dest = jnp.asarray(np.floor(pos[:, 0]).astype('i4') // 8)
    tag = jnp.arange(npart, dtype=jnp.int32)
    pos = jnp.asarray(pos)
    cap = auto_capacity(dest, 4)
    eager = exchange_by_dest(dest, [pos, tag], mesh, cap)
    traced = jax.jit(lambda d, p, t: exchange_by_dest(
        d, [p, t], mesh, cap))(dest, pos, tag)
    counted = exchange_by_dest(dest, [pos, tag], mesh)
    for other in (traced, counted):
        for a, b in zip(jax.tree_util.tree_leaves(eager),
                        jax.tree_util.tree_leaves(other)):
            assert a.shape == b.shape and a.dtype == b.dtype
            assert np.array_equal(np.asarray(a), np.asarray(b))
    (_, got), valid, dropped = eager
    assert int(dropped) == 0
    assert np.array_equal(np.sort(np.asarray(got)[np.asarray(valid)]),
                          np.arange(npart))


@pytest.mark.parametrize('kind', ['r2c', 'c2r'])
def test_eager_slab_fft_equals_the_traced_one_to_the_bit(tpu_branch,
                                                         kind):
    from nbodykit_tpu.parallel.dfft import dist_irfftn, dist_rfftn
    mesh, one = cpu_mesh(4), cpu_mesh(1)
    x = jnp.asarray(np.random.RandomState(34).normal(
        size=(32, 32, 32)).astype('f4'))
    if kind == 'r2c':
        def fn(v, m):
            return dist_rfftn(v, m)
        arg = x
    else:
        def fn(v, m):
            return dist_irfftn(v, 32, m)
        arg = dist_rfftn(x, one)
    eager = np.asarray(fn(shard_leading(mesh, arg), mesh))
    traced = np.asarray(jax.jit(lambda v: fn(v, mesh))(
        shard_leading(mesh, arg)))
    assert np.array_equal(eager, traced)
    want = np.asarray(fn(arg, one))
    assert np.max(np.abs(eager - want)) < 1e-5 * np.max(np.abs(want))


def test_the_route_computes_in_the_dtype_of_the_mesh_it_is_called_for():
    # an f8 mesh made while x64 is off computes in f4, one made with
    # x64 on in f8: the routing program takes the compute dtype as an
    # argument, so the first does not decide for the second
    mesh, one = cpu_mesh(4), cpu_mesh(1)
    pos = np.random.RandomState(41).uniform(0, 32.0, (40001, 3))
    mass = np.random.RandomState(42).uniform(1, 2, 40001)
    with jax.enable_x64(False):
        narrow = ParticleMesh(32, 32.0, dtype='f8', comm=mesh)
        assert narrow.compute_dtype == np.dtype('f4')
        field = narrow.paint(jnp.asarray(pos, 'f4'),
                             jnp.asarray(mass, 'f4'))
        assert field.dtype == np.dtype('f4')
    wide = ParticleMesh(32, 32.0, dtype='f8', comm=mesh)
    assert wide.compute_dtype == np.dtype('f8')
    field = wide.paint(jnp.asarray(pos), jnp.asarray(mass))
    assert field.dtype == np.dtype('f8')
    want = ParticleMesh(32, 32.0, dtype='f8', comm=one).paint(
        jnp.asarray(pos), jnp.asarray(mass))
    np.testing.assert_allclose(np.asarray(field), np.asarray(want),
                               rtol=1e-12, atol=1e-12)


def test_eager_gradient_through_the_slab_paint_takes_the_raw_programs(
        tpu_branch, clean_registry):
    # under an eager jax.grad the weights are tracers while the
    # positions are not: every pick between a program's two forms looks
    # at all its operands, and the raw forms compose into the gradient
    pos = jnp.asarray(uniform_positions(20000, 43))
    mass = jnp.asarray(np.random.RandomState(44).uniform(
        1, 2, 20000).astype('f4'))
    weight = jnp.asarray(np.random.RandomState(45).normal(
        size=(32, 32, 32)).astype('f4'))

    def loss(pm):
        return lambda m: jnp.vdot(pm.paint(pos, m, resampler='cic'),
                                  weight)
    pm4 = ParticleMesh(32, 32.0, dtype='f4', comm=cpu_mesh(4))
    pm1 = ParticleMesh(32, 32.0, dtype='f4', comm=cpu_mesh(1))
    got = jax.grad(loss(pm4))(mass)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jax.grad(loss(pm1))(mass)),
        rtol=1e-4, atol=1e-5)
