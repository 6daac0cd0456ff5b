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
    scopes in the slab r2c's HLO, ``exchange.dropped`` at 0."""

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


def test_eager_slab_r2c_puts_its_all_to_all_on_the_host_line(
        tpu_branch, tmp_path):
    # an eager shard_map launches its body one primitive a program and
    # those programs' op names carry no name stack (on the chip:
    # ``jit(<unknown>)/shard_map/all_to_all``), so the benchmark can
    # name the all_to_all only by the annotation it was launched under
    import glob
    import os
    from jax.profiler import ProfileData
    from nbodykit_tpu.parallel.dfft import dist_rfftn
    mesh = cpu_mesh(4)
    x = shard_leading(mesh, jnp.ones((16, 16, 16), jnp.float32))
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
    marks = {}
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith('nbk.'):
                marks.setdefault(ev.name, []).append(
                    (line.name, ev.start_ns, ev.end_ns))
    assert set(marks) == {'nbk.fft.r2c', 'nbk.fft.a2a.dev'}
    (line, r0, r1), = marks['nbk.fft.r2c']
    for other, a0, a1 in marks['nbk.fft.a2a.dev']:
        assert other == line and r0 <= a0 and a1 <= r1
