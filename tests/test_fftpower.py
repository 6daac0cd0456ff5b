"""FFTPower/FFTCorr/ProjectedFFTPower tests, mirroring the reference's
oracle styles (SURVEY.md §4): physical invariants (flat shot noise),
independent numpy implementations, device-count invariance, round-trips.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from nbodykit_tpu.lab import (UniformCatalog, LinearMesh, ArrayMesh,
                              FFTPower, FFTCorr, ProjectedFFTPower,
                              FieldMesh, ArrayCatalog)
from nbodykit_tpu.base.mesh import Field
from nbodykit_tpu.pmesh import ParticleMesh
from nbodykit_tpu.parallel.runtime import cpu_mesh


def numpy_power_oracle(field_np, BoxSize, kedges, Nmu, los=[0, 0, 1]):
    """Independent numpy implementation of the (k, mu) binned power of a
    real field (hermitian double-counting, under/overflow bins, last mu
    bin inclusive)."""
    N = field_np.shape[0]
    c = np.fft.rfftn(field_np) / field_np.size
    p3 = (np.abs(c) ** 2) * np.prod(BoxSize)
    p3[0, 0, 0] = 0.0

    kf = 2 * np.pi / np.asarray(BoxSize)
    kx = np.fft.fftfreq(N, 1.0 / N)[:, None, None] * kf[0]
    ky = np.fft.fftfreq(N, 1.0 / N)[None, :, None] * kf[1]
    kz = np.arange(N // 2 + 1)[None, None, :] * kf[2]
    kk = np.sqrt(kx ** 2 + ky ** 2 + kz ** 2)
    with np.errstate(invalid='ignore'):
        mu = np.where(kk == 0, 0.0,
                      (kx * los[0] + ky * los[1] + kz * los[2]) / kk)

    w = np.full(c.shape, 2.0)
    w[..., 0] = 1.0
    if N % 2 == 0:
        w[..., -1] = 1.0

    muedges = np.linspace(-1, 1, Nmu + 1)
    dig_k = np.digitize(kk.ravel() ** 2, np.asarray(kedges) ** 2)
    dig_mu = np.digitize(mu.ravel(), muedges)
    idx = dig_k * (Nmu + 2) + dig_mu
    nb = (len(kedges) + 1) * (Nmu + 2)
    Psum = np.bincount(idx, weights=(w * p3).flat, minlength=nb)
    Nsum = np.bincount(idx, weights=w.flat, minlength=nb)
    Psum = Psum.reshape(len(kedges) + 1, Nmu + 2)
    Nsum = Nsum.reshape(len(kedges) + 1, Nmu + 2)
    Psum[:, -2] += Psum[:, -1]
    Nsum[:, -2] += Nsum[:, -1]
    with np.errstate(invalid='ignore', divide='ignore'):
        pk = (Psum / Nsum)[1:-1, 1:-1]
        modes = Nsum[1:-1, 1:-1]
    return pk, modes


def test_fftpower_matches_numpy_oracle(comm):
    # arbitrary real field -> power must match the independent oracle
    rng = np.random.RandomState(8)
    N, L = 16, 50.0
    field_np = rng.standard_normal((N, N, N))
    mesh = ArrayMesh(field_np, BoxSize=L, comm=comm)
    r = FFTPower(mesh, mode='2d', Nmu=4)
    kedges = r.power.edges['k']
    want, modes_want = numpy_power_oracle(field_np, [L] * 3, kedges, 4)
    got = r.power['power'].real
    np.testing.assert_allclose(r.power['modes'], modes_want)
    valid = modes_want > 0
    np.testing.assert_allclose(got[valid], want[valid], rtol=1e-9)


def test_fftpower_shotnoise_flat(comm):
    # reference oracle (test_fftpower.py:12-44): compensated paint of a
    # uniform catalog has flat power = shot noise, reduced chi2 < 1
    from nbodykit_tpu.parallel.runtime import use_mesh
    with use_mesh(comm):
        cat = UniformCatalog(nbar=3e-3, BoxSize=100.0, seed=42)
        mesh = cat.to_mesh(Nmesh=32, resampler='cic', compensated=True)
        r = FFTPower(mesh, mode='1d')
    pk = r.power['power'].real
    sn = r.attrs['shotnoise']
    modes = r.power['modes']
    valid = (modes > 0) & (pk != 0)
    chi2 = np.sum(((pk[valid] - sn) / sn) ** 2 * modes[valid] / 2)
    assert chi2 / valid.sum() < 1.5


def test_fftpower_device_count_invariance():
    rng = np.random.RandomState(5)
    N, L = 16, 10.0
    field_np = rng.standard_normal((N, N, N))
    results = []
    for mesh in [cpu_mesh(1), cpu_mesh()]:
        r = FFTPower(ArrayMesh(field_np, BoxSize=L, comm=mesh),
                     mode='2d', Nmu=3, poles=[0, 2])
        results.append(r)
    np.testing.assert_allclose(results[0].power['power'].real,
                               results[1].power['power'].real,
                               rtol=1e-8, equal_nan=True)
    np.testing.assert_allclose(results[0].poles['power_2'].real,
                               results[1].poles['power_2'].real,
                               rtol=1e-8, equal_nan=True)


def test_fftpower_poles_consistency(comm):
    # P0 from poles == P(k) 1d (monopole == mu-average); reference
    # oracle test_fftpower.py:47-61
    rng = np.random.RandomState(3)
    field_np = rng.standard_normal((16, 16, 16))
    mesh = ArrayMesh(field_np, BoxSize=20.0, comm=comm)
    r = FFTPower(mesh, mode='1d', poles=[0])
    p1d = r.power['power'].real
    p0 = r.poles['power_0'].real
    valid = r.power['modes'] > 0
    np.testing.assert_allclose(p0[valid], p1d[valid], rtol=1e-8)


def test_fftpower_cross(comm):
    # cross power of a field with itself == auto power
    rng = np.random.RandomState(4)
    field_np = rng.standard_normal((8, 8, 8))
    m1 = ArrayMesh(field_np, BoxSize=10.0, comm=comm)
    m2 = ArrayMesh(field_np, BoxSize=10.0, comm=comm)
    auto = FFTPower(m1, mode='1d')
    cross = FFTPower(m1, mode='1d', second=m2)
    np.testing.assert_allclose(auto.power['power'].real,
                               cross.power['power'].real,
                               rtol=1e-9, equal_nan=True)


def test_fftpower_save_load(comm, tmp_path):
    rng = np.random.RandomState(6)
    field_np = rng.standard_normal((8, 8, 8))
    r = FFTPower(ArrayMesh(field_np, BoxSize=10.0, comm=comm),
                 mode='2d', Nmu=3, poles=[0, 2])
    fn = str(tmp_path / "power.json")
    r.save(fn)
    r2 = FFTPower.load(fn)
    np.testing.assert_allclose(r.power['power'].real,
                               r2.power['power'].real, equal_nan=True)
    np.testing.assert_allclose(r.poles['power_2'].real,
                               r2.poles['power_2'].real, equal_nan=True)
    assert r2.attrs['mode'] == '2d'


def test_linear_mesh_recovers_power(comm):
    # LinearMesh realization must recover the input P(k) within sample
    # variance; with unitary_amplitude the scatter shrinks drastically
    Plin = lambda k: 100.0 * np.ones_like(k)
    from nbodykit_tpu.parallel.runtime import use_mesh
    with use_mesh(comm):
        mesh = LinearMesh(Plin, BoxSize=64.0, Nmesh=32, seed=7,
                          unitary_amplitude=True, dtype='f8')
        r = FFTPower(mesh, mode='1d')
    pk = r.power['power'].real
    modes = r.power['modes']
    valid = (modes > 0) & ~np.isnan(pk) & (pk != 0)
    np.testing.assert_allclose(pk[valid], 100.0, rtol=1e-6)


def test_fftcorr_runs_and_integrates(comm):
    # xi(r) of a white field: all power in the r=0 bin; elsewhere ~0
    rng = np.random.RandomState(9)
    field_np = rng.standard_normal((16, 16, 16))
    mesh = ArrayMesh(field_np, BoxSize=16.0, comm=comm)
    r = FFTCorr(mesh, mode='1d')
    xi = r.corr['corr'].real
    # white noise: xi(r>0) ~ 0 vs xi(0) ~ var
    assert abs(xi[0]) > 10 * np.nanmax(np.abs(xi[1:]))


def test_fftcorr_device_invariance():
    rng = np.random.RandomState(10)
    field_np = rng.standard_normal((16, 16, 16))
    rs = [FFTCorr(ArrayMesh(field_np, BoxSize=16.0, comm=m), mode='1d')
          for m in [cpu_mesh(1), cpu_mesh()]]
    np.testing.assert_allclose(rs[0].corr['corr'], rs[1].corr['corr'],
                               rtol=1e-8, equal_nan=True)


def _projected_power_oracle(field_np, boxsize, axes, dk, kmin=0.0):
    """Independent numpy computation of the projected power."""
    nd = len(axes)
    dropped = tuple(i for i in range(3) if i not in axes)
    proj = np.transpose(field_np.sum(axis=dropped),
                        [sorted(axes).index(a) for a in axes])
    c = np.fft.rfftn(proj) / field_np.size
    pk = (c * c.conj())
    pk.flat[0] = 0.0
    dims = [field_np.shape[i] for i in axes]
    lens = [boxsize] * nd
    kk = np.zeros(pk.shape)
    for j in range(nd):
        freq = (np.arange(pk.shape[-1]) if j == nd - 1
                else np.fft.fftfreq(dims[j], 1.0 / dims[j]))
        sh = [1] * nd
        sh[j] = freq.size
        kk = kk + (freq * 2 * np.pi / lens[j]).reshape(sh) ** 2
    kmag = np.sqrt(kk)
    w = np.full(pk.shape, 2.0)
    w[..., 0] = 1.0
    if dims[-1] % 2 == 0:
        w[..., -1] = 1.0
    kedges = np.arange(kmin, np.pi * min(dims) / max(lens) + dk / 2, dk)
    dig = np.digitize(kmag.reshape(-1), kedges)
    nb = len(kedges) + 1
    nsum = np.bincount(dig, weights=w.reshape(-1), minlength=nb)
    psum = np.bincount(dig, weights=(w * pk.real).reshape(-1),
                       minlength=nb)
    with np.errstate(invalid='ignore', divide='ignore'):
        return (psum / nsum)[1:-1] * np.prod(lens)


def test_projected_fftpower(comm):
    rng = np.random.RandomState(11)
    field_np = rng.standard_normal((16, 16, 16))
    mesh = ArrayMesh(field_np, BoxSize=16.0, comm=comm)
    r = ProjectedFFTPower(mesh, axes=(0, 1))
    assert 'power' in r.power.variables
    oracle = _projected_power_oracle(field_np, 16.0, (0, 1),
                                     dk=2 * np.pi / 16.0)
    np.testing.assert_allclose(r.power['power'].real, oracle,
                               rtol=1e-8, equal_nan=True)


def test_projected_fftpower_1d_axis(comm):
    rng = np.random.RandomState(13)
    field_np = rng.standard_normal((16, 16, 16))
    mesh = ArrayMesh(field_np, BoxSize=16.0, comm=comm)
    r = ProjectedFFTPower(mesh, axes=(2,))
    oracle = _projected_power_oracle(field_np, 16.0, (2,),
                                     dk=2 * np.pi / 16.0)
    np.testing.assert_allclose(r.power['power'].real, oracle,
                               rtol=1e-8, equal_nan=True)


def test_project_to_basis_chunked_multidevice(monkeypatch):
    # forcing tiny chunks on an 8-device mesh must reproduce the
    # unchunked single-device result exactly (the chunked path now
    # engages inside shard_map, round-2 VERDICT weak #4)
    import nbodykit_tpu.algorithms.fftpower as fp
    rng = np.random.RandomState(20)
    field_np = rng.standard_normal((16, 16, 16))
    r_one = FFTPower(ArrayMesh(field_np, BoxSize=16.0, comm=cpu_mesh(1)),
                     mode='2d', Nmu=5, poles=[0, 2])
    monkeypatch.setattr(fp, '_BIN_CHUNK_ELEMENTS', 16 * 9)
    r_many = FFTPower(ArrayMesh(field_np, BoxSize=16.0, comm=cpu_mesh()),
                      mode='2d', Nmu=5, poles=[0, 2])
    np.testing.assert_allclose(r_one.power['power'].real,
                               r_many.power['power'].real,
                               rtol=1e-10, equal_nan=True)
    np.testing.assert_allclose(r_one.poles['power_0'].real,
                               r_many.poles['power_0'].real,
                               rtol=1e-10, equal_nan=True)


def test_project_to_basis_mxu_binning(monkeypatch):
    # the MXU one-hot-matmul histogram is the production binning on
    # TPU; force it on CPU and compare against the exact bincount path
    # (the switch is part of the binning program's key)
    import nbodykit_tpu.utils
    rng = np.random.RandomState(21)
    field_np = rng.standard_normal((16, 16, 16))
    r_exact = FFTPower(ArrayMesh(field_np, BoxSize=16.0), mode='2d',
                       Nmu=5, poles=[0, 2, 4])
    monkeypatch.setattr(nbodykit_tpu.utils, 'is_mxu_backend',
                        lambda: True)
    r_mxu = FFTPower(ArrayMesh(field_np, BoxSize=16.0), mode='2d',
                     Nmu=5, poles=[0, 2, 4])
    np.testing.assert_allclose(r_mxu.power['power'].real,
                               r_exact.power['power'].real,
                               rtol=2e-5, equal_nan=True)
    np.testing.assert_allclose(r_mxu.poles['power_2'].real,
                               r_exact.poles['power_2'].real,
                               atol=2e-5 * np.nanmax(
                                   np.abs(r_exact.poles['power_2'].real)),
                               equal_nan=True)
    np.testing.assert_allclose(np.asarray(r_mxu.power['modes'], 'f8'),
                               np.asarray(r_exact.power['modes'], 'f8'))


def test_fftpower_index_is_numpy_digitize_end_to_end(monkeypatch):
    # the benchmark's lab call (mode='2d', kmin=0.001, Nmu=10) at a
    # small mesh, against the same call with every bin index taken by
    # np.digitize on the host from the same 3-d power: the
    # compare-and-count index (ops.histogram.edge_count_index) gives
    # jnp.digitize's integers, so the results agree to the bit. x64 as
    # the suite runs and the TPU's no-x64 exact-integer path.
    import jax
    import nbodykit_tpu.ops.histogram as hist
    rng = np.random.RandomState(26)
    field_np = rng.standard_normal((32, 32, 32))

    def call():
        r = FFTPower(ArrayMesh(field_np, BoxSize=5000.0), mode='2d',
                     kmin=0.001, Nmu=10)
        return [np.asarray(r.power[c]) for c in
                ('power', 'modes', 'k', 'mu')]

    def host_digitize(v, edges):
        return jax.pure_callback(
            lambda v, e: np.digitize(v, e).astype('i4'),
            jax.ShapeDtypeStruct(v.shape, jnp.int32), v, edges)

    from nbodykit_tpu.algorithms.fftpower import _binning_program
    modes = {}
    for x64 in (True, False):
        with jax.enable_x64(x64):
            got = call()
            # the index is not in the binning program's key: built anew
            # under the patch, and not left for the next caller
            _binning_program.cache_clear()
            with monkeypatch.context() as m:
                m.setattr(hist, 'edge_count_index', host_digitize)
                want = call()
            _binning_program.cache_clear()
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            assert g.tobytes() == w.tobytes()
        modes[x64] = got[1]
    # and the mode counts against the independent numpy oracle: all of
    # them in f8; in f4 a lattice mode ON an interior mu edge (mu = 3/5)
    # may round to either side, so the k shells only
    kedges = np.arange(0.001, np.pi * 32 / 5000.0
                       + np.pi / 5000.0, 2 * np.pi / 5000.0)
    _, modes_want = numpy_power_oracle(field_np, [5000.0] * 3, kedges, 10)
    assert modes_want.sum() > 32 ** 3 / 2
    np.testing.assert_array_equal(modes[True], modes_want)
    np.testing.assert_array_equal(modes[False].sum(axis=1),
                                  modes_want.sum(axis=1))


def test_projected_fftpower_device_invariance():
    rng = np.random.RandomState(12)
    field_np = rng.standard_normal((16, 16, 16))
    rs = [ProjectedFFTPower(ArrayMesh(field_np, BoxSize=16.0, comm=m),
                            axes=(0, 1))
          for m in [cpu_mesh(1), cpu_mesh()]]
    np.testing.assert_allclose(rs[0].power['power'].real,
                               rs[1].power['power'].real,
                               rtol=1e-8, equal_nan=True)


def test_fftpower_anisotropic_box_and_mesh():
    """Anisotropic BoxSize triplet + anisotropic Nmesh: shot noise is
    V/N and the flat spectrum tracks it (reference supports 3-vector
    BoxSize/Nmesh throughout)."""
    rng = np.random.RandomState(0)
    box = np.array([100.0, 150.0, 80.0])
    pos = rng.uniform(0, 1, (20000, 3)) * box
    cat = ArrayCatalog({'Position': pos}, BoxSize=box)
    r = FFTPower(cat, mode='2d', Nmesh=[32, 48, 24], poles=[0, 2])
    V = float(np.prod(box))
    np.testing.assert_allclose(r.attrs['shotnoise'], V / 20000,
                               rtol=1e-6)
    p = np.asarray(r.power['power'].real)
    valid = np.asarray(r.power['modes']) > 0
    ratio = np.nanmean(p[valid] / r.attrs['shotnoise'])
    assert abs(ratio - 1) < 0.3


@pytest.mark.parametrize('ndev', [1, 4])
@pytest.mark.parametrize('cross', [False, True])
def test_cross_power_program_equals_its_ops_one_by_one(ndev, cross):
    # the 3-D power is one program (op by op a free-running host kept
    # three or four mesh-sized fields of it alive, by its lead): the
    # same bits and the same dtype as the ops in turn, compiled once
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from nbodykit_tpu.algorithms.fftpower import _cross_power
    from nbodykit_tpu.diagnostics.metrics import REGISTRY
    from nbodykit_tpu.parallel.runtime import AXIS
    rng = np.random.RandomState(5)
    shape = (16, 16, 9)
    a = (rng.normal(size=shape) + 1j * rng.normal(size=shape)
         ).astype('c8')
    b = (rng.normal(size=shape) + 1j * rng.normal(size=shape)
         ).astype('c8') if cross else a
    a, b = jnp.asarray(a), jnp.asarray(b)
    if ndev > 1:
        rows = NamedSharding(cpu_mesh(ndev), P(AXIS, None, None))
        a, b = jax.device_put(a, rows), jax.device_put(b, rows)
    volume = np.array([100.0, 50.0, 20.0]).prod()
    want = a * jnp.conj(b)
    want = want.at[0, 0, 0].set(0.0) * volume

    def counts():
        snap = REGISTRY.snapshot()
        return [snap.get('compile.fftpower.p3d.' + k, {}).get('value', 0)
                for k in ('hits', 'misses')]
    _cross_power(a, b, volume)
    before = counts()
    got = _cross_power(a, b, volume)
    assert counts() == [before[0] + 1, before[1]]
    assert got.dtype == want.dtype and got.sharding == a.sharding
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    assert got[0, 0, 0] == 0


# ---------------------------------------------------------------------------
# the binning as one cached program (``fftpower._binning_program``)

def _binning_counts():
    """``[hits, misses]`` of the builder's own cache and of the jit it
    returns, and how many backend compiles the process has timed."""
    from nbodykit_tpu.algorithms import fftpower as fp
    from nbodykit_tpu.diagnostics.metrics import REGISTRY
    snap = REGISTRY.snapshot()
    info = fp._binning_program.cache_info()
    return {'builder': [info.hits, info.misses],
            'jit': [snap.get('compile.fftpower.binning.' + k,
                             {}).get('value', 0)
                    for k in ('hits', 'misses')],
            'backend': snap.get('xla.compile.backend_s',
                                {}).get('count', 0)}


def _bits(result):
    (r2d, poles) = result
    return [np.asarray(a).tobytes()
            for a in tuple(r2d) + tuple(poles or ())]


def _binning_case(kind, comm=None, dtype='c8', nmesh=16, box=32.0):
    """A field of one kind on ``comm`` with edges of its own: what
    ``project_to_basis`` takes."""
    import jax
    nmesh = np.broadcast_to(nmesh, 3)
    pm = ParticleMesh(Nmesh=nmesh, BoxSize=box, dtype='f4', comm=comm)
    rng = np.random.RandomState(38)
    shape = {'hermitian': pm.shape_complex,
             'full': tuple(int(n) for n in nmesh[[1, 0, 2]]),
             'real': pm.shape_real}[kind]
    value = rng.standard_normal(shape)
    if np.dtype(dtype).kind == 'c':
        value = value + 1j * rng.standard_normal(shape)
    value = jnp.asarray(value.astype(dtype))
    if pm.comm is not None:
        value = jax.device_put(value, pm.sharding())
    if kind == 'real':
        xedges = np.arange(0, box / 2, box / nmesh[0])
    else:
        dk = 2 * np.pi / box
        xedges = np.arange(0, np.pi * nmesh[0] / box + dk / 2, dk)
    field = Field(value, pm, 'real' if kind == 'real' else 'complex')
    return field, [xedges, np.linspace(-1, 1, 6)]


def _launches_by_mark(tmp_path, call):
    """How many programs ``call`` launches under each ``nbk.`` host
    annotation (the innermost), on the profiler's host lines."""
    import glob
    import os
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = call()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), 'plugins', 'profile',
                                   '*', '*.xplane.pb'))
    host = ProfileData.from_file(path).find_plane_with_name('/host:CPU')
    counts = {}
    for line in host.lines:
        events = list(line.events)
        marks = [(ev.end_ns - ev.start_ns, ev.name, ev.start_ns,
                  ev.end_ns) for ev in events
                 if ev.name.startswith('nbk.')]
        for ev in events:
            if not ev.name.endswith('Executable::Execute'):
                continue
            inside = [m for m in marks
                      if m[2] <= ev.start_ns and ev.end_ns <= m[3]]
            name = min(inside)[1] if inside else None
            counts[name] = counts.get(name, 0) + 1
    return out, counts


@pytest.mark.parametrize('chunked', [False, True],
                         ids=['whole', 'chunked'])
@pytest.mark.parametrize('ndev', [1, 4])
@pytest.mark.parametrize('kind, poles', [
    ('hermitian', []), ('hermitian', [0, 2, 4]), ('full', [1, 2]),
    ('real', [0, 2])])
def test_binning_second_call_builds_nothing(tmp_path, monkeypatch, kind,
                                            poles, ndev, chunked):
    """``project_to_basis`` keeps its program per geometry: a second
    call with the same key is one hit of the builder and of its jit,
    traces, lowers and compiles nothing, launches the one program and
    no eager op before it, and gives the first call's bits."""
    import nbodykit_tpu
    from nbodykit_tpu.algorithms import fftpower as fp
    from nbodykit_tpu.diagnostics import read_trace
    if chunked:
        monkeypatch.setattr(fp, '_BIN_CHUNK_ELEMENTS', 2 * 16 * 9)
    field, edges = _binning_case(
        kind, comm=cpu_mesh(ndev) if ndev > 1 else None,
        dtype='f4' if kind == 'real' else 'c8')

    def call():
        return fp.project_to_basis(field, edges, poles=poles)
    zero = _binning_counts()        # of an empty cache: conftest.py
    first = call()
    cold = _binning_counts()
    assert cold['builder'] == [0, 1]
    assert cold['jit'] == [zero['jit'][0], zero['jit'][1] + 1]
    trace_dir = tmp_path / 'trace'
    with nbodykit_tpu.set_options(diagnostics=str(trace_dir)):
        second = call()
    warm = _binning_counts()
    assert warm['builder'] == [1, 1]
    assert warm['jit'] == [cold['jit'][0] + 1, cold['jit'][1]]
    assert warm['backend'] == cold['backend']
    records, bad = read_trace(str(trace_dir))
    assert bad == 0
    names = [r['name'] for r in records if r.get('t') == 'span']
    assert 'fftpower.binning' in names
    assert not [n for n in names if n.startswith('compile.')]
    third, launches = _launches_by_mark(tmp_path / 'profile', call)
    assert _binning_counts()['builder'] == [2, 1]
    assert launches == {'nbk.fftpower.binning': 1}
    assert _bits(first) == _bits(second) == _bits(third)


def _stale_cases():
    """part of the key -> (base setting, changed setting): each a dict
    of what ``_binning_case`` and ``project_to_basis`` take, and of
    the ambient switches."""
    return {
        'xedges': ({}, {'xedges': np.arange(0.0, 2.4, 0.3)}),
        'muedges': ({}, {'muedges': np.linspace(-1, 1, 4)}),
        'poles': ({}, {'poles': [0, 2]}),
        'los': ({}, {'los': [1, 0, 0]}),
        # the hermitian shape (16, 16, 9) of both: an odd last axis has
        # no Nyquist plane to count once
        'nmesh': ({}, {'nmesh': (16, 16, 17)}),
        'boxsize': ({}, {'box': 40.0}),
        # one complex array of (16, 16, 16): a c2c spectrum, or a
        # complex field of separations, over the same edges
        'kind': ({'kind': 'full', 'xedges': np.arange(0.0, 4.4, 0.4)},
                 {'kind': 'real', 'dtype': 'c8',
                  'xedges': np.arange(0.0, 4.4, 0.4)}),
        'dtype': ({'kind': 'real', 'dtype': 'f4'},
                  {'kind': 'real', 'dtype': 'f8'}),
        'chunk': ({}, {'chunk': 2 * 16 * 9}),
        'mxu': ({}, {'mxu': True}),
        'x64': ({}, {'x64': False}),
        'mesh': ({}, {'ndev': 4}),
    }


def _stale_call(setting):
    """``project_to_basis`` under one setting of ``_stale_cases``."""
    import jax
    import nbodykit_tpu.utils
    from nbodykit_tpu.algorithms import fftpower as fp
    s = dict(kind='hermitian', dtype='c8', nmesh=16, box=32.0, ndev=1,
             poles=[], los=[0, 0, 1], chunk=fp._BIN_CHUNK_ELEMENTS,
             mxu=False, x64=True, xedges=None, muedges=None)
    s.update(setting)
    field, edges = _binning_case(
        s['kind'], comm=cpu_mesh(s['ndev']) if s['ndev'] > 1 else None,
        dtype=s['dtype'], nmesh=s['nmesh'], box=s['box'])
    # the base's edges for every box, so that one part changes at a time
    edges = [np.arange(0.0, 2.4, 0.2) if s['xedges'] is None
             else s['xedges'],
             edges[1] if s['muedges'] is None else s['muedges']]
    if s['kind'] == 'real' and s['xedges'] is None:
        edges[0] = edges[0] * 10
    with pytest.MonkeyPatch.context() as m:
        m.setattr(fp, '_BIN_CHUNK_ELEMENTS', s['chunk'])
        m.setattr(nbodykit_tpu.utils, 'is_mxu_backend',
                  lambda: s['mxu'])
        with jax.enable_x64(s['x64']):
            return _bits(fp.project_to_basis(field, edges, los=s['los'],
                                             poles=s['poles']))


@pytest.mark.parametrize('part', sorted(_stale_cases()))
def test_binning_program_key_holds_every_part(part):
    """No stale program: with the base setting's program cached, a call
    that differs in one part of the key is a miss, and gives what the
    builder gives for that setting from an empty cache; the base
    setting's entry is still there afterwards, and still its own."""
    from nbodykit_tpu.algorithms import fftpower as fp
    base, changed = _stale_cases()[part]
    want_base = _stale_call(base)   # into an empty cache: conftest.py
    assert _binning_counts()['builder'] == [0, 1]
    before = _binning_counts()
    got = _stale_call(changed)
    after = _binning_counts()
    assert after['builder'] == [0, 2], part
    assert after['jit'] == [before['jit'][0], before['jit'][1] + 1]
    assert _stale_call(base) == want_base
    assert _binning_counts()['builder'] == [1, 2]
    fp._binning_program.cache_clear()
    want = _stale_call(changed)
    assert _binning_counts()['builder'] == [0, 1]
    assert got == want
    # and the change is one the answer shows (or, for the chunking,
    # one the program's loop shows: its sums agree to rounding)
    if part != 'chunk':
        assert got != want_base


def test_convpower_three_binnings_share_one_program():
    """The survey call bins three multipoles over one ``[kedges,
    muedges]``, shape and ``poles=[]``: one entry serves all three, and
    a second call (the next mock of a set) builds nothing."""
    from nbodykit_tpu.algorithms import fftpower as fp
    from nbodykit_tpu.lab import ConvolvedFFTPower, FKPCatalog
    data = UniformCatalog(nbar=2e-3, BoxSize=64., seed=21)
    ran = UniformCatalog(nbar=2e-2, BoxSize=64., seed=22)
    nbar = data.csize / 64. ** 3
    data['NZ'] = np.ones(data.csize) * nbar
    ran['NZ'] = np.ones(ran.csize) * nbar
    mesh = FKPCatalog(data, ran, BoxSize=70.0).to_mesh(
        Nmesh=16, resampler='cic', compensated=True)
    zero = _binning_counts()        # of an empty cache: conftest.py
    first = ConvolvedFFTPower(mesh, poles=[0, 2, 4], dk=0.05)
    cold = _binning_counts()
    assert cold['builder'] == [2, 1]
    assert cold['jit'] == [zero['jit'][0] + 2, zero['jit'][1] + 1]
    second = ConvolvedFFTPower(mesh, poles=[0, 2, 4], dk=0.05)
    warm = _binning_counts()
    assert warm['builder'] == [5, 1]
    assert warm['jit'] == [cold['jit'][0] + 3, cold['jit'][1]]
    assert warm['backend'] == cold['backend']
    for col in ('power_0', 'power_2', 'power_4', 'k', 'modes'):
        assert np.asarray(first.poles[col]).tobytes() \
            == np.asarray(second.poles[col]).tobytes()
