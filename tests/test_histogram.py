"""ops/histogram (the MXU (k,mu)-binning engine) and the bench.py
fused pipeline that uses it.

Oracles: exact numpy scatter-add histograms, and the production
FFTPower binning (itself verified against an independent numpy oracle
in test_fftpower.py).
"""

import sys
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from nbodykit_tpu.ops.histogram import (edge_count_index, hist2d_mxu,
                                        hist2d_bincount, hist2d_weighted,
                                        lattice_shell_edges, shell_sums)

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _ref_hist(a, b, ws, NA, NB):
    outs = []
    for w in ws:
        H = np.zeros((NA, NB))
        np.add.at(H, (np.asarray(a), np.asarray(b)), np.asarray(w, 'f8'))
        outs.append(H)
    return outs


@pytest.mark.parametrize("method", ["mxu", "bincount"])
def test_hist2d_matches_numpy(method):
    rng = np.random.RandomState(0)
    M, NA, NB = 40_000, 37, 12
    a = rng.randint(0, NA, M).astype('i4')
    b = rng.randint(0, NB, M).astype('i4')
    ws = [rng.uniform(0.5, 2.0, M), rng.standard_normal(M)]
    refs = _ref_hist(a, b, ws, NA, NB)
    got = hist2d_weighted(jnp.asarray(a), jnp.asarray(b),
                          [jnp.asarray(w) for w in ws], NA, NB,
                          method=method, chunk=8192)
    scale = max(np.abs(refs[1]).max(), 1.0)
    np.testing.assert_allclose(np.asarray(got[0]), refs[0], rtol=3e-6)
    np.testing.assert_allclose(np.asarray(got[1]) / scale,
                               refs[1] / scale, atol=3e-6)


def _hist2d_mxu_pr35(abin, bbin, weights, NA, NB, chunk,
                     acc_dtype=jnp.float64):
    """``hist2d_mxu`` as it stood before PR 36, the reference for the
    new product's rounding: flat arrays padded to whole chunks, a
    ``[chunk, 2 * nw * NB]`` block of one-hot columns concatenated and
    stored a chunk, ``A^T @ B`` contracted over the flat axis."""
    from nbodykit_tpu.ops.histogram import _bf16_grid
    M, nw = int(abin.shape[0]), len(weights)
    nch = max(1, -(-M // chunk))

    def pad(x, fill):
        return jnp.concatenate(
            [x, jnp.full((nch * chunk - M,), fill, x.dtype)])
    abin, bbin = pad(abin.astype(jnp.int32), 0), pad(
        bbin.astype(jnp.int32), 0)
    ws = [pad(w.astype(jnp.float32), 0.0) for w in weights]

    def body(i, acc):
        def cut(x):
            return jax.lax.dynamic_slice(x, (i * chunk,), (chunk,))
        A = jax.nn.one_hot(cut(abin), NA, dtype=jnp.bfloat16)
        Boh = jax.nn.one_hot(cut(bbin), NB, dtype=jnp.bfloat16)
        cols = []
        for w in ws:
            hi = _bf16_grid(cut(w))
            for part in (hi, cut(w) - hi):
                cols.append(Boh * part.astype(jnp.bfloat16)[:, None])
        H = jax.lax.dot_general(A, jnp.concatenate(cols, axis=1),
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return acc + H.astype(acc_dtype)
    H = jax.lax.fori_loop(0, nch, body,
                          jnp.zeros((NA, 2 * nw * NB), acc_dtype))
    return [H[:, 2 * i * NB:(2 * i + 1) * NB]
            + H[:, (2 * i + 1) * NB:(2 * i + 2) * NB] for i in range(nw)]


#: the (NA, NB, streams) classes of project_to_basis's callers, the
#: third stream the count: ``hermitian`` where it is 1.0 / 2.0 along
#: the last axis, else all ones (a real field, a c2c spectrum)
_CALLERS = {
    'lab': (257, 12, 5, True),          # FFTPower 2d, Nmu 10, 512^3
    'four_chips': (513, 12, 5, True),   # the same at 1024^3
    'survey': (128, 3, 5, True),        # ConvolvedFFTPower, one mu bin
    'poles': (66, 7, 9, True),          # Nmu 5, poles 0, 2, 4
    '1d': (34, 3, 5, True),             # FFTPower 1d
    'real': (34, 7, 5, False),          # FFTCorr: 3 + Nell real streams
}


@pytest.mark.parametrize('how', ['eager', 'jit', 'shard_map'])
@pytest.mark.parametrize('layout', ['3d', 'flat'])
@pytest.mark.parametrize('caller', list(_CALLERS))
def test_hist2d_mxu_against_f64_bincount(caller, layout, how,
                                         record_property):
    """The MXU product, forced (the CPU picks bincount), over its
    callers' classes of bins and streams: cells as a 3-d chunk with the
    count stream constant along two axes, or flat; eagerly, under jit,
    and a device's rows at a time inside a four-device ``shard_map``
    with ``psum``.  Every case ends in a chunk that does not divide its
    rows.  The count stream comes out as exact integers; every other
    stream's error against an f64 ``numpy.bincount`` is recorded and no
    worse than that of the kernel this one replaced (same two bf16
    parts a stream: what differs is the order of the f32 sums)."""
    from jax.sharding import PartitionSpec as P
    from nbodykit_tpu.parallel.runtime import AXIS, cpu_mesh
    NA, NB, nw, hermitian = _CALLERS[caller]
    shape = (20, 7, 11)
    # 3-d: 3 rows of 77 cells a chunk, 7 chunks for 20 rows (2 for a
    # device's 5); flat: 8 chunks of 193 for 1540 cells (2 for 385)
    chunk = 250 if layout == '3d' else 200
    rng = np.random.RandomState(NA + nw)
    a = rng.randint(0, NA, shape).astype('i4')
    b = rng.randint(0, NB, shape).astype('i4')
    count = (rng.randint(1, 3, (1, 1, shape[2])) if hermitian
             else np.ones((1, 1, 1))).astype('f4')
    # a spectrum's dynamic range: chi-squared under a power law in a
    full = [(rng.standard_normal(shape) ** 2 * 1e4 / (1.0 + a) ** 2
             * rng.choice([-1, 1], shape)).astype('f4')
            for _ in range(nw - 1)]
    flat_count = np.broadcast_to(count, shape)
    streams = full[:2] + [flat_count] + full[2:]
    refs = [np.bincount((a * NB + b).ravel(), np.asarray(w, 'f8').ravel(),
                        NA * NB).reshape(NA, NB) for w in streams]

    if layout == 'flat':
        a_in, b_in, full_in = a.ravel(), b.ravel(), [w.ravel() for w in full]
        count_in = jnp.asarray(flat_count.ravel(), jnp.bfloat16)
        spec = P(AXIS)
    else:
        a_in, b_in, full_in = a, b, full
        count_in = jnp.asarray(count, jnp.bfloat16)
        spec = P(AXIS, None, None)

    def hists(a, b, count, *full):
        return tuple(hist2d_weighted(
            a, b, list(full[:2]) + [count] + list(full[2:]), NA, NB,
            method='mxu', chunk=chunk))

    args = [jnp.asarray(x) for x in [a_in, b_in] + full_in]
    if how == 'shard_map':
        # a flat count is a device's own cells; the 3-d one is the same
        # (1, 1, nz) on every device, closed over as w_b is
        sharded_count = layout == 'flat'

        def local(a, b, *rest):
            c, full = (rest[0], rest[1:]) if sharded_count \
                else (count_in, rest)
            return tuple(jax.lax.psum(h, AXIS)
                         for h in hists(a, b, c, *full))
        args = args[:2] + ([count_in] if sharded_count else []) + args[2:]
        got = jax.jit(jax.shard_map(
            local, mesh=cpu_mesh(4), in_specs=(spec,) * len(args),
            out_specs=(P(),) * nw))(*args)
    else:
        fn = jax.jit(hists) if how == 'jit' else hists
        got = fn(args[0], args[1], count_in, *args[2:])
    old = _hist2d_mxu_pr35(jnp.asarray(a.ravel()), jnp.asarray(b.ravel()),
                           [jnp.asarray(w.ravel()) for w in streams],
                           NA, NB, chunk=200)

    assert len(got) == nw and got[0].shape == (NA, NB)
    np.testing.assert_array_equal(np.asarray(got[2]), refs[2])
    for i in (0, 1) + tuple(range(3, nw)):
        scale = np.abs(refs[i]).max()
        err = float(np.abs(np.asarray(got[i], 'f8') - refs[i]).max() / scale)
        was = float(np.abs(np.asarray(old[i], 'f8') - refs[i]).max() / scale)
        record_property('stream%d_max_err_vs_f64' % i, err)
        record_property('stream%d_pr35_max_err_vs_f64' % i, was)
        assert err < 1e-5           # two bf16 parts: 16 bits a weight
        assert err <= 1.5 * was + 1e-8, (i, err, was)


def test_hist2d_mxu_chunk_tail():
    """M not divisible by chunk: the cells the last chunk shares with
    the one before it must not contribute twice."""
    rng = np.random.RandomState(1)
    M, NA, NB = 10_001, 9, 5
    a = rng.randint(0, NA, M).astype('i4')
    b = rng.randint(0, NB, M).astype('i4')
    w = rng.uniform(1.0, 2.0, M)
    (ref,) = _ref_hist(a, b, [w], NA, NB)
    (got,) = hist2d_mxu(jnp.asarray(a), jnp.asarray(b),
                        [jnp.asarray(w)], NA, NB, chunk=4096)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=3e-6)
    assert float(np.asarray(got).sum()) == pytest.approx(w.sum(),
                                                         rel=1e-6)


def test_hist2d_under_jit():
    a = jnp.asarray([0, 1, 2, 1], jnp.int32)
    b = jnp.asarray([0, 0, 1, 1], jnp.int32)
    w = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    f = jax.jit(lambda a, b, w: hist2d_mxu(a, b, [w], 3, 2, chunk=2)[0])
    got = np.asarray(f(a, b, w))
    want = np.array([[1.0, 0.0], [2.0, 4.0], [0.0, 3.0]])
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize('NA, NB, parts, want', [
    (257, 12, 9, (386, 72)),            # desi_like_n512.lab
    (513, 12, 9, (770, 72)),            # desi_like_n1024.lab_x4
    (128, 3, 9, (48, 72)),              # boss_like_n512.convpower
    (258, 7, 17, (226, 136)),           # Nmu 5, poles 0, 2, 4
    (3, 2, 2, (1, 16))])
def test_mxu_split(NA, NB, parts, want):
    from nbodykit_tpu.ops.histogram import mxu_split
    rows, cols = mxu_split(NA, NB, parts)
    assert (rows, cols) == want
    assert rows * cols >= NA * NB * parts


def _ref_shell_sums(shell, value, weight, nbins):
    """f64 ``numpy.bincount`` of ``value * weight`` and of ``weight``."""
    shell = np.broadcast_to(shell, value.shape).ravel()
    w = np.broadcast_to(weight, value.shape).ravel().astype('f8')
    return (np.bincount(shell, value.ravel().astype('f8') * w, nbins),
            np.bincount(shell, w, nbins))


# (6, 5, 4): one chunk.  (101, 40, 33): two chunks of 51 rows for 101,
# the cells no multiple of the chunk.  (7, 300, 200): four chunks of 2
# rows for 7.  In the last two the closing chunk overlaps the one
# before it, and the shared row must count once
@pytest.mark.parametrize("under", ["jit", "vmap"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unit", "hermitian"])
@pytest.mark.parametrize("shape, nbins", [
    ((6, 5, 4), 7), ((101, 40, 33), 20), ((7, 300, 200), 16)])
def test_shell_sums_matches_numpy(shape, nbins, weighted, under):
    rng = np.random.RandomState(3)
    shell = rng.randint(0, nbins, shape).astype('i4')
    value = rng.standard_normal((2,) + shape).astype('f4')
    # 1 or 2 along the last axis, as pm.hermitian_weights is
    weight = rng.randint(1, 3, (1, 1, shape[2])).astype('f4') \
        if weighted else None

    def sums(v):
        return shell_sums(jnp.asarray(shell), v, nbins,
                          None if weight is None else jnp.asarray(weight))

    if under == "vmap":
        S, N = jax.jit(jax.vmap(sums))(jnp.asarray(value))
    else:
        S, N = (jnp.stack(x) for x in
                zip(*[jax.jit(sums)(jnp.asarray(v)) for v in value]))
    assert N.dtype == S.dtype == jnp.float32
    for b in range(2):
        S0, N0 = _ref_shell_sums(shell, value[b],
                                 1 if weight is None else weight, nbins)
        np.testing.assert_array_equal(np.asarray(N[b]), N0)
        np.testing.assert_allclose(np.asarray(S[b]), S0, rtol=1e-5,
                                   atol=1e-6)


def test_shell_sums_error_on_a_chi_squared_field(record_property):
    """The served spectrum's dynamic range: |delta_k|^2 is chi-squared
    with two degrees of freedom, under a power law in the shell.  The
    three bf16 parts give the MXU the f32 product bit for bit, so the
    sum is no rounder than an f32 scatter-add's (on this field, on the
    CPU: 9.8e-8 for the scatter-add's 1.05e-7; at 512^3 on the chip
    1.0e-7 for 8.5e-7, and 4.9e-6 with two parts, on the first
    shell)."""
    from nbodykit_tpu.ops.histogram import lattice_shell_index
    n, nbins = 64, 32
    rng = np.random.RandomState(11)
    i = np.fft.fftfreq(n, 1.0 / n).astype('i4')
    iz = np.arange(n // 2 + 1, dtype='i4')
    shell = np.asarray(lattice_shell_index(jnp.asarray(
        i[:, None, None] ** 2 + i[None, :, None] ** 2
        + iz[None, None, :] ** 2), nbins))
    value = ((rng.standard_normal((2,) + shell.shape) ** 2).sum(axis=0)
             * 1e4 / (1.0 + shell) ** 2).astype('f4')
    weight = np.where((iz == 0) | (iz == n // 2), 1, 2).astype('f4')
    S, N = jax.jit(shell_sums, static_argnums=2)(
        jnp.asarray(shell), jnp.asarray(value), nbins,
        jnp.asarray(weight))
    S0, N0 = _ref_shell_sums(shell, value, weight, nbins)
    np.testing.assert_array_equal(np.asarray(N), N0)
    err = float(np.max(np.abs(np.asarray(S, 'f8') - S0) / S0))
    record_property("max_rel_err_vs_f64", err)
    assert err < 5e-7


@pytest.mark.parametrize("weight, cols", [(None, 2 ** 22 + 1),
                                          (2, 2 ** 21 + 1)])
def test_shell_sums_counts_past_the_f32_stall(weight, cols):
    """More than 2^25 of weight in ONE shell (2^25 + 8 unit weights;
    2^24 + 8 weights of 2), the catch-all last shell of a 512^3 served
    spectrum in miniature: a single f32 accumulator stops at 2^24 (at
    2^25 under weight 2; shown here, so the test is known to be large
    enough to catch a relapse); the per-chunk partials count every
    cell."""
    nbins, rows = 4, 8
    n = rows * cols * (weight or 1)
    value = jnp.ones((rows, 1, cols), jnp.float32)
    shell = jnp.full((1, 1, 1), nbins - 1, jnp.int32)
    S, N = jax.jit(shell_sums, static_argnums=2)(
        shell, value, nbins,
        None if weight is None else jnp.full((1, 1, 1), weight,
                                             jnp.float32))
    assert np.asarray(N).tolist() == [0, 0, 0, n]
    assert float(S[-1]) == pytest.approx(n, rel=1e-6)
    naive = jnp.zeros(1, jnp.float32).at[
        jnp.zeros(rows * cols, jnp.int32)].add(
            jnp.full(rows * cols, weight or 1, jnp.float32))
    assert float(naive[0]) < n


def test_bench_pipeline_matches_fftpower(request):
    """bench.py's fused paint->fft->bin program must agree with the
    production FFTPower(mode='2d') on the in-range bins."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'bench_mod', os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), 'bench.py'))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    import nbodykit_tpu
    from nbodykit_tpu.pmesh import ParticleMesh
    from nbodykit_tpu.lab import FFTPower, ArrayCatalog

    Nmesh, Npart, L = 64, 20_000, 1000.0
    rng = np.random.RandomState(5)
    pos = rng.uniform(0, L, (Npart, 3)).astype('f4')

    # both sides with the scatter paint, and the option put back: the
    # tests that follow in this process read the default
    opts = nbodykit_tpu.set_options(paint_method='scatter')
    request.addfinalizer(lambda: opts.__exit__())
    pm = ParticleMesh(Nmesh=Nmesh, BoxSize=L, dtype='f4')
    fused, _phases = bench._bench_fftpower_fn(pm, slab_chunks=8)
    fn = jax.jit(fused)
    Psum, Nsum = (np.asarray(x, 'f8') for x in fn(jnp.asarray(pos)))
    with np.errstate(invalid='ignore'):
        Pmu = Psum / Nsum

    # 1. mode counts must EXACTLY match the integer-lattice oracle
    # (the bench bins on integer norms: isq vs m^2, 25*iz^2 vs m^2*isq)
    ix = np.fft.fftfreq(Nmesh, d=1.0 / Nmesh).astype('i8')
    IX, IY, IZ = np.meshgrid(ix, ix, np.arange(Nmesh // 2 + 1,
                                               dtype='i8'),
                             indexing='ij')
    ISQ = IX ** 2 + IY ** 2 + IZ ** 2
    w = np.where((IZ > 0) & (IZ < Nmesh // 2), 2.0, 1.0)
    Nx = Nmesh // 2
    dig_k = np.searchsorted(np.arange(Nx + 1) ** 2, ISQ.ravel(),
                            side='right')
    dig_mu = sum((25 * IZ ** 2 >= (m * m) * ISQ).astype('i8')
                 for m in range(1, 6))
    dig_mu = (np.where(ISQ == 0, 0, dig_mu) + 6).ravel()
    NsumO = np.zeros((Nx + 2, 12))
    np.add.at(NsumO, (dig_k, dig_mu), w.ravel())
    np.testing.assert_array_equal(Nsum, NsumO)

    # 2. P values must match the production FFTPower on bins whose
    # counts agree (production digitizes float coordinates, so modes on
    # Pythagorean lattice edges may sit in the neighboring bin there)
    cat = ArrayCatalog({'Position': pos}, BoxSize=L, comm=None)
    mesh = cat.to_mesh(Nmesh=Nmesh, resampler='cic', compensated=True,
                       dtype='f4')
    r = FFTPower(mesh, mode='2d', dk=2 * np.pi / L, kmin=0.0, Nmu=10,
                 los=[0, 0, 1])
    Pref = np.asarray(r.power['power'].real)
    Nref = np.asarray(r.power['modes'], dtype='f8')

    # fold the internal mu==1 bin like the production path does
    PmuF = Psum.copy()
    NsumF = Nsum.copy()
    PmuF[:, -2] += PmuF[:, -1]
    NsumF[:, -2] += NsumF[:, -1]
    with np.errstate(invalid='ignore'):
        PmuF = PmuF / NsumF
    got = PmuF[1:-1, 1:-1][:Pref.shape[0], :]
    gotN = NsumF[1:-1, 1:-1][:Pref.shape[0], :]
    want = Pref[:got.shape[0]]
    wantN = Nref[:got.shape[0]]
    m = np.isfinite(got) & np.isfinite(want)
    # equal counts can still hide a swap of boundary modes with an
    # adjacent bin (one in, one out) — require the neighbors to agree
    # as well before comparing values
    eq = (gotN == wantN)
    same = m & eq
    for ax, sh in ((0, 1), (0, -1), (1, 1), (1, -1)):
        pad = np.ones_like(eq)
        sl_to = [slice(None)] * 2
        sl_from = [slice(None)] * 2
        if sh > 0:
            sl_to[ax] = slice(1, None); sl_from[ax] = slice(None, -1)
        else:
            sl_to[ax] = slice(None, -1); sl_from[ax] = slice(1, None)
        pad[tuple(sl_to)] = eq[tuple(sl_from)]
        same &= pad
    assert same.sum() > 25
    np.testing.assert_allclose(got[same], want[same], rtol=2e-4)


def test_project_to_basis_chunked_matches_unchunked(monkeypatch):
    """The slab-chunked binning reduction (active at Nmesh >= 1024 on
    one device) must agree exactly with the whole-array path — for both
    the transposed hermitian complex layout (leading axis = ky) and
    real fields (leading axis = rx)."""
    from nbodykit_tpu.algorithms import fftpower as fp
    from nbodykit_tpu.pmesh import ParticleMesh
    from nbodykit_tpu.base.mesh import Field

    N, L = 32, 100.0
    pm = ParticleMesh(Nmesh=N, BoxSize=L, dtype='f8')
    rng = np.random.RandomState(7)
    field = jnp.asarray(rng.standard_normal((N, N, N)))
    cplx = pm.r2c(field)
    kedges = np.arange(0, np.pi * N / L + np.pi / L, 2 * np.pi / L)
    muedges = np.linspace(-1, 1, 6)

    for kind, val in (('complex', cplx), ('real', field)):
        y3d = Field(val, pm, kind=kind)
        ref2d, refp = fp.project_to_basis(y3d, [kedges, muedges],
                                          poles=[0, 2])
        monkeypatch.setattr(fp, '_BIN_CHUNK_ELEMENTS', 2 * N * N)
        got2d, gotp = fp.project_to_basis(y3d, [kedges, muedges],
                                          poles=[0, 2])
        monkeypatch.undo()
        for a, b in zip(ref2d, got2d):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-12, equal_nan=True)
        np.testing.assert_allclose(np.asarray(refp[1]),
                                   np.asarray(gotp[1]), rtol=1e-12,
                                   equal_nan=True)


def _index_case(kind):
    """Edges and values of one ``edge_count_index`` case: every edge,
    its two neighbours, below the first, above the last."""
    if kind == 'i4':
        # the lab path's exact-int side: int32 |i|^2 against the
        # integer thresholds of FFTPower's own k edges at Nmesh 64
        unit = 2 * np.pi / 1000.0
        edges = lattice_shell_edges(
            np.arange(0.001, np.pi * 64 / 1000.0 + unit / 2, unit), unit)
        v = np.concatenate([edges - 1, edges, edges + 1,
                            [0, 3 * 32 ** 2]]).astype('i4')
        return edges, v
    # the float side: mu against linspace(-1, 1, Nmu + 1), with
    # mu = +-1 and both zeros against the edge at 0.0
    edges = np.linspace(-1, 1, 11).astype(kind)
    edges[5] = 0.0
    below = np.nextafter(edges, -np.inf, dtype=kind)
    above = np.nextafter(edges, np.inf, dtype=kind)
    # next to 0.0 the neighbours are subnormal, which XLA flushes to
    # zero on the CPU: the smallest normal numbers there
    below[5], above[5] = -np.finfo(kind).tiny, np.finfo(kind).tiny
    v = np.concatenate([below, edges, above,
                        np.array([-0.0, 0.0, -1.0, 1.0, -7.0, 7.0, 0.3],
                                 dtype=kind)])
    return edges, v


@pytest.mark.parametrize('how', ['eager', 'jit', 'fori_loop'])
@pytest.mark.parametrize('kind', ['i4', 'f4', 'f8'])
def test_edge_count_index_is_numpy_digitize(kind, how):
    edges, v = _index_case(kind)
    want = np.digitize(v, edges)
    assert want.min() == 0 and want.max() == len(edges)
    ej, vj = jnp.asarray(edges), jnp.asarray(v)
    assert vj.dtype == v.dtype
    if how == 'eager':
        got = edge_count_index(vj, ej)
    elif how == 'jit':
        got = jax.jit(edge_count_index)(vj, ej)
    else:
        # as chunk_hists calls it: on a slice taken inside a loop body,
        # the edges closed over
        half = len(v) // 2

        def body(i, out):
            idx = edge_count_index(
                jax.lax.dynamic_slice_in_dim(vj, i * half, half), ej)
            return jax.lax.dynamic_update_slice_in_dim(
                out, idx, i * half, 0)
        got = jax.lax.fori_loop(
            0, 2, body, jnp.zeros(2 * half, jnp.int32))
        want = want[:2 * half]
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)
    # any shape in, the same shape out
    got3 = edge_count_index(vj[:8].reshape(2, 2, 2), ej)
    np.testing.assert_array_equal(np.asarray(got3).ravel(), want[:8])


@pytest.mark.parametrize('which', ['x', 'mu'])
@pytest.mark.parametrize('fault', ['descending', 'repeated'])
def test_project_to_basis_refuses_edges_not_ascending(fault, which):
    from nbodykit_tpu.algorithms.fftpower import project_to_basis
    from nbodykit_tpu.pmesh import ParticleMesh
    from nbodykit_tpu.base.mesh import Field
    pm = ParticleMesh(Nmesh=8, BoxSize=8.0, dtype='f8')
    y3d = Field(pm.r2c(jnp.ones((8, 8, 8))), pm, kind='complex')
    edges = {'x': np.arange(0.0, 4.0, 0.5), 'mu': np.linspace(-1, 1, 6)}
    if fault == 'descending':
        edges[which] = edges[which][::-1]
    else:
        edges[which] = np.insert(edges[which], 2, edges[which][2])
    with pytest.raises(ValueError, match=which + ' edges'):
        project_to_basis(y3d, [edges['x'], edges['mu']])
