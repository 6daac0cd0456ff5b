"""The bispectrum subsystem (ISSUE 20): the FFT Scoccimarro estimator
and the direct pairblock estimator against brute-force numpy oracles,
cross-path agreement on the multi-device mesh, bit-identical replay and
save/load, the MXU pairblock kernel, memory_plan
pricing, and the serve plane's Bispectrum requests.

Oracle conventions (docs/BISPECTRUM.md): the FFT path closes triangles
mod Nmesh (the aliased closure of the mesh product), so its oracle
wraps ``q3 = -(q1+q2)`` back into the fftfreq range; the direct path
uses TRUE closure over the enumerated integer lattice.  The two agree
wherever no wrapped triangle can occur — ``2 (nbins+1) <= Nmesh/2``.
"""

import numpy as np
import jax.numpy as jnp
import pytest

import nbodykit_tpu
from nbodykit_tpu import _global_options
from nbodykit_tpu.algorithms import Bispectrum
from nbodykit_tpu.algorithms.bispectrum import (direct_bispectrum,
                                                fft_bispectrum,
                                                shell_modes,
                                                triangle_bins)
from nbodykit_tpu.lab import UniformCatalog
from nbodykit_tpu.ops.pairblock import lattice_kvecs, pairblock_sum
from nbodykit_tpu.parallel.runtime import cpu_mesh, use_mesh
from nbodykit_tpu.pmesh import ParticleMesh, memory_plan


@pytest.fixture(autouse=True)
def _clean_options():
    saved = _global_options.copy()
    yield
    _global_options.clear()
    _global_options.update(saved)


# ---------------------------------------------------------------------------
# enumeration helpers

def test_triangle_bins_canonical_and_closable():
    tris = triangle_bins(4)
    for (i, j, l) in tris:
        assert i <= j <= l
        assert (l + 1) < (i + 2) + (j + 2)
    # the equilateral diagonal always closes
    for b in range(4):
        assert (b, b, b) in tris


def test_shell_modes_half_sphere():
    q, shell = shell_modes(3)
    assert q.shape == (shell.size, 3)
    seen = {tuple(v) for v in q}
    for v in q:
        assert tuple(-v) not in seen      # exactly one of q / -q
    isq = (q ** 2).sum(axis=1)
    assert np.all(isq >= (shell + 1) ** 2)
    assert np.all(isq < (shell + 2) ** 2)


# ---------------------------------------------------------------------------
# the MXU pairblock kernel

def test_pairblock_matches_numpy_and_is_device_invariant():
    rng = np.random.RandomState(11)
    pos = rng.uniform(0, 100.0, (300, 3))
    w = rng.uniform(0.5, 1.5, 300)
    q, _ = shell_modes(2)
    kv = lattice_kvecs(q, 100.0)
    want = (w[None, :] * np.exp(-1j * (kv @ pos.T))).sum(axis=1)
    got1 = np.asarray(pairblock_sum(jnp.asarray(pos), jnp.asarray(w),
                                    kv, tile=64))
    np.testing.assert_allclose(got1, want, rtol=1e-10, atol=1e-10)
    got8 = np.asarray(pairblock_sum(jnp.asarray(pos), jnp.asarray(w),
                                    kv, tile=64, comm=cpu_mesh()))
    np.testing.assert_allclose(got8, want, rtol=1e-10, atol=1e-10)


# ---------------------------------------------------------------------------
# FFT estimator vs the all-triangles oracle (aliased mod-N closure)

def test_fft_bispectrum_matches_all_triangle_oracle():
    N, L, nbins = 16, 100.0, 4
    pm = ParticleMesh(Nmesh=N, BoxSize=L, dtype='f8')
    rng = np.random.RandomState(42)
    real = rng.standard_normal((N, N, N))
    B, ntri = fft_bispectrum(pm, pm.r2c(jnp.asarray(real)), nbins)

    # oracle: full c2c spectrum, every mod-N-closed mode triangle
    dk = np.fft.fftn(real).reshape(-1) / N ** 3
    fx = np.fft.fftfreq(N, 1.0 / N).astype(int)
    qx, qy, qz = np.meshgrid(fx, fx, fx, indexing='ij')
    q = np.stack([qx, qy, qz], -1).reshape(-1, 3)
    isq = (q ** 2).sum(1)
    sh = np.floor(np.sqrt(isq.astype('f8'))).astype(int) - 1
    pos_of = {tuple(v): i for i, v in enumerate(q)}
    idx = {b: np.flatnonzero((isq >= 1) & (sh == b))
           for b in range(nbins)}
    So = np.zeros((nbins,) * 3, complex)
    No = np.zeros((nbins,) * 3)
    for b1 in range(nbins):
        for b2 in range(nbins):
            q2s, d2 = q[idx[b2]], dk[idx[b2]]
            for i1 in idx[b1]:
                q3 = (-(q[i1] + q2s) + N // 2) % N - N // 2
                for i2 in range(len(q2s)):
                    t = pos_of[tuple(q3[i2])]
                    b3 = sh[t]
                    if 0 <= b3 < nbins and isq[t] >= 1:
                        So[b1, b2, b3] += dk[i1] * d2[i2] * dk[t]
                        No[b1, b2, b3] += 1
    V = L ** 3
    Bo = np.where(No > 0, V * V * So.real / np.where(No > 0, No, 1),
                  np.nan)
    assert np.array_equal(np.nan_to_num(ntri, nan=0.0), No)
    assert np.array_equal(np.isnan(B), No == 0)
    both = No > 0
    np.testing.assert_allclose(B[both], Bo[both], rtol=1e-6)


# ---------------------------------------------------------------------------
# direct estimator vs the true-closure oracle

def test_direct_bispectrum_matches_true_closure_oracle():
    rng = np.random.RandomState(7)
    Np, L, nbins = 400, 100.0, 3
    pos = rng.uniform(0, L, (Np, 3))
    w = rng.uniform(0.5, 1.5, Np)
    B, ntri = direct_bispectrum(jnp.asarray(pos), jnp.asarray(w), L,
                                nbins, tile=128)

    q, sh = shell_modes(nbins)
    q = np.concatenate([q, -q])
    sh = np.concatenate([sh, sh])
    kv = q * (2 * np.pi / L)
    d = (w[None, :] * np.exp(-1j * (kv @ pos.T))).sum(1) / w.sum()
    pos_of = {tuple(v): i for i, v in enumerate(q)}
    S = np.zeros((nbins,) * 3, complex)
    No = np.zeros((nbins,) * 3)
    for i1 in range(len(q)):
        for i2 in range(len(q)):
            t = pos_of.get(tuple(-(q[i1] + q[i2])))
            if t is not None:
                S[sh[i1], sh[i2], sh[t]] += d[i1] * d[i2] * d[t]
                No[sh[i1], sh[i2], sh[t]] += 1
    V = L ** 3
    Bo = np.where(No > 0, V * V * S.real / np.where(No > 0, No, 1),
                  np.nan)
    assert np.array_equal(np.nan_to_num(ntri, nan=0.0), No)
    assert np.array_equal(np.isnan(B), No == 0)
    both = No > 0
    np.testing.assert_allclose(B[both], Bo[both], rtol=1e-10)


# ---------------------------------------------------------------------------
# cross-path agreement on the 8-device mesh

def _signal_catalog(L=100.0, seed=42):
    """A uniform catalog with a strong imprinted non-Gaussian weight
    field (a squared sum of low-|q| cosines): the bispectrum signal
    dominates shot noise, so the two estimators must agree instead of
    both measuring near-cancelling noise."""
    cat = UniformCatalog(nbar=1e-2, BoxSize=L, seed=seed)
    pos = np.asarray(cat['Position'])
    rng = np.random.RandomState(3)
    g = np.zeros(len(pos))
    for m in [(1, 0, 0), (0, 1, 0), (0, 0, 1), (1, 1, 0), (0, 1, 1),
              (1, 0, 1), (2, 0, 0), (1, 1, 1)]:
        ph = rng.uniform(0, 2 * np.pi)
        g += 0.4 * np.cos(2 * np.pi * (pos @ np.array(m)) / L + ph)
    cat['Weight'] = (1.0 + 0.5 * g) ** 2
    return cat


def test_fft_vs_direct_agreement_multi_device(cpu8):
    """nbins=3 at Nmesh=32: 2 (nbins+1) = 8 <= 16 = Nmesh/2, so no
    aliased triangle exists and the mod-N and true closures coincide —
    the two estimators measure the SAME statistic and must agree to
    estimator-difference tolerance (window/resolution only)."""
    with use_mesh(cpu8):
        cat = _signal_catalog()
        bf = Bispectrum(cat, nbins=3, Nmesh=32, method='fft')
        bd = Bispectrum(cat, nbins=3, method='direct', tile=256)
    Bf, Bd = bf.B['B'], bd.B['B']
    assert bf.attrs['method'] == 'fft'
    assert bd.attrs['method'] == 'direct'
    # identical closed-triangle counts, bit for bit
    assert np.array_equal(np.nan_to_num(bf.B['ntri'], nan=-1.0),
                          np.nan_to_num(bd.B['ntri'], nan=-1.0))
    m = ~np.isnan(Bf)
    assert np.array_equal(m, ~np.isnan(Bd))
    scale = np.abs(Bd[m]).max()
    assert np.allclose(Bf[m], Bd[m], rtol=2e-2, atol=2e-2 * scale)


def test_bispectrum_deterministic_and_roundtrip(tmp_path):
    cat = _signal_catalog()
    a = Bispectrum(cat, nbins=3, Nmesh=16, method='fft')
    b = Bispectrum(cat, nbins=3, Nmesh=16, method='fft')
    assert np.array_equal(np.nan_to_num(a.B['B'], nan=1.25),
                          np.nan_to_num(b.B['B'], nan=1.25))
    path = str(tmp_path / 'bspec.json')
    a.save(path)
    c = Bispectrum.load(path)
    assert np.array_equal(np.nan_to_num(a.B['B'], nan=1.25),
                          np.nan_to_num(c.B['B'], nan=1.25))
    assert c.attrs['nbins'] == 3 and c.attrs['method'] == 'fft'


def test_bispectrum_validates_method_and_sources():
    cat = UniformCatalog(nbar=2e-3, BoxSize=100.0, seed=1)
    with pytest.raises(ValueError):
        Bispectrum(cat, nbins=0, Nmesh=16)
    for method in ('exact', 'auto'):
        with pytest.raises(ValueError):
            Bispectrum(cat, nbins=2, Nmesh=16, method=method)
    mesh = cat.to_mesh(Nmesh=16)
    with pytest.raises(ValueError):
        Bispectrum(mesh, nbins=2, method='direct')
    # the option's 'direct' on a mesh source runs the FFT path
    with nbodykit_tpu.set_options(bspec_method='direct'):
        r = Bispectrum(mesh, nbins=2)
    assert r.attrs['method'] == 'fft'


# ---------------------------------------------------------------------------
# memory_plan pricing

def test_memory_plan_bispectrum_fft_and_direct():
    fft = memory_plan(256, 10 ** 6, workload='bispectrum', nbins=4,
                      hbm_bytes=16e9)
    assert fft['workload'] == 'bispectrum'
    assert fft['bspec_method'] == 'fft'
    # the streaming contract: 3 shell fields, never nbins fields
    assert fft['shell_fields_bytes'] == pytest.approx(3 * 4 * 256 ** 3)
    assert fft['fits']
    big = memory_plan(2048, 10 ** 8, workload='bispectrum', nbins=8,
                      dtype='f8', hbm_bytes=16e9)
    assert not big['fits']

    d = memory_plan(256, 10 ** 6, workload='bispectrum', nbins=4,
                    bspec_method='direct', pairblock_tile=4096,
                    hbm_bytes=16e9)
    assert d['bspec_method'] == 'direct'
    assert d['pairblock_bytes'] == pytest.approx(4.0 * 4096 * 4096 * 4)
    assert d['fits']
    # the tile knob is the direct path's memory dial
    d2 = memory_plan(256, 10 ** 6, workload='bispectrum', nbins=4,
                     bspec_method='direct', pairblock_tile=256,
                     hbm_bytes=16e9)
    assert d2['peak_bytes'] < d['peak_bytes']


# ---------------------------------------------------------------------------
# the serve plane

def test_serve_bispectrum_admit_degrade_reject():
    from nbodykit_tpu.serve import AnalysisRequest, admit
    ok = admit(AnalysisRequest(algorithm='Bispectrum', nmesh=64,
                               npart=10000, nbins=4),
               ndevices=1, hbm_bytes=16e9)
    assert ok.status == 'admit'
    assert ok.plan['workload'] == 'bispectrum'
    # the paint phase dominates here (pos + unchunked scatter temps);
    # the scoped ladder's paint_chunk_size rung pulls it under budget
    mid = admit(AnalysisRequest(algorithm='Bispectrum', nmesh=64,
                                npart=10 ** 8, nbins=4,
                                paint_method='scatter'),
                ndevices=1, hbm_bytes=2.3e9)
    assert mid.status == 'degrade'
    assert mid.options.get('paint_chunk_size')
    bad = admit(AnalysisRequest(algorithm='Bispectrum', nmesh=1024,
                                npart=10 ** 7, nbins=8, dtype='f8'),
                ndevices=1, hbm_bytes=2e9)
    assert bad.status == 'reject'
    assert bad.reason['code'] == 'over_budget'
    # request-model validation: seeded only, Nyquist-bounded shells
    with pytest.raises(ValueError):
        AnalysisRequest(algorithm='Bispectrum', nmesh=16, nbins=9)
    with pytest.raises(ValueError):
        AnalysisRequest(algorithm='FFTPower', nbins=3)
    r = AnalysisRequest(algorithm='Bispectrum', nmesh=32, npart=1000)
    assert r.nbins == 4                    # the default shell count
    r3 = AnalysisRequest(algorithm='Bispectrum', nmesh=32, npart=1000,
                         nbins=3)
    assert r.program_key(1) != r3.program_key(1)


def test_serve_bispectrum_end_to_end_batched():
    from nbodykit_tpu.serve import (AnalysisRequest, AnalysisServer,
                                    BatchPolicy)
    with use_mesh(cpu_mesh(1)):
        srv = AnalysisServer(
            per_task=1, batch=BatchPolicy(max_batch=4, max_delay_s=1.0))
    with srv:
        tickets = [srv.submit(AnalysisRequest(
            algorithm='Bispectrum', nmesh=16, npart=5000, nbins=3,
            seed=s)) for s in (1, 2, 3)]
        batched = [srv.wait(t, timeout=240) for t in tickets]
        assert all(r.status == 'completed' for r in batched)
        assert max(r.batch_size for r in batched) > 1
        solo = srv.wait(srv.submit(AnalysisRequest(
            algorithm='Bispectrum', nmesh=16, npart=5000, nbins=3,
            seed=1)), timeout=120)
        # vmap-batched execution is bit-identical to solo
        assert np.array_equal(np.asarray(batched[0].y),
                              np.asarray(solo.y))
        assert np.array_equal(np.asarray(batched[0].nmodes),
                              np.asarray(solo.nmodes))
        y = np.asarray(batched[0].y, dtype='f8')
        assert np.isfinite(y).all()
        assert np.asarray(batched[0].nmodes).min() > 0
        summary = srv.summary()
    assert summary['lost'] == 0
    assert summary['completed'] == 4
