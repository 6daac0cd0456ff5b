"""The halved-bytes accuracy gate: every compressed candidate vs the
full-width oracle (ISSUE 13).

``set_options(mesh_dtype='bf16')`` stores the painted mesh in bfloat16
(compute stays f32: weights, FFT, readout — pmesh.ParticleMesh splits
storage dtype from compute dtype, and ops/paint.py deposits with a
two-sum hi/lo split so the merge recovers f32-grade sums).
``set_options(a2a_compress='bf16'|'int16')`` keeps every FFT stage
f32 but halves the all_to_all wire payload (parallel/dfft._a2a):
bf16-on-wire/f32-out, or int16 quantized with per-shard scale factors
carried via all_gather.

The gate: each compressed posture's P(k) must match the full-width
pipeline (the oracle — f8 here since the suite enables x64, a strictly
tighter reference than the TPU-regime f32 it stands in for) on every
bin up to k_Nyquist/2, with IDENTICAL mode counts (compression must
never flip a bin assignment) and scale-relative error inside the
per-posture budget.  Measured errors (CPU, mesh64, 8 devices):
mesh-bf16 4.3e-3, a2a-bf16 1.9e-3, a2a-int16 9.0e-5; budgets sit
3-5x above.  Margins are committed to PRECISION.json
(diagnostics.regress.write_precision_margins), which the doctor and
the round record read back.
"""

import os

import numpy as np
import pytest

import nbodykit_tpu
from nbodykit_tpu.pmesh import ParticleMesh, memory_plan

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NMESH = 64
NPART = 20_000
BOX = 200.0
SEED = 42
# incommensurate edges (test_f32_accuracy.py convention): no lattice
# |i|^2 sits within a ulp of a bin edge, so both regimes must agree on
# every mode-to-bin assignment exactly
KMIN = 0.31 * (2 * np.pi / BOX)
DK = 2.6718 * (2 * np.pi / BOX)
K_NYQ = np.pi * NMESH / BOX

# per-posture scale-relative P(k) error budgets up to k_Nyquist/2,
# 3-5x above the measured margins in the module docstring
BUDGETS = {
    'mesh-bf16': 2e-2,
    'a2a-bf16': 1e-2,
    'a2a-int16': 5e-4,
}


def _pk(**opts):
    """P(k) of the fixed uniform catalog on the 8-device mesh under
    ``set_options(**opts)`` (empty -> the full-width oracle)."""
    from nbodykit_tpu.lab import ArrayCatalog, FFTPower
    from nbodykit_tpu.parallel.runtime import cpu_mesh, use_mesh
    rng = np.random.RandomState(SEED)
    pos = rng.uniform(0.0, BOX, size=(NPART, 3))
    with use_mesh(cpu_mesh()):
        with nbodykit_tpu.set_options(**(opts or {'mesh_dtype': 'f4'})):
            cat = ArrayCatalog({'Position': pos}, BoxSize=BOX)
            r = FFTPower(cat, mode='1d', Nmesh=NMESH, kmin=KMIN, dk=DK)
    return (np.asarray(r.power['k'], 'f8'),
            np.asarray(r.power['power'].real, 'f8'),
            np.asarray(r.power['modes'], 'f8'))


@pytest.fixture(scope='module')
def oracle():
    return _pk()


@pytest.mark.slow
@pytest.mark.parametrize('posture,opts', [
    ('mesh-bf16', {'mesh_dtype': 'bf16'}),
    ('a2a-bf16', {'a2a_compress': 'bf16'}),
    ('a2a-int16', {'a2a_compress': 'int16'}),
])
def test_compressed_pk_within_budget(oracle, posture, opts):
    k0, p0, m0 = oracle
    k, p, m = _pk(**opts)

    # compression must never flip a mode's bin: wire payload precision
    # does not enter bin assignment (exact-integer lattice binning)
    np.testing.assert_array_equal(m, m0)

    sel = (m0 > 0) & np.isfinite(p0) & (k0 <= 0.5 * K_NYQ)
    assert sel.sum() >= 5, 'too few bins below k_Nyquist/2'
    scale = np.abs(p0[sel]).mean()
    err = float((np.abs(p[sel] - p0[sel]) / scale).max())
    budget = BUDGETS[posture]
    assert err < budget, \
        '%s: max P(k) rel err %.3e exceeds budget %.0e' \
        % (posture, err, budget)

    # commit the measured margin (regress.precision_summary reads it
    # back for the doctor and the round record)
    from nbodykit_tpu.diagnostics.regress import write_precision_margins
    write_precision_margins(
        {posture: {'max_rel_err': err, 'budget': budget}}, root=ROOT)


@pytest.mark.slow
def test_stacked_compression_within_mesh_budget(oracle):
    """bf16 mesh + bf16 wire stacked stays inside the mesh budget (the
    dominant term; wire error does not compound multiplicatively)."""
    k0, p0, m0 = oracle
    k, p, m = _pk(mesh_dtype='bf16', a2a_compress='bf16')
    np.testing.assert_array_equal(m, m0)
    sel = (m0 > 0) & np.isfinite(p0) & (k0 <= 0.5 * K_NYQ)
    scale = np.abs(p0[sel]).mean()
    err = float((np.abs(p[sel] - p0[sel]) / scale).max())
    assert err < BUDGETS['mesh-bf16'], 'stacked err %.3e' % err


def test_bf16_readout_rewidens():
    """NBK702 contract: readout of a bf16-stored mesh computes and
    returns f32 — the narrow storage never leaks into interpolation."""
    import jax.numpy as jnp
    pm = ParticleMesh(16, 32.0, dtype='bf16')
    assert pm.dtype == np.dtype(jnp.bfloat16)
    assert pm.compute_dtype == np.dtype('f4')
    pos = np.random.RandomState(0).uniform(0, 32.0, (100, 3))
    field = pm.paint(pos)
    assert field.dtype == np.dtype(jnp.bfloat16)
    vals = pm.readout(field, pos)
    assert vals.dtype == np.dtype('f4')
    # r2c re-widens before the transform: complex64, not a narrow type
    assert pm.r2c(field).dtype == np.dtype('c8')


def test_bf16_paint_conserves_mass():
    """The two-sum compensated deposit keeps total mass within bf16
    storage rounding of the particle count."""
    pm = ParticleMesh(32, 64.0, dtype='bf16')
    pos = np.random.RandomState(1).uniform(0, 64.0, (5000, 3))
    total = float(np.sum(np.asarray(pm.paint(pos), dtype='f8')))
    assert abs(total - 5000.0) / 5000.0 < 5e-3


def test_memory_plan_prices_bf16_at_half():
    plan4 = memory_plan(256, 10**6, ndevices=8, dtype='f4')
    plan2 = memory_plan(256, 10**6, ndevices=8, dtype='bf16')
    assert plan2['mesh_dtype'] == 'bfloat16'
    assert plan2['mesh_itemsize'] == 2
    assert plan4['mesh_itemsize'] == 4
    # the real mesh halves exactly; complex/FFT work stays f32-priced
    assert plan2['real_field'] * 2 == plan4['real_field']
    assert plan2['complex_field'] == plan4['complex_field']
    assert plan2['peak_bytes'] < plan4['peak_bytes']


def test_serve_admission_prices_bf16():
    """A bf16 request admits where the identical f4 request is priced
    strictly higher — admission sees the halved mesh (NBK503)."""
    from nbodykit_tpu.serve.request import AnalysisRequest
    from nbodykit_tpu.serve.admission import _plan
    req4 = AnalysisRequest(nmesh=256, npart=10**6, dtype='f4',
                           paint_method='scatter')
    req2 = AnalysisRequest(nmesh=256, npart=10**6, dtype='bf16',
                           paint_method='scatter')
    p4 = _plan(req4, ndevices=8, hbm_bytes=16e9)
    p2 = _plan(req2, ndevices=8, hbm_bytes=16e9)
    assert p2['real_field'] * 2 == p4['real_field']
    assert p2['peak_bytes'] < p4['peak_bytes']


def test_request_rejects_unknown_dtype():
    from nbodykit_tpu.serve.request import AnalysisRequest
    with pytest.raises(ValueError):
        AnalysisRequest(dtype='f2')


def test_resolve_validates_postures():
    """The read sites take the postures as the options stand: full
    width by default, the halved-bytes formats when set."""
    from nbodykit_tpu.lab import UniformCatalog
    from nbodykit_tpu.parallel.dfft import _a2a_mode
    cat = UniformCatalog(nbar=1e-3, BoxSize=50.0, seed=1)
    assert _a2a_mode() == 'none'
    assert cat.to_mesh(Nmesh=8).pm.dtype == np.dtype('f4')
    with nbodykit_tpu.set_options(a2a_compress='int16',
                                  mesh_dtype='bf16'):
        assert _a2a_mode() == 'int16'
        assert cat.to_mesh(Nmesh=8).pm.dtype.name == 'bfloat16'


def test_precision_summary_attestation(tmp_path):
    """regress: no PRECISION.json, no posture; a written margin is
    read back and lands on the round record's posture line."""
    from nbodykit_tpu.diagnostics import regress
    root = str(tmp_path)
    assert regress.precision_summary(root) is None
    regress.write_precision_margins(
        {'a2a-bf16': {'max_rel_err': 1.9e-3, 'budget': 1e-2}},
        root=root)
    p = regress.precision_summary(root)
    assert p['margins']['a2a-bf16']['budget'] == 1e-2
    assert p['k_max'] == 'k_nyquist/2'
    # the render carries the posture line
    h = regress.build_history(root, write=False)
    assert 'precision:' in regress.render_regress(h)
