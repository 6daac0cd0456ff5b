"""Tests for the end-to-end data-integrity layer
(nbodykit_tpu/resilience/integrity.py, docs/INTEGRITY.md).

The detection matrix is the core contract: with ``integrity='cheap'``
every clean program on the 8-device mesh reports ZERO violations
(including every registered paint candidate and both FFT
decompositions under every wire format), and every injected
``corrupt`` fault is caught by its OWNING guard — the corruption flows
through the real guarded surface, so the detector is what gets tested,
not the injector.  Tier 2 is covered end to end: Supervisor
retry-once-with-strike, two-strike quarantine into the sealed fleet
manifest, adoption + own-rank refusal on reload.  Tier 1 (shadow
verification) is covered in the serve tests below.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nbodykit_tpu
from nbodykit_tpu import _global_options, diagnostics
from nbodykit_tpu.diagnostics import REGISTRY
from nbodykit_tpu.parallel import dfft
from nbodykit_tpu.parallel.runtime import (cpu_mesh, pencil_mesh,
                                           use_mesh)
from nbodykit_tpu.pmesh import ParticleMesh
from nbodykit_tpu.resilience import (IntegrityError, RetryPolicy,
                                     Supervisor, checks_enabled,
                                     integrity_mode, reset_faults,
                                     reset_integrity, reset_suspects,
                                     shadow_margin, suspect_tracker,
                                     violation_counts)
from nbodykit_tpu.resilience.integrity import (check_a2a, check_close,
                                               check_mass,
                                               corrupt_host,
                                               flip_bits_value,
                                               violation)
from conftest import PAINT_CANDIDATES as CANDS


@pytest.fixture(autouse=True)
def _clean_state():
    """Options, fault counts, the violation ledger and the suspect
    tracker are process-wide; every test sees (and leaves) a pristine
    copy."""
    saved = _global_options.copy()
    REGISTRY.reset()
    reset_faults()
    reset_integrity()
    reset_suspects()
    yield
    REGISTRY.reset()
    reset_faults()
    reset_integrity()
    reset_suspects()
    diagnostics.configure(None)
    _global_options.clear()
    _global_options.update(saved)


def _pos(n=2000, box=64.0, seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(0.0, box, (n, 3)), jnp.float32)


def _field(nmesh=32, seed=5):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((nmesh,) * 3), jnp.float32)


# ---------------------------------------------------------------------------
# the corruption primitive: catastrophic by construction

def test_flip_bits_catastrophic_for_any_finite_input():
    """The stuck-at-one exponent fault must land ANY finite input at a
    magnitude no rounding budget can absorb (or at inf/NaN, which the
    nonfinite tripwire owns) — detection never depends on the
    corrupted element's value."""
    for v in (0.0, -0.0, 1e-30, 1.0, -3.5, 1e20, -1e38):
        for nbits in (1, 2, 4, 8):
            got = float(flip_bits_value(v, nbits))
            assert not math.isfinite(got) or abs(got) >= 2.0 ** 64, \
                (v, nbits, got)


def test_corrupt_host_flips_exactly_one_element():
    arr = np.linspace(0.0, 1.0, 16, dtype=np.float32)
    out = corrupt_host(arr, 1)
    assert out.dtype == np.float32 and out.shape == arr.shape
    assert not math.isfinite(out[0]) or abs(out[0]) >= 2.0 ** 64
    np.testing.assert_array_equal(out[1:], arr[1:])
    # the input is untouched (a copy, not an in-place flip)
    assert arr[0] == 0.0


# ---------------------------------------------------------------------------
# the mode knob and the comparators

def test_integrity_option_resolution():
    assert integrity_mode() == 'off' and not checks_enabled()
    with nbodykit_tpu.set_options(integrity='cheap'):
        assert integrity_mode() == 'cheap' and checks_enabled()
    with nbodykit_tpu.set_options(integrity='off'):
        assert not checks_enabled()
    with nbodykit_tpu.set_options(integrity='bogus'):
        with pytest.raises(ValueError):
            integrity_mode()


def test_check_close_budget_and_tripwires():
    # inside budget: returns the delta, no ledger entry
    assert check_close('t.site', 1.0 + 1e-9, 1.0, 1e-6) <= 2e-9
    assert violation_counts()['violations'] == 0
    with pytest.raises(IntegrityError) as ei:
        check_close('t.site', 2.0, 1.0, 1e-6)
    assert ei.value.site == 't.site' and ei.value.delta == 1.0
    with pytest.raises(IntegrityError) as ei:
        check_close('t.site', float('nan'), 1.0, 1e-6)
    assert ei.value.site == 't.site.nonfinite'
    vc = violation_counts()
    assert vc['violations'] == 2
    assert vc['by_site'] == {'t.site': 1, 't.site.nonfinite': 1}


def test_check_mass_and_a2a_comparators():
    check_mass('paint.mass', 1000.0 + 1e-4, 1000.0, 1000.0,
               10 ** 6, 'f4')
    with pytest.raises(IntegrityError):
        check_mass('paint.mass', 1100.0, 1000.0, 1000.0, 10 ** 6, 'f4')
    check_a2a('a2a.t', 5.0, 5.0 + 1e-9, 1e-6)
    with pytest.raises(IntegrityError):
        check_a2a('a2a.t', 5.0, 6.0, 1e-6)
    with pytest.raises(IntegrityError) as ei:
        check_a2a('a2a.t', float('inf'), 6.0, 1e-6)
    assert ei.value.site == 'a2a.t.nonfinite'


def test_shadow_margin_from_options():
    assert shadow_margin({}) == 0.0
    assert shadow_margin({'a2a_compress': 'bf16'}) > 0.0
    assert shadow_margin({'a2a_compress': 'int16',
                          'mesh_dtype': 'bf16'}) > \
        shadow_margin({'a2a_compress': 'int16'})


# ---------------------------------------------------------------------------
# zero false positives: clean programs under integrity='cheap'


@pytest.mark.parametrize('name', sorted(CANDS))
def test_paint_candidates_clean_under_cheap(name, cpu8):
    """Every registered paint candidate, eager on the 8-device mesh
    with the guard armed: zero violations (the mass budget absorbs
    legitimate tree-reduction and bf16 storage rounding)."""
    pm = ParticleMesh(Nmesh=32, BoxSize=64.0, dtype='f4', comm=cpu8)
    opts = dict(CANDS[name], integrity='cheap')
    with nbodykit_tpu.set_options(**opts):
        out = pm.paint(_pos())
    assert np.isfinite(np.asarray(out)).all()
    assert violation_counts()['violations'] == 0


@pytest.mark.parametrize('case', ['slab', 'pencil', 'slab-bf16',
                                  'slab-int16', 'roundtrip'])
def test_fft_clean_under_cheap(case, cpu8):
    """Both decompositions and both compressed wire formats run the
    guarded eager FFT with zero violations — the a2a fold budgets
    absorb exactly the quantization each format implies."""
    x = _field()
    opts = {'integrity': 'cheap'}
    mesh = cpu8
    if case == 'pencil':
        mesh = pencil_mesh(px=4, py=2)
    elif case.startswith('slab-'):
        opts['a2a_compress'] = case.split('-')[1]
    with nbodykit_tpu.set_options(**opts):
        y = dfft.dist_rfftn(x, mesh)
        if case == 'roundtrip':
            back = dfft.dist_irfftn(y, x.shape[0], mesh)
            np.testing.assert_allclose(np.asarray(back),
                                       np.asarray(x), atol=1e-4)
    assert violation_counts()['violations'] == 0


def test_integrity_off_is_bit_identical(cpu8):
    """The acceptance bit-identity contract: integrity='off' compiles
    and executes the exact program shipped before this layer existed,
    and 'cheap' only ADDS reductions — the data path is unchanged."""
    pm = ParticleMesh(Nmesh=32, BoxSize=64.0, dtype='f4', comm=cpu8)
    pos, x = _pos(), _field()
    with nbodykit_tpu.set_options(integrity='off'):
        f_off = np.asarray(pm.paint(pos))
        y_off = np.asarray(dfft.dist_rfftn(x, cpu8))
    with nbodykit_tpu.set_options(integrity='cheap'):
        f_chk = np.asarray(pm.paint(pos))
        y_chk = np.asarray(dfft.dist_rfftn(x, cpu8))
    np.testing.assert_array_equal(f_off, f_chk)
    np.testing.assert_array_equal(y_off, y_chk)


# ---------------------------------------------------------------------------
# the detection matrix: every injected corruption caught by its
# owning guard

MATRIX = [
    ('paint', 'paint.accum@1:corrupt', {}, 'paint.mass'),
    ('slab-r2c', 'a2a.payload@1:corrupt', {}, 'a2a.slab.r2c'),
    ('slab-c2r', 'a2a.payload@1:corrupt', {}, 'a2a.slab.c2r'),
    ('pencil-stage1', 'a2a.payload@1:corrupt', {},
     'a2a.pencil.r2c.stage1'),
    ('pencil-stage2', 'a2a.payload@2:corrupt', {},
     'a2a.pencil.r2c.stage2'),
    ('slab-r2c', 'a2a.payload@1:corrupt', {'a2a_compress': 'bf16'},
     'a2a.slab.r2c'),
    ('slab-r2c', 'a2a.payload@1:corrupt', {'a2a_compress': 'int16'},
     'a2a.slab.r2c'),
]


@pytest.mark.parametrize('kind,spec,extra,owner', MATRIX)
def test_detection_matrix(kind, spec, extra, owner, cpu8):
    """One corrupt point at a time: the guard that owns the surface —
    and no other — must classify the corruption.  A saturated exponent
    may overflow the fold to inf, in which case the same guard's
    ``.nonfinite`` tripwire fires; both spell detection by the owner.
    """
    # the c2r case needs a clean spectrum BEFORE the rule arms — the
    # forward transform's own a2a would consume the injection first
    y = dfft.dist_rfftn(_field(), cpu8) if kind == 'slab-c2r' else None
    opts = dict(extra, integrity='cheap', faults=spec)
    with nbodykit_tpu.set_options(**opts):
        reset_faults()
        with pytest.raises(IntegrityError) as ei:
            if kind == 'paint':
                pm = ParticleMesh(Nmesh=32, BoxSize=64.0, dtype='f4',
                                  comm=cpu8)
                pm.paint(_pos())
            elif kind.startswith('pencil'):
                dfft.dist_rfftn(_field(), pencil_mesh(px=4, py=2))
            elif kind == 'slab-c2r':
                dfft.dist_irfftn(y, 32, cpu8)
            else:
                dfft.dist_rfftn(_field(), cpu8)
    assert ei.value.site.startswith(owner), ei.value.site
    assert 'DATA_CORRUPTION' in str(ei.value)
    assert violation_counts()['violations'] == 1


def test_corruption_undetected_when_integrity_off(cpu8):
    """integrity='off' must not pay for detection: the corrupt rule
    still fires (the injector is independent) but nothing raises —
    which is exactly why 'cheap' exists."""
    with nbodykit_tpu.set_options(faults='a2a.payload@1:corrupt'):
        reset_faults()
        y = dfft.dist_rfftn(_field(), cpu8)
    assert violation_counts()['violations'] == 0
    # the poisoned element really is in the spectrum
    assert not np.isfinite(np.asarray(y)).all() or \
        np.abs(np.asarray(y)).max() >= 2.0 ** 64


# ---------------------------------------------------------------------------
# tier 2: supervisor retry-once + strike, quarantine, sealed manifest

def test_supervisor_retries_integrity_exactly_once():
    state = {'n': 0}

    def task():
        state['n'] += 1
        if state['n'] == 1:
            raise violation('test.guard', rank=3, delta=42.0)
        return 'ok'

    sup = Supervisor('t', policy=RetryPolicy(max_retries=0))
    assert sup.run(task) == 'ok'
    kinds = [e['kind'] for e in sup.events]
    assert kinds == ['integrity_retries']
    assert suspect_tracker().strike_counts() == {3: 1}
    assert suspect_tracker().quarantined() == []


def test_supervisor_second_violation_reraises_and_quarantines():
    def task():
        raise violation('test.guard', rank=5, delta=1.0)

    sup = Supervisor('t', policy=RetryPolicy(max_retries=3,
                                             base_s=0.001))
    with pytest.raises(IntegrityError):
        sup.run(task)
    # one retry, then the re-raise; both strikes recorded -> K=2
    # quarantines the rank
    assert suspect_tracker().strike_counts() == {5: 2}
    assert suspect_tracker().quarantined() == [5]


def test_quarantine_rides_sealed_manifest_and_reload(tmp_path):
    from nbodykit_tpu.resilience import FleetCheckpointStore
    tr = suspect_tracker()
    tr.strike(1, site='a2a.slab.r2c', task='t')
    tr.strike(1, site='a2a.slab.r2c', task='t')
    assert tr.quarantined() == [1]

    st = FleetCheckpointStore(tmp_path)
    for r in range(2):
        st.save_shard('k', 1, r, 2, {'step': 7},
                      arrays={'x': np.arange(4.0) + r})
    st.seal('k', 1, nranks=2, rank=0)
    man = st.latest_manifest('k')
    assert man['quarantined'] == [1]

    # a fresh process adopting the sealed checkpoint inherits the list
    reset_suspects()
    state, arrays, info = st.load('k', rank=0, nranks=2)
    assert state == {'step': 7} and info['quarantined'] == [1]
    assert suspect_tracker().is_quarantined(1)

    # and the quarantined rank itself REFUSES to rejoin
    with pytest.raises(RuntimeError, match='quarantined'):
        st.load('k', rank=1, nranks=2)
    snap = REGISTRY.snapshot().get('resilience.fleet.'
                                   'quarantine_refused')
    assert snap and snap['value'] == 1


def test_manifest_without_quarantine_stays_backcompat(tmp_path):
    """An empty quarantine list must not change the sealed body — an
    old manifest keeps verifying, and a new one without strikes is
    byte-compatible with the pre-integrity format."""
    from nbodykit_tpu.resilience import FleetCheckpointStore
    st = FleetCheckpointStore(tmp_path)
    for r in range(2):
        st.save_shard('k', 1, r, 2, {'step': 1})
    st.seal('k', 1, nranks=2, rank=0)
    man = st.latest_manifest('k')
    assert man is not None and 'quarantined' not in man
    got = st.load('k', rank=0, nranks=2)
    # no strikes → the info dict too stays byte-compatible (no key)
    assert got is not None and 'quarantined' not in got[2]


# ---------------------------------------------------------------------------
# tier 1: shadow verification in serve

def _server(**kw):
    from nbodykit_tpu.serve import AnalysisServer, BatchPolicy
    kw.setdefault('batch', BatchPolicy(max_delay_s=0))
    kw.setdefault('retry', RetryPolicy(max_retries=1, base_s=0.01))
    return AnalysisServer(per_task=4, **kw)


def test_request_verify_flag_rules():
    from nbodykit_tpu.serve import AnalysisRequest
    r = AnalysisRequest(nmesh=32, npart=20000, seed=1, verify=True)
    assert r.verify and r.to_dict()['verify'] is True
    # verify is a scheduling attribute, not program identity
    plain = AnalysisRequest(nmesh=32, npart=20000, seed=1)
    assert r.program_key() == plain.program_key()
    with pytest.raises(ValueError, match='verify'):
        AnalysisRequest(nmesh=32, data_ref={'path': 'x',
                                            'format': 'binary'},
                        verify=True)


def test_shadow_verification_bit_identical_clean():
    from nbodykit_tpu.serve import AnalysisRequest
    with _server() as srv:
        assert len(srv.meshes) >= 2, 'shadow needs two sub-meshes'
        r = srv.wait(srv.submit(AnalysisRequest(
            nmesh=32, npart=20000, seed=3, verify=True)), timeout=300)
        summary = srv.summary()
    assert r.status == 'completed'
    assert summary['shadow_verified'] == 1
    assert summary['shadow_mismatch'] == 0
    assert summary['integrity_retried'] == 0


def test_shadow_catches_corrupted_result_and_retries():
    """serve.result corruption happens AFTER compute — no tier-0
    invariant can see it; only the shadow re-execution can.  The
    mismatch classifies as INTEGRITY, the supervisor strikes + retries
    once, the rule has burnt out, and the clean result is delivered.
    """
    from nbodykit_tpu.serve import AnalysisRequest
    with nbodykit_tpu.set_options(faults='serve.result@1:corrupt'):
        reset_faults()
        with _server() as srv:
            r = srv.wait(srv.submit(AnalysisRequest(
                nmesh=32, npart=20000, seed=3, verify=True)),
                timeout=300)
            summary = srv.summary()
    assert r.status == 'completed'
    assert r.event_count('integrity_retries') == 1
    assert summary['shadow_verified'] == 2
    assert summary['shadow_mismatch'] == 1
    assert summary['integrity_retried'] == 1
    assert np.isfinite(np.asarray(r.y, dtype=np.float64)).all()
    assert suspect_tracker().summary()['strikes'] == 1


# ---------------------------------------------------------------------------
# the posture: regress + doctor

def test_integrity_summary_and_doctor_fail_on_unacknowledged(tmp_path):
    from nbodykit_tpu.diagnostics.__main__ import run_doctor
    from nbodykit_tpu.diagnostics.regress import integrity_summary
    root = str(tmp_path)
    assert integrity_summary(root) is None
    with open(os.path.join(root, 'BENCH_r10.json'), 'w') as f:
        json.dump({'parsed': {
            'metric': 'integrity_nmesh64', 'value': 1.0, 'unit': 's',
            'integrity': {'violations': 1, 'retried': 1}}}, f)
    s = integrity_summary(root)
    assert s['stamped_records'] == 1 and s['violations'] == 1 \
        and s['retried'] == 1 and s['unacknowledged_mismatch'] == 0

    # a shadow mismatch nobody retried is the doctor's hard failure
    with open(os.path.join(root, 'BENCH_r11.json'), 'w') as f:
        json.dump({'parsed': {
            'metric': 'servetrace_n8', 'value': 0.5, 'unit': 's',
            'requests': 8, 'rps': 2.0, 'p99_s': 0.5, 'lost': 0,
            'shadow_verified': 3, 'shadow_mismatch': 2,
            'integrity_retried': 1}}, f)
    s = integrity_summary(root)
    assert s['unacknowledged_mismatch'] == 1
    import io as _io
    out = _io.StringIO()
    rc = run_doctor(root=root, out=out, self_check_only=False)
    text = out.getvalue()
    line = [ln for ln in text.splitlines()
            if ln.startswith('integrity')][0]
    assert rc == 1 and 'FAIL' in line and 'shadow' in line
    assert 'integrity' in text.split('VERDICT:')[1]
