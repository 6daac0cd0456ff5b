"""Tests for nbodykit_tpu.resilience: checkpoint atomicity (including
under SIGKILL replay, reusing the pattern from test_diagnostics.py),
error classification, supervised retry with backoff, OOM degradation
down the FFT/paint ladder, deterministic fault injection, and the
acceptance path — a bench rep killed mid-run resuming on relaunch
into one complete record with ``resumed: true``."""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import nbodykit_tpu
from nbodykit_tpu import _global_options, diagnostics
from nbodykit_tpu.diagnostics import REGISTRY, read_trace
from nbodykit_tpu.resilience import (DEADLINE, FATAL, OOM, TRANSIENT,
                                     CheckpointStore, DegradationLadder,
                                     RetryPolicy, Supervisor,
                                     classify_error, default_ladder,
                                     error_class, fault_point,
                                     parse_spec, reset_faults)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_state():
    """Registry, tracer, fault counts and the degradable options are
    process-wide; every test sees (and leaves) a pristine copy."""
    saved = _global_options.copy()
    REGISTRY.reset()
    reset_faults()
    yield
    REGISTRY.reset()
    reset_faults()
    diagnostics.configure(None)
    _global_options.clear()
    _global_options.update(saved)


def _counter(name):
    snap = REGISTRY.snapshot().get(name)
    return snap['value'] if snap else 0


def _spans(path):
    records, _ = read_trace(str(path))
    return [r for r in records if r.get('t') == 'span']


# ---------------------------------------------------------------------------
# checkpoint store

def test_checkpoint_roundtrip(tmp_path):
    st = CheckpointStore(tmp_path)
    state = {'a': 1, 'b': [1.5, 'x'], 'nested': {'k': 2}}
    arrays = {'acc': np.arange(12.0).reshape(3, 4),
              'idx': np.array([3, 1, 2], np.int32)}
    st.save('bench.metric+1e_07', state, arrays=arrays)
    got = st.load('bench.metric+1e_07')
    assert got is not None
    got_state, got_arrays = got
    assert got_state == state
    np.testing.assert_array_equal(got_arrays['acc'], arrays['acc'])
    np.testing.assert_array_equal(got_arrays['idx'], arrays['idx'])
    assert got_arrays['idx'].dtype == np.int32
    assert st.keys() == ['bench.metric_1e_07']
    assert st.age_s('bench.metric+1e_07') >= 0
    assert st.oldest_age_s() >= 0
    st.delete('bench.metric+1e_07')
    assert st.load('bench.metric+1e_07') is None
    assert st.keys() == [] and st.oldest_age_s() is None


def test_checkpoint_overwrite_latest_wins(tmp_path):
    st = CheckpointStore(tmp_path)
    st.save('k', {'completed': 1})
    st.save('k', {'completed': 2})
    assert st.load('k')[0] == {'completed': 2}


def test_checkpoint_corrupt_state_detected(tmp_path):
    st = CheckpointStore(tmp_path)
    path = st.save('k', {'completed': 3})
    meta = json.load(open(path))
    meta['state']['completed'] = 4          # tampered, hash now stale
    with open(path, 'w') as f:
        json.dump(meta, f)
    assert st.load('k') is None
    assert _counter('resilience.checkpoint.corrupt') == 1
    # a torn metadata file (killed writer) is corrupt, not fatal
    with open(path, 'w') as f:
        f.write('{"v": 1, "state": {"comp')
    assert st.load('k') is None


def test_checkpoint_corrupt_array_detected(tmp_path):
    st = CheckpointStore(tmp_path)
    st.save('k', {'n': 1}, arrays={'x': np.ones(4)})
    apath = [os.path.join(tmp_path, f) for f in os.listdir(tmp_path)
             if f.endswith('.npy')][0]
    with open(apath, 'wb') as f:
        np.save(f, np.zeros(4))             # bytes no longer match
    assert st.load('k') is None
    assert _counter('resilience.checkpoint.corrupt') == 1


def test_checkpoint_atomic_under_sigkill(tmp_path):
    """A SIGKILL mid-save (injected at the pre-commit fault point)
    must leave the PREVIOUS checkpoint intact and loadable — the
    atomic tmp+rename contract."""
    script = r"""
import os, sys
sys.path.insert(0, %r)
import nbodykit_tpu
from nbodykit_tpu.resilience import CheckpointStore
# the SECOND save of key 'k' dies between writing the tmp file and
# the commit rename
nbodykit_tpu.set_options(faults='ckpt.write.k@2:kill')
st = CheckpointStore(%r)
st.save('k', {'completed': 1, 'elapsed_s': 2.5})
st.save('k', {'completed': 2, 'elapsed_s': 5.0})   # SIGKILLed here
raise SystemExit('unreachable')
""" % (REPO, str(tmp_path))
    proc = subprocess.run([sys.executable, '-c', script],
                          capture_output=True, timeout=300)
    assert proc.returncode == -signal.SIGKILL, proc.stderr
    got = CheckpointStore(tmp_path).load('k')
    assert got is not None, 'checkpoint lost to a mid-save kill'
    assert got[0] == {'completed': 1, 'elapsed_s': 2.5}


# ---------------------------------------------------------------------------
# error classification

def test_classify_error():
    X = error_class()
    assert classify_error(X('UNAVAILABLE: socket closed')) == TRANSIENT
    assert classify_error(RuntimeError('DATA_LOSS: link')) == TRANSIENT
    assert classify_error(
        X('RESOURCE_EXHAUSTED: Out of memory while trying to allocate '
          '4294967296 bytes.')) == OOM
    assert classify_error(MemoryError()) == OOM
    assert classify_error(
        X('DEADLINE_EXCEEDED: timed out')) == DEADLINE
    assert classify_error(ValueError('Nmesh must divide')) == FATAL
    assert classify_error(RuntimeError('INTERNAL: broken')) == FATAL


def test_retry_policy_backoff_bounded():
    p = RetryPolicy(max_retries=5, base_s=1.0, factor=2.0, max_s=4.0,
                    jitter=0.5, seed=3)
    delays = [p.backoff_s(i) for i in range(5)]
    # exponential then capped, jitter adds at most 50%
    assert 1.0 <= delays[0] <= 1.5
    assert 2.0 <= delays[1] <= 3.0
    assert all(4.0 <= d <= 6.0 for d in delays[2:])
    # deterministic for a fixed seed
    q = RetryPolicy(max_retries=5, base_s=1.0, factor=2.0, max_s=4.0,
                    jitter=0.5, seed=3)
    assert [q.backoff_s(i) for i in range(5)] == delays


# ---------------------------------------------------------------------------
# fault injection

def test_parse_spec():
    assert parse_spec('bench.rep@2:kill,p:unavailable') == [
        ('bench.rep', 2, 'kill'), ('p', 1, 'unavailable')]
    with pytest.raises(ValueError):
        parse_spec('p@2:explode')
    with pytest.raises(ValueError):
        parse_spec('justaname')


def test_fault_point_fires_on_nth_call_only():
    with nbodykit_tpu.set_options(faults='p@3:unavailable'):
        reset_faults()
        fault_point('p')
        fault_point('p')
        fault_point('other')                 # untargeted: never counted
        with pytest.raises(Exception, match='UNAVAILABLE'):
            fault_point('p')
        fault_point('p')                     # 4th call: rule spent
    assert _counter('resilience.faults.injected') == 1


def test_fault_point_raises_real_xla_error_class():
    with nbodykit_tpu.set_options(faults='q@1:resource_exhausted'):
        reset_faults()
        with pytest.raises(error_class()) as ei:
            fault_point('q')
        assert 'RESOURCE_EXHAUSTED' in str(ei.value)
        assert classify_error(ei.value) == OOM


# ---------------------------------------------------------------------------
# supervisor

def test_supervisor_retries_transient_with_backoff(tmp_path):
    """ISSUE acceptance: injected UNAVAILABLE is retried with backoff,
    then the call succeeds; the retry is a counter + a trace event."""
    diagnostics.configure(str(tmp_path))
    sleeps = []
    with nbodykit_tpu.set_options(faults='work.attempt@1:unavailable'):
        reset_faults()
        sup = Supervisor('work',
                         policy=RetryPolicy(max_retries=3, base_s=0.25,
                                            jitter=0.5, seed=7),
                         sleep=sleeps.append)
        assert sup.run(lambda: 'done') == 'done'
    diagnostics.configure(None)
    assert _counter('resilience.retries') == 1
    assert len(sleeps) == 1 and 0.25 <= sleeps[0] <= 0.375
    spans = _spans(tmp_path)
    retry = [s for s in spans if s['name'] == 'resilience.retry']
    assert len(retry) == 1
    assert retry[0]['attrs']['cls'] == TRANSIENT
    assert retry[0]['attrs']['task'] == 'work'
    assert 'UNAVAILABLE' in retry[0]['attrs']['error']
    backoff = [s for s in spans if s['name'] == 'resilience.backoff']
    assert len(backoff) == 1                 # the wait itself is a span


def test_supervisor_retry_budget_exhausted_reraises():
    with nbodykit_tpu.set_options(
            faults='w.attempt@1:unavailable,w.attempt@2:unavailable'):
        reset_faults()
        sup = Supervisor('w', policy=RetryPolicy(max_retries=1),
                         sleep=lambda s: None)
        with pytest.raises(Exception, match='UNAVAILABLE'):
            sup.run(lambda: 'never')
    assert _counter('resilience.retries') == 1


def test_supervisor_fatal_passes_through():
    sup = Supervisor('f', sleep=lambda s: None)
    with pytest.raises(ValueError, match='a real bug'):
        sup.run(lambda: (_ for _ in ()).throw(ValueError('a real bug')))
    assert _counter('resilience.retries') == 0


def test_supervisor_oom_steps_down_ladder(tmp_path):
    """ISSUE acceptance: injected RESOURCE_EXHAUSTED steps down the
    FFT/paint ladder (fft_chunk_bytes then paint_chunk_size halved)
    with each degradation recorded as a counter + trace event."""
    diagnostics.configure(str(tmp_path))
    fc0 = int(_global_options['fft_chunk_bytes'])
    pc0 = int(_global_options['paint_chunk_size'])

    def fn():
        # "OOMs" until BOTH knobs have stepped down one rung
        if _global_options['fft_chunk_bytes'] == fc0 or \
                _global_options['paint_chunk_size'] == pc0:
            raise error_class()('RESOURCE_EXHAUSTED: out of memory')
        return 'fits now'

    sup = Supervisor('big', ladder=default_ladder(),
                     sleep=lambda s: None)
    assert sup.run(fn) == 'fits now'
    diagnostics.configure(None)
    assert int(_global_options['fft_chunk_bytes']) == fc0 // 2
    assert int(_global_options['paint_chunk_size']) == pc0 // 2
    assert _counter('resilience.degradations') == 2
    degr = [s for s in _spans(tmp_path)
            if s['name'] == 'resilience.degrade']
    assert [d['attrs']['rung'] for d in degr] == \
        ['fft_chunk_bytes/2', 'paint_chunk_size/2']
    assert degr[0]['attrs']['detail']['fft_chunk_bytes'] == fc0 // 2


def test_supervisor_oom_without_ladder_reraises():
    sup = Supervisor('nl', sleep=lambda s: None)
    with pytest.raises(Exception, match='RESOURCE_EXHAUSTED'):
        sup.run(lambda: (_ for _ in ()).throw(
            error_class()('RESOURCE_EXHAUSTED: oom')))
    assert _counter('resilience.degradations') == 0


def test_supervisor_ladder_exhausted_reraises():
    ladder = DegradationLadder([('noop', lambda: {'step': 1})])
    sup = Supervisor('x', ladder=ladder, sleep=lambda s: None)
    with pytest.raises(Exception, match='RESOURCE_EXHAUSTED'):
        sup.run(lambda: (_ for _ in ()).throw(
            error_class()('RESOURCE_EXHAUSTED: oom')))
    assert _counter('resilience.degradations') == 1
    assert ladder.applied == [('noop', {'step': 1})]


def test_default_ladder_respects_floors():
    nbodykit_tpu.set_options(fft_chunk_bytes=1 << 24,
                             paint_chunk_size=1 << 18)
    ladder = default_ladder()
    while ladder.step() is not None:
        pass
    assert int(_global_options['fft_chunk_bytes']) == 1 << 24
    assert int(_global_options['paint_chunk_size']) == 1 << 18


def test_ladder_halves_auto_resolved_value():
    """The first rung halves from the option as it stands (the table's
    2**31 here) and PINS the result; the request-scoped ladder does the
    same into its own mapping and writes no global."""
    from nbodykit_tpu.resilience.supervise import scoped_ladder
    opts = {}
    label, detail = scoped_ladder(opts).step()
    assert label == 'fft_chunk_bytes/2'
    assert detail == {'fft_chunk_bytes': 2 ** 30, 'was': 2 ** 31}
    assert opts == {'fft_chunk_bytes': 2 ** 30}
    assert _global_options['fft_chunk_bytes'] == 2 ** 31
    label, detail = default_ladder().step()
    assert label == 'fft_chunk_bytes/2'
    assert detail == {'fft_chunk_bytes': 2 ** 30, 'was': 2 ** 31}
    assert _global_options['fft_chunk_bytes'] == 2 ** 30


def test_supervisor_resume_validate_rejects_mismatch(tmp_path):
    st = CheckpointStore(tmp_path)
    st.save('k', {'reps': 4, 'completed': 1})
    sup = Supervisor('v', checkpoint=st)
    assert sup.resume('k', validate=lambda s: s['reps'] == 2) is None
    assert _counter('resilience.resumes') == 0
    got = sup.resume('k', validate=lambda s: s['reps'] == 4)
    assert got[0]['completed'] == 1
    assert _counter('resilience.resumes') == 1
    sup.done('k')
    assert st.load('k') is None


# ---------------------------------------------------------------------------
# doctor / history posture

def test_resilience_summary_flags_pending_checkpoints(tmp_path):
    """A leftover checkpoint is an interrupted measurement awaiting
    relaunch: the regress history and the doctor must surface it."""
    from nbodykit_tpu.diagnostics.regress import resilience_summary
    res = resilience_summary(str(tmp_path))
    assert res == {'resumed_records': 0, 'pending_checkpoints': 0,
                   'oldest_checkpoint_hours': None}
    CheckpointStore(tmp_path / 'BENCH_CKPT').save(
        'bench.fftpower_x', {'completed': 1, 'reps': 2})
    with open(tmp_path / 'BENCH_STAGED.json', 'w') as f:
        json.dump({'results': {'m': {'metric': 'm', 'value': 1.0,
                                     'resumed': True}}}, f)
    res = resilience_summary(str(tmp_path))
    assert res['pending_checkpoints'] == 1
    assert res['resumed_records'] == 1
    assert res['oldest_checkpoint_hours'] is not None


def test_doctor_counts_resilience_events_from_trace(tmp_path):
    """Registry counters and trace events are merged per-key by max —
    a same-process doctor run must not double-count its own trace."""
    from nbodykit_tpu.diagnostics.__main__ import _resilience_counts
    tr = diagnostics.configure(str(tmp_path))
    tr.event('resilience.retry', {'task': 't'})
    tr.event('resilience.retry', {'task': 't'})
    tr.event('resilience.resume', {'key': 'k'})
    REGISTRY.counter('resilience.retries').add(2)
    diagnostics.configure(None)
    counts = _resilience_counts(str(tmp_path))
    assert counts['retries'] == 2
    assert counts['resumes'] == 1


# ---------------------------------------------------------------------------
# the OOM-ladder FFT rung (satellite): eager large c2c gets the
# tracer check + a Python-driven lowmem driver

def test_c2c_lowmem_matches_fftn():
    import jax
    import jax.numpy as jnp
    from nbodykit_tpu.parallel import dfft
    rng = np.random.RandomState(5)
    x = (rng.randn(8, 12, 10) + 1j * rng.randn(8, 12, 10)) \
        .astype('c16')
    ref = np.transpose(np.fft.fftn(x), (1, 0, 2))
    # direct driver call (chunked: tiny target)
    got = dfft.fftn_c2c_single_lowmem([jnp.asarray(x)], target=4096)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-12,
                               atol=1e-10)
    back = dfft.fftn_c2c_single_lowmem([jnp.asarray(got)],
                                       inverse=True, target=4096)
    np.testing.assert_allclose(np.asarray(back), x, rtol=1e-12,
                               atol=1e-12)
    with nbodykit_tpu.set_options(fft_chunk_bytes=4096):
        # eager dispatch goes through the lowmem driver...
        got2 = dfft.dist_fftn_c2c(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(got2), ref, rtol=1e-12,
                                   atol=1e-10)
        # ...while a traced call takes the in-jit chunked branch (the
        # Tracer check: jitting must neither fail nor call back out)
        traced = jax.jit(lambda v: dfft.dist_fftn_c2c(v))(
            jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(traced), ref,
                                   rtol=1e-12, atol=1e-10)


def test_c2c_lowmem_emits_chunk_spans(tmp_path):
    import jax.numpy as jnp
    from nbodykit_tpu.parallel import dfft
    x = jnp.ones((8, 8, 8), jnp.complex64)
    with nbodykit_tpu.set_options(diagnostics=str(tmp_path)):
        dfft.fftn_c2c_single_lowmem([x], target=2048)
    spans = _spans(tmp_path)
    names = [s['name'] for s in spans]
    assert 'fft.lowmem.c2c' in names
    assert any(s['name'] == 'fft.chunk' for s in spans)


# ---------------------------------------------------------------------------
# acceptance: a bench rep SIGKILLed mid-run resumes on relaunch

@pytest.mark.parametrize('nmesh,npart', [(32, 2000)])
def test_bench_rep_kill_then_resume(tmp_path, nmesh, npart):
    """bench.py --config, killed by the fault harness at the start of
    rep 2, relaunched without faults: the relaunch must RESUME (not
    restart), flush one complete record with ``resumed: true``, clean
    up its checkpoint, and leave the resume event in the trace."""
    env_base = dict(
        os.environ,
        JAX_PLATFORMS='cpu',
        BENCH_REPS='2', BENCH_PHASES='0',
        BENCH_STAGED_PATH=str(tmp_path / 'STAGED.json'),
        BENCH_DETAIL_PATH=str(tmp_path / 'DETAIL.json'),
        BENCH_CKPT_DIR=str(tmp_path / 'CKPT'),
        BENCH_TRACE_DIR=str(tmp_path / 'TRACE'),
    )
    env_base.pop('NBKIT_FAULTS', None)
    bench = os.path.join(REPO, 'bench.py')

    # run 1: rep 0 completes and checkpoints; the kill fires entering
    # rep 1
    env1 = dict(env_base, NBKIT_FAULTS='bench.rep@2:kill')
    p1 = subprocess.run([sys.executable, bench, '--config',
                         str(nmesh), str(npart)],
                        capture_output=True, timeout=560, env=env1)
    assert p1.returncode == -signal.SIGKILL, p1.stderr.decode()[-2000:]
    ckpts = os.listdir(tmp_path / 'CKPT')
    assert any(f.endswith('.ckpt.json') for f in ckpts), ckpts
    staged = json.load(open(tmp_path / 'STAGED.json'))['results']
    (partial,) = staged.values()
    assert partial['partial'] is True        # warmed record survived

    # run 2: no faults — resumes rep 1 from the checkpoint
    p2 = subprocess.run([sys.executable, bench, '--config',
                         str(nmesh), str(npart)],
                        capture_output=True, timeout=560, env=env_base)
    assert p2.returncode == 0, p2.stderr.decode()[-2000:]
    rec = json.loads(p2.stdout.decode().strip().splitlines()[-1])
    # one complete, doctor-clean record (regress.classify's shape
    # contract: metric + unit + positive value), marked resumed
    assert rec['resumed'] is True and rec['resumed_reps'] == 1
    assert rec['metric'] and rec['unit'] == 's' and rec['value'] > 0
    staged = json.load(open(tmp_path / 'STAGED.json'))['results']
    (final,) = staged.values()
    assert final['partial'] is False and final['stage'] == 'complete'
    assert final['resumed'] is True
    # checkpoint consumed; nothing left to resume
    assert not any(f.endswith('.ckpt.json')
                   for f in os.listdir(tmp_path / 'CKPT'))
    # the resume event is visible in the merged trace
    records, _ = read_trace(str(tmp_path / 'TRACE'))
    names = {r.get('name') for r in records if r.get('t') == 'span'}
    assert 'resilience.resume' in names
    assert 'ckpt.save' in names
