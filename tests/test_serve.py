"""Tests for nbodykit_tpu.serve: the declarative request model,
memory-plan admission control (reject vs degrade, structured reasons),
the warm program cache (second identical-shape request compiles
nothing, proven by compile-miss counters), vmap batching
bit-equivalence, deadline eviction, queue bounding, per-request fault
isolation under injected faults (one request degrades, the fleet
survives), checkpoint resume, and graceful drain/shutdown — plus the
thread-safety satellites: ``option_scope`` leak-proofing across reused
worker threads and ``TaskManager.map`` exception propagation."""

import json
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import nbodykit_tpu
from nbodykit_tpu import _global_options, diagnostics
from nbodykit_tpu.diagnostics import REGISTRY
from nbodykit_tpu.parallel.runtime import cpu_mesh, use_mesh
from nbodykit_tpu.resilience import CheckpointStore, reset_faults
from nbodykit_tpu.serve import (ADMIT, DEGRADE, REJECT, AnalysisRequest,
                                AnalysisServer, BatchPolicy, admit,
                                generate_trace, replay)


@pytest.fixture(autouse=True)
def _clean_state():
    """Registry, fault counts and options are process-wide; every test
    sees (and leaves) a pristine copy."""
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


def _one_worker_server(**kw):
    """A server pinned to ONE 1-device worker (deterministic queueing
    tests need a single consumer)."""
    with use_mesh(cpu_mesh(1)):
        return AnalysisServer(per_task=1, **kw)


# ---------------------------------------------------------------------------
# request model

def test_request_validation_and_keys():
    r = AnalysisRequest(nmesh=64, npart=100000, seed=5, priority=2)
    assert r.request_id.startswith('req-')
    assert r.shape_class == 'mesh64-part1e5'
    # seed / deadline / priority are runtime inputs, never program id
    r2 = AnalysisRequest(nmesh=64, npart=100000, seed=99, priority=0)
    assert r.program_key(1) == r2.program_key(1)
    assert r.program_key(1) != r.program_key(8)
    rt = AnalysisRequest.from_dict(r.to_dict())
    assert rt.program_key(1) == r.program_key(1)
    assert rt.request_id == r.request_id
    with pytest.raises(ValueError):
        AnalysisRequest(algorithm='PairCount')
    with pytest.raises(ValueError):
        AnalysisRequest(dtype='f2')
    with pytest.raises(ValueError):
        AnalysisRequest(deadline_s=0)
    with pytest.raises(ValueError):
        AnalysisRequest(nmesh=2)


def test_shape_class_buckets():
    from nbodykit_tpu.serve.request import shape_class
    assert shape_class(64, 10_000) == 'mesh64-part1e4'
    assert shape_class(100, 9e4) == 'mesh128-part1e5'
    assert shape_class(512) == 'mesh512'
    assert shape_class(npart=1e7) == 'part1e7'
    with pytest.raises(ValueError):
        shape_class()


# ---------------------------------------------------------------------------
# admission control

def test_admission_admit_clean():
    d = admit(AnalysisRequest(nmesh=64, npart=10 ** 5), ndevices=1,
              hbm_bytes=16e9)
    assert d.status == ADMIT and d.admitted
    assert not d.options
    assert d.plan['fits']


def test_admission_reject_structured_over_budget():
    d = admit(AnalysisRequest(nmesh=2048, npart=10 ** 9), ndevices=1,
              hbm_bytes=16e9)
    assert d.status == REJECT and not d.admitted
    r = d.reason
    assert r['code'] == 'over_budget'
    assert r['peak_bytes'] > r['budget_bytes']
    assert r['rungs_tried']          # it tried the whole ladder
    assert 'ndevices' in r and 'detail' in r
    # machine-shape round trip
    assert json.loads(json.dumps(d.to_dict()))['reason']['code'] \
        == 'over_budget'


def test_admission_degrade_steps_scoped_ladder():
    # nmesh=64 / npart=1e8 / scatter: peak ~2.27 GB unchunked,
    # ~1.74 GB at paint_chunk 8M — budget between the two admits
    # degraded (and ONLY via per-request options, never set_options)
    before = dict(_global_options)
    d = admit(AnalysisRequest(nmesh=64, npart=10 ** 8,
                              paint_method='scatter'),
              ndevices=1, hbm_bytes=2.3e9)
    assert d.status == DEGRADE and d.admitted
    assert d.options.get('paint_chunk_size')
    assert [r[0] for r in d.rungs][-1] == 'paint_chunk_size/2'
    assert d.plan['fits']
    assert dict(_global_options) == before


def test_admission_reject_indivisible():
    d = admit(AnalysisRequest(nmesh=36, npart=1000), ndevices=8,
              hbm_bytes=16e9)
    assert d.status == REJECT
    assert d.reason['code'] == 'indivisible'


# ---------------------------------------------------------------------------
# the server: warm cache, batching, eviction, bounding

def test_serve_warm_cache_second_request_compiles_nothing():
    label = 'compile.serve.fftpower.mesh32-part1e4'
    with _one_worker_server(batch=BatchPolicy(max_delay_s=0)) as srv:
        r1 = srv.wait(srv.submit(AnalysisRequest(
            nmesh=32, npart=20000, seed=1)), timeout=180)
        assert r1.status == 'completed'
        miss0 = _counter(label + '.misses')
        build0 = _counter('serve.program.build')
        r2 = srv.wait(srv.submit(AnalysisRequest(
            nmesh=32, npart=20000, seed=2)), timeout=60)
        assert r2.status == 'completed'
        assert _counter(label + '.misses') == miss0     # ZERO recompile
        assert _counter(label + '.hits') >= 1
        assert _counter('serve.program.build') == build0
        assert _counter('serve.program.reuse') >= 1


@pytest.mark.parametrize('algorithm', ['FFTPower', 'FFTCorr'])
def test_serve_shell_counts_are_the_lattice_and_repeat(algorithm):
    """The served spectrum and the served correlation function bin
    through one :func:`~nbodykit_tpu.ops.histogram.shell_sums`: integer
    shell counts equal to a numpy count of the lattice (Hermitian
    pairs twice and the DC mode dropped in k space; every cell in real
    space), and the same bytes for the same seed twice."""
    n = 32
    with _one_worker_server(batch=BatchPolicy(max_delay_s=0)) as srv:
        r, again = [srv.wait(srv.submit(AnalysisRequest(
            algorithm=algorithm, nmesh=n, npart=20000, seed=5)),
            timeout=180) for _ in range(2)]
    assert r.status == again.status == 'completed'
    i = np.abs(np.fft.fftfreq(n, 1.0 / n)).astype('i8') ** 2
    shell = np.minimum(np.floor(np.sqrt(
        i[:, None, None] + i[None, :, None] + i[None, None, :])),
        n // 2 - 1).astype('i8')
    want = np.bincount(shell.ravel(), minlength=n // 2)
    if algorithm == 'FFTPower':
        want[0] -= 1
    else:
        # shell 1: the 6 face neighbours + 12 edge (sqrt 2) + 8 corner
        # (sqrt 3) cells of the periodic lattice
        assert want[0] == 1 and want[1] == 26
    nm = np.asarray(r.nmodes)
    np.testing.assert_array_equal(nm, want)
    assert np.isfinite(np.asarray(r.y)).all()
    assert np.asarray(r.y).tobytes() == np.asarray(again.y).tobytes()
    np.testing.assert_array_equal(np.asarray(again.nmodes), nm)


def test_serve_batched_bit_equal_to_sequential():
    seeds = [11, 12, 13, 14]
    with _one_worker_server(
            batch=BatchPolicy(max_batch=4, max_delay_s=1.0)) as srv:
        # 4 compatible requests submitted together: one vmap launch
        tickets = [srv.submit(AnalysisRequest(
            nmesh=32, npart=20000, seed=s)) for s in seeds]
        batched = [srv.wait(t, timeout=180) for t in tickets]
        assert all(r.status == 'completed' for r in batched)
        assert max(r.batch_size for r in batched) > 1
        # same seeds one at a time: sequential launches
        solo = [srv.wait(srv.submit(AnalysisRequest(
            nmesh=32, npart=20000, seed=s)), timeout=60)
            for s in seeds]
    for rb, rs in zip(batched, solo):
        assert rs.batch_size == 1
        assert np.array_equal(np.asarray(rb.y), np.asarray(rs.y))
        assert np.array_equal(np.asarray(rb.nmodes),
                              np.asarray(rs.nmodes))


def test_serve_deadline_eviction_structured():
    with _one_worker_server(batch=BatchPolicy(max_delay_s=0)) as srv:
        # occupy the only worker, then submit an already-hopeless
        # deadline: it must be EVICTED with a verdict, not run late
        blocker = srv.submit(AnalysisRequest(nmesh=32, npart=20000,
                                             seed=100))
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:   # blocker on the worker
            with srv._lock:
                if not srv._pending:
                    break
            time.sleep(0.005)
        doomed = srv.submit(AnalysisRequest(nmesh=32, npart=20000,
                                            seed=101, deadline_s=1e-3))
        rb = srv.wait(blocker, timeout=180)
        rd = srv.wait(doomed, timeout=60)
    assert rb.status == 'completed'
    assert rd.status == 'evicted'
    assert rd.reason['code'] == 'deadline'
    assert rd.reason['waited_s'] >= 0


def test_serve_queue_full_structured_reject():
    with _one_worker_server(max_queue=1,
                            batch=BatchPolicy(max_delay_s=0)) as srv:
        blocker = srv.submit(AnalysisRequest(nmesh=32, npart=20001,
                                             seed=0))
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:     # wait until picked up
            with srv._lock:
                if not srv._pending:
                    break
            time.sleep(0.01)
        q1 = srv.submit(AnalysisRequest(nmesh=32, npart=20001, seed=1))
        q2 = srv.submit(AnalysisRequest(nmesh=32, npart=20001, seed=2))
        r2 = srv.wait(q2, timeout=10)
        assert r2.status == 'rejected'
        assert r2.reason['code'] == 'queue_full'
        assert r2.reason['max_queue'] == 1
        assert srv.wait(blocker, timeout=180).status == 'completed'
        assert srv.wait(q1, timeout=60).status == 'completed'


def test_serve_rejected_never_queued():
    with _one_worker_server() as srv:
        t = srv.submit(AnalysisRequest(nmesh=2048, npart=10 ** 9))
        r = srv.wait(t, timeout=5)
        assert r.status == 'rejected'
        assert r.reason['code'] == 'over_budget'
        assert srv.summary()['rejected'] == 1


# ---------------------------------------------------------------------------
# fault isolation

def test_serve_injected_fault_degrades_one_request_not_fleet():
    from nbodykit_tpu.resilience import RetryPolicy
    n = 4
    with nbodykit_tpu.set_options(
            faults='serve.request.attempt@2:unavailable'):
        reset_faults()
        with _one_worker_server(
                batch=BatchPolicy(max_delay_s=0),
                retry=RetryPolicy(max_retries=3, base_s=0.01)) as srv:
            tickets = [srv.submit(AnalysisRequest(
                nmesh=32, npart=20000, seed=s)) for s in range(n)]
            results = [srv.wait(t, timeout=180) for t in tickets]
            summary = srv.summary()
    # the fleet survived: every request completed, nothing lost
    assert [r.status for r in results] == ['completed'] * n
    assert summary['lost'] == 0
    # and EXACTLY ONE request absorbed the injected device loss
    hit = [r for r in results if r.event_count('retries')]
    assert len(hit) == 1
    assert summary['retried'] == 1


def test_serve_fault_after_work_resumes_from_checkpoint(tmp_path):
    from nbodykit_tpu.resilience import RetryPolicy
    with nbodykit_tpu.set_options(
            faults='serve.request.work@1:unavailable'):
        reset_faults()
        with _one_worker_server(
                batch=BatchPolicy(max_delay_s=0),
                checkpoint=CheckpointStore(tmp_path),
                retry=RetryPolicy(max_retries=3, base_s=0.01)) as srv:
            r = srv.wait(srv.submit(AnalysisRequest(
                nmesh=32, npart=20000, seed=7)), timeout=180)
            summary = srv.summary()
    assert r.status == 'completed'
    # the kill landed AFTER the checkpoint: the retry resumed saved
    # results instead of recomputing
    assert r.event_count('resumes') == 1
    assert summary['resumed'] == 1
    assert summary['lost'] == 0


# ---------------------------------------------------------------------------
# lifecycle

def test_serve_graceful_drain_and_idempotent_shutdown():
    srv = _one_worker_server(batch=BatchPolicy(max_delay_s=0))
    tickets = [srv.submit(AnalysisRequest(nmesh=32, npart=20000,
                                          seed=s)) for s in range(3)]
    assert srv.drain(timeout=180)
    assert all(t.result is not None for t in tickets)
    srv.shutdown()
    srv.shutdown()                      # second call: no-op
    late = srv.submit(AnalysisRequest(nmesh=32, npart=20000))
    assert late.result.status == 'rejected'
    assert late.result.reason['code'] == 'shutting_down'
    s = srv.summary()
    assert s['lost'] == 0
    assert s['submitted'] == s['resolved']


def test_trace_generator_deterministic():
    a = [r.to_dict() for r in generate_trace(60, seed=3)]
    b = [r.to_dict() for r in generate_trace(60, seed=3)]
    assert a == b
    c = [r.to_dict() for r in generate_trace(60, seed=4)]
    assert a != c
    assert a[0]['request_id'] == 'trace-00000'
    # Zipf head: the hottest shape dominates
    algos = [d['algorithm'] for d in a]
    assert algos.count('FFTPower') > len(a) // 2


def test_serve_trace_replay_end_to_end():
    trace = generate_trace(12, seed=1, deadline_s=300.0)
    with _one_worker_server(
            batch=BatchPolicy(max_batch=4, max_delay_s=0.05)) as srv:
        tickets = replay(srv, trace, seed=1)
        assert all(t.result is not None for t in tickets)
        s = srv.summary()
    assert s['submitted'] == 12
    assert s['lost'] == 0
    assert s['completed'] + s['rejected'] + s['evicted'] \
        + s['failed'] == 12
    assert s['p99_s'] is not None and s['p50_s'] <= s['p99_s']


# ---------------------------------------------------------------------------
# satellites: thread safety

def test_option_scope_restores_and_cannot_leak_across_threads():
    import random

    def task(i):
        # each reused pool thread overrides, works, and MUST restore
        with nbodykit_tpu.option_scope(
                paint_chunk_size=1000 + i,
                fft_chunk_bytes=2000 + i):
            time.sleep(random.random() * 0.01)
            assert _global_options['paint_chunk_size'] == 1000 + i
        return _global_options['paint_chunk_size']

    baseline = _global_options['paint_chunk_size']
    with ThreadPoolExecutor(max_workers=4) as ex:
        out = list(ex.map(task, range(64)))
    # every task saw the default restored after its scope — including
    # on threads the pool reused across tasks
    assert set(out) == {baseline}
    assert _global_options['paint_chunk_size'] == baseline


def test_option_scope_restores_on_exception_and_rejects_bad_keys():
    baseline = _global_options['paint_chunk_size']
    with pytest.raises(RuntimeError):
        with nbodykit_tpu.option_scope(paint_chunk_size=7):
            raise RuntimeError('boom')
    assert _global_options['paint_chunk_size'] == baseline
    with pytest.raises(KeyError):
        with nbodykit_tpu.option_scope(not_an_option=1):
            pass


def test_taskmanager_map_propagates_original_exception(cpu8):
    from nbodykit_tpu.batch import TaskManager

    def work(i):
        if i == 2:
            raise ValueError('task two exploded')
        return i * i

    with use_mesh(cpu8):
        with TaskManager(cpus_per_task=4) as tm:     # 2 sub-meshes
            assert tm.map(lambda i: i * i, range(4)) == [0, 1, 4, 9]
            with pytest.raises(ValueError, match='task two exploded') \
                    as ei:
                tm.map(work, range(4))
    assert ei.value.task_index == 2


def test_taskmanager_injected_fault_surfaces_not_deadlocks(cpu8):
    from nbodykit_tpu.batch import TaskManager
    from nbodykit_tpu.resilience import fault_point

    def work(i):
        fault_point('batch.map.task')
        return i

    with nbodykit_tpu.set_options(faults='batch.map.task@3:internal'):
        reset_faults()
        with use_mesh(cpu8):
            with TaskManager(cpus_per_task=4) as tm:
                with pytest.raises(Exception) as ei:
                    tm.map(work, range(6))
    assert 'INTERNAL' in str(ei.value)
    assert hasattr(ei.value, 'task_index')


# ---------------------------------------------------------------------------
# CLI

def test_serve_cli_main(tmp_path):
    from nbodykit_tpu.serve.__main__ import main
    out = tmp_path / 'serve.json'
    with use_mesh(cpu_mesh(1)):
        rc = main(['--trace', '6', '--seed', '2', '--max-delay-ms',
                   '10', '--json', str(out)])
    assert rc == 0
    data = json.loads(out.read_text())
    assert data['submitted'] == 6
    assert data['lost'] == 0
    assert len(data['verdicts']) == data['resolved']


# ---------------------------------------------------------------------------
# regress / doctor posture

def test_serve_summary_reads_committed_round(tmp_path):
    """serve_summary must read the FULL parsed record from the round
    file (load_rounds flattens it to the headline keys, which lose the
    lost/retried/degraded ledger) and render a posture line."""
    from nbodykit_tpu.diagnostics.regress import (build_history,
                                                  render_regress,
                                                  serve_summary)
    rec = {'metric': 'servetrace_n12', 'unit': 's', 'value': 0.5,
           'requests': 12, 'rps': 24.0, 'p50_s': 0.3, 'p99_s': 0.5,
           'completed': 11, 'rejected': 1, 'evicted': 0, 'failed': 0,
           'lost': 0, 'retried': 1, 'degraded': 0, 'resumed': 0,
           'admit_degraded': 0,
           'faults_injected': {'serve.request.attempt': 13},
           'measured_at': '2026-08-05T00:00:00Z'}
    (tmp_path / 'BENCH_r01.json').write_text(json.dumps(
        {'n': 1, 'cmd': 'bench --serve-trace 12', 'rc': 0,
         'tail': json.dumps(rec), 'parsed': rec}))
    srv = serve_summary(str(tmp_path))
    assert srv is not None
    assert srv['round'] == 'BENCH_r01.json'
    assert srv['lost'] == 0 and srv['retried'] == 1
    assert srv['faults_injected'] == {'serve.request.attempt': 13}
    history = build_history(str(tmp_path), write=False)
    assert history['serve']['metric'] == 'servetrace_n12'
    text = render_regress(history)
    line = next(l for l in text.splitlines()
                if l.strip().startswith('serve:'))
    assert '12 req @ 24.0 rps' in line
    assert 'faults injected at serve.request.attempt' in line
    assert '0 lost' in line


def test_serve_summary_none_without_round(tmp_path):
    from nbodykit_tpu.diagnostics.regress import serve_summary
    assert serve_summary(str(tmp_path)) is None
