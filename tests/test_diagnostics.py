"""Tests for nbodykit_tpu.diagnostics: span nesting + exception
safety, disabled-mode overhead (no file I/O, no span objects), JSONL
replay of a killed run, metric registry semantics, report/export
round-trips, and the end-to-end acceptance run (FFTPower on the
8-device CPU mesh leaves paint/FFT/exchange/binning spans with
byte/throughput metrics)."""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import nbodykit_tpu
from nbodykit_tpu import diagnostics
from nbodykit_tpu.diagnostics import (NULL_SPAN, REGISTRY, counter,
                                      export_chrome_trace, gauge,
                                      histogram, read_trace, span)


@pytest.fixture(autouse=True)
def _clean_registry():
    """Metric registry + tracer reset between tests (the registry is
    process-wide by design; tests must not see each other's data)."""
    REGISTRY.reset()
    yield
    REGISTRY.reset()
    diagnostics.configure(None)


def _spans(path):
    records, bad = read_trace(path)
    return [r for r in records if r.get('t') == 'span'], bad


# ---------------------------------------------------------------------------
# tracer core

def test_disabled_mode_is_noop_singleton(tmp_path):
    # no tracer, no span objects, no file I/O
    assert diagnostics.current_tracer() is None
    assert span('a') is NULL_SPAN
    assert span('b', attr=1) is NULL_SPAN          # attrs don't allocate
    assert diagnostics.span_eager('c') is NULL_SPAN
    assert diagnostics.span_if(True, 'd') is NULL_SPAN
    with span('nested'):
        with span('inner'):
            pass
    assert os.listdir(tmp_path) == []              # nothing written
    assert diagnostics.current_trace_file() is None


def test_span_nesting_depth_and_parent(tmp_path):
    tr = diagnostics.configure(str(tmp_path))
    assert tr is not None
    with span('outer', phase='x'):
        with span('middle'):
            with span('inner'):
                pass
        with span('middle2'):
            pass
    diagnostics.configure(None)
    spans, bad = _spans(str(tmp_path))
    assert bad == 0
    by = {s['name']: s for s in spans}
    assert by['outer']['depth'] == 0
    assert by['middle']['depth'] == 1
    assert by['inner']['depth'] == 2
    assert by['inner']['par'] == by['middle']['id']
    assert by['middle']['par'] == by['outer']['id']
    assert by['middle2']['par'] == by['outer']['id']
    assert by['outer']['attrs'] == {'phase': 'x'}
    # children close before parents; durations nest
    assert by['outer']['dur'] >= by['middle']['dur'] >= by['inner']['dur']


def test_span_exception_safety(tmp_path):
    diagnostics.configure(str(tmp_path))
    with pytest.raises(ValueError, match='boom'):
        with span('will_fail'):
            raise ValueError('boom')
    # the tracer stack must be clean after the exception unwinds
    with span('after'):
        pass
    diagnostics.configure(None)
    spans, _ = _spans(str(tmp_path))
    by = {s['name']: s for s in spans}
    assert by['will_fail']['ok'] is False
    assert 'boom' in by['will_fail']['exc']
    assert by['after']['ok'] is True
    assert by['after']['depth'] == 0               # stack unwound


def test_span_set_attrs_and_decorator(tmp_path):
    diagnostics.configure(str(tmp_path))
    with span('s') as sp:
        sp.set(found=42)

    @diagnostics.traced('deco.span')
    def work(x):
        return x + 1

    assert work(1) == 2
    diagnostics.configure(None)
    spans, _ = _spans(str(tmp_path))
    by = {s['name']: s for s in spans}
    assert by['s']['attrs'] == {'found': 42}
    assert 'deco.span' in by


def test_replay_of_killed_run_truncated_line(tmp_path):
    diagnostics.configure(str(tmp_path))
    with span('complete1'):
        pass
    with span('complete2'):
        pass
    tf = diagnostics.current_trace_file()
    diagnostics.configure(None)
    # simulate a mid-line death: truncate the file inside its last line
    size = os.path.getsize(tf)
    with open(tf, 'r+b') as f:
        f.truncate(size - 7)
    spans, bad = _spans(tf)
    assert bad == 1                                # exactly the torn tail
    assert {s['name'] for s in spans} >= {'complete1'}
    # every surviving record is complete and well-formed
    for s in spans:
        assert 'dur' in s and 'ts' in s


def test_sigkill_leaves_completed_spans_readable(tmp_path):
    """A SIGKILLed process (no atexit, no flush-on-close) must leave
    every completed span on disk — the per-span fsync contract."""
    script = r"""
import os, sys
sys.path.insert(0, %r)
import nbodykit_tpu
from nbodykit_tpu import diagnostics
diagnostics.configure(%r)
with diagnostics.span('done1'):
    pass
with diagnostics.span('done2', n=7):
    pass
sp = diagnostics.span('inflight')
sp.__enter__()
os.kill(os.getpid(), 9)   # SIGKILL: no exit handlers run
""" % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
       str(tmp_path))
    proc = subprocess.run([sys.executable, '-c', script],
                          capture_output=True, timeout=300)
    assert proc.returncode == -signal.SIGKILL
    records, bad = read_trace(str(tmp_path))
    spans = [r for r in records if r.get('t') == 'span']
    begins = [r for r in records if r.get('t') == 'b']
    assert {s['name'] for s in spans} == {'done1', 'done2'}
    # the in-flight span's begin event is visible post-mortem
    assert 'inflight' in {b['name'] for b in begins}


def test_heartbeat_records_written(tmp_path, monkeypatch):
    """The background heartbeat leaves periodic hb records so a wedged
    or SIGKILLed worker is distinguishable post-mortem (analyze.py
    flags the gap)."""
    import time
    monkeypatch.setenv('NBKIT_DIAGNOSTICS_HEARTBEAT', '0.05')
    tr = diagnostics.configure(str(tmp_path))
    assert tr.heartbeat_s == 0.05
    deadline = time.time() + 5.0
    while time.time() < deadline:
        records, _ = read_trace(str(tmp_path))
        if sum(1 for r in records if r.get('t') == 'hb') >= 2:
            break
        time.sleep(0.05)
    diagnostics.configure(None)
    records, _ = read_trace(str(tmp_path))
    hbs = [r for r in records if r.get('t') == 'hb']
    assert len(hbs) >= 2
    assert all(r['pid'] == os.getpid() and r['iv'] == 0.05
               for r in hbs)
    meta = next(r for r in records if r.get('t') == 'meta')
    assert meta['heartbeat_s'] == 0.05


def test_heartbeat_disabled(tmp_path, monkeypatch):
    import time
    monkeypatch.setenv('NBKIT_DIAGNOSTICS_HEARTBEAT', '0')
    diagnostics.configure(str(tmp_path))
    with span('s'):
        time.sleep(0.05)
    diagnostics.configure(None)
    records, _ = read_trace(str(tmp_path))
    assert not any(r.get('t') == 'hb' for r in records)


def test_emit_span_retroactive(tmp_path):
    """Out-of-band completed spans (compile telemetry) are normal
    records to every reader."""
    tr = diagnostics.configure(str(tmp_path))
    tr.emit_span('compile.backend', 123.0, 0.25, {'src': 'test'})
    diagnostics.configure(None)
    spans, _ = _spans(str(tmp_path))
    rec = next(s for s in spans if s['name'] == 'compile.backend')
    assert rec['ts'] == 123.0 and rec['dur'] == 0.25
    assert rec['depth'] == 0 and rec['attrs'] == {'src': 'test'}


# ---------------------------------------------------------------------------
# metrics

def test_metric_registry_counter_gauge_histogram():
    counter('c').add(2)
    counter('c').add(3)
    gauge('g').set(5)
    gauge('g').set(2)
    histogram('h').observe(1.0)
    histogram('h').observe(3.0)
    snap = REGISTRY.snapshot()
    assert snap['c'] == {'type': 'counter', 'value': 5}
    assert snap['g'] == {'type': 'gauge', 'value': 2, 'max': 5, 'min': 2}
    assert snap['h']['count'] == 2 and snap['h']['mean'] == 2.0
    assert snap['h']['min'] == 1.0 and snap['h']['max'] == 3.0
    with pytest.raises(TypeError):
        gauge('c')                                 # type clash is loud


def test_metric_registry_reset_between_tests_a():
    # the pair (a, b) relies on the autouse fixture: each sees a
    # pristine registry no matter the execution order
    assert len(REGISTRY) == 0
    counter('leak').add(1)


def test_metric_registry_reset_between_tests_b():
    assert len(REGISTRY) == 0
    counter('leak').add(1)


def test_instrumented_jit_compile_telemetry(tmp_path):
    """instrumented_jit attributes compiles to a label: miss + first
    call wall + a compile.<label> span on the first call, a hit
    counter on re-use."""
    import jax.numpy as jnp
    f = diagnostics.instrumented_jit(lambda x: x + 1, label='t.addone')
    with nbodykit_tpu.set_options(diagnostics=str(tmp_path)):
        np.testing.assert_array_equal(
            np.asarray(f(jnp.zeros(4))), np.ones(4))
        f(jnp.zeros(4))                        # cached executable
    snap = REGISTRY.snapshot()
    assert snap['compile.t.addone.misses']['value'] == 1
    assert snap['compile.t.addone.hits']['value'] == 1
    assert snap['compile.t.addone.first_call_s']['count'] == 1
    spans, _ = _spans(str(tmp_path))
    comp = [s for s in spans if s['name'] == 'compile.t.addone']
    assert len(comp) == 1
    assert comp[0]['attrs'] == {'misses': 1}


def test_instrumented_jit_inside_outer_trace():
    """Under an outer jit the wrapper must pass straight through (no
    host-side bookkeeping while staging)."""
    import jax
    import jax.numpy as jnp
    inner = diagnostics.instrumented_jit(lambda x: x * 2,
                                         label='t.inner')

    @jax.jit
    def outer(x):
        return inner(x) + 1

    np.testing.assert_array_equal(np.asarray(outer(jnp.ones(3))),
                                  np.full(3, 3.0))
    snap = REGISTRY.snapshot()
    assert 'compile.t.inner.misses' not in snap
    assert 'compile.t.inner.hits' not in snap


# ---------------------------------------------------------------------------
# report + chrome export

def test_report_and_chrome_export(tmp_path):
    diagnostics.configure(str(tmp_path))
    with span('phase_one'):
        with span('sub'):
            pass
    counter('work.items').add(10)
    tr = diagnostics.current_tracer()
    paths = diagnostics.write_report(tracer=tr)
    chrome = export_chrome_trace(tr.path)
    diagnostics.configure(None)
    with open(paths[0]) as f:
        rep = json.load(f)
    assert rep['nspans'] == 2
    assert [p['name'] for p in rep['phases']] == ['phase_one']
    assert rep['spans']['sub']['count'] == 1
    assert rep['metrics']['work.items']['value'] == 10
    txt = open(paths[1]).read()
    assert 'phase_one' in txt and 'work.items' in txt
    with open(chrome) as f:
        ev = json.load(f)['traceEvents']
    assert {e['name'] for e in ev} == {'phase_one', 'sub'}
    assert all(e['ph'] == 'X' for e in ev)


def test_self_check_in_process(tmp_path):
    from nbodykit_tpu.diagnostics.__main__ import self_check
    assert self_check(str(tmp_path), verbose=False) == 0


# ---------------------------------------------------------------------------
# option plumbing + instrumented pipelines

def test_set_options_context_restores_disabled(tmp_path):
    with nbodykit_tpu.set_options(diagnostics=str(tmp_path)):
        assert diagnostics.enabled()
        with span('inside'):
            pass
    assert not diagnostics.enabled()
    assert span('outside') is NULL_SPAN
    spans, _ = _spans(str(tmp_path))
    assert {s['name'] for s in spans} == {'inside'}


def test_timer_routes_through_tracer(tmp_path):
    with nbodykit_tpu.set_options(diagnostics=str(tmp_path)):
        with nbodykit_tpu.timer('existing_phase'):
            pass
    spans, _ = _spans(str(tmp_path))
    assert {s['name'] for s in spans} == {'timer.existing_phase'}


def test_fft_chunk_spans_lowmem(tmp_path):
    """The eager lowmem FFT driver emits per-chunk spans, each with
    its own wall and start (the ``fft.chunk_wall_s`` histogram that
    repeated them went with PR 37)."""
    import jax.numpy as jnp
    from nbodykit_tpu.parallel.dfft import rfftn_single_lowmem
    x = jnp.zeros((16, 16, 16), jnp.float32)
    with nbodykit_tpu.set_options(diagnostics=str(tmp_path)):
        out = rfftn_single_lowmem([x], target=16 * 16 * 9 * 8 * 2)
    assert out.shape == (16, 16, 9)
    spans, _ = _spans(str(tmp_path))
    names = [s['name'] for s in spans]
    assert 'fft.lowmem.r2c' in names
    chunk_spans = [s for s in spans if s['name'] == 'fft.chunk']
    assert len(chunk_spans) >= 2
    # chunks nest under the lowmem span
    low = next(s for s in spans if s['name'] == 'fft.lowmem.r2c')
    assert all(c['par'] == low['id'] for c in chunk_spans)
    snap = REGISTRY.snapshot()
    assert snap['fft.chunks']['value'] == len(chunk_spans)
    assert 'fft.chunk_wall_s' not in snap
    assert all(c['dur'] >= 0 and c['t0_ns'] > 0 and
               c['attrs']['rows'] >= 1 for c in chunk_spans)
    assert sum(c['dur'] for c in chunk_spans) <= low['dur'] + 1e-3


def test_fftpower_acceptance_trace(tmp_path, cpu8):
    """ISSUE acceptance: a full FFTPower run on the 8-virtual-device
    CPU mesh produces a JSONL trace containing paint, FFT, exchange,
    and binning spans with byte/throughput metrics."""
    from nbodykit_tpu.parallel.runtime import use_mesh
    from nbodykit_tpu.source.catalog.uniform import UniformCatalog
    from nbodykit_tpu.algorithms.fftpower import FFTPower
    # a compile to attribute needs a cold cache: the binning program is
    # kept per geometry, and conftest.py empties that cache for each test
    with nbodykit_tpu.set_options(diagnostics=str(tmp_path)):
        with use_mesh(cpu8):
            cat = UniformCatalog(nbar=3e-3, BoxSize=32.0, seed=42)
            mesh = cat.to_mesh(Nmesh=16, resampler='cic')
            FFTPower(mesh, mode='2d', Nmu=5)
        snap = REGISTRY.snapshot()
    spans, bad = _spans(str(tmp_path))
    assert bad == 0
    names = {s['name'] for s in spans}
    assert {'paint', 'exchange', 'fft.r2c', 'fftpower.binning',
            'fftpower.run', 'mesh.compute'} <= names
    # byte + throughput metrics landed
    assert snap['exchange.bytes_sent']['value'] > 0
    assert snap['exchange.calls']['value'] >= 1
    # the paint's throughput is its span: the synced wall with the
    # particle count beside it; its host time is on the ledger with
    # no tracer needed (PR 37 took the paint.<method>.* histograms)
    paints = [s for s in spans if s['name'] == 'paint']
    assert paints and all(s['dur'] > 0 and s['attrs']['npart'] > 0
                          and s['attrs']['method'] == 'mxu'
                          for s in paints)
    assert not [k for k in snap if k.endswith('mpart_per_s')]
    assert snap['host.paint.n']['value'] == len(paints)
    assert snap['host.paint.self_s']['value'] > 0
    assert snap['host.syncs']['value'] >= 2
    # device watermarks were sampled for the 8 virtual devices
    assert snap['device.cpu:0.live_bytes']['max'] > 0
    # compile telemetry (ISSUE 2 acceptance): the binning program's
    # compile is attributed by label, and the jax.monitoring hook
    # timed the XLA compile stages
    assert snap['compile.fftpower.binning.misses']['value'] >= 1
    assert snap['compile.fftpower.binning.first_call_s']['count'] >= 1
    assert snap['xla.compile.backend_s']['count'] >= 1
    assert 'compile.fftpower.binning' in names
    # spans nest: the exchange happens inside the paint
    by = {s['name']: s for s in spans}
    assert by['exchange']['par'] == by['paint']['id']


def test_paint_results_identical_with_diagnostics(tmp_path, cpu8):
    """Tracing must not perturb numerics: same paint with and without
    diagnostics enabled."""
    import jax
    import jax.numpy as jnp
    from nbodykit_tpu.pmesh import ParticleMesh
    from nbodykit_tpu.parallel.runtime import use_mesh
    with use_mesh(cpu8):
        pm = ParticleMesh(Nmesh=16, BoxSize=10.0, dtype='f8')
        pos = jax.random.uniform(jax.random.key(3), (999, 3),
                                 jnp.float64, 0.0, 10.0)
        ref = np.asarray(pm.paint(pos, 1.0, resampler='cic'))
        with nbodykit_tpu.set_options(diagnostics=str(tmp_path)):
            traced = np.asarray(pm.paint(pos, 1.0, resampler='cic'))
    np.testing.assert_array_equal(ref, traced)
    spans, _ = _spans(str(tmp_path))
    assert 'paint' in {s['name'] for s in spans}
