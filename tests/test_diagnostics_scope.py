"""Tests for ``diagnostics.scope``: one library layer on the JSONL
trace, on the profiler's host line and in the HLO op names.

With the ``diagnostics`` option off it opens no file and allocates no
span; with it on its record equals ``span``'s; under a jax trace the
lowered program carries ``nbk.<layer>`` in its op names and is
byte-identical, debug info aside, to the program lowered with
``scope`` patched out."""

import os
import re
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nbodykit_tpu
from nbodykit_tpu import diagnostics
from nbodykit_tpu.diagnostics import (NULL_SPAN, REGISTRY, read_trace,
                                      scope, span)
from nbodykit_tpu.diagnostics import trace as trace_mod


@pytest.fixture(autouse=True)
def _clean_registry():
    REGISTRY.reset()
    yield
    REGISTRY.reset()
    diagnostics.configure(None)


def _patch_scope_out(m):
    """``scope`` as a no-op, wherever it was imported to."""
    m.setattr(trace_mod._Scope, '__enter__', lambda self: self)
    m.setattr(trace_mod._Scope, '__exit__', lambda self, *exc: False)


def _spans(path):
    records, bad = read_trace(path)
    assert bad == 0
    return [r for r in records if r.get('t') == 'span']


def _shape(spans):
    """What two traces must share: names, nesting, attributes."""
    by_id = {s['id']: s['name'] for s in spans}
    return sorted((s['name'], s['depth'], by_id.get(s['par']),
                   s['ok'], repr(s.get('attrs'))) for s in spans)


# ---------------------------------------------------------------------------
# the primitive

def test_scope_off_opens_no_file_and_allocates_no_span(tmp_path,
                                                       monkeypatch):
    made = []
    real = trace_mod._Span.__init__
    monkeypatch.setattr(
        trace_mod._Span, '__init__',
        lambda self, *a: (made.append(a), real(self, *a))[1])
    assert diagnostics.current_tracer() is None
    with scope('paint', npart=3) as sc:
        with scope('exchange') as inner:
            assert inner.span_id == 0
        assert sc.set(more=1) is sc
        assert sc.done(7) == 7
    assert sc.span_id == 0
    jax.jit(lambda x: _scoped_double(x))(jnp.ones(3))
    assert made == []
    assert os.listdir(tmp_path) == []
    assert diagnostics.current_trace_file() is None


def _scoped_double(x):
    with scope('paint') as sc:
        assert sc.span_id == 0          # staging: never a JSONL span
        return sc.done(2 * x)


def test_scope_on_record_and_nesting_equal_spans(tmp_path):
    def run(ctx, root):
        diagnostics.configure(str(root))
        with ctx('fftpower.run', mode='2d'):
            with ctx('mesh.compute', nactions=1):
                with ctx('paint', npart=5) as sp:
                    sp.set(method='scatter')
            with pytest.raises(ValueError):
                with ctx('fftpower.binning'):
                    raise ValueError('boom')
        diagnostics.configure(None)
        return _spans(str(root))
    a = run(scope, tmp_path / 'scope')
    b = run(span, tmp_path / 'span')
    assert len(a) == 4 and _shape(a) == _shape(b)
    assert {s['name']: s['ok'] for s in a}['fftpower.binning'] is False


def test_scope_staging_writes_no_span_but_names_the_ops(tmp_path):
    diagnostics.configure(str(tmp_path))
    low = jax.jit(_scoped_double).lower(jnp.ones(3))
    assert 'nbk.paint/mul' in low.as_text(debug_info=True)
    with scope('eager') as sc:
        assert sc.span_id > 0
    diagnostics.configure(None)
    # (lowering with the tracer on leaves its own compile.trace /
    # compile.lower spans: the staged scope leaves none)
    assert [s['name'] for s in _spans(str(tmp_path))
            if not s['name'].startswith('compile.')] == ['eager']


def test_scope_done_waits_only_while_the_span_records(tmp_path,
                                                      monkeypatch):
    waited = []
    monkeypatch.setattr(jax, 'block_until_ready',
                        lambda x: waited.append(x) or x)
    with scope('fft.r2c') as sc:
        sc.done(1)
    assert waited == []
    diagnostics.configure(str(tmp_path))
    with scope('fft.r2c') as sc:
        sc.done(2)
    jax.jit(_scoped_double).lower(jnp.ones(3))
    ctx = diagnostics.new_request_context('r', fraction=0.0)
    with diagnostics.trace_scope(ctx):  # outside the exemplar sample
        with scope('fft.r2c') as sc:
            assert sc._span is NULL_SPAN
            sc.done(3)
    assert waited == [2]


def test_scope_lands_on_the_profilers_host_line_with_tracer_off(tmp_path):
    """Window (a) of the benchmark runs with the library's tracer off:
    the annotation must not hang on the ``diagnostics`` option."""
    from jax.profiler import ProfileData
    import glob
    assert diagnostics.current_tracer() is None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with scope('fftpower.run'):
            with scope('paint'):
                jax.block_until_ready(jnp.ones(8) * 2)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), 'plugins', 'profile',
                                   '*', '*.xplane.pb'))
    host = ProfileData.from_file(path).find_plane_with_name('/host:CPU')
    events = {ev.name: (ev.start_ns, ev.end_ns)
              for line in host.lines for ev in line.events
              if ev.name.startswith('nbk.')}
    assert set(events) == {'nbk.fftpower.run', 'nbk.paint'}
    (r0, r1), (p0, p1) = events['nbk.fftpower.run'], events['nbk.paint']
    assert r0 <= p0 and p1 <= r1


# ---------------------------------------------------------------------------
# the sites: the served program and the lab binning program

def _served_lowered(debug):
    from jax.sharding import Mesh
    from nbodykit_tpu.serve import AnalysisRequest
    from nbodykit_tpu.serve.scheduler import Program
    req = AnalysisRequest('FFTPower', nmesh=32, npart=1000)
    prog = Program(req, Mesh(np.array(jax.devices()[:1]), ('dev',)))
    return prog._fn._jitted.lower(jnp.zeros((1,), jnp.uint32)).as_text(
        debug_info=debug)


def _lab_bin_lowered(debug, monkeypatch, trace_dir=None):
    """Run the lab FFTPower at 16^3 and lower the ``fftpower.binning``
    program it built, with the argument it was called on."""
    from nbodykit_tpu.algorithms import fftpower
    from nbodykit_tpu.source.catalog.uniform import UniformCatalog
    seen = {}

    def spy(fun, label=None, **kw):
        jitted = diagnostics.instrumented_jit(fun, label=label, **kw)

        def call(*args):
            seen['lowered'] = jitted._jitted.lower(*args).as_text(
                debug_info=debug)
            return jitted(*args)
        return call
    monkeypatch.setattr(fftpower, 'instrumented_jit', spy)
    # the program is kept per geometry: built here, under the spy and
    # under whatever the caller patched since its last call
    fftpower._binning_program.cache_clear()
    with nbodykit_tpu.set_options(diagnostics=trace_dir):
        cat = UniformCatalog(nbar=3e-3, BoxSize=32.0, seed=42)
        fftpower.FFTPower(cat.to_mesh(Nmesh=16, resampler='cic',
                                      compensated=True),
                          mode='2d', Nmu=5)
    return seen['lowered']


@pytest.fixture(scope='module')
def served_text():
    return _served_lowered(True)


@pytest.mark.parametrize('name', [
    'nbk.serve.program', 'nbk.paint', 'nbk.fft.r2c',
    'nbk.fftpower.transfer', 'nbk.fftpower.binning.digitize',
    'nbk.fftpower.binning.hist'])
def test_served_program_names_its_layers(served_text, name):
    paths = re.findall(r'loc\("(jit\([^"]*)"', served_text)
    mine = [p for p in paths if re.findall(r'nbk\.[\w.]+', p)[-1:]
            == [name]]
    assert mine, name
    # every layer sits under the program's root scope
    assert all('nbk.serve.program' in p for p in mine)


def test_served_program_leaves_little_unnamed(served_text):
    """The guard the benchmark's ``unscoped_device_share`` keeps on
    the chip, counted here in ops: what carries no layer is the
    realization and the normalisation, not a layer that lost its
    scope."""
    paths = re.findall(r'loc\("(jit\(program\)/[^"]*)"', served_text)
    layers = ('nbk.paint', 'nbk.fft.', 'nbk.fftpower.')
    bare = [p for p in paths if not any(n in p for n in layers)]
    assert len(bare) < 0.2 * len(paths)
    assert not [p for p in bare if re.search(r'scatter-add|fft$', p)]


@pytest.mark.parametrize('name', [
    'nbk.fftpower.binning', 'nbk.fftpower.binning.digitize',
    'nbk.fftpower.binning.hist'])
def test_lab_binning_program_names_its_children(monkeypatch, name):
    text = _lab_bin_lowered(True, monkeypatch)
    paths = re.findall(r'loc\("(jit\([^"]*)"', text)
    mine = [p for p in paths if re.findall(r'nbk\.[\w.]+', p)[-1:]
            == [name]]
    assert mine, name
    assert all(p.split('/')[1] == 'nbk.fftpower.binning' for p in mine)


def test_served_program_identical_without_scope(monkeypatch):
    named = _served_lowered(False)
    assert 'nbk.' not in named          # metadata only
    # the module's name is in the persistent cache's key, the scopes
    # are not: it is not the name (jit_single) of the days before them
    assert 'module @jit_program' in named
    with monkeypatch.context() as m:
        _patch_scope_out(m)
        assert 'nbk.' not in _served_lowered(True)
        bare = _served_lowered(False)
    assert named == bare


def test_lab_binning_program_identical_without_scope(monkeypatch):
    named = _lab_bin_lowered(False, monkeypatch)
    assert 'nbk.' not in named
    assert 'module @jit_binning' in named       # was jit__lambda_
    with monkeypatch.context() as m:
        _patch_scope_out(m)
        assert 'nbk.' not in _lab_bin_lowered(True, m)
        bare = _lab_bin_lowered(False, m)
    assert named == bare


def test_lab_call_trace_names_every_layer_once_synced(tmp_path,
                                                      monkeypatch):
    """The operator's JSONL trace of one eager call: the layer names
    of the table in docs/OBSERVABILITY.md, ``fftpower.transfer`` among
    them, nested under their parents; ``mesh.r2c`` / ``mesh.c2r`` are
    gone; ``fft.r2c`` ends before ``fftpower.binning`` begins."""
    _lab_bin_lowered(False, monkeypatch, trace_dir=str(tmp_path))
    spans = _spans(str(tmp_path))
    names = {s['name'] for s in spans}
    assert {'paint', 'fft.r2c', 'fftpower.transfer', 'fftpower.binning',
            'fftpower.run', 'mesh.compute'} <= names
    assert not names & {'mesh.r2c', 'mesh.c2r'}
    by_id = {s['id']: s['name'] for s in spans}
    parents = {}
    for s in spans:
        parents.setdefault(s['name'], set()).add(by_id.get(s['par']))
    assert parents['paint'] == {'mesh.compute'}
    assert parents['fft.r2c'] == {'mesh.compute'}
    # the compensation inside the action pipeline, |delta_k|^2 after it
    assert parents['fftpower.transfer'] == {'mesh.compute',
                                            'fftpower.run'}
    assert parents['fftpower.binning'] == {'fftpower.run'}
    end = {s['name']: s['ts'] + s['dur'] for s in spans}
    start = {s['name']: s['ts'] for s in spans}
    assert end['fft.r2c'] <= start['fftpower.binning'] + 1e-6


@pytest.mark.parametrize('on_mxu', [True, False], ids=['mxu', 'bincount'])
def test_binning_span_names_its_split(tmp_path, monkeypatch, on_mxu):
    """Where the sums are the MXU histogram's product, the
    ``fftpower.binning`` span says how ``ops.histogram.mxu_split`` cut
    the bin index between the product's two sides (``split``: rows of
    the A side, columns of the B side) and how many bf16 ``parts`` the
    streams are, and ``fftpower.binning.trace.split`` counts one a
    compiled program (``_binning_program`` keeps it: two calls, one);
    where they are a bincount, neither."""
    import nbodykit_tpu.utils
    from nbodykit_tpu.algorithms.fftpower import project_to_basis
    from nbodykit_tpu.base.mesh import Field
    from nbodykit_tpu.ops.histogram import mxu_split
    from nbodykit_tpu.pmesh import ParticleMesh
    monkeypatch.setattr(nbodykit_tpu.utils, 'is_mxu_backend',
                        lambda: on_mxu)
    pm = ParticleMesh(Nmesh=16, BoxSize=32.0, dtype='f4')
    rng = np.random.default_rng(36)
    y3d = Field(pm.r2c(jnp.asarray(rng.standard_normal((16,) * 3), 'f4')),
                pm, 'complex')
    dk = 2 * np.pi / 32.0
    edges = [np.arange(0.0, np.pi * 16 / 32.0 + dk / 2, dk),
             np.linspace(-1, 1, 6)]
    with nbodykit_tpu.set_options(diagnostics=str(tmp_path)):
        for _ in range(2):
            project_to_basis(y3d, edges)
    spans = [s for s in _spans(str(tmp_path))
             if s['name'] == 'fftpower.binning']
    assert len(spans) == 2
    snap = REGISTRY.snapshot()
    traced = snap.get('fftpower.binning.trace.split', {'value': 0})
    for s in spans:
        assert s['attrs']['nstreams'] == 5
        if on_mxu:
            # two bf16 parts a stream, one for the count's 1.0 and 2.0
            assert s['attrs']['parts'] == 9
            assert s['attrs']['split'] == list(
                mxu_split(len(edges[0]) + 1, len(edges[1]) + 1, 9))
        else:
            assert s['attrs']['split'] is None
            assert s['attrs']['parts'] is None
    assert traced['value'] == (1 if on_mxu else 0)


# ---------------------------------------------------------------------------
# the paint's engine: an attribute of the span, a counter per program

@pytest.mark.parametrize('nmesh, engine', [(32, 'tile'), (2, 'scatter')])
def test_paint_span_names_its_engine(tmp_path, nmesh, engine):
    """The default ``paint_method`` picks its engine from the block's
    shape (``pmesh.paint_engine``): the tile deposit wherever the
    block admits it, the scatter on a mesh narrower than the window.
    The ``paint`` span says which ran, and ``paint.trace.<engine>``
    counts one per compiled program, not one per call."""
    from nbodykit_tpu.pmesh import ParticleMesh
    pm = ParticleMesh(nmesh, float(nmesh), dtype='f4')
    rng = np.random.default_rng(nmesh)
    with nbodykit_tpu.set_options(diagnostics=str(tmp_path)):
        for seed in range(2):
            # a shape no other test paints, so the program is new
            pos = jnp.asarray(rng.uniform(0, nmesh, (1237, 3)), 'f4')
            pm.paint(pos, 1.0, resampler='tsc')
    paints = [s for s in _spans(str(tmp_path)) if s['name'] == 'paint']
    assert [s['attrs']['engine'] for s in paints] == [engine] * 2
    assert all(s['attrs']['method'] == 'mxu' for s in paints)
    snap = REGISTRY.snapshot()

    def value(name):
        return snap[name]['value'] if name in snap else 0

    other = 'scatter' if engine == 'tile' else 'tile'
    assert value('paint.trace.' + other) == 0
    if engine == 'tile':
        # two calls, one program (compile.paint.tile: a miss, a hit)
        assert value('paint.trace.tile') == 1
        assert value('paint.trace.tile_particles') == 1237
        assert value('compile.paint.tile.misses') == 1
        assert value('compile.paint.tile.hits') == 1
        assert value('paint.tile.buckets') == (4 + 1) * 4
        assert value('paint.tile.ck') == 256


# ---------------------------------------------------------------------------
# the host ledger (PR 37): every eager scope's self time, with no
# instrument on

from nbodykit_tpu.diagnostics import HOST_CALLS, fetch  # noqa: E402


def _last_call(root):
    rec = [r for r in HOST_CALLS.snapshot() if r['root'] == root]
    assert rec, 'no call record under %r' % root
    return rec[-1]


def _nested():
    with scope('t.root'):
        with scope('t.a'):
            with scope('t.b'):
                sum(range(2000))
        sum(range(2000))


def _repeated():
    with scope('t.root'):
        for _ in range(5):
            with scope('t.a'):
                sum(range(500))
            with scope('t.root'):       # the root's name again, inside
                sum(range(500))


def _misnested():
    root = scope('t.root')
    a, b = scope('t.a'), scope('t.b')
    root.__enter__()
    a.__enter__()
    b.__enter__()
    sum(range(2000))
    a.__exit__(None, None, None)        # before b: a generator's gc
    sum(range(2000))
    b.__exit__(None, None, None)        # late: off the ledger already
    root.__exit__(None, None, None)


@pytest.mark.parametrize('body,names', [
    (_nested, {'t.root', 't.a', 't.b'}),
    (_repeated, {'t.root', 't.a'}),
    (_misnested, {'t.root', 't.a', 't.b'})],
    ids=['nested', 'repeated', 'misnested'])
def test_ledger_parts_sum_to_the_roots_wall(body, names):
    """The parts are a partition of the host thread's time: they sum
    to the root's wall to the nanosecond, however the scopes nest, and
    they are kept with no tracer and no profiler on."""
    assert diagnostics.current_tracer() is None
    body()
    rec = _last_call('t.root')
    assert set(rec['self_s']) == names
    assert all(v >= 0 for v in rec['self_s'].values())
    assert sum(rec['self_s'].values()) == pytest.approx(
        rec['wall_s'], abs=2e-9)
    assert rec['syncs'] == 0 and rec['sync_wait_s'] == 0
    snap = REGISTRY.snapshot()
    for name in names:
        assert snap['host.%s.self_s' % name]['value'] == pytest.approx(
            rec['self_s'][name])
        assert snap['host.%s.n' % name]['value'] >= 1
    assert trace_mod._LEDGER.stack == []


def test_ledger_two_threads_keep_two_stacks():
    import threading
    inside = threading.Event()
    leave = threading.Event()

    def other():
        with scope('t.other'):
            with scope('t.a'):
                inside.set()
                leave.wait(5)

    th = threading.Thread(target=other)
    with scope('t.main'):
        th.start()
        assert inside.wait(5)
        # the other thread's open scopes are not this thread's
        assert trace_mod.open_scope() == 't.main'
        with scope('t.b'):
            pass
        leave.set()
        th.join()
    main, side = _last_call('t.main'), _last_call('t.other')
    assert set(main['self_s']) == {'t.main', 't.b'}
    assert set(side['self_s']) == {'t.other', 't.a'}
    for rec in (main, side):
        assert sum(rec['self_s'].values()) == pytest.approx(
            rec['wall_s'], abs=2e-9)


def test_ledger_records_nothing_and_never_syncs_while_staging(
        monkeypatch):
    waited = []
    monkeypatch.setattr(jax, 'block_until_ready',
                        lambda x: waited.append(x) or x)
    before = len(HOST_CALLS.snapshot())

    open_inside = []

    def body(x):
        with scope('t.staged') as sc:
            open_inside.append(trace_mod.open_scope())
            return sc.done(2 * x)

    jax.jit(body)(jnp.ones(3))
    assert waited == [] and open_inside == [None]
    assert len(HOST_CALLS.snapshot()) == before
    assert not [k for k in REGISTRY.snapshot() if 't.staged' in k]
    # eagerly the same scope is on the ledger, and still never syncs
    body(jnp.ones(3))
    assert open_inside == [None, 't.staged']
    assert waited == [] and _last_call('t.staged')['wall_s'] > 0


def test_scope_off_costs_a_bare_annotation_plus_a_stated_budget():
    """With the option off a scope is its ``TraceAnnotation``, two
    clock readings and a dict update: the median over 10,000 scopes
    inside one root stays within 10 us of the bare annotation's (the
    figure measured on an idle machine is in PERF.md, section 7: some
    3.5 us a scope, 2.6 of them the parent's)."""
    import statistics
    import time
    assert diagnostics.current_tracer() is None
    clock = time.perf_counter_ns

    def median_ns(enter):
        laps = []
        for _ in range(10000):
            t0 = clock()
            with enter():
                pass
            laps.append(clock() - t0)
        return statistics.median(laps)

    bare = median_ns(lambda: jax.profiler.TraceAnnotation('nbk.t.x'))
    with scope('t.root'):
        scoped = median_ns(lambda: scope('t.x'))
    rec = _last_call('t.root')
    assert REGISTRY.snapshot()['host.t.x.n']['value'] == 10000
    assert sum(rec['self_s'].values()) == pytest.approx(
        rec['wall_s'], abs=2e-9)
    assert scoped - bare < 10e3, (scoped, bare)


def test_fetch_returns_host_arrays_and_counts_one_sync():
    tree = {'a': jnp.arange(4.0), 'b': (jnp.ones(2), 3.0)}
    with scope('t.root'):
        got = fetch(tree, 't.tree')
    assert isinstance(got['a'], np.ndarray)
    assert isinstance(got['b'][0], np.ndarray) and got['b'][1] == 3.0
    np.testing.assert_array_equal(got['a'], np.arange(4.0))
    rec = _last_call('t.root')
    assert rec['syncs'] == 1
    assert rec['sync_wait_s'] == rec['self_s']['sync.t.tree'] > 0
    snap = REGISTRY.snapshot()
    assert snap['host.syncs']['value'] == 1
    assert snap['host.sync.t.tree.n']['value'] == 1
    # a fetch outside every scope is its own root
    assert float(fetch(jnp.float32(2.5), 't.alone')) == 2.5
    assert _last_call('sync.t.alone')['syncs'] == 1
    assert REGISTRY.snapshot()['host.syncs']['value'] == 2


def test_retrace_lands_on_the_scope_it_happened_under(tmp_path):
    """All three stages of a jit cache miss are charged to the
    innermost open scope of the compiling thread, and with the tracer
    on each is a span that names it."""
    diagnostics.install_compile_telemetry()
    diagnostics.configure(str(tmp_path))
    with scope('t.root'):
        with scope('t.quiet'):
            pass
        with scope('t.retrace'):
            # a new function object: traced, lowered and compiled anew
            jax.jit(lambda x: x * 3 + 1)(jnp.ones(7))
    diagnostics.configure(None)
    rec = _last_call('t.root')
    snap = REGISTRY.snapshot()
    assert rec['retrace_s'] > 0
    assert snap['host.t.retrace.retrace_s']['value'] == pytest.approx(
        rec['retrace_s'])
    assert 'host.t.quiet.retrace_s' not in snap
    assert 'host.t.root.retrace_s' not in snap
    # an attribution, not a part: the scope's self time holds it
    assert rec['self_s']['t.retrace'] >= 0.5 * rec['retrace_s']
    stages = [s for s in _spans(str(tmp_path))
              if s['name'] in ('compile.trace', 'compile.lower',
                               'compile.backend')]
    assert {s['name'] for s in stages} == {
        'compile.trace', 'compile.lower', 'compile.backend'}
    assert all(s['attrs']['scope'] == 't.retrace' for s in stages)
    assert sum(s['dur'] for s in stages) == pytest.approx(
        rec['retrace_s'], abs=1e-5 * len(stages))
    # the doctor reads ``compile.<label>`` spans as labelled jits that
    # missed their cache: the stage spans are no label's
    from nbodykit_tpu.diagnostics.__main__ import _compile_miss_labels
    assert not {'trace', 'lower', 'backend'} \
        & set(_compile_miss_labels(str(tmp_path)))


def test_ring_keeps_the_last_1024_roots_in_time_order(tmp_path):
    for i in range(1030):
        with scope('t.ring'):
            pass
    calls = HOST_CALLS.snapshot()
    assert len(calls) == 1024
    assert all(r['root'] == 't.ring' for r in calls)
    stamps = [r['t0_ns'] for r in calls]
    assert stamps == sorted(stamps)
    import time
    assert abs(stamps[-1] - time.time_ns()) < 60e9      # the wall clock
    assert set(calls[-1]) == {'root', 't0_ns', 'wall_s', 'self_s',
                              'syncs', 'sync_wait_s', 'retrace_s'}
    # the JSONL span keeps the same clock
    diagnostics.configure(str(tmp_path))
    with scope('t.ring'):
        pass
    diagnostics.configure(None)
    span_rec = _spans(str(tmp_path))[-1]
    assert abs(span_rec['t0_ns'] - HOST_CALLS.snapshot()[-1]['t0_ns']) \
        < 5e6
    assert span_rec['t0_ns'] == pytest.approx(span_rec['ts'] * 1e9,
                                              abs=1e3)


def _lab_call():
    from nbodykit_tpu.lab import FFTPower, UniformCatalog
    cat = UniformCatalog(nbar=2e-4, BoxSize=256.0, seed=11)
    return lambda: FFTPower(cat, mode='2d', Nmesh=64, kmin=0.001, Nmu=10)


def _survey_call():
    from nbodykit_tpu.lab import (ConvolvedFFTPower, FKPCatalog,
                                  UniformCatalog)
    data = UniformCatalog(nbar=1e-4, BoxSize=256.0, seed=12)
    randoms = UniformCatalog(nbar=1e-3, BoxSize=256.0, seed=13)
    for cat in (data, randoms):
        cat['NZ'] = jnp.full(cat.size, 1e-4, 'f4')
    mesh = FKPCatalog(data, randoms).to_mesh(Nmesh=32, resampler='tsc')
    return lambda: ConvolvedFFTPower(mesh, poles=[0, 2], dk=0.05)


def _pair_call():
    from nbodykit_tpu.lab import SimulationBoxPairCount, UniformCatalog
    cat = UniformCatalog(nbar=3e-4, BoxSize=256.0, seed=14)
    edges = np.logspace(0, np.log10(20.0), 9)
    return lambda: SimulationBoxPairCount('1d', cat, edges, BoxSize=256.0)


def _served_call():
    from nbodykit_tpu.serve import AnalysisRequest, AnalysisServer
    server = AnalysisServer(per_task=1, hbm_bytes=16e9)
    server.__enter__()
    seeds = iter(range(100, 200))

    def call():
        req = AnalysisRequest(algorithm='FFTPower', nmesh=32,
                              npart=20000, seed=next(seeds),
                              deadline_s=120.0)
        res = server.wait(server.submit(req), timeout=120.0)
        assert res.status == 'completed', res.to_dict()
    call.close = lambda: server.__exit__(None, None, None)
    return call


@pytest.mark.parametrize('make,root,syncs,calls', [
    (_lab_call, 'fftpower.run', 2, 7),
    (_survey_call, 'convpower.run', 10, 3),
    (_pair_call, 'paircount.run', 1, 3),
    (_served_call, 'serve.request', 1, 3)],
    ids=['lab', 'survey', 'paircount', 'served'])
def test_no_host_second_without_a_name(make, root, syncs, calls):
    """The five cells' calls on the CPU (32^3; the lab call 64^3): warm,
    the root's own self time (host code under no scope but the root) is
    under 5% of its wall, the parts sum to it, and every fetch is a
    ``sync.*``.  The lab call builds no program warm (PR 38): at 32^3
    it is 7-8 ms in a warm process, and the 0.30-0.43 ms of Python the
    root runs at any size (the ``los`` check, the source's cast, making
    its children's scopes) is 4-7% of that in every call; at 64^3 the
    call is the mesh's work again (17-23 ms, the root 2-3%).  It makes
    seven calls and the median of the last five is held to the 5%: one
    hiccup of a loaded machine is 5% of one such call.  The other three
    read their third call alone."""
    call = make()
    try:
        for _ in range(calls):
            call()
    finally:
        getattr(call, 'close', lambda: None)()
    recs = [r for r in HOST_CALLS.snapshot() if r['root'] == root]
    recs = recs[2 - calls:]                 # all but the first two
    assert len(recs) == calls - 2
    for rec in recs:
        assert sum(rec['self_s'].values()) == pytest.approx(
            rec['wall_s'], abs=2e-9)
        assert rec['syncs'] == syncs
        assert rec['sync_wait_s'] == pytest.approx(sum(
            v for k, v in rec['self_s'].items() if k.startswith('sync.')))
    shares = [rec['self_s'][root] / rec['wall_s'] for rec in recs]
    assert statistics.median(shares) < 0.05, recs
