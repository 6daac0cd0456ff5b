"""Tests for ``diagnostics.scope``: one library layer on the JSONL
trace, on the profiler's host line and in the HLO op names.

With the ``diagnostics`` option off it opens no file and allocates no
span; with it on its record equals ``span``'s; under a jax trace the
lowered program carries ``nbk.<layer>`` in its op names and is
byte-identical, debug info aside, to the program lowered with
``scope`` patched out."""

import os
import re

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
    assert [s['name'] for s in _spans(str(tmp_path))] == ['eager']


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
    compiled program (the program is traced anew each call, ROADMAP
    S9a: two calls, two); where they are a bincount, neither."""
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
    assert traced['value'] == (2 if on_mxu else 0)


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
