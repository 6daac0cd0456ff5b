"""One table of defaults (ISSUE 29): ``_default_options`` is the only
place a default is written, ``'auto'`` is a value only where the code
decides from something it observes, and under default options every
read site takes the constant the table holds.
"""

import importlib

import numpy as np
import pytest

import jax.numpy as jnp

import nbodykit_tpu
from nbodykit_tpu import _default_options, _global_options

CHUNK = 1024 * 1024 * 16

# the options whose 'auto' used to ask the tuner's database
NO_AUTO = ('mesh_dtype', 'a2a_compress', 'paint_method',
           'paint_chunk_size', 'paint_deposit', 'paint_streams',
           'fft_chunk_bytes', 'fft_decomp', 'ingest_chunk_rows',
           'bspec_method', 'pairblock_tile')
# the ones the code answers itself: ops/radix.order_keys (the
# backend), memory_plan at admission, the environment
KEEP_AUTO = ('paint_order', 'ingest_cache_bytes', 'data_steal_grace_s')


@pytest.fixture(autouse=True)
def _clean_options():
    saved = _global_options.copy()
    yield
    _global_options.clear()
    _global_options.update(saved)


@pytest.mark.parametrize('option', NO_AUTO)
def test_auto_is_refused(option):
    before = _global_options.copy()
    default = repr(_default_options[option])
    with pytest.raises(ValueError, match=option) as e:
        nbodykit_tpu.set_options(**{option: 'auto'})
    assert default in str(e.value)
    with pytest.raises(ValueError, match=option) as e:
        with nbodykit_tpu.option_scope(**{option: 'auto'}):
            pass
    assert default in str(e.value)
    assert _global_options.copy() == before


def _paint_kernel_args(monkeypatch):
    """What ``_paint_impl`` hands ``_paint_kernel`` on one device."""
    from nbodykit_tpu import pmesh
    seen = []
    real = pmesh._paint_kernel

    def spy(*args):
        seen.append(args)
        return real(*args)
    monkeypatch.setattr(pmesh, '_paint_kernel', spy)
    pm = pmesh.ParticleMesh(Nmesh=8, BoxSize=8.0, dtype='f4')
    pm.paint(jnp.asarray(np.random.RandomState(0).uniform(
        0, 8.0, (64, 3)), jnp.float32))
    return dict(zip(('method', 'chunk', 'order', 'deposit', 'streams'),
                    seen[0]))


def _plan(**kw):
    from nbodykit_tpu.pmesh import memory_plan
    return memory_plan(64, 10 ** 8, hbm_bytes=16e9, **kw)


def _tile_rows(monkeypatch):
    """Rows per particle tile of ``pairblock_sum(tile=None)``."""
    from nbodykit_tpu.ops import pairblock
    seen = []
    real = pairblock._pairblock_tiles

    def spy(pos, w, kv, tile_p, tile_k):
        seen.append(tile_p)
        return real(pos, w, kv, tile_p, tile_k)
    monkeypatch.setattr(pairblock, '_pairblock_tiles', spy)
    rs = np.random.RandomState(1)
    pairblock.pairblock_sum(rs.uniform(0, 1, (1500, 3)),
                            np.ones(1500), np.eye(3))
    return seen[0]


def _uniform():
    from nbodykit_tpu.lab import UniformCatalog
    return UniformCatalog(nbar=2e-3, BoxSize=100.0, seed=1)


def _rung(n):
    """The ``was`` of the n-th rung of a fresh request-scoped ladder."""
    from nbodykit_tpu.resilience.supervise import scoped_ladder
    lad = scoped_ladder({})
    for _ in range(n):
        label, detail = lad.step()
    return label, detail['was']


def _dfft(name):
    from nbodykit_tpu.parallel import dfft
    return getattr(dfft, name)


def _chunk_rows():
    from nbodykit_tpu.ingest.stream import resolve_chunk_rows
    return resolve_chunk_rows()


def _bispectrum_method():
    from nbodykit_tpu.algorithms.bispectrum import Bispectrum
    return Bispectrum(_uniform(), nbins=2, Nmesh=16).attrs['method']


# (id, what reaches the read site under default options, the constant)
READ_SITES = [
    ('paint.method', lambda mp: _paint_kernel_args(mp)['method'],
     'mxu'),
    ('paint.chunk', lambda mp: _paint_kernel_args(mp)['chunk'], CHUNK),
    ('paint.order', lambda mp: _paint_kernel_args(mp)['order'], 'auto'),
    ('paint.deposit', lambda mp: _paint_kernel_args(mp)['deposit'],
     'xla'),
    ('paint.streams', lambda mp: _paint_kernel_args(mp)['streams'], 4),
    ('fft.chunk_bytes', lambda mp: _dfft('_fft_chunk_bytes')(),
     2 ** 31),
    ('fft.a2a_mode', lambda mp: _dfft('_a2a_mode')(), 'none'),
    ('fft.decomp4', lambda mp: _dfft('resolve_decomp')(4),
     ('slab', (2, 2))),
    ('fft.decomp8', lambda mp: _dfft('resolve_decomp')(8),
     ('slab', (2, 4))),
    ('ingest.chunk_rows', lambda mp: _chunk_rows(), 262144),
    ('to_mesh.dtype', lambda mp: _uniform().to_mesh(Nmesh=8).pm.dtype,
     np.dtype('f4')),
    ('bispectrum.method', lambda mp: _bispectrum_method(), 'fft'),
    ('bispectrum.tile', _tile_rows, 1024),
    ('plan.chunk',
     lambda mp: _plan(paint_method='scatter')['peak_bytes']
     == _plan(paint_method='scatter', paint_chunk=CHUNK)['peak_bytes']
     != _plan(paint_method='scatter',
              paint_chunk=CHUNK // 2)['peak_bytes'], True),
    ('plan.streams',
     lambda mp: _plan(paint_method='streams')['peak_bytes']
     == _plan(paint_method='streams', paint_streams=4)['peak_bytes']
     != _plan(paint_method='streams', paint_streams=2)['peak_bytes'],
     True),
    ('plan.tile',
     lambda mp: _plan(workload='bispectrum',
                      bspec_method='direct')['pairblock_tile'], 1024),
    ('ladder.fft_chunk_bytes', lambda mp: _rung(1),
     ('fft_chunk_bytes/2', 2 ** 31)),
    ('ladder.paint_chunk_size', lambda mp: _rung(2),
     ('paint_chunk_size/2', CHUNK)),
]


@pytest.mark.parametrize('site', READ_SITES, ids=[s[0] for s in READ_SITES])
def test_default_reaches_read_site(site, monkeypatch):
    _, read, constant = site
    assert read(monkeypatch) == constant


def test_one_table_of_defaults():
    assert len(_default_options) == 22
    assert 'paint_bucket_slack' not in _default_options
    assert 'tune_cache' not in _default_options
    assert 'exchange_slack' not in _default_options
    autos = sorted(k for k, v in _default_options.items()
                   if isinstance(v, str) and v == 'auto')
    assert autos == sorted(KEEP_AUTO)
    # where 'auto' stays, it is still a value
    with nbodykit_tpu.set_options(**{k: 'auto' for k in KEEP_AUTO}):
        pass
    # no second table: the tuner's FALLBACKS went with the tuner
    for mod in ('nbodykit_tpu.tune', 'nbodykit_tpu.tune.resolve'):
        with pytest.raises(ImportError):
            importlib.import_module(mod)
    assert not hasattr(nbodykit_tpu, 'FALLBACKS')


def test_exchange_capacity_refuses_auto(cpu8):
    from nbodykit_tpu.pmesh import ParticleMesh
    pm = ParticleMesh(Nmesh=16, BoxSize=16.0, dtype='f4', comm=cpu8)
    pos = jnp.asarray(np.random.RandomState(2).uniform(
        0, 16.0, (256, 3)), jnp.float32)
    with pytest.raises(ValueError, match='1.05'):
        pm.exchange_capacity(pos, slack='auto')
    assert pm.exchange_capacity(pos) == pm.exchange_capacity(
        pos, slack=1.05)
