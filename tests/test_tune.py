"""Tests for nbodykit_tpu.tune: cache roundtrip + atomicity,
nearest-shape-class fallback, deterministic trial plans,
infeasible-candidate handling via fault injection, and the 'auto'
resolution contract — cold cache falls back to today's defaults with
zero trial overhead, warm cache selects the measured winner (asserted
against the committed repo TUNE_CACHE.json on the 8-device CPU
mesh)."""

import json
import os
import time

import numpy as np
import pytest

import nbodykit_tpu
from nbodykit_tpu import _global_options, diagnostics
from nbodykit_tpu.diagnostics import REGISTRY
from nbodykit_tpu.resilience import reset_faults
from nbodykit_tpu.tune import (Candidate, SearchSpace, TuneCache,
                               cache_summary, class_coords,
                               class_distance, device_signature,
                               entry_key, plan_spaces,
                               reset_cache_memo, resolve_exchange_slack,
                               resolve_fft_chunk_bytes, resolve_paint,
                               resolve_paint_deposit, run_space,
                               shape_class, tuned_snapshot,
                               validate_cache)
from nbodykit_tpu.tune.space import (_paint_runner, default_spaces,
                                     paint_space)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMITTED = os.path.join(REPO, 'TUNE_CACHE.json')


@pytest.fixture(autouse=True)
def _clean_state():
    """Options, registry, fault counts and the cache mtime memo are
    process-wide; every test sees (and leaves) a pristine copy."""
    saved = _global_options.copy()
    REGISTRY.reset()
    reset_faults()
    reset_cache_memo()
    yield
    REGISTRY.reset()
    reset_faults()
    reset_cache_memo()
    diagnostics.configure(None)
    _global_options.clear()
    _global_options.update(saved)


def _counter(name):
    snap = REGISTRY.snapshot().get(name)
    return snap['value'] if snap else 0


def _entry(op='paint', sclass='mesh16-part1e3', winner=None,
           device_count=1, platform='cpu', device_kind='cpu',
           measured_at=None, **extra):
    if measured_at is None:
        # fresh by construction: a fixed date would age past the
        # 30-day staleness window
        measured_at = time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())
    return dict({
        'platform': platform, 'device_kind': device_kind,
        'device_count': device_count, 'op': op, 'shape_class': sclass,
        'dtype': 'float32', 'winner': winner, 'winner_name':
        next(iter(winner.values())) if winner else None,
        'trials': {}, 'infeasible': [], 'measured_at': measured_at,
    }, **extra)


# ---------------------------------------------------------------------------
# shape classes

def test_shape_class_buckets():
    assert shape_class(64, 10_000) == 'mesh64-part1e4'
    assert shape_class(100, 9e4) == 'mesh128-part1e5'
    assert shape_class(512) == 'mesh512'
    assert shape_class(npart=1e7) == 'part1e7'
    with pytest.raises(ValueError):
        shape_class()


def test_class_coords_and_distance():
    assert class_coords('mesh64-part1e4') == (6.0, 4.0)
    assert class_coords('mesh512') == (9.0, None)
    assert class_coords('part1e7') == (None, 7.0)
    assert class_coords('nonsense') is None
    assert class_distance('mesh64-part1e4', 'mesh64-part1e4') == 0.0
    assert class_distance('mesh64', 'mesh256') == 2.0
    # different axes are not comparable
    assert class_distance('mesh64', 'part1e4') is None
    assert class_distance('mesh64', 'mesh64-part1e4') is None


# ---------------------------------------------------------------------------
# cache roundtrip / atomicity / fallback

def test_cache_roundtrip_and_atomic_commit(tmp_path):
    path = str(tmp_path / 'TC.json')
    tc = TuneCache(path)
    assert tc.entries() == {}          # cold cache is just empty
    key = tc.put(_entry(winner={'paint_method': 'sort'}))
    # a fresh instance reads the committed file, exact lookup hits
    tc2 = TuneCache(path)
    entry, match = tc2.lookup('cpu', 'cpu', 1, 'paint',
                              'mesh16-part1e3', 'f4')
    assert match == 'exact'
    assert entry['winner'] == {'paint_method': 'sort'}
    assert entry_key(entry) == key
    # tmp+rename discipline: no tmp siblings survive the commit
    assert [f for f in os.listdir(tmp_path) if 'tmp' in f] == []
    # a second put merges (and overwrites same-key entries)
    tc2.put(_entry(sclass='mesh64-part1e4',
                   winner={'paint_method': 'scatter'}))
    tc2.put(_entry(winner={'paint_method': 'scatter'}))
    entries = TuneCache(path).entries()
    assert len(entries) == 2
    entry, match = TuneCache(path).lookup('cpu', 'cpu', 1, 'paint',
                                          'mesh16-part1e3', 'f4')
    assert entry['winner'] == {'paint_method': 'scatter'}
    assert validate_cache(path) == []


def test_cache_corrupt_file_is_empty_and_invalid(tmp_path):
    path = str(tmp_path / 'TC.json')
    with open(path, 'w') as f:
        f.write('{"entries": {"k": ')       # torn write
    assert TuneCache(path).entries() == {}
    assert validate_cache(path)             # non-empty problem list
    # a well-formed file with a mis-keyed entry is caught too
    good = _entry(winner={'paint_method': 'sort'})
    with open(path, 'w') as f:
        json.dump({'version': 1, 'entries': {'wrong|key': good}}, f)
    problems = validate_cache(path)
    assert any('does not match' in p for p in problems)


def test_cache_nearest_fallback(tmp_path):
    tc = TuneCache(str(tmp_path / 'TC.json'))
    tc.put(_entry(sclass='mesh64-part1e4',
                  winner={'paint_method': 'sort'}))
    tc.put(_entry(sclass='mesh1024-part1e8',
                  winner={'paint_method': 'scatter'}))
    # miss on the exact class -> nearest (log-space) same-sig entry
    entry, match = tc.lookup('cpu', 'cpu', 1, 'paint',
                             'mesh128-part1e5', 'f4')
    assert match == 'nearest'
    assert entry['winner'] == {'paint_method': 'sort'}
    # other platform / device kind never matches
    assert tc.lookup('tpu', 'v5e', 1, 'paint', 'mesh64-part1e4',
                     'f4') == (None, 'miss')
    # same-count entries are preferred over closer other-count ones
    tc.put(_entry(sclass='mesh128-part1e5', device_count=8,
                  winner={'paint_method': 'mxu'}))
    entry, match = tc.lookup('cpu', 'cpu', 1, 'paint',
                             'mesh128-part1e5', 'f4')
    assert entry['device_count'] == 1 and match == 'nearest'
    # ...but an other-count entry is still reachable when it is all
    # there is
    entry, match = tc.lookup('cpu', 'cpu', 8, 'paint',
                             'mesh128-part1e5', 'f4')
    assert entry['winner'] == {'paint_method': 'mxu'}
    assert match == 'exact'


def test_winnerless_entries_never_steer(tmp_path):
    tc = TuneCache(str(tmp_path / 'TC.json'))
    tc.put(_entry(winner=None, infeasible=['scatter', 'sort']))
    assert tc.lookup('cpu', 'cpu', 1, 'paint', 'mesh16-part1e3',
                     'f4') == (None, 'miss')


# ---------------------------------------------------------------------------
# trial plans + infeasible handling

def test_trial_plan_deterministic():
    spaces = default_spaces()
    pairs = [(spaces['paint'], {'nmesh': 64, 'npart': 10_000,
                                'dtype': 'f4', 'seed': 7}),
             (spaces['fft'], {'nmesh': 64, 'dtype': 'f4', 'seed': 7})]
    sig = ('cpu', 'cpu', 8)
    p1 = plan_spaces(pairs, reps=2, signature=sig)
    p2 = plan_spaces(pairs, reps=2, signature=sig)
    assert p1 == p2
    assert p1[0]['key'] == 'cpu|cpu|8|paint|mesh64-part1e4|float32'
    assert 'scatter' in p1[0]['candidates']
    assert 'sort' in p1[0]['candidates']
    # the ISSUE 8 kernel families compete deterministically: both
    # segsum orders, and every stream count memory_plan admits at
    # this shape (all of {2,4,8} at mesh64/1e4)
    for name in ('segsum-argsort', 'segsum-radix',
                 'streams2', 'streams4', 'streams8'):
        assert name in p1[0]['candidates']


def _tiny_paint_space():
    """A two-candidate paint space small enough for tier-1."""
    return SearchSpace(
        'paint', ('paint_method', 'paint_chunk_size'),
        lambda ctx: [Candidate('scatter', {'paint_method': 'scatter'}),
                     Candidate('sort', {'paint_method': 'sort'})],
        _paint_runner)


def test_run_space_commits_measured_winner(tmp_path):
    tc = TuneCache(str(tmp_path / 'TC.json'))
    ctx = {'nmesh': 16, 'npart': 400, 'dtype': 'f4', 'seed': 7}
    entry = run_space(_tiny_paint_space(), ctx, cache=tc, reps=1)
    assert entry['winner_name'] in ('scatter', 'sort')
    assert entry['winner']['paint_method'] == entry['winner_name']
    assert entry['infeasible'] == []
    for rec in entry['trials'].values():
        assert rec['wall_s'] > 0 and rec['reps'] == 1
    assert _counter('tune.trials') == 2
    # and it landed in the cache, resolvable at this signature
    sig = device_signature(count=1)
    got, match = tc.lookup(sig[0], sig[1], 1, 'paint',
                           'mesh16-part1e3', 'f4')
    assert match == 'exact' and got['winner_name'] == entry['winner_name']


def test_infeasible_candidate_via_fault_injection(tmp_path):
    """An injected RESOURCE_EXHAUSTED at the first trial attempt (the
    same spec `NBKIT_FAULTS` carries into detached workers) condemns
    that candidate only; the tune run survives and the other
    candidate wins."""
    tc = TuneCache(str(tmp_path / 'TC.json'))
    nbodykit_tpu.set_options(
        faults='tune.trial.attempt@1:resource_exhausted')
    ctx = {'nmesh': 16, 'npart': 400, 'dtype': 'f4', 'seed': 7}
    entry = run_space(_tiny_paint_space(), ctx, cache=tc, reps=1)
    assert entry['infeasible'] == ['scatter']
    assert entry['trials']['scatter']['infeasible'] == 'oom'
    assert 'RESOURCE_EXHAUSTED' in entry['trials']['scatter']['error']
    assert entry['winner_name'] == 'sort'
    assert _counter('tune.infeasible') == 1
    assert _counter('tune.trials') == 1


def test_all_infeasible_commits_winnerless_entry(tmp_path):
    tc = TuneCache(str(tmp_path / 'TC.json'))
    nbodykit_tpu.set_options(
        faults='tune.trial.attempt@1:internal,'
               'tune.trial.attempt@2:internal')
    ctx = {'nmesh': 16, 'npart': 400, 'dtype': 'f4', 'seed': 7}
    entry = run_space(_tiny_paint_space(), ctx, cache=tc, reps=1)
    assert entry['winner'] is None
    assert sorted(entry['infeasible']) == ['scatter', 'sort']
    # the committed winner-less entry is posture, not guidance
    assert tc.lookup('cpu', 'cpu', 1, 'paint', 'mesh16-part1e3',
                     'f4') == (None, 'miss')


# ---------------------------------------------------------------------------
# 'auto' resolution

def test_auto_cold_cache_zero_trials(tmp_path):
    import jax.numpy as jnp
    nbodykit_tpu.set_options(
        tune_cache=str(tmp_path / 'ABSENT.json'),
        paint_method='auto', fft_chunk_bytes='auto')
    cfg = resolve_paint(nmesh=16, npart=500, nproc=1)
    assert cfg['paint_method'] == 'scatter'
    assert cfg['source'] == 'default'
    # the new knob resolves to its concrete fallback on a cold cache
    assert cfg['paint_streams'] == 4
    assert resolve_fft_chunk_bytes(shape=(16, 16, 16)) == 2 ** 31
    # resolution NEVER runs trials: cold cache == today's defaults
    assert _counter('tune.trials') == 0
    # end to end: an eager paint under 'auto' matches explicit scatter
    from nbodykit_tpu.pmesh import ParticleMesh
    pm = ParticleMesh(Nmesh=16, BoxSize=100.0, dtype='f4')
    pos = jnp.asarray(np.random.RandomState(0).uniform(
        0, 100, (300, 3)).astype('f4'))
    auto = pm.paint(pos, 1.0)
    with nbodykit_tpu.set_options(paint_method='scatter'):
        explicit = pm.paint(pos, 1.0)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(explicit))
    assert _counter('tune.trials') == 0
    assert _counter('tune.resolve.miss') > 0


def test_auto_warm_cache_selects_winner(tmp_path):
    import jax.numpy as jnp
    path = str(tmp_path / 'TC.json')
    TuneCache(path).put(_entry(winner={'paint_method': 'sort'}))
    nbodykit_tpu.set_options(tune_cache=path, paint_method='auto')
    cfg = resolve_paint(nmesh=16, npart=1000, nproc=1)
    assert cfg['paint_method'] == 'sort'
    assert cfg['source'] == 'cache'
    assert _counter('tune.resolve.hit') == 1
    # the tuned kernel actually runs: the sort paint's trace counter
    # bumps when the 'auto' paint executes
    from nbodykit_tpu.pmesh import ParticleMesh
    pm = ParticleMesh(Nmesh=16, BoxSize=100.0, dtype='f4')
    pos = jnp.asarray(np.random.RandomState(1).uniform(
        0, 100, (1000, 3)).astype('f4'))
    before = _counter('paint.trace.sort')
    out = pm.paint(pos, 1.0)
    np.testing.assert_allclose(float(out.sum()), 1000.0, rtol=1e-4)
    assert _counter('paint.trace.sort') == before + 1


def test_auto_explicit_options_never_overridden(tmp_path):
    path = str(tmp_path / 'TC.json')
    TuneCache(path).put(_entry(winner={'paint_method': 'mxu',
                                       'paint_order': 'radix'}))
    nbodykit_tpu.set_options(tune_cache=path, paint_method='auto',
                             paint_order='argsort')
    cfg = resolve_paint(nmesh=16, npart=1000, nproc=1)
    assert cfg['paint_method'] == 'mxu'       # asked: from the cache
    assert cfg['paint_order'] == 'argsort'    # explicit: untouched
    # a fully explicit call never consults the cache at all
    nbodykit_tpu.set_options(paint_method='scatter',
                             paint_order='auto')
    REGISTRY.reset()
    cfg = resolve_paint(nmesh=16, npart=1000, nproc=1)
    assert cfg['source'] == 'explicit'
    assert _counter('tune.resolve.hit') == 0
    assert _counter('tune.resolve.miss') == 0


def test_auto_mxu_winner_keeps_traced_contract(tmp_path):
    """A cached mxu winner must not impose the traced-overflow
    contract on an 'auto' caller inside jit: the call falls back to
    scatter instead of raising; an EXPLICIT mxu still raises."""
    import jax
    import jax.numpy as jnp
    from nbodykit_tpu.pmesh import ParticleMesh
    path = str(tmp_path / 'TC.json')
    TuneCache(path).put(_entry(winner={'paint_method': 'mxu'}))
    nbodykit_tpu.set_options(tune_cache=path, paint_method='auto')
    pm = ParticleMesh(Nmesh=16, BoxSize=100.0, dtype='f4')
    pos = jnp.asarray(np.random.RandomState(2).uniform(
        0, 100, (1000, 3)).astype('f4'))
    out = jax.jit(lambda p: pm.paint(p, 1.0))(pos)
    np.testing.assert_allclose(float(out.sum()), 1000.0, rtol=1e-4)
    with nbodykit_tpu.set_options(paint_method='mxu'):
        with pytest.raises(ValueError, match='return_dropped'):
            jax.jit(lambda p: pm.paint(p, 1.0))(pos)


def test_fft_chunk_bytes_auto(tmp_path):
    from nbodykit_tpu.parallel.dfft import _fft_chunk_bytes
    path = str(tmp_path / 'TC.json')
    TuneCache(path).put(_entry(op='fft', sclass='mesh16',
                               winner={'fft_chunk_bytes': 1 << 20}))
    nbodykit_tpu.set_options(tune_cache=path, fft_chunk_bytes='auto')
    assert _fft_chunk_bytes((16, 16, 16), 'f4') == 1 << 20
    # complex dtypes key by their real base: the c2r path sees the
    # same winner
    assert _fft_chunk_bytes((16, 16, 9), np.dtype('c8')) == 1 << 20
    # an explicit integer bypasses the cache entirely
    with nbodykit_tpu.set_options(fft_chunk_bytes=123):
        assert _fft_chunk_bytes((16, 16, 16), 'f4') == 123


def test_ladder_halves_auto_resolved_value(tmp_path):
    from nbodykit_tpu.resilience import default_ladder
    nbodykit_tpu.set_options(
        tune_cache=str(tmp_path / 'ABSENT.json'),
        fft_chunk_bytes='auto')
    lad = default_ladder()
    label, detail = lad.step()
    assert label == 'fft_chunk_bytes/2'
    assert detail == {'fft_chunk_bytes': 2 ** 30, 'was': 2 ** 31}
    # the rung PINNED the option to a concrete int
    assert _global_options['fft_chunk_bytes'] == 2 ** 30


def test_exchange_slack_and_deposit_resolution(tmp_path):
    path = str(tmp_path / 'TC.json')
    tc = TuneCache(path)
    tc.put(_entry(op='exchange', sclass='part1e5',
                  winner={'exchange_slack': 2.0}))
    tc.put(_entry(winner={'paint_method': 'mxu',
                          'paint_deposit': 'pallas'}))
    nbodykit_tpu.set_options(tune_cache=path)
    assert resolve_exchange_slack(npart=100_000, nproc=1) == 2.0
    assert resolve_paint_deposit(nmesh=16, npart=1000) == 'pallas'
    # cold fallbacks
    nbodykit_tpu.set_options(tune_cache=str(tmp_path / 'NONE.json'))
    reset_cache_memo()
    assert resolve_exchange_slack(npart=100_000, nproc=1) == 1.05
    assert resolve_paint_deposit(nmesh=16, npart=1000) == 'xla'


def test_tuned_snapshot_records_sources(tmp_path):
    nbodykit_tpu.set_options(
        tune_cache=str(tmp_path / 'ABSENT.json'),
        paint_method='auto', fft_chunk_bytes='auto')
    snap = tuned_snapshot(nmesh=16, npart=500, nproc=1)
    assert snap['paint_method'] == 'scatter'
    assert snap['paint_source'] == 'default'
    assert snap['fft_chunk_bytes'] == 2 ** 31
    assert snap['fft_source'] == 'auto'
    nbodykit_tpu.set_options(paint_method='scatter',
                             fft_chunk_bytes=2 ** 28)
    snap = tuned_snapshot(nmesh=16, npart=500, nproc=1)
    assert snap['paint_source'] == 'explicit'
    assert snap['fft_source'] == 'explicit'
    assert snap['fft_chunk_bytes'] == 2 ** 28


# ---------------------------------------------------------------------------
# posture: doctor / regression tracking

def test_tune_summary_in_bench_history(tmp_path):
    from nbodykit_tpu.diagnostics.regress import (build_history,
                                                  tune_summary)
    root = str(tmp_path)
    assert tune_summary(root) is None       # no cache file -> None
    tc = TuneCache(os.path.join(root, 'TUNE_CACHE.json'))
    tc.put(_entry(winner={'paint_method': 'sort'},
                  measured_at='2020-01-01T00:00:00Z'))   # stale
    tc.put(_entry(op='fft', sclass='mesh64', platform='tpu',
                  device_kind='v5e',
                  winner={'fft_chunk_bytes': 1 << 26},
                  infeasible=['chunk2g']))
    summary = tune_summary(root)
    assert summary['entries'] == 2
    assert summary['stale'] == 1
    assert summary['infeasible'] == 1
    assert summary['platforms'] == ['cpu/cpu', 'tpu/v5e']
    history = build_history(root, write=False)
    assert history['tune']['entries'] == 2
    # a malformed cache is reported, not raised
    with open(os.path.join(root, 'TUNE_CACHE.json'), 'w') as f:
        f.write('not json')
    reset_cache_memo()
    assert 'error' in tune_summary(root)


# ---------------------------------------------------------------------------
# CLI

def test_cli_dry_run_is_deterministic(tmp_path, capsys):
    from nbodykit_tpu.tune.__main__ import main
    args = ['--dry-run', '--devices', '8',
            '--cache', str(tmp_path / 'TC.json')]
    assert main(args) == 0
    out1 = json.loads(capsys.readouterr().out)
    assert main(args) == 0
    out2 = json.loads(capsys.readouterr().out)
    assert out1 == out2
    ops = [p['op'] for p in out1['plan']]
    assert ops.count('paint') == 2 and 'fft' in ops
    assert all('|' in p['key'] for p in out1['plan'])
    # every paint plan carries the stream/segsum families (the CLI's
    # default shapes are small enough for all stream counts to fit)
    for p in out1['plan']:
        if p['op'] == 'paint':
            for name in ('segsum-argsort', 'segsum-radix',
                         'streams2', 'streams4', 'streams8'):
                assert name in p['candidates']
    # dry-run touches nothing: no cache file, no trials
    assert not os.path.exists(str(tmp_path / 'TC.json'))
    assert _counter('tune.trials') == 0


def test_cli_validate_gate(tmp_path, capsys):
    from nbodykit_tpu.tune.__main__ import main
    absent = str(tmp_path / 'ABSENT.json')
    assert main(['--validate', '--cache', absent]) == 0
    capsys.readouterr()
    bad = str(tmp_path / 'BAD.json')
    with open(bad, 'w') as f:
        f.write('{"entries": []}')
    assert main(['--validate', '--cache', bad]) == 1


# ---------------------------------------------------------------------------
# acceptance: the committed database

def test_committed_cache_is_valid():
    assert os.path.exists(COMMITTED), \
        'the committed TUNE_CACHE.json is part of this PR'
    assert validate_cache(COMMITTED) == []
    summary = cache_summary(COMMITTED)
    paint_classes = {
        e['shape_class'] for e in TuneCache(COMMITTED).entries().values()
        if e['op'] == 'paint' and e['platform'] == 'cpu'
        and e['device_count'] == 8 and e['winner']}
    assert len(paint_classes) >= 2, \
        'committed cache must cover paint at two shape-classes on ' \
        'the 8-device CPU mesh: %s' % summary


def test_committed_cache_resolves_auto_on_cpu8(cpu8):
    """The acceptance path: on the 8-device CPU mesh,
    set_options(paint_method='auto') resolves through the committed
    TUNE_CACHE.json to the measured winner."""
    from nbodykit_tpu.parallel.runtime import use_mesh
    entries = [e for e in TuneCache(COMMITTED).entries().values()
               if e['op'] == 'paint' and e['platform'] == 'cpu'
               and e['device_count'] == 8 and e['winner']]
    assert entries
    entry = entries[0]
    ctx = entry['context']
    nbodykit_tpu.set_options(tune_cache=COMMITTED,
                             paint_method='auto')
    with use_mesh(cpu8):
        cfg = resolve_paint(nmesh=ctx['nmesh'], npart=ctx['npart'],
                            nproc=8)
    assert cfg['source'] == 'cache'
    assert cfg['paint_method'] == \
        entry['winner']['paint_method']
    assert _counter('tune.resolve.hit') == 1
