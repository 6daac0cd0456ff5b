"""The pair-counting cell's driver and yardstick, rehearsed on the CPU
(run by hand, like its neighbours):

    JAX_PLATFORMS=cpu python -m pytest perf/tests/test_paircount_cell.py -q -p no:cacheprovider

The ``lab_paircount`` driver end to end at 5e3 points, its checks
catching a float count, a count a pair off, a result that is not the
first's; the plain reference against itself; the five readers of the
cell's own layers on a synthetic reduction of one call (with the pair
counter's scopes, without them, and with too much under no scope);
``pair_flops`` by hand; and what the new cell reports.  No number from
here is a device number."""

import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

import numpy as np      # noqa: E402
import pytest           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perf import run                                    # noqa: E402
from perf.layers import (pair_grid_device_s, pair_rate_share,  # noqa: E402
                         pair_slots_per_pair, pair_tiles_device_s,
                         paircount_unscoped_share, peak_over_plan)
from perf.lib import manifest, scopes, work_paircount   # noqa: E402
from perf.reference import lab_paircount as plain       # noqa: E402

CELL = 'mr19_like.paircount'


def small_driver(seed=2 ** 31 + 11, n=5000):
    """The cell's density and bins, cut to what the CPU does in
    seconds."""
    from perf.drivers.lab_paircount import Driver
    files = manifest.cell_files(manifest.benchmark(), CELL)
    config = dict(files['config'], N=n,
                  BoxSize=420.0 * (n / 1.2e6) ** (1 / 3.0) * 2)
    traffic = files['traffic']
    traffic['oracle'].update(N=3000, typed_N=300)
    return Driver(config, traffic, 1, seed)


@pytest.fixture(scope='module')
def driven():
    d = small_driver()
    rec = d.setup()
    walls, results, errors, _ = run.window(d, 0.0, min_calls=2)
    assert len(walls) == 2 and not errors
    return d, rec, results


def test_paircount_driver_end_to_end(driven):
    d, rec, results = driven
    assert rec['oracle_outside'] == 0 < rec['oracle_bfloat16_outside']
    assert rec['npoints'] == 5000 and rec['oracle_pairs'] > 10000
    r = results[0]
    assert r['npairs'].dtype == np.int64 and r['npairs'].shape == (14,)
    failed, said = d.verify(results)
    assert failed == 0, said
    assert said['reference_outside'] == 0 and said['reference_s'] > 0
    assert said['pairs'] == int(r['npairs'].sum())
    assert d.reference() is d.reference()       # made once


def test_paircount_verify_catches_what_moved(driven):
    d, _, results = driven
    first = results[0]
    # a float count, however right its values
    failed, said = d.verify([dict(first, npairs=first['npairs'] * 1.0)])
    assert failed == 1 and 'integer type' in said['why_failed'][0]
    # one pair moved across an edge, both ways counted
    moved = first['npairs'].copy()
    moved[-1] -= 2
    moved[-2] += 2
    lo, hi, _ = d.reference()
    if (hi - lo)[-2] < 2:       # the bracket has no room for it
        failed, said = d.verify([dict(first, npairs=moved,
                                      wnpairs=moved * 1.0)])
        assert failed == 1 and 'bracket' in said['why_failed'][0]
    odd = first['npairs'].copy()
    odd[-1] += 1
    failed, said = d.verify([dict(first, npairs=odd)])
    assert failed == 1 and 'odd count' in said['why_failed'][0]
    failed, said = d.verify([dict(first, wnpairs=first['wnpairs'] * 1.01)])
    assert failed == 1 and 'wnpairs' in said['why_failed'][0]
    thin = dict(first, npairs=first['npairs'] // 4 * 2)
    failed, said = d.verify([dict(thin, wnpairs=thin['npairs'] * 1.0)])
    assert failed == 1 and 'sigma' in said['why_failed'][0]
    later = dict(results[1], npairs=results[1]['npairs'] + 2)
    failed, said = d.verify([first, later])
    assert failed == 1 and 'differs from the first' in said['why_failed'][0]


def test_paircount_oracle_refuses_a_float_count(monkeypatch):
    # what the parent's program returns: the oracle's first check, on
    # its smallest call
    d = small_driver(seed=5)
    count = d.count
    calls = []

    def floats(cat, edges, boxsize):
        calls.append(cat.size)
        out = count(cat, edges, boxsize)
        return dict(out, npairs=out['npairs'].astype('f4'))
    monkeypatch.setattr(d, 'count', floats)
    with pytest.raises(AssertionError, match='not an integer type'):
        d.oracle()
    assert calls == [300]


def test_plain_reference_two_ways_and_its_bracket():
    rng = np.random.RandomState(3)
    pos = rng.uniform(0, 50.0, (1500, 3)).astype('f4')
    edges = np.logspace(-1, 1, 9)
    radii = plain.bracket_radii(edges, 1e-6)
    every, low = plain.brute_cumulative(
        pos, 50.0, radii, quantize=plain.round_to_bfloat16)
    assert np.array_equal(every, plain.tree_cumulative(pos, 50.0, radii))
    assert not np.array_equal(every, low)
    lo, hi = plain.bracket(every)
    assert lo[0] <= 0 <= hi[0] and np.all(lo <= hi)
    exact = plain.brute_cumulative(pos, 50.0, edges)
    assert np.all(lo <= exact - exact[0]) and np.all(exact - exact[0] <= hi)
    x = np.array([1.0, 1.00390625, 420.3, 0.1, -3.3])
    assert plain.round_to_bfloat16(x).tolist() == [
        1.0, 1.0, 420.0, 0.10009765625, -3.296875]
    mean = plain.shell_means(1500, 50.0, edges)
    assert abs(np.diff(exact)[-1] / mean[-1] - 1) < 0.05


# --------------------------------------------------------------------------
# the readers, on one call as scopes.reduce sees it

def trace(named=True, unscoped_ms=5):
    """Two calls of 1 s.  Each launches one program from under the
    host's ``nbk.paircount.run`` / ``nbk.paircount.tiles``: 20 ms of
    ops that name ``paircount.grid``, 800 ms that name
    ``paircount.tiles``, and ``unscoped_ms`` more launched under the
    root alone.  Without ``named`` neither the host nor the program
    names a ``paircount.`` scope (the parent)."""
    ms = 1e6
    line, ops, modules = [], [], []

    def path(s):
        return 'jit(count)/nbk.paircount.%s/op' % s if named else None

    for k in range(2):
        t0 = k * 1000 * ms
        line.append(('perf.call', t0, 1000 * ms, None, None, None))
        if named:
            line.append(('nbk.paircount.run', t0 + ms, 990 * ms,
                         None, None, None))
            line.append(('nbk.paircount.tiles', t0 + 2 * ms, 825 * ms,
                         None, None, None))
        line.append(('launch', t0 + 3 * ms, 1, 2 * k + 1,
                     'p:%d' % (2 * k + 1), None))
        at = t0 + 3 * ms + 10
        modules.append(('jit_count', at, 820 * ms, 2 * k + 1))
        ops.append(('sort', at, 20 * ms, path('grid')))
        ops.append(('while', at + 20 * ms, 800 * ms, path('tiles')))
        line.append(('launch', t0 + 830 * ms, 1, 2 * k + 2,
                     'p:%d' % (2 * k + 2), None))
        modules.append(('jit_copy', t0 + 830 * ms + 10, unscoped_ms * ms,
                        2 * k + 2))
        ops.append(('copy', t0 + 830 * ms + 10, unscoped_ms * ms, None))
    return {'device': 0, 'ops': ops, 'modules': modules,
            'host': {'python3#0': line}}


def ctx_of(tmp_path, monkeypatch, tr):
    (tmp_path / 'scopes.json').unlink(missing_ok=True)
    monkeypatch.setattr(scopes, '_of_path',
                        lambda path, ncalls: scopes.reduce(tr, ncalls))
    monkeypatch.setattr(scopes.xplane, 'find_xplane', lambda d: 'x.pb')
    files = manifest.cell_files(manifest.benchmark(), CELL)
    spans = [{'name': 'paircount.tiles', 'dur': 0.9,
              'attrs': {'slots': 9 * n, 'pairs': n}} for n in (100, 200)]
    return {'outdir': str(tmp_path), 'device_kind': 'TPU v5 lite',
            'chips': 1, 'config': files['config'], 'cell': files['cell'],
            'xplane': {'ncalls': 2}, 'spans': spans, 'peak_bytes': 1e8}


def test_paircount_readers_on_one_call(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, trace())
    busy = 0.020 + 0.800 + 0.005
    assert scopes.of_run(ctx)['busy_s'] == pytest.approx(busy)
    assert pair_grid_device_s.read(ctx) == pytest.approx(0.020)
    assert pair_tiles_device_s.read(ctx) == pytest.approx(0.800)
    assert paircount_unscoped_share.read(ctx) == pytest.approx(
        100 * 0.005 / busy)
    # the benchmark's own guard knows no paircount. scope
    assert scopes.unscoped_share(ctx) == pytest.approx(100.0)
    flops = 8 * 1.2e6 ** 2 * 4 / 3 * np.pi * 25.0 ** 3 / 420.0 ** 3
    assert pair_rate_share.read(ctx) == pytest.approx(
        100 * flops / 0.800 / 197e12)
    assert 0 < pair_rate_share.read(ctx) < 100
    assert pair_slots_per_pair.read(ctx) == pytest.approx(9.0)


def test_paircount_readers_on_the_parent(tmp_path, monkeypatch):
    # a program from before this PR names no paircount. scope and
    # writes no such span: the readers say nothing and raise nothing
    ctx = dict(ctx_of(tmp_path, monkeypatch, trace(named=False)),
               spans=[{'name': 'paint', 'dur': 0.1, 'attrs': {}}])
    readers = (pair_grid_device_s, pair_tiles_device_s, pair_rate_share,
               pair_slots_per_pair, paircount_unscoped_share)
    for reader in readers:
        assert reader.read(ctx) is None
    for blank in (dict(ctx, outdir=None), dict(ctx, spans=None)):
        for reader in readers:
            assert reader.read(blank) is None


def test_pair_rate_share_withheld_above_the_unscoped_limit(
        tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, trace(unscoped_ms=150))
    assert paircount_unscoped_share.read(ctx) > scopes.UNSCOPED_MAX
    assert pair_tiles_device_s.read(ctx) == pytest.approx(0.800)
    assert pair_rate_share.read(ctx) is None


def test_pair_flops_by_hand():
    # 1.2e6^2 x the sphere of 25 over the box of 420: 1.272e9 ordered
    # pairs, eight flops each
    pairs = 1.44e12 * (4 / 3 * np.pi * 15625) / 74088000
    assert pairs == pytest.approx(1.2721e9, rel=1e-4)
    assert work_paircount.pair_flops(1200000, 420.0, 25.0) \
        == pytest.approx(8 * pairs)
    assert work_paircount.pair_flops(10, 1.0, 0.0) == 0


def test_the_new_cell_reports_its_metrics():
    bench = manifest.benchmark()
    cell = manifest.cell_files(bench, CELL)
    assert cell['cell']['chips'] == 1 and cell['config']['reduced'] == {}
    assert len(cell['config']['edges']) == cell['config']['nbins'] + 1
    assert np.allclose(cell['config']['edges'],
                       np.logspace(-1, np.log10(25), 15), rtol=1e-15)
    names = [m['name'] for m in cell['per_layer']]
    assert names == ['device_idle_share', 'device_busy_s',
                     'launches_per_call', 'compile_s_in_window',
                     'pair_grid_device_s', 'pair_tiles_device_s',
                     'pair_rate_share', 'pair_slots_per_pair',
                     'paircount_unscoped_share']
    assert [m['name'] for m in cell['end_to_end']] == [
        'call_s', 'peak_hbm_gb', 'setup_s']
    # the plan prices a mesh: the four cells that have one, as before
    old = [w['name'] for w in bench['workloads'] if w['name'] != CELL]
    plan = [m for m in bench['per_layer'] if m['name'] == 'peak_over_plan']
    assert plan[0]['workloads'] == old
    # and no other cell gained a metric
    for name in old:
        assert not any(m['name'].startswith('pair')
                       for m in manifest.cell_files(bench, name)['per_layer'])
