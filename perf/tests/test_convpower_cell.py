"""The survey cell's driver and yardstick, rehearsed on the CPU (run
by hand, like its neighbours):

    JAX_PLATFORMS=cpu python -m pytest perf/tests/test_convpower_cell.py -q -p no:cacheprovider

The ``lab_convpower`` driver end to end at 32^3, its oracle catching a
harmonic with the wrong sign, a timed result that is not the first's,
the five readers of the cell's own layers on a synthetic reduction of
one survey call (with the survey path's scopes, without them, and with
too much under no scope), ``ylm_bytes`` and ``nfft`` by hand, and what
the new cell reports.  No number from here is a device number."""

import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

import pytest           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perf import run                                    # noqa: E402
from perf.layers import (convpower_fft_roofline,        # noqa: E402
                         convpower_unscoped_share, tsc_paint_hbm_share,
                         ylm_device_s, ylm_hbm_share)
from perf.lib import manifest, scopes, work, work_convpower  # noqa: E402

CELL, LAB = 'boss_like_n512.convpower', 'boss_like_n512.lab'


def small_driver(seed=2 ** 31 + 11):
    """The cell's sizes cut to what the CPU does in seconds."""
    from perf.drivers.lab_convpower import Driver
    files = manifest.cell_files(manifest.benchmark(), CELL)
    config = dict(files['config'], BoxSize=500.0, Nmesh=32, N=100000,
                  min_modes=500)
    traffic = files['traffic']
    traffic['oracle'].update(Nmesh=32, ndata=5000)
    traffic['call']['dk'] = 0.02
    return Driver(config, traffic, 1, seed)


@pytest.fixture(scope='module')
def driven():
    d = small_driver()
    rec = d.setup()
    walls, results, errors, _ = run.window(d, 0.0, min_calls=2)
    assert len(walls) == 2 and not errors
    return d, rec, results


def test_convpower_driver_end_to_end(driven):
    d, rec, results = driven
    # f4 against f8, and where bfloat16 fields would land
    assert rec['oracle_max_err'] < 1e-5 < 1e-4 < rec['oracle_bfloat16_err']
    assert rec['oracle_p2_over_p0'] > 0.5
    assert (rec['ndata'], rec['nrandoms']) == (100000, 1000000)
    r = results[0]
    assert r['power_0'].shape == r['power_4'].shape == r['modes'].shape
    assert r['BoxSize'].tolist() == [510.0] * 3
    failed, said = d.verify(results)
    assert failed == 0, said
    assert abs(said['p0_over_shot_mean'] - 1) < 0.05
    assert said['p2_over_limit_worst'] < 1 > said['p4_over_limit_worst']
    # the timed call itself against the reference on its own particles
    assert said['reference_max_err'] < 1e-5 and said['reference_s'] > 0


def test_convpower_timed_result_is_held_to_the_reference(driven):
    # what the lattice, the catalog's sums and a flat shot noise let
    # through: A_4 zeroed, one harmonic's worth of P_2 lost, fields a
    # format too coarse
    from perf.reference.lab_convpower import round_to_bfloat16
    d, _, results = driven
    first = results[0]
    for fault in (dict(power_4=0 * first['power_4']),
                  dict(power_2=0.8 * first['power_2'])):
        failed, said = d.verify([dict(first, **fault)])
        assert failed == 1, said
        assert 'off the reference' in said['why_failed'][0]
    low = d.reference(quantize=round_to_bfloat16)
    assert d.worst(low, d.reference()) > d.timed_rtol
    with pytest.raises(AssertionError, match='off the reference'):
        d.check_reference(dict(first, **{
            k: low[k] for k in low if k.startswith('power_')}))
    assert d.reference() is d.reference()       # made once


def test_convpower_verify_catches_a_result_that_moved(driven):
    d, _, results = driven
    moved = dict(results[1], power_2=results[1]['power_2'] * (1 + 1e-6))
    failed, said = d.verify([results[0], moved])
    assert failed == 1 and 'differs from the first' in said['why_failed'][0]
    # and a first result off the shot noise, or off the lattice
    high = dict(results[0], power_0=results[0]['power_0'] * 1.2)
    failed, said = d.verify([high])
    assert failed == 1 and 'shot noise' in said['why_failed'][0]
    fewer = dict(results[0], modes=results[0]['modes'] - 1)
    failed, said = d.verify([fewer])
    assert failed == 1 and 'lattice' in said['why_failed'][0]
    tilted = dict(results[0], power_2=results[0]['power_2']
                  + 0.2 * results[0]['shotnoise'])
    failed, said = d.verify([tilted])
    assert failed == 1 and 'P_2' in said['why_failed'][0]


def test_convpower_oracle_catches_a_wrong_answer(monkeypatch):
    # one harmonic of ell = 2 with its sign turned in k space: the
    # system's A_2 then misses one m of five and more
    from nbodykit_tpu.algorithms.convpower import fkp
    real = fkp.get_real_Ylm

    def turned(l, m):
        Y = real(l, m)
        if (l, m) != (2, 1):
            return Y
        calls = []

        def flipped(x, y, z):
            calls.append(1)     # x space first, then k space
            return Y(x, y, z) * (1 if len(calls) % 2 else -1)
        return flipped
    monkeypatch.setattr(fkp, 'get_real_Ylm', turned)
    # the per-ell programs are cached (a program from before PR 32
    # builds them on every call and has nothing to clear)
    clear = getattr(getattr(fkp, '_ell_program', None), 'cache_clear',
                    lambda: None)
    clear()
    try:
        with pytest.raises(AssertionError, match='oracle: a multipole'):
            small_driver(seed=5).oracle()
    finally:
        clear()


# --------------------------------------------------------------------------
# the readers, on one survey call as scopes.reduce sees it

def host(name, s, d, rid=None, produces=None, consumes=None):
    return (name, float(s), float(d), rid, produces, consumes)


def trace(survey=True, unscoped_ms=10):
    """Two calls of 1 s.  Each launches, from the calling thread under
    ``nbk.convpower.run``: two paints (30 + 270 ms), the species'
    combine (5 ms under ``convpower.density``), a reduction (5 ms
    under ``convpower.stats``), three per-ell programs under the host's
    ``convpower.ylm`` whose ops name ``fft.r2c`` (20 ms a transform),
    ``convpower.ylm`` (10 ms a term with ell > 0) and
    ``fftpower.transfer`` (5 ms) themselves, three binnings of 30 ms,
    and ``unscoped_ms`` under the root alone.  Without ``survey`` the
    program names none of the ``convpower.`` scopes."""
    ms = 1e6
    line = []
    ops, modules = [], []
    rid = [0]

    def mark(name, t, dur):
        line.append(host('nbk.' + name, t - 1000, dur + 2000))

    def launch(t, pieces):
        rid[0] += 1
        line.append(host('launch', t, 1, rid[0], 'p:%d' % rid[0], None))
        total = sum(d for _, d, _ in pieces)
        modules.append(('jit_x', t + 10, total, rid[0]))
        at = t + 10
        for name, dur, path in pieces:
            ops.append((name, at, dur, path))
            at += dur

    def named(*scopes_):
        return 'jit(prog)/' + '/'.join('nbk.' + s for s in scopes_) + '/op'

    for k in range(2):
        t0 = k * 1000 * ms
        line.append(host('perf.call', t0, 1000 * ms))
        if survey:
            mark('convpower.run', t0 + 1 * ms, 990 * ms)
        t = t0 + 5 * ms
        if survey:
            mark('convpower.density', t - 5000, 310 * ms)
        for dur in (30, 270):
            mark('paint', t, dur * ms)
            launch(t, [('scatter', dur * ms, None)])
            t += dur * ms + 1000
        launch(t, [('combine', 5 * ms, None)])
        t = t0 + 320 * ms
        if survey:
            mark('convpower.stats', t, 5 * ms)
        launch(t, [('reduce', 5 * ms, None)])
        t += 10 * ms
        for ell in (0, 2, 4):
            pieces = []
            for m in range(2 * ell + 1):
                if ell:
                    pieces.append(('mul', 5 * ms, named('convpower.ylm')))
                pieces.append(('fft', 20 * ms, named('fft.r2c')))
                if ell:
                    pieces.append(('fma', 5 * ms, named('convpower.ylm')))
            pieces.append(('div', 5 * ms, named('fftpower.transfer')))
            if not survey:
                pieces = [(n, d, p if 'convpower' not in p else None)
                          for n, d, p in pieces]
            dur = sum(d for _, d, _ in pieces)
            if survey:
                mark('convpower.ylm', t, dur)
            launch(t, pieces)
            t += dur + 1 * ms
            mark('fftpower.binning', t, 30 * ms)
            launch(t, [('dot', 30 * ms, None)])
            t += 31 * ms
        launch(t, [('copy', unscoped_ms * ms, None)])
    return {'device': 0, 'ops': ops, 'modules': modules,
            'host': {'python3#0': line}}


def ctx_of(tmp_path, monkeypatch, tr, cell=CELL):
    (tmp_path / 'scopes.json').unlink(missing_ok=True)
    monkeypatch.setattr(scopes, '_of_path',
                        lambda path, ncalls: scopes.reduce(tr, ncalls))
    monkeypatch.setattr(scopes.xplane, 'find_xplane', lambda d: 'x.pb')
    files = manifest.cell_files(manifest.benchmark(), cell)
    return {'outdir': str(tmp_path), 'device_kind': 'TPU v5 lite',
            'chips': 1, 'config': files['config'], 'cell': files['cell'],
            'xplane': {'ncalls': 2}}


def test_convpower_readers_on_a_survey_call(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, trace())
    busy = 0.300 + 0.005 + 0.005 + 15 * 0.020 + 14 * 0.010 \
        + 3 * 0.005 + 3 * 0.030 + 0.010
    assert scopes.of_run(ctx)['busy_s'] == pytest.approx(busy)
    assert scopes.layer_s(ctx, 'paint') == pytest.approx(0.300)
    assert scopes.layer_s(ctx, 'fft') == pytest.approx(0.300)
    assert scopes.layer_s(ctx, 'transfer') == pytest.approx(0.015)
    assert scopes.layer_s(ctx, 'binning') == pytest.approx(0.090)
    assert ylm_device_s.read(ctx) == pytest.approx(0.140)
    # the benchmark's own guard counts the Ylm passes, the combine and
    # the reductions as unscoped; the cell's does not
    assert scopes.unscoped_share(ctx) == pytest.approx(
        100 * 0.160 / busy)
    assert convpower_unscoped_share.read(ctx) == pytest.approx(
        100 * 0.010 / busy)
    peak = 819e9
    assert ylm_hbm_share.read(ctx) == pytest.approx(
        100 * 14 * 2690646016 / 0.140 / peak)
    assert convpower_fft_roofline.read(ctx) == pytest.approx(
        100 * 15 * work.r2c_bytes(512) / 0.300 / peak)
    assert tsc_paint_hbm_share.read(ctx) == pytest.approx(
        100 * 11e6 * 228 / 0.300 / peak)
    for reader in (ylm_hbm_share, convpower_fft_roofline,
                   tsc_paint_hbm_share):
        assert 0 < reader.read(ctx) < 100
    # the layers and the survey path's own scopes add up to the busy
    # time, less what ran under the root alone
    red = scopes.of_run(ctx)
    mine = sum(v['device_s'] for k, v in red['scopes'].items()
               if k.startswith('convpower.') and k != 'convpower.run')
    layers = sum(v for k, v in red['layers'].items() if k != 'unscoped')
    assert layers + mine == pytest.approx(busy - 0.010)


def test_convpower_readers_without_the_survey_scopes(tmp_path,
                                                     monkeypatch):
    # a program from before this PR names no convpower. scope: the
    # readers that need one say nothing and raise nothing
    ctx = ctx_of(tmp_path, monkeypatch, trace(survey=False))
    assert scopes.layer_s(ctx, 'paint') == pytest.approx(0.300)
    assert ylm_device_s.read(ctx) is None
    assert ylm_hbm_share.read(ctx) is None
    assert convpower_unscoped_share.read(ctx) is None
    assert convpower_fft_roofline.read(ctx) is None
    assert tsc_paint_hbm_share.read(ctx) == pytest.approx(
        100 * 11e6 * 228 / 0.300 / 819e9)
    # no trace at all, or a configuration with no randoms
    blank = dict(ctx, outdir=None)
    for reader in (ylm_device_s, ylm_hbm_share, convpower_unscoped_share,
                   convpower_fft_roofline, tsc_paint_hbm_share):
        assert reader.read(blank) is None
    other = manifest.cell_files(manifest.benchmark(),
                                'desi_like_n512.lab')['config']
    assert tsc_paint_hbm_share.read(dict(ctx, config=other)) is None


def test_convpower_rooflines_withheld_above_the_unscoped_limit(
        tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, trace(unscoped_ms=200))
    assert convpower_unscoped_share.read(ctx) > scopes.UNSCOPED_MAX
    assert ylm_device_s.read(ctx) == pytest.approx(0.140)
    assert ylm_hbm_share.read(ctx) is None
    assert convpower_fft_roofline.read(ctx) is None
    # and where the survey path's scopes hold more than ran under no
    # layer's, the guard is a miscount: it says nothing, not 0
    red = scopes.of_run(ctx)
    red['layers'][scopes.UNSCOPED] -= 0.300
    monkeypatch.setattr(scopes, 'of_run', lambda ctx: red)
    assert convpower_unscoped_share.read(ctx) is None
    assert ylm_hbm_share.read(ctx) is None


def test_ylm_bytes_and_nfft_by_hand():
    real, cplx = 512 ** 3 * 4, 512 * 512 * 257 * 8
    assert (real, cplx) == (536870912, 538968064)
    # a term reads and writes the real field, reads the transform and
    # A_ell and writes A_ell: 2.69 GB, and poles 0, 2, 4 have 5 + 9
    assert 2 * real + 3 * cplx == 2690646016
    assert work_convpower.ylm_bytes(512, [0, 2, 4]) == 14 * 2690646016
    assert work_convpower.ylm_bytes(512, [0]) == 0
    assert work_convpower.ylm_bytes(512, [2]) == 5 * 2690646016
    assert work_convpower.ylm_bytes(64, [4]) == 9 * (
        2 * 64 ** 3 * 4 + 3 * 64 * 64 * 33 * 8)
    assert work_convpower.nfft([0, 2, 4]) == 15
    assert work_convpower.nfft([2]) == 6        # A_0 is made in any case
    assert work_convpower.nfft([0]) == 1
    assert work_convpower.poles_of({'traffic': 'convpower'}) == [0, 2, 4]
    # TSC touches 27 cells: 4 x (3 + 54) bytes a particle
    assert work.paint_bytes(1, 'tsc') == 228


def test_the_new_cell_reports_its_metrics():
    bench = manifest.benchmark()
    survey = manifest.cell_files(bench, CELL)
    names = [m['name'] for m in survey['per_layer']]
    for want in ('ylm_device_s', 'ylm_hbm_share', 'convpower_fft_roofline',
                 'tsc_paint_hbm_share', 'convpower_unscoped_share',
                 'paint_device_s', 'fft_device_s', 'transfer_device_s',
                 'binning_device_s', 'device_idle_share', 'device_busy_s',
                 'launches_per_call', 'compile_s_in_window',
                 'peak_over_plan'):
        assert want in names
    # one r2c's bytes and a table of layers that does not know the
    # survey path: neither is this cell's
    for gone in ('fft_roofline', 'unscoped_device_share', 'paint_s',
                 'paint_hbm_share', 'after_paint_s'):
        assert gone not in names
    assert survey['traffic']['kind'] == 'lab_convpower'
    assert survey['config']['reduced'].keys() == {'Nmesh'}
    assert [m['name'] for m in survey['end_to_end']] \
        == ['call_s', 'peak_hbm_gb', 'setup_s']
    # the same configuration's data-only cell (PERF.md section 7, cell
    # 0) is not in: its call_s spread 2.9% over six runs on the chip
    assert LAB not in [w['name'] for w in bench['workloads']]
    # and the cells that were there report none of the five
    old = manifest.cell_files(bench, 'desi_like_n512.lab')
    assert not {'ylm_device_s', 'ylm_hbm_share', 'convpower_fft_roofline',
                'tsc_paint_hbm_share', 'convpower_unscoped_share'} \
        & {m['name'] for m in old['per_layer']}
