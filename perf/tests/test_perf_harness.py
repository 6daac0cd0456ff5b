"""CPU rehearsal of the benchmark harness (run by hand; tier-1 collects
``tests/`` only):

    JAX_PLATFORMS=cpu python -m pytest perf/tests -q -p no:cacheprovider

Both drivers at 32^3, the lab driver on four virtual devices with the
TPU-shaped branches, ``run.py`` refusing the CPU, the xplane arithmetic
on synthetic events, the span readers on a recorded 32^3 span file,
the bytes functions, the lattice count against a brute-force count,
and the manifest check.  No number from here is a device number."""

import json
import os
import re
import subprocess
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
os.environ.setdefault(
    'XLA_FLAGS', '--xla_force_host_platform_device_count=4')

import numpy as np      # noqa: E402
import pytest           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from perf import run                            # noqa: E402
from perf.lib import manifest, work, xplane     # noqa: E402
from perf.lib.checks import lattice_mode_counts     # noqa: E402
from perf.reference.lab_fftpower import shell_thresholds    # noqa: E402

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')

#: the cells' sizes cut to what the CPU does in seconds
SMALL = {'BoxSize': 500.0, 'Nmesh': 32, 'N': 100000,
         'served_BoxSize': 1000.0, 'min_modes': 500}


def small(traffic_name):
    traffic = manifest.load_json('perf', 'traffic', traffic_name + '.json')
    traffic['oracle'].update(Nmesh=32, npart=50000)
    return traffic


# --------------------------------------------------------------------------
# drivers

def check_lab(mesh, chips):
    from perf.drivers.lab_fftpower import Driver
    d = Driver(SMALL, small('lab'), chips, seed=2 ** 31 + 11, mesh=mesh)
    rec = d.setup()
    assert rec['oracle_max_rel_err'] < 1e-3 and rec['npart'] == SMALL['N']
    walls, results, errors, _ = run.window(d, 0.0, min_calls=2)
    assert len(walls) == 2 and not errors
    assert results[0]['power'].shape == (16, 10)
    failed, rec = d.verify(results)
    assert failed == 0, rec
    assert abs(rec['p0_over_shot_mean'] - 1) < 0.05
    # a wrong answer is counted, not passed
    bad = dict(results[1], power=results[1]['power'] * 1.5)
    assert d.verify([results[0], bad])[0] == 1
    assert d.verify([bad])[0] == 1
    d.close()


def test_lab_driver_one_device():
    check_lab(None, 1)


def test_lab_driver_four_virtual_devices(monkeypatch):
    # with the TPU-shaped branch of every is_mxu_backend() dispatch, as
    # tests/test_chip_smoke.py rehearses the four-chip path
    import nbodykit_tpu.utils
    from nbodykit_tpu.lab import cpu_mesh
    monkeypatch.setattr(nbodykit_tpu.utils, 'is_mxu_backend', lambda: True)
    check_lab(cpu_mesh(4), 4)


def test_lab_oracle_catches_a_wrong_answer(monkeypatch):
    from nbodykit_tpu.source.mesh import catalog
    from perf.drivers.lab_fftpower import Driver
    monkeypatch.setattr(catalog, 'compensation_transfer',
                        lambda *a: (lambda w, v: v), raising=True)
    with pytest.raises(AssertionError, match='oracle'):
        Driver(SMALL, small('lab'), 1, seed=3).oracle()


def test_served_driver():
    from nbodykit_tpu.lab import cpu_mesh
    from perf.drivers.served_closed_loop import Driver
    traffic = dict(small('served'), hbm_bytes=16e9)
    d = Driver(SMALL, traffic, 1, seed=2 ** 31 + 11, mesh=cpu_mesh(1))
    try:
        rec = d.setup()
        assert rec['oracle_max_rel_err'] < 1e-3
        walls, results, errors, _ = run.window(d, 0.0, min_calls=3)
        assert len(walls) == 3 and not errors
        failed, rec = d.verify(results)
        assert failed == 0, rec
        assert rec['summary']['completed'] == 6     # oracle, 2 warm, 3
        # the same spectrum under two seeds is counted
        twin = dict(results[1], seed=results[0]['seed'] + 99,
                    y=results[0]['y'])
        assert d.verify([results[0], twin])[0] == 1
    finally:
        d.close()


def test_traced_run_on_the_cpu_reads_the_spans(tmp_path):
    # no device plane on the CPU: the trace readers return nothing and
    # their metrics are left out; the span readers work
    from perf.drivers.lab_fftpower import Driver
    files = manifest.cell_files(manifest.benchmark(), 'desi_like_n512.lab')
    files['config'] = SMALL
    d = Driver(SMALL, small('lab'), 1, seed=5)
    d.setup()
    values, attempted, failed, _, red = run.measure_traced(
        d, 0.0, files, str(tmp_path / 'out'), 'TPU v5 lite')
    assert attempted == 4 and failed == 0 and red is None
    assert 'device_idle_share' not in values
    assert values['paint_s'] > 0 and values['after_paint_s'] > 0
    assert values['compile_s_in_window'] >= 0
    # and the plain window: every call's wall, the median, no failure
    values, attempted, failed, peak = run.measure(d, 0.0)
    assert attempted == 1 and failed == 0 and values['call_s'] > 0
    assert peak is None and 'peak_hbm_gb' not in values


def test_the_program_refuses_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'perf', 'run.py'),
         '--workload', 'desi_like_n512.lab', '--seed', '1',
         '--seconds', '1', '--trace', '0'],
        env=dict(os.environ, JAX_PLATFORMS='cpu'), capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert 'needs 1 TPU device' in out.stderr
    assert out.stdout.strip() == ''


# --------------------------------------------------------------------------
# the reductions

def test_xplane_arithmetic_on_synthetic_events():
    # two overlapping ops, a gap, a third op; window [0, 100]
    ops = [('fusion.1', 10.0, 30.0), ('fusion.2', 30.0, 30.0),
           ('copy.3', 80.0, 10.0)]
    busy = xplane.busy_intervals(ops, 0.0, 100.0)
    assert busy == [(10.0, 60.0), (80.0, 90.0)]
    assert xplane.busy_ns(busy) == 60.0
    gaps = xplane.idle_gaps(busy, 0.0, 100.0)
    assert gaps == [(0.0, 10.0), (60.0, 20.0), (90.0, 10.0)]
    # clipped to the window
    assert xplane.busy_ns(xplane.busy_intervals(ops, 20.0, 85.0)) == 45.0


def test_xplane_self_times_and_labels():
    # a while op of 50 with a body op of 20 inside it
    ops = [('while.1', 0.0, 50.0), ('fusion.2', 10.0, 20.0),
           ('all-to-all.3', 60.0, 10.0), ('copy.4', 92.0, 8.0)]
    assert dict(xplane.self_times(ops, 0.0, 100.0)) == {
        'while.1': 30e-9, 'fusion.2': 20e-9, 'all-to-all.3': 10e-9,
        'copy.4': 8e-9}
    assert xplane.matching_ns(ops, xplane.COLLECTIVE, 0.0, 100.0) == 10.0
    host = {'main': [(xplane.CALL, 0.0, 68.0), ('PjitFunction(f)', 5.0, 60.0),
                     ('Execute', 50.0, 10.0), (xplane.CALL, 90.0, 10.0)]}
    spans = xplane.call_spans(host)
    assert xplane.window_of(spans) == (0.0, 100.0)
    events = [e for e in host['main'] if e[0] != xplane.CALL]
    assert xplane.label_gap(52.0, spans, events) == 'in_call.Execute'
    assert xplane.label_gap(66.0, spans, events) == 'in_call.unattributed'
    assert xplane.label_gap(80.0, spans, events) == 'between_calls'
    trace = {'host': host, 'devices': {0: {
        'ops': ops, 'modules': [('jit_f', 0.0, 50.0), ('jit_g', 60.0, 10.0)]}}}
    red = xplane.reduce(trace)
    assert red['ncalls'] == 2 and red['window_s'] == 100e-9
    assert red['devices'][0]['launches'] == 2
    # busy 50 + 10 + 8 of 100; idle 50..60 under Execute, 70..92 after
    # the first call returned
    assert abs(red['devices'][0]['idle_share'] - 0.32) < 1e-12
    assert red['idle_gaps'] == [['between_calls', 22e-9],
                                ['in_call.Execute', 10e-9]]
    assert red['device_ops'][0] == ['while.1', 30e-9]
    assert red['window_from'] == 'call_annotations'
    # no annotation in the trace: the device events' own extent
    bare = xplane.reduce(dict(trace, host={}), ncalls=2)
    assert bare['window_from'] == 'device_events' and bare['ncalls'] == 2
    assert bare['window_s'] == 100e-9
    assert xplane.reduce({'host': host, 'devices': {}}) is None


def test_span_readers_on_a_recorded_file():
    # recorded at 32^3 on the CPU: two calls (perf/tests/data)
    from perf.layers import after_paint_s, paint_hbm_share, paint_s
    with open(os.path.join(HERE, 'data', 'spans_32.jsonl')) as f:
        spans = [json.loads(line) for line in f if line.strip()]
    ctx = {'spans': [r for r in spans if r.get('t') == 'span'],
           'config': {'N': 100000}, 'chips': 1,
           'device_kind': 'TPU v5 lite'}
    paints = sorted(r['dur'] for r in ctx['spans'] if r['name'] == 'paint')
    assert len(paints) == 2
    assert paint_s.read(ctx) == sum(paints) / 2
    ends = {n: sorted(r['ts'] + r['dur'] for r in ctx['spans']
                      if r['name'] == n)
            for n in ('paint', 'fftpower.binning')}
    want = [b - p for p, b in zip(ends['paint'], ends['fftpower.binning'])]
    assert after_paint_s.read(ctx) == sum(want) / 2
    assert after_paint_s.read(ctx) > 0
    share = paint_hbm_share.read(ctx)
    assert share == 100 * 7.6e6 / paint_s.read(ctx) / 819e9
    assert paint_s.read({'spans': []}) is None
    assert after_paint_s.read({'spans': None}) is None


def test_work_functions():
    # CIC in f4: 12 B of position, 8 cells read and written
    assert work.paint_bytes(1, 'cic') == 76
    assert work.paint_bytes(10 ** 7, 'cic') == 760000000
    assert work.paint_bytes(1, 'tsc') == 4 * (3 + 2 * 27)
    # 4^3 reals in, 4*4*3 complex out, then two complex passes
    assert work.r2c_bytes(4) == 64 * 4 + 5 * 48 * 8


def test_peaks_table():
    from perf.lib.peaks import peaks_for
    assert peaks_for('TPU v5 lite')['hbm_bytes_per_s'] == 819e9
    with pytest.raises(KeyError):
        peaks_for('cpu')
    with pytest.raises(KeyError):
        peaks_for('_source')


@pytest.mark.parametrize('nmesh,kmin', [(16, 0.0), (32, 0.001), (24, 0.0)])
def test_lattice_count_against_brute_force(nmesh, kmin):
    q = shell_thresholds(nmesh, 1000.0, kmin)
    ix = np.fft.fftfreq(nmesh, 1.0 / nmesh).astype('i8')
    iz = np.arange(nmesh // 2 + 1)
    isq = (ix[:, None, None] ** 2 + ix[None, :, None] ** 2
           + iz[None, None, :] ** 2)
    w = np.broadcast_to(np.where((iz == 0) | (iz == nmesh // 2), 1., 2.),
                        isq.shape)
    kb = np.searchsorted(q, isq, side='right') - 1
    keep = (kb >= 0) & (kb < len(q) - 1)
    want = np.bincount(kb[keep], weights=w[keep], minlength=len(q) - 1)
    assert np.array_equal(lattice_mode_counts(nmesh, q), want)


# --------------------------------------------------------------------------
# the manifest

def test_manifest():
    bench = manifest.benchmark()
    assert set(bench) == {'command', 'paths', 'run_seconds', 'configs',
                          'workloads', 'end_to_end', 'per_layer'}
    e2e = {m['name']: m for m in bench['end_to_end']}
    assert 'setup_s' in e2e
    names = [m['name'] for m in bench['end_to_end'] + bench['per_layer']]
    assert len(set(names)) == len(names)
    cells = [w['name'] for w in bench['workloads']]
    assert len(set(cells)) == len(cells)
    for m in bench['end_to_end'] + bench['per_layer']:
        assert NAME.match(m['name']) and UNIT.match(m['unit']), m
        assert m['better'] in ('lower', 'higher')
        assert set(m.get('workloads', cells)) <= set(cells), m
    for m in bench['end_to_end']:
        assert 0.01 <= m['bound'] <= 0.25
        assert m['source'] in ('host_clock', 'device_trace')
    for c in bench['configs']:
        assert NAME.match(c['name']) and len(c['source']) <= 200
        assert len(c['why']) <= 200 and '\n' not in c['source']
        assert c['file'].startswith('perf/configs/')
        assert any(w['config'] == c['name'] for w in bench['workloads'])
        config = manifest.load_json(c['file'])
        assert sorted(config['reduced']) == sorted(c['reduced'])
        assert all(NAME.match(k) for k in c['reduced'])
    four = sum(w['chips'] == 4 for w in bench['workloads'])
    assert four <= max(1, len(cells) // 2)
    for w in bench['workloads']:
        assert NAME.match(w['name']) and NAME.match(w['traffic'])
        assert len(w['why']) <= 200 and w['chips'] in (1, 4)
        files = manifest.cell_files(bench, w['name'])
        assert files['config']['chips'] == w['chips']
        kind = files['traffic']['kind']
        for part in ('drivers', 'reference'):
            assert os.path.exists(os.path.join(
                ROOT, 'perf', part, kind + '.py'))
        assert manifest.driver_class(kind)
        reported = {m['name'] for m in files['end_to_end']}
        assert 'setup_s' in reported and len(reported) >= 2
        assert files['per_layer']
        for m in files['per_layer']:
            assert callable(manifest.layer_reader(m['name']))
            # its arrow points at a metric this cell reports
            assert m['moves'] in reported, (w['name'], m['name'])
    for m in bench['per_layer']:
        assert m['moves'] in e2e and len(m['layer']) <= 200
    assert len(json.dumps(bench)) < 64 * 1024
    # every file under perf/ is named from the characters of a name
    for base, dirs, names in os.walk(os.path.join(ROOT, 'perf')):
        dirs[:] = [d for d in dirs if d not in ('out', '__pycache__')]
        for n in names:
            assert re.match(r'^[A-Za-z0-9_.\-]+$', n), n
