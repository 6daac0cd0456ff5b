"""The four-chip cell's yardstick, rehearsed on the CPU (run by hand,
like its neighbours):

    JAX_PLATFORMS=cpu python -m pytest perf/tests/test_four_chip_cell.py -q -p no:cacheprovider

``a2a_bytes`` against the arithmetic in ``BENCHMARK.json``'s issue, the
three readers of the ``exchange`` / ``a2a`` layers on a synthetic
reduction with and without those layers (the all_to_all named by the
host annotation it was launched under, as the chip shows it, and by
its own op_name, as a staged r2c would) and on one device, and
``a2a_ici_share`` withheld above the unscoped limit.  No number from
here is a device number."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perf.layers import (a2a_device_s, a2a_ici_share,     # noqa: E402
                         collective_s, exchange_device_s)
from perf.lib import ici, manifest, scopes      # noqa: E402

CELL = 'desi_like_n1024.lab_x4'


def test_a2a_bytes():
    # N1 x N0/P x (N2/2 + 1) complex64 a device, (P - 1) / P of it sent
    block = 1024 * 256 * 513 * 8
    assert block == 1075838976
    assert ici.a2a_bytes(1024, 4) == block * 3 // 4 == 806879232
    assert ici.a2a_bytes(512, 4) == 512 * 128 * 257 * 8 * 3 // 4
    assert ici.a2a_bytes(1024, 2) == 1024 * 512 * 513 * 8 // 2
    assert ici.a2a_bytes(1024, 1) == 0      # nothing leaves one chip
    # 1600 Gbit/s a chip, the figure peaks.json's _source cites
    assert ici.ICI_BYTES_PER_S == {'TPU v5 lite': 200e9, 'TPU v5e': 200e9}
    assert '1600 Gbit/s' in manifest.load_json(
        'perf', 'lib', 'peaks.json')['_source']


# --------------------------------------------------------------------------
# the readers, on one eager four-chip call as scopes.reduce sees it

def host(name, s, d, rid=None, produces=None, consumes=None):
    return (name, float(s), float(d), rid, produces, consumes)


#: how the slab r2c's all_to_all gets its scope: ``host`` is what the
#: chip shows for the cell (an eager ``shard_map`` launches it as a
#: program of its own, op_name ``jit(<unknown>)/shard_map/all_to_all``
#: with no name stack, from inside ``nbk.fft.a2a.dev`` nested in
#: ``nbk.fft.r2c`` on the calling thread: rule 2); ``op_name`` is a
#: staged r2c, one program whose all_to_all names its scopes (rule 1)
VIA = ('host', 'op_name')


def trace(layers=('exchange', 'a2a'), unscoped_ms=2, via='host'):
    """Two calls of 100 ms, each launching its programs from the
    calling thread under their layer's annotation: exchange 10 ms,
    paint 30, fft 8 around an all_to_all of 6 (scoped as ``via``
    says), binning 20, and ``unscoped_ms`` under no scope.  Times in
    ns."""
    ms = 1e6
    lines = {'python3#0': []}
    ops, modules = [], []
    rid = [0]

    def mark(name, t, dur):
        lines['python3#0'].append(host('nbk.' + name, t - 1000,
                                       dur + 2000))

    def launch(t, dur, name, path=None, inner=None):
        rid[0] += 1
        lines['python3#0'].append(host('launch', t, 1, rid[0], 'p:%d'
                                       % rid[0], None))
        modules.append(('jit_x', t + 10, dur, rid[0]))
        ops.append((name, t + 10, dur, path))
        if inner:
            ops.append(inner)

    for k in range(2):
        t = k * 100 * ms + 5 * ms
        lines['python3#0'].append(host('perf.call', k * 100 * ms,
                                       100 * ms))
        if 'exchange' in layers:
            mark('exchange', t, 10 * ms)
            launch(t, 10 * ms, 'all-to-all.1')
        mark('paint', t + 12 * ms, 30 * ms)
        launch(t + 12 * ms, 30 * ms, 'scatter')
        mark('fft.r2c', t + 44 * ms, 14 * ms)
        if 'a2a' not in layers:
            launch(t + 44 * ms, 14 * ms, 'fft')
        elif via == 'op_name':
            launch(t + 44 * ms, 14 * ms, 'fft', None,
                   ('all-to-all.7', t + 46 * ms, 6 * ms,
                    'jit(f)/nbk.fft.r2c/nbk.fft.a2a.x/all_to_all'))
        else:
            launch(t + 44 * ms, 4 * ms, 'fft')
            mark('fft.a2a.dev', t + 48 * ms + 2000, 6 * ms - 4000)
            launch(t + 48 * ms + 2000, 6 * ms - 4000, 'all-to-all.7',
                   'jit(<unknown>)/shard_map/all_to_all')
            launch(t + 54 * ms, 4 * ms, 'fft')
        mark('fftpower.binning', t + 60 * ms, 20 * ms)
        launch(t + 60 * ms, 20 * ms, 'dot')
        launch(t + 82 * ms, unscoped_ms * ms, 'copy')
    return {'device': 0, 'ops': ops, 'modules': modules, 'host': lines}


def ctx_of(tmp_path, monkeypatch, tr, chips=4):
    (tmp_path / 'scopes.json').unlink(missing_ok=True)
    monkeypatch.setattr(scopes, '_of_path',
                        lambda path, ncalls: scopes.reduce(tr, ncalls))
    monkeypatch.setattr(scopes.xplane, 'find_xplane', lambda d: 'x.pb')
    return {'outdir': str(tmp_path), 'device_kind': 'TPU v5 lite',
            'chips': chips, 'config': {'Nmesh': 1024},
            'xplane': {'ncalls': 2, 'devices': {
                n: {'collective_s': 0.032} for n in range(chips)}}}


@pytest.mark.parametrize('via', VIA)
def test_readers_with_both_layers(tmp_path, monkeypatch, via):
    ctx = ctx_of(tmp_path, monkeypatch, trace(via=via))
    a2a = 0.006 if via == 'op_name' else 0.006 - 4e-6
    assert exchange_device_s.read(ctx) == pytest.approx(0.010)
    assert a2a_device_s.read(ctx) == pytest.approx(a2a)
    # the a2a is left out of the fft's own seconds
    assert scopes.layer_s(ctx, 'fft') == pytest.approx(0.008)
    assert scopes.unscoped_share(ctx) == pytest.approx(
        100 * 2 / (70.0 + 1e3 * a2a))
    # 0.807 GB in 6 ms of 200 GB/s
    assert a2a_ici_share.read(ctx) == pytest.approx(
        100 * 806879232 / a2a / 200e9)
    assert a2a_ici_share.read(ctx) < 100
    assert collective_s.read(ctx) == pytest.approx(0.016)


def test_readers_without_the_layers(tmp_path, monkeypatch):
    # a readable trace with no op under either scope reads 0 s, and
    # the share of a time of 0 is withheld
    ctx = ctx_of(tmp_path, monkeypatch, trace(layers=()))
    assert exchange_device_s.read(ctx) == 0.0
    assert a2a_device_s.read(ctx) == 0.0
    assert a2a_ici_share.read(ctx) is None
    # no trace at all: nothing, and nothing raised
    blank = dict(ctx, outdir=None)
    assert exchange_device_s.read(blank) is None
    assert a2a_device_s.read(blank) is None
    assert a2a_ici_share.read(blank) is None


def test_readers_on_one_device(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, trace(layers=()), chips=1)
    assert exchange_device_s.read(ctx) is None
    assert a2a_device_s.read(ctx) is None
    assert a2a_ici_share.read(ctx) is None
    assert collective_s.read(ctx) is None


def test_a2a_ici_share_withheld_above_the_unscoped_limit(tmp_path,
                                                         monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, trace(unscoped_ms=15))
    assert scopes.unscoped_share(ctx) > scopes.UNSCOPED_MAX
    assert a2a_device_s.read(ctx) == pytest.approx(0.006, rel=1e-3)
    assert a2a_ici_share.read(ctx) is None


def test_the_cell_reports_the_new_metrics():
    files = manifest.cell_files(manifest.benchmark(), CELL)
    names = [m['name'] for m in files['per_layer']]
    for want in ('collective_s', 'exchange_device_s', 'a2a_device_s',
                 'a2a_ici_share', 'paint_device_s', 'fft_device_s',
                 'transfer_device_s', 'binning_device_s',
                 'unscoped_device_share', 'fft_roofline',
                 'device_idle_share', 'launches_per_call'):
        assert want in names
    for gone in ('paint_s', 'paint_hbm_share', 'after_paint_s'):
        assert gone not in names
    assert files['cell']['chips'] == 4 == files['config']['chips']
    assert files['config']['reduced'] == {}
    assert [m['name'] for m in files['end_to_end']] \
        == ['call_s', 'peak_hbm_gb', 'setup_s']
    # and the one-chip cells report none of the four
    lab = manifest.cell_files(manifest.benchmark(), 'desi_like_n512.lab')
    assert not {'collective_s', 'exchange_device_s', 'a2a_device_s',
                'a2a_ici_share'} & {m['name'] for m in lab['per_layer']}
