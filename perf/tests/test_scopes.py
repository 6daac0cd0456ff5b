"""The reduction of ``perf/lib/scopes.py`` on plain tuples and on two
small traces recorded on the chip (run by hand, like
``test_perf_harness.py``):

    JAX_PLATFORMS=cpu python -m pytest perf/tests -q -p no:cacheprovider

No number from here is a device number of a cell: the recorded traces
are 64^3 calls, kept to hold the reduction to a real trace's layout."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perf.layers import fft_roofline            # noqa: E402
from perf.lib import scopes                     # noqa: E402

MS = 1e6        # ns


def host(name, s, d, rid=None, produces=None, consumes=None):
    return (name, s * MS, d * MS, rid, produces, consumes)


def mark(scope, s, d):
    return host('nbk.' + scope, s, d)


def launch(k, s, deferred=False):
    """What one eager launch leaves on the host: the call's linkage
    event on the python line, the runtime's execute on the thread's
    other line, and the enqueue (with the module's run id) inside an
    issue event — on a worker thread, later, when it was deferred."""
    py = [host('PjitFunction(f%d)' % k, s, 0.5),
          host('Execute linkage', s + 0.1, 0.01, produces='14:%d' % k)]
    main = [host('PJRT_Execute', s + 0.12, 0.3, consumes='14:%d' % k),
            host('System::Execute', s + 0.2, 0.1, produces='7:%d' % k)]
    t = s + 40 if deferred else s + 0.21
    issue = [host('Execute=>Issue', t, 0.08, consumes='7:%d' % k),
             host('DoEnqueueProgram', t + 0.02, 0.05, rid=k,
                  produces='12:%d' % k)]
    done = [host('CompleteCallbacks', t + 60, 0.01, rid=k,
                 consumes='12:%d' % k)]
    return py, main + ([] if deferred else issue), \
        issue if deferred else [], done


def module(k, s, d):
    return ('jit_f%d(123)' % k, s * MS, d * MS, k)


def op(name, s, d, path=None):
    return (name, s * MS, d * MS, path)


def eager_trace(lose=None):
    """One 110 ms call: paint (30 ms of device time) and r2c (10 ms)
    launched eagerly under their annotations, one multiply under
    ``mesh.compute`` alone (5 ms), then the binning program (40 ms)
    whose ops name their own scopes.  The device runs 20 ms behind,
    and the r2c's enqueue is deferred to a worker thread, 40 ms after
    the call that made it, when ``fft.r2c`` has long been left."""
    py = [host('perf.call', 0, 110),
          mark('fftpower.run', 1, 108), mark('mesh.compute', 2, 40),
          mark('paint', 3, 10), mark('fft.r2c', 15, 5),
          mark('fftpower.binning', 50, 57)]
    lines = {'python3#5': py, 'main/289#2': [], 'pjrt-tpu-tasks#3': [],
             'futex#1': []}
    for k, s, deferred in ((1, 4, False), (2, 16, True), (3, 30, False),
                           (4, 51, False)):
        a, b, c, d = launch(k, s, deferred)
        if k != lose:
            lines['python3#5'] += a
        lines['main/289#2'] += b
        lines['pjrt-tpu-tasks#3'] += c
        lines['futex#1'] += d
    return {'device': 0, 'host': lines,
            'modules': [module(1, 20, 30), module(2, 50, 10),
                        module(3, 60, 5), module(4, 65, 40)],
            'ops': [op('scatter.1', 20, 30), op('fft.2', 50, 10),
                    op('multiply.3', 60, 5),
                    # a loop and its body, both events of one line
                    op('while.4', 65, 40,
                       'jit(binning)/nbk.fftpower.binning/while'),
                    op('fusion.5', 66, 25, 'jit(binning)/nbk.fftpower.'
                       'binning/while/body/nbk.fftpower.binning.digitize'
                       '/digitize'),
                    op('fusion.6', 92, 10, 'jit(binning)/nbk.fftpower.'
                       'binning/while/body/nbk.fftpower.binning.hist/dot')]}


def test_scope_stack_and_layer():
    path = ('jit(program)/vmap(nbk.serve.program)/vmap(nbk.fft.r2c)/'
            'nbk.fft.a2a.dev/all-to-all')
    assert scopes.scope_stack(path) == ['serve.program', 'fft.r2c',
                                        'fft.a2a.dev']
    assert scopes.scope_stack(None) == [] == scopes.scope_stack('jit(f)/mul')
    # the innermost scope that belongs to a layer decides
    assert scopes.layer_of(['serve.program', 'fft.r2c', 'fft.a2a.dev']) \
        == 'a2a'
    assert scopes.layer_of(['paint', 'exchange']) == 'exchange'
    assert scopes.layer_of(['fftpower.run', 'fftpower.binning',
                            'fftpower.binning.hist']) == 'binning'
    assert scopes.layer_of(['fftpower.run', 'mesh.compute']) is None
    assert scopes.layer_of(['fftpower.transfer', 'mesh.compute']) \
        == 'transfer'
    assert scopes.layer_of(['fft.lowmem.r2c']) is None
    assert scopes.layer_of([]) is None


def test_resolve_gives_what_the_compiler_left_bare_a_scope():
    """The served paint as the TPU compiler leaves it: a sort and a
    custom fusion with no ``op_name``; the fusion's body still holds
    paint's instructions, and the sort feeds nothing else."""
    p, t = ('jit(program)/vmap(nbk.serve.program)/nbk.paint/',
            'jit(program)/vmap(nbk.serve.program)/nbk.fftpower.transfer/')
    program = [
        [('param.1', None, [], []), ('select.2', p + 'select_n', [], []),
         ('scatter.3', None, ['param.1', 'select.2'], [])],     # 0: body
        [('lt.4', None, [], [])],                               # 1: compare
        [('mixed.5', p + 'mul', [], []), ('mixed.6', t + 'div', [], [])],
        [('keys.7', p + 'floor', [], []),                       # 3: entry
         ('sort.8', None, ['keys.7'], [1]),
         ('gte.9', None, ['sort.8'], []),
         ('fusion.10', None, ['gte.9'], [0]),
         ('fusion.11', None, ['fusion.10'], [2]),
         ('rng.12', 'jit(program)/vmap(nbk.serve.program)/random_bits',
          [], []),
         ('copy.13', None, ['rng.12'], []),
         ('bare.14', None, [], []),
         ('div.15', 'jit(program)/vmap(nbk.serve.program)/div',
          ['fusion.11', 'copy.13'], [])]]
    got = scopes.resolve(program)
    paint = ['serve.program', 'paint']
    assert got['keys.7'] == paint                   # its own
    assert got['fusion.10'] == paint                # what is in it
    assert got['sort.8'] == got['gte.9'] == paint   # what consumes it
    # a fusion across two layers keeps what they share: the parent
    assert got['fusion.11'] == ['serve.program']
    assert scopes.layer_of(got['fusion.11']) is None
    assert got['copy.13'] == ['serve.program']
    assert got['bare.14'] == []
    assert scopes.common([paint, [], paint]) == paint
    assert scopes.common([]) == []


def test_eager_call_joined_through_the_ids():
    red = scopes.reduce(eager_trace())
    assert red['modules'] == 4 and red['unjoined_modules'] == 0
    assert red['unjoined_s'] == 0 and not scopes.unreadable(red)
    lay = red['layers']
    assert lay['paint'] == pytest.approx(0.030)
    # launched under fft.r2c, enqueued from another thread after it
    assert lay['fft'] == pytest.approx(0.010)
    # the loop's own 5 ms + its two children, nested self time
    assert lay['binning'] == pytest.approx(0.040)
    assert lay['unscoped'] == pytest.approx(0.005)
    assert sum(lay.values()) == pytest.approx(red['busy_s'])
    sc = red['scopes']
    assert sc['fftpower.binning']['device_s'] == pytest.approx(0.005)
    assert sc['fftpower.binning.digitize']['device_s'] \
        == pytest.approx(0.025)
    assert sc['fftpower.binning.hist']['device_s'] == pytest.approx(0.010)
    # the multiply ran under mesh.compute alone: named, in no layer
    assert sc['mesh.compute']['device_s'] == pytest.approx(0.005)
    assert sc['mesh.compute']['launches'] == 1
    assert sc['paint']['launches'] == 1 == sc['fft.r2c']['launches']
    assert sc['paint']['host_s'] == pytest.approx(0.010)
    assert red['named_ops'] == 3 and red['ops'] == 6
    # the device idles until the scatter arrives (the gap began under
    # nothing, inside the call) and while the host fetches the result
    assert red['idle_gaps'] == {'in_call.no_scope': pytest.approx(0.020),
                                'fftpower.binning': pytest.approx(0.005)}


def test_a_launch_that_cannot_be_found_is_not_guessed():
    red = scopes.reduce(eager_trace(lose=1))    # no linkage on python3
    assert red['unjoined_modules'] == 0         # the chain ends early:
    assert red['layers'].get('paint') is None   # on a line with no scope
    assert red['layers']['unscoped'] == pytest.approx(0.035)
    t = eager_trace()
    t['host']['main/289#2'] = [e for e in t['host']['main/289#2']
                               if e[3] != 1]    # the enqueue is gone
    red = scopes.reduce(t)
    assert red['unjoined_modules'] == 1
    assert red['unjoined_s'] == pytest.approx(0.030)
    assert red['layers']['unscoped'] == pytest.approx(0.035)
    # rule 1 still names the binning program's ops
    assert red['layers']['binning'] == pytest.approx(0.040)
    assert '1 of 4 programs' in scopes.unreadable(red)     # 30 of 85 ms


def served_trace(named=True):
    """Two requests: one program each, launched from a worker thread
    with no annotation, its ops named by the program itself."""
    def p(scope, prim):
        return ('jit(program)/vmap(nbk.serve.program)/%s%s'
                % ('nbk.%s/' % scope if scope else '', prim)) \
            if named else 'jit(program)/vmap()/' + prim
    lines = {'main#0': [host('perf.call', 0, 50), host('perf.call', 50, 50)],
             'python3#4': [], 'rt#1': [], 'rt#2': [], 'rt#3': []}
    ops, modules = [], []
    for k, t in enumerate((5, 55)):
        a, b, c, d = launch(7 + k, t - 1)
        lines['python3#4'] += a
        lines['rt#1'] += b
        lines['rt#2'] += c
        lines['rt#3'] += d
        modules.append(module(7 + k, t, 40))
        ops += [op('rng', t, 2, p(None, 'random_bits')),
                op('scatter', t + 2, 14, p('paint', 'scatter-add')),
                op('fft', t + 16, 2, p('fft.r2c', 'fft')),
                op('mul', t + 18, 2, p('fftpower.transfer', 'mul')),
                op('sums', t + 20, 20, p('fftpower.binning', 'dot'))]
    return {'device': 0, 'ops': ops, 'modules': modules, 'host': lines}


def ctx_of(tmp_path, monkeypatch, trace, nmesh=512):
    (tmp_path / 'scopes.json').unlink(missing_ok=True)
    monkeypatch.setattr(scopes, '_of_path',
                        lambda path, ncalls: scopes.reduce(trace, ncalls))
    monkeypatch.setattr(scopes.xplane, 'find_xplane', lambda d: 'x.pb')
    return {'outdir': str(tmp_path), 'xplane': {'ncalls': 2},
            'device_kind': 'TPU v5 lite', 'chips': 1,
            'config': {'Nmesh': nmesh}}


def test_served_request_by_op_name_alone(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, served_trace())
    assert scopes.layer_s(ctx, 'paint') == pytest.approx(0.014)
    assert scopes.layer_s(ctx, 'fft') == pytest.approx(0.002)
    assert scopes.layer_s(ctx, 'transfer') == pytest.approx(0.002)
    assert scopes.layer_s(ctx, 'binning') == pytest.approx(0.020)
    assert scopes.layer_s(ctx, 'exchange') == 0.0
    assert scopes.unscoped_share(ctx) == pytest.approx(5.0)
    with open(os.path.join(str(tmp_path), 'scopes.json')) as f:
        said = json.load(f)
    assert said['named_ops'] == 10 == said['ops']
    assert said['annotations'] == 0 and said['unjoined_modules'] == 0
    assert said['scopes']['serve.program']['device_s'] \
        == pytest.approx(0.002)
    assert said['idle_gaps'] == {'in_call.no_scope': pytest.approx(0.010)}


def test_a_program_without_scopes_reads_nothing(tmp_path, monkeypatch):
    """The parent commit: the join works, nothing is named, and every
    reader says nothing rather than 0 or 100%."""
    assert scopes.layer_s({'outdir': None}, 'paint') is None
    assert scopes.layer_s({'outdir': str(tmp_path / 'none')},
                          'paint') is None         # no trace there
    ctx = ctx_of(tmp_path, monkeypatch, served_trace(named=False))
    assert scopes.layer_s(ctx, 'paint') is None
    assert scopes.unscoped_share(ctx) is None
    assert fft_roofline.read(ctx) is None
    with open(os.path.join(str(tmp_path), 'scopes.json')) as f:
        assert 'no nbk. scope' in json.load(f)['unreadable']


def test_fft_roofline_and_its_guard(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, served_trace())
    # r2c_bytes(512) = 3.23e9 B in 2 ms against 819 GB/s
    want = 100 * (512 ** 3 * 4 + 5 * 512 * 512 * 257 * 8) / 0.002 / 819e9
    assert fft_roofline.read(ctx) == pytest.approx(want)
    # more than 10% of the busy time unscoped: withheld
    loose = served_trace()
    loose['ops'] = [(n, s, d, None if n == 'sums' else path)
                    for n, s, d, path in loose['ops']]
    ctx = ctx_of(tmp_path, monkeypatch, loose)
    assert scopes.unscoped_share(ctx) == pytest.approx(55.0)
    assert fft_roofline.read(ctx) is None
    assert scopes.layer_s(ctx, 'fft') == pytest.approx(0.002)


# --------------------------------------------------------------------------
# two real traces (64^3 / 2e5 particles, one v5e, PR 25) as scopes.load
# reduces them, cut to the first call and to the host events on a
# launch's chain of ids; times in ns from 60 ms before the call

def recorded(name):
    with open(os.path.join(HERE, 'data', name)) as f:
        t = json.load(f)
    return {'device': t['device'], 'ops': [tuple(e) for e in t['ops']],
            'modules': [tuple(e) for e in t['modules']],
            'host': {k: [tuple(e) for e in v]
                     for k, v in t['host'].items()}}


def test_recorded_lab_call_every_launch_comes_home():
    t = recorded('trace_lab_64.json')
    red = scopes.reduce(t)
    # 393 programs in the window: the call's 392 and one of the next
    # call's (the device's clock runs about a millisecond behind)
    assert red['modules'] == 393 and red['unjoined_modules'] == 0
    homes = scopes.launches(t['modules'], t['host'])
    assert {line.split('#')[0] for line, _ in homes} == {'python3'}
    # a fifth of the enqueues were deferred to another thread
    enq = {e[3]: line for line, evs in t['host'].items() for e in evs
           if e[3] is not None and e[4] is not None}
    deferred = [m for m in t['modules']
                if not enq[m[3]].startswith('main/')]
    assert 40 < len(deferred) < 160
    lay = red['layers']
    assert sum(lay.values()) == pytest.approx(red['busy_s'], rel=1e-3)
    assert lay['paint'] > 0.9 * red['busy_s']       # 2e5 particles, 64^3
    assert lay['unscoped'] < 0.01 * red['busy_s']
    assert set(red['scopes']) >= {'fftpower.binning.digitize',
                                  'fftpower.binning.hist', 'fft.r2c'}
    assert round(red['scopes']['paint']['launches']) == 264
    assert round(red['scopes']['fftpower.binning']['launches']) == 1
    assert scopes.unreadable(red) is None


def test_recorded_served_request_bare_scatter_is_paint():
    t = recorded('trace_served_64.json')
    red = scopes.reduce(t)
    assert red['modules'] == 2 and red['annotations'] == 0
    assert red['unjoined_modules'] == 0
    lay = red['layers']
    # the scatter's fusions and sorts carry no op_name of their own:
    # their scope came out of the program's HLO
    assert lay['paint'] > 0.8 * red['busy_s']
    assert lay['binning'] > 0.1 * red['busy_s']
    assert lay['unscoped'] < 0.001 * red['busy_s']
    assert sum(lay.values()) == pytest.approx(red['busy_s'], rel=1e-3)
    assert scopes.unreadable(red) is None


# --------------------------------------------------------------------------
# the file reader, on a small XSpace written here field by field

def vi(n):
    n &= (1 << 64) - 1
    out = b''
    while n > 0x7f:
        out += bytes([n & 0x7f | 0x80])
        n >>= 7
    return out + bytes([n])


def num(field, n):
    return vi(field << 3) + vi(n)


def sub(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return vi(field << 3 | 2) + vi(len(payload)) + payload


def xplane_bytes(name, lines, events, stat_names):
    """``events``: {id: (name, [stat bytes])}; ``lines``: [(name, t0_ns,
    [(metadata id, offset_ps, duration_ps, [stat bytes])])]."""
    out = sub(2, name)
    for label, t0, evs in lines:
        out += sub(3, sub(2, label) + num(3, t0) + b''.join(
            sub(4, num(1, mid) + num(2, off) + num(3, dur)
                + b''.join(sub(4, s) for s in stats))
            for mid, off, dur, stats in evs))
    for mid, (label, stats) in events.items():
        out += sub(4, num(1, mid) + sub(2, num(1, mid) + sub(2, label)
                                        + b''.join(sub(5, s)
                                                   for s in stats)))
    for sid, label in stat_names.items():
        out += sub(5, num(1, sid) + sub(2, num(1, sid) + sub(2, label)))
    return out


def test_load_reads_metadata_stats_links_and_the_programs_hlo(tmp_path):
    path = 'jit(program)/vmap(nbk.serve.program)/nbk.paint/'

    def ins(name, iid, op_name=None, operands=(), called=()):
        return sub(2, sub(1, name) + num(35, iid)
                   + (sub(7, sub(2, op_name)) if op_name else b'')
                   + b''.join(num(36, o) for o in operands)
                   + (sub(38, b''.join(vi(c) for c in called))
                      if called else b''))
    hlo = sub(1, sub(3, num(5, 70) + ins('select.2', 1, path + 'select_n')
                     + ins('scatter.3', 2, None, [1]))
              + sub(3, num(5, 71) + ins('keys.7', 3, path + 'floor')
                    + ins('sort.8', 4, None, [3])
                    + ins('fusion.10', 5, None, [4], [70])))
    names = {1: 'tf_op', 2: 'program_id', 3: 'run_id', 4: '_pt', 5: '_p',
             6: '_ct', 7: '_c', 8: 'Hlo Proto'}
    dev = xplane_bytes(
        '/device:TPU:0',
        [('XLA Modules', 5, [(1, 1000000, 9000000, [num(1, 3) + num(4, 77)])]),
         ('XLA Ops', 5, [(2, 1000000, 2000000, []),
                         (3, 3000000, 4000000, []),
                         (4, 7000000, 1000000, [])])],
        {1: ('jit_program(99)', []),
         2: ('%keys.7 = f32[8] floor(f32[8] %p)',
             [num(1, 1) + sub(5, path + 'floor'), num(1, 2) + sub(5, '99')]),
         3: ('%sort.8 = f32[8] sort(f32[8] %keys.7)',
             [num(1, 2) + sub(5, '99')]),
         # a ref value: the string is a stat metadata's name
         4: ('%other.1 = f32[] add()', [num(1, 1) + num(7, 2),
                                        num(1, 2) + sub(5, '98')])},
        names)
    meta = xplane_bytes('/host:metadata', [], {
        99: ('jit_program(99)', [num(1, 8) + sub(6, hlo)]),
        98: ('jit_other(98)', [num(1, 8) + sub(6, sub(1, b''))])}, names)
    cpu = xplane_bytes(
        '/host:CPU',
        [('python3', 0, [(1, 0, 20000000, []), (2, 500000, 5000000, []),
                         (3, 600000, 10000, [num(1, 4) + num(3, 14),
                                             num(1, 5) + num(3, 7)]),
                         (5, 700000, 10000, [])]),
         ('main/1', 0, [(4, 650000, 100000,
                         [num(1, 3) + num(4, 77), num(1, 4) + num(3, 12),
                          num(1, 5) + num(4, -5), num(1, 6) + num(3, 14),
                          num(1, 7) + num(3, 7)])])],
        {1: ('perf.call', []), 2: ('nbk.paint', []), 3: ('linkage', []),
         4: ('DoEnqueueProgram', []), 5: ('ParseArguments', [])}, names)
    f = tmp_path / 't.xplane.pb'
    f.write_bytes(sub(1, dev) + sub(1, meta) + sub(1, cpu))
    t = scopes.load(str(f))
    assert t['device'] == 0
    assert t['modules'] == [('jit_program(99)', 1005.0, 9000.0, 77)]
    assert [(n.split(' = ')[0], s, d) for n, s, d, _ in t['ops']] == [
        ('%keys.7', 1005.0, 2000.0), ('%sort.8', 3005.0, 4000.0),
        ('%other.1', 7005.0, 1000.0)]
    # its own op_name; the scopes its program's HLO gives it (the sort
    # feeds a fusion whose body is paint's); a program with no scope
    assert [p for _, _, _, p in t['ops']] == [
        path + 'floor', 'nbk.serve.program/nbk.paint', 'program_id']
    assert t['host'] == {
        'python3#0': [('perf.call', 0.0, 20000.0, None, None, None),
                      ('nbk.paint', 500.0, 5000.0, None, None, None),
                      ('linkage', 600.0, 10.0, None, '14:7', None)],
        'main/1#1': [('DoEnqueueProgram', 650.0, 100.0, 77, '12:-5',
                      '14:7')]}
    red = scopes.reduce(t)
    assert red['unjoined_modules'] == 0
    # the op with no scope of its own ran in a program launched
    # under nbk.paint: rule 2
    assert red['layers'] == {'paint': pytest.approx(7e-6)}
