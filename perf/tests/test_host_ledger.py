"""The host's half of a traced run (``perf/lib/hostledger.py`` and its
seven readers) on plain tuples, on a recorded trace's layout and on a
profile of this machine's CPU (run by hand, like
``test_perf_harness.py``):

    JAX_PLATFORMS=cpu python -m pytest perf/tests -q -p no:cacheprovider

No number from here is a device number of a cell."""

import json
import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

import pytest           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perf.layers import (host_gap_s, host_work_s, retrace_s,    # noqa: E402
                         sync_gap_s, syncs_per_call, traced_call_s,
                         unscoped_gap_share)
from perf.lib import hostledger, scopes, xplane     # noqa: E402

MS = 1e6                        # ns
START = 1_790_000_000 * 10 ** 9         # the session's start, wall clock
READERS = (traced_call_s, host_gap_s, unscoped_gap_share, sync_gap_s,
           syncs_per_call, retrace_s, host_work_s)


def lab_trace(scoped=True, fetches=True):
    """Two lab calls of 100 ms, 20 ms apart, the device a few ms
    behind the host.  Each call's idle gaps: 20 ms that begin under
    ``mesh.compute`` (the paint's annotation has ended), 12 under
    ``fftpower.coords``, 10 under the fetch inside
    ``fftpower.result``, 7 under the root alone; and 22 ms that begin
    between the calls.  ``scoped`` False: a program that names its
    root and nothing else; ``fetches`` False: one from before
    ``fetch``."""
    line, busy = [], []
    for k in range(2):
        t = k * 120
        line.append(('perf.call', t, 100))
        line.append(('nbk.fftpower.run', t + 1, 98))
        if scoped:
            line += [('nbk.mesh.compute', t + 2, 30),
                     ('nbk.paint', t + 3, 10),
                     ('nbk.fftpower.coords', t + 40, 25),
                     ('nbk.fftpower.result', t + 77, 14)]
        if scoped and fetches:
            line.append(('nbk.sync.fftpower.binning', t + 78, 7))
        busy += [(t + (5 if k else 0), t + 25), (t + 45, t + 60),
                 (t + 72, t + 80),
                 (t + 90, t + 92), (t + 99, t + 103)]
    return {'host': {'python3': [(n, s * MS, d * MS) for n, s, d in line]},
            'busy': [(a * MS, b * MS) for a, b in busy],
            'start_ns': START}


def served_trace():
    """One request: the client's call on one line, the worker's root,
    launch and fetch on another.  10 ms idle before the program starts
    (under the worker's root alone when it begins: the launch's
    annotation opens later), 15 ms after it under the fetch."""
    return {'host': {
        'python3': [('perf.call', 0, 100 * MS)],
        'worker': [('nbk.serve.request', 0, 95 * MS),
                   ('nbk.serve.launch', 3 * MS, 5 * MS),
                   ('nbk.sync.serve.result', 8 * MS, 80 * MS)]},
        'busy': [(10 * MS, 85 * MS)], 'start_ns': START}


def as_scopes(trace):
    """The same trace as ``perf/lib/scopes.py:load`` gives it."""
    return {'device': 0, 'modules': [],
            'ops': [('op', a, b - a, None) for a, b in trace['busy']],
            'host': {k: [e + (None, None, None) for e in v]
                     for k, v in trace['host'].items()}}


def records(walls=(0.098, 0.0981), waits=(0.010, 0.0102)):
    """What the library's ring holds after a traced run: the oracle's
    call (before the window), window (a)'s two, a fetch that was its
    own root, and window (b)'s slower calls."""
    def rec(root, t_ms, wall, wait, off_ns=0):
        return {'root': root, 't0_ns': START + int(t_ms * MS) + off_ns,
                'wall_s': wall, 'syncs': 1, 'sync_wait_s': wait,
                'retrace_s': 0.01,
                'self_s': {root: wall - wait, 'sync.x': wait}}
    return [rec('fftpower.run', -500, 0.5, 0.2),
            rec('fftpower.run', 1, walls[0], waits[0], 3000),
            rec('sync.alone', 50, 0.001, 0.001),
            rec('fftpower.run', 121, walls[1], waits[1], 4000),
            rec('fftpower.run', 400, 0.3, 0.1),
            rec('fftpower.run', 800, 0.31, 0.1)]


def ctx_of(tmp_path, monkeypatch, trace, ring=None, spans=None):
    for name in ('scopes.json', 'hostledger.json'):
        (tmp_path / name).unlink(missing_ok=True)
    monkeypatch.setattr(xplane, 'find_xplane', lambda d: 'x.pb')
    monkeypatch.setattr(
        scopes, '_of_path',
        lambda path, ncalls: scopes.reduce(as_scopes(trace), ncalls))
    monkeypatch.setattr(hostledger, 'load', lambda path: trace)
    monkeypatch.setattr(hostledger, 'ring', lambda: ring)
    hostledger._of_path.cache_clear()
    spans_of = xplane.call_spans(trace['host'])
    t0, t1 = xplane.window_of(spans_of) if spans_of else (0.0, 1.0)
    return {'outdir': str(tmp_path), 'spans': spans,
            'ncalls_b': 2 if spans is not None else 0,
            'xplane': {'ncalls': len(spans_of), 't0': t0, 't1': t1,
                       'window_s': (t1 - t0) / 1e9,
                       'window_from': 'call_annotations' if spans_of
                       else 'device_events'}}


def test_gaps_by_the_hosts_innermost_scope(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, lab_trace())
    red = hostledger.of_run(ctx)
    assert red['by_scope'] == pytest.approx({
        'mesh.compute': 0.020, 'fftpower.coords': 0.012,
        'sync.fftpower.binning': 0.010, 'unscoped': 0.007})
    # the same seconds by what the host was under while they passed:
    # the gap that begins under mesh.compute (at 25 ms) outlasts it
    # (32), runs on under the root alone to 40 and ends 5 ms into
    # fftpower.coords; the one that begins under coords (60) outlasts
    # it at 65; the fetch's (80 to 90) outlasts the fetch (85) and
    # ends under fftpower.result; the last begins under the root alone
    assert sum(red['during'].values()) == pytest.approx(red['gap_s'])
    assert red['during']['mesh.compute'] == pytest.approx(0.007)
    assert red['during']['fftpower.coords'] == pytest.approx(0.010)
    assert red['during']['sync.fftpower.binning'] == pytest.approx(0.005)
    assert red['during']['fftpower.result'] == pytest.approx(0.005)
    assert red['during']['unscoped'] == pytest.approx(0.022)
    assert red['between_calls_s'] == pytest.approx(0.022 / 2)
    assert traced_call_s.read(ctx) == pytest.approx(0.220 / 2)
    assert host_gap_s.read(ctx) == pytest.approx(0.049)
    assert host_gap_s.read(ctx) == pytest.approx(red['gap_s'])
    assert unscoped_gap_share.read(ctx) == pytest.approx(100 * 7 / 49.0)
    assert sync_gap_s.read(ctx) == pytest.approx(0.010)
    assert syncs_per_call.read(ctx) == 1.0
    # the old reader labels by the outermost scope and knows no fetch
    assert scopes.of_run(ctx)['idle_gaps'] == pytest.approx({
        'mesh.compute': 0.020, 'fftpower.coords': 0.012,
        'fftpower.result': 0.010, 'in_call.no_scope': 0.007,
        'between_calls': 0.011})
    said = json.load(open(tmp_path / 'hostledger.json'))
    assert said['by_scope'] == pytest.approx(red['by_scope'])
    assert 'ring' not in said


def test_gaps_under_a_root_alone_are_unscoped(tmp_path, monkeypatch):
    # the parent: its root (and here nothing else) on the host line
    ctx = ctx_of(tmp_path, monkeypatch, lab_trace(scoped=False))
    assert host_gap_s.read(ctx) == pytest.approx(0.049)
    assert unscoped_gap_share.read(ctx) == pytest.approx(100.0)
    assert sync_gap_s.read(ctx) is None
    assert syncs_per_call.read(ctx) is None
    # scopes, but a program from before ``fetch``
    ctx = ctx_of(tmp_path, monkeypatch, lab_trace(fetches=False))
    assert unscoped_gap_share.read(ctx) == pytest.approx(100 * 7 / 49.0)
    assert hostledger.of_run(ctx)['by_scope']['fftpower.result'] \
        == pytest.approx(0.010)
    assert sync_gap_s.read(ctx) is None
    assert syncs_per_call.read(ctx) is None


def test_every_host_line_counts_and_roots_do_not(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, served_trace())
    red = hostledger.of_run(ctx)
    assert red['by_scope'] == pytest.approx({
        'unscoped': 0.010, 'sync.serve.result': 0.015})
    assert unscoped_gap_share.read(ctx) == pytest.approx(40.0)
    assert sync_gap_s.read(ctx) == pytest.approx(0.015)
    assert syncs_per_call.read(ctx) == 1.0
    assert red['roots'] == [('serve.request', 0.0, 95 * MS)]


def test_ring_records_are_selected_by_time(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, lab_trace(), ring=records())
    red = hostledger.of_run(ctx)
    assert [r['wall_s'] for r in red['records']] == [0.098, 0.001, 0.0981]
    assert host_work_s.read(ctx) == pytest.approx(
        (0.098 - 0.010 + 0.0981 - 0.0102) / 2)
    assert hostledger.clock_offsets(red, red['records'], START) \
        == [3000, 4000]
    said = json.load(open(tmp_path / 'hostledger.json'))
    assert said['clock_offset_ns'] == {'n': 2, 'min': 3000, 'max': 4000}
    assert said['ring']['root'] == 'fftpower.run'
    assert said['ring']['records'] == 2
    assert said['ring']['parts_over_wall'] == pytest.approx([1.0, 1.0])
    assert said['ring']['root_over_call'] == pytest.approx(0.98, rel=1e-3)
    # as many records, at other times: none is window (a)'s
    late = [dict(r, t0_ns=r['t0_ns'] + 10 ** 10) for r in records()]
    ctx = ctx_of(tmp_path, monkeypatch, lab_trace(), ring=late)
    assert host_work_s.read(ctx) is None
    # a program without a ring, a trace without its clock
    assert host_work_s.read(ctx_of(tmp_path, monkeypatch,
                                   lab_trace())) is None
    blind = dict(lab_trace(), start_ns=None)
    assert host_work_s.read(ctx_of(tmp_path, monkeypatch, blind,
                                   ring=records())) is None


def test_retrace_from_the_three_compile_spans(tmp_path, monkeypatch):
    spans = [{'name': n, 'dur': d} for n, d in (
        ('compile.trace', 0.05), ('compile.lower', 0.03),
        ('compile.backend', 0.02), ('compile.trace', 0.05),
        ('compile.lower', 0.03), ('compile.backend', 0.02),
        # the first-call wall of a labelled jit holds the same seconds
        ('compile.fftpower.binning', 0.2), ('paint', 0.1))]
    ctx = ctx_of(tmp_path, monkeypatch, lab_trace(), spans=spans)
    assert retrace_s.read(ctx) == pytest.approx(0.1)
    # the parent writes the last stage alone
    ctx['spans'] = [s for s in spans if s['name'] != 'compile.trace'
                    and s['name'] != 'compile.lower']
    assert retrace_s.read(ctx) == pytest.approx(0.02)
    # a cached program: a window (b) that re-traced nothing
    ctx['spans'] = [{'name': 'paircount.tiles', 'dur': 0.3}]
    assert retrace_s.read(ctx) == 0.0
    assert retrace_s.read(dict(ctx, spans=None)) is None
    assert retrace_s.read(dict(ctx, ncalls_b=0)) is None


def test_readers_say_nothing_where_there_is_nothing_to_read(
        tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, lab_trace())
    for blank in (dict(ctx, outdir=None, xplane=None),
                  dict(ctx, outdir=str(tmp_path / 'none'), xplane=None)):
        monkeypatch.setattr(xplane, 'find_xplane',
                            lambda d: (_ for _ in ()).throw(
                                FileNotFoundError(d)))
        for reader in READERS:
            assert reader.read(blank) is None
    # a trace without call annotations, one without device ops, one
    # whose device never idled inside a call
    for tr in (dict(lab_trace(), host={}), dict(lab_trace(), busy=[]),
               dict(lab_trace(), busy=[(0, 220 * MS)])):
        c = ctx_of(tmp_path, monkeypatch, tr)
        for reader in (unscoped_gap_share, sync_gap_s, syncs_per_call,
                       host_work_s)[:1 if tr['host'] and tr['busy']
                                    else 4]:
            assert reader.read(c) is None


def test_a_recorded_lab_call_reads_the_same_gaps_both_ways():
    """A 64^3 lab call recorded on the chip (PR 25): the gaps by
    innermost scope sum to what ``scopes.reduce`` calls idle inside
    the call, and ``union`` is ``xplane.busy_intervals``."""
    with open(os.path.join(HERE, 'data', 'trace_lab_64.json')) as f:
        rec = json.load(f)
    ops = [tuple(e[:3]) for e in rec['ops']]
    mine = {'host': {k: [tuple(e[:3]) for e in v]
                     for k, v in rec['host'].items()},
            'busy': hostledger.union([s for _, s, _ in ops],
                                     [s + d for _, s, d in ops]),
            'start_ns': None}
    lo, hi = min(s for _, s, _ in ops), max(s + d for _, s, d in ops)
    assert mine['busy'] == pytest.approx(
        xplane.busy_intervals(ops, lo, hi))
    rec['ops'] = [tuple(e) for e in rec['ops']]
    rec['modules'] = [tuple(e) for e in rec['modules']]
    rec['host'] = {k: [tuple(e) for e in v]
                   for k, v in rec['host'].items()}
    old, new = scopes.reduce(rec), hostledger.reduce(mine)
    inside = sum(v for k, v in old['idle_gaps'].items()
                 if k != 'between_calls')
    assert new['gap_s'] == pytest.approx(inside) and inside > 0
    assert new['between_calls_s'] == pytest.approx(
        old['idle_gaps'].get('between_calls', 0.0))
    assert new['sync_marks'] == 0       # recorded before ``fetch``


def test_load_reads_annotations_and_the_sessions_clock(tmp_path):
    """The one function that touches the profiler's file, on a profile
    of this machine: the ``nbk.`` and call annotations, and a session
    start that puts them on ``time.time_ns()``'s clock.  (No device
    plane on the CPU: ``busy`` is empty and the reduction ``None``.)"""
    import time
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    stamps = []
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation(xplane.CALL):
                with jax.profiler.TraceAnnotation('nbk.fftpower.run'):
                    stamps.append(time.time_ns())
                    with jax.profiler.TraceAnnotation('other'):
                        time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    trace = hostledger.load(xplane.find_xplane(str(tmp_path)))
    events = sorted(e for line in trace['host'].values() for e in line)
    assert [e[0] for e in events] == ['nbk.fftpower.run'] * 3 \
        + [xplane.CALL] * 3
    assert trace['busy'] == [] and hostledger.reduce(trace) is None
    roots = sorted(e[1] for e in events if e[0] == 'nbk.fftpower.run')
    offsets = [t - (s + trace['start_ns']) for s, t in zip(roots, stamps)]
    # the stamp is read right after the annotation is entered
    assert all(0 <= o < 1e6 for o in offsets), offsets
