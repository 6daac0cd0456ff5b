"""Tests for the fleet-level diagnostics: multi-host trace merging
with clock alignment (analyze.py) — straggler tables, critical-path
attribution, hung-collective and heartbeat post-mortems — and the
bench regression tracker (regress.py) with injected regression and
malformed-record gating."""

import json
import os
import time

import pytest

from nbodykit_tpu.diagnostics import analyze as A
from nbodykit_tpu.diagnostics import regress as R
from nbodykit_tpu.diagnostics.__main__ import main as cli_main

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


# ---------------------------------------------------------------------------
# synthetic two-process traces

def _w(path, records):
    with open(path, 'w') as f:
        for r in records:
            f.write(json.dumps(r) + '\n')


def _span(pid, sid, name, ts, dur, depth=0, par=0, ok=True):
    return {'t': 'span', 'id': sid, 'par': par, 'name': name,
            'ts': ts, 'dur': dur, 'depth': depth, 'pid': pid, 'ok': ok}


def _begin(pid, sid, name, ts, depth=0, par=0):
    return {'t': 'b', 'id': sid, 'par': par, 'name': name,
            'ts': ts, 'depth': depth, 'pid': pid}


SKEW = 5.0          # pid 202's wall clock runs 5 s ahead of pid 101


def _two_process_trace(tmp_path):
    """Two workers, identical collective program, pid 202 with a +5 s
    wall-clock skew and consistently late into every collective."""
    t = 100.0
    p101 = [
        {'t': 'meta', 'version': 1, 'pid': 101, 'ts': t},
        _span(101, 1, 'barrier', t + 0.00, 0.30),
        _span(101, 2, 'paint', t + 1.0, 2.0),
        _span(101, 3, 'exchange', t + 1.5, 1.0, depth=1, par=2),
        _span(101, 4, 'fft.r2c', t + 3.0, 1.0),
        _span(101, 5, 'fftpower.binning', t + 4.0, 0.5),
        _span(101, 6, 'barrier', t + 5.0, 0.1),
    ]
    s = t + SKEW    # 202 records skewed timestamps, same true events
    p202 = [
        {'t': 'meta', 'version': 1, 'pid': 202, 'ts': s},
        _span(202, 1, 'barrier', s + 0.20, 0.10),        # in 0.2 late
        _span(202, 2, 'paint', s + 1.0, 1.0),
        _span(202, 3, 'exchange', s + 1.2, 0.5, depth=1, par=2),
        _span(202, 4, 'fft.r2c', s + 3.5, 0.5),          # in 0.5 late
        _span(202, 5, 'fftpower.binning', s + 4.0, 0.5),
        _span(202, 6, 'barrier', s + 4.8, 0.3),
    ]
    _w(str(tmp_path / 'trace-101.jsonl'), p101)
    _w(str(tmp_path / 'trace-202.jsonl'), p202)
    return str(tmp_path)


def test_clock_alignment_recovers_skew(tmp_path):
    res = A.analyze(_two_process_trace(tmp_path))
    assert res['nprocs'] == 2 and res['pids'] == [101, 202]
    assert res['clock_offsets']['101'] == 0.0
    # collective END times align, so 202's recovered offset is -SKEW
    assert res['clock_offsets']['202'] == pytest.approx(-SKEW,
                                                        abs=1e-6)
    assert res['unaligned_pids'] == []
    assert res['anchors_used'] >= 3          # 2 barriers + fft.r2c


def test_merged_timeline_is_time_ordered_across_pids(tmp_path):
    res = A.analyze(_two_process_trace(tmp_path))
    tl = res['timeline']
    assert {r['pid'] for r in tl} == {101, 202}
    assert [r['ts'] for r in tl] == sorted(r['ts'] for r in tl)
    # after alignment the two 'fftpower.binning' begins coincide
    bins = [r for r in tl if r['name'] == 'fftpower.binning']
    assert len(bins) == 2
    assert bins[0]['ts'] == pytest.approx(bins[1]['ts'], abs=1e-6)


def test_straggler_table(tmp_path):
    res = A.analyze(_two_process_trace(tmp_path))
    per_name = res['stragglers']['per_name']
    # pid 202 was last into the first barrier by 0.2 s...
    barrier = per_name['barrier']
    assert barrier['worst_straggler'] == '202'
    assert barrier['max_skew_s'] == pytest.approx(0.2, abs=1e-6)
    # ...and into the FFT by 0.5 s
    fft = per_name['fft.r2c']
    assert fft['worst_straggler'] == '202'
    assert fft['max_skew_s'] == pytest.approx(0.5, abs=1e-6)
    rows = res['stragglers']['per_collective']
    first_barrier = next(r for r in rows if r['name'] == 'barrier'
                         and r['occurrence'] == 0)
    assert first_barrier['straggler'] == 202


def test_critical_path_attribution(tmp_path):
    res = A.analyze(_two_process_trace(tmp_path))
    cp = res['critical_path']
    # nested exchange time is charged to exchange, not paint:
    # pid 101 painted 2.0 s of which 1.0 s was the exchange
    assert cp['per_process']['101']['paint'] == pytest.approx(1.0)
    assert cp['per_process']['101']['exchange'] == pytest.approx(1.0)
    # the breakdown takes the WORST process per phase
    assert cp['phases']['paint'] == pytest.approx(1.0)
    assert cp['phases']['dfft'] == pytest.approx(1.0)
    assert cp['phases']['binning'] == pytest.approx(0.5)
    # wall spans first begin to last end (aligned)
    assert cp['wall_s'] == pytest.approx(5.1, abs=1e-6)
    text = A.render_analysis(res)
    assert 'critical path' in text and 'straggler report' in text


def test_hung_collective_reported_not_crash(tmp_path):
    """One trace is missing the close event of a collective: the
    analyzer must name the hung span and the process stuck in it."""
    _w(str(tmp_path / 'trace-7.jsonl'), [
        _span(7, 1, 'paint', 10.0, 1.0),
        _begin(7, 2, 'exchange', 11.0),
        _span(7, 2, 'exchange', 11.0, 0.5),
        _span(7, 3, 'barrier', 12.0, 0.1),
    ])
    _w(str(tmp_path / 'trace-8.jsonl'), [
        _span(8, 1, 'paint', 10.0, 1.0),
        _begin(8, 2, 'exchange', 11.0),      # never closed: wedged
        _span(8, 3, 'barrier', 12.0, 0.1),
    ])
    res = A.analyze(str(tmp_path))
    hung = res['hangs']['hung_collectives']
    assert len(hung) == 1
    assert hung[0]['name'] == 'exchange'
    assert hung[0]['open_pid'] == 8
    assert hung[0]['closed_pids'] == [7]
    text = A.render_analysis(res)
    assert 'HUNG COLLECTIVES' in text and 'exchange' in text


def test_heartbeat_gap_flags_silent_process(tmp_path):
    hb7 = [{'t': 'hb', 'pid': 7, 'ts': 10.0 + i, 'iv': 1.0}
           for i in range(20)]
    hb9 = [{'t': 'hb', 'pid': 9, 'ts': 10.0 + i, 'iv': 1.0}
           for i in range(5)]                # falls silent at t=14
    _w(str(tmp_path / 'trace-7.jsonl'),
       [_span(7, 1, 'paint', 10.0, 1.0)] + hb7)
    _w(str(tmp_path / 'trace-9.jsonl'),
       [_span(9, 1, 'paint', 10.0, 1.0)] + hb9)
    res = A.analyze(str(tmp_path))
    assert res['heartbeat']['9']['silent'] is True
    assert res['heartbeat']['7']['silent'] is False
    assert 'SILENT PROCESSES' in A.render_analysis(res)


def test_analyze_empty_and_torn(tmp_path):
    assert A.analyze(str(tmp_path)).get('empty') is True
    with open(str(tmp_path / 'trace-1.jsonl'), 'w') as f:
        f.write(json.dumps(_span(1, 1, 'paint', 1.0, 1.0)) + '\n')
        f.write('{"t":"span","name":"torn')
    res = A.analyze(str(tmp_path))
    assert res['torn_lines'] == 1 and res['nspans'] == 1


def test_analyze_cli(tmp_path, capsys):
    _two_process_trace(tmp_path)
    assert cli_main(['--analyze', str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert 'merged timeline' in out
    assert '101' in out and '202' in out
    assert cli_main(['--analyze', str(tmp_path / 'nope')]) == 2


# ---------------------------------------------------------------------------
# bench regression tracking

NOW = time.time()


def _round(path, n, value, metric='fftpower_wallclock_nmesh256',
           rc=0, note=None, extra=None, parsed=True):
    data = {'n': n, 'rc': rc}
    if parsed:
        rec = {'metric': metric, 'value': value, 'unit': 's',
               'platform': 'tpu'}
        if note:
            rec['note'] = note
        if extra:
            rec.update(extra)
        data['parsed'] = rec
    with open(path, 'w') as f:
        json.dump(data, f)


def test_regress_flags_injected_regression(tmp_path):
    root = str(tmp_path)
    _round(os.path.join(root, 'BENCH_r01.json'), 1, 1.00)
    _round(os.path.join(root, 'BENCH_r02.json'), 2, 2.00)  # 2x slower
    history = R.build_history(root, now=NOW)
    by_file = {e['file']: e for e in history['rounds']}
    assert by_file['BENCH_r01.json']['verdict'] == 'ok'
    assert by_file['BENCH_r02.json']['verdict'] == 'regression'
    assert '+100%' in by_file['BENCH_r02.json']['why']
    # the history landed atomically next to the rounds
    with open(os.path.join(root, 'BENCH_HISTORY.json')) as f:
        on_disk = json.load(f)
    assert on_disk['summary']['regression'] == 1
    text = R.render_regress(history)
    assert 'REGRESSION' in text and 'WARN' in text
    # a regression warns loudly but does not fail the gate
    assert R.gate_rc(history) == 0


def test_regress_improved_advances_the_comparison(tmp_path):
    """Each round is judged against the previous round of the SAME
    metric: a faster round reads 'improved' and becomes the value the
    next one is held to; another metric starts its own series."""
    root = str(tmp_path)
    _round(os.path.join(root, 'BENCH_r01.json'), 1, 2.0)
    _round(os.path.join(root, 'BENCH_r02.json'), 2, 1.0)
    _round(os.path.join(root, 'BENCH_r03.json'), 3, 1.1)
    _round(os.path.join(root, 'BENCH_r04.json'), 4, 9.0,
           metric='fftpower_wallclock_nmesh1024')
    history = R.build_history(root, now=NOW, write=False)
    v = {e['file']: e['verdict'] for e in history['rounds']}
    assert v == {'BENCH_r01.json': 'ok', 'BENCH_r02.json': 'improved',
                 'BENCH_r03.json': 'ok', 'BENCH_r04.json': 'ok'}
    assert 'RESULT: OK' in R.render_regress(history)


def test_regress_malformed_record_fails_gate(tmp_path, capsys):
    root = str(tmp_path)
    _round(os.path.join(root, 'BENCH_r01.json'), 1, 1.0)
    # rc=0 round whose record is missing value/unit: the smoke-gate
    # failure mode
    with open(os.path.join(root, 'BENCH_r02.json'), 'w') as f:
        json.dump({'n': 2, 'rc': 0, 'parsed': {'metric': 'm'}}, f)
    with open(os.path.join(root, 'BENCH_r03.json'), 'w') as f:
        f.write('{not json')
    history = R.build_history(root, now=NOW, write=False)
    v = {e['file']: e['verdict'] for e in history['rounds']}
    assert v['BENCH_r02.json'] == 'malformed'
    assert v['BENCH_r03.json'] == 'malformed'
    assert R.gate_rc(history) == 1
    assert cli_main(['--regress', root]) == 1
    assert 'FAIL' in capsys.readouterr().out


def test_regress_failed_rounds_are_no_result_not_malformed(tmp_path):
    root = str(tmp_path)
    _round(os.path.join(root, 'BENCH_r01.json'), 1, None, rc=124,
           parsed=False)
    _round(os.path.join(root, 'BENCH_r02.json'), 2, -1, rc=1,
           extra={'error': 'device hung'})
    history = R.build_history(root, now=NOW, write=False)
    assert all(e['verdict'] == 'no-result' for e in history['rounds'])
    assert R.gate_rc(history) == 0


def test_regress_committed_records_are_well_formed():
    """--regress over the repo's committed BENCH_r*.json: nothing
    committed may be malformed (the smoke gate runs this)."""
    history = R.build_history(REPO, write=False)
    assert history['rounds'], 'no committed bench records found'
    assert history['summary']['malformed'] == 0
    assert R.gate_rc(history) == 0


# ---------------------------------------------------------------------------
# doctor

def test_doctor_self_check_only(capsys):
    assert cli_main(['--doctor', '--self-check-only']) == 0
    out = capsys.readouterr().out
    assert 'nbodykit-tpu doctor' in out
    assert 'self-check   OK' in out
    assert 'VERDICT: OK' in out


def test_doctor_full_block(tmp_path, capsys):
    _two_process_trace(tmp_path)
    root = str(tmp_path / 'bench')
    os.makedirs(root)
    _round(os.path.join(root, 'BENCH_r01.json'), 1, 1.0)
    rc = cli_main(['--doctor', '--trace', str(tmp_path),
                   '--root', root])
    out = capsys.readouterr().out
    assert rc == 0
    assert 'analyze      OK' in out
    assert 'regress      OK' in out
    assert 'VERDICT: OK' in out


def test_doctor_fails_on_hung_collective(tmp_path, capsys):
    _w(str(tmp_path / 'trace-7.jsonl'),
       [_span(7, 1, 'exchange', 1.0, 0.5)])
    _w(str(tmp_path / 'trace-8.jsonl'),
       [_begin(8, 1, 'exchange', 1.0)])
    root = str(tmp_path / 'bench')
    os.makedirs(root)
    rc = cli_main(['--doctor', '--trace', str(tmp_path),
                   '--root', root])
    out = capsys.readouterr().out
    assert rc == 1
    assert 'hung' in out and 'VERDICT: FAIL' in out
