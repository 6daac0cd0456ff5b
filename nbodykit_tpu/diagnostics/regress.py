"""Bench regression tracking: the BENCH_r*.json trajectory as data.

Every round commits one ``BENCH_rNN.json`` (the driver's record of
``python bench.py``: rc, output tail, the parsed headline JSON line),
plus the committed CPU measurement store ``BASELINE_CPU.json``.  This
module makes the trajectory machine-checked:

- :func:`load_rounds` ingests the family and normalizes each round to
  one entry (metric, value, platform, note);
- :func:`classify` assigns each entry a verdict —

  ``malformed``    unreadable JSON, or a "successful" round whose
                   record is missing metric/value/unit (gate-failing:
                   scripts/smoke.sh runs ``--regress`` so a broken
                   bench record cannot land),
  ``no-result``    the round produced no number and said so (rc != 0);
  ``regression``   value worse than the previous round's same-metric
                   value by more than ``threshold`` (relative),
  ``improved`` / ``ok`` otherwise;

- :func:`build_history` writes the whole thing to ``BENCH_HISTORY.json``
  atomically (same tmp+rename discipline as report.py) so the next
  round — and the doctor — reads one file, not eight.
"""

import calendar
import glob
import json
import os
import re
import time

from .trace import atomic_write

HISTORY_NAME = 'BENCH_HISTORY.json'
PRECISION_NAME = 'PRECISION.json'
ROUND_GLOBS = ('BENCH_r*.json', 'MULTICHIP_r*.json')
CACHE_FILES = ('BASELINE_CPU.json',)
_TS_RE = re.compile(r'(\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2})Z?')


def parse_utc(ts):
    """Epoch seconds for a ``YYYY-MM-DDTHH:MM:SSZ`` stamp, or None."""
    if not ts:
        return None
    m = _TS_RE.search(str(ts))
    if not m:
        return None
    try:
        return calendar.timegm(
            time.strptime(m.group(1), '%Y-%m-%dT%H:%M:%S'))
    except ValueError:
        return None


def _round_key(path):
    m = re.search(r'_r(\d+)\.json$', path)
    return (os.path.basename(path).split('_r')[0],
            int(m.group(1)) if m else 0)


def load_rounds(root):
    """Normalize every committed round file under ``root`` into one
    entry per file, oldest round first per family."""
    entries = []
    for pattern in ROUND_GLOBS:
        for path in sorted(glob.glob(os.path.join(root, pattern)),
                           key=_round_key):
            fname = os.path.basename(path)
            entry = {'file': fname, 'round': _round_key(path)[1],
                     'family': fname.split('_r')[0]}
            try:
                with open(path) as f:
                    data = json.load(f)
            except (OSError, ValueError) as e:
                entry.update(load_error='unreadable: %s' % e)
                entries.append(entry)
                continue
            entry['rc'] = data.get('rc')
            # some round families (MULTICHIP_r*) record a pass/fail
            # probe, not a parsed headline metric — legitimate, not
            # malformed
            entry['has_headline'] = 'parsed' in data
            for k in ('ok', 'skipped'):
                if k in data:
                    entry[k] = data[k]
            rec = data.get('parsed')
            if isinstance(rec, dict):
                for k in ('metric', 'value', 'unit', 'platform',
                          'vs_baseline', 'note', 'measured_at'):
                    if rec.get(k) is not None:
                        entry[k] = rec[k]
                if rec.get('error') is not None:
                    entry['record_error'] = rec['error']
            entries.append(entry)
    return entries


def classify(entries, threshold=0.25):
    """Assign each entry a ``verdict`` (+ ``why``) in place and return
    the entries.  Regressions compare consecutive rounds of the SAME
    metric (a 256-cubed timing vs a 1024-cubed one is not a trend)."""
    last_by_metric = {}
    for entry in entries:
        if entry.get('load_error'):
            entry['verdict'] = 'malformed'
            entry['why'] = entry['load_error']
            continue
        if not entry.get('has_headline'):
            entry['verdict'] = 'no-result'
            entry['why'] = ('round family records no headline metric '
                            '(ok=%s, skipped=%s)'
                            % (entry.get('ok'), entry.get('skipped')))
            continue
        value = entry.get('value')
        ok_shape = (entry.get('metric') and entry.get('unit')
                    and isinstance(value, (int, float)))
        if not ok_shape or (isinstance(value, (int, float))
                            and value <= 0):
            if entry.get('rc') not in (0, None) or \
                    (isinstance(value, (int, float)) and value <= 0):
                entry['verdict'] = 'no-result'
                entry['why'] = ('round recorded a failure (rc=%s)%s'
                                % (entry.get('rc'),
                                   ': %s' % entry['record_error']
                                   if entry.get('record_error') else ''))
            else:
                entry['verdict'] = 'malformed'
                entry['why'] = ('rc=0 but the record is missing '
                                'metric/value/unit')
            continue
        prev = last_by_metric.get(entry['metric'])
        verdict, why = 'ok', ''
        if prev is not None and prev > 0:
            rel = (value - prev) / prev
            if rel > threshold:
                verdict = 'regression'
                why = ('%.4g s vs %.4g s previous (+%.0f%%, '
                       'threshold %.0f%%)'
                       % (value, prev, 100 * rel, 100 * threshold))
            elif rel < -threshold:
                verdict = 'improved'
                why = '%.4g s vs %.4g s previous (%.0f%%)' \
                    % (value, prev, 100 * rel)
        entry['verdict'] = verdict
        if why:
            entry['why'] = why
        last_by_metric[entry['metric']] = value
    return entries


def load_caches(root, now=None):
    """Summarize the committed measurement stores: per metric, value +
    measurement age."""
    now = time.time() if now is None else now
    out = {}
    for fname in CACHE_FILES:
        path = os.path.join(root, fname)
        try:
            with open(path) as f:
                data = json.load(f)
        except OSError:
            continue
        except ValueError as e:
            out[fname] = {'error': 'unreadable: %s' % e}
            continue
        summary = {}
        for metric, rec in sorted(data.get('results', {}).items()):
            ts = parse_utc(rec.get('measured_at'))
            age = None if ts is None else round((now - ts) / 3600.0, 1)
            summary[metric] = {
                'value': rec.get('value'),
                'platform': rec.get('platform'),
                'measured_at': rec.get('measured_at'),
                'age_hours': age,
            }
        out[fname] = summary
    return out


def lint_summary(root):
    """Current shard-safety lint counts for the round record: the
    committed ``lint_baseline.json`` is expected to *shrink* over PRs,
    so the count is tracked in BENCH_HISTORY.json like a bench metric
    — and since PR 6 per rule FAMILY (NBK1xx collectives ...
    NBK5xx memory/donation), so shrinkage in one family cannot mask
    growth in another.  Returns None when ``root`` holds no lintable
    package; never raises (a broken linter must not wedge the bench
    gate — the error string is recorded instead)."""
    if not os.path.isdir(os.path.join(root, 'nbodykit_tpu')):
        return None
    try:
        from .. import lint as lint_mod
        targets = lint_mod.default_targets(root)
        bl = os.path.join(root, 'lint_baseline.json')
        new, grandfathered, unused = lint_mod.run_lint(
            targets, baseline_path=bl if os.path.exists(bl) else None)
        return {
            'findings': len(new) + len(grandfathered),
            'new': len(new),
            'baselined': len(grandfathered),
            'stale_baseline_entries': len(unused),
            'families': lint_mod.family_stats(new, grandfathered),
            'baseline': os.path.basename(bl)
            if os.path.exists(bl) else None,
        }
    except Exception as e:      # pragma: no cover - defensive
        return {'error': str(e)}


def resilience_summary(root, now=None):
    """Resilience posture for the round record: how many committed
    records were produced by a resumed run, and whether checkpoints
    are pending under ``root``/BENCH_CKPT (a pending checkpoint is an
    interrupted measurement nobody has relaunched — exactly the
    round-5 evidence loss, now visible).  Never raises."""
    now = time.time() if now is None else now
    out = {'resumed_records': 0, 'pending_checkpoints': 0,
           'oldest_checkpoint_hours': None}
    for fname in ('BENCH_STAGED.json',) + CACHE_FILES:
        try:
            with open(os.path.join(root, fname)) as f:
                recs = json.load(f).get('results', {})
        except (OSError, ValueError):
            continue
        out['resumed_records'] += sum(
            1 for rec in recs.values()
            if isinstance(rec, dict) and rec.get('resumed'))
    ckpt_dir = os.path.join(root, 'BENCH_CKPT')
    if os.path.isdir(ckpt_dir):
        try:
            from ..resilience import CheckpointStore
            store = CheckpointStore(ckpt_dir)
            keys = store.keys()
            out['pending_checkpoints'] = len(keys)
            age = store.oldest_age_s(now=now)
            if age is not None:
                out['oldest_checkpoint_hours'] = round(age / 3600.0, 1)
        except Exception as e:     # pragma: no cover - defensive
            out['error'] = str(e)
    return out


def fleet_summary(root, now=None):
    """Fleet-survivability posture for the round record
    (nbodykit_tpu.resilience.fleet, docs/RESILIENCE.md): how many
    committed records came from preempted or shrunk-and-re-formed
    runs, and the state of the coordinated checkpoint directory —
    sealed vs incomplete (shards without a manifest: a seal
    interrupted mid-commit) vs orphaned ``*.tmp`` debris.  Never
    raises."""
    now = time.time() if now is None else now
    out = {'preempted_records': 0, 'reformed_records': 0,
           'reformations': []}
    for fname in ('BENCH_STAGED.json',) + CACHE_FILES:
        try:
            with open(os.path.join(root, fname)) as f:
                recs = json.load(f).get('results', {})
        except (OSError, ValueError):
            continue
        for rec in recs.values():
            if not isinstance(rec, dict):
                continue
            if rec.get('preempted'):
                out['preempted_records'] += 1
            if rec.get('reformed_from'):
                out['reformed_records'] += 1
                out['reformations'].append(
                    {'metric': rec.get('metric'),
                     'reformed_from': rec.get('reformed_from'),
                     'reformed_to': rec.get('reformed_to')})
    ckpt_dir = os.path.join(root, 'BENCH_CKPT')
    if os.path.isdir(ckpt_dir):
        try:
            from ..resilience import FleetCheckpointStore
            survey = FleetCheckpointStore(ckpt_dir).survey()
            out['sealed_manifests'] = survey.get('sealed', 0)
            out['incomplete_seqs'] = survey.get('incomplete', 0)
            out['orphan_tmp'] = survey.get('orphan_tmp', 0)
        except Exception as e:     # pragma: no cover - defensive
            out['error'] = str(e)
    return out


def serve_summary(root):
    """Serving posture for the round record: the latest committed
    ``servetrace_*`` bench record (nbodykit_tpu.serve via ``bench.py
    --serve-trace``) reduced to the numbers the doctor judges —
    throughput, tail latency, the admission/eviction/fault ledger and
    above all ``lost``, which must be zero.  ``None`` when no round
    carries a serve record; never raises.

    Reads the round files directly: :func:`load_rounds` flattens the
    ``parsed`` record to the headline keys, and the serve ledger
    (lost/retried/degraded/...) is not among them."""
    latest = None
    try:
        for pattern in ROUND_GLOBS:
            for path in sorted(glob.glob(os.path.join(root, pattern)),
                               key=_round_key):
                try:
                    with open(path) as f:
                        rec = json.load(f).get('parsed') or {}
                except (OSError, ValueError):
                    continue
                metric = str(rec.get('metric', ''))
                if not metric.startswith('servetrace'):
                    continue
                latest = {
                    'round': os.path.basename(path),
                'metric': metric,
                'requests': rec.get('requests'),
                'rps': rec.get('rps'),
                'p50_s': rec.get('p50_s'),
                'p99_s': rec.get('p99_s'),
                'completed': rec.get('completed'),
                'rejected': rec.get('rejected'),
                'evicted': rec.get('evicted'),
                'failed': rec.get('failed'),
                'lost': rec.get('lost'),
                'retried': rec.get('retried'),
                'degraded': rec.get('degraded',
                                    rec.get('fault_degraded')),
                'resumed': rec.get('resumed'),
                'admit_degraded': rec.get('admit_degraded'),
                'faults_injected': rec.get('faults_injected'),
            }
    except Exception as e:      # pragma: no cover - defensive
        return {'error': str(e)}
    return latest


def ingest_summary(root):
    """Ingestion posture for the round record: the latest committed
    ``ingest*`` bench record (``bench.py --ingest``) reduced to the
    headline throughput — GB/s from file to painted mesh, cold and
    cache-hit, overlapped vs serialized — plus the cache ledger the
    doctor's thrash verdict (evictions > hits) judges.  ``None`` when
    no round carries an ingest record; never raises."""
    latest = None
    try:
        for pattern in ROUND_GLOBS:
            for path in sorted(glob.glob(os.path.join(root, pattern)),
                               key=_round_key):
                try:
                    with open(path) as f:
                        rec = json.load(f).get('parsed') or {}
                except (OSError, ValueError):
                    continue
                metric = str(rec.get('metric', ''))
                if not metric.startswith('ingest'):
                    continue
                latest = {
                    'round': os.path.basename(path),
                    'metric': metric,
                    'rows': rec.get('rows'),
                    'bytes': rec.get('bytes'),
                    'chunk_rows': rec.get('chunk_rows'),
                    'cold_gbs': rec.get('cold_gbs'),
                    'warm_gbs': rec.get('warm_gbs'),
                    'serial_gbs': rec.get('serial_gbs'),
                    'overlap_speedup': rec.get('overlap_speedup'),
                    'host_peak_bytes': rec.get('host_peak_bytes'),
                    'cache_hits': rec.get('cache_hits'),
                    'cache_evictions': rec.get('cache_evictions'),
                    'serve_completed': rec.get('serve_completed'),
                    'serve_cache_hits': rec.get('serve_cache_hits'),
                    'serve_lost': rec.get('serve_lost'),
                }
    except Exception as e:      # pragma: no cover - defensive
        return {'error': str(e)}
    return latest


def forward_summary(root):
    """Forward-model posture for the round record: the latest
    committed ``forward_*`` bench record (``bench.py --forward``,
    docs/FORWARD.md) reduced to the numbers the doctor judges —
    backward/forward overhead, the finite-difference gradient check
    (``grad_check_ok`` False is a FAIL verdict: a forward model with a
    wrong gradient is not differentiable, however fast), and the
    recovery-vs-FFTRecon cross-correlations (``beats_baseline`` False
    is a FAIL: the gradient exists to beat the classical estimator).
    ``None`` when no round carries a forward record; never raises."""
    latest = None
    try:
        for pattern in ROUND_GLOBS:
            for path in sorted(glob.glob(os.path.join(root, pattern)),
                               key=_round_key):
                try:
                    with open(path) as f:
                        rec = json.load(f).get('parsed') or {}
                except (OSError, ValueError):
                    continue
                metric = str(rec.get('metric', ''))
                if not metric.startswith('forward'):
                    continue
                check = rec.get('grad_check') or {}
                recov = rec.get('recovery') or {}
                latest = {
                    'round': os.path.basename(path),
                    'metric': metric,
                    'nmesh': rec.get('nmesh'),
                    'npart': rec.get('npart'),
                    'pm_steps': rec.get('pm_steps'),
                    'paint_method': rec.get('paint_method'),
                    'adjoint_mode': rec.get('adjoint_mode'),
                    'forward_s': rec.get('forward_s'),
                    'grad_s': rec.get('grad_s'),
                    'grad_overhead': rec.get('grad_overhead'),
                    'grad_check_ok': rec.get('grad_check_ok'),
                    'grad_rel_err': check.get('rel_err'),
                    'r_recovered': recov.get('r_recovered'),
                    'r_fftrecon': recov.get('r_fftrecon'),
                    'beats_baseline': recov.get('beats_baseline'),
                    'grad_residual_bytes':
                        rec.get('grad_residual_bytes'),
                }
    except Exception as e:      # pragma: no cover - defensive
        return {'error': str(e)}
    return latest


def bispectrum_summary(root):
    """Higher-order-statistics posture for the round record: the
    latest committed ``bispectrum_*`` bench record (``bench.py
    --bispectrum``, docs/BISPECTRUM.md) reduced to the numbers the
    doctor judges — the FFT/direct crossover at the measured shape and
    the cross-path agreement stamps.  ``agree_ok`` False is a FAIL
    verdict: two estimators of one statistic disagreeing in their
    overlap band means one of them is wrong.  ``None`` when no round
    carries a bispectrum record; never raises."""
    latest = None
    try:
        for pattern in ROUND_GLOBS:
            for path in sorted(glob.glob(os.path.join(root, pattern)),
                               key=_round_key):
                try:
                    with open(path) as f:
                        rec = json.load(f).get('parsed') or {}
                except (OSError, ValueError):
                    continue
                metric = str(rec.get('metric', ''))
                if not metric.startswith('bispectrum'):
                    continue
                cross = rec.get('crossover') or {}
                agree = rec.get('agreement') or {}
                latest = {
                    'round': os.path.basename(path),
                    'metric': metric,
                    'nmesh': rec.get('nmesh'),
                    'npart': rec.get('npart'),
                    'nbins': rec.get('nbins'),
                    'fft_s': rec.get('fft_s'),
                    'direct_s': rec.get('direct_s'),
                    'speedup_fft_over_direct':
                        cross.get('speedup_fft_over_direct'),
                    'faster': cross.get('faster'),
                    'resolved_method': rec.get('resolved_method'),
                    'pairblock_tile': rec.get('pairblock_tile'),
                    'closure_overlap': rec.get('closure_overlap'),
                    'ntri_bit_identical':
                        agree.get('ntri_bit_identical'),
                    'b_max_rel': agree.get('b_max_rel'),
                    'agree_ok': rec.get('agree_ok'),
                }
    except Exception as e:      # pragma: no cover - defensive
        return {'error': str(e)}
    return latest


def region_summary(root):
    """Region posture for the round record: the latest committed
    ``regiontrace_*`` bench record (``bench.py --region-trace``, the
    multi-fleet front door of nbodykit_tpu.serve.region) reduced to
    the numbers the doctor judges — result-cache hit rate, structured
    spill count, elastic joins with their ``reformed_from/to``
    stamps, per-QoS-class tail latency, and above all ``lost`` and
    ``unverified_as_verified``, which must both be zero.  ``None``
    when no round carries a region record; never raises."""
    latest = None
    try:
        for pattern in ROUND_GLOBS:
            for path in sorted(glob.glob(os.path.join(root, pattern)),
                               key=_round_key):
                try:
                    with open(path) as f:
                        rec = json.load(f).get('parsed') or {}
                except (OSError, ValueError):
                    continue
                metric = str(rec.get('metric', ''))
                if not metric.startswith('regiontrace'):
                    continue
                latest = {
                    'round': os.path.basename(path),
                    'metric': metric,
                    'requests': rec.get('requests'),
                    'fleets': rec.get('fleets'),
                    'fleet_count': rec.get('fleet_count'),
                    'completed': rec.get('completed'),
                    'rejected': rec.get('rejected'),
                    'evicted': rec.get('evicted'),
                    'lost': rec.get('lost'),
                    'result_hits': rec.get('result_hits'),
                    'hit_rate': rec.get('hit_rate'),
                    'cache_corrupt': rec.get('cache_corrupt'),
                    'cache_bit_identical':
                        rec.get('cache_bit_identical'),
                    'unverified_as_verified':
                        rec.get('unverified_as_verified'),
                    'spills': rec.get('spills'),
                    'joins': rec.get('joins'),
                    'reformed_from': rec.get('reformed_from'),
                    'reformed_to': rec.get('reformed_to'),
                    'throttled': rec.get('throttled'),
                    'starved': rec.get('starved'),
                    'interactive_p50_s':
                        rec.get('interactive_p50_s'),
                    'interactive_p99_s':
                        rec.get('interactive_p99_s'),
                }
    except Exception as e:      # pragma: no cover - defensive
        return {'error': str(e)}
    return latest


def slo_summary(root):
    """SLO posture for the round record: the latest committed bench
    record carrying an ``slo`` stamp (``bench.py --serve-trace`` /
    ``--region-trace``) reduced to the judgment surface — the overall
    burn-rate verdict and per-class fast/slow burns
    (diagnostics/slo.py), the request-waterfall completeness ledger
    (every completed request must render a fully linked, orphan-free
    waterfall), and the measured tracing overhead, which the doctor
    FAILs at >= 5%.  ``None`` when no round carries an SLO stamp;
    never raises."""
    latest = None
    try:
        for pattern in ROUND_GLOBS:
            for path in sorted(glob.glob(os.path.join(root, pattern)),
                               key=_round_key):
                try:
                    with open(path) as f:
                        rec = json.load(f).get('parsed') or {}
                except (OSError, ValueError):
                    continue
                slo = rec.get('slo')
                if not isinstance(slo, dict):
                    continue
                classes = {}
                for cname, c in (slo.get('classes') or {}).items():
                    wins = c.get('windows') or {}
                    classes[cname] = {
                        'verdict': c.get('verdict'),
                        'total': c.get('total'),
                        'shed': c.get('shed'),
                        'bad': c.get('bad'),
                        'p99_s': c.get('p99_s'),
                        'fast_burn': (wins.get('fast') or {})
                        .get('burn'),
                        'slow_burn': (wins.get('slow') or {})
                        .get('burn'),
                    }
                wf = rec.get('waterfalls') \
                    if isinstance(rec.get('waterfalls'), dict) else {}
                ov = rec.get('trace_overhead') \
                    if isinstance(rec.get('trace_overhead'), dict) \
                    else {}
                latest = {
                    'round': os.path.basename(path),
                    'metric': rec.get('metric'),
                    'verdict': slo.get('verdict'),
                    'classes': classes,
                    'traces': wf.get('traces'),
                    'complete': wf.get('complete'),
                    'complete_fraction': wf.get('complete_fraction'),
                    'orphan_spans': wf.get('orphan_spans'),
                    'overhead': ov.get('overhead'),
                    'overhead_n': ov.get('n'),
                }
    except Exception as e:      # pragma: no cover - defensive
        return {'error': str(e)}
    return latest


def integrity_summary(root):
    """Data-integrity posture for the round record
    (docs/INTEGRITY.md): every committed record carrying an
    ``integrity`` stamp (tripwire violations caught / supervisor
    retries that recovered them), the latest servetrace round's
    shadow-verification ledger, and the quarantine lists riding the
    sealed fleet manifests under ``root``/BENCH_CKPT.  The one number
    the doctor FAILs on is ``unacknowledged_mismatch`` — a shadow
    re-execution that disagreed with the primary and was NOT followed
    by an integrity retry means a silently-divergent result may have
    been delivered.  ``None`` when no evidence exists; never raises.
    """
    out = {'stamped_records': 0, 'violations': 0, 'retried': 0,
           'shadow_verified': 0, 'shadow_mismatch': 0,
           'integrity_retried': 0, 'quarantined': [],
           'unacknowledged_mismatch': 0}
    found = False
    try:
        for pattern in ROUND_GLOBS:
            for path in sorted(glob.glob(os.path.join(root, pattern)),
                               key=_round_key):
                try:
                    with open(path) as f:
                        rec = json.load(f).get('parsed') or {}
                except (OSError, ValueError):
                    continue
                stamp = rec.get('integrity')
                if isinstance(stamp, dict):
                    found = True
                    out['stamped_records'] += 1
                    out['violations'] += int(stamp.get('violations',
                                                       0) or 0)
                    out['retried'] += int(stamp.get('retried', 0) or 0)
                if rec.get('shadow_verified') is not None:
                    # the servetrace ledger: keep the LATEST record's
                    # numbers (rounds sort oldest-first)
                    found = True
                    out['shadow_verified'] = \
                        int(rec.get('shadow_verified') or 0)
                    out['shadow_mismatch'] = \
                        int(rec.get('shadow_mismatch') or 0)
                    out['integrity_retried'] = \
                        int(rec.get('integrity_retried') or 0)
        for fname in ('BENCH_STAGED.json',) + CACHE_FILES:
            try:
                with open(os.path.join(root, fname)) as f:
                    recs = json.load(f).get('results', {})
            except (OSError, ValueError):
                continue
            for rec in recs.values():
                stamp = rec.get('integrity') \
                    if isinstance(rec, dict) else None
                if isinstance(stamp, dict):
                    found = True
                    out['stamped_records'] += 1
                    out['violations'] += int(stamp.get('violations',
                                                       0) or 0)
                    out['retried'] += int(stamp.get('retried', 0) or 0)
        ckpt_dir = os.path.join(root, 'BENCH_CKPT')
        if os.path.isdir(ckpt_dir):
            # quarantine evidence rides the sealed manifest body —
            # read the files directly so a half-written store cannot
            # make the posture raise
            quarantined = set()
            for path in glob.glob(os.path.join(ckpt_dir,
                                               '*.manifest.json')):
                try:
                    with open(path) as f:
                        man = json.load(f)
                except (OSError, ValueError):
                    continue
                for r in man.get('quarantined') or []:
                    found = True
                    quarantined.add(int(r))
            out['quarantined'] = sorted(quarantined)
        out['unacknowledged_mismatch'] = max(
            0, out['shadow_mismatch'] - out['integrity_retried'])
        return out if found else None
    except Exception as e:      # pragma: no cover - defensive
        return {'error': str(e)}


def write_precision_margins(margins, root='.', k_max='k_nyquist/2'):
    """Commit measured P(k) accuracy margins to ``PRECISION.json``
    (atomic).  ``margins`` maps margin key ('mesh-bf16' / 'a2a-bf16' /
    'a2a-int16') to ``{'max_rel_err': float, 'budget': float}``;
    existing keys are merged so the paint and fft gates can each
    attest their own postures.  :func:`precision_summary` reads it
    back for the round record."""
    path = os.path.join(root, PRECISION_NAME)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    if not isinstance(doc.get('margins'), dict):
        doc['margins'] = {}
    doc['margins'].update({str(k): dict(v)
                           for k, v in (margins or {}).items()})
    doc['k_max'] = k_max
    doc['measured_at'] = time.strftime('%Y-%m-%dT%H:%M:%SZ',
                                       time.gmtime())
    atomic_write(path, json.dumps(doc, indent=1, sort_keys=True))
    return path


def precision_summary(root, now=None):
    """Precision posture for the round record: the measured max P(k)
    relative error vs the f32 oracle that each halved-bytes posture
    (bf16 mesh storage, compressed all_to_all payloads) has on record
    (``PRECISION.json``, written by the accuracy harness up to
    k_Nyquist/2).  ``None`` when no PRECISION.json exists; never
    raises."""
    pr_path = os.path.join(root, PRECISION_NAME)
    if not os.path.exists(pr_path):
        return None
    try:
        with open(pr_path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return {'error': 'PRECISION.json unreadable: %s' % e}
    out = {'margins': dict(doc.get('margins') or {})}
    if doc.get('k_max') is not None:
        out['k_max'] = doc['k_max']
    return out


def build_history(root='.', out=None, threshold=0.25, now=None,
                  write=True):
    """Assemble + (atomically) write ``BENCH_HISTORY.json``; returns
    the history dict.  ``write=False`` analyzes without touching disk.
    """
    entries = classify(load_rounds(root), threshold=threshold)
    history = {
        'generated_at': time.strftime('%Y-%m-%dT%H:%M:%SZ',
                                      time.gmtime(now)),
        'root': os.path.abspath(root),
        'threshold': threshold,
        'rounds': entries,
        'lint': lint_summary(root),
        'resilience': resilience_summary(root, now=now),
        'fleet': fleet_summary(root, now=now),
        'serve': serve_summary(root),
        'region': region_summary(root),
        'ingest': ingest_summary(root),
        'forward': forward_summary(root),
        'bispectrum': bispectrum_summary(root),
        'integrity': integrity_summary(root),
        'slo': slo_summary(root),
        'precision': precision_summary(root, now=now),
        'caches': load_caches(root, now=now),
        'summary': {v: sum(1 for e in entries
                           if e.get('verdict') == v)
                    for v in ('ok', 'improved', 'regression',
                              'no-result', 'malformed')},
    }
    if write:
        path = out or os.path.join(root, HISTORY_NAME)
        atomic_write(path, json.dumps(history, indent=1, default=str))
        history['path'] = path
    return history


def render_regress(history):
    """The history as an aligned plain-text report."""
    out = []
    w = out.append
    w('== nbodykit_tpu bench regression report ==')
    w('root: %s   rounds: %d   threshold: %.0f%%'
      % (history['root'], len(history['rounds']),
         100 * history['threshold']))
    rounds = history['rounds']
    if rounds:
        fw = max(len(e['file']) for e in rounds)
        for e in rounds:
            v = e.get('value')
            val = '%10.4f %s' % (v, e.get('unit') or 's') \
                if isinstance(v, (int, float)) else '         --'
            line = '  %-*s  %-44s %s  %-10s' \
                % (fw, e['file'], e.get('metric', '(no record)')[:44],
                   val, e.get('verdict', '?').upper())
            if e.get('why'):
                line += '  %s' % e['why']
            w(line)
    caches = history.get('caches', {})
    for fname, summary in sorted(caches.items()):
        if 'error' in summary:
            w('  %s: MALFORMED (%s)' % (fname, summary['error']))
            continue
        w('  %s: %d metrics' % (fname, len(summary)))
    res = history.get('resilience')
    if res is not None:
        bits = []
        if res.get('resumed_records'):
            bits.append('%d committed record(s) from resumed runs'
                        % res['resumed_records'])
        if res.get('pending_checkpoints'):
            bits.append('%d PENDING checkpoint(s) (oldest %s h) — an '
                        'interrupted measurement awaits relaunch'
                        % (res['pending_checkpoints'],
                           res.get('oldest_checkpoint_hours', '?')))
        if bits:
            w('  resilience: %s' % '; '.join(bits))
    fleet = history.get('fleet')
    if fleet is not None:
        bits = []
        if fleet.get('preempted_records'):
            bits.append('%d record(s) interrupted by preemption'
                        % fleet['preempted_records'])
        for rf in fleet.get('reformations') or []:
            bits.append('%s resumed with a SHRUNK mesh (%s -> %s '
                        'ranks)' % (rf.get('metric', '?'),
                                    rf.get('reformed_from', '?'),
                                    rf.get('reformed_to', '?')))
        if fleet.get('incomplete_seqs'):
            bits.append('%d INCOMPLETE manifest seq(s) — a seal died '
                        'mid-commit; the previous sealed manifest is '
                        'authoritative' % fleet['incomplete_seqs'])
        if fleet.get('orphan_tmp'):
            bits.append('%d orphaned .tmp file(s) (gc candidates)'
                        % fleet['orphan_tmp'])
        if fleet.get('sealed_manifests'):
            bits.append('%d sealed manifest(s) on disk'
                        % fleet['sealed_manifests'])
        if bits:
            w('  fleet: %s' % '; '.join(bits))
    serve = history.get('serve')
    if serve is not None:
        if 'error' in serve:
            w('  serve: unavailable (%s)' % serve['error'])
        else:
            # fault_counts() tallies point HITS, not rules fired — the
            # honest render is which points were under injection
            fpoints = sorted((serve.get('faults_injected') or {}))
            w('  serve: %s req @ %s rps, p99 %ss — %s rejected, '
              '%s evicted, %s degraded, %s resumed, %s lost%s'
              % (serve.get('requests', '?'), serve.get('rps', '?'),
                 serve.get('p99_s', '?'), serve.get('rejected', '?'),
                 serve.get('evicted', '?'),
                 serve.get('degraded', '?'), serve.get('resumed', '?'),
                 serve.get('lost', '?'),
                 ', faults injected at %s and survived'
                 % ', '.join(fpoints) if fpoints else ''))
    reg = history.get('region')
    if reg is not None:
        if 'error' in reg:
            w('  region: unavailable (%s)' % reg['error'])
        else:
            bits = []
            if reg.get('joins'):
                bits.append('%s elastic join(s), fleet re-formed '
                            '%s -> %s'
                            % (reg['joins'],
                               reg.get('reformed_from', '?'),
                               reg.get('reformed_to', '?')))
            if reg.get('throttled'):
                bits.append('%s throttled by fair share'
                            % reg['throttled'])
            if reg.get('starved'):
                bits.append('WARN — %s interactive request(s) '
                            'STARVED' % reg['starved'])
            if reg.get('unverified_as_verified'):
                bits.append('FAIL — %s unverified cache hit(s) '
                            'served as verified'
                            % reg['unverified_as_verified'])
            if reg.get('cache_bit_identical') is False:
                bits.append('FAIL — cached result NOT bit-identical '
                            'to recomputation')
            w('  region: %s req over %s fleet(s) — cache hit rate '
              '%s (%s hit(s)), %s spill(s), interactive p99 %ss, '
              '%s lost%s'
              % (reg.get('requests', '?'),
                 reg.get('fleet_count', reg.get('fleets', '?')),
                 reg.get('hit_rate', '?'),
                 reg.get('result_hits', '?'), reg.get('spills', '?'),
                 reg.get('interactive_p99_s', '?'),
                 reg.get('lost', '?'),
                 ' — %s' % '; '.join(bits) if bits else ''))
    ing = history.get('ingest')
    if ing is not None:
        if 'error' in ing:
            w('  ingest: unavailable (%s)' % ing['error'])
        else:
            bits = []
            if ing.get('overlap_speedup') is not None:
                bits.append('overlap x%.2f vs serialized'
                            % ing['overlap_speedup'])
            if ing.get('serve_completed') is not None:
                bits.append('%s data_ref request(s) served, %s from '
                            'cache, %s lost'
                            % (ing['serve_completed'],
                               ing.get('serve_cache_hits', '?'),
                               ing.get('serve_lost', '?')))
            ev, hits = (ing.get('cache_evictions'),
                        ing.get('cache_hits'))
            if ev is not None and hits is not None and ev > hits:
                bits.append('WARN — cache thrash: %d eviction(s) vs '
                            '%d hit(s)' % (ev, hits))
            w('  ingest: %s rows -> painted mesh at %s GB/s cold, '
              '%s GB/s cache-hit%s'
              % (ing.get('rows', '?'), ing.get('cold_gbs', '?'),
                 ing.get('warm_gbs', '?'),
                 ' — %s' % '; '.join(bits) if bits else ''))
    fwd = history.get('forward')
    if fwd is not None:
        if 'error' in fwd:
            w('  forward: unavailable (%s)' % fwd['error'])
        else:
            bits = []
            if fwd.get('grad_check_ok') is False:
                bits.append('FAIL — gradient check VIOLATED (FD rel '
                            'err %s): the forward model is not '
                            'differentiable as deployed'
                            % fwd.get('grad_rel_err', '?'))
            if fwd.get('beats_baseline') is False:
                bits.append('FAIL — recovery r=%s does NOT beat the '
                            'FFTRecon baseline r=%s'
                            % (fwd.get('r_recovered', '?'),
                               fwd.get('r_fftrecon', '?')))
            w('  forward: mesh%s/part%s x%s steps (%s paint, %s '
              'adjoint) — grad %ss (x%s over forward), FD check '
              '%s; recovery r=%s vs FFTRecon r=%s%s'
              % (fwd.get('nmesh', '?'), fwd.get('npart', '?'),
                 fwd.get('pm_steps', '?'),
                 fwd.get('paint_method', '?'),
                 fwd.get('adjoint_mode', '?'),
                 fwd.get('grad_s', '?'),
                 fwd.get('grad_overhead', '?'),
                 'ok' if fwd.get('grad_check_ok') else 'VIOLATED',
                 fwd.get('r_recovered', '?'),
                 fwd.get('r_fftrecon', '?'),
                 ' — %s' % '; '.join(bits) if bits else ''))
    bsp = history.get('bispectrum')
    if bsp is not None:
        if 'error' in bsp:
            w('  bispectrum: unavailable (%s)' % bsp['error'])
        else:
            bits = []
            if bsp.get('ntri_bit_identical') is False:
                bits.append('FAIL — triangle counts differ between '
                            'the FFT and direct paths')
            if bsp.get('agree_ok') is False:
                bits.append('FAIL — estimators disagree (max rel %s)'
                            % bsp.get('b_max_rel', '?'))
            w('  bispectrum: mesh%s/part%s x%s shells — fft %ss vs '
              'direct %ss (%s faster at this shape), agreement max '
              'rel %s%s'
              % (bsp.get('nmesh', '?'), bsp.get('npart', '?'),
                 bsp.get('nbins', '?'), bsp.get('fft_s', '?'),
                 bsp.get('direct_s', '?'), bsp.get('faster', '?'),
                 bsp.get('b_max_rel', '?'),
                 ' — %s' % '; '.join(bits) if bits else ''))
    integ = history.get('integrity')
    if integ is not None:
        if 'error' in integ:
            w('  integrity: unavailable (%s)' % integ['error'])
        else:
            bits = []
            if integ.get('quarantined'):
                bits.append('rank(s) %s QUARANTINED in the sealed '
                            'fleet manifest'
                            % ', '.join(map(str,
                                            integ['quarantined'])))
            if integ.get('unacknowledged_mismatch'):
                bits.append('FAIL — %d shadow mismatch(es) with NO '
                            'integrity retry: a divergent result may '
                            'have been delivered'
                            % integ['unacknowledged_mismatch'])
            w('  integrity: %d stamped record(s) — %d violation(s) '
              'caught, %d retried clean; shadow %d verified / %d '
              'mismatch%s'
              % (integ.get('stamped_records', 0),
                 integ.get('violations', 0), integ.get('retried', 0),
                 integ.get('shadow_verified', 0),
                 integ.get('shadow_mismatch', 0),
                 ' — %s' % '; '.join(bits) if bits else ''))
    slo = history.get('slo')
    if slo is not None:
        if 'error' in slo:
            w('  slo: unavailable (%s)' % slo['error'])
        else:
            bits = []
            for cname, c in sorted((slo.get('classes') or {}).items()):
                bits.append('%s %s (burn fast %s / slow %s, p99 %ss)'
                            % (cname, c.get('verdict', '?'),
                               c.get('fast_burn', '?'),
                               c.get('slow_burn', '?'),
                               c.get('p99_s', '?')))
            extra = []
            if slo.get('orphan_spans'):
                extra.append('%s ORPHAN span(s)' % slo['orphan_spans'])
            ov = slo.get('overhead')
            if ov is not None:
                extra.append('tracing overhead %.1f%%%s'
                             % (100.0 * ov,
                                ' — OVER the 5%% budget'
                                if ov >= 0.05 else ''))
            w('  slo: %s — %s/%s waterfall(s) complete%s%s'
              % (slo.get('verdict', '?'), slo.get('complete', '?'),
                 slo.get('traces', '?'),
                 '; %s' % '; '.join(bits) if bits else '',
                 '; %s' % '; '.join(extra) if extra else ''))
    prec = history.get('precision')
    if prec is not None:
        if 'error' in prec:
            w('  precision: unavailable (%s)' % prec['error'])
        else:
            attested = ', '.join(
                '%s err %.2e/budget %.0e'
                % (k, v.get('max_rel_err', float('nan')),
                   v.get('budget', float('nan')))
                for k, v in sorted(prec.get('margins', {}).items()))
            w('  precision: %d margin(s) on record%s'
              % (len(prec.get('margins', {})),
                 ' vs f32 oracle to %s (%s)'
                 % (prec.get('k_max', '?'), attested)
                 if attested else ''))
    lint = history.get('lint')
    if lint is not None:
        if 'error' in lint:
            w('  lint: unavailable (%s)' % lint['error'])
        else:
            fams = lint.get('families') or {}
            per_family = '  '.join(
                '%s=%d+%d' % (k, v['new'], v['baselined'])
                for k, v in sorted(fams.items())
                if v['new'] or v['baselined'])
            w('  lint: %d finding(s) — %d new, %d baselined%s%s'
              % (lint['findings'], lint['new'], lint['baselined'],
                 ' (%s)' % per_family if per_family else '',
                 ', %d stale baseline entr%s to prune'
                 % (lint['stale_baseline_entries'],
                    'y' if lint['stale_baseline_entries'] == 1
                    else 'ies')
                 if lint.get('stale_baseline_entries') else ''))
    s = history['summary']
    w('verdicts: %s' % '  '.join('%s=%d' % (k, n)
                                 for k, n in s.items() if n))
    bad = s.get('malformed', 0)
    warn = s.get('regression', 0)
    if bad:
        w('RESULT: FAIL — %d malformed bench record(s)' % bad)
    elif warn:
        w('RESULT: WARN — %d regression verdict(s)' % warn)
    else:
        w('RESULT: OK')
    return '\n'.join(out) + '\n'


def gate_rc(history):
    """Exit code for CI gates: malformed records fail; regressions
    warn loudly but do not block."""
    return 1 if history['summary'].get('malformed') else 0
