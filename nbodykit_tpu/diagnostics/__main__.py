"""Diagnostics CLI: self-check, post-mortem report, fleet analysis,
bench regression tracking, chrome export — and the doctor that runs
them all.

    python -m nbodykit_tpu.diagnostics --self-check
        Round-trip a trace file end to end: emit nested + failing
        spans and metrics, simulate a killed writer (torn final line),
        replay, write the report and the chrome-trace export, verify
        every step.  Exit 0 on success.  Run by scripts/smoke.sh and
        installed as the ``nbodykit-tpu-selfcheck`` console script.

    python -m nbodykit_tpu.diagnostics --report PATH
        Print the text report for an existing trace file/directory
        (e.g. from a dead TPU run).

    python -m nbodykit_tpu.diagnostics --analyze DIR
        Fleet analysis of a directory of per-process traces: merged
        timeline with aligned clocks, per-collective straggler table,
        critical-path breakdown, hung collectives, heartbeat gaps.

    python -m nbodykit_tpu.diagnostics --regress [ROOT]
        Build BENCH_HISTORY.json from the BENCH_r*.json /
        BASELINE*.json family under ROOT (default .) and print the
        verdicts.  Exits nonzero on a malformed bench record (the
        smoke-gate contract); regressions warn loudly but do not
        block.

    python -m nbodykit_tpu.diagnostics --chrome PATH
        Export PATH to chrome_trace.json for ui.perfetto.dev.

    python -m nbodykit_tpu.diagnostics --lint [ROOT]
        Run the shard-safety static analyzer (nbodykit_tpu.lint) over
        ROOT's package + multi-host worker, gated on
        ROOT/lint_baseline.json when present.  Same exit contract as
        the ``nbodykit-tpu-lint`` console script.

    python -m nbodykit_tpu.diagnostics --doctor [--trace DIR] [--root R]
        Self-check + analyze + regress + lint, one verdict block.
        Compile-cache misses for a jit label that also carries an open
        NBK2xx lint finding are cross-linked: the static finding is
        printed next to the runtime telemetry line.  Installed as the
        ``nbodykit-tpu-doctor`` console script; ``--self-check-only``
        restricts it to the trace round-trip.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile


def self_check(path=None, verbose=True):
    """Returns 0 on success; raises AssertionError on any mismatch."""
    import nbodykit_tpu
    from . import (NULL_SPAN, REGISTRY, counter, current_tracer,
                   export_chrome_trace, histogram, read_trace, span,
                   write_report)

    tmp = None
    if path is None:
        tmp = path = tempfile.mkdtemp(prefix='nbodykit-tpu-diag-')
    try:
        # disabled mode really is a no-op singleton
        with nbodykit_tpu.set_options(diagnostics=None):
            assert span('off') is NULL_SPAN
            assert current_tracer() is None

        with nbodykit_tpu.set_options(diagnostics=path):
            tr = current_tracer()
            assert tr is not None, 'tracer did not come up'
            # deltas, not absolutes: the registry is process-global and
            # the doctor may run the self-check more than once
            c0 = counter('selfcheck.count').value
            h0 = histogram('selfcheck.hist').count
            with span('selfcheck', kind='root'):
                with span('selfcheck.child'):
                    counter('selfcheck.count').add(3)
                    histogram('selfcheck.hist').observe(1.5)
                try:
                    with span('selfcheck.raises'):
                        raise RuntimeError('expected failure')
                except RuntimeError:
                    pass
            trace_file = tr.path

            # simulate a SIGKILLed writer: a torn final line must be
            # tolerated, not poison the replay
            with open(trace_file, 'a') as f:
                f.write('{"t":"span","name":"torn')

            records, bad = read_trace(trace_file)
            spans = [r for r in records if r.get('t') == 'span']
            names = {r['name'] for r in spans}
            assert bad == 1, 'torn-line count: %d' % bad
            assert {'selfcheck', 'selfcheck.child',
                    'selfcheck.raises'} <= names, names
            child = next(r for r in spans
                         if r['name'] == 'selfcheck.child')
            root = next(r for r in spans if r['name'] == 'selfcheck')
            assert child['depth'] == 1 and child['par'] == root['id'], \
                'nesting broken: %r' % child
            failed = next(r for r in spans
                          if r['name'] == 'selfcheck.raises')
            assert failed['ok'] is False \
                and 'expected failure' in failed.get('exc', ''), failed

            chrome = export_chrome_trace(trace_file)
            with open(chrome) as f:
                events = json.load(f)['traceEvents']
            assert any(e['name'] == 'selfcheck' for e in events)

            snap = REGISTRY.snapshot()
            assert snap['selfcheck.count']['value'] == c0 + 3
            assert snap['selfcheck.hist']['count'] == h0 + 1

            paths = write_report(tracer=tr)
            assert paths is not None
            with open(paths[0]) as f:
                rep = json.load(f)
            assert rep['torn_lines'] == 1
            assert rep['spans']['selfcheck.raises']['errors'] == 1
        # the option restore must tear the tracer down again
        assert current_tracer() is None
        if verbose:
            print('diagnostics self-check OK: %d spans round-tripped, '
                  '1 torn line tolerated, report at %s'
                  % (len(spans), paths[1]))
        return 0
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def run_analyze(path, out=None):
    """--analyze: print the fleet analysis; exit 0 unless the trace is
    missing (2).  Hung collectives / silent processes are findings to
    report, not tool failures."""
    from .analyze import analyze, render_analysis
    out = out if out is not None else sys.stdout
    if not os.path.exists(path):
        print('no such trace: %s' % path, file=sys.stderr)
        return 2
    out.write(render_analysis(analyze(path)))
    return 0


def run_regress(root, out=None, threshold=0.25, write=True):
    """--regress: build + print the bench history; the exit code is
    the CI gate (nonzero only on malformed records)."""
    from .regress import build_history, gate_rc, render_regress
    out = out if out is not None else sys.stdout
    history = build_history(root, threshold=threshold, write=write)
    out.write(render_regress(history))
    return gate_rc(history)


def run_lint_cmd(root='.', out=None):
    """--lint: the shard-safety analyzer over ROOT's lint surface,
    gated on ROOT/lint_baseline.json when committed.  Exit contract ==
    nbodykit-tpu-lint: 0 clean, 1 new findings."""
    from .. import lint as lint_mod
    out = out if out is not None else sys.stdout
    targets = lint_mod.default_targets(root)
    bl = os.path.join(root, 'lint_baseline.json')
    argv = list(targets)
    if os.path.exists(bl):
        argv += ['--baseline', bl]
    import contextlib
    with contextlib.redirect_stdout(out):
        return lint_mod.main(argv)


def _lint_findings(root):
    """(new, open_findings, jit_label_map) for the doctor; raises on a
    broken baseline so the doctor reports it."""
    from .. import lint as lint_mod
    targets = lint_mod.default_targets(root)
    bl = os.path.join(root, 'lint_baseline.json')
    new, grandfathered, _ = lint_mod.run_lint(
        targets, baseline_path=bl if os.path.exists(bl) else None)
    return new, new + grandfathered, lint_mod.collect_jit_labels(targets)


def _compile_miss_labels(trace):
    """jit labels with observed cache misses: live registry counters
    (``compile.<label>.misses``) merged with ``compile.<label>`` spans
    found in the analyzed trace directory (the three stage spans of
    the ``jax.monitoring`` hook are no label's)."""
    from . import REGISTRY
    from .metrics import STAGE_SPANS
    labels = {}
    for name, snap in REGISTRY.snapshot().items():
        if name.startswith('compile.') and name.endswith('.misses') \
                and snap.get('value'):
            labels[name[len('compile.'):-len('.misses')]] = \
                int(snap['value'])
    if trace and os.path.exists(trace):
        try:
            from .analyze import load_processes
            procs, _ = load_processes(trace)
        except Exception:
            procs = {}
        for records in procs.values():
            for r in records:
                name = r.get('name', '')
                if r.get('t') == 'span' and \
                        name.startswith('compile.') and \
                        name not in STAGE_SPANS:
                    lbl = name[len('compile.'):]
                    labels[lbl] = labels.get(lbl, 0) + 1
    return labels


def _device_watermark_bytes(trace):
    """Per-device live-byte watermarks: the ``device.<d>.live_bytes``
    gauge maxima from the live registry, merged (per-device max) with
    gauge records found in the analyzed trace directory."""
    from . import REGISTRY
    marks = {}
    for name, snap in REGISTRY.snapshot().items():
        if name.startswith('device.') and \
                name.endswith('.live_bytes') and \
                snap.get('type') == 'gauge':
            peak = snap.get('max') or snap.get('value')
            if peak:
                dev = name[len('device.'):-len('.live_bytes')]
                marks[dev] = max(marks.get(dev, 0), int(peak))
    if trace and os.path.exists(trace):
        try:
            from .analyze import load_processes
            procs, _ = load_processes(trace)
        except Exception:
            procs = {}
        for records in procs.values():
            for r in records:
                name = r.get('name', '')
                if r.get('t') == 'metric' and \
                        name.startswith('device.') and \
                        name.endswith('.live_bytes'):
                    peak = r.get('max') or r.get('value') or 0
                    if peak:
                        dev = name[len('device.'):-len('.live_bytes')]
                        marks[dev] = max(marks.get(dev, 0), int(peak))
    return marks


def _resilience_counts(trace):
    """Observed retry/degrade/resume/fault totals: live registry
    counters merged (per-key max, so a same-process doctor run does
    not double-count its own trace) with ``resilience.*`` event spans
    found in the analyzed trace directory."""
    from .metrics import prefixed
    counts = {k: int(m.get('value', 0))
              for k, m in prefixed('resilience.').items()
              if m.get('type') == 'counter'}
    span_keys = {'resilience.retry': 'retries',
                 'resilience.degrade': 'degradations',
                 'resilience.resume': 'resumes',
                 'resilience.preempted': 'preempted',
                 'resilience.fleet.dead_rank': 'fleet.dead_ranks',
                 'resilience.fleet.reform': 'fleet.reformed'}
    if trace and os.path.exists(trace):
        try:
            from .analyze import load_processes
            procs, _ = load_processes(trace)
        except Exception:
            procs = {}
        traced = {}
        for records in procs.values():
            for r in records:
                key = span_keys.get(r.get('name', ''))
                if r.get('t') == 'span' and key:
                    traced[key] = traced.get(key, 0) + 1
        for key, n in traced.items():
            counts[key] = max(counts.get(key, 0), n)
    return counts


def run_doctor(trace=None, root='.', self_check_only=False,
               out=None, threshold=0.25):
    """Self-check + analyze + regress + lint, one verdict block.

    Returns 0 (OK/WARN) or 1 (FAIL).  FAIL means the diagnostics stack
    itself is broken, a trace shows a hung collective or silent
    process, a committed bench record is malformed, or the lint gate
    has non-baselined findings.
    WARN covers regressions, compile-cache misses
    whose jit label carries an open NBK2xx finding (the
    static/runtime cross-link), device live-byte watermarks past half
    a v5e's HBM while open NBK5xx (donation/peak) findings exist (the
    same cross-link for memory), and open NBK801/NBK803
    host-concurrency findings printed next to hung-collective /
    silent-process trace evidence (the same cross-link for the
    threaded control plane) — loud, but not blocking.
    """
    out = out if out is not None else sys.stdout
    lines, fail, warn = [], [], []

    try:
        self_check(verbose=False)
        lines.append('self-check   OK: trace round-trip, torn-line '
                     'replay, report, chrome export')
    except Exception as e:
        fail.append('self-check')
        lines.append('self-check   FAIL: %s' % e)

    if self_check_only:
        trace = None
        root = None

    hung, silent = [], []     # runtime evidence the concurrency
    # cross-link below pairs with open NBK801/NBK803 findings
    if trace and os.path.exists(trace):
        from .analyze import analyze
        try:
            res = analyze(trace)
        except Exception as e:    # a broken trace must still report
            res = None
            fail.append('analyze')
            lines.append('analyze      FAIL: %s' % e)
        if res is not None and res.get('empty'):
            lines.append('analyze      SKIP: no trace records under %s'
                         % trace)
        elif res is not None:
            hung = res['hangs']['hung_collectives']
            silent = [p for p, st in res['heartbeat'].items()
                      if st.get('silent')]
            skews = [st['max_skew_s'] for st in
                     res['stragglers']['per_name'].values()]
            desc = ('%d procs, %d spans, wall %.3f s, max skew %s'
                    % (res['nprocs'], res['nspans'],
                       res['critical_path']['wall_s'],
                       '%.1f ms' % (max(skews) * 1e3) if skews
                       else 'n/a'))
            if hung or silent:
                fail.append('analyze')
                lines.append('analyze      FAIL: %s; %d hung '
                             'collective(s), %d silent process(es) — '
                             'run --analyze %s for the post-mortem'
                             % (desc, len(hung), len(silent), trace))
            else:
                lines.append('analyze      OK: %s' % desc)
    elif trace:
        lines.append('analyze      SKIP: no trace at %s' % trace)
    elif not self_check_only:
        lines.append('analyze      SKIP: no trace directory (pass '
                     '--trace DIR or set NBKIT_DIAGNOSTICS)')

    if root is not None:
        from .regress import build_history, render_regress
        try:
            history = build_history(root, threshold=threshold)
        except Exception as e:
            history = None
            fail.append('regress')
            lines.append('regress      FAIL: %s' % e)
        if history is not None:
            s = history['summary']
            desc = ('%d rounds: %s'
                    % (len(history['rounds']),
                       '  '.join('%s=%d' % (k, n)
                                 for k, n in s.items() if n)
                       or 'none found'))
            if s.get('malformed'):
                fail.append('regress')
                lines.append('regress      FAIL: %s — malformed bench '
                             'record(s)' % desc)
            elif s.get('regression'):
                warn.append('regress')
                lines.append('regress      WARN: %s — regressions '
                             '(see %s)'
                             % (desc, history.get('path',
                                                  'BENCH_HISTORY.json')))
            else:
                lines.append('regress      OK: %s' % desc)
            prec = history.get('precision') or {}
            if prec.get('margins'):
                lines.append('precision    OK: %d accuracy margin(s) '
                             'on record for the halved-bytes postures'
                             % len(prec['margins']))

    if root is not None and \
            not os.path.isdir(os.path.join(root, 'nbodykit_tpu')):
        lines.append('lint         SKIP: no nbodykit_tpu package '
                     'under %s (pass the repo root as --root to lint)'
                     % root)
    elif root is not None:
        open_nbk2, open_nbk5, label_map = [], [], {}
        try:
            new, open_findings, label_map = _lint_findings(root)
        except Exception as e:
            fail.append('lint')
            lines.append('lint         FAIL: %s' % e)
        else:
            open_nbk2 = [f for f in open_findings
                         if f.code.startswith('NBK2')]
            open_nbk5 = [f for f in open_findings
                         if f.code.startswith('NBK5')]
            ngrand = len(open_findings) - len(new)
            if new:
                fail.append('lint')
                lines.append('lint         FAIL: %d non-baselined '
                             'finding(s) — run --lint %s for the '
                             'listing' % (len(new), root))
            else:
                lines.append('lint         OK: 0 new findings '
                             '(%d grandfathered in lint_baseline.json)'
                             % ngrand)
            # static/runtime cross-link #3 — the host-concurrency
            # form of the NBK2xx<->compile pattern: an open NBK801
            # (lock-order inversion) or NBK803 (blocking under a
            # lock) finding is the static shape of a wedge, and a
            # trace showing hung collectives or silent processes is
            # the same wedge observed at runtime — print them on one
            # line so the pairing is unmissable
            open_nbk8 = [f for f in open_findings
                         if f.code in ('NBK801', 'NBK803')]
            if open_nbk8:
                warn.append('concurrency')
                f0 = open_nbk8[0]
                evidence = ''
                if hung or silent:
                    bits = []
                    if hung:
                        bits.append('%d hung collective(s) (e.g. %r)'
                                    % (len(hung),
                                       hung[0].get('name', '?')))
                    if silent:
                        bits.append('%d silent process(es)'
                                    % len(silent))
                    evidence = ('; runtime evidence in the trace: %s'
                                % '; '.join(bits))
                lines.append('concurrency  WARN: %d open '
                             'NBK801/NBK803 finding(s) — e.g. %s at '
                             '%s:%d: %s%s'
                             % (len(open_nbk8), f0.code, f0.path,
                                f0.line, f0.message, evidence))
            else:
                lines.append('concurrency  OK: 0 open NBK8xx '
                             'findings (lock order + '
                             'blocking-under-lock statically clean)')
        # static/runtime cross-link: a jit label that missed the
        # compile cache AND sits in a file with an open NBK2xx finding
        # is almost certainly the finding biting at runtime
        for label, nmiss in sorted(_compile_miss_labels(trace).items()):
            site = label_map.get(label)
            related = [f for f in open_nbk2
                       if site and f.path == site[0]]
            if not related:
                continue
            warn.append('compile')
            f0 = related[0]
            lines.append('compile      WARN: label %r missed the jit '
                         'cache %dx — open %s at %s:%d: %s'
                         % (label, nmiss, f0.code, f0.path, f0.line,
                            f0.message))
        # static/runtime cross-link #2 — the NBK2xx<->compile pattern
        # for memory: a device whose live-bytes watermark crossed half
        # of a v5e's HBM while the tree carries open NBK5xx
        # (donation/peak) findings is the static hazard biting at
        # runtime; print the finding next to the watermark
        if open_nbk5:
            for dev, peak in sorted(
                    _device_watermark_bytes(trace).items()):
                if peak < 0.5 * 16e9:
                    continue
                warn.append('memory')
                f0 = open_nbk5[0]
                lines.append(
                    'memory       WARN: device %s live-bytes '
                    'watermark %.2f GB with %d open NBK5xx '
                    'finding(s) — e.g. %s at %s:%d: %s'
                    % (dev, peak / 1e9, len(open_nbk5), f0.code,
                       f0.path, f0.line, f0.message))

    if root is not None or trace:
        # resilience posture: what the supervisor did (retries /
        # degradations / resumes, from counters + the merged trace)
        # and whether an interrupted measurement is still awaiting
        # relaunch (pending checkpoints under BENCH_CKPT)
        from .regress import resilience_summary
        counts = _resilience_counts(trace)
        res = resilience_summary(root) if root is not None else {}
        activity = ('retries=%d degradations=%d resumes=%d '
                    'faults_injected=%d'
                    % (counts.get('retries', 0),
                       counts.get('degradations', 0),
                       counts.get('resumes', 0),
                       counts.get('faults.injected', 0)))
        pending = res.get('pending_checkpoints', 0)
        if pending:
            warn.append('resilience')
            lines.append('resilience   WARN: %s; %d pending '
                         'checkpoint(s) under BENCH_CKPT (oldest '
                         '%s h) — an interrupted run has not been '
                         'resumed, relaunch the bench to finish it'
                         % (activity, pending,
                            res.get('oldest_checkpoint_hours', '?')))
        else:
            extra = ''
            if res.get('resumed_records'):
                extra = ('; %d committed record(s) came from resumed '
                         'runs' % res['resumed_records'])
            lines.append('resilience   OK: %s; no pending '
                         'checkpoints%s' % (activity, extra))

        # fleet posture: preemptions, dead ranks, shrink-to-survive
        # re-formations, and the coordinated-checkpoint directory's
        # sealed/incomplete ledger (nbodykit_tpu.resilience.fleet)
        from .regress import fleet_summary
        flt = fleet_summary(root) if root is not None else {}
        preempted = max(counts.get('preempted', 0),
                        flt.get('preempted_records', 0))
        dead = counts.get('fleet.dead_ranks', 0)
        reforms = flt.get('reformations') or []
        incomplete = flt.get('incomplete_seqs', 0)
        orphans = flt.get('orphan_tmp', 0)
        activity = ('preemptions=%d dead_ranks=%d sealed=%d'
                    % (preempted, dead,
                       flt.get('sealed_manifests',
                               counts.get('fleet.manifests_sealed',
                                          0))))
        problems = []
        if incomplete:
            problems.append('%d INCOMPLETE manifest seq(s) — a seal '
                            'died mid-commit, the previous sealed '
                            'manifest stays authoritative; a relaunch '
                            'or fleet gc clears the debris'
                            % incomplete)
        if preempted:
            problems.append('%d preemption(s) took the grace-budget '
                            'exit — relaunch resumes from the sealed '
                            'checkpoint' % preempted)
        if dead:
            problems.append('%d dead rank(s) detected by the live '
                            'monitor' % dead)
        if orphans:
            problems.append('%d orphaned .tmp file(s) (gc candidates)'
                            % orphans)
        notes = ''
        if reforms:
            notes = '; ' + '; '.join(
                '%s resumed with a SHRUNK mesh (%s -> %s ranks)'
                % (rf.get('metric', '?'), rf.get('reformed_from', '?'),
                   rf.get('reformed_to', '?')) for rf in reforms)
        if problems:
            warn.append('fleet')
            lines.append('fleet        WARN: %s; %s%s'
                         % (activity, '; '.join(problems), notes))
        elif preempted or dead or reforms \
                or flt.get('sealed_manifests'):
            lines.append('fleet        OK: %s%s' % (activity, notes))
        else:
            lines.append('fleet        OK: no preemptions, dead '
                         'ranks, or fleet checkpoints this round')

    if root is not None:
        # serving posture: the latest committed servetrace round.  The
        # ONE hard failure is a lost request — a submission that ended
        # with no structured verdict; everything else (rejections,
        # evictions, degradations) is the server doing its job and is
        # reported, not punished.
        from .regress import serve_summary
        srv = serve_summary(root)
        if srv is None:
            lines.append('serve        SKIP: no servetrace record in '
                         'any committed bench round')
        elif 'error' in srv:
            warn.append('serve')
            lines.append('serve        WARN: serve summary unavailable '
                         '(%s)' % srv['error'])
        else:
            # fault_counts() tallies point HITS, not rules fired — name
            # the injected points rather than pretend a fired count
            fpoints = sorted((srv.get('faults_injected') or {}))
            desc = ('%s req @ %s rps, p99 %ss; rejected=%s evicted=%s '
                    'failed=%s degraded=%s resumed=%s'
                    % (srv.get('requests', '?'), srv.get('rps', '?'),
                       srv.get('p99_s', '?'), srv.get('rejected', '?'),
                       srv.get('evicted', '?'), srv.get('failed', '?'),
                       srv.get('degraded', '?'),
                       srv.get('resumed', '?')))
            if fpoints:
                desc += ('; faults injected at %s — survived'
                         % ', '.join(fpoints))
            lost = srv.get('lost')
            if lost:
                fail.append('serve')
                lines.append('serve        FAIL: %s request(s) lost '
                             'WITHOUT a structured verdict (%s) — '
                             'every submission must end as a result'
                             % (lost, desc))
            elif srv.get('failed'):
                warn.append('serve')
                lines.append('serve        WARN: %s — failed requests '
                             'got structured verdicts but the errors '
                             'deserve a look (%s)'
                             % (srv.get('failed'), desc))
            else:
                lines.append('serve        OK: %s' % desc)

    if root is not None:
        # region posture: the latest committed regiontrace round (the
        # multi-fleet front door, docs/SERVING.md "Region").  Two hard
        # failures: a lost request (no structured verdict) and an
        # unverified result-cache hit served stamped verified — the
        # verified stamp is a chain-of-custody claim, and a forged one
        # is worse than no cache at all.  Starvation (an interactive
        # request dying of old age under a bulk flood) warns: it means
        # fair share is not holding.
        from .regress import region_summary
        reg = region_summary(root)
        if reg is None:
            lines.append('region       SKIP: no regiontrace record in '
                         'any committed bench round')
        elif 'error' in reg:
            warn.append('region')
            lines.append('region       WARN: region summary '
                         'unavailable (%s)' % reg['error'])
        else:
            desc = ('%s req over %s fleet(s); cache hit rate %s '
                    '(%s hit(s)); spills=%s joins=%s (re-formed '
                    '%s->%s); throttled=%s; interactive p99 %ss'
                    % (reg.get('requests', '?'),
                       reg.get('fleet_count', reg.get('fleets', '?')),
                       reg.get('hit_rate', '?'),
                       reg.get('result_hits', '?'),
                       reg.get('spills', '?'), reg.get('joins', '?'),
                       reg.get('reformed_from', '?'),
                       reg.get('reformed_to', '?'),
                       reg.get('throttled', '?'),
                       reg.get('interactive_p99_s', '?')))
            if reg.get('lost'):
                fail.append('region')
                lines.append('region       FAIL: %s request(s) lost '
                             'WITHOUT a structured verdict (%s) — '
                             'every region submission must end as a '
                             'result' % (reg['lost'], desc))
            elif reg.get('unverified_as_verified'):
                fail.append('region')
                lines.append('region       FAIL: %s unverified '
                             'result-cache hit(s) served stamped '
                             'verified — the stamp must only ever '
                             'mean shadow-verified (%s)'
                             % (reg['unverified_as_verified'], desc))
            elif reg.get('cache_bit_identical') is False:
                fail.append('region')
                lines.append('region       FAIL: cached result NOT '
                             'bit-identical to a fresh recomputation '
                             '(%s)' % desc)
            elif reg.get('starved'):
                warn.append('region')
                lines.append('region       WARN: %s interactive '
                             'request(s) starved under the bulk '
                             'flood — fair share is not holding (%s)'
                             % (reg['starved'], desc))
            else:
                lines.append('region       OK: %s' % desc)

    if root is not None:
        # ingestion posture: the latest committed ingest round.  The
        # WARN condition is cache thrash — more evictions than hits
        # means the catalog cache is churning instead of serving, so
        # repeat requests re-pay ingestion (shrink the catalogs or
        # grow the budget); a lost data_ref request fails like any
        # other lost serve request would.
        from .regress import ingest_summary
        ing = ingest_summary(root)
        if ing is None:
            lines.append('ingest       SKIP: no ingest record in any '
                         'committed bench round')
        elif 'error' in ing:
            warn.append('ingest')
            lines.append('ingest       WARN: ingest summary '
                         'unavailable (%s)' % ing['error'])
        else:
            desc = ('%s rows -> painted mesh at %s GB/s cold, %s GB/s '
                    'cache-hit; overlap x%s vs serialized; served=%s '
                    'from_cache=%s'
                    % (ing.get('rows', '?'), ing.get('cold_gbs', '?'),
                       ing.get('warm_gbs', '?'),
                       ing.get('overlap_speedup', '?'),
                       ing.get('serve_completed', '?'),
                       ing.get('serve_cache_hits', '?')))
            ev = ing.get('cache_evictions') or 0
            hits = ing.get('cache_hits') or 0
            if ing.get('serve_lost'):
                fail.append('ingest')
                lines.append('ingest       FAIL: %s data_ref '
                             'request(s) lost without a structured '
                             'verdict (%s)'
                             % (ing['serve_lost'], desc))
            elif ev > hits:
                warn.append('ingest')
                lines.append('ingest       WARN: cache thrash — %d '
                             'eviction(s) vs %d hit(s); repeat '
                             'requests are re-paying ingestion (%s)'
                             % (ev, hits, desc))
            else:
                lines.append('ingest       OK: %s' % desc)

    if root is not None:
        # forward-model posture: the latest committed forward round
        # (bench.py --forward, docs/FORWARD.md).  The hard failure is
        # a violated finite-difference gradient check — a forward
        # model whose deployed gradient is wrong poisons every
        # inference sample built on it, however fast it runs.  A
        # recovery that does not beat the classical FFTRecon baseline
        # WARNs: the pipeline is differentiable but the inference
        # configuration is not earning its keep.
        from .regress import forward_summary
        fwd = forward_summary(root)
        if fwd is None:
            lines.append('forward      SKIP: no forward record in any '
                         'committed bench round')
        elif 'error' in fwd:
            warn.append('forward')
            lines.append('forward      WARN: forward summary '
                         'unavailable (%s)' % fwd['error'])
        else:
            desc = ('mesh%s/part%s x%s steps, %s paint (%s adjoint); '
                    'grad %ss = x%s forward; recovery r=%s vs '
                    'FFTRecon r=%s'
                    % (fwd.get('nmesh', '?'), fwd.get('npart', '?'),
                       fwd.get('pm_steps', '?'),
                       fwd.get('paint_method', '?'),
                       fwd.get('adjoint_mode', '?'),
                       fwd.get('grad_s', '?'),
                       fwd.get('grad_overhead', '?'),
                       fwd.get('r_recovered', '?'),
                       fwd.get('r_fftrecon', '?')))
            if fwd.get('grad_check_ok') is False:
                fail.append('forward')
                lines.append('forward      FAIL: finite-difference '
                             'gradient check VIOLATED (rel err %s) — '
                             'the deployed forward model is not '
                             'differentiable (%s)'
                             % (fwd.get('grad_rel_err', '?'), desc))
            elif fwd.get('beats_baseline') is False:
                warn.append('forward')
                lines.append('forward      WARN: gradient recovery '
                             'does NOT beat the FFTRecon baseline '
                             '(%s)' % desc)
            else:
                lines.append('forward      OK: %s' % desc)

    if root is not None:
        # bispectrum posture: the latest committed bispectrum round
        # (bench.py --bispectrum, docs/BISPECTRUM.md).  The hard
        # failure is cross-path disagreement in the overlap band —
        # the FFT and direct estimators measure the SAME statistic
        # wherever no triangle can alias, so differing triangle
        # counts or divergent B means one estimator is wrong.
        from .regress import bispectrum_summary
        bsp = bispectrum_summary(root)
        if bsp is None:
            lines.append('bispectrum   SKIP: no bispectrum record in '
                         'any committed bench round')
        elif 'error' in bsp:
            warn.append('bispectrum')
            lines.append('bispectrum   WARN: bispectrum summary '
                         'unavailable (%s)' % bsp['error'])
        else:
            desc = ('mesh%s/part%s x%s shells; fft %ss vs direct %ss '
                    '(%s faster at this shape, tile %s)'
                    % (bsp.get('nmesh', '?'), bsp.get('npart', '?'),
                       bsp.get('nbins', '?'), bsp.get('fft_s', '?'),
                       bsp.get('direct_s', '?'),
                       bsp.get('faster', '?'),
                       bsp.get('pairblock_tile', '?')))
            if bsp.get('closure_overlap') and (
                    bsp.get('ntri_bit_identical') is False
                    or bsp.get('agree_ok') is False):
                fail.append('bispectrum')
                lines.append('bispectrum   FAIL: the FFT and direct '
                             'estimators DISAGREE in the closure '
                             'overlap (ntri identical: %s, B max rel '
                             '%s) — one of them is wrong (%s)'
                             % (bsp.get('ntri_bit_identical', '?'),
                                bsp.get('b_max_rel', '?'), desc))
            elif not bsp.get('closure_overlap'):
                warn.append('bispectrum')
                lines.append('bispectrum   WARN: measured shape has '
                             'no alias-free closure overlap — the '
                             'cross-path agreement went unchecked '
                             '(%s)' % desc)
            else:
                lines.append('bispectrum   OK: agreement max rel %s '
                             'over %s shells — %s'
                             % (bsp.get('b_max_rel', '?'),
                                bsp.get('nbins', '?'), desc))

    if root is not None:
        # integrity posture: tripwire violations caught vs retried
        # clean, the shadow-verification ledger, and quarantined
        # ranks.  The ONE hard failure is an unacknowledged shadow
        # mismatch — a re-execution disagreed with the primary and no
        # integrity retry followed, so a silently-divergent result may
        # have been delivered.  A quarantined rank is the system
        # working, but the hardware needs a look: WARN.
        from .regress import integrity_summary
        integ = integrity_summary(root)
        if integ is None:
            lines.append('integrity    SKIP: no integrity-stamped '
                         'record, shadow ledger, or quarantine '
                         'evidence in any committed round')
        elif 'error' in integ:
            warn.append('integrity')
            lines.append('integrity    WARN: integrity summary '
                         'unavailable (%s)' % integ['error'])
        else:
            desc = ('%d stamped record(s): %d violation(s) caught, '
                    '%d retried clean; shadow %d verified / %d '
                    'mismatch'
                    % (integ.get('stamped_records', 0),
                       integ.get('violations', 0),
                       integ.get('retried', 0),
                       integ.get('shadow_verified', 0),
                       integ.get('shadow_mismatch', 0)))
            unack = integ.get('unacknowledged_mismatch', 0)
            quarantined = integ.get('quarantined') or []
            if unack:
                fail.append('integrity')
                lines.append('integrity    FAIL: %d shadow '
                             'mismatch(es) with NO integrity retry '
                             '(%s) — a divergent result may have been '
                             'delivered; see docs/INTEGRITY.md'
                             % (unack, desc))
            elif quarantined:
                warn.append('integrity')
                lines.append('integrity    WARN: rank(s) %s '
                             'QUARANTINED in the sealed fleet '
                             'manifest (%s) — the fleet healed '
                             'itself, but the hardware behind those '
                             'ranks needs attention'
                             % (', '.join(map(str, quarantined)),
                                desc))
            else:
                lines.append('integrity    OK: %s' % desc)

    if root is not None:
        # SLO posture: the latest bench round carrying an slo stamp
        # (diagnostics/slo.py).  Fast-window burn over threshold means
        # the error budget dies in days — FAIL (a page); slow-window
        # burn over 1.0 is budget-on-track-to-exhaust — WARN (a
        # ticket).  An orphaned or incomplete request waterfall fails
        # too: a trace that cannot be followed end-to-end is the
        # observability analogue of a lost request.  Tracing overhead
        # at or over 5% fails — telemetry must never become the
        # workload.
        from .regress import slo_summary
        slo = slo_summary(root)
        if slo is None:
            lines.append('slo          SKIP: no slo-stamped record in '
                         'any committed bench round')
        elif 'error' in slo:
            warn.append('slo')
            lines.append('slo          WARN: slo summary unavailable '
                         '(%s)' % slo['error'])
        else:
            burns = '; '.join(
                '%s %s (burn fast %s / slow %s)'
                % (c, d.get('verdict', '?'), d.get('fast_burn', '?'),
                   d.get('slow_burn', '?'))
                for c, d in sorted((slo.get('classes') or {}).items()))
            ov = slo.get('overhead')
            desc = ('%s/%s waterfall(s) complete, %s orphan span(s); '
                    '%s%s'
                    % (slo.get('complete', '?'), slo.get('traces', '?'),
                       slo.get('orphan_spans', '?'), burns or '-',
                       '; tracing overhead %.1f%%' % (100.0 * ov)
                       if ov is not None else ''))
            incomplete = (slo.get('traces') or 0) \
                - (slo.get('complete') or 0)
            if slo.get('verdict') == 'FAIL':
                fail.append('slo')
                lines.append('slo          FAIL: fast-window burn '
                             'rate over threshold — the error budget '
                             'is being consumed at page speed (%s)'
                             % desc)
            elif ov is not None and ov >= 0.05:
                fail.append('slo')
                lines.append('slo          FAIL: tracing overhead '
                             '%.1f%% is at or over the 5%% budget '
                             '(%s)' % (100.0 * ov, desc))
            elif incomplete or slo.get('orphan_spans'):
                fail.append('slo')
                lines.append('slo          FAIL: %s request '
                             'waterfall(s) incomplete / %s orphan '
                             'span(s) — every request must render a '
                             'fully linked waterfall (%s)'
                             % (incomplete,
                                slo.get('orphan_spans', '?'), desc))
            elif slo.get('verdict') == 'WARN':
                warn.append('slo')
                lines.append('slo          WARN: slow-window burn '
                             'rate over 1.0 — the error budget is on '
                             'track to exhaust (%s)' % desc)
            else:
                lines.append('slo          OK: %s' % desc)

    verdict = 'FAIL (%s)' % ', '.join(fail) if fail else \
        ('WARN (%s)' % ', '.join(warn) if warn else 'OK')
    out.write('== nbodykit-tpu doctor ==\n')
    for line in lines:
        out.write(line + '\n')
    out.write('VERDICT: %s\n' % verdict)
    if fail:
        # seal the flight recorder beside the trace: a FAIL verdict is
        # a post-mortem moment and the last N request summaries are
        # exactly what it wants
        from .export import FLIGHT
        FLIGHT.dump('doctor.fail')
    return 1 if fail else 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog='python -m nbodykit_tpu.diagnostics',
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument('--self-check', action='store_true',
                    help='round-trip a trace end to end; exit 0 on '
                         'success')
    ap.add_argument('--path', default=None,
                    help='directory for --self-check artifacts '
                         '(default: a private temp dir, removed after)')
    ap.add_argument('--report', metavar='TRACE',
                    help='print the text report for a trace '
                         'file/directory')
    ap.add_argument('--analyze', metavar='TRACE',
                    help='fleet analysis of a per-process trace '
                         'directory: merged timeline, stragglers, '
                         'critical path, hangs')
    ap.add_argument('--regress', metavar='ROOT', nargs='?',
                    const='.', default=None,
                    help='build BENCH_HISTORY.json from the bench '
                         'record family under ROOT (default .) and '
                         'print verdicts; exits nonzero on malformed '
                         'records')
    ap.add_argument('--threshold', type=float, default=0.25,
                    help='relative regression threshold for --regress '
                         '/ --doctor (default 0.25)')
    ap.add_argument('--chrome', metavar='TRACE',
                    help='export a trace to chrome_trace.json')
    ap.add_argument('--lint', metavar='ROOT', nargs='?', const='.',
                    default=None,
                    help='run the shard-safety static analyzer over '
                         "ROOT's package (default .), gated on "
                         'ROOT/lint_baseline.json when present')
    ap.add_argument('--doctor', action='store_true',
                    help='self-check + analyze + regress, one verdict '
                         'block')
    ap.add_argument('--trace', default=None,
                    help='trace directory for --doctor (default: '
                         '$NBKIT_DIAGNOSTICS)')
    ap.add_argument('--root', default='.',
                    help='bench-record root for --doctor (default .)')
    ap.add_argument('--self-check-only', action='store_true',
                    help='restrict --doctor to the self-check')
    args = ap.parse_args(argv)

    if args.doctor or args.self_check_only:
        trace = args.trace if args.trace is not None \
            else os.environ.get('NBKIT_DIAGNOSTICS') or None
        return run_doctor(trace=trace, root=args.root,
                          self_check_only=args.self_check_only,
                          threshold=args.threshold)
    if args.self_check:
        return self_check(args.path)
    if args.report:
        from . import render_text, summarize
        if not os.path.exists(args.report):
            print('no such trace: %s' % args.report, file=sys.stderr)
            return 2
        sys.stdout.write(render_text(summarize(trace_path=args.report)))
        return 0
    if args.analyze:
        return run_analyze(args.analyze)
    if args.regress is not None:
        return run_regress(args.regress, threshold=args.threshold)
    if args.lint is not None:
        return run_lint_cmd(args.lint)
    if args.chrome:
        from . import export_chrome_trace
        print(export_chrome_trace(args.chrome))
        return 0
    ap.print_help()
    return 2


def main_selfcheck(argv=None):
    """Entry point for the ``nbodykit-tpu-selfcheck`` console script:
    a bare invocation runs ``--self-check``; any explicit arguments
    are passed through to :func:`main` unchanged."""
    argv = sys.argv[1:] if argv is None else argv
    return main(argv or ['--self-check'])


def main_doctor(argv=None):
    """Entry point for the ``nbodykit-tpu-doctor`` console script:
    runs ``--doctor`` with any further arguments passed through
    (``--self-check-only``, ``--trace DIR``, ``--root R``, ...)."""
    argv = sys.argv[1:] if argv is None else argv
    return main(['--doctor'] + list(argv))


if __name__ == '__main__':
    sys.exit(main())
