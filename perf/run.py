"""One cell of the benchmark, once.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  Reads the cell from ``BENCHMARK.json`` and its
configuration, traffic, driver and layer files by name
(``perf/lib/manifest.py``); refuses to run unless JAX reports a TPU with
the cell's number of chips; builds its data on the device from
``--seed``; checks the cell's own path against plain numpy at 64^3;
warms the cell's one shape with one call; measures for ``--seconds``;
checks every timed result; prints earlier lines as it goes and the
contract's one JSON object last.

``--trace 0``: the end-to-end metrics, no instrument on.  ``--trace
1``: the per-layer metrics, from two short windows so that neither
instrument disturbs the other — (a) ``jax.profiler`` with the
library's tracer off, (b) the library's tracer (``set_options(
diagnostics=...)``) with the profiler off, for drivers whose path it
covers.  Each is half of ``--seconds`` or two calls, whichever is
longer."""

import argparse
import json
import os
import shutil
import statistics
import sys
import time

_T0 = time.perf_counter()       # process start, as near as Python sees it

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from perf.lib import manifest           # noqa: E402


def say(line, **fields):
    print(json.dumps(dict(line=line, **fields), sort_keys=True),
          flush=True)


def window(driver, seconds, min_calls=1, annotate=None):
    """Closed loop: a new call starts while the window is open (and
    until ``min_calls`` are made).  Returns walls, results, failures
    and the window's length."""
    walls, results, errors = [], [], []
    i, w0 = 0, time.perf_counter()
    while i < min_calls or time.perf_counter() - w0 < seconds:
        t0 = time.perf_counter()
        try:
            if annotate is None:
                res = driver.call(i)
            else:
                with annotate(i):
                    res = driver.call(i)
            walls.append(time.perf_counter() - t0)
            results.append(res)
        except Exception as e:          # a failed call is counted
            import traceback
            traceback.print_exc()
            errors.append('%s: %s' % (type(e).__name__, str(e)[:500]))
            if len(errors) >= 3:
                break
        i += 1
    return walls, results, errors, time.perf_counter() - w0


def measure(driver, seconds):
    """``--trace 0``: the plain window."""
    from perf.lib.checks import compile_seconds, peak_bytes
    c0 = compile_seconds()
    walls, results, errors, length = window(driver, seconds)
    peak = peak_bytes()
    failed, rec = driver.verify(results)
    say('window', calls=len(walls), walls_s=walls, window_s=length,
        compile_s_in_window=compile_seconds() - c0, errors=errors, **rec)
    values = {}
    if peak is not None:        # the CPU keeps no such statistic
        values['peak_hbm_gb'] = peak / 1e9
    if walls:
        values['call_s'] = statistics.median(walls)
    return values, len(walls) + len(errors), failed + len(errors), peak


def measure_traced(driver, seconds, files, outdir, kind):
    """``--trace 1``: windows (a) and (b), then one reader per metric."""
    import jax
    from nbodykit_tpu.lab import set_options
    from perf.lib import xplane
    from perf.lib.checks import compile_seconds, peak_bytes

    shutil.rmtree(outdir, ignore_errors=True)
    os.makedirs(outdir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host trace-me events only: the
    opts.enable_hlo_proto = False       # python tracer slows eager code
    c0 = compile_seconds()
    jax.profiler.start_trace(os.path.join(outdir, 'profile'),
                             profiler_options=opts)
    try:
        walls, results, errors, length = window(
            driver, seconds / 2.0, min_calls=2,
            annotate=lambda i: jax.profiler.TraceAnnotation(xplane.CALL))
    finally:
        jax.profiler.stop_trace()
    compile_a = compile_seconds() - c0
    failed, rec = driver.verify(results)
    say('window_a', instrument='jax.profiler', calls=len(walls),
        walls_s=walls, window_s=length, errors=errors,
        call_s=statistics.median(walls) if walls else None, **rec)
    attempted, failed = len(walls) + len(errors), failed + len(errors)
    ncalls_a = len(walls)

    spans, ncalls_b = None, 0
    if driver.library_spans:
        from nbodykit_tpu.diagnostics.trace import read_trace
        spandir = os.path.join(outdir, 'spans')
        with set_options(diagnostics=spandir):
            walls, results, errors, length = window(
                driver, seconds / 2.0, min_calls=2)
        spans = [r for r in read_trace(spandir)[0] if r.get('t') == 'span']
        ncalls_b = len(walls)
        f, rec = driver.verify(results)
        say('window_b', instrument='set_options(diagnostics)',
            calls=len(walls), walls_s=walls, window_s=length,
            errors=errors, spans=len(spans),
            call_s=statistics.median(walls) if walls else None, **rec)
        attempted += len(walls) + len(errors)
        failed += f + len(errors)

    peak = peak_bytes()
    trace = xplane.load(xplane.find_xplane(os.path.join(outdir, 'profile')))
    red = xplane.reduce(trace, ncalls_a)
    say('trace', lines=trace['lines'],
        window_from=red and red['window_from'],
        devices=red and red['devices'])
    ctx = {'cell': files['cell'], 'config': files['config'],
           'chips': files['cell']['chips'], 'device_kind': kind,
           'xplane': red, 'spans': spans,
           'ncalls_b': ncalls_b, 'compile_s_window_a': compile_a,
           'peak_bytes': peak, 'outdir': outdir}
    values = {}
    for m in files['per_layer']:
        v = manifest.layer_reader(m['name'])(ctx)
        if v is not None:       # nothing to read: left out of the line
            values[m['name']] = v
    return values, attempted, failed, peak, ctx['xplane']


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = manifest.benchmark()
    files = manifest.cell_files(bench, args.workload)
    cell, traffic = files['cell'], files['traffic']

    from nbodykit_tpu._jax_compat import enable_compile_cache
    from perf.lib.checks import device_record
    from perf.lib.peaks import peaks_for
    enable_compile_cache()
    dev = device_record()
    if dev['platform'] != 'tpu' or dev['count'] != cell['chips']:
        print('perf/run.py: %s needs %d TPU device(s), JAX reports %r'
              % (cell['name'], cell['chips'], dev), file=sys.stderr)
        return 1
    peaks_for(dev['kind'])      # an unknown kind is an error, now

    driver = manifest.driver_class(traffic['kind'])(
        files['config'], traffic, cell['chips'], args.seed)
    try:
        say('setup', workload=cell['name'], seed=args.seed, **driver.setup())
        setup_s = time.perf_counter() - _T0
        breakdown = None
        if args.trace:
            outdir = os.path.join(manifest.PERF, 'out', cell['name'])
            values, attempted, failed, peak, red = measure_traced(
                driver, args.seconds, files, outdir, dev['kind'])
            if red:
                busy = [d['busy_s'] for d in red['devices'].values()]
                dev.update(busy_s=sum(busy) / len(busy),
                           window_s=red['window_s'])
                breakdown = {'device_ops': red['device_ops'],
                             'idle_gaps': red['idle_gaps']}
            names = files['per_layer']
        else:
            values, attempted, failed, peak = measure(driver, args.seconds)
            values['setup_s'] = setup_s
            names = files['end_to_end']
    finally:
        driver.close()

    dev['memory_peak_bytes'] = peak
    out = {'correct': failed == 0 and attempted > 0,
           'attempted': attempted, 'failed': failed,
           'metrics': {m['name']: {'value': values[m['name']],
                                   'unit': m['unit']}
                       for m in names if m['name'] in values},
           'device': dev, 'workload': cell['name'], 'seed': args.seed,
           'trace': args.trace, 'setup_s': setup_s}
    if breakdown:
        out['breakdown'] = breakdown
    print(json.dumps(out), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
