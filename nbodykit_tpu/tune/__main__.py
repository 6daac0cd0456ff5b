"""Tune CLI: populate, inspect and validate the performance database.

    nbodykit-tpu-tune                         (== python -m nbodykit_tpu.tune)
        Run the default trial plan on the current backend (paint at
        two shape classes, the FFT chunk ladder, the exchange slack
        when a multi-device mesh is up, the ingest chunk-rows ladder)
        and commit the winners to TUNE_CACHE.json.

    nbodykit-tpu-tune --dry-run
        Print the deterministic trial plan (cache keys + candidates)
        WITHOUT building arrays or touching a device.  Bounded and
        cheap — the smoke gate runs it.

    nbodykit-tpu-tune --validate
        Schema-check the committed cache and print its posture
        summary; exit 1 on a malformed file (the smoke gate).

    Options: --ops paint,fft,exchange,ingest,bspec
    · --paint-shapes 64x1e4,128x1e5
    · --fft-nmesh 64,128 · --pencil PXxPY (fft decomp factorization)
    · --reps N · --cache PATH · --devices N (CPU: force N virtual
    devices and tune on that mesh).

The committed repo-root TUNE_CACHE.json is produced by exactly this
command on the 8-device CPU mesh; the on-chip run (same command on
the machine with the chip) overwrites the TPU-keyed entries without touching
the CPU ones — keys carry the platform, so the two coexist.
"""

import argparse
import json
import sys


def _parse_paint_shapes(text):
    """'64x1e4,128x1e5' -> [(64, 10000), (128, 100000)]."""
    shapes = []
    for part in str(text).split(','):
        part = part.strip()
        if not part:
            continue
        nmesh, _, npart = part.partition('x')
        shapes.append((int(nmesh), int(float(npart))))
    return shapes


def _contexts(args, spaces, nproc):
    """The deterministic (space, ctx) list for this invocation."""
    ops = [o.strip() for o in args.ops.split(',') if o.strip()]
    unknown = sorted(set(ops) - set(spaces))
    if unknown:
        raise SystemExit('unknown op(s): %s (choose from %s)'
                         % (','.join(unknown), ','.join(sorted(spaces))))
    pairs = []
    if 'paint' in ops:
        for nmesh, npart in _parse_paint_shapes(args.paint_shapes):
            pairs.append((spaces['paint'],
                          {'nmesh': nmesh, 'npart': npart,
                           'dtype': 'f4', 'resampler': 'cic',
                           'seed': 7}))
    if 'fft' in ops:
        # multi-device ffts also race fft_decomp; the ctx records the
        # (Px, Py) factorization the pencil candidate runs with
        # (--pencil override, else the near-square default), and the
        # entry is keyed under it (cache.shape_class)
        mesh_shape = None
        if nproc > 1:
            if args.pencil:
                px, _, py = args.pencil.lower().partition('x')
                mesh_shape = (int(px), int(py))
                if mesh_shape[0] * mesh_shape[1] != nproc:
                    raise SystemExit(
                        '--pencil %s does not cover %d devices'
                        % (args.pencil, nproc))
            else:
                from ..parallel.runtime import default_pencil_factor
                mesh_shape = default_pencil_factor(nproc)
        for nmesh in [int(x) for x in args.fft_nmesh.split(',') if x]:
            ctx = {'nmesh': nmesh, 'dtype': 'f4', 'seed': 7,
                   'nproc': nproc}
            if mesh_shape is not None:
                ctx['mesh_shape'] = list(mesh_shape)
            pairs.append((spaces['fft'], ctx))
    if 'exchange' in ops and nproc > 1:
        for _, npart in _parse_paint_shapes(args.paint_shapes)[-1:]:
            pairs.append((spaces['exchange'],
                          {'npart': npart, 'dtype': 'f4', 'seed': 7}))
    if 'ingest' in ops:
        # the streaming window ladder, one entry per part-count class
        # (the knob is keyed by npart alone — shape_class(npart=...))
        for nmesh, npart in _parse_paint_shapes(args.paint_shapes):
            pairs.append((spaces['ingest'],
                          {'nmesh': nmesh, 'npart': npart,
                           'dtype': 'f4', 'seed': 7}))
    if 'bspec' in ops:
        # the FFT/direct bispectrum crossover, one entry per shape
        # class (the same NMESHxNPART grid as paint: the crossover
        # moves with both the mesh the FFT path would need and the
        # particle count the direct path sums over)
        for nmesh, npart in _parse_paint_shapes(args.paint_shapes):
            pairs.append((spaces['bspec'],
                          {'nmesh': nmesh, 'npart': npart,
                           'nbins': 3, 'dtype': 'f4', 'seed': 7}))
    return pairs


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog='nbodykit-tpu-tune', description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument('--ops', default='paint,fft,exchange,ingest,bspec',
                    help='comma list of ops to tune (default: all)')
    ap.add_argument('--paint-shapes', default='64x1e4,128x1e5',
                    help="paint trial shapes as NMESHxNPART, comma-"
                         "separated (default: 64x1e4,128x1e5)")
    ap.add_argument('--fft-nmesh', default='64,128',
                    help='FFT trial mesh sizes (default: 64,128)')
    ap.add_argument('--pencil', default=None,
                    help="fft decomp trials: (Px, Py) factorization "
                         "as 'PXxPY' (default: the near-square "
                         "factorization of the device count)")
    ap.add_argument('--reps', type=int, default=2,
                    help='timed reps per candidate (default 2)')
    ap.add_argument('--cache', default=None,
                    help='cache file (default: the tune_cache option '
                         '/ $NBKIT_TUNE_CACHE / repo TUNE_CACHE.json)')
    ap.add_argument('--devices', type=int, default=None,
                    help='CPU only: force N virtual devices and tune '
                         'on that mesh (e.g. 8 for the committed '
                         'cache)')
    ap.add_argument('--dry-run', action='store_true',
                    help='print the deterministic trial plan and exit')
    ap.add_argument('--validate', action='store_true',
                    help='schema-check the cache file; exit 1 on a '
                         'malformed one')
    args = ap.parse_args(argv)

    from .cache import (TuneCache, cache_summary, device_signature,
                        validate_cache)

    cache = TuneCache(args.cache)

    if args.validate:
        problems = validate_cache(cache.path)
        if problems:
            print('TUNE_CACHE INVALID: %s' % cache.path)
            for p in problems:
                print('  - %s' % p)
            return 1
        summary = cache_summary(cache.path)
        if summary is None:
            print('tune cache OK: %s absent (cold cache — dispatch '
                  'falls back to defaults)' % cache.path)
        else:
            print('tune cache OK: %(entries)d entr%(ies)s, '
                  '%(stale)d stale (>%(days).0f d), %(inf)d '
                  'infeasible candidate(s), platforms %(plat)s'
                  % {'entries': summary['entries'],
                     'ies': 'y' if summary['entries'] == 1 else 'ies',
                     'stale': summary['stale'],
                     'days': summary['stale_days'],
                     'inf': summary['infeasible'],
                     'plat': ','.join(summary['platforms']) or '-'})
        return 0

    from .space import default_spaces
    from .trial import plan_spaces, run_space

    if args.dry_run:
        # no arrays, no mesh: plan against the process-visible devices
        # (or the forced count), purely for display
        sig = device_signature(count=args.devices)
        spaces = default_spaces()
        nproc = args.devices if args.devices else sig[2]
        plan = plan_spaces(_contexts(args, spaces, nproc),
                           reps=args.reps, signature=sig)
        print(json.dumps({'cache': cache.path, 'signature': list(sig),
                          'plan': plan}, indent=1))
        return 0

    # live run: bring up the mesh, then walk the plan.  The device
    # count must be forced BEFORE anything initializes a backend
    # (jax.default_backend()/jax.devices() lock it in), so the CPU
    # check reads the requested platform, not the live backend
    import os
    import jax
    if args.devices:
        plats = '%s %s' % (os.environ.get('JAX_PLATFORMS', ''),
                           getattr(jax.config, 'jax_platforms', '')
                           or '')
        if 'cpu' in plats:
            from .._jax_compat import set_cpu_devices
            set_cpu_devices(int(args.devices))
    from .._jax_compat import enable_compile_cache
    enable_compile_cache()
    from ..parallel.runtime import cpu_mesh, tpu_mesh, use_mesh
    from ..utils import is_mxu_backend
    mesh = tpu_mesh() if is_mxu_backend() else cpu_mesh()
    spaces = default_spaces()
    with use_mesh(mesh):
        from ..parallel.runtime import mesh_size
        nproc = mesh_size(mesh)
        pairs = _contexts(args, spaces, nproc)
        entries = []
        for space, ctx in pairs:
            entry = run_space(space, ctx, cache=cache, reps=args.reps,
                              log=lambda msg: print('[tune] ' + msg,
                                                    flush=True))
            entries.append(entry)
            print('[tune] committed %s/%s: winner=%s'
                  % (entry['op'], entry['shape_class'],
                     entry['winner_name']), flush=True)
    print(json.dumps({
        'cache': cache.path,
        'entries': len(entries),
        'winners': {'%s/%s' % (e['op'], e['shape_class']):
                    e['winner_name'] for e in entries},
        'infeasible': sum(len(e['infeasible']) for e in entries),
    }))
    return 0


def main_tune(argv=None):
    """Entry point for the ``nbodykit-tpu-tune`` console script."""
    return main(sys.argv[1:] if argv is None else argv)


if __name__ == '__main__':
    sys.exit(main())
