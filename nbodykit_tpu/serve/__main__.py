"""Serve CLI: run the analysis server against a synthetic trace.

    nbodykit-tpu-serve --trace 100      (== python -m nbodykit_tpu.serve)
        Generate a deterministic 100-request trace, replay it through
        an :class:`~nbodykit_tpu.serve.AnalysisServer` on the local
        devices, print the serving scorecard (and exit 1 if any
        request was lost without a structured verdict).

    Options: --trace N · --seed S · --per-task K (devices per worker
    sub-mesh) · --max-batch B · --max-delay-ms MS (batch window) ·
    --max-queue Q · --hbm-gb G (admission budget is 0.85x this;
    default: what the device reports) ·
    --deadline-s D · --devices N (CPU: force N virtual devices) ·
    --json PATH (write the full summary + per-request verdicts).

Fault injection rides the usual channel: ``NBKIT_FAULTS`` (e.g.
``serve.request.attempt@3:unavailable``) — survived faults show in
the scorecard's retried/degraded/resumed columns.  The 1k-request
benchmark round lives in ``bench.py --serve-trace`` (same machinery,
BENCH-stamped).
"""

import argparse
import json
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog='nbodykit-tpu-serve',
        description='replay a synthetic multi-tenant trace through '
                    'the analysis server')
    ap.add_argument('--trace', type=int, default=100,
                    help='number of requests to generate (default 100)')
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--per-task', type=int, default=1)
    ap.add_argument('--max-batch', type=int, default=8)
    ap.add_argument('--max-delay-ms', type=float, default=20.0)
    ap.add_argument('--max-queue', type=int, default=1024)
    ap.add_argument('--hbm-gb', type=float, default=None,
                    help='per-device HBM to admit against (default: '
                         'what the device reports)')
    ap.add_argument('--deadline-s', type=float, default=300.0)
    ap.add_argument('--devices', type=int, default=None)
    ap.add_argument('--json', default=None,
                    help='write summary + per-request verdicts here')
    args = ap.parse_args(argv)

    from .._jax_compat import enable_compile_cache, set_cpu_devices
    if args.devices:
        set_cpu_devices(args.devices)
    enable_compile_cache()

    import nbodykit_tpu  # noqa: F401  (option/env wiring)
    from . import AnalysisServer, BatchPolicy, generate_trace, replay

    trace = generate_trace(args.trace, seed=args.seed,
                           deadline_s=args.deadline_s)
    server = AnalysisServer(
        per_task=args.per_task, max_queue=args.max_queue,
        hbm_bytes=args.hbm_gb * 1e9 if args.hbm_gb else None,
        batch=BatchPolicy(max_batch=args.max_batch,
                          max_delay_s=args.max_delay_ms / 1e3))
    with server:
        replay(server, trace, seed=args.seed)
        summary = server.summary()

    if args.json:
        from ..diagnostics import atomic_write
        payload = dict(summary, verdicts=[
            r.to_dict() for _, r in sorted(server.results.items())])
        atomic_write(args.json,
                     json.dumps(payload, indent=1, sort_keys=True))

    for key in ('submitted', 'completed', 'rejected', 'evicted',
                'failed', 'lost', 'retried', 'fault_degraded',
                'resumed', 'admit_degraded', 'programs'):
        print('%-16s %s' % (key, summary[key]))
    for key in ('p50_s', 'p99_s', 'rps'):
        v = summary[key]
        print('%-16s %s' % (key, '%.4f' % v if v is not None else '-'))
    return 1 if summary['lost'] else 0


if __name__ == '__main__':
    sys.exit(main())
