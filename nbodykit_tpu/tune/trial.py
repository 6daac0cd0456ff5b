"""Measured micro-trials: run every candidate, record the winner.

Each candidate runs warmup + timed reps *under the resilience
Supervisor* (:mod:`..resilience`): a device loss gets a bounded
retry, and an HBM OOM (``RESOURCE_EXHAUSTED``) — or any other raised
error — marks the **candidate** infeasible instead of killing the tune
run; the next candidate still gets measured.  Infeasibility is data:
it lands in the cache entry (and the doctor's posture line) so the
next round knows a kernel refused to run at that shape, not just that
it was slow.

Every trial is a ``tune.trial`` span plus ``tune.trials`` /
``tune.infeasible`` counters (:mod:`..diagnostics`), and the
Supervisor's fault point (``tune.trial.attempt``, fired before every
attempt) makes the infeasible path deterministically testable:
``NBKIT_FAULTS='tune.trial.attempt@1:resource_exhausted'`` condemns
the first attempted candidate on the CPU mesh (docs/RESILIENCE.md).

Trial *plans* are deterministic — candidates, order, reps and seeds
are pure functions of the requested contexts — so two invocations of
``nbodykit-tpu-tune`` at the same shapes measure the same programs.
"""

import time

from .cache import (TuneCache, canonical_dtype, device_signature,
                    make_key, utcnow)

DEFAULT_REPS = 2


def _mesh_nproc():
    from ..parallel.runtime import CurrentMesh, mesh_size
    return mesh_size(CurrentMesh.resolve(None))


def plan_spaces(pairs, reps=DEFAULT_REPS, signature=None):
    """The deterministic trial plan for ``(space, ctx)`` pairs: one
    record per pair with the cache key and the candidate names, in
    execution order.  Pure bookkeeping — builds no arrays, runs
    nothing."""
    sig = signature or device_signature(count=_mesh_nproc())
    plan = []
    for space, ctx in pairs:
        sclass = space.shape_class(ctx)
        dtype = canonical_dtype(ctx.get('dtype', 'f4'))
        plan.append({
            'op': space.op,
            'key': make_key(sig[0], sig[1], sig[2], space.op, sclass,
                            dtype),
            'shape_class': sclass,
            'context': {k: ctx[k] for k in sorted(ctx)},
            'reps': int(reps),
            'candidates': [c.name for c in space.candidates(ctx)],
        })
    return plan


def run_space(space, ctx, cache=None, reps=DEFAULT_REPS, policy=None,
              signature=None, log=None):
    """Measure every candidate of ``space`` at ``ctx`` and commit the
    winner to ``cache``.  Returns the cache entry (committed whenever
    at least one candidate was feasible; an all-infeasible entry is
    committed too, with ``winner: null`` — resolution skips it but the
    doctor reports it)."""
    from .. import set_options
    from ..diagnostics import counter, span
    from ..resilience import RetryPolicy, Supervisor, classify_error

    cache = cache if cache is not None else TuneCache()
    sig = signature or device_signature(count=_mesh_nproc())
    sclass = space.shape_class(ctx)
    dtype = canonical_dtype(ctx.get('dtype', 'f4'))
    reps = int(reps)
    trials = {}

    with span('tune.space', op=space.op, shape_class=sclass,
              platform=sig[0], device_count=sig[2]):
        for cand in space.candidates(ctx):
            sup = Supervisor('tune.trial',
                             policy=policy or RetryPolicy(
                                 max_retries=1, base_s=0.05,
                                 max_s=0.2))
            rec = {'options': dict(cand.options)}
            t_span = time.perf_counter()
            with span('tune.trial', op=space.op, candidate=cand.name,
                      shape_class=sclass):
                try:
                    with set_options(**cand.options):
                        once = space.make_runner(ctx)
                        sup.run(once)                 # warmup/compile
                        rec['warm_s'] = round(
                            time.perf_counter() - t_span, 6)
                        t0 = time.perf_counter()
                        for _ in range(reps):
                            sup.run(once)
                        rec['wall_s'] = round(
                            (time.perf_counter() - t0) / reps, 6)
                        rec['reps'] = reps
                    counter('tune.trials').add(1)
                except Exception as e:
                    rec['infeasible'] = classify_error(e)
                    rec['error'] = str(e)[:200]
                    counter('tune.infeasible').add(1)
            retr = [e for e in sup.events if e['kind'] == 'retries']
            if retr:
                rec['retries'] = len(retr)
            trials[cand.name] = rec
            if log is not None:
                log('%s/%s %s: %s'
                    % (space.op, sclass, cand.name,
                       '%.4f s' % rec['wall_s'] if 'wall_s' in rec
                       else 'INFEASIBLE (%s)' % rec['infeasible']))

    feasible = {name: rec for name, rec in trials.items()
                if 'wall_s' in rec}
    winner_name = min(feasible, key=lambda k: feasible[k]['wall_s']) \
        if feasible else None
    entry = {
        'platform': sig[0], 'device_kind': sig[1],
        'device_count': sig[2], 'op': space.op, 'shape_class': sclass,
        'dtype': dtype,
        'context': {k: ctx[k] for k in sorted(ctx)},
        'winner_name': winner_name,
        'winner': {k: v for k, v in
                   trials[winner_name]['options'].items()
                   if k in space.provides} if winner_name else None,
        'trials': trials,
        'infeasible': sorted(name for name, rec in trials.items()
                             if 'infeasible' in rec),
        'measured_at': utcnow(),
    }
    cache.put(entry)
    counter('tune.entries_committed').add(1)
    return entry
