"""The Supervisor: classify faults, retry transients, degrade on OOM.

Round 5's three losses map to three error classes with three correct
responses, and nothing in the stack applied any of them:

- ``UNAVAILABLE`` (device loss) is *transient*: the correct
  response is a bounded retry with exponential backoff + jitter;
- ``RESOURCE_EXHAUSTED`` (HBM OOM) is *deterministic for a given
  program shape* — retrying the identical program is futile, but the
  codebase already exposes a memory ladder nothing selects adaptively:
  the FFT dispatch steps in-jit → chunked → eager lowmem as
  ``fft_chunk_bytes`` shrinks (parallel/dfft.py), and the paint
  bounds its live set via ``paint_chunk_size`` (ops/paint.py,
  pmesh.py).  The correct response is to *step down that ladder* and
  re-run;
- ``DEADLINE_EXCEEDED`` is retried like a transient (a runtime that
  hangs and then recovers surfaces as deadlines);
- anything else is *fatal* and re-raised untouched — a real bug must
  never be retried into flakiness.

Every retry / degradation is emitted as a ``resilience.*`` span and
counter (:mod:`..diagnostics`), so the merged fleet trace shows what
the supervisor did and the doctor surfaces the totals.
"""

import random
import time

from ..diagnostics import counter, current_tracer, span
from .faults import fault_point

# error classes
TRANSIENT = 'transient'
OOM = 'oom'
DEADLINE = 'deadline'
INTEGRITY = 'integrity'
FATAL = 'fatal'

# gRPC-status / runtime substrings, checked in order: OOM first, since
# an allocator message can mention the device that was lost
_OOM_MARKERS = ('RESOURCE_EXHAUSTED', 'RESOURCE EXHAUSTED',
                'Out of memory', 'out of memory', 'OOM')
_DEADLINE_MARKERS = ('DEADLINE_EXCEEDED', 'Deadline Exceeded',
                     'deadline exceeded')
_TRANSIENT_MARKERS = ('UNAVAILABLE', 'DATA_LOSS', 'socket closed',
                      'connection reset', 'failed to connect',
                      'device lost')


def classify_error(exc):
    """One of TRANSIENT / OOM / DEADLINE / INTEGRITY / FATAL for a
    raised error.

    Classification is by message substring — the runtime's gRPC status
    prefixes (``UNAVAILABLE: ...``) survive every re-wrap the stack
    applies, while the exception *types* do not (XlaRuntimeError covers
    all of them).  ``MemoryError`` is OOM regardless of text;
    integrity violations carry the ``DATA_CORRUPTION:`` prefix
    (resilience/integrity.py) through the same discipline."""
    if isinstance(exc, MemoryError):
        return OOM
    text = str(exc)
    if 'DATA_CORRUPTION' in text:
        return INTEGRITY
    for marker in _OOM_MARKERS:
        if marker in text:
            return OOM
    for marker in _DEADLINE_MARKERS:
        if marker in text:
            return DEADLINE
    for marker in _TRANSIENT_MARKERS:
        if marker in text:
            return TRANSIENT
    return FATAL


class RetryPolicy(object):
    """Bounded exponential backoff with deterministic jitter.

    ``backoff_s(attempt)`` is ``base * factor**attempt`` capped at
    ``max_s``, plus up to ``jitter`` of itself from a policy-local RNG
    (seeded, so tests and multi-process fleets are reproducible)."""

    def __init__(self, max_retries=3, base_s=0.5, factor=2.0,
                 max_s=30.0, jitter=0.5, seed=0):
        self.max_retries = int(max_retries)
        self.base_s = float(base_s)
        self.factor = float(factor)
        self.max_s = float(max_s)
        self.jitter = float(jitter)
        self._rng = random.Random(seed)

    def backoff_s(self, attempt):
        d = min(self.base_s * self.factor ** attempt, self.max_s)
        return d * (1.0 + self.jitter * self._rng.random())


class DegradationLadder(object):
    """Ordered rungs of graceful degradation.  Each rung is a
    ``(label, apply)`` pair; ``apply()`` performs the step (typically
    a ``set_options`` change) and returns a detail dict for the
    record.  :meth:`step` applies the next rung, or returns None when
    exhausted."""

    def __init__(self, rungs):
        self.rungs = list(rungs)
        self.applied = []

    def step(self):
        i = len(self.applied)
        if i >= len(self.rungs):
            return None
        label, apply = self.rungs[i]
        detail = apply() or {}
        self.applied.append((label, detail))
        return label, detail


def _halve_option(option, floor):
    """A ladder rung halving a global option (not below ``floor``)
    and pinning the result, so every later attempt in this degraded
    run stays below the OOM point."""
    def apply():
        import nbodykit_tpu
        from .. import _global_options
        cur = int(_global_options[option])
        new = max(int(floor), cur // 2)
        nbodykit_tpu.set_options(**{option: new})
        return {option: new, 'was': cur}
    return apply


def default_ladder():
    """The FFT/paint memory ladder the codebase already exposes,
    as supervisor rungs.

    Halving ``fft_chunk_bytes`` pulls single-device FFTs out of the
    one-shot in-jit program into the chunked / eager-lowmem drivers
    (parallel/dfft.py dispatches on output bytes vs this target, for
    r2c, c2r and the c2c path convpower's odd multipoles use) with
    ever-smaller slabs; halving ``paint_chunk_size`` halves the paint
    batch the host-streaming path keeps live (pmesh.py).  Rungs
    alternate so one OOM doesn't collapse both knobs at once."""
    return DegradationLadder([
        ('fft_chunk_bytes/2', _halve_option('fft_chunk_bytes', 1 << 24)),
        ('paint_chunk_size/2',
         _halve_option('paint_chunk_size', 1 << 18)),
        ('fft_chunk_bytes/2', _halve_option('fft_chunk_bytes', 1 << 24)),
        ('paint_chunk_size/2',
         _halve_option('paint_chunk_size', 1 << 18)),
    ])


def _halve_scoped(opts, option, floor):
    """A ladder rung halving an option INSIDE a caller-owned mapping
    (not below ``floor``).  The first step seeds from the mapping's
    current value when present, else from the option as it stands —
    same pinning discipline as :func:`_halve_option` but with zero
    writes to the process-wide options."""
    def apply():
        from .. import _global_options
        cur = opts.get(option)
        if cur is None:
            cur = _global_options[option]
        cur = int(cur)
        new = max(int(floor), cur // 2)
        opts[option] = new
        return {option: new, 'was': cur}
    return apply


def scoped_ladder(opts):
    """:func:`default_ladder` writing into ``opts`` (a caller-owned
    dict) instead of the process-wide options.

    This is the multi-tenant form: one request's OOM response must
    reconfigure THAT request, not every other tenant sharing the
    process.  The serving layer steps this ladder at admission
    (:mod:`nbodykit_tpu.serve.admission`) and at runtime, then applies
    the accumulated ``opts`` with :func:`nbodykit_tpu.option_scope`
    around just that request's execution."""
    return DegradationLadder([
        ('fft_chunk_bytes/2',
         _halve_scoped(opts, 'fft_chunk_bytes', 1 << 24)),
        ('paint_chunk_size/2',
         _halve_scoped(opts, 'paint_chunk_size', 1 << 18)),
        ('fft_chunk_bytes/2',
         _halve_scoped(opts, 'fft_chunk_bytes', 1 << 24)),
        ('paint_chunk_size/2',
         _halve_scoped(opts, 'paint_chunk_size', 1 << 18)),
    ])


class Supervisor(object):
    """Run callables under per-error-class policy.

    Parameters
    ----------
    name : str — names the supervisor's fault point
        (``<name>.attempt``, fired before every attempt) and labels
        its spans/events.
    policy : RetryPolicy — transient/deadline retry budget + backoff.
    ladder : DegradationLadder or None — OOM response; None re-raises
        the first OOM (no silent degradation unless asked for).
    checkpoint : CheckpointStore or None — enables :meth:`save` /
        :meth:`resume`.
    sleep : injectable for tests (defaults to ``time.sleep``).
    """

    def __init__(self, name, policy=None, ladder=None, checkpoint=None,
                 sleep=time.sleep):
        self.name = str(name)
        self.policy = policy if policy is not None else RetryPolicy()
        self.ladder = ladder
        self.checkpoint = checkpoint
        self.sleep = sleep
        self.events = []

    # -- event plumbing ---------------------------------------------------

    # counter name (plural) -> trace event span name
    _EVENT_SPANS = {'retries': 'resilience.retry',
                    'degradations': 'resilience.degrade',
                    'resumes': 'resilience.resume',
                    'integrity_retries': 'resilience.integrity_retry'}

    def _event(self, kind, **attrs):
        attrs['task'] = self.name
        self.events.append(dict(attrs, kind=kind))
        counter('resilience.%s' % kind).add(1)
        tr = current_tracer()
        if tr is not None:
            tr.event(self._EVENT_SPANS[kind], attrs)

    # -- checkpoint conveniences ------------------------------------------

    def save(self, key, state, arrays=None):
        """Checkpoint progress (no-op without a store)."""
        if self.checkpoint is None:
            return None
        return self.checkpoint.save(key, state, arrays=arrays)

    def resume(self, key, validate=None):
        """``(state, arrays)`` from the last checkpoint, or None.  A
        hit is a *resume*: counted and visible in the trace.  An
        optional ``validate(state) -> bool`` rejects a checkpoint
        written for a different unit of work (wrong rep target, stale
        config) WITHOUT emitting a resume event."""
        if self.checkpoint is None:
            return None
        got = self.checkpoint.load(key)
        if got is None:
            return None
        if validate is not None and not validate(got[0]):
            return None
        self._event('resumes', key=str(key))
        return got

    def done(self, key):
        """Drop ``key``'s checkpoint (the unit of work completed)."""
        if self.checkpoint is not None:
            self.checkpoint.delete(key)

    # -- the run loop -----------------------------------------------------

    def run(self, fn, *args, **kwargs):
        """Call ``fn(*args, **kwargs)`` under the per-class policy:
        bounded backoff retries for TRANSIENT/DEADLINE, ladder
        degradation for OOM, exactly-one retry for INTEGRITY (a
        transient bit flip heals on re-execution; a sick chip fails
        again, and every strike lands in the fleet's SuspectTracker
        either way), immediate re-raise for FATAL (and for exhausted
        budgets/ladders)."""
        retries = 0
        integrity_retried = False
        while True:
            try:
                # inside the try: injected faults at the attempt point
                # go through the same classification as real ones
                fault_point('%s.attempt' % self.name)
                return fn(*args, **kwargs)
            except Exception as e:
                kind = classify_error(e)
                if kind == INTEGRITY:
                    # attribution first: the strike is recorded whether
                    # or not the retry heals, so a chip that corrupts
                    # once per K tasks still accumulates toward
                    # quarantine (resilience/fleet.py)
                    from .fleet import suspect_tracker
                    rank = getattr(e, 'rank', None)
                    site = getattr(e, 'site', 'unknown')
                    suspect_tracker().strike(rank, site=site,
                                             task=self.name)
                    if integrity_retried:
                        raise
                    integrity_retried = True
                    self._event('integrity_retries', site=site,
                                rank=rank, error=str(e)[:200])
                    continue
                if kind == OOM:
                    rung = self.ladder.step() if self.ladder is not None \
                        else None
                    if rung is None:
                        raise
                    label, detail = rung
                    self._event('degradations', rung=label,
                                detail=detail, error=str(e)[:200])
                    continue
                if kind in (TRANSIENT, DEADLINE):
                    if retries >= self.policy.max_retries:
                        raise
                    from .fleet import preemption_requested
                    if preemption_requested():
                        # a SIGTERM'd process must spend its grace
                        # budget sealing a checkpoint, not sleeping in
                        # backoff — surface the error and let the safe
                        # point raise Preempted
                        raise
                    delay = self.policy.backoff_s(retries)
                    retries += 1
                    self._event('retries', attempt=retries,
                                delay_s=round(delay, 3), cls=kind,
                                error=str(e)[:200])
                    # the wait itself is a span: visible dead time in
                    # the merged timeline, attributed to resilience
                    with span('resilience.backoff', task=self.name,
                              attempt=retries, delay_s=round(delay, 3)):
                        self.sleep(delay)
                    continue
                raise
