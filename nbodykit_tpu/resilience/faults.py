"""Deterministic fault injection: make every recovery path testable.

The faults this subsystem exists for — ``UNAVAILABLE`` device losses,
``RESOURCE_EXHAUSTED`` OOMs, SIGKILLed workers — only occur on the
real TPU fleet, which tier-1 never touches.  This harness injects
them *deterministically* on the CPU mesh so the retry / degrade /
resume machinery (:mod:`.supervise`, :mod:`.checkpoint`) is exercised
by ordinary tests instead of waiting for the hardware to misbehave.

Spec format (``set_options(faults=...)`` or ``$NBKIT_FAULTS``):

    [rankR@]point[@N]:action[,...]

``point`` names a fault point (a host-side call site instrumented
with :func:`fault_point` — e.g. ``bench.rep``, ``ckpt.write.<key>``,
``ckpt.manifest``, ``<supervisor>.attempt``), ``N`` is the 1-based
call count at which the rule fires (default 1), and ``action`` is one
of:

- ``unavailable`` / ``resource_exhausted`` / ``deadline`` /
  ``internal`` — raise a real ``XlaRuntimeError`` (the class jax's
  runtime raises; a plain RuntimeError subclass when jax is absent)
  whose message carries the canonical gRPC status prefix, so error
  classification sees exactly what the fleet produces;
- ``kill`` / ``sigkill`` — ``SIGKILL`` this process on the spot (no
  atexit, no flush): the checkpoint-atomicity and resume paths see a
  true mid-run death;
- ``sigterm`` — deliver a real SIGTERM to this process and *return*:
  with the preemption handler installed (:mod:`.fleet`) execution
  continues to the next safe point exactly as under a preemptible
  scheduler; without one the default disposition terminates.
- ``corrupt[:bits]`` — a DATA action, not an error: at a named
  data-injection point (``a2a.payload``, ``paint.accum``,
  ``serve.result``) the site consults :func:`corrupt_spec` and, when
  the rule fires, applies a deterministic stuck-at-one fault to the
  top ``bits`` (default 1) of one payload word's exponent
  (:func:`integrity.flip_bits_value` — catastrophic by construction,
  so detection never depends on the corrupted element's value).  This is how every silent-data-corruption
  detector (:mod:`.integrity`, docs/INTEGRITY.md) is exercised in CI
  without real hardware faults: the corruption flows through the
  guarded surface and the guard — not the injector — must catch it.

The optional ``rankR@`` prefix scopes a rule to one fleet rank
(``rank1@bench.rep:sigkill`` kills only rank 1), which is how the
chaos matrix kills chosen ranks of a multi-process fleet.  Call
*counting* stays rank-uniform — every process counts every targeted
point — so all ranks agree on the call index a rule names.

Each rule fires exactly once (the call count passes ``N`` once per
process).  Calls to points no rule targets cost one string lookup.
Counting is per-process and deterministic, so a multi-process fleet
given the same spec injects the same fault at the same logical step
everywhere — collective-consistent by construction.
"""

import os
import re
import signal
import threading

from ..diagnostics import counter

_lock = threading.Lock()
_counts = {}
_parsed = None          # (source_spec, rules)

_STATUS_MESSAGES = {
    'unavailable': 'UNAVAILABLE: injected fault at %s (call %d); '
                   'socket closed',
    'resource_exhausted': 'RESOURCE_EXHAUSTED: injected fault at %s '
                          '(call %d); out of memory while allocating',
    'deadline': 'DEADLINE_EXCEEDED: injected fault at %s (call %d)',
    'internal': 'INTERNAL: injected fault at %s (call %d)',
}
ACTIONS = tuple(_STATUS_MESSAGES) + ('kill', 'sigkill', 'sigterm',
                                     'corrupt')

_RANK_RE = re.compile(r'^rank(\d+)$')


class InjectedFault(RuntimeError):
    """Raised for injected faults when jax's XlaRuntimeError is not
    importable (diagnostics-only environments)."""


def error_class():
    """The exception class injected errors are raised as: the real
    ``XlaRuntimeError`` when jax is present (classification and any
    caller except-clauses see the genuine article)."""
    try:
        from jax._src.lib import xla_client
        return xla_client.XlaRuntimeError
    except Exception:
        return InjectedFault


def _spec():
    try:
        from .. import _global_options
    except ImportError:     # pragma: no cover - interpreter teardown
        return None
    try:
        return _global_options['faults']
    except KeyError:
        return None


def parse_spec(spec):
    """``[(point, nth, action), ...]`` for a spec string — rank-scoped
    rules (``rankR@point[@N]:action``) parse to 4-tuples ``(point,
    nth, action, rank)``; raises ValueError on malformed rules (a
    typo'd spec must not silently inject nothing)."""
    rules = []
    for part in str(spec).split(','):
        part = part.strip()
        if not part:
            continue
        name, _, action = part.rpartition(':')
        if not name:
            raise ValueError('fault rule %r: expected point@N:action'
                             % part)
        action = action.strip().lower()
        if name.lower().endswith(':corrupt') and action.isdigit():
            # 'point:corrupt:3' — the bits suffix landed in rpartition's
            # tail; fold it back into a single 'corrupt:N' action
            name = name[:-len(':corrupt')]
            action = 'corrupt:' + action
        base = action.partition(':')[0]
        if base not in ACTIONS or (base != 'corrupt' and base != action):
            raise ValueError('fault rule %r: unknown action %r '
                             '(choose %s)' % (part, action,
                                              '/'.join(ACTIONS)))
        if base == 'corrupt':
            bits = action.partition(':')[2]
            if bits and (not bits.isdigit() or not 1 <= int(bits) <= 30):
                raise ValueError('fault rule %r: corrupt bit count %r '
                                 'must be an integer in [1, 30]'
                                 % (part, bits))
        point, at, nth = name.partition('@')
        rank = None
        m = _RANK_RE.match(point.strip())
        if m is not None and at:
            rank = int(m.group(1))
            point, at, nth = nth.partition('@')
        try:
            n = int(nth) if at else 1
        except ValueError:
            raise ValueError('fault rule %r: call count %r is not an '
                             'integer' % (part, nth))
        point = point.strip()
        rules.append((point, n, action) if rank is None
                     else (point, n, action, rank))
    return rules


def _rules():
    global _parsed
    spec = _spec()
    if not spec:
        return ()
    cached = _parsed
    if cached is not None and cached[0] == spec:
        return cached[1]
    rules = tuple(parse_spec(spec))
    with _lock:
        _parsed = (spec, rules)
    return rules


def reset_faults():
    """Clear per-process call counts + the parsed-spec cache (test
    isolation; the spec itself lives in the options/env)."""
    global _parsed
    with _lock:
        _counts.clear()
        _parsed = None


def fault_counts():
    """Snapshot of per-point call counts (observability for tests)."""
    with _lock:
        return dict(_counts)


def fault_point(name):
    """Declare a named fault point.  Free when no spec is configured
    or no rule targets ``name``; otherwise counts the call and fires
    any rule matching (name, count) — rank-scoped rules only on their
    fleet rank, though every rank counts the call."""
    rules = _rules()
    if not rules:
        return
    mine = [r for r in rules if r[0] == name]
    if not mine:
        return
    with _lock:
        n = _counts[name] = _counts.get(name, 0) + 1
    for rule in mine:
        nth, action = rule[1], rule[2]
        if nth != n or action.startswith('corrupt'):
            # corrupt rules are DATA actions consumed by corrupt_spec
            # at the injection site, never raised from a fault point
            continue
        if len(rule) > 3:
            from .fleet import fleet_rank
            if fleet_rank() != rule[3]:
                continue
        if action in ('kill', 'sigkill'):
            # no flush, no atexit: the genuine mid-run death
            os.kill(os.getpid(), signal.SIGKILL)
        counter('resilience.faults.injected').add(1)
        if action == 'sigterm':
            # the real signal, then return: the preemption handler
            # sees exactly what a preemptible scheduler sends and the
            # run continues to its next safe point
            os.kill(os.getpid(), signal.SIGTERM)
            continue
        raise error_class()(_STATUS_MESSAGES[action] % (name, n))


def corrupt_spec(name):
    """Declare a named DATA-injection point: the number of payload
    bits to flip at this call (0 almost always).

    The query form of :func:`fault_point` for ``corrupt`` rules: the
    site calls this once per logical payload, and when a rule matches
    (name, call count) it returns the rule's bit count — the site then
    flips that many top bits of one payload word itself (the
    corruption must flow through the guarded surface so the DETECTOR
    is what gets tested, not the injector).  Counting shares
    :func:`fault_point`'s per-process table and stays rank-uniform;
    rank-scoped rules return 0 everywhere but their fleet rank (every
    rank still counts the call, so all ranks agree on indices).  Each
    rule fires once.  Free when no rule targets ``name``."""
    rules = _rules()
    if not rules:
        return 0
    mine = [r for r in rules if r[0] == name]
    if not mine:
        return 0
    with _lock:
        n = _counts[name] = _counts.get(name, 0) + 1
    for rule in mine:
        nth, action = rule[1], rule[2]
        if nth != n or not action.startswith('corrupt'):
            continue
        if len(rule) > 3:
            from .fleet import fleet_rank
            if fleet_rank() != rule[3]:
                continue
        counter('resilience.faults.injected').add(1)
        bits = action.partition(':')[2]
        return int(bits) if bits else 1
    return 0
