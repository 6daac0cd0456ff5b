"""From a profiler trace (``.xplane.pb``) to busy time, op totals and
idle gaps.

:func:`load` is the only function that touches the profiler's file;
everything else works on plain ``(name, start_ns, dur_ns)`` tuples, so
the arithmetic is tested without a profiler
(``perf/tests/test_perf_harness.py``).

What a TPU v5e trace holds (read by hand, PR 24, see PERF.md): one
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one
event per program execution) and ``XLA Ops`` (one event per HLO op,
nested where an op has a body), beside step and trace-me lines; and
one plane ``/host:CPU`` with a line per host thread, on the same
clock.  The harness's own ``TraceAnnotation`` around each call
(:data:`CALL`) lands on the calling thread's line."""

import glob
import os
import re

#: name of the harness's TraceAnnotation around each timed call
CALL = 'perf.call'
DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
COLLECTIVE = re.compile(
    r'all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute',
    re.I)


def find_xplane(logdir):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` directory."""
    found = sorted(glob.glob(os.path.join(
        logdir, 'plugins', 'profile', '*', '*.xplane.pb')),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError('no .xplane.pb under %s' % logdir)
    return found[-1]


def load(path):
    """Read a trace into plain tuples::

        {'devices': {0: {'ops': [...], 'modules': [...]}, ...},
         'host': {'<thread line>': [...], ...},
         'lines': {'<plane>': {'<line>': n_events}}}

    every event a ``(name, start_ns, dur_ns)`` tuple."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {'devices': {}, 'host': {}, 'lines': {}}
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        seen = out['lines'].setdefault(plane.name, {})
        for line in plane.lines:
            keep = None
            if dev and line.name in (OPS_LINE, MODULES_LINE):
                slot = out['devices'].setdefault(
                    int(dev.group(1)), {'ops': [], 'modules': []})
                keep = slot['ops' if line.name == OPS_LINE else 'modules']
            elif plane.name == '/host:CPU':
                keep = out['host'].setdefault(line.name, [])
            n = 0
            for ev in line.events:
                n += 1
                if keep is not None:
                    keep.append((ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)))
            seen[line.name] = seen.get(line.name, 0) + n
    return out


def call_spans(host):
    """The harness's call annotations, in time order, from whichever
    host line carries them."""
    spans = [e for events in host.values() for e in events
             if e[0] == CALL]
    return sorted(spans, key=lambda e: e[1])


def window_of(spans):
    """From the first call's start to the last call's end."""
    return spans[0][1], max(s + d for _, s, d in spans)


def busy_intervals(events, t0, t1):
    """The union of the events' intervals, clipped to [t0, t1], as a
    sorted list of disjoint ``(start, end)``."""
    cut = sorted((max(s, t0), min(s + d, t1)) for _, s, d in events
                 if s < t1 and s + d > t0)
    merged = []
    for a, b in cut:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(intervals):
    return sum(b - a for a, b in intervals)


def idle_gaps(intervals, t0, t1):
    """The complement of ``intervals`` in [t0, t1]: ``(start, dur)``."""
    gaps, at = [], t0
    for a, b in intervals:
        if a > at:
            gaps.append((at, a - at))
        at = max(at, b)
    if t1 > at:
        gaps.append((at, t1 - at))
    return gaps


def self_times(events, t0, t1):
    """Seconds per op name inside [t0, t1], each op's time less the
    time of the ops nested in it (a ``while`` and its body are both
    events of one line), largest first."""
    totals = {}
    stack = []      # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + own

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        a, b = max(s, t0), min(s + d, t1)
        if b <= a:
            continue
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    close(float('inf'))
    return sorted(((n, v / 1e9) for n, v in totals.items()),
                  key=lambda kv: -kv[1])


def count_in(events, t0, t1):
    """Events that start inside [t0, t1)."""
    return sum(1 for _, s, _ in events if t0 <= s < t1)


def matching_ns(events, pattern, t0, t1):
    """Union, in ns, of the events whose name matches ``pattern``."""
    return busy_ns(busy_intervals(
        [e for e in events if pattern.search(e[0])], t0, t1))


def label_gap(start, spans, events):
    """What the host was doing when an idle gap began:
    ``between_calls`` outside every call annotation; else the
    innermost of ``events`` (the one that began last) covering the
    gap's start, as ``in_call.<name>``; else ``in_call.unattributed``."""
    if not any(s <= start < s + d for _, s, d in spans):
        return 'between_calls'
    covering = [(s, name) for name, s, d in events if s <= start < s + d]
    return 'in_call.%s' % max(covering)[1] if covering \
        else 'in_call.unattributed'


def top_gaps(gaps, spans, host, n=10, longest=60):
    """The ``longest`` gaps summed by label, as ``[label, seconds]``,
    the ``n`` largest first."""
    events = [e for line in host.values() for e in line
              if e[0] != CALL and e[2] > 0]
    by = {}
    for start, dur in sorted(gaps, key=lambda g: -g[1])[:longest]:
        label = label_gap(start, spans, events)
        by[label] = by.get(label, 0.0) + dur / 1e9
    return [[k, v] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace, ncalls=None):
    """Everything the per-layer readers and ``breakdown`` need from
    window (a) of one traced run::

        {'ncalls', 'window_s', 't0', 't1', 'window_from',
         'devices': {n: {'busy_s', 'idle_share', 'launches',
                         'collective_s'}},
         'device_ops': [[name, s], ...], 'idle_gaps': [[label, s], ...]}

    ``device_ops`` and ``idle_gaps`` are the first device's.  The
    window is the call annotations' (``ncalls`` of them, unless the
    harness says how many calls it made); where the trace holds none,
    or no device event falls inside them, it is the device events' own
    extent and ``window_from`` says so."""
    if not trace['devices']:
        return None
    spans = call_spans(trace['host'])
    every = [e for dev in trace['devices'].values()
             for e in dev['ops'] or dev['modules']]
    if not every:
        return None
    t0, t1 = window_of(spans) if spans else (None, None)
    source = 'call_annotations'
    if not spans or not busy_intervals(every, t0, t1):
        t0 = min(s for _, s, _ in every)
        t1 = max(s + d for _, s, d in every)
        source = 'device_events'
    out = {'ncalls': ncalls or len(spans), 'window_s': (t1 - t0) / 1e9,
           't0': t0, 't1': t1, 'window_from': source, 'devices': {}}
    for n, dev in sorted(trace['devices'].items()):
        busy = busy_intervals(dev['ops'] or dev['modules'], t0, t1)
        out['devices'][n] = {
            'busy_s': busy_ns(busy) / 1e9,
            'idle_share': 1.0 - busy_ns(busy) / (t1 - t0),
            'launches': count_in(dev['modules'], t0, t1),
            'collective_s': matching_ns(dev['ops'], COLLECTIVE,
                                        t0, t1) / 1e9}
        if 'device_ops' not in out:     # the first device's
            out['device_ops'] = [list(kv) for kv in
                                 self_times(dev['ops'], t0, t1)[:10]]
            out['idle_gaps'] = top_gaps(idle_gaps(busy, t0, t1), spans,
                                        trace['host'])
    return out
