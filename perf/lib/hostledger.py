"""The host's half of window (a): the device's idle gaps by the scope
the host was under, the fetches, and the library's own ring of calls.

``perf/lib/scopes.py`` names the device's *busy* time by library layer;
its ``idle_gaps`` label a gap by the outermost scope of the host and
know one root.  Here each gap of the first device inside a call is
labelled by the host's **innermost** ``nbk.`` annotation over all host
lines when the gap began, the calls' roots (:data:`ROOTS`) left out:

- under ``nbk.sync.<what>`` (``diagnostics.fetch``): the round trip
  after a fetch, the host waiting and the device with nothing queued;
- under any other scope: host work with a name;
- under a root alone, or nothing: ``unscoped``, the guard on the
  library's claim that no host second is without a name.

The library keeps a ring of its calls with no instrument on
(``nbodykit_tpu.diagnostics.HOST_CALLS``: ``t0_ns`` on the wall clock,
``wall_s``, ``self_s`` by scope, ``sync_wait_s``).  The profiler's
lines keep the same clock less the session's start, which the trace
holds (plane ``Task Environment``, stat ``profile_start_time``), so
window (a)'s records are selected **by time**.  A program from before
the ring has none, and every reader of it returns ``None``.

:func:`load` is the only function that touches the profiler's file;
the rest works on plain tuples (``perf/tests/test_host_ledger.py``)."""

import array
import bisect
import functools
import json
import os
import statistics

from perf.lib import scopes, xplane

#: the root scope of each cell's call: no use as a gap's label
ROOTS = ('fftpower.run', 'convpower.run', 'paircount.run', 'serve.request')
SYNC = 'sync.'
UNSCOPED = 'unscoped'
#: the three stages of a jit cache miss, as the library's tracer names
#: their spans in window (b)
COMPILE_SPANS = ('compile.trace', 'compile.lower', 'compile.backend')
ENVIRONMENT_PLANE = 'Task Environment'
START_STAT = 'profile_start_time'


def union(starts, ends):
    """The union of the intervals ``[starts[i], ends[i])`` as a sorted
    list of disjoint ``(start, end)``: numpy, since a window holds
    millions of device op events."""
    import numpy as np
    s, e = np.asarray(starts, 'f8'), np.asarray(ends, 'f8')
    if not s.size:
        return []
    order = np.argsort(s, kind='stable')
    s, e = s[order], np.maximum.accumulate(e[order])
    first = np.concatenate([[True], s[1:] > e[:-1]])
    last = np.concatenate([first[1:], [True]])
    return list(zip(s[first].tolist(), e[last].tolist()))


def load(path):
    """Read a trace into plain tuples::

        {'host': {'<thread line>': [(name, start_ns, dur_ns), ...]},
         'busy': [(start_ns, end_ns), ...], 'start_ns': int or None}

    ``host`` keeps the call annotations and the ``nbk.`` ones; ``busy``
    is the union of the first device's ``XLA Ops`` events (what
    ``scopes.reduce`` calls busy); ``start_ns`` is the session's start
    on the wall clock: an event's ``start_ns + trace['start_ns']`` is
    ``time.time_ns()`` at that event."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {'host': {}, 'busy': [], 'start_ns': None}
    devices = {}
    for plane in data.planes:
        dev = xplane.DEVICE_PLANE.match(plane.name)
        if dev:
            devices[int(dev.group(1))] = plane
        elif plane.name == ENVIRONMENT_PLANE:
            out['start_ns'] = dict(plane.stats).get(START_STAT)
        elif plane.name == '/host:CPU':
            for line in plane.lines:
                keep = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in line.events
                        if ev.name == xplane.CALL
                        or ev.name.startswith(scopes.PREFIX)]
                if keep:
                    out['host'].setdefault(line.name, []).extend(keep)
    if devices:
        # (a window holds millions of op events: 8 bytes each, not a
        # Python float's 32)
        starts, ends = array.array('d'), array.array('d')
        for line in devices[min(devices)].lines:
            if line.name == xplane.OPS_LINE:
                for ev in line.events:
                    s = ev.start_ns
                    starts.append(s)
                    ends.append(s + ev.duration_ns)
        out['busy'] = union(starts, ends)
    return out


def clip(intervals, t0, t1):
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if a < t1 and b > t0]


def label(marks, t):
    """A gap's label from the annotations ``(name, start, end)``, of
    whatever host line, that cover ``t``, the roots left out:
    ``sync.<what>`` where one of them is a fetch's (it is the
    innermost: a fetch opens nothing), else the one that began last,
    else :data:`UNSCOPED`."""
    under = [(s, name) for name, s, e in marks
             if s <= t < e and name not in ROOTS]
    if not under:
        return UNSCOPED
    waits = [x for x in under if x[1].startswith(SYNC)]
    return max(waits or under)[1]


def spread(marks, a, b):
    """``{label: ns}`` of the gap ``[a, b)`` by what the host was
    under *during* it, not only when it began: the gap cut at every
    annotation's start and end inside it, each piece labelled at its
    middle.  (A gap that begins as the last small launch of one scope
    ends runs on through the next scope's host work: its label is the
    first, its seconds mostly the second's.)"""
    cuts = sorted({a, b} | {t for _, s, e in marks for t in (s, e)
                            if a < t < b})
    out = {}
    for lo, hi in zip(cuts, cuts[1:]):
        name = label(marks, 0.5 * (lo + hi))
        out[name] = out.get(name, 0.0) + hi - lo
    return out


def reduce(trace, ncalls=None):
    """The first device's idle seconds inside the calls of window (a),
    by the host's scope when each gap began, all a call::

        {'ncalls', 't0', 't1', 'gap_s', 'between_calls_s',
         'by_scope': {label: s}, 'unscoped_s', 'sync_s',
         'during': {label: s} (the same seconds by what the host was
         under while they passed, :func:`spread`: for people),
         'syncs': ``nbk.sync.*`` annotations begun inside calls,
         'sync_marks': how many the trace holds at all,
         'roots': [(name, start_ns, dur_ns), ...] inside the window,
         'calls': [(start_ns, dur_ns), ...]}

    or ``None`` without call annotations or device ops."""
    spans = xplane.call_spans(trace['host'])
    if not spans or not trace['busy']:
        return None
    t0, t1 = xplane.window_of(spans)
    n = float(ncalls or len(spans))
    begins = [s for _, s, _ in spans]
    every = [x for line in scopes.annotations(trace['host']).values()
             for x in line]
    # the annotations that overlap each call (the calls are one
    # caller's: disjoint and in order), so that a gap is held against
    # its own call's few dozen and not against the window's thousands
    during = [[] for _ in spans]
    for x in every:
        k = max(bisect.bisect_right(begins, x[1]) - 1, 0)
        while k < len(spans) and begins[k] < x[2]:
            if x[2] > begins[k] and x[1] < begins[k] + spans[k][2]:
                during[k].append(x)
            k += 1

    def call_of(t):
        k = bisect.bisect_right(begins, t) - 1
        return k if k >= 0 and t < begins[k] + spans[k][2] else None

    by, while_, between = {}, {}, 0.0
    for start, dur in xplane.idle_gaps(clip(trace['busy'], t0, t1),
                                       t0, t1):
        k = call_of(start)
        if k is None:
            between += dur / 1e9 / n
            continue
        name = label(during[k], start)
        by[name] = by.get(name, 0.0) + dur / 1e9 / n
        for name, ns in spread(during[k], start, start + dur).items():
            while_[name] = while_.get(name, 0.0) + ns / 1e9 / n
    syncs = [x for x in every if x[0].startswith(SYNC)]
    return {'ncalls': n, 't0': t0, 't1': t1,
            'gap_s': sum(by.values()), 'between_calls_s': between,
            'by_scope': by, 'during': while_,
            'unscoped_s': by.get(UNSCOPED, 0.0),
            'sync_s': sum(v for k, v in by.items()
                          if k.startswith(SYNC)),
            'syncs': sum(1 for _, s, _ in syncs
                         if call_of(s) is not None) / n,
            'sync_marks': len(syncs),
            'roots': sorted((name, s, e - s) for name, s, e in every
                            if name in ROOTS and t0 <= s < t1),
            'calls': [(s, d) for _, s, d in spans]}


# --------------------------------------------------------------------------
# the library's ring of calls

def ring():
    """The library's records of its calls, oldest first, or ``None``
    where the program keeps no such ring (a commit from before it)."""
    try:
        from nbodykit_tpu.diagnostics import export
    except ImportError:
        return None
    calls = getattr(export, 'HOST_CALLS', None)
    return None if calls is None else calls.snapshot()


def in_window(records, start_ns, t0, t1):
    """The records whose call began inside ``[t0, t1)`` of the trace's
    clock: selected by time, so that window (b)'s calls, the warm-up's
    and the oracle's are left out whatever their number."""
    if records is None or start_ns is None:
        return None
    return [r for r in records
            if t0 <= r['t0_ns'] - start_ns < t1]


def main_root(records):
    """The root whose calls hold most of the records' wall: the cell's
    own (a served request also leaves ``serve.submit`` and
    ``serve.deliver`` records, of their threads)."""
    wall = {}
    for r in records:
        wall[r['root']] = wall.get(r['root'], 0.0) + r['wall_s']
    return max(wall, key=wall.get) if wall else None


def calls_of(records):
    root = main_root(records or ())
    return [r for r in records or () if r['root'] == root]


def clock_offsets(red, records, start_ns):
    """``record.t0_ns - (annotation start + session start)`` in ns for
    the k-th root annotation of the window and the k-th record of that
    root: one clock if they are all a few microseconds (the record
    reads its clock right after the annotation is entered)."""
    mine = calls_of(records)
    if not mine:
        return []
    marks = [s for name, s, _ in red['roots'] if name == mine[0]['root']]
    # integers first: the wall clock in ns is past a float's 53 bits
    return [(r['t0_ns'] - start_ns) - s
            for s, r in zip(sorted(marks), mine)]


# --------------------------------------------------------------------------
# what the readers under perf/layers/ call

def of_run(ctx):
    """The reduction of this traced run's window (a) with the ring's
    records of the same window (``'records'``, ``None`` without a
    ring), or ``None``; written once to ``<outdir>/hostledger.json``
    for people."""
    outdir = ctx.get('outdir')
    if not outdir:
        return None
    try:
        path = xplane.find_xplane(os.path.join(outdir, 'profile'))
    except FileNotFoundError:
        return None
    red = _of_path(path, (ctx.get('xplane') or {}).get('ncalls'))
    said = os.path.join(outdir, 'hostledger.json')
    if red and not os.path.exists(said):
        with open(said, 'w') as f:
            json.dump(summary(red), f, indent=1, sort_keys=True)
    return red


@functools.lru_cache(maxsize=4)
def _of_path(path, ncalls):
    """Memoised on the path: five readers, one pass over the file."""
    trace = load(path)
    red = reduce(trace, ncalls)
    if red:
        red['start_ns'] = trace['start_ns']
        red['records'] = in_window(ring(), trace['start_ns'],
                                   red['t0'], red['t1'])
    return red


def summary(red):
    """What a person wants beside the metrics: the gaps by scope, the
    ring's median call by scope, whether the parts sum to the wall,
    the root against the harness's call, the two clocks' offset."""
    out = {k: red[k] for k in ('ncalls', 'gap_s', 'between_calls_s',
                               'by_scope', 'during', 'unscoped_s',
                               'sync_s', 'syncs', 'start_ns')}
    mine = calls_of(red.get('records'))
    calls = sorted(d for _, d in red['calls'])
    if mine and calls:
        names = sorted({k for r in mine for k in r['self_s']})
        out['ring'] = {
            'root': mine[0]['root'], 'records': len(mine),
            'wall_s': statistics.median(r['wall_s'] for r in mine),
            'sync_wait_s': statistics.median(
                r['sync_wait_s'] for r in mine),
            'retrace_s': statistics.median(r['retrace_s'] for r in mine),
            'syncs': statistics.median(r['syncs'] for r in mine),
            'self_s': {k: statistics.median(
                r['self_s'].get(k, 0.0) for r in mine) for k in names},
            'parts_over_wall': [f(sum(r['self_s'].values()) / r['wall_s']
                                  for r in mine) for f in (min, max)],
            'root_over_call': statistics.median(
                r['wall_s'] for r in mine) * 1e9
            / statistics.median(calls)}
        offs = clock_offsets(red, red['records'], red['start_ns'])
        if offs:
            out['clock_offset_ns'] = {'n': len(offs), 'min': min(offs),
                                      'max': max(offs)}
    return out


def gaps(ctx):
    """The reduction, or ``None`` where the call has no idle second to
    label or the trace no ``nbk.`` annotation to label it by."""
    red = of_run(ctx)
    if not red or not red['gap_s'] > 0:
        return None
    if set(red['by_scope']) == {UNSCOPED} and not red['roots']:
        return None
    return red


def sync_marks(ctx):
    """The reduction where the trace holds a fetch's annotation at all
    (a program from before ``fetch`` marks none: nothing to read, which
    is not a reading of zero)."""
    red = of_run(ctx)
    return red if red and red['sync_marks'] else None


def retrace_s(ctx):
    """Seconds a call of window (b) that jax spent tracing, lowering
    and compiling (or loading from the persistent cache): the three
    ``compile.*`` spans' durations over the window's calls.  0.0 where
    the window re-traced nothing; ``None`` without a window (b)."""
    spans, n = ctx.get('spans'), ctx.get('ncalls_b')
    if spans is None or not n:
        return None
    return sum(r['dur'] for r in spans
               if r['name'] in COMPILE_SPANS) / float(n)


def host_work_s(ctx):
    """Median over window (a)'s calls, from the library's own records,
    of the wall less what the host waited in fetches: the seconds the
    host worked a call.  ``None`` without a ring or its clock."""
    red = of_run(ctx)
    mine = calls_of(red and red.get('records'))
    if not mine:
        return None
    return statistics.median(r['wall_s'] - r['sync_wait_s'] for r in mine)
