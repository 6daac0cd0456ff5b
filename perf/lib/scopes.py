"""From a profiler trace to device time by library layer.

The library names its layers with ``nbodykit_tpu.diagnostics.scope``:
``nbk.<scope>`` as a ``TraceAnnotation`` on the host line where the
code runs eagerly, and as a ``named_scope`` in the HLO op names where
jax is staging.  This module reads both back from window (a) of a
traced run and gives each device op event a stack of scopes:

1. those of its own HLO ``op_name`` path (inside one program: the
   served request, the lab ``_bin``), the innermost last; where the
   compiler left the instruction none, those its program's HLO gives
   it (:func:`resolve`);
2. below them, those of the ``nbk.`` annotations under which the host
   launched the op's program (the eager lab path), each ``XLA
   Modules`` event led back to the host call by the ids the trace
   carries (:func:`launches`);
3. with neither, ``unscoped``.

The op's scope is the innermost of the stack; its layer that of the
innermost scope that belongs to one (:data:`LAYERS`).  Where a
module's launch cannot be found the ops that needed it are counted in
``unjoined_s``, and above 2% of the busy time every reader returns
``None``; nothing is guessed.  Where the program names no scope at
all (a commit from before ``scope``) the readers return ``None`` too,
and ``scopes.json`` says which it was (``unreadable``).

:func:`load` is the only function that touches the profiler's file;
the rest works on plain tuples (``perf/tests/test_scopes.py``).

What a TPU v5e trace holds beyond ``perf/lib/xplane.py``'s notes
(read by hand, PR 25; ``perf/tests/data/`` keeps two small ones):

- ``jax.profiler.ProfileData`` gives an event's own stats, not those
  of its metadata, and the ``op_name`` path is one of the latter
  (``tf_op`` of an ``XLA Ops`` event's metadata, beside ``program_id``,
  ``hlo_category``, ``flops``, ``bytes_accessed``).  Hence the small
  reader of the file's wire format here.
- The TPU compiler keeps no ``op_name`` on what it expands: the
  served program's scatter (the paint) runs as eight custom fusions
  and eight sorts with no ``tf_op``, 41% of the request's device
  time.  The plane ``/host:metadata`` holds each program's optimised
  HLO (stat ``Hlo Proto``, also with ``enable_hlo_proto`` off), where
  such a fusion's body still holds instructions named
  ``.../nbk.paint/...`` and the sorts feed only those fusions.
- An ``XLA Modules`` event carries ``run_id``; so does the host's
  ``DoEnqueueProgram``, on the calling thread or, when the launch was
  deferred (a fifth of them at 64^3; at 512^3 the device runs seconds
  behind), on a ``pjrt-tpu-tasks`` thread.  TraceMe links lead back
  from there: the enqueue sits inside ``tpu::System::Execute=>
  IssueSequencedEvent`` (consumes ``_ct`` 7, ``_c``), produced by
  ``tpu::System::Execute`` (``_pt`` 7, ``_p``) inside
  ``PJRT_LoadedExecutable_Execute`` (consumes 14), produced by
  ``PJRT_LoadedExecutable_Execute linkage`` on the line ``python3``:
  the thread that made the call, at the time of the call, with the
  ``nbk.`` annotations on the same line.  All 1568 modules of a
  512^3 lab window come back this way, in two hops.  (The runtime's
  own events of the calling thread are on a line of their own,
  ``main/<tid>``; two lines may share the name ``python3``.)
- With an id for every launch there is no join by order; the launch
  events it would have counted (``PjitFunction(<name>)``) come in
  nested pairs, and not every launch is a ``PjitFunction``.
"""

import bisect
import functools
import json
import os
import re
import struct

from perf.lib import xplane

PREFIX = 'nbk.'
SCOPE = re.compile(r'nbk\.([A-Za-z0-9_][A-Za-z0-9_.]*[A-Za-z0-9_])')
UNSCOPED = 'unscoped'

#: the stat of an ``XLA Ops`` event's metadata that carries the HLO
#: ``op_name`` path: ``jit(program)/vmap(nbk.serve.program)/nbk.paint/mul``
OP_NAME = 'tf_op'
#: the stat an ``XLA Modules`` event and the host's enqueue event share
RUN_ID = 'run_id'
#: an op's program, and the stat of ``/host:metadata`` that holds the
#: program's optimised HLO (there with ``enable_hlo_proto`` off too)
PROGRAM_ID = 'program_id'
HLO_PROTO = 'Hlo Proto'

#: layer -> the scopes it sums (a scope belongs to the first layer
#: whose name it equals or, ending in a dot, whose prefix it has).
#: ``fftpower.run``, ``mesh.compute`` and ``serve.program`` are
#: parents: what runs under them alone is ``unscoped``.
LAYERS = (('paint', ('paint',)),
          ('exchange', ('exchange',)),
          ('a2a', ('fft.a2a.',)),
          ('fft', ('fft.r2c', 'fft.c2r', 'fft.c2c')),
          ('transfer', ('fftpower.transfer',)),
          ('binning', ('fftpower.binning', 'fftpower.binning.')))
#: the root every lab call sits under: no use as an idle gap's label
ROOT_SCOPE = 'fftpower.run'


# --------------------------------------------------------------------------
# names

def scope_stack(text):
    """The ``nbk.`` scopes in an ``op_name`` path, outermost first.
    A transform wraps the component (``vmap(nbk.paint)``), so they are
    searched for, not split out."""
    return SCOPE.findall(text or '')


def layer_of(stack):
    """The layer of a scope stack (outermost first): that of the
    innermost scope that belongs to one; ``None`` under parents only."""
    for scope in reversed(stack):
        for layer, keys in LAYERS:
            if any(scope == k or (k.endswith('.') and scope.startswith(k))
                   for k in keys):
                return layer
    return None


def common(stacks):
    """The longest common prefix of the stacks that are not empty."""
    stacks = [s for s in stacks if s]
    out = stacks[0] if stacks else []
    for s in stacks[1:]:
        k = 0
        while k < min(len(out), len(s)) and out[k] == s[k]:
            k += 1
        out = out[:k]
    return out


def resolve(program):
    """``{instruction: scope stack}`` of one compiled program, given
    as its computations, each a list of ``(name, op_name, operand
    names, indexes of the computations it calls)`` with operands
    before their users (the HLO proto's order).

    The TPU compiler drops the ``op_name`` of what it expands (a
    scatter becomes sorts and custom fusions that carry none).  So an
    instruction without scopes of its own takes what the instructions
    inside it agree on (a fusion is what is in it); failing that, what
    its users agree on (a value belongs to what consumes it).  Where
    they disagree the common outer scopes remain, and under parents
    alone the time counts as unscoped: nothing is guessed."""
    inside = {}

    def within(k):
        """The stacks of every instruction of computation ``k`` and
        of the computations it calls."""
        if k not in inside:
            inside[k] = []      # a cycle cannot occur; be safe
            inside[k] = [st for name, op_name, _, called in program[k]
                         for st in [scope_stack(op_name)]
                         + [s for c in called for s in within(c)] if st]
        return inside[k]

    out = {}
    for comp in program:
        users = {}
        for name, op_name, operands, called in comp:
            out[name] = scope_stack(op_name) or common(
                [s for c in called for s in within(c)])
            for o in operands:
                users.setdefault(o, []).append(name)
        for name, _, _, _ in reversed(comp):
            if not out[name]:
                out[name] = common([out[u] for u in users.get(name, ())])
    return out


# --------------------------------------------------------------------------
# the file: a reader of the XSpace wire format, as far as needed.
# (tsl/profiler/protobuf/xplane.proto; the field numbers are below.)

def _varint(b, i):
    r = sh = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7f) << sh
        if c < 0x80:
            return r, i
        sh += 7


def _fields(b):
    """``(field number, value)`` of one message: an int for a varint,
    the bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError('wire type %d in an xplane file' % wire)
        yield key >> 3, v


def _text(v):
    return bytes(v).decode('utf-8', 'replace')


def _xstat(b, names):
    """XStat -> ``(name, value)``: metadata_id = 1, double = 2,
    uint64 = 3, int64 = 4, str = 5, bytes = 6, ref (a stat
    metadata's name used as a string) = 7."""
    name = value = None
    for f, v in _fields(b):
        if f == 1:
            name = names.get(v, v)
        elif f == 2:
            value = struct.unpack('<d', v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = v - (1 << 64) if v >> 63 else v
        elif f == 5:
            value = _text(v)
        elif f == 6:
            value = bytes(v)
        elif f == 7:
            value = names.get(v, v)
    return name, value


def _xplane(b):
    """XPlane: name = 2, lines = 3, event_metadata = 4 and
    stat_metadata = 5 (maps: key = 1, value = 2).  Returns the name,
    the lines' bytes, ``{event metadata id: (name, stats)}``."""
    name, lines, raw, names = '', [], [], {}
    for f, v in _fields(b):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            raw += [w for g, w in _fields(v) if g == 2]
        elif f == 5:
            for g, w in _fields(v):
                if g == 2:      # XStatMetadata: id = 1, name = 2
                    meta = dict(_fields(w))
                    names[meta.get(1, 0)] = _text(meta.get(2, b''))
    events = {}
    for w in raw:               # XEventMetadata: id 1, name 2, stats 5
        mid, label, stats = 0, '', {}
        for f, v in _fields(w):
            if f == 1:
                mid = v
            elif f == 2:
                label = _text(v)
            elif f == 5:
                k, val = _xstat(v, names)
                stats[k] = val
        events[mid] = (label, stats)
    return name, lines, events, names


def _xline(b, events, names):
    """XLine: name = 2, timestamp_ns = 3, events = 4; XEvent:
    metadata_id = 1, offset_ps = 2, duration_ps = 3, stats = 4.
    Yields ``(name, start_ns, dur_ns, stats)``, the event's own stats
    over its metadata's."""
    label, t0, raw = '', 0, []
    for f, v in _fields(b):
        if f == 2:
            label = _text(v)
        elif f == 3:
            t0 = v
        elif f == 4:
            raw.append(v)
    out = []
    for w in raw:
        mid = off = dur = 0
        own = []
        for f, v in _fields(w):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
            elif f == 4:
                own.append(v)
        name, stats = events.get(mid, ('', {}))
        if own:
            stats = dict(stats, **dict(_xstat(v, names) for v in own))
        out.append((name, t0 + off / 1e3, dur / 1e3, stats))
    return label, out


def _ints(v):
    """A repeated integer field's value: one varint, or packed."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _hlo(b):
    """HloProto (xla/service/hlo.proto) -> the plain program that
    :func:`resolve` takes.  hlo_module = 1; computations = 3; a
    computation's instructions = 2 and id = 5; an instruction's
    name = 1, metadata = 7 (op_name = 2), id = 35, operand_ids = 36,
    called_computation_ids = 38."""
    module = next((v for f, v in _fields(b) if f == 1), b'')
    comps = []
    for f, v in _fields(module):
        if f != 3:
            continue
        cid, raw = None, []
        for g, w in _fields(v):
            if g == 5:
                cid = w
            elif g == 2:
                ins = {'name': '', 'op_name': None, 'id': None,
                       'operands': [], 'called': []}
                for h, x in _fields(w):
                    if h == 1:
                        ins['name'] = _text(x)
                    elif h == 7:
                        ins['op_name'] = next(
                            (_text(y) for k, y in _fields(x) if k == 2),
                            None)
                    elif h == 35:
                        ins['id'] = x
                    elif h == 36:
                        ins['operands'] += _ints(x)
                    elif h == 38:
                        ins['called'] += _ints(x)
                raw.append(ins)
        comps.append((cid, raw))
    index = {cid: k for k, (cid, _) in enumerate(comps)}
    program = []
    for _, raw in comps:
        names = {ins['id']: ins['name'] for ins in raw}
        program.append([
            (ins['name'], ins['op_name'],
             [names[o] for o in ins['operands'] if o in names],
             [index[c] for c in ins['called'] if c in index])
            for ins in raw])
    return program


def _flow(stats, kind, ident):
    """A TraceMe producer / consumer link as one hashable."""
    if ident in stats:
        return '%s:%s' % (stats.get(kind), stats[ident])
    return None


def _op_name(name, stats, programs):
    """An op's ``op_name``: its own where that holds a scope, else
    what :func:`resolve` found for it in its program's HLO, spelled
    as a path of scopes."""
    own = stats.get(OP_NAME)
    if SCOPE.search(own or ''):
        return own
    stack = programs.get(str(stats.get(PROGRAM_ID)), {}).get(
        name.split(' = ')[0].lstrip('%'))
    return '/'.join(PREFIX + x for x in stack) if stack else own


def load(path):
    """Read a trace into plain tuples::

        {'device': n,
         'ops': [(name, start_ns, dur_ns, op_name), ...],
         'modules': [(name, start_ns, dur_ns, run_id), ...],
         'host': {'<thread line>#<k>': [
             (name, start_ns, dur_ns, run_id, produces, consumes),
             ...]}}

    of the first device.  Names are cut to 96 characters (an op's name
    is its whole HLO text).  Host lines keep what the reduction reads:
    the call annotations, the ``nbk.`` annotations and every event
    with a run id or a producer / consumer link."""
    with open(path, 'rb') as f:
        space = memoryview(f.read())
    out = {'device': None, 'ops': [], 'modules': [], 'host': {}}
    devices, programs = {}, {}
    for f, v in _fields(space):
        if f != 1:              # XSpace: planes = 1
            continue
        name, lines, events, names = _xplane(v)
        dev = xplane.DEVICE_PLANE.match(name)
        if dev:
            devices[int(dev.group(1))] = (lines, events, names)
        elif name == '/host:metadata':
            # one event metadata per program, its id the program's;
            # only a program that names a scope at all is decoded
            programs = {str(k): resolve(_hlo(st[HLO_PROTO]))
                        for k, (_, st) in events.items()
                        if PREFIX.encode() in st.get(HLO_PROTO, b'')}
        elif name == '/host:CPU':
            for k, raw in enumerate(lines):
                label, evs = _xline(raw, events, names)
                keep = [(n[:96], s, d, st.get(RUN_ID),
                         _flow(st, '_pt', '_p'), _flow(st, '_ct', '_c'))
                        for n, s, d, st in evs
                        if n == xplane.CALL or n.startswith(PREFIX)
                        or RUN_ID in st or '_p' in st or '_c' in st]
                if keep:
                    out['host']['%s#%d' % (label, k)] = keep
    if devices:
        out['device'] = min(devices)
        lines, events, names = devices[out['device']]
        for raw in lines:
            label, evs = _xline(raw, events, names)
            if label == xplane.OPS_LINE:
                out['ops'] = [(n[:96], s, d, _op_name(n, st, programs))
                              for n, s, d, st in evs]
            elif label == xplane.MODULES_LINE:
                out['modules'] = [(n[:96], s, d, st.get(RUN_ID))
                                  for n, s, d, st in evs]
    return out


# --------------------------------------------------------------------------
# rule 2: from a program's execution to the host code that launched it

def annotations(host):
    """``{line: [(scope, start, end), ...]}`` of the ``nbk.``
    annotations, in start order."""
    out = {}
    for line, events in host.items():
        mine = sorted((e[1], e[1] + e[2], e[0][len(PREFIX):])
                      for e in events if e[0].startswith(PREFIX))
        if mine:
            out[line] = [(name, s, e) for s, e, name in mine]
    return out


def stack_at(marks, t):
    """The scopes of one line's annotations covering ``t``, outermost
    first (annotations of one thread nest)."""
    return [name for name, s, e in marks if s <= t < e]


def enclosing(events):
    """Per event of one line the index of the event it is nested in,
    or ``None``: events of one thread nest."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    parent, stack = {}, []
    for i in order:
        start = events[i][1]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] \
                <= start:
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return parent


def launches(modules, host, hops=8):
    """Per module ``(line, start_ns)`` of the host event that began its
    launch, or ``None``.

    The module and the host's enqueue event share a run id; from the
    enqueue, each event that consumes a link (itself, or the nearest
    event it is nested in) leads to the event that produced it, on
    whichever thread, until nothing more is consumed: that event is on
    the thread that made the call, inside the call."""
    parents, produced, enqueued = {}, {}, {}
    for line, events in host.items():
        for i, e in enumerate(events):
            if e[4] is not None:
                produced[e[4]] = (line, i)
            if e[3] is not None and e[4] is not None:
                enqueued[e[3]] = (line, i)      # not the completion
    out = []
    for m in modules:
        at = enqueued.get(m[3])
        for _ in range(hops if at else 0):
            line, i = at
            if line not in parents:
                parents[line] = enclosing(host[line])
            while i is not None and host[line][i][5] is None:
                i = parents[line][i]
            if i is None or host[line][i][5] not in produced:
                break
            at = produced[host[line][i][5]]
        out.append(at and (at[0], host[at[0]][at[1]][1]))
    return out


def module_stacks(modules, host):
    """Per module the scope stack its launch began under (``None``
    where the chain of ids does not lead back to a host event), and
    how many modules that leaves unjoined."""
    marks = annotations(host)
    stacks = [at and stack_at(marks.get(at[0], ()), at[1])
              for at in launches(modules, host)]
    return stacks, sum(1 for s in stacks if s is None)


# --------------------------------------------------------------------------
# the reduction

def reduce(trace, ncalls=None):
    """Device seconds by scope and by layer over the calls of window
    (a), first device::

        {'ncalls', 'busy_s', 'ops', 'named_ops', 'annotations',
         'modules', 'unjoined_modules', 'unjoined_s',
         'scopes': {scope: {'device_s', 'launches', 'host_s'}},
         'layers': {layer: s a call, 'unscoped': ...},
         'idle_gaps': {label: s a call}}

    every ``*_s`` and ``launches`` a call.  ``unjoined_s`` is the time
    of the ops that needed rule 2 in a module whose launch was not
    found (they count as ``unscoped``)."""
    host = trace['host']
    spans = xplane.call_spans(
        {k: [e[:3] for e in v] for k, v in host.items()})
    if not spans or not trace['ops']:
        return None
    t0, t1 = xplane.window_of(spans)
    n = float(ncalls or len(spans))
    modules = sorted((m for m in trace['modules'] if t0 <= m[1] < t1),
                     key=lambda m: m[1])
    stacks, lost = module_stacks(modules, host)
    starts = [m[1] for m in modules]

    def launch_stack(t):
        """The host stack of the program running at ``t``."""
        i = bisect.bisect_right(starts, t) - 1
        if i < 0 or t >= modules[i][1] + modules[i][2]:
            return None
        return stacks[i]

    labelled, named, unjoined = [], 0, []
    for name, s, d, op_name in trace['ops']:
        own = scope_stack(op_name)
        named += bool(own)
        below = launch_stack(s)
        stack = (below or []) + own
        if not own and below is None and t0 <= s < t1:
            unjoined.append((name, s, d))
        labelled.append(((stack[-1] if stack else UNSCOPED,
                          layer_of(stack) or UNSCOPED), s, d))
    blank = {'device_s': 0.0, 'launches': 0.0, 'host_s': 0.0}
    scopes, by_layer = {}, {}
    for (scope, layer), sec in xplane.self_times(labelled, t0, t1):
        scopes.setdefault(scope, dict(blank))['device_s'] += sec / n
        by_layer[layer] = by_layer.get(layer, 0.0) + sec / n
    for st in stacks:
        scopes.setdefault(st[-1] if st else UNSCOPED,
                          dict(blank))['launches'] += 1 / n
    marks = annotations(host)
    for line in marks.values():
        for scope, s, e in line:
            if s < t1 and e > t0:
                scopes.setdefault(scope, dict(blank))['host_s'] += (
                    min(e, t1) - max(s, t0)) / 1e9 / n

    busy = xplane.busy_intervals([e[:3] for e in trace['ops']], t0, t1)
    gaps = {}
    for start, dur in xplane.idle_gaps(busy, t0, t1):
        label = 'between_calls'
        if any(s <= start < s + d for _, s, d in spans):
            under = [x for line in marks.values()
                     for x in stack_at(line, start) if x != ROOT_SCOPE]
            label = under[0] if under else 'in_call.no_scope'
        gaps[label] = gaps.get(label, 0.0) + dur / 1e9 / n
    return {'ncalls': n, 'busy_s': xplane.busy_ns(busy) / 1e9 / n,
            'ops': len(trace['ops']), 'named_ops': named,
            'annotations': sum(len(v) for v in marks.values()),
            'modules': len(modules), 'unjoined_modules': lost,
            # they nest among themselves only: their union
            'unjoined_s': xplane.busy_ns(xplane.busy_intervals(
                unjoined, t0, t1)) / 1e9 / n,
            'scopes': scopes, 'layers': by_layer, 'idle_gaps': gaps}


# --------------------------------------------------------------------------
# what the readers under perf/layers/ call

#: above this share of the busy time left unjoined, no layer's seconds
#: can be trusted (the acceptance holds the layers' sum to 2% of busy)
UNJOINED_MAX = 0.02
#: above this unscoped share ``fft_roofline`` is withheld: an
#: under-attributed FFT would read too fast
UNSCOPED_MAX = 10.0


def of_run(ctx):
    """The reduction of this traced run's window (a), or ``None``;
    written once to ``<outdir>/scopes.json`` for people, with why the
    readers say nothing (``unreadable``) where they do."""
    outdir = ctx.get('outdir')
    if not outdir:
        return None
    try:
        path = xplane.find_xplane(os.path.join(outdir, 'profile'))
    except FileNotFoundError:
        return None
    red = _of_path(path, (ctx.get('xplane') or {}).get('ncalls'))
    said = os.path.join(outdir, 'scopes.json')
    if not os.path.exists(said):
        with open(said, 'w') as f:
            json.dump(dict(red or {}, unreadable=unreadable(red)), f,
                      indent=1, sort_keys=True)
    return red


@functools.lru_cache(maxsize=4)
def _of_path(path, ncalls):
    """Memoised on the path: six readers, one pass over the file."""
    return reduce(load(path), ncalls)


def unreadable(red):
    """Why the layers' seconds may not be reported, or ``None`` where
    they may: the program names its layers at all (a commit from
    before ``scope`` does not), and little enough needed a join that
    could not be made."""
    if not red or not red['busy_s'] > 0:
        return 'no perf.call annotation or no device op inside the calls'
    if red['named_ops'] + red['annotations'] == 0:
        return 'no nbk. scope in any op_name or on any host line'
    if red['unjoined_s'] > UNJOINED_MAX * red['busy_s']:
        return ('%d of %d programs could not be led back to the host call '
                'that launched them: %.3g of %.3g busy seconds a call'
                % (red['unjoined_modules'], red['modules'],
                   red['unjoined_s'], red['busy_s']))
    return None


def layer_s(ctx, layer):
    """Device seconds a call under ``layer``; 0.0 where the layer has
    no op in a trace whose scopes were readable; ``None`` where they
    were not (a parent commit without scopes, launches not found)."""
    red = of_run(ctx)
    if unreadable(red):
        return None
    return red['layers'].get(layer, 0.0)


def unscoped_share(ctx):
    """Unscoped device self time over the busy time, in %."""
    red = of_run(ctx)
    if unreadable(red):
        return None
    return 100.0 * red['layers'].get(UNSCOPED, 0.0) / red['busy_s']
