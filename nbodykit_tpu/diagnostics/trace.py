"""Low-overhead span tracer with crash-safe JSONL output.

Round 5's verdict (ISSUE #1): the north-star TPU measurement died
mid-timing and left *nothing* on disk, and nobody could say where the
paint kernel's time went.  This tracer is built around those two
failure modes:

- **crash-safe**: every completed span is appended to the trace file
  and flushed (``fsync``) the moment it closes, and a begin event is
  flushed at span entry — a SIGKILL or a hung device loses at
  most the in-flight spans' durations, never their existence.  Summary
  artifacts (reports, chrome-trace exports) are written atomically
  (tmp + rename) so a death mid-write cannot corrupt them.
- **zero cost when disabled**: :func:`span` returns a shared no-op
  context manager — no span objects are allocated, no file is ever
  opened or touched.  The disabled fast path is one option read and a
  ``None`` check.

Enable with ``nbodykit_tpu.set_options(diagnostics=PATH)`` (or the
``NBKIT_DIAGNOSTICS`` environment variable, read at import so detached
workers inherit it).  ``PATH`` names a directory; each process appends
to ``trace-<pid>.jsonl`` inside it (a value ending in ``.jsonl`` is
used verbatim instead).  See docs/OBSERVABILITY.md for the record
format and how to read a trace from a dead run.

Spans nest per-thread; exceptions are recorded (``ok: false`` plus the
exception repr) and re-raised.  Durations use the monotonic
``time.perf_counter``; the wall-clock ``ts`` is kept for aligning
traces across processes.

A background **heartbeat** thread additionally appends a tiny ``hb``
record every ``NBKIT_DIAGNOSTICS_HEARTBEAT`` seconds (default 5; 0
disables).  Spans only prove a process was alive when it *finished*
something — a worker wedged inside one long collective writes nothing.
The heartbeat gives the fleet analyzer (analyze.py) a per-process
liveness signal, so a SIGKILLed or hung worker is distinguishable
post-mortem from one that merely had no spans to emit.
"""

import atexit
import contextlib
import contextvars
import hashlib
import json
import os
import sys
import threading
import time

from .metrics import REGISTRY

_lock = threading.Lock()
_tracer = None

#: The ambient request context.  A contextvar — NOT inherited by
#: long-lived worker threads (they were created before any request
#: existed), so the serve stack carries the context on its tickets and
#: re-activates it with :func:`trace_scope` at every thread hop it
#: owns.  That explicitness is the point: a hop the code forgot shows
#: up as an orphan span in ``analyze.py``'s request report.
_CTX = contextvars.ContextVar('nbkit_request_ctx', default=None)

#: Span names at or above these prefixes are *request-level*: they are
#: always recorded, even for requests outside the exemplar sample.
#: Everything else (kernel-depth spans: paint, fft.*, compile.*) is
#: dropped for unsampled requests — cheap envelopes for the many, full
#: waterfalls for the hash-chosen few.
_REQUEST_LEVEL = ('serve.', 'region.', 'resilience.')


class RequestContext(object):
    """W3C-style causal identity for one request: a ``trace_id``
    shared by every span the request causes (across threads and
    processes), the root span's id (``span_id``) that cross-thread
    spans re-parent to via the ``rpar`` field, and the exemplar
    ``sampled`` bit."""

    __slots__ = ('trace_id', 'span_id', 'sampled')

    def __init__(self, trace_id, span_id=0, sampled=True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled

    def __repr__(self):
        return 'RequestContext(%r, span_id=%r, sampled=%r)' % (
            self.trace_id, self.span_id, self.sampled)


def exemplar_fraction():
    """Fraction of requests recorded at full kernel depth
    (``NBKIT_TRACE_EXEMPLAR``, default 1.0, clamped to [0, 1]).
    Requests outside the sample still emit their request-level spans
    (:data:`_REQUEST_LEVEL`), so every waterfall is complete — only
    the kernel interior is elided."""
    try:
        f = float(os.environ.get('NBKIT_TRACE_EXEMPLAR', '1') or 1.0)
    except ValueError:
        return 1.0
    return min(1.0, max(0.0, f))


def new_request_context(request_id, fraction=None):
    """Mint the :class:`RequestContext` for ``request_id``.

    The trace id is a hash of the request id — deterministic, so a
    replayed request lands on the same trace id (and the same exemplar
    decision) in every process that handles it, with zero
    coordination.  ``span_id`` starts 0; the owner assigns it from the
    root span after entering it."""
    trace_id = hashlib.blake2b(str(request_id).encode('utf-8'),
                               digest_size=8).hexdigest()
    if fraction is None:
        fraction = exemplar_fraction()
    sampled = (int(trace_id[:8], 16) % 10000) < int(fraction * 10000)
    return RequestContext(trace_id, 0, sampled)


def trace_context():
    """The ambient :class:`RequestContext`, or None."""
    return _CTX.get()


@contextlib.contextmanager
def trace_scope(ctx):
    """Activate ``ctx`` as the ambient request context for the
    duration of the block.  ``ctx=None`` is a no-op (so call sites at
    thread hops can wrap unconditionally)."""
    if ctx is None:
        yield None
        return
    token = _CTX.set(ctx)
    try:
        yield ctx
    finally:
        _CTX.reset(token)


class _NullSpan(object):
    """Shared, stateless no-op context manager (the disabled path).

    Reentrant and reusable by construction: it holds no state, so one
    module-level instance serves every disabled ``span()`` call without
    allocation.
    """

    __slots__ = ()

    #: uniform with :class:`_Span` so ``span(...).span_id`` is safe on
    #: the disabled path (0 = "no span": never a real id)
    span_id = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


NULL_SPAN = _NullSpan()


def _option():
    """The current ``diagnostics`` option value (lazy import: this
    module must be importable while the package __init__ is still
    executing)."""
    try:
        from .. import _global_options
    except ImportError:      # pragma: no cover - partial interpreter teardown
        return None
    try:
        return _global_options['diagnostics']
    except KeyError:
        return None


def current_tracer():
    """The active :class:`Tracer`, (re)configured from the
    ``diagnostics`` option, or ``None`` when disabled.

    This is THE fast path: when disabled it costs one (thread-aware)
    dict read and a falsy check.  Changing the option mid-run swaps the
    tracer on the next call; restoring it to ``None`` (e.g. a
    ``set_options`` context exiting) closes the file.
    """
    global _tracer
    opt = _option()
    t = _tracer
    if not opt:
        if t is not None:
            with _lock:
                if _tracer is t:
                    _tracer = None
                    t.close()
        return None
    if t is not None and t.root == opt:
        return t
    with _lock:
        t = _tracer
        if t is None or t.root != opt:
            if t is not None:
                t.close()
            _tracer = t = Tracer(opt)
    return t


def trace_state_clean():
    """True when no jax trace (jit/scan/shard_map) is being staged —
    host-side span timing is only meaningful eagerly.  True as well
    when jax is not importable (diagnostics never requires jax)."""
    jc = sys.modules.get('jax.core')
    if jc is None:
        return True
    return jc.trace_ctx.is_top_level()


#: prefix of every library layer on the profiler's host line and in the
#: HLO op names (``nbk.paint``, ``nbk.fft.r2c``): what a reduction of
#: a device trace looks for
SCOPE_PREFIX = 'nbk.'


class _Ledger(threading.local):
    """One thread's half of the host ledger: the scopes it has open,
    innermost last, and what its call (the outermost of them) has
    gathered so far."""

    def __init__(self):
        self.stack = []
        self.call = None


_LEDGER = _Ledger()
_ring = None

#: prefix of the scopes whose self time the host spent waiting for the
#: device (:func:`nbodykit_tpu.diagnostics.fetch`); every other
#: scope's self time is time the host worked
SYNC_PREFIX = 'sync.'


def open_scope():
    """Name of the innermost scope the calling thread has open on the
    host ledger, or ``None``."""
    st = _LEDGER.stack
    return st[-1]._name if st else None


def note_retrace(seconds):
    """Charge ``seconds`` of tracing, lowering or compiling to the
    innermost open scope of the calling thread (``metrics.py``'s
    ``jax.monitoring`` hook, which jax calls on the thread that
    compiles).  The seconds stay part of that scope's self time: this
    is an attribution, not a part of the partition.  Returns the
    scope's name, or ``None`` where none is open."""
    name = open_scope()
    if name is not None:
        REGISTRY.counter('host.%s.retrace_s' % name).add(seconds)
        _LEDGER.call['retrace_s'] += seconds
    return name


def _call_done(call, wall_ns):
    """The outermost scope of a thread closed: its call's parts go to
    the registry and one record into the ring of calls."""
    global _ring
    if _ring is None:           # export.py imports this module
        from .export import HOST_CALLS as _ring
    self_s, adds, syncs, waited = {}, [], 0, 0.0
    for name, (ns, n) in call['self'].items():
        self_s[name] = sec = ns / 1e9
        adds.append(('host.%s.self_s' % name, sec))
        adds.append(('host.%s.n' % name, n))
        if name.startswith(SYNC_PREFIX):
            syncs += n
            waited += sec
    if syncs:
        adds.append(('host.syncs', syncs))
    REGISTRY.add(adds)
    _ring.record({
        'root': call['root'], 't0_ns': call['t0_ns'],
        'wall_s': wall_ns / 1e9, 'self_s': self_s, 'syncs': syncs,
        'sync_wait_s': waited, 'retrace_s': call['retrace_s']})


class _Scope(object):
    """One library layer on all three clocks (see
    :func:`nbodykit_tpu.diagnostics.scope`): ``mark`` is the
    profiler's ``TraceAnnotation``, ``staged`` the ``jax.named_scope``
    entered inside it while jax is staging (else ``None``), ``span``
    the JSONL :class:`_Span` or :data:`NULL_SPAN`.

    While jax is not staging it also keeps the **host ledger**,
    whatever the ``diagnostics`` option says: two ``perf_counter_ns``
    readings and a per-thread stack, no sync and no file.  On exit
    the scope's *self* time (its wall less its children's) is added
    to its call's parts; when the outermost scope of the thread
    closes, the parts (which sum to its wall by construction) go to
    the registry (``host.<scope>.self_s`` / ``.n``) and one record to
    ``export.HOST_CALLS``."""

    __slots__ = ('_name', '_mark', '_span', '_staged', '_t0', '_kids')

    def __init__(self, name, mark, span, staged=None):
        self._name = name
        self._mark = mark
        self._span = span
        self._staged = staged
        self._t0 = None         # on the ledger while not None

    @property
    def span_id(self):
        """The JSONL span's id; 0 while none is recording (the option
        off, jax staging, a request outside the exemplar sample)."""
        return self._span.span_id

    def set(self, **attrs):
        self._span.set(**attrs)
        return self

    def done(self, result):
        """``result``, waited for first while the JSONL tracer records
        this scope — dispatch is asynchronous, so only then is the
        span's wall the layer's work and not its enqueue.  Never syncs
        otherwise."""
        if self._span is not NULL_SPAN:
            sys.modules['jax'].block_until_ready(result)
        return result

    def __enter__(self):
        self._mark.__enter__()
        if self._staged is not None:
            self._staged.__enter__()
        else:
            led = _LEDGER
            if not led.stack:
                # the call's root: its start on the wall clock, which
                # is the profiler's (PERF.md section 3), read as near
                # the annotation's own start as Python allows
                led.call = {'root': self._name, 't0_ns': time.time_ns(),
                            'self': {}, 'retrace_s': 0.0}
            led.stack.append(self)
            self._kids = 0
            self._t0 = time.perf_counter_ns()
        self._span.__enter__()
        return self

    def _close(self):
        """Off the ledger.  A scope that exits while others opened
        after it are still on the stack (a generator collected late)
        closes them first, as of now, so that the parts still sum to
        the root's wall; their own late exits then find ``_t0`` unset
        and do nothing."""
        now = time.perf_counter_ns()
        led = _LEDGER
        st = led.stack
        if self not in st:      # entered on another thread
            self._t0 = None
            return
        while True:
            top = st.pop()
            wall = now - top._t0
            top._t0 = None
            parts = led.call['self']
            mine = parts.get(top._name)
            if mine is None:
                parts[top._name] = [wall - top._kids, 1]
            else:
                mine[0] += wall - top._kids
                mine[1] += 1
            if st:
                st[-1]._kids += wall
            else:
                call, led.call = led.call, None
                _call_done(call, wall)
            if top is self:
                return

    def __exit__(self, etype, evalue, tb):
        try:
            self._span.__exit__(etype, evalue, tb)
        finally:
            try:
                if self._staged is not None:
                    self._staged.__exit__(etype, evalue, tb)
                elif self._t0 is not None:
                    self._close()
            finally:
                self._mark.__exit__(etype, evalue, tb)
        return False


def fleet_rank_hint():
    """This process's fleet rank from the environment
    (``NBKIT_FLEET_RANK`` / ``JAX_PROCESS_ID``), or None.  Env-only on
    purpose: the tracer (and its heartbeat thread) must never trigger
    jax backend initialization.  Stamped into ``meta``/``hb`` records
    so the live failure detector (resilience/fleet.py) can map a pid
    to the rank it must re-form without."""
    for var in ('NBKIT_FLEET_RANK', 'JAX_PROCESS_ID'):
        v = os.environ.get(var)
        if v:
            try:
                return int(v)
            except ValueError:
                pass
    return None


class _Span(object):
    """One timed, nested region.  Attributes set via constructor or
    :meth:`set` land in the trace record's ``attrs``."""

    __slots__ = ('_tr', 'name', 'attrs', '_id', '_par', '_depth',
                 '_t0_ns', '_ts', '_tm', '_ctx')

    def __init__(self, tr, name, attrs):
        self._tr = tr
        self.name = name
        self.attrs = dict(attrs) if attrs else None
        self._id = 0

    @property
    def span_id(self):
        """The span's id once entered (0 before) — what a
        :class:`RequestContext` records as its root."""
        return self._id

    def set(self, **attrs):
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(attrs)
        return self

    def _stamp(self, rec):
        ctx = self._ctx
        if ctx is not None:
            rec['trace'] = ctx.trace_id
            # cross-thread re-parenting: a span opened on an empty
            # per-thread stack hangs off the request's root span, not
            # off nothing — 'rpar' is the remote parent the request
            # report resolves across thread/process boundaries
            if self._par == 0 and ctx.span_id \
                    and ctx.span_id != self._id:
                rec['rpar'] = ctx.span_id

    def __enter__(self):
        tr = self._tr
        st = tr._stack()
        self._id = tr._new_id()
        self._par = st[-1]._id if st else 0
        self._depth = len(st)
        self._ctx = _CTX.get()
        st.append(self)
        # one reading of the wall clock, kept whole (``t0_ns``: the
        # profiler's host line and the ring of calls keep the same
        # clock) and as the rounded seconds older readers know
        self._t0_ns = time.time_ns()
        self._ts = self._t0_ns / 1e9
        self._tm = time.perf_counter()
        # begin event: flushed (not fsynced — an OS-level flush already
        # survives a SIGKILL of this process) so a post-mortem shows
        # what was IN FLIGHT when the run died, not just what finished
        rec = {'t': 'b', 'id': self._id, 'par': self._par,
               'name': self.name, 'ts': round(self._ts, 6),
               'depth': self._depth, 'pid': tr.pid}
        self._stamp(rec)
        tr._emit(rec, sync=False)
        return self

    def __exit__(self, etype, evalue, tb):
        dur = time.perf_counter() - self._tm
        tr = self._tr
        st = tr._stack()
        if st and st[-1] is self:
            st.pop()
        else:                   # mis-nested exit (generator gc, ...)
            try:
                st.remove(self)
            except ValueError:
                pass
        rec = {'t': 'span', 'id': self._id, 'par': self._par,
               'name': self.name, 'ts': round(self._ts, 6),
               't0_ns': self._t0_ns,
               'dur': round(dur, 6), 'depth': self._depth,
               'pid': tr.pid, 'ok': etype is None}
        self._stamp(rec)
        if etype is not None:
            rec['exc'] = '%s: %s' % (getattr(etype, '__name__', etype),
                                     evalue)
        if self.attrs:
            rec['attrs'] = self.attrs
        tr._emit(rec)
        return False


class Tracer(object):
    """Appends span records to one JSONL file, fsync per completed
    span.  Create via the ``diagnostics`` option / :func:`current_tracer`,
    not directly."""

    def __init__(self, root):
        self.root = root
        roots = str(root)
        if roots.endswith('.jsonl'):
            self.dir = os.path.dirname(roots) or '.'
            os.makedirs(self.dir, exist_ok=True)
            self.path = roots
        else:
            os.makedirs(roots, exist_ok=True)
            self.dir = roots
            self.path = os.path.join(roots,
                                     'trace-%d.jsonl' % os.getpid())
        self.pid = os.getpid()
        self._f = open(self.path, 'a')
        self._wlock = threading.Lock()
        self._tls = threading.local()
        self._next_id = 0
        # NBKIT_DIAGNOSTICS_SYNC=0 drops the per-span fsync (flush
        # only — still survives a SIGKILL of this process, loses only
        # on kernel/power death).  The bench overhead gate runs here.
        self.sync = os.environ.get('NBKIT_DIAGNOSTICS_SYNC',
                                   '1') != '0'
        try:
            self.heartbeat_s = float(os.environ.get(
                'NBKIT_DIAGNOSTICS_HEARTBEAT', '5') or 0)
        except ValueError:
            self.heartbeat_s = 5.0
        meta = {'t': 'meta', 'version': 1, 'pid': self.pid,
                'ts': round(time.time(), 6),
                'argv': [str(a) for a in getattr(sys, 'argv', [])],
                'heartbeat_s': self.heartbeat_s}
        rank = fleet_rank_hint()
        if rank is not None:
            meta['rank'] = rank
        self._emit(meta)
        self._hb_stop = threading.Event()
        if self.heartbeat_s > 0:
            t = threading.Thread(target=self._hb_loop, daemon=True,
                                 name='nbkit-trace-heartbeat')
            t.start()
        atexit.register(self._at_exit)

    # -- internals --------------------------------------------------------

    def _stack(self):
        st = getattr(self._tls, 'stack', None)
        if st is None:
            st = []
            self._tls.stack = st
        return st

    def _new_id(self):
        with self._wlock:
            self._next_id += 1
            return self._next_id

    def _emit(self, rec, sync=True):
        line = json.dumps(rec, separators=(',', ':'), default=str) + '\n'
        with self._wlock:
            f = self._f
            if f.closed:
                return
            f.write(line)
            f.flush()
            if sync and self.sync:
                try:
                    os.fsync(f.fileno())
                except OSError:     # pragma: no cover - exotic fs
                    pass

    def _hb_loop(self):
        # flush, no fsync: an OS-level write survives a SIGKILL of this
        # process, and the heartbeat must stay near-free.  The wait
        # doubles as the stop signal so close() never blocks on us.
        while not self._hb_stop.wait(self.heartbeat_s):
            if self._f.closed:
                return
            rec = {'t': 'hb', 'pid': self.pid,
                   'ts': round(time.time(), 6),
                   'iv': self.heartbeat_s}
            # re-read per beat: launchers/workers may export the rank
            # after the tracer came up
            rank = fleet_rank_hint()
            if rank is not None:
                rec['rank'] = rank
            self._emit(rec, sync=False)

    def _at_exit(self):
        # end-of-run summary on clean interpreter exit (a crash relies
        # on the per-span fsyncs instead); atomic, never raises.  A
        # tracer already closed (option restored) reported elsewhere.
        if self._f.closed:
            return
        try:
            from .report import write_report
            write_report(tracer=self)
        except Exception:
            pass
        self.close()

    # -- API --------------------------------------------------------------

    def span(self, name, attrs=None):
        # exemplar sampling: for requests outside the sample, only
        # request-level spans are recorded — the kernel interior
        # (paint, fft.*, binning, ...) costs nothing
        ctx = _CTX.get()
        if ctx is not None and not ctx.sampled \
                and not name.startswith(_REQUEST_LEVEL):
            return NULL_SPAN
        return _Span(self, name, attrs)

    def event(self, name, attrs=None, ok=True, ctx=None):
        """Record an instantaneous event as a zero-duration span at
        *now* — the form the resilience supervisor uses for retry /
        degrade / resume marks, so they land in the merged timeline
        (and straggler/critical-path tables) like any other span."""
        self.emit_span(name, time.time(), 0.0, attrs=attrs, ok=ok,
                       ctx=ctx)

    def emit_span(self, name, ts, dur, attrs=None, ok=True, ctx=None):
        """Record a completed span observed out-of-band — e.g. a compile
        reported after the fact by ``jax.monitoring`` (metrics.py), where
        there was no way to enter a context manager before the work ran.
        ``ts`` is the wall-clock start, ``dur`` the duration in seconds;
        the record is a normal top-level span to every reader.  The
        ambient request context (or an explicit ``ctx``) stamps the
        record into its request's trace."""
        rec = {'t': 'span', 'id': self._new_id(), 'par': 0,
               'name': name, 'ts': round(float(ts), 6),
               't0_ns': int(float(ts) * 1e9),
               'dur': round(float(dur), 6), 'depth': 0,
               'pid': self.pid, 'ok': bool(ok)}
        if ctx is None:
            ctx = _CTX.get()
        if ctx is not None:
            rec['trace'] = ctx.trace_id
            if ctx.span_id:
                rec['rpar'] = ctx.span_id
        if attrs:
            rec['attrs'] = dict(attrs)
        self._emit(rec)

    def close(self):
        self._hb_stop.set()
        with self._wlock:
            if not self._f.closed:
                try:
                    self._f.flush()
                except (OSError, ValueError):  # pragma: no cover
                    pass
                self._f.close()


# ---------------------------------------------------------------------------
# replay + export

def trace_files(path):
    """The trace file(s) named by ``path``: a .jsonl file itself, or
    every ``*.jsonl`` in a directory (one per process)."""
    if os.path.isdir(path):
        return sorted(os.path.join(path, f) for f in os.listdir(path)
                      if f.endswith('.jsonl'))
    return [path]


def read_trace(path):
    """Replay a JSONL trace (file or directory of per-process files).

    Tolerant of a killed writer: lines that fail to parse (the torn
    final line of a SIGKILLed run) are counted, not fatal.

    Returns ``(records, n_bad)``.
    """
    records, bad = [], 0
    for p in trace_files(path):
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(json.loads(line))
                except ValueError:
                    bad += 1
    return records, bad


def atomic_write(path, text):
    """Write ``text`` to ``path`` via tmp + rename (crash-safe: readers
    never observe a half-written file)."""
    tmp = '%s.tmp.%d' % (path, os.getpid())
    with open(tmp, 'w') as f:
        f.write(text)
        f.flush()
        try:
            os.fsync(f.fileno())
        except OSError:         # pragma: no cover
            pass
    os.replace(tmp, path)
    return path


def export_chrome_trace(src, out=None):
    """Convert a JSONL trace to the Chrome/Perfetto trace-event format
    (open in ``ui.perfetto.dev`` or ``chrome://tracing``).

    ``src`` is a trace file or directory; ``out`` defaults to
    ``chrome_trace.json`` next to it.  Written atomically; returns the
    output path.
    """
    records, _ = read_trace(src)
    events = []
    for r in records:
        if r.get('t') != 'span':
            continue
        ev = {'name': r.get('name', '?'), 'ph': 'X', 'cat': 'span',
              'ts': float(r.get('ts', 0.0)) * 1e6,
              'dur': float(r.get('dur', 0.0)) * 1e6,
              'pid': r.get('pid', 0), 'tid': r.get('depth', 0)}
        if r.get('attrs'):
            ev['args'] = r['attrs']
        if not r.get('ok', True):
            ev['cname'] = 'terrible'        # red in the trace viewer
            ev.setdefault('args', {})['exc'] = r.get('exc', '')
        events.append(ev)
    if out is None:
        base = src if os.path.isdir(src) else os.path.dirname(src) or '.'
        out = os.path.join(base, 'chrome_trace.json')
    atomic_write(out, json.dumps({'traceEvents': events,
                                  'displayTimeUnit': 'ms'}))
    return out
