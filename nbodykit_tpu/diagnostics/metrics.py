"""Process-wide metric registry: counters, gauges, histograms.

The numeric companion to the span tracer (trace.py): spans answer
"where did the wall clock go", metrics answer "how much work moved" —
exchange bytes shipped, FFT chunks executed, paint throughput per
kernel, retry counts, per-device live-buffer watermarks.

Metrics are always-on (recording is a dict lookup + a lock-guarded
add — cheap enough for every hot path) and land on disk only through
the report writer (report.py) or a snapshot, so they impose no file
I/O on the measured code.  ``REGISTRY.reset()`` restores a pristine
registry (tests isolate through it).

Instrumentation that runs *inside* a jitted function executes once per
trace (compilation), not once per device execution — counters bumped
there (e.g. ops/paint.py's kernel-trace counters) are labeled
``*.trace.*`` to make that explicit.

Compile telemetry ("why was rep 1 slow") lives here too:

- :func:`install_compile_telemetry` hooks ``jax.monitoring`` so every
  XLA compile lands as ``xla.compile.*`` histograms plus persistent
  compilation-cache hit/miss counters (``xla.cache.*``); each stage's
  seconds (trace, lower, backend) are charged to the innermost scope
  the compiling thread has open (``host.<scope>.retrace_s``) and —
  when a tracer is active — land as retroactive ``compile.trace`` /
  ``.lower`` / ``.backend`` spans in the trace file.
- :func:`instrumented_jit` is a drop-in ``jax.jit`` that attributes
  compiles to a *named* entry point: per-label hit/miss counters, a
  first-call-wall histogram, and a ``compile.<label>`` span on every
  cache miss.  The jit hot paths (pmesh.py, parallel/dfft.py,
  ops/paint.py, algorithms/fftpower.py, bench.py) route through it.
"""

import threading
import time


class Counter(object):
    """Monotonic sum (``add``)."""

    __slots__ = ('name', '_lock', 'value')

    def __init__(self, name, lock):
        self.name = name
        self._lock = lock
        self.value = 0

    def add(self, n=1):
        with self._lock:
            self.value += n
        return self

    def snapshot(self):
        return {'type': 'counter', 'value': self.value}


class Gauge(object):
    """Last-value metric with min/max watermarks (``set``)."""

    __slots__ = ('name', '_lock', 'value', 'max', 'min')

    def __init__(self, name, lock):
        self.name = name
        self._lock = lock
        self.value = None
        self.max = None
        self.min = None

    def set(self, v):
        with self._lock:
            self.value = v
            self.max = v if self.max is None else max(self.max, v)
            self.min = v if self.min is None else min(self.min, v)
        return self

    def snapshot(self):
        return {'type': 'gauge', 'value': self.value,
                'max': self.max, 'min': self.min}


class Histogram(object):
    """Streaming distribution summary (``observe``): count, sum, mean,
    min/max, last.  No buckets are kept — the spans carry the
    per-event detail; this is the cheap aggregate for the report's
    throughput tables."""

    __slots__ = ('name', '_lock', 'count', 'sum', 'min', 'max', 'last')

    def __init__(self, name, lock):
        self.name = name
        self._lock = lock
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None
        self.last = None

    def observe(self, v):
        v = float(v)
        with self._lock:
            self.count += 1
            self.sum += v
            self.min = v if self.min is None else min(self.min, v)
            self.max = v if self.max is None else max(self.max, v)
            self.last = v
        return self

    @property
    def mean(self):
        return self.sum / self.count if self.count else None

    def snapshot(self):
        return {'type': 'histogram', 'count': self.count,
                'sum': self.sum, 'mean': self.mean,
                'min': self.min, 'max': self.max, 'last': self.last}


class MetricsRegistry(object):
    """Named metrics, one process-wide instance (``REGISTRY``).

    ``counter``/``gauge``/``histogram`` get-or-create; asking for an
    existing name with a different type raises (a typo'd re-use would
    otherwise silently fork the data).
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._metrics = {}

    def _get(self, cls, name):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, self._lock)
            elif type(m) is not cls:
                raise TypeError(
                    'metric %r already registered as %s, not %s'
                    % (name, type(m).__name__, cls.__name__))
            return m

    def counter(self, name):
        return self._get(Counter, name)

    def gauge(self, name):
        return self._get(Gauge, name)

    def histogram(self, name):
        return self._get(Histogram, name)

    def add(self, items):
        """Add to several counters (``(name, n)`` pairs) under one
        hold of the lock: the host ledger's flush when a call ends."""
        with self._lock:
            for name, n in items:
                self._get(Counter, name).value += n

    def snapshot(self):
        """A plain-dict copy of every metric, sorted by name."""
        with self._lock:
            return {name: m.snapshot()
                    for name, m in sorted(self._metrics.items())}

    def reset(self):
        """Drop every metric (test isolation)."""
        with self._lock:
            self._metrics.clear()

    def __len__(self):
        with self._lock:
            return len(self._metrics)


REGISTRY = MetricsRegistry()


def labelled(name, labels):
    """Fold ``labels`` into a registry name: ``'a.b{k=v,k2=v2}'``
    (keys sorted, so the same label set always lands on the same
    metric).  The registry stays a flat name->metric map — labels are
    a naming convention the export plane (export.py) parses back into
    Prometheus label syntax."""
    if not labels:
        return name
    body = ','.join('%s=%s' % (k, labels[k]) for k in sorted(labels))
    return '%s{%s}' % (name, body)


def split_label(name):
    """Inverse of :func:`labelled`: ``(bare_name, {labels})``."""
    if name.endswith('}') and '{' in name:
        bare, _, body = name.partition('{')
        labels = {}
        for part in body[:-1].split(','):
            k, eq, v = part.partition('=')
            if eq:
                labels[k] = v
        return bare, labels
    return name, {}


# module-level conveniences bound to the process-wide registry; the
# keyword form labels the metric: ``gauge('serve.queue_depth',
# fleet='a')`` names ``serve.queue_depth{fleet=a}``
def counter(name, **labels):
    return REGISTRY.counter(labelled(name, labels))


def gauge(name, **labels):
    return REGISTRY.gauge(labelled(name, labels))


def histogram(name, **labels):
    return REGISTRY.histogram(labelled(name, labels))


def prefixed(prefix, registry=None):
    """Snapshot of every metric whose name starts with ``prefix``
    (e.g. ``prefixed('resilience.')`` for the doctor's retry/
    degradation/resume totals), keyed by the name with the prefix
    stripped."""
    reg = registry if registry is not None else REGISTRY
    snap = reg if isinstance(reg, dict) else reg.snapshot()
    return {name[len(prefix):]: m for name, m in snap.items()
            if name.startswith(prefix)}


# ---------------------------------------------------------------------------
# compile telemetry

# jax.monitoring event name -> registry counter
_XLA_EVENT_COUNTERS = {
    '/jax/compilation_cache/cache_hits': 'xla.cache.hits',
    '/jax/compilation_cache/cache_misses': 'xla.cache.misses',
    '/jax/compilation_cache/compile_requests_use_cache':
        'xla.cache.requests',
}
# jax.monitoring duration event -> (registry histogram, span name):
# the three stages of a jit cache miss
_XLA_DURATION_EVENTS = {
    '/jax/core/compile/jaxpr_trace_duration':
        ('xla.compile.trace_s', 'compile.trace'),
    '/jax/core/compile/jaxpr_to_mlir_module_duration':
        ('xla.compile.lower_s', 'compile.lower'),
    '/jax/core/compile/backend_compile_duration':
        ('xla.compile.backend_s', 'compile.backend'),
}
#: the stage spans' names: ``compile.<anything else>`` is a labelled
#: jit's first-call wall (:func:`instrumented_jit`)
STAGE_SPANS = tuple(span for _, span in _XLA_DURATION_EVENTS.values())
_monitoring_lock = threading.Lock()
_monitoring_installed = False


def install_compile_telemetry():
    """Route jax.monitoring compile/cache events into the registry.

    Idempotent and cheap; called at import by the jit hot paths (they
    all import jax anyway) so XLA recompiles are never invisible.  Each
    stage of a cache miss (``jaxpr_trace``, ``jaxpr_to_mlir_module``,
    ``backend_compile``) is charged to the scope it happened under
    (``trace.note_retrace``) and lands as a retroactive
    ``compile.trace`` / ``.lower`` / ``.backend`` span, ``attrs.scope``
    naming that scope, when a tracer is active — the out-of-band
    path, since jax reports the duration only after the fact.
    Returns True when the
    hook is (already) installed, False when jax.monitoring is missing.
    """
    global _monitoring_installed
    with _monitoring_lock:
        if _monitoring_installed:
            return True
        try:
            from jax import monitoring
        except ImportError:
            return False

        def _on_event(event, **kw):
            name = _XLA_EVENT_COUNTERS.get(event)
            if name is not None:
                REGISTRY.counter(name).add(1)

        def _on_duration(event, duration, **kw):
            names = _XLA_DURATION_EVENTS.get(event)
            if names is None:
                return
            REGISTRY.histogram(names[0]).observe(duration)
            from .trace import current_tracer, note_retrace
            under = note_retrace(duration)
            tr = current_tracer()
            if tr is not None:
                tr.emit_span(names[1], time.time() - duration, duration,
                             {'scope': under})

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _monitoring_installed = True
        return True


def instrumented_jit(fun=None, label=None, **jit_kwargs):
    """``jax.jit`` plus per-entry-point compile telemetry.

    Every eager call checks the jit cache size before/after dispatch:
    a growth is a compile attributed to ``label`` —
    ``compile.<label>.misses`` is bumped, the first-call wall (compile
    + one execution) lands in ``compile.<label>.first_call_s``, and a
    ``compile.<label>`` span is written to the active trace; a re-used
    executable bumps ``compile.<label>.hits``.  Calls made while jax is
    staging an outer trace pass straight through (the inner jit is
    inlined there; host-side bookkeeping would be noise).

    Usable exactly like ``jax.jit`` (decorator or call form); extra
    keyword arguments (``donate_argnums``, ...) are forwarded.
    """
    if fun is None:
        return lambda f: instrumented_jit(f, label=label, **jit_kwargs)
    import functools
    import jax
    install_compile_telemetry()
    jitted = jax.jit(fun, **jit_kwargs)
    lbl = label or getattr(fun, '__name__', None) or 'fn'

    @functools.wraps(fun)
    def wrapper(*args, **kwargs):
        from .trace import current_tracer, trace_state_clean
        if not trace_state_clean():
            return jitted(*args, **kwargs)
        try:
            n0 = jitted._cache_size()
        except Exception:       # pragma: no cover - jax internals moved
            return jitted(*args, **kwargs)
        ts = time.time()
        t0 = time.perf_counter()
        out = jitted(*args, **kwargs)
        try:
            n1 = jitted._cache_size()
        except Exception:       # pragma: no cover
            return out
        if n1 > n0:
            dt = time.perf_counter() - t0
            REGISTRY.counter('compile.%s.misses' % lbl).add(n1 - n0)
            REGISTRY.histogram(
                'compile.%s.first_call_s' % lbl).observe(dt)
            tr = current_tracer()
            if tr is not None:
                # first-call wall, compile included (the execution share
                # is usually noise next to it; xla.compile.* histograms
                # hold the pure-compile stages)
                tr.emit_span('compile.%s' % lbl, ts, dt,
                             {'misses': n1 - n0})
        else:
            REGISTRY.counter('compile.%s.hits' % lbl).add(1)
        return out

    wrapper._jitted = jitted    # escape hatch: .lower(), cache control
    return wrapper


def device_watermarks(registry=None):
    """Record per-device live-buffer totals from ``jax.live_arrays()``
    as gauges (``device.<platform>:<id>.live_bytes`` / ``.live_arrays``
    — the gauge ``max`` is the watermark) and return them.

    Best-effort: returns ``{}`` when jax is not already imported (this
    module never forces a backend init) or the runtime refuses.
    """
    import sys
    jax = sys.modules.get('jax')
    if jax is None:
        return {}
    try:
        arrs = jax.live_arrays()
    except Exception:
        return {}
    per = {}
    for a in arrs:
        try:
            for s in a.addressable_shards:
                d = s.device
                key = '%s:%d' % (d.platform, d.id)
                st = per.setdefault(key, [0, 0])
                st[0] += 1
                st[1] += int(getattr(s.data, 'nbytes', 0) or 0)
        except Exception:
            continue
    reg = registry if registry is not None else REGISTRY
    out = {}
    for key, (narr, nbytes) in sorted(per.items()):
        reg.gauge('device.%s.live_arrays' % key).set(narr)
        reg.gauge('device.%s.live_bytes' % key).set(nbytes)
        out[key] = {'live_arrays': narr, 'live_bytes': nbytes}
    return out
