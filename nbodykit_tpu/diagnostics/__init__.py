"""nbodykit_tpu.diagnostics — structured tracing, metrics and
crash-safe telemetry for every hot path.

The reference nbodykit only ever had ad-hoc wall-clock logging
(SURVEY §L0); a production-scale TPU stack needs first-class
observability that *survives the run dying* — the recurring failure
mode here is a process dying mid-measurement and taking the evidence
with it.  Three pieces:

- :mod:`.trace` — a low-overhead span tracer (context manager +
  decorator, monotonic clocks, per-thread nesting, exception-safe)
  emitting crash-safe JSONL (append + fsync per completed span) and a
  Perfetto/chrome-trace export.  No-op when disabled.  ``scope``
  puts one library layer on that trace, on ``jax.profiler``'s host
  line and in the HLO op names at once (``nbk.<layer>``).
- :mod:`.metrics` — process-wide counters/gauges/histograms (exchange
  bytes, FFT chunks, device live-buffer
  watermarks) plus compile telemetry (``instrumented_jit``, the
  ``jax.monitoring`` hook).
- :mod:`.report` — end-of-run summary (per-phase wall, top spans,
  metric tables) as JSON + text, written atomically.
- :mod:`.analyze` — fleet-level analysis of a directory of per-process
  traces: clock alignment on collective anchors, merged timeline,
  straggler tables, critical-path attribution, hung-collective and
  heartbeat post-mortems.
- :mod:`.regress` — the BENCH_r*.json trajectory as machine-checked
  history (``BENCH_HISTORY.json``): regression and stale-evidence
  verdicts.

Enable with ``nbodykit_tpu.set_options(diagnostics='/tmp/trace')`` (or
``$NBKIT_DIAGNOSTICS``); self-check with
``python -m nbodykit_tpu.diagnostics --self-check``; fleet doctor with
``nbodykit-tpu-doctor``.  Full guide: docs/OBSERVABILITY.md.
"""

import functools
import os
import sys

from .trace import (NULL_SPAN, SCOPE_PREFIX, SYNC_PREFIX,  # noqa: F401
                    RequestContext, Tracer, _Scope, atomic_write,
                    current_tracer,
                    exemplar_fraction, export_chrome_trace,
                    new_request_context, read_trace, trace_context,
                    trace_files, trace_scope, trace_state_clean)
from .metrics import (REGISTRY, Counter, Gauge, Histogram,  # noqa: F401
                      MetricsRegistry, counter, gauge, histogram,
                      device_watermarks, install_compile_telemetry,
                      instrumented_jit)
from .report import render_text, summarize, write_report  # noqa: F401
# the function is re-exported as analyze_trace so the submodule
# remains reachable as nbodykit_tpu.diagnostics.analyze
from .analyze import analyze as analyze_trace  # noqa: F401
from .analyze import render_analysis, request_report  # noqa: F401
from .regress import build_history, render_regress  # noqa: F401
from .slo import (DEFAULT_SLOS, SLObjective, SLOPolicy,  # noqa: F401
                  SLOTracker)
from .export import (FLIGHT, HOST_CALLS, FlightRecorder,  # noqa: F401
                     TelemetryExporter, ensure_exporter, flight_recorder,
                     prometheus_text, register_source)


def enabled():
    """True when a trace sink is configured (the ``diagnostics``
    option is set)."""
    return current_tracer() is not None


def configure(path):
    """Enable tracing to ``path`` (a directory, or a ``*.jsonl`` file)
    process-wide; ``configure(None)`` disables.  Equivalent to
    ``set_options(diagnostics=path)`` as a plain call.  Returns the
    active tracer (or None)."""
    from .. import _global_options
    _global_options['diagnostics'] = path
    return current_tracer()


def configure_from_env(default=None, var='NBKIT_DIAGNOSTICS'):
    """Resolve the trace destination from the environment and enable it.

    The single place detached workers (bench ladder, multi-host test
    workers) decide where to trace: ``$NBKIT_DIAGNOSTICS`` wins when
    set (an empty value explicitly disables), else ``default``; None
    disables.  Returns the active tracer (or None).
    """
    path = os.environ.get(var)
    if path is None:
        path = default
    return configure(path or None)


def span(name, **attrs):
    """A timed, nested span::

        with span('paint', method='mxu', npart=n):
            ...

    Returns a shared no-op context manager when diagnostics are
    disabled — safe (and free) to leave in hot paths.  Attributes must
    be JSON-serializable (anything else is stringified)."""
    t = current_tracer()
    if t is None:
        return NULL_SPAN
    return t.span(name, attrs)


def span_if(cond, name, **attrs):
    """:func:`span` gated on ``cond`` — the idiom for call sites that
    may run under a jax trace, where host-side timing is meaningless
    (pass e.g. ``not isinstance(x, jax.core.Tracer)``)."""
    if not cond:
        return NULL_SPAN
    t = current_tracer()
    if t is None:
        return NULL_SPAN
    return t.span(name, attrs)


def span_eager(name, **attrs):
    """:func:`span`, but a no-op while jax is staging a trace
    (jit/scan/shard_map) — for call sites without a handy operand to
    test for tracer-ness."""
    t = current_tracer()
    if t is None or not trace_state_clean():
        return NULL_SPAN
    return t.span(name, attrs)


def scope(name, **attrs):
    """One library layer (``paint``, ``fft.r2c``, ``fftpower.binning``)
    on all three clocks::

        with scope('fft.r2c', shape=list(shape)) as sc:
            out = sc.done(_impl(x))

    - the JSONL trace: :func:`span_eager`'s record under the same name,
      only while the ``diagnostics`` option is on;
    - the profiler's host line: ``TraceAnnotation('nbk.' + name)``
      whenever jax is not staging, whatever the option says (a
      TraceMe costs next to nothing with no profiler session open), so
      a device trace can join each launched program to its layer;
    - the HLO op names: ``jax.named_scope('nbk.' + name)`` while jax
      is staging (jit / shard_map / vmap): trace-time metadata only,
      nothing at run time and no change to the compiled program.  The
      host annotation is kept there too: an eager ``shard_map`` runs
      its body one primitive a program, each launched from inside the
      scope with no name stack in its op names (an all_to_all reads
      ``jit(<unknown>)/shard_map/all_to_all`` on the chip), so only
      the host line can name them; under a real jit it marks the
      tracing and launches nothing.

    - the host ledger: whenever jax is not staging, whatever the
      option says, the scope's self time (its wall less its
      children's) on the calling thread; the outermost scope is the
      call's root, and when it closes its parts go to the registry
      (``host.<name>.self_s``, ``.n``) and one record to the ring
      ``HOST_CALLS`` (``trace._Scope``).  Two clock readings and a
      dict update; no sync, no file.

    Never syncs by itself; ``sc.done(result)`` waits for ``result``
    only while the JSONL span is recording.  Eager ops do not carry a
    named scope reliably (the dispatch cache reuses whichever name
    compiled first), hence the host annotation there."""
    jax = sys.modules.get('jax')
    if jax is None:             # diagnostics never requires jax
        return _Scope(name, NULL_SPAN, span(name, **attrs))
    mark = jax.profiler.TraceAnnotation(SCOPE_PREFIX + name)
    if trace_state_clean():
        return _Scope(name, mark, span(name, **attrs))
    return _Scope(name, mark, NULL_SPAN,
                  jax.named_scope(SCOPE_PREFIX + name))


def fetch(x, what):
    """``x`` (an array, or a tree of them) as host arrays, under
    ``scope('sync.' + what)``: the one marked way from the device to
    the host.  The conversion waits for whatever produces ``x``, so a
    ``sync.*`` scope's self time is time the host *waited*, where
    every other scope's is time it worked; the ledger counts them
    (``host.syncs``, a call record's ``syncs`` / ``sync_wait_s``).
    Launch the op that produces ``x`` before the call, outside the
    scope (``fetch(total(x), 'catalog.total')`` does: arguments are
    evaluated first), so that its device time keeps its layer."""
    with scope(SYNC_PREFIX + what):
        jax = sys.modules.get('jax')
        if jax is None:
            import numpy
            return numpy.asarray(x)
        return jax.device_get(x)


def traced(name=None):
    """Decorator form of :func:`span`::

        @traced()               # span named module.qualname
        def load_catalog(...): ...

        @traced('io.read')      # explicit span name
        def read(...): ...
    """
    def deco(fn):
        label = name or '%s.%s' % (fn.__module__, fn.__qualname__)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            t = current_tracer()
            if t is None:
                return fn(*args, **kwargs)
            with t.span(label):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def current_trace_file():
    """Path of the active trace file, or None."""
    t = current_tracer()
    return t.path if t is not None else None
