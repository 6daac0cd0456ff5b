"""Live telemetry export: Prometheus/JSON endpoints + flight recorder.

Everything the diagnostics layer accumulates — the metrics registry,
the SLO burn state, the last N completed request waterfalls — is
in-process state that today only reaches disk at end of run.  This
module is the *live* window: a zero-dependency background HTTP thread
(``http.server`` from the standard library, nothing installed) serving

- ``/metrics``       the registry as Prometheus exposition text
  (labelled names — ``serve.queue_depth{fleet=a}`` — parse back into
  real Prometheus labels),
- ``/metrics.json``  the raw registry snapshot,
- ``/slo``           every registered source (SLO trackers, server
  summaries) as one JSON document,
- ``/flight``        the flight-recorder ring, and the ring of host
  calls (``host_calls``: one record per outermost ``scope`` closed),
- ``/healthz``       liveness.

Enable with ``set_options(telemetry_port=9464)`` (or
``$NBKIT_TELEMETRY_PORT``); port 0 binds an ephemeral port and the
exporter reports the real one.  The serve/region front doors call
:func:`ensure_exporter` at construction, so a served process is
scrapeable the moment it can accept a request.

The **flight recorder** is the crash companion: a bounded ring of the
last ``NBKIT_FLIGHT_N`` (default 64) completed request waterfall
summaries, dumped atomically to ``flight-<pid>.json`` beside the
trace on preemption, on a doctor FAIL, or on demand — so a post-mortem
has the final requests' shape even when nobody was scraping.
"""

import json
import os
import threading
import time
from collections import deque

from .metrics import REGISTRY, split_label
from .trace import atomic_write, current_tracer

_lock = threading.Lock()
_exporter = None
_sources = {}


def register_source(name, fn):
    """Register ``fn`` (no-args -> JSON-able) under ``name`` in the
    ``/slo`` document.  Re-registering a name replaces it (a rebuilt
    Region replaces its predecessor's tracker)."""
    with _lock:
        _sources[str(name)] = fn


def _sources_snapshot():
    with _lock:
        items = list(_sources.items())
    out = {}
    for name, fn in items:
        try:
            out[name] = fn()
        except Exception as e:      # a broken source must not 500 /slo
            out[name] = {'error': '%s: %s' % (type(e).__name__, e)}
    return out


def _sanitize(name):
    """Prometheus metric-name charset: [a-zA-Z_:][a-zA-Z0-9_:]*."""
    out = []
    for i, ch in enumerate(name):
        if ch.isalnum() or ch in '_:':
            out.append(ch)
        else:
            out.append('_')
    s = ''.join(out)
    if s and s[0].isdigit():
        s = '_' + s
    return s


def _prom_labels(labels):
    if not labels:
        return ''
    body = ','.join('%s="%s"' % (_sanitize(k),
                                 str(v).replace('\\', '\\\\')
                                 .replace('"', '\\"'))
                    for k, v in sorted(labels.items()))
    return '{%s}' % body


def _prom_value(v):
    if v is None:
        return 'NaN'
    if isinstance(v, bool):
        return '1' if v else '0'
    return repr(float(v)) if isinstance(v, float) else str(v)


def prometheus_text(snapshot=None):
    """The metrics registry as Prometheus exposition text.

    Counters export as ``<name>_total``; gauges as ``<name>`` plus
    ``_max``/``_min`` watermarks; histograms as the summary quartet
    ``_count``/``_sum``/``_last``/``_max``.  Labelled registry names
    (metrics.labelled) become real Prometheus labels.
    """
    snap = snapshot if snapshot is not None else REGISTRY.snapshot()
    groups = {}
    for name, m in sorted(snap.items()):
        bare, labels = split_label(name)
        groups.setdefault(bare, []).append((labels, m))
    lines = []
    for bare in sorted(groups):
        base = _sanitize(bare)
        series = groups[bare]
        kind = series[0][1].get('type')
        if kind == 'counter':
            lines.append('# TYPE %s_total counter' % base)
            for labels, m in series:
                lines.append('%s_total%s %s'
                             % (base, _prom_labels(labels),
                                _prom_value(m.get('value', 0))))
        elif kind == 'gauge':
            lines.append('# TYPE %s gauge' % base)
            for labels, m in series:
                lines.append('%s%s %s' % (base, _prom_labels(labels),
                                          _prom_value(m.get('value'))))
            for suffix in ('max', 'min'):
                lines.append('# TYPE %s_%s gauge' % (base, suffix))
                for labels, m in series:
                    lines.append('%s_%s%s %s'
                                 % (base, suffix, _prom_labels(labels),
                                    _prom_value(m.get(suffix))))
        elif kind == 'histogram':
            lines.append('# TYPE %s summary' % base)
            for labels, m in series:
                lab = _prom_labels(labels)
                lines.append('%s_count%s %s'
                             % (base, lab,
                                _prom_value(m.get('count', 0))))
                lines.append('%s_sum%s %s'
                             % (base, lab, _prom_value(m.get('sum', 0))))
            for suffix in ('last', 'max'):
                lines.append('# TYPE %s_%s gauge' % (base, suffix))
                for labels, m in series:
                    lines.append('%s_%s%s %s'
                                 % (base, suffix, _prom_labels(labels),
                                    _prom_value(m.get(suffix))))
    return '\n'.join(lines) + '\n'


# ---------------------------------------------------------------------------
# flight recorder

class FlightRecorder(object):
    """Bounded ring of the last N completed request summaries.

    ``record`` is called once per terminal request by the serve/region
    delivery paths with a small JSON-able dict (trace id, request id,
    status, stage durations).  ``dump`` seals the ring — plus the
    reason and the metric snapshot — to ``flight-<pid>.json`` next to
    the active trace (else ``$NBKIT_FLIGHT_PATH``; else nothing),
    atomically, never raising: it runs on preemption paths where a
    second failure must not mask the first.
    """

    def __init__(self, maxlen=None):
        if maxlen is None:
            try:
                maxlen = int(os.environ.get('NBKIT_FLIGHT_N', '64')
                             or 64)
            except ValueError:
                maxlen = 64
        self._lock = threading.Lock()
        self._ring = deque(maxlen=max(1, int(maxlen)))
        self.dumps = 0

    def record(self, entry):
        with self._lock:
            self._ring.append(dict(entry))

    def __len__(self):
        with self._lock:
            return len(self._ring)

    def snapshot(self):
        with self._lock:
            return list(self._ring)

    def _dump_path(self):
        tr = current_tracer()
        if tr is not None:
            return os.path.join(tr.dir, 'flight-%d.json' % os.getpid())
        env = os.environ.get('NBKIT_FLIGHT_PATH')
        if env:
            return env
        return None

    def dump(self, reason, path=None):
        """Seal the ring to disk; returns the path or None (no sink
        configured).  Never raises."""
        try:
            if path is None:
                path = self._dump_path()
            if path is None:
                return None
            body = {'v': 1, 'reason': str(reason), 'pid': os.getpid(),
                    'ts': round(time.time(), 6),
                    'requests': self.snapshot(),
                    'host_calls': HOST_CALLS.snapshot(),
                    'metrics': REGISTRY.snapshot(),
                    'sources': _sources_snapshot()}
            atomic_write(path, json.dumps(body, indent=1, default=str))
            with self._lock:
                self.dumps += 1
            return path
        except Exception:       # pragma: no cover - crash path
            return None


#: The process-wide flight recorder the serve/region stacks feed.
FLIGHT = FlightRecorder()

#: The host ledger's ring (trace.py:_Scope): one record per call, the
#: outermost ``scope`` of its thread: ``root``, ``t0_ns``
#: (``time.time_ns()`` at its start), ``wall_s``, ``self_s`` by scope
#: (they sum to ``wall_s``), ``syncs``, ``sync_wait_s`` (the self time
#: of the ``sync.*`` scopes: what the host waited), ``retrace_s``.
#: Kept with no instrument on; room for a benchmark window's calls.
HOST_CALLS = FlightRecorder(maxlen=1024)


def flight_recorder():
    return FLIGHT


# ---------------------------------------------------------------------------
# the HTTP thread

class TelemetryExporter(object):
    """Background ``ThreadingHTTPServer`` serving the export plane.

    Construct via :func:`ensure_exporter` (option-driven singleton) or
    directly in tests; ``port=0`` binds an ephemeral port.  ``stop()``
    shuts the socket down; the daemon thread never blocks exit.
    """

    def __init__(self, port=0, host='127.0.0.1'):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)
        exporter = self

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):   # stay silent on the console
                pass

            def _send(self, body, ctype):
                data = body.encode('utf-8')
                self.send_response(200)
                self.send_header('Content-Type', ctype)
                self.send_header('Content-Length', str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                path = self.path.split('?', 1)[0]
                try:
                    if path in ('/metrics', '/'):
                        self._send(prometheus_text(),
                                   'text/plain; version=0.0.4')
                    elif path == '/metrics.json':
                        self._send(json.dumps(REGISTRY.snapshot(),
                                              default=str),
                                   'application/json')
                    elif path == '/slo':
                        self._send(json.dumps(_sources_snapshot(),
                                              default=str),
                                   'application/json')
                    elif path == '/flight':
                        self._send(json.dumps(
                            {'requests': exporter.flight.snapshot(),
                             'host_calls': HOST_CALLS.snapshot(),
                             'dumps': exporter.flight.dumps},
                            default=str), 'application/json')
                    elif path == '/healthz':
                        self._send('ok\n', 'text/plain')
                    else:
                        self.send_error(404)
                except Exception:   # a scrape must never kill serving
                    try:
                        self.send_error(500)
                    except Exception:
                        pass

        self.flight = FLIGHT
        self._httpd = ThreadingHTTPServer((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        self.host = host
        self.port = self._httpd.server_address[1]
        self.url = 'http://%s:%d' % (host, self.port)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name='nbkit-telemetry')
        self._thread.start()

    def stop(self):
        # shutdown() only *requests* serve_forever to exit; without
        # the join an immediate successor exporter can race this one
        # for the port, and a stop_exporter()/ensure_exporter() pair
        # in a loop flakes with address-in-use.  The join makes stop
        # a contract: when it returns, the serving thread is gone.
        # Bounded join: serve_forever polls at 0.5s, so 5s is ample,
        # and a wedged scrape must not hang interpreter exit.
        try:
            self._httpd.shutdown()
        except Exception:       # pragma: no cover - double stop
            pass
        t = self._thread
        if t is not None and t.is_alive() and \
                t is not threading.current_thread():
            t.join(timeout=5.0)
        try:
            self._httpd.server_close()
        except Exception:       # pragma: no cover - double stop
            pass


def ensure_exporter():
    """Start (or return) the option-driven exporter singleton.

    Reads the ``telemetry_port`` option; None/empty disables (returns
    None).  Idempotent — every serve/region front door calls this at
    construction.  A port that fails to bind logs nothing and returns
    None rather than killing the server it rides on.
    """
    global _exporter
    try:
        from .. import _global_options
        port = _global_options['telemetry_port']
    except (ImportError, KeyError):
        return None
    if port is None or port == '':
        return _exporter
    try:
        port = int(port)
    except (TypeError, ValueError):
        return None
    with _lock:
        if _exporter is not None:
            return _exporter
    try:
        exp = TelemetryExporter(port=port)
    except OSError:
        return None
    with _lock:
        if _exporter is None:
            _exporter = exp
            return exp
    exp.stop()                  # lost the race
    return _exporter


def stop_exporter():
    """Stop the singleton (tests)."""
    global _exporter
    with _lock:
        exp, _exporter = _exporter, None
    if exp is not None:
        exp.stop()
