"""The persistent per-platform performance database (``TUNE_CACHE.json``).

One JSON file holds every measured winner, content-keyed by

    (platform, device kind, device count, op, shape class, dtype)

so a number measured on a v5e chip can never silently steer a CPU run
(and vice versa — the round-5 failure mode was exactly a hand-picked
kernel choice that lost on the real hardware).  Writes are atomic
(tmp + ``os.replace``, the same discipline as
:mod:`..resilience.checkpoint`), reads are mtime-cached so dispatch-time
lookups cost one ``stat`` plus dict lookups.

Shape classes bucket (Nmesh, Npart) logarithmically — ``mesh512-part1e7``
— because the kernel ranking flips with regime, not with the exact
count (Jing 2005; Cui et al. 2008, PAPERS.md).  A lookup that misses its
exact class falls back to the *nearest* measured class of the same
(platform, device kind, op, dtype), preferring the same device count;
the match kind is reported so callers (and the doctor) can tell a
measured answer from an extrapolated one.

Every entry carries ``measured_at``; :func:`entry_age_days` feeds the
doctor's staleness verdict (entries older than 30 days WARN — a tuned
choice is evidence, and evidence goes stale).
"""

import json
import math
import os
import re
import threading
import time

import numpy as np

# options a winner config may legitimately carry (anything else in a
# committed cache is a validation error, not silently applied)
TUNABLE_OPTIONS = ('paint_method', 'paint_order', 'paint_deposit',
                   'paint_chunk_size', 'paint_bucket_slack',
                   'paint_streams', 'fft_chunk_bytes', 'fft_decomp',
                   'fft_pencil', 'exchange_slack', 'mesh_dtype',
                   'a2a_compress', 'ingest_chunk_rows')

STALE_DAYS = 30.0

_ENTRY_REQUIRED = ('platform', 'device_kind', 'device_count', 'op',
                   'shape_class', 'dtype', 'measured_at')

_CLASS_RE = re.compile(
    r'^mesh(\d+)(?:-part1e(\d+))?(?:-g\d+x\d+)?$'
    r'|^part1e(\d+)(?:-g\d+x\d+)?$')
_FACTOR_RE = re.compile(r'-g(\d+)x(\d+)$')


def utcnow():
    return time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())


# ---------------------------------------------------------------------------
# shape classes

def shape_class(nmesh=None, npart=None, mesh_shape=None):
    """The logarithmic shape bucket for (nmesh, npart):
    ``mesh512-part1e7`` / ``mesh512`` / ``part1e7``.  Nmesh buckets to
    the nearest power of two, Npart to the nearest decade.

    ``mesh_shape`` is the (Px, Py) device-mesh factorization when the
    op's ranking depends on it (the fft decomp knob): it appends
    ``-g4x2``-style suffix, making classes measured under different
    factorizations mutually incomparable (:func:`class_distance`) — a
    pencil winner measured on a 4x2 mesh must never be replayed onto
    8x1, where the two transposes have entirely different shapes.
    """
    parts = []
    if nmesh:
        parts.append('mesh%d' % (1 << max(0, int(round(
            math.log2(float(nmesh)))))))
    if npart:
        parts.append('part1e%d' % max(0, int(round(
            math.log10(float(npart))))))
    if not parts:
        raise ValueError('shape_class needs nmesh and/or npart')
    if mesh_shape is not None:
        px, py = mesh_shape
        parts.append('g%dx%d' % (int(px), int(py)))
    return '-'.join(parts)


def class_coords(sclass):
    """``(log2 nmesh, log10 npart)`` (either may be None) for a shape
    class string, or None when it does not parse."""
    m = _CLASS_RE.match(str(sclass))
    if not m:
        return None
    mesh, part, part_only = m.groups()
    lm = math.log2(int(mesh)) if mesh else None
    lp = float(part if part is not None else part_only) \
        if (part is not None or part_only is not None) else None
    return (lm, lp)


def class_factorization(sclass):
    """The (Px, Py) device-mesh factorization suffix of a shape class
    (``mesh256-g4x2`` -> (4, 2)), or None when absent."""
    m = _FACTOR_RE.search(str(sclass))
    if not m:
        return None
    return (int(m.group(1)), int(m.group(2)))


def class_distance(a, b):
    """Log-space distance between two shape classes; None when either
    does not parse or they describe different axes (a mesh-only class
    is not comparable to a part-only one, and classes keyed under
    different device-mesh factorizations are mutually incomparable)."""
    ca, cb = class_coords(a), class_coords(b)
    if ca is None or cb is None:
        return None
    if class_factorization(a) != class_factorization(b):
        return None
    d = 0.0
    for xa, xb in zip(ca, cb):
        if (xa is None) != (xb is None):
            return None
        if xa is not None:
            d += (xa - xb) ** 2
    return math.sqrt(d)


def canonical_dtype(dtype):
    """Canonical dtype name for a cache key.  Complex dtypes map to
    their real base (``c8`` -> ``float32``): the FFT chunk target for a
    field is a property of its real footprint, and the tuner measures
    the forward r2c.  The ``'bf16'`` storage token (which ``np.dtype``
    cannot parse) keys as ``bfloat16``."""
    if str(dtype).lower() in ('bf16', 'bfloat16'):
        return 'bfloat16'
    dt = np.dtype(dtype)
    if dt.kind == 'c':
        dt = np.dtype('f4' if dt.itemsize == 8 else 'f8')
    return dt.name


# ---------------------------------------------------------------------------
# device signature

def device_signature(count=None):
    """``(platform, device_kind, device_count)`` of the running
    backend.  ``count`` overrides the device count with the size of
    the mesh the op actually runs on (a paint on a 1-device
    ``ParticleMesh`` in an 8-device process is a 1-device paint)."""
    import jax
    devs = jax.devices()
    n = len(devs) if count is None else int(count)
    return (str(devs[0].platform), str(devs[0].device_kind), n)


def make_key(platform, device_kind, device_count, op, sclass, dtype):
    return '|'.join([str(platform), str(device_kind),
                     str(int(device_count)), str(op), str(sclass),
                     canonical_dtype(dtype)])


def entry_key(entry):
    return make_key(entry['platform'], entry['device_kind'],
                    entry['device_count'], entry['op'],
                    entry['shape_class'], entry['dtype'])


def entry_age_days(entry, now=None):
    """Days since the entry's measurement, or None without a parseable
    stamp."""
    from ..diagnostics.regress import parse_utc
    ts = parse_utc(entry.get('measured_at'))
    if ts is None:
        return None
    return ((time.time() if now is None else now) - ts) / 86400.0


# ---------------------------------------------------------------------------
# default location

def default_cache_path():
    """The committed repo-root ``TUNE_CACHE.json`` when running from a
    checkout, else a ``TUNE_CACHE.json`` next to the installed package."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, 'TUNE_CACHE.json')


def cache_path():
    """The active cache path: the ``tune_cache`` option (seeded from
    ``$NBKIT_TUNE_CACHE``) when set, else :func:`default_cache_path`."""
    try:
        from .. import _global_options
        configured = _global_options['tune_cache']
    except (ImportError, KeyError):
        configured = None
    return str(configured) if configured else default_cache_path()


# mtime-cached loads: dispatch-time resolution costs one stat.  The
# memo is hit concurrently by every serve worker thread resolving
# tuned options per request (nbodykit_tpu.serve), so reads and
# writes go through one lock — a dict half-updated by a racing
# loader must never be visible.
_loaded = {}            # path -> (mtime_ns, size, entries)
_loaded_lock = threading.Lock()


def _load_entries(path):
    try:
        st = os.stat(path)
    except OSError:
        return {}
    tag = (st.st_mtime_ns, st.st_size)
    with _loaded_lock:
        hit = _loaded.get(path)
        if hit is not None and hit[0] == tag:
            return hit[1]
    # parse outside the lock (a slow disk must not serialize every
    # dispatch); concurrent loaders may parse twice, last one wins —
    # both parsed the same (mtime, size) snapshot
    try:
        with open(path) as f:
            entries = dict(json.load(f).get('entries') or {})
    except (OSError, ValueError):
        entries = {}
    with _loaded_lock:
        _loaded[path] = (tag, entries)
    return entries


def reset_cache_memo():
    """Drop the mtime memo (test isolation)."""
    with _loaded_lock:
        _loaded.clear()


class TuneCache(object):
    """The performance database over one JSON file (default:
    :func:`cache_path`)."""

    def __init__(self, path=None):
        self.path = str(path) if path else cache_path()

    def entries(self):
        """``{key: entry}`` of every committed record (mtime-cached)."""
        return _load_entries(self.path)

    def get(self, platform, device_kind, device_count, op, sclass,
            dtype):
        return self.entries().get(make_key(
            platform, device_kind, device_count, op, sclass, dtype))

    def lookup(self, platform, device_kind, device_count, op, sclass,
               dtype):
        """``(entry, match)`` with match ``'exact'`` / ``'nearest'``,
        or ``(None, 'miss')``.  Nearest fallback searches the same
        (platform, device kind, op, dtype) for the closest shape
        class, preferring entries measured at the same device count;
        winner-less entries (everything infeasible) never match."""
        dtype = canonical_dtype(dtype)
        exact = self.get(platform, device_kind, device_count, op,
                         sclass, dtype)
        if exact is not None and exact.get('winner'):
            return exact, 'exact'
        same_sig = [e for e in self.entries().values()
                    if e.get('platform') == platform
                    and e.get('device_kind') == device_kind
                    and e.get('op') == op
                    and e.get('dtype') == dtype
                    and e.get('winner')]
        if not same_sig:
            return None, 'miss'
        same_count = [e for e in same_sig
                      if int(e.get('device_count', -1))
                      == int(device_count)]
        best, best_d = None, None
        for e in (same_count or same_sig):
            d = class_distance(sclass, e.get('shape_class'))
            if d is None:
                continue
            if best is None or d < best_d:
                best, best_d = e, d
        if best is None:
            return None, 'miss'
        return best, 'nearest'

    def put(self, entry):
        """Merge one entry (keyed by :func:`entry_key`) and commit the
        whole file atomically (tmp + rename).  Returns the key."""
        from ..diagnostics.trace import atomic_write
        entry = dict(entry)
        entry.setdefault('measured_at', utcnow())
        key = entry_key(entry)
        try:
            with open(self.path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
        if not isinstance(data.get('entries'), dict):
            data = {'version': 1, 'entries': {}}
        data['version'] = 1
        data['entries'][key] = entry
        atomic_write(self.path,
                     json.dumps(data, indent=1, sort_keys=True))
        with _loaded_lock:
            _loaded.pop(self.path, None)
        return key


def validate_cache(path):
    """Schema problems of a committed cache file, as a list of strings
    (empty == valid).  A missing file is valid (cold cache); garbage
    or mis-keyed entries are not — the smoke gate runs this so a
    broken committed database cannot silently steer dispatch."""
    problems = []
    if not os.path.exists(path):
        return problems
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        return ['unreadable: %s' % e]
    entries = data.get('entries')
    if not isinstance(entries, dict):
        return ['no "entries" mapping']
    for key, entry in sorted(entries.items()):
        if not isinstance(entry, dict):
            problems.append('%s: entry is not an object' % key)
            continue
        missing = [k for k in _ENTRY_REQUIRED if entry.get(k) is None]
        if missing:
            problems.append('%s: missing %s' % (key, ','.join(missing)))
            continue
        try:
            want = entry_key(entry)
        except (KeyError, TypeError, ValueError) as e:
            problems.append('%s: unkeyable entry (%s)' % (key, e))
            continue
        if want != key:
            problems.append('%s: key does not match entry fields (%s)'
                            % (key, want))
        if class_coords(entry['shape_class']) is None:
            problems.append('%s: unparseable shape_class %r'
                            % (key, entry['shape_class']))
        winner = entry.get('winner')
        if winner is not None:
            if not isinstance(winner, dict):
                problems.append('%s: winner is not an options mapping'
                                % key)
            else:
                unknown = sorted(set(winner) - set(TUNABLE_OPTIONS))
                if unknown:
                    problems.append('%s: winner carries non-tunable '
                                    'option(s) %s'
                                    % (key, ','.join(unknown)))
        if not isinstance(entry.get('trials', {}), dict):
            problems.append('%s: trials is not a mapping' % key)
    return problems


def cache_summary(path, now=None, stale_days=STALE_DAYS):
    """Posture summary for the doctor / regression tracker: entry
    count, stale count, infeasible-candidate count, the set of
    platform/device-kind signatures present.  ``None`` when the file
    does not exist; an ``error`` key when it is malformed."""
    if not os.path.exists(path):
        return None
    problems = validate_cache(path)
    if problems:
        return {'path': path, 'error': '; '.join(problems[:3]),
                'problems': len(problems)}
    entries = _load_entries(path)
    stale = infeasible = 0
    platforms, ops = set(), {}
    for entry in entries.values():
        age = entry_age_days(entry, now=now)
        if age is None or age > stale_days:
            stale += 1
        infeasible += len(entry.get('infeasible') or [])
        platforms.add('%s/%s' % (entry.get('platform'),
                                 entry.get('device_kind')))
        ops[entry.get('op')] = ops.get(entry.get('op'), 0) + 1
    return {'path': path, 'entries': len(entries), 'stale': stale,
            'infeasible': infeasible, 'platforms': sorted(platforms),
            'ops': ops, 'stale_days': stale_days}
