"""Content-addressed memoization of completed ``BinnedStatistic``s.

An analysis request is a *pure function* of what it computes — the
compiled-program identity, the realization input, and the options that
reach jit.  Nothing else.  So a completed spectrum can be served again
without re-execution, to any tenant, forever — the millionth user of a
public survey pays zero FLOPs — provided the address is exactly the
purity boundary:

    (program_key, seed | catalog-digest, sorted(jit options))

Runtime-only fields — priority, deadline_s, verify, the tenant, the
request id — must NEVER key the cache: they change *how* a request is
scheduled, not *what* it computes.  :data:`JIT_OPTIONS` /
:data:`RUNTIME_OPTIONS` make the split explicit, and
``tests/test_region.py`` holds the property: every jit-reaching
option perturbs the address, every runtime field perturbs nothing.

The addressing reuses the idioms the repo already trusts:

- the **catalog digest** for ``data_ref`` requests is the same
  stat-level fingerprint discipline as the ingest plane's
  :class:`~nbodykit_tpu.ingest.cache.CatalogCache` front door
  (realpath, size, mtime_ns, column map) — O(1), and a changed file
  bumps size/mtime and misses;
- **commits** are atomic tmp+rename with a content hash over the
  canonical body (``_atomic_bytes``/``_canonical``/``_sha`` from
  :mod:`nbodykit_tpu.resilience.checkpoint`) — a torn entry fails
  hash verification and is *deleted and recomputed, never served*;
- **eviction** is LRU under a byte cap, like every cache here.

Entries carry ``verified`` — True only when the committed result came
from a shadow-verified execution (docs/INTEGRITY.md tier-1), so a hit
can honestly say "two disjoint device groups agreed on these bytes".
The stamp is part of the hash-covered body: serving an unverified
entry as verified is a doctor-FAILable offense, provable in CI via
the ``region.result.stamp`` corrupt rule.
"""

import json
import os
import threading
from collections import OrderedDict

from ...diagnostics import counter, gauge
from ...resilience.checkpoint import (_atomic_bytes, _canonical, _safe,
                                      _sha)

#: Options that reach the compiled program (or the deterministic
#: streaming/deposit order) and therefore key the result address.
#: Inclusive by policy: an over-keyed cache splits; an under-keyed one
#: serves wrong bytes.
JIT_OPTIONS = (
    'mesh_dtype', 'a2a_compress', 'resampler', 'paint_method',
    'paint_chunk_size', 'paint_streams',
    'fft_chunk_bytes', 'fft_decomp', 'fft_pencil', 'integrity',
    'ingest_chunk_rows',
)

#: Options that only steer scheduling/telemetry — NEVER key material.
RUNTIME_OPTIONS = (
    'diagnostics', 'faults', 'io_verify_checksums', 'ingest_overlap',
    'ingest_cache_bytes', 'data_steal_grace_s', 'telemetry_port',
)


def catalog_identity(data_ref):
    """The stat-level catalog digest for a ``data_ref`` request: the
    CatalogCache fingerprint discipline (realpath, size, mtime_ns,
    column map, reader options) folded to one sha256.  A rewritten
    file bumps size/mtime and mints a new address; the request's
    ``seed`` is ignored exactly as execution ignores it."""
    path = str(data_ref.get('path'))
    try:
        st = os.stat(path)
        stat = (os.path.realpath(path), int(st.st_size),
                int(st.st_mtime_ns))
    except OSError:
        # unreadable at addressing time: key on the path alone — the
        # fleet's admission probe owns the structured reject
        stat = (os.path.realpath(path), None, None)
    return _sha(_canonical({
        'stat': list(stat),
        'format': data_ref.get('format'),
        'columns': data_ref.get('columns'),
        'options': data_ref.get('options'),
    }))


def result_key(request, ndevices=1, options=None):
    """``(digest, canonical_text)`` — the content address of this
    request's result on an ``ndevices`` sub-mesh.

    Key material is exactly ``(program_key, seed | catalog-digest,
    sorted(jit options))``; ``options`` (request-scoped overrides,
    e.g. an admission ladder rung) are merged over the ambient
    globals, both filtered to :data:`JIT_OPTIONS`."""
    from ... import _global_options
    opts = {}
    for k in JIT_OPTIONS:
        try:
            opts[k] = _global_options[k]
        except KeyError:        # pragma: no cover - trimmed globals
            pass
    for k, v in (options or {}).items():
        if k in JIT_OPTIONS:
            opts[k] = v
    if getattr(request, 'data_ref', None) is not None:
        realization = ['data', catalog_identity(request.data_ref)]
    else:
        realization = ['seed', int(request.seed)]
    text = _canonical({
        'program': [str(p) for p in request.program_key(ndevices)],
        'input': realization,
        'options': sorted((k, str(v)) for k, v in opts.items()),
    })
    return _sha(text), text


def _encode(arr):
    import numpy as np
    a = np.asarray(arr)
    return {'dtype': str(a.dtype), 'shape': list(a.shape),
            'data': a.ravel().tolist()}


def _decode(d):
    import numpy as np
    return np.array(d['data'], dtype=d['dtype']).reshape(d['shape'])


class ResultCache(object):
    """Disk-backed LRU of completed spectra, one hash-covered
    ``<digest>.res.json`` per entry under ``root``.

    Commits are atomic (tmp+rename); reads verify the content hash
    and treat any torn/corrupt entry as a miss — counted, deleted,
    recomputed, never served.  ``budget_bytes`` bounds the summed
    entry bytes (LRU eviction; None = unbounded).  Thread-safe.
    """

    _SUFFIX = '.res.json'

    def __init__(self, root, budget_bytes=None):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)
        self.budget_bytes = None if budget_bytes is None \
            else int(budget_bytes)
        self._lock = threading.Lock()
        self._index = OrderedDict()     # digest -> file bytes
        self.hits = 0
        self.misses = 0
        self.commits = 0
        self.evictions = 0
        self.corrupt = 0
        for name in sorted(os.listdir(self.root)):
            if name.endswith(self._SUFFIX):
                path = os.path.join(self.root, name)
                try:
                    self._index[name[:-len(self._SUFFIX)]] = \
                        os.path.getsize(path)
                except OSError:     # pragma: no cover - racing rm
                    pass

    def _path(self, digest):
        return os.path.join(self.root, _safe(digest) + self._SUFFIX)

    def __len__(self):
        with self._lock:
            return len(self._index)

    def get(self, digest):
        """The committed entry for ``digest`` as ``{'x', 'y',
        'nmodes', 'verified', 'key'}`` (arrays decoded), or None.
        Hash-verifies the body; a torn or tampered file counts as
        ``region.result_cache.corrupt``, is unlinked, and misses —
        the caller recomputes."""
        path = self._path(digest)
        present = True
        try:
            with open(path) as f:
                stored = json.load(f)
        except FileNotFoundError:
            stored, present = None, False
        except (OSError, ValueError):
            # the file exists but will not parse: a torn write
            stored = None
        body = (stored or {}).get('body')
        if stored is None or not isinstance(body, dict) \
                or _sha(_canonical(body)) != stored.get('sha256'):
            with self._lock:
                self._index.pop(digest, None)
                if present:
                    # torn or hash-failing files are corruption
                    # evidence, not a cold miss
                    self.corrupt += 1
                self.misses += 1
            if present:
                counter('region.result_cache.corrupt').add(1)
                try:
                    os.unlink(path)
                except OSError:     # pragma: no cover - racing rm
                    pass
            counter('region.result_cache.misses').add(1)
            return None
        with self._lock:
            self.hits += 1
            if digest in self._index:
                self._index.move_to_end(digest)
        counter('region.result_cache.hits').add(1)
        return {'x': _decode(body['x']), 'y': _decode(body['y']),
                'nmodes': _decode(body['nmodes']),
                'verified': bool(body.get('verified')),
                'key': body.get('key')}

    def put(self, digest, key_text, x, y, nmodes, verified=False):
        """Commit one completed result under ``digest`` (atomic;
        idempotent — a concurrent twin commits identical bytes).
        Evicts LRU entries past ``budget_bytes`` first."""
        body = {'key': key_text, 'x': _encode(x), 'y': _encode(y),
                'nmodes': _encode(nmodes), 'verified': bool(verified)}
        data = json.dumps({'v': 1, 'sha256': _sha(_canonical(body)),
                           'body': body}, indent=1).encode('utf-8')
        self._ensure_room(len(data))
        _atomic_bytes(self._path(digest), data)
        with self._lock:
            self._index[digest] = len(data)
            self._index.move_to_end(digest)
            resident = sum(self._index.values())
        self.commits += 1
        counter('region.result_cache.commits').add(1)
        gauge('region.result_cache.bytes').set(resident)
        return digest

    def _ensure_room(self, incoming):
        if self.budget_bytes is None:
            return
        evicted = []
        with self._lock:
            while self._index and \
                    sum(self._index.values()) + incoming \
                    > self.budget_bytes:
                digest, _ = self._index.popitem(last=False)
                evicted.append(digest)
                self.evictions += 1
        for digest in evicted:
            try:
                os.unlink(self._path(digest))
            except OSError:         # pragma: no cover - racing rm
                pass
        if evicted:
            counter('region.result_cache.evictions').add(len(evicted))

    def stats(self):
        with self._lock:
            return {'entries': len(self._index),
                    'resident_bytes': sum(self._index.values()),
                    'hits': self.hits, 'misses': self.misses,
                    'commits': self.commits,
                    'evictions': self.evictions,
                    'corrupt': self.corrupt}
