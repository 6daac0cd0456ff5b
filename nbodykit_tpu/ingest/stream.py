"""Chunked host-to-device catalog streaming with overlapped paint.

The binding cost of serving a real survey is moving its bytes onto the
device mesh.  This module makes that cost a PIPELINE, not a staging
area:

- the io reader delivers bounded column chunks
  (:meth:`~nbodykit_tpu.io.base.FileType.read_chunks` — this process's
  row range split into ``chunk_rows`` windows), so the host never
  materializes the catalog;
- each chunk is padded to the device count, placed under its
  partition-rule spec (:mod:`.rules`) with an async ``device_put``,
  and the PREVIOUS chunk is painted while the transfer flies — the
  double buffer that hides H2D behind the deposit
  (``ingest_overlap`` option; the serialized transfer-then-paint path
  stays selectable for A/B measurement);
- chunk boundaries are checkpointable
  (:class:`~nbodykit_tpu.resilience.CheckpointStore`): a killed ingest
  resumes by re-transferring — never re-PAINTING — the completed
  chunks, validated against the checkpointed per-chunk digests;
- the per-chunk sha256s fold into the content address that keys the
  on-device :class:`~nbodykit_tpu.ingest.cache.CatalogCache`, so the
  next request against the same survey skips the file and the wire
  entirely and goes straight to paint.

Bit-identity contract: the painted mesh is defined by the CHUNKED
deposit order (chunk 0's scatter, then chunk 1's scatter merged via
``paint(out=...)``, ...).  The cold streamed path, the cache-hit path
(:func:`paint_cached` replays the stored chunks) and a whole-resident
catalog painted through :func:`paint_chunks` at the same ``chunk_rows``
all execute the identical op sequence on identical values — the tests
assert equality to the bit.

Observability: ``ingest.stream`` / ``ingest.h2d`` /
``ingest.paint_cached`` spans (the ``ingest`` critical-path phase in
``diagnostics/analyze.py``), ``ingest.rows`` / ``.bytes`` / ``.chunks``
/ ``.resumed_chunks`` counters, and an ``ingest.host_bytes`` gauge
whose high-water mark is the proof the host stayed bounded.
"""

import hashlib
import os
import time

import numpy as np

from ..diagnostics import counter, gauge, span
from ..io.base import FileType
from .cache import CatalogEntry, fold_digest, layout_token
from .rules import (DEFAULT_RULES, make_shard_and_gather_fns,
                    match_partition_rules, resolve_partition_spec)

# formats a serialized data_ref may name (FileStack composes
# programmatically and is not addressable by one path + format token)
FORMATS = {
    'binary': 'BinaryFile',
    'csv': 'CSVFile',
    'bigfile': 'BigFile',
    'hdf': 'HDFFile',
    'fits': 'FITSFile',
    'tpm': 'TPMBinaryFile',
    'gadget1': 'Gadget1File',
}

DEFAULT_COLUMNS = {'Position': 'Position'}


class IngestError(Exception):
    """A structured ingestion failure: ``code`` is machine-readable
    (``unreadable_data_ref`` / ``unknown_format`` / ``empty_catalog``
    / ``checkpoint_mismatch``), ``detail`` is for humans."""

    def __init__(self, code, detail, **extra):
        super(IngestError, self).__init__('%s: %s' % (code, detail))
        self.code = code
        self.detail = detail
        self.extra = dict(extra)

    def to_reason(self):
        out = {'code': self.code, 'detail': self.detail}
        out.update(self.extra)
        return out


class DataRef(object):
    """A serializable pointer to an on-disk catalog: path + format +
    the logical->file column map (``{'Position': 'pos', 'Weight':
    'Mass'}``) + reader keyword options.  This is what an
    :class:`~nbodykit_tpu.serve.AnalysisRequest` carries instead of a
    ``seed`` — a few hundred bytes however large the survey."""

    __slots__ = ('path', 'format', 'columns', 'options')

    def __init__(self, path, format, columns=None, options=None):
        self.path = str(path)
        self.format = str(format)
        if self.format not in FORMATS:
            raise IngestError(
                'unknown_format',
                'format %r is not one of %s'
                % (self.format, sorted(FORMATS)), path=self.path)
        self.columns = dict(columns or DEFAULT_COLUMNS)
        if 'Position' not in self.columns:
            raise IngestError(
                'unknown_format',
                "column map must bind 'Position'", path=self.path)
        self.options = dict(options or {})

    def open(self):
        """The reader instance, or a structured
        ``unreadable_data_ref`` failure — never a bare OSError."""
        from .. import io as nbio
        cls = getattr(nbio, FORMATS[self.format])
        try:
            f = cls(self.path, **self.options)
        except Exception as e:
            raise IngestError(
                'unreadable_data_ref',
                '%s: %s' % (type(e).__name__, str(e)[:300]),
                path=self.path, format=self.format)
        missing = [c for c in self.columns.values()
                   if c not in f.dtype.names]
        if missing:
            raise IngestError(
                'unreadable_data_ref',
                'file lacks mapped column(s) %s (has %s)'
                % (missing, list(f.dtype.names)), path=self.path,
                format=self.format)
        return f

    def fingerprint(self, layout):
        """The stat-cheap cache front door: realpath + size + mtime_ns
        + column map + partition layout.  A rewritten file changes
        size/mtime and misses; content identity is re-established by
        the digest computed during the cold ingest."""
        try:
            st = os.stat(self.path)
        except OSError as e:
            raise IngestError('unreadable_data_ref', str(e),
                              path=self.path)
        return (os.path.realpath(self.path), int(st.st_size),
                int(st.st_mtime_ns),
                tuple(sorted(self.columns.items())),
                hashlib.sha256(layout.encode()).hexdigest())

    def to_dict(self):
        return {'path': self.path, 'format': self.format,
                'columns': dict(self.columns),
                'options': dict(self.options)}

    @classmethod
    def from_dict(cls, d):
        if isinstance(d, DataRef):
            return d
        d = dict(d)
        return cls(d['path'], d['format'], d.get('columns'),
                   d.get('options'))


class ArraySource(FileType):
    """An in-memory FileType over named host arrays — the whole-load
    reference the bit-identity tests stream against.  Same
    ``read``/``read_chunks`` contract as every on-disk reader."""

    def __init__(self, columns):
        names = list(columns)
        arrays = {k: np.asarray(v) for k, v in columns.items()}
        n = {len(a) for a in arrays.values()}
        if len(n) != 1:
            raise ValueError('columns disagree on length: %s'
                             % sorted(n))
        self.size = n.pop()
        self.dtype = np.dtype([(k, arrays[k].dtype,
                                arrays[k].shape[1:]) for k in names])
        self._data = arrays

    def read(self, columns, start, stop, step=1):
        out = self._empty(columns, len(range(start, stop, step)))
        for c in columns:
            out[c] = self._data[c][start:stop:step]
        return out


def _open_source(ref):
    """(reader, logical->file column map) for a DataRef, a dict form
    of one, or a bare FileType (in-memory trials)."""
    if isinstance(ref, FileType):
        cols = {'Position': 'Position'}
        for c in ('Weight', 'Velocity', 'Selection'):
            if c in (ref.dtype.names or ()):
                cols[c] = c
        return ref, cols
    ref = DataRef.from_dict(ref)
    return ref.open(), dict(ref.columns)


def probe_ref(ref):
    """Admission's cheap look: row count and ingested bytes-per-row
    for the mapped columns (what throughput and memory are priced
    against).  Raises :class:`IngestError` on an unreadable ref."""
    f, cols = _open_source(ref)
    row_bytes = sum(int(f.dtype[c].itemsize) for c in cols.values())
    return {'nrows': int(f.size), 'row_bytes': row_bytes,
            'total_bytes': int(f.size) * row_bytes,
            'columns': cols}


def resolve_chunk_rows(chunk_rows=None):
    """The streaming window: an explicit value wins, then the
    ``ingest_chunk_rows`` option."""
    if chunk_rows is None:
        from .. import _global_options
        chunk_rows = _global_options['ingest_chunk_rows']
    return max(int(chunk_rows), 1)


def _mesh_of(pm):
    return getattr(pm, 'comm', None)


def _catalog_layout(f, cols, chunk_rows, mesh, rules=DEFAULT_RULES):
    """(layout token, shard fns) for the mapped columns on the live
    mesh — the rule tree resolved once per ingest."""
    from ..parallel.runtime import mesh_size
    logical = {'Position': 2}
    if 'Weight' in cols:
        logical['Weight'] = 1
    if 'Velocity' in cols:
        logical['Velocity'] = 2
    if 'Selection' in cols:
        logical['Selection'] = 1
    templates = match_partition_rules(rules, logical)
    specs = {k: resolve_partition_spec(t, mesh)
             for k, t in templates.items()}
    shard_fns, _ = make_shard_and_gather_fns(specs, mesh)
    layout = layout_token(
        sorted(logical), [f.dtype[cols[c]].base for c in
                          sorted(logical) if c in cols],
        chunk_rows, mesh_size(mesh), templates)
    return layout, shard_fns


class _HostMeter(object):
    """High-water accounting of live host chunk bytes — the evidence
    the catalog is never host-resident.  The double buffer holds at
    most two chunks."""

    def __init__(self):
        import threading
        self._lock = threading.Lock()
        self.live = 0
        self.peak = 0

    def add(self, nbytes):
        with self._lock:
            self.live += int(nbytes)
            self.peak = max(self.peak, self.live)
            live = self.live
        gauge('ingest.host_bytes').set(live)

    def drop(self, nbytes):
        with self._lock:
            self.live -= int(nbytes)
            live = self.live
        gauge('ingest.host_bytes').set(live)


def _put_chunk(chunk, cols, shard_fns, ndev, pos_dtype):
    """Pad a host chunk to the device count and place it under the
    partition specs.  Padding slots carry mass 0 — inert in the
    deposit (pmesh.paint's documented contract).

    A mapped ``Selection`` column multiplies into the effective
    deposit mass on the host (FKP-style: a 0/1 mask or a completeness
    weight scales each particle's contribution before it ever reaches
    the device), so selection never forces the whole-resident catalog
    path.  A mapped ``Velocity`` column is sharded alongside Position
    and rides the chunk as a 4th element — resident for RSD-style
    consumers, invisible to :func:`paint_chunks`."""
    import jax.numpy as jnp
    n = len(chunk)
    pad = (-n) % max(ndev, 1)
    pos = np.ascontiguousarray(chunk[cols['Position']], dtype=pos_dtype)
    if 'Weight' in cols:
        mass = np.ascontiguousarray(chunk[cols['Weight']],
                                    dtype=pos_dtype)
    else:
        mass = np.ones(n, dtype=pos_dtype)
    if 'Selection' in cols:
        mass = mass * np.ascontiguousarray(
            chunk[cols['Selection']]).astype(pos_dtype)
    vel = None
    if 'Velocity' in cols:
        vel = np.ascontiguousarray(chunk[cols['Velocity']],
                                   dtype=pos_dtype)
    if pad:
        pos = np.concatenate(
            [pos, np.zeros((pad, 3), dtype=pos_dtype)])
        mass = np.concatenate([mass, np.zeros(pad, dtype=pos_dtype)])
        if vel is not None:
            vel = np.concatenate(
                [vel, np.zeros((pad, 3), dtype=pos_dtype)])
    nbytes = pos.nbytes + mass.nbytes \
        + (vel.nbytes if vel is not None else 0)
    with span('ingest.h2d', rows=n, bytes=nbytes):
        pos_dev = shard_fns['Position'](pos)
        mass_dev = shard_fns.get('Weight', jnp.asarray)(mass)
        if vel is None:
            return pos_dev, mass_dev, n
        vel_dev = shard_fns.get('Velocity',
                                shard_fns['Position'])(vel)
    return pos_dev, mass_dev, n, vel_dev


def _chunk_digest(chunk, cols):
    h = hashlib.sha256()
    for c in sorted(cols):
        h.update(np.ascontiguousarray(chunk[cols[c]]).tobytes())
    return h.hexdigest()


def paint_chunks(pm, chunks, resampler=None, out=None):
    """The canonical chunked deposit: paint each (pos, mass) chunk
    into the accumulator in order.  EVERY path to a painted ingest
    mesh goes through this op sequence — that is the bit-identity
    contract.  Chunks are ``(pos, mass, n)`` or ``(pos, mass, n,
    vel)`` — a resident Velocity column rides along untouched."""
    for chunk in chunks:
        out = pm.paint(chunk[0], chunk[1], resampler=resampler,
                       out=out)
    return out


def paint_cached(pm, entry, resampler=None):
    """The cache-hit path: replay the stored chunks straight into
    paint — no file, no wire."""
    with span('ingest.paint_cached', chunks=len(entry.chunks),
              rows=entry.nrows):
        out = paint_chunks(pm, entry.chunks, resampler=resampler)
    return out


def host_chunks(source, cols, chunk_rows, rank=0, nranks=1):
    """This worker's host chunk stream via the uniform reader
    interface (:meth:`FileType.read_chunks`)."""
    file_cols = [cols[c] for c in sorted(cols)]
    return source.read_chunks(file_cols, chunk_rows, rank=rank,
                              nranks=nranks)


def ingest_catalog(ref, pm, resampler=None, chunk_rows=None,
                   overlap=None, cache=None, fits=None,
                   checkpoint=None, ckpt_key=None, ckpt_every=0,
                   rules=DEFAULT_RULES):
    """File -> painted mesh, streaming.  Returns
    ``(field, entry, stats)``.

    On a cache hit the stored chunks replay straight into paint
    (``stats['cache_hit']`` True, zero bytes read).  Cold, the chunk
    loop double-buffers: ``device_put`` of chunk *i+1* is dispatched
    before the paint of chunk *i* is awaited (``overlap``; default the
    ``ingest_overlap`` option), per-chunk digests fold into the
    content address, and — with a ``checkpoint`` store — the painted
    accumulator is saved every ``ckpt_every`` chunk boundaries so a
    kill resumes by re-transferring, never re-painting, finished
    chunks.  ``fits(resident_bytes)`` is the memory_plan eviction
    predicate forwarded to the cache.
    """
    import jax
    import jax.numpy as jnp

    from .. import _global_options
    from ..parallel.runtime import mesh_size, process_count, \
        process_index
    from ..resilience.faults import fault_point

    t0 = time.perf_counter()
    f, cols = _open_source(ref)
    if f.size == 0:
        raise IngestError('empty_catalog', 'catalog has zero rows',
                          path=getattr(ref, 'path', '<memory>'))
    mesh = _mesh_of(pm)
    ndev = mesh_size(mesh)
    nproc = max(ndev, 1)
    chunk_rows = resolve_chunk_rows(chunk_rows)
    if overlap is None:
        overlap = bool(_global_options['ingest_overlap'])
    layout, shard_fns = _catalog_layout(f, cols, chunk_rows, mesh,
                                        rules=rules)
    pos_dtype = np.dtype('f8') \
        if f.dtype[cols['Position']].base == np.dtype('f8') \
        else np.dtype('f4')

    fingerprint = None
    if isinstance(ref, (DataRef, dict)):
        fingerprint = DataRef.from_dict(ref).fingerprint(layout)
    elif cache is not None:
        fingerprint = ('memory', id(f), int(f.size),
                       hashlib.sha256(layout.encode()).hexdigest())

    stats = {'rows': 0, 'bytes': 0, 'chunks': 0,
             'chunk_rows': chunk_rows, 'overlap': bool(overlap),
             'cache_hit': False, 'resumed_chunks': 0,
             'host_peak_bytes': 0}
    if cache is not None:
        entry = cache.lookup(fingerprint)
        if entry is not None:
            field = paint_cached(pm, entry, resampler=resampler)
            stats.update(cache_hit=True, rows=entry.nrows,
                         chunks=len(entry.chunks),
                         chunk_rows=entry.chunk_rows,
                         seconds=time.perf_counter() - t0)
            return field, entry, stats

    # ---- cold path: stream, hash, (optionally) resume -------------------
    key = ckpt_key or ('ingest-%s' % (
        hashlib.sha256(layout.encode()).hexdigest()[:12]
        if fingerprint is None else
        hashlib.sha256(repr(fingerprint).encode()).hexdigest()[:12]))
    layout_id = hashlib.sha256(layout.encode()).hexdigest()
    painted = 0
    digests = []
    acc = None
    if checkpoint is not None:
        got = checkpoint.load(key)
        if got is not None:
            state, arrays = got
            if state.get('layout') == layout_id \
                    and state.get('chunk_rows') == chunk_rows:
                painted = int(state['painted'])
                digests = list(state['digests'])
                host_field = np.asarray(arrays['field'],
                                        dtype='f4').astype(
                    np.dtype('f4'))
                fld = jnp.asarray(host_field, pm.dtype)
                acc = jax.device_put(fld, pm.sharding()) \
                    if mesh is not None else fld
                stats['resumed_chunks'] = painted
                counter('ingest.resumed_chunks').add(painted)

    meter = _HostMeter()
    rank, nranks = process_index(), process_count()
    pending = None          # (pos_dev, mass_dev, nvalid, host_bytes)
    stored = []
    i = 0
    with span('ingest.stream', rows=int(f.size),
              chunk_rows=chunk_rows, overlap=bool(overlap),
              ndevices=nproc):
        for chunk in host_chunks(f, cols, chunk_rows, rank=rank,
                                 nranks=nranks):
            hb = int(chunk.nbytes)
            meter.add(hb)
            d = _chunk_digest(chunk, cols)
            if i < painted:
                # resumed: the paint is checkpointed; re-transfer for
                # the cache and VERIFY the bytes are the same catalog
                if d != digests[i]:
                    raise IngestError(
                        'checkpoint_mismatch',
                        'chunk %d bytes changed since the checkpoint'
                        % i, chunk=i)
            else:
                digests.append(d)
            dev = _put_chunk(chunk, cols, shard_fns, nproc, pos_dtype)
            meter.drop(hb)   # device owns the bytes now
            del chunk
            counter('ingest.chunks').add(1)
            counter('ingest.rows').add(dev[2])
            counter('ingest.bytes').add(hb)
            stats['rows'] += dev[2]
            stats['bytes'] += hb
            stats['chunks'] += 1
            if not overlap:
                jax.block_until_ready(dev[:2])
            if pending is not None:
                pi = i - 1
                if pi >= painted:
                    acc = paint_chunks(pm, [pending[:-1]],
                                       resampler=resampler, out=acc)
                    if not overlap:
                        jax.block_until_ready(acc)
                    acc, painted = _maybe_ckpt(
                        checkpoint, key, layout_id, chunk_rows,
                        pi + 1, digests, acc, ckpt_every, pm, mesh,
                        painted)
                stored.append(pending[:-1])
                fault_point('ingest.chunk')
            pending = dev + (hb,)
            i += 1
        if pending is not None:
            if i - 1 >= painted:
                acc = paint_chunks(pm, [pending[:-1]],
                                   resampler=resampler, out=acc)
            stored.append(pending[:-1])
            fault_point('ingest.chunk')
        jax.block_until_ready(acc)
    if acc is None:
        raise IngestError('empty_catalog',
                          'no rows on this worker rank',
                          path=getattr(ref, 'path', '<memory>'))
    if checkpoint is not None:
        checkpoint.delete(key)

    digest = fold_digest(layout, digests)
    entry = CatalogEntry(digest, layout, stored, stats['rows'],
                         chunk_rows)
    if cache is not None:
        cache.put(fingerprint, entry, fits=fits)
    stats['host_peak_bytes'] = meter.peak
    stats['digest'] = digest
    stats['seconds'] = time.perf_counter() - t0
    return acc, entry, stats


def _maybe_ckpt(checkpoint, key, layout_id, chunk_rows, painted_now,
                digests, acc, ckpt_every, pm, mesh, painted_before):
    """Save the accumulator at a chunk boundary (and return it
    re-placed, since np.asarray gathered it)."""
    if not checkpoint or not ckpt_every \
            or painted_now % ckpt_every or painted_now <= painted_before:
        return acc, painted_before
    import jax
    import jax.numpy as jnp
    host = np.asarray(acc, dtype='f4')
    checkpoint.save(key, {'layout': layout_id,
                          'chunk_rows': int(chunk_rows),
                          'painted': int(painted_now),
                          'digests': list(digests[:painted_now])},
                    arrays={'field': host})
    fld = jnp.asarray(host, pm.dtype)
    acc = jax.device_put(fld, pm.sharding()) if mesh is not None \
        else fld
    return acc, painted_before
