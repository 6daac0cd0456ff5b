"""Atomic, content-hashed checkpoint/restore of pipeline state.

Without it nothing of a run survives a mid-run fault: the device is
lost mid-timing and the partial measurement goes with the process.  This module is the durable
half of the resilience story (the reference nbodykit inherits
restartability from MPI batch schedulers, SURVEY §L0 — here it has to
be built in): small host-side pipeline state — staged jit'd programs'
host inputs, partial bench reps, partial lowmem-FFT passes, FFTPower
binned accumulators — is written to disk after every unit of progress
so a relaunch resumes instead of restarting.

Discipline (same as :mod:`..diagnostics.report`):

- **atomic**: every file is written to a tmp sibling and committed
  with one ``os.replace`` — a SIGKILL mid-save leaves the *previous*
  checkpoint intact, never a torn one.  Array payloads are committed
  before the metadata file, so the metadata rename is the single
  commit point.
- **content-hashed**: the metadata records a sha256 over the
  canonical JSON state and over each array's raw bytes; :meth:`load`
  re-verifies everything and returns ``None`` (plus a
  ``resilience.checkpoint.corrupt`` counter bump) on any mismatch —
  a half-written or bit-rotted checkpoint is detected, not replayed.

Checkpoints are named by a caller-chosen key; the bench keys on the
config metric (``bench.fftpower_wallclock_...``), so concurrent
workers (the TPU + forced-CPU pair) never collide.  Fault-injection
points (:mod:`.faults`) fire around the commit so the atomicity claim
is testable: ``ckpt.write.<key>`` before the metadata rename,
``ckpt.<key>`` after it.
"""

import hashlib
import json
import os
import time

from ..diagnostics import counter, span

_META_SUFFIX = '.ckpt.json'


def _safe(name):
    """Filesystem-safe checkpoint/array name (keys carry metric names
    with ``+`` etc.)."""
    return ''.join(c if c.isalnum() or c in '._-' else '_'
                   for c in str(name))


def _canonical(obj):
    """Canonical JSON text of a state payload: the hashed form and the
    stored form are byte-identical because both pass through one
    serialization with sorted keys."""
    return json.dumps(obj, sort_keys=True, separators=(',', ':'),
                      default=str)


def _sha(text):
    if isinstance(text, str):
        text = text.encode('utf-8')
    return hashlib.sha256(text).hexdigest()


def _atomic_bytes(path, data):
    tmp = '%s.tmp.%d' % (path, os.getpid())
    with open(tmp, 'wb') as f:
        f.write(data)
        f.flush()
        try:
            os.fsync(f.fileno())
        except OSError:         # pragma: no cover - exotic fs
            pass
    os.replace(tmp, path)


class CheckpointStore(object):
    """Checkpoints under one directory, one ``<key>.ckpt.json`` (plus
    optional ``<key>.<name>.npy`` array payloads) per key."""

    def __init__(self, root):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)

    # -- paths ------------------------------------------------------------

    def _meta_path(self, key):
        return os.path.join(self.root, _safe(key) + _META_SUFFIX)

    def _array_path(self, key, name):
        return os.path.join(self.root,
                            '%s.%s.npy' % (_safe(key), _safe(name)))

    def keys(self):
        """Keys with a committed metadata file, sorted."""
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        return sorted(f[:-len(_META_SUFFIX)] for f in names
                      if f.endswith(_META_SUFFIX))

    # -- save / load ------------------------------------------------------

    def save(self, key, state, arrays=None):
        """Commit ``state`` (a JSON-serializable dict) plus optional
        named numpy ``arrays`` under ``key``.  Returns the metadata
        path.  The metadata rename is the commit point; a death at any
        earlier moment leaves the previous checkpoint loadable."""
        from .faults import fault_point
        with span('ckpt.save', key=str(key)):
            # tuples etc. must hash the way they re-load: round-trip
            # the state through JSON before hashing
            state = json.loads(_canonical(state))
            arr_meta = {}
            if arrays:
                import numpy as np
                for name, arr in sorted(arrays.items()):
                    data = np.ascontiguousarray(np.asarray(arr))
                    apath = self._array_path(key, name)
                    tmp = '%s.tmp.%d' % (apath, os.getpid())
                    with open(tmp, 'wb') as f:
                        np.save(f, data)
                        f.flush()
                        try:
                            os.fsync(f.fileno())
                        except OSError:  # pragma: no cover
                            pass
                    os.replace(tmp, apath)
                    arr_meta[str(name)] = {
                        'file': os.path.basename(apath),
                        'sha256': _sha(data.tobytes()),
                        'dtype': str(data.dtype),
                        'shape': list(data.shape),
                    }
            body = _canonical({'state': state, 'arrays': arr_meta})
            meta = {
                'v': 1, 'key': str(key),
                'saved_at': round(time.time(), 6),
                'sha256': _sha(body),
                'state': state, 'arrays': arr_meta,
            }
            path = self._meta_path(key)
            # the pre-commit fault point: a kill here proves the
            # previous checkpoint survives a death mid-save
            fault_point('ckpt.write.%s' % key)
            _atomic_bytes(path, json.dumps(meta, indent=1,
                                           default=str).encode('utf-8'))
            counter('resilience.checkpoint.saves').add(1)
            fault_point('ckpt.%s' % key)
            return path

    def load(self, key):
        """``(state, arrays)`` for ``key``, or ``None`` when absent or
        failing any content-hash check (corrupt checkpoints are
        counted, never trusted)."""
        path = self._meta_path(key)
        try:
            with open(path) as f:
                meta = json.load(f)
        except (OSError, ValueError):
            if os.path.exists(path):
                counter('resilience.checkpoint.corrupt').add(1)
            return None
        body = _canonical({'state': meta.get('state'),
                           'arrays': meta.get('arrays', {})})
        if _sha(body) != meta.get('sha256'):
            counter('resilience.checkpoint.corrupt').add(1)
            return None
        arrays = {}
        for name, am in (meta.get('arrays') or {}).items():
            import numpy as np
            apath = os.path.join(self.root, am.get('file', ''))
            try:
                data = np.load(apath)
            except (OSError, ValueError):
                counter('resilience.checkpoint.corrupt').add(1)
                return None
            if _sha(np.ascontiguousarray(data).tobytes()) \
                    != am.get('sha256'):
                counter('resilience.checkpoint.corrupt').add(1)
                return None
            arrays[name] = data
        counter('resilience.checkpoint.restores').add(1)
        return meta.get('state'), arrays

    def delete(self, key):
        """Remove ``key``'s metadata + array payloads (metadata first,
        so a death mid-delete leaves only harmless orphan arrays)."""
        meta = self._meta_path(key)
        names = []
        try:
            with open(meta) as f:
                names = [am.get('file') for am in
                         (json.load(f).get('arrays') or {}).values()]
        except (OSError, ValueError):
            pass
        for path in [meta] + [os.path.join(self.root, n)
                              for n in names if n]:
            try:
                os.remove(path)
            except OSError:
                pass

    # -- retention --------------------------------------------------------

    def orphan_tmp(self, max_age_s=0.0, now=None):
        """Paths of ``*.tmp.<pid>`` siblings at least ``max_age_s`` old
        — debris a kill mid-commit leaves behind (the rename never
        happened, so they are invisible to load; they only waste
        disk)."""
        now = time.time() if now is None else now
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        for f in names:
            if '.tmp.' not in f:
                continue
            path = os.path.join(self.root, f)
            try:
                age = now - os.path.getmtime(path)
            except OSError:
                continue
            if age >= max_age_s:
                out.append(path)
        return sorted(out)

    def gc_tmp(self, max_age_s=3600.0, now=None):
        """Remove stale tmp orphans; returns the count removed.  The
        default age spares a concurrent writer's in-flight tmp."""
        n = 0
        for path in self.orphan_tmp(max_age_s=max_age_s, now=now):
            try:
                os.remove(path)
                n += 1
            except OSError:
                pass
        return n

    # -- freshness --------------------------------------------------------

    def saved_at(self, key):
        """Epoch seconds of ``key``'s commit, or None."""
        try:
            with open(self._meta_path(key)) as f:
                return float(json.load(f).get('saved_at'))
        except (OSError, ValueError, TypeError):
            return None

    def age_s(self, key, now=None):
        """Seconds since ``key`` was committed, or None."""
        ts = self.saved_at(key)
        if ts is None:
            return None
        return (time.time() if now is None else now) - ts

    def oldest_age_s(self, now=None):
        """Age of the oldest committed checkpoint, or None when the
        store is empty — the doctor's last-checkpoint-age signal."""
        ages = [self.age_s(k, now=now) for k in self.keys()]
        ages = [a for a in ages if a is not None]
        return max(ages) if ages else None
