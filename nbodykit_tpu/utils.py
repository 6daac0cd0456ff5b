"""Small shared utilities (reference analog: nbodykit/utils.py).

The distributed-collective helpers of the reference (GatherArray/
ScatterArray, utils.py:128,249) are unnecessary here — global jax.Arrays
already are the gathered view — but JSON encoding of numpy-laden attrs
dicts (utils.py:381-489) and a few array helpers carry over.
"""

import json

import numpy as np
import jax


def is_mxu_backend():
    """True on MXU hardware (a TPU backend) — the shared dispatch
    predicate for kernels with a TPU-shaped and a CPU-shaped
    implementation (histogram, paint bucketing, radix ordering,
    exchange routing)."""
    return jax.default_backend() == 'tpu'


def working_dtype(dt='f8'):
    """The widest available dtype no wider than ``dt``: the 64-bit
    float/complex/int types when x64 is enabled, else their 32-bit
    counterparts — *without* the per-callsite "requested dtype float64
    ... truncated" warning that a direct ``jnp.asarray(x, jnp.float64)``
    emits on TPU (no f64 hardware). Use for 'compute in the best
    precision we have' sites."""
    import jax
    dt = np.dtype(dt)
    if dt.itemsize == 8 * (2 if dt.kind == 'c' else 1) \
            and dt.kind in 'fciu' and not jax.config.jax_enable_x64:
        return np.dtype({'f': 'f4', 'c': 'c8', 'i': 'i4',
                         'u': 'u4'}[dt.kind])
    return dt


def mesh_storage_dtype(dt='f4'):
    """Resolve a mesh-buffer STORAGE dtype token, including the
    ``'bf16'`` half-storage request that ``np.dtype`` cannot parse.

    ``'bf16'``/``'bfloat16'`` resolves to the ml_dtypes-registered
    bfloat16 (itemsize 2 — half the f4 mesh bytes; docs/PERF.md
    "Halving the bytes").  Everything else goes through
    :func:`working_dtype`, so f8 requests still demote to f4 when x64
    is off.  Storage dtype only: compute (weights, FFT butterflies,
    readout results) stays f32 — callers re-widen immediately
    (NBK701/702 contracts, docs/LINT.md)."""
    if str(dt).lower() in ('bf16', 'bfloat16'):
        import jax.numpy as jnp
        return np.dtype(jnp.bfloat16)
    return working_dtype(dt)


def is_narrow_float(dt):
    """True when ``dt`` is a sub-f32 float storage dtype (bfloat16 or
    float16) — the predicate behind every 'compute wide, store narrow'
    branch in pmesh/ops.paint."""
    dt = np.dtype(dt)
    return dt.kind in 'fV' and dt.itemsize == 2


def as_numpy(arr):
    """Fetch a jax array to host numpy.  Complex arrays move as they
    are: the attached TPU transfers complex64 both ways
    (chip_smoke.py's device phase, TPU v5 lite, PR 22)."""
    return np.asarray(jax.numpy.asarray(arr))


def to_device_complex(arr_np, sharding=None):
    """Place a host complex array on device (inverse of
    :func:`as_numpy` for complex inputs)."""
    return jax.device_put(np.ascontiguousarray(arr_np), sharding)


class JSONEncoder(json.JSONEncoder):
    """JSON encoder handling numpy scalars/arrays and complex values,
    mirroring the reference's persistence format (nbodykit/utils.py:381):
    arrays become {'__dtype__': ..., '__shape__': ..., '__data__': ...}.
    """

    def default(self, obj):
        if isinstance(obj, jax.Array):
            obj = as_numpy(obj)
        if isinstance(obj, np.generic):
            obj = obj.item()
        if isinstance(obj, complex):
            return {'__complex__': [obj.real, obj.imag]}
        if isinstance(obj, np.ndarray):
            if obj.dtype.kind == 'c':
                data = np.stack([obj.real, obj.imag], axis=-1).tolist()
            elif obj.dtype.kind == 'V':  # structured
                data = {name: self.default(np.ascontiguousarray(obj[name]))
                        for name in obj.dtype.names}
            else:
                data = obj.tolist()
            return {'__dtype__': obj.dtype.str if obj.dtype.kind != 'V'
                    else [list(x) for x in obj.dtype.descr],
                    '__shape__': list(obj.shape),
                    '__data__': data}
        if isinstance(obj, (bool, int, float, str)) or obj is None:
            return obj
        try:
            return json.JSONEncoder.default(self, obj)
        except TypeError:
            return str(obj)


def json_object_hook(value):
    """Decoder hook inverting :class:`JSONEncoder`."""
    if '__complex__' in value:
        re, im = value['__complex__']
        return complex(re, im)
    if '__dtype__' in value:
        dtype = value['__dtype__']
        shape = tuple(value['__shape__'])
        data = value['__data__']
        if isinstance(dtype, list):  # structured
            fields = []
            for f in (tuple(x) for x in dtype):
                # reference files may carry (name, type, shape) triples
                # (nbodykit/utils.py:441-448 accepts both arities)
                if len(f) == 3:
                    fields.append((str(f[0]), str(f[1]), tuple(f[2])))
                else:
                    fields.append((str(f[0]), str(f[1])))
            dtype = np.dtype(fields)
            if isinstance(data, dict):
                # our column-oriented layout
                arr = np.empty(shape, dtype=dtype)
                for name in dtype.names:
                    arr[name] = json_object_hook(data[name]) \
                        if isinstance(data[name], dict) else data[name]
                return arr
            # reference row-oriented layout: nested lists down to the
            # record level, each record a list of field values
            # (written by nbodykit/utils.py JSONEncoder, decoded at
            # utils.py:450-461) — np.array needs tuples at that level
            def _rows_to_tuples(d, depth):
                if depth > 0:
                    return [_rows_to_tuples(i, depth - 1) for i in d]
                return tuple(d)
            return np.array(_rows_to_tuples(data, len(shape)),
                            dtype=dtype)
        dt = np.dtype(str(dtype))
        if dt.kind == 'c':
            a = np.asarray(data, dtype='f8')
            return (a[..., 0] + 1j * a[..., 1]).astype(dt).reshape(shape)
        return np.asarray(data, dtype=dt).reshape(shape)
    return value


class JSONDecoder(json.JSONDecoder):
    def __init__(self, *args, **kwargs):
        kwargs['object_hook'] = json_object_hook
        json.JSONDecoder.__init__(self, *args, **kwargs)


def attrs_to_dict(attrs, prefix=''):
    """Flatten an attrs dict with a prefix (reference analog used when
    saving meta-data to file headers)."""
    return {prefix + k: v for k, v in attrs.items()}


def is_structured_array(arr):
    """True if ``arr`` is a numpy structured array (reference
    utils.py helper)."""
    return getattr(getattr(arr, 'dtype', None), 'names', None) is not None


def split_size_3d(s):
    """Split ``s`` into (a, b, c) with a*b*c == s and a <= b <= c —
    the 3-D process-grid factorization (reference utils.py:84-113),
    used here to shape subvolume domain grids."""
    a = int(s ** (1.0 / 3)) + 1
    while a > 1 and s % a:
        a -= 1
    rest = s // a
    b = int(rest ** 0.5) + 1
    while b > 1 and rest % b:
        b -= 1
    c = rest // b
    return tuple(sorted((a, b, c)))


def get_data_bounds(data, comm=None, selection=None):
    """Global (min, max) of an array along the first axis (reference
    utils.py:23). Columns are global device arrays, so this is a plain
    reduction (jit-fused; no chunking needed)."""
    import jax.numpy as jnp
    arr = jnp.asarray(data)
    if selection is not None:
        sel = jnp.asarray(selection, bool)
        if jnp.issubdtype(arr.dtype, jnp.integer):
            big = jnp.asarray(jnp.iinfo(arr.dtype).max, arr.dtype)
            small = jnp.asarray(jnp.iinfo(arr.dtype).min, arr.dtype)
        else:
            big, small = (jnp.asarray(np.inf, arr.dtype),
                          jnp.asarray(-np.inf, arr.dtype))
        mask = sel[:, None] if arr.ndim > 1 else sel
        lo = jnp.where(mask, arr, big)
        hi = jnp.where(mask, arr, small)
        return (np.asarray(lo.min(axis=0)), np.asarray(hi.max(axis=0)))
    return (np.asarray(arr.min(axis=0)), np.asarray(arr.max(axis=0)))


def GatherArray(data, comm=None, root=0):
    """Materialize a (possibly device-sharded) array on the host
    (reference utils.py:128 gathers rank-local pieces to root; columns
    here are global device arrays, so the gather is a device-to-host
    transfer — complex-safe via :func:`as_numpy`)."""
    return as_numpy(data)


def ScatterArray(data, comm=None, root=0, counts=None):
    """Distribute a host array onto the active device mesh, sharded on
    its leading axis (reference utils.py:249 scatters from root; here
    the inverse of :func:`GatherArray`)."""
    import jax.numpy as jnp
    from .parallel.runtime import CurrentMesh, shard_leading
    if counts is not None:
        raise ValueError("explicit per-device counts are not "
                         "supported: global arrays shard evenly")
    arr = jnp.asarray(data)
    mesh = CurrentMesh.get()
    if mesh is not None and len(mesh.devices) > 1:
        arr = shard_leading(mesh, arr)
    return arr


class captured_output(object):
    """Context manager capturing Python-level stdout/stderr (reference
    utils.py:513 captures C-level output via wurlitzer for its C
    extensions; the compute here is in-process XLA, so Python streams
    are the relevant ones). Yields (stdout, stderr) StringIO."""

    def __enter__(self):
        import io as _io
        import sys
        self._sys = sys
        self._old = (sys.stdout, sys.stderr)
        self.stdout = _io.StringIO()
        self.stderr = _io.StringIO()
        sys.stdout, sys.stderr = self.stdout, self.stderr
        return self.stdout, self.stderr

    def __exit__(self, *exc):
        self._sys.stdout, self._sys.stderr = self._old
        return False
