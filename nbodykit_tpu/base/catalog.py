"""CatalogSource: the distributed particle-table abstraction.

Reference: ``nbodykit/base/catalog.py:168,875``. A catalog is a table of
particle columns with metadata; the reference implements it as rank-local
dask arrays over MPI. Here a column is a *global* jax.Array (sharded over
the device mesh on its leading axis when one is active), so collective
sizes/slices/sorts are ordinary jnp ops and XLA inserts the collectives.

Laziness: the reference's dask-lazy columns become (a) hardcolumns
declared with the ``@column`` decorator — computed on first access and
cached — and (b) whatever jit fusion downstream consumers apply. The
``attrs`` reproducibility convention carries over verbatim.
"""

import logging

import numpy as np
import jax
import jax.numpy as jnp

from ..parallel.runtime import CurrentMesh, shard_leading, mesh_size
from ..utils import as_numpy


def column(name=None):
    """Decorator declaring a hardcolumn on a CatalogSource subclass
    (reference: base/catalog.py:97). The method computes the column on
    first access; the result is cached."""
    def wrapper(func):
        func.column_name = name or func.__name__
        return func
    if callable(name):
        func, name = name, name.__name__
        return wrapper(func)
    return wrapper


def find_columns(cls):
    """Collect hardcolumn methods from a class hierarchy (reference's
    ColumnFinder metaclass, base/catalog.py:127)."""
    hard = {}
    for klass in reversed(cls.__mro__):
        for value in vars(klass).values():
            if callable(value) and hasattr(value, 'column_name'):
                hard[value.column_name] = value
    return hard


class CatalogSourceBase(object):
    """Dict-like base: column get/set, attrs, views, mesh conversion."""

    logger = logging.getLogger('CatalogSource')

    def __init__(self, comm=None):
        self.comm = CurrentMesh.resolve(comm)
        if not hasattr(self, 'attrs'):
            self.attrs = {}
        self._columns = {}     # explicitly set columns
        self._cache = {}       # evaluated hardcolumns

    # -- column access ----------------------------------------------------

    @property
    def hardcolumns(self):
        return sorted(find_columns(type(self)))

    @property
    def columns(self):
        return sorted(set(self.hardcolumns) | set(self._columns))

    def __contains__(self, col):
        return col in self.columns

    def __getitem__(self, sel):
        if isinstance(sel, str):
            if sel in self._columns:
                return self._columns[sel]
            if sel in self._cache:
                return self._cache[sel]
            hard = find_columns(type(self))
            if sel in hard:
                val = hard[sel](self)
                val = self._promote(val)
                self._cache[sel] = val
                return val
            raise KeyError("column '%s' not found; available: %s"
                           % (sel, self.columns))
        # boolean-mask or slice selection -> new catalog view
        return self._select(sel)

    def __setitem__(self, col, value):
        value = self._promote(value, col=col)
        self._columns[col] = value

    def __delitem__(self, col):
        if col in self._columns:
            del self._columns[col]
        elif col in self.hardcolumns:
            raise ValueError("cannot delete hardcolumn '%s'" % col)
        else:
            raise KeyError(col)

    def _promote(self, value, col=None):
        """Coerce a column value to a global device array of length
        self.size (scalars broadcast)."""
        size = len(self)
        if np.isscalar(value):
            value = jnp.full((size,), value)
        else:
            value = jnp.asarray(value)
        if value.shape[0] != size:
            raise ValueError(
                "size mismatch setting column%s: got %d, catalog has %d"
                % ('' if col is None else " '%s'" % col, value.shape[0],
                   size))
        nproc = mesh_size(self.comm) if self.comm is not None else 1
        if nproc > 1 and size % nproc == 0:
            # evenly shard over the device mesh; ragged sizes stay on the
            # default device until a paint/readout exchange distributes
            # them (exchange_by_dest pads internally)
            value = shard_leading(self.comm, value)
        return value

    def compute(self, *args):
        """Materialize columns (the reference's dask barrier,
        base/catalog.py:705); arrays are already concrete, so this just
        resolves names."""
        out = [self[a] if isinstance(a, str) else a for a in args]
        return out[0] if len(out) == 1 else out

    def get_hardcolumn(self, col):
        return self[col]

    # -- views / selection -------------------------------------------------

    def _select(self, sel):
        """Boolean-mask / slice selection returning an ArrayCatalog-like
        view with all columns materialized and sliced."""
        from ..source.catalog.array import ArrayCatalog
        if isinstance(sel, (slice, np.ndarray, jnp.ndarray, list)):
            data = {}
            for col in self.columns:
                data[col] = self[col][sel]
            cat = ArrayCatalog(data, comm=self.comm, **self.attrs)
            return cat
        raise KeyError("invalid catalog selection %r" % (sel,))

    def view(self, type=None):
        """A re-typed view sharing column *data* (reference
        base/catalog.py:727). The column dicts are shallow-copied so
        adding derived columns on the view does not pollute the base."""
        type = type or self.__class__
        obj = object.__new__(type)
        obj.__dict__.update(self.__dict__)
        obj._columns = dict(self._columns)
        obj._cache = dict(self._cache)
        obj._size = len(self)
        obj.base = self
        return obj

    def __finalize__(self, other):
        self.attrs.update(getattr(other, 'attrs', {}))
        return self

    @staticmethod
    def make_column(array):
        """Convert an array-like to a column array (reference
        base/catalog.py:193 returns a dask array; columns here are
        global device arrays)."""
        return jnp.asarray(array)

    @staticmethod
    def create_instance(cls, comm=None):
        """A bare, empty instance of ``cls`` with only the base state
        initialized (reference base/catalog.py:223)."""
        obj = object.__new__(cls)
        CatalogSourceBase.__init__(obj, comm)
        return obj

    def copy(self):
        """A shallow copy holding references to all current columns,
        with a decoupled ``attrs`` (reference base/catalog.py:474)."""
        toret = CatalogSourceBase.create_instance(self.__class__,
                                                  comm=self.comm)
        toret._size = len(self)
        toret.__finalize__(self)
        for col in self.columns:
            toret[col] = self[col]
        toret.attrs = dict(self.attrs)
        return toret

    def persist(self, columns=None):
        """An ArrayCatalog with the selected columns materialized
        (reference base/catalog.py:1078; columns here are already
        device-resident, so this just snapshots them)."""
        from ..source.catalog.array import ArrayCatalog
        cols = {key: self[key] for key in (columns or self.columns)}
        c = ArrayCatalog(cols, comm=self.comm)
        c.attrs.update(self.attrs)
        return c

    def to_subvolumes(self, domain=None, position='Position',
                      columns=None):
        """Spatially domain-decomposed copy of this catalog (reference
        base/catalog.py:754 -> SubVolumesCatalog)."""
        from ..source.catalog.subvolumes import SubVolumesCatalog
        return SubVolumesCatalog(self, domain=domain,
                                 position=position, columns=columns)

    # -- conversion --------------------------------------------------------

    def to_mesh(self, Nmesh=None, BoxSize=None, dtype=None, interlaced=False,
                compensated=False, resampler='cic', position='Position',
                weight='Weight', value='Value', selection='Selection'):
        """Make a CatalogMesh that paints this catalog (reference
        base/catalog.py:787-873)."""
        from ..source.mesh.catalog import CatalogMesh
        from .. import _global_options

        if Nmesh is None:
            Nmesh = self.attrs.get('Nmesh', None)
            if Nmesh is None:
                raise ValueError("cannot infer Nmesh; pass it to to_mesh "
                                 "or set attrs['Nmesh']")
        if BoxSize is None:
            BoxSize = self.attrs.get('BoxSize', None)
            if BoxSize is None:
                raise ValueError("cannot infer BoxSize; pass it to "
                                 "to_mesh or set attrs['BoxSize']")
        if dtype is None:
            dtype = _global_options['mesh_dtype']
        return CatalogMesh(self, Nmesh=Nmesh, BoxSize=BoxSize, dtype=dtype,
                           interlaced=interlaced, compensated=compensated,
                           resampler=resampler, position=position,
                           weight=weight, value=value, selection=selection)

    def save(self, output, columns=None, dataset=None, datasets=None,
             header='Header'):
        """Persist columns + attrs (reference base/catalog.py:562 writes
        bigfile; same format here via io.bigfile)."""
        from ..io.bigfile import BigFileWriter
        if columns is None:
            columns = self.columns
        if datasets is None:
            datasets = columns
        with BigFileWriter(output, create=True) as ff:
            ff.write_attrs(header, self.attrs)
            for col, ds in zip(columns, datasets):
                ff.write(ds, as_numpy(self[col]))

    def read(self, columns):
        return [self[col] for col in columns]


class CatalogSource(CatalogSourceBase):
    """A catalog with a definite global size and the default
    Selection/Weight/Value columns (reference base/catalog.py:875)."""

    def __init__(self, size, comm=None):
        CatalogSourceBase.__init__(self, comm=comm)
        self._size = int(size)

    def __len__(self):
        return self._size

    @property
    def size(self):
        return self._size

    @property
    def csize(self):
        """Collective size == global size (columns are global arrays)."""
        return self._size

    def __repr__(self):
        return "%s(size=%d)" % (self.__class__.__name__, self._size)

    # default columns (reference base/catalog.py:1166-1216)

    @column
    def Selection(self):
        return jnp.ones(self._size, dtype=bool)

    @column
    def Weight(self):
        return jnp.ones(self._size)

    @column
    def Value(self):
        return jnp.ones(self._size)

    @column
    def Index(self):
        return jnp.arange(self._size, dtype=jnp.int64)

    # -- global ops --------------------------------------------------------

    def gslice(self, start, stop, step=1):
        """Global slice (reference base/catalog.py:1013)."""
        return self._select(slice(start, stop, step))

    def sort(self, keys, reverse=False, usecols=None):
        """Globally sort by one or more columns (reference
        base/catalog.py:1100 via mpsort).

        On a multi-device mesh every combination of multi-key and
        ``reverse`` runs through the distributed sample sort
        (parallel/sort.py): columns map to order-preserving unsigned
        keys (bit-flipped for descending), and multiple keys resolve
        via least-significant-first stable passes that carry the
        not-yet-sorted keys and the permutation as all_to_all payload —
        no global argsort of a gathered key ever appears in the
        compiled program. Ties keep their original catalog order (also
        under ``reverse``, where the reference's gather-argsort-flip
        would invert them)."""
        if isinstance(keys, str):
            keys = [keys]
        cols = usecols or self.columns
        from ..source.catalog.array import ArrayCatalog
        if self.comm is not None and mesh_size(self.comm) > 1:
            from ..parallel.sort import dist_sort, sortable_key
            cur = [sortable_key(self[k], reverse) for k in keys]
            perm = jnp.arange(self._size)
            for j in range(len(cur) - 1, -1, -1):
                payload = cur[:j] + [perm]
                _, out = dist_sort(cur[j], payload, self.comm)
                cur, perm = out[:j], out[j]
            order = perm
        else:
            order = jnp.argsort(self[keys[-1]])
            for key in reversed(keys[:-1]):
                order = order[jnp.argsort(self[key][order],
                                          stable=True)]
            if reverse:
                order = order[::-1]
        data = {c: self[c][order] for c in cols}
        return ArrayCatalog(data, comm=self.comm, **self.attrs)

    def concatenate(self, *others):
        from ..transform import ConcatenateSources
        return ConcatenateSources(self, *others)
