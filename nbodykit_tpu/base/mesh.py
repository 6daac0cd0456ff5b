"""MeshSource: the distributed 3-D field abstraction.

Reference: ``nbodykit/base/mesh.py:6``. A MeshSource is *a recipe for a
field*: it can produce a real-space or Fourier-space view of itself
(``compute``), with a queue of deferred ``apply`` actions (window
compensation, smoothing filters, transfer functions) composed on top.

TPU-native redesign: the action queue is function composition that jit
traces through — paint, FFTs, and every queued transfer fuse into one
XLA program. Fields are :class:`Field` wrappers around global sharded
jnp arrays (value + attrs), registered as pytrees so they flow through
jax transforms.

Complex fields use the transposed hermitian layout of
:mod:`nbodykit_tpu.parallel.dfft`; ``apply(kind=...)`` passes
coordinate arrays matching the reference's kinds
(wavenumber/circular/index for complex, relative/index for real;
reference base/mesh.py:132-176).
"""

import logging

import numpy as np
import jax
import jax.numpy as jnp

from ..pmesh import ParticleMesh
from ..parallel.runtime import CurrentMesh
from ..utils import as_numpy
from ..diagnostics import device_watermarks, enabled, scope

logger = logging.getLogger('MeshSource')


@jax.tree_util.register_pytree_node_class
class Field(object):
    """A mesh field: a global (possibly sharded) jnp array + metadata.

    Replaces pmesh's RealField/ComplexField at the API surface consumed
    by the reference's algorithms (r2c/c2r/apply/csum/readout...).
    """

    def __init__(self, value, pm, kind=None, attrs=None):
        self.value = value
        self.pm = pm
        # kind: 'real' or 'complex'; inferred when not given
        if kind is None:
            kind = 'complex' if jnp.iscomplexobj(value) else 'real'
        self.kind = kind
        self.attrs = {} if attrs is None else attrs

    # pytree protocol: value is the leaf, the rest rides along
    def tree_flatten(self):
        return (self.value,), (self.pm, self.kind, self.attrs)

    @classmethod
    def tree_unflatten(cls, aux, children):
        pm, kind, attrs = aux
        return cls(children[0], pm, kind=kind, attrs=attrs)

    @property
    def shape(self):
        return self.value.shape

    @property
    def dtype(self):
        return self.value.dtype

    def r2c(self):
        assert self.kind == 'real'
        return Field(self.pm.r2c(self.value), self.pm, 'complex',
                     self.attrs)

    def c2r(self):
        assert self.kind == 'complex'
        return Field(self.pm.c2r(self.value), self.pm, 'real',
                     self.attrs)

    def apply(self, func, kind=None):
        """Apply ``func(coords, value) -> value`` immediately with the
        coordinate arrays implied by ``kind`` (see
        :meth:`MeshSource.apply` for the deferred version)."""
        if kind is None and isinstance(func, MeshFilter):
            kind = func.kind
        if kind is None:
            kind = 'wavenumber' if self.kind == 'complex' else 'relative'
        coords = _coords_for(self.pm, self.kind, kind)
        return Field(func(coords, self.value), self.pm, self.kind,
                     self.attrs)

    def csum(self):
        """Collective sum (global — a plain sum over the global array)."""
        return self.value.sum()

    def cmean(self):
        return self.value.mean()

    def readout(self, pos, resampler=None):
        assert self.kind == 'real'
        return self.pm.readout(self.value, pos, resampler=resampler)

    def preview(self, axes=None):
        """Project the (real) field onto ``axes`` by summing the others;
        returns host numpy (reference: base/mesh.py:340)."""
        v = self.value
        if axes is None:
            return as_numpy(v)
        axes = tuple(axes) if np.iterable(axes) else (axes,)
        other = tuple(i for i in range(3) if i not in axes)
        return as_numpy(v.sum(axis=other))

    def numpy(self):
        return as_numpy(self.value)


def _coords_for(pm, field_kind, coord_kind):
    """Coordinate arrays for an apply action (reference kinds at
    base/mesh.py:132-176)."""
    if field_kind == 'complex':
        if coord_kind == 'wavenumber':
            return pm.k_list()
        if coord_kind == 'circular':
            return pm.k_list(circular=True)
        if coord_kind == 'index':
            return pm.i_list_complex()
        raise ValueError("invalid coord kind %r for a complex field "
                         "(wavenumber|circular|index)" % coord_kind)
    else:
        if coord_kind in ('relative', 'untransformed'):
            return pm.x_list()
        if coord_kind == 'index':
            N0, N1, N2 = pm.shape_real
            return [jnp.arange(N0).reshape(N0, 1, 1),
                    jnp.arange(N1).reshape(1, N1, 1),
                    jnp.arange(N2).reshape(1, 1, N2)]
        raise ValueError("invalid coord kind %r for a real field "
                         "(relative|index)" % coord_kind)


class MeshFilter(object):
    """Base class for named mesh filters (reference base/mesh.py
    MeshFilter): subclasses declare the coordinate ``kind`` and field
    ``mode`` they operate in and implement ``filter(coords, value)``;
    instances can then be passed to :meth:`MeshSource.apply` /
    :meth:`Field.apply` without repeating kind/mode at the call
    site."""

    kind = None
    mode = None

    def filter(self, coords, value):
        raise NotImplementedError

    def __call__(self, coords, value):
        return self.filter(coords, value)


class MeshSource(object):
    """Base class: a recipe for a distributed 3-D field.

    Subclasses implement ``to_real_field()`` or ``to_complex_field()``;
    users call :meth:`compute` (alias :meth:`paint`) with
    ``mode='real'|'complex'``, optionally after queueing transfer
    functions with :meth:`apply`.
    """

    def __init__(self, Nmesh, BoxSize, dtype='f4', comm=None):
        comm = CurrentMesh.resolve(comm)
        self.comm = comm
        self.pm = ParticleMesh(Nmesh, BoxSize, dtype=dtype, comm=comm)
        if not hasattr(self, 'attrs'):
            self.attrs = {}
        self.attrs['Nmesh'] = self.pm.Nmesh.copy()
        self.attrs['BoxSize'] = self.pm.BoxSize.copy()
        self._actions = []

    @property
    def actions(self):
        """The queue of deferred (mode, func, kind) transfer actions."""
        return self._actions

    def view(self):
        """A view MeshSource whose computation is owned by ``self``
        (reference base/mesh.py:82)."""
        import copy
        view = copy.copy(self)
        view.attrs = self.attrs.copy()
        view.base = self
        return view

    def apply(self, func, kind='wavenumber', mode='complex'):
        """Return a *view* of this mesh with ``func`` appended to the
        action queue (reference base/mesh.py:118-176). ``func`` takes
        ``(coords, value)`` and returns the new value; it runs on the
        ``mode``-space field with ``kind`` coordinates. A
        :class:`MeshFilter` instance carries its own kind/mode."""
        import copy
        if isinstance(func, MeshFilter):
            kind = func.kind if func.kind is not None else kind
            mode = func.mode if func.mode is not None else mode
        view = copy.copy(self)
        view.attrs = self.attrs.copy()
        view._actions = self._actions + [(mode, func, kind)]
        return view

    # subclasses implement one of these -----------------------------------

    def to_real_field(self):
        return NotImplemented

    def to_complex_field(self):
        return NotImplemented

    def to_field(self, mode='real'):
        if mode == 'real':
            real = self.to_real_field()
            if real is NotImplemented:
                real = self.to_complex_field().c2r()
            return real
        elif mode == 'complex':
            cplx = self.to_complex_field()
            if cplx is NotImplemented:
                cplx = self.to_real_field().r2c()
            return cplx
        raise ValueError("mode must be 'real' or 'complex'")

    def compute(self, mode='real', Nmesh=None):
        """Produce the field, running the action pipeline (alternating
        r2c/c2r as needed) and optionally resampling to ``Nmesh``
        (reference paint pipeline, base/mesh.py:246-338)."""
        if mode not in ('real', 'complex'):
            raise ValueError("mode must be 'real' or 'complex'")

        with scope('mesh.compute', mode=mode,
                   cls=type(self).__name__,
                   nactions=len(self.actions)):
            # decide the starting representation: prefer the native one
            native_real = (type(self).to_real_field
                           is not MeshSource.to_real_field)
            field = self.to_field('real' if native_real else 'complex')

            for amode, func, kind in self.actions:
                if amode == 'real' and field.kind != 'real':
                    field = field.c2r()
                elif amode == 'complex' and field.kind != 'complex':
                    field = field.r2c()
                field = field.apply(func, kind=kind)

            if Nmesh is not None and any(
                    np.atleast_1d(Nmesh) != self.pm.Nmesh):
                field = self._resample(field, Nmesh)

            if mode == 'real' and field.kind != 'real':
                field = field.c2r()
            elif mode == 'complex' and field.kind != 'complex':
                field = field.r2c()
            if enabled():
                # per-device live-buffer watermarks at the end of each
                # compute phase: the gauge maxima answer "what was HBM
                # holding when it OOMed" post-mortem
                device_watermarks()
            return field

    paint = compute

    def preview(self, axes=None, Nmesh=None, root=0):
        """Project the (optionally ``Nmesh``-downsampled) real field
        onto ``axes`` and return host numpy (reference
        base/mesh.py:340-383). ``root`` is accepted for signature
        parity; global arrays make the result identical on every
        process, so no broadcast is needed."""
        return self.compute(mode='real', Nmesh=Nmesh).preview(axes=axes)

    def _resample(self, field, Nmesh):
        """Fourier-space resample to a new mesh size: mode truncation
        (down) or zero-padding (up), reference base/mesh.py:320-330."""
        if field.kind != 'complex':
            field = field.r2c()
        pm2 = self.pm.reshape(Nmesh)
        src, dst = self.pm, pm2
        a = field.value
        # build the destination spectrum by gathering the overlapping
        # modes; operate on host-safe index arithmetic with jnp.take
        sN0, sN1, sN2 = src.shape_real
        dN0, dN1, dN2 = dst.shape_real
        n1 = min(sN1, dN1)
        n0 = min(sN0, dN0)
        nz = min(sN2 // 2 + 1, dN2 // 2 + 1)

        def modes(n_dst, n_src, count):
            # signed mode index list of the destination's first `count`
            # positive + matching negative frequencies in source ordering
            half = (count + 1) // 2
            pos = jnp.arange(half)
            neg = jnp.arange(-(count - half), 0) % n_src
            return jnp.concatenate([pos, neg])

        i1 = modes(dN1, sN1, n1)
        i0 = modes(dN0, sN0, n0)
        sub = jnp.take(jnp.take(a[:, :, :nz], i1, axis=0), i0, axis=1)
        out = jnp.zeros(dst.shape_complex, dtype=a.dtype)
        o1 = modes(dN1, dN1, n1)
        o0 = modes(dN0, dN0, n0)
        out = out.at[jnp.ix_(o1, o0, jnp.arange(nz))].set(sub)
        f2 = Field(out, pm2, 'complex', field.attrs)
        return f2

    def save(self, output, dataset='Field', mode='real'):
        """Persist the computed field (+ attrs) to disk; see
        :mod:`nbodykit_tpu.io.bigfile` for the format. Reference:
        base/mesh.py:367-412."""
        from ..io.bigfile import BigFileWriter
        field = self.compute(mode=mode)
        with BigFileWriter(output, create=True) as ff:
            attrs = dict(self.attrs)
            attrs['ndarray.shape'] = np.asarray(field.shape)
            ff.write(dataset, as_numpy(field.value).reshape(-1), attrs=attrs)

    def to_mesh(self):
        return self

    def __len__(self):
        return 0


class FieldMesh(MeshSource):
    """Wrap an existing field (array or Field) as a MeshSource
    (reference: nbodykit/source/mesh/field.py:6)."""

    def __init__(self, field, BoxSize=None, comm=None):
        if isinstance(field, Field):
            pm = field.pm
            self.attrs = dict(field.attrs)
            MeshSource.__init__(self, pm.Nmesh, pm.BoxSize,
                                dtype=pm.dtype.str, comm=pm.comm)
            self._field = field
        else:
            field = jnp.asarray(field)
            if BoxSize is None:
                raise ValueError("BoxSize is required when wrapping a "
                                 "plain array")
            if jnp.iscomplexobj(field):
                raise ValueError("pass complex fields as Field objects "
                                 "(the layout is ambiguous)")
            MeshSource.__init__(self, field.shape, BoxSize,
                                dtype=field.dtype.str, comm=comm)
            self._field = Field(field, self.pm, 'real')

    def to_real_field(self):
        f = self._field
        return f if f.kind == 'real' else f.c2r()

    def to_complex_field(self):
        f = self._field
        return f if f.kind == 'complex' else f.r2c()
