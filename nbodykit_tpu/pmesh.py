"""ParticleMesh: the TPU-native replacement for ``pmesh.pm.ParticleMesh``.

The reference builds everything on pmesh's MPI ParticleMesh/RealField/
ComplexField (created at nbodykit/base/mesh.py:50, consumed throughout).
Here the same capability surface is provided over JAX:

- fields are *global* ``jax.Array``s (slab-sharded over a 1-D device mesh
  when one is active), not rank-local blocks;
- ``r2c``/``c2r`` use :mod:`nbodykit_tpu.parallel.dfft` (local FFTs +
  all_to_all), with pmesh's forward-normalized convention
  (``c2r(r2c(x)) == x``; r2c divides by Nmesh^3);
- complex fields are hermitian-compressed and *transposed*: global shape
  (N1, N0, N2//2+1), leading axis = ky (see dfft.py docstring);
- ``paint``/``readout`` route particles to slab owners with a fixed-
  capacity all_to_all, then scatter/gather on halo-extended blocks
  (parallel/halo.py), replacing pmesh.domain decompose/exchange
  (reference call sites: nbodykit/source/mesh/catalog.py:271-296);
- ``generate_whitenoise`` draws a device-count-invariant unit-variance
  complex field (reference: pm.generate_whitenoise at mockmaker.py:83).

Everything returned is a plain jnp array; the RealField/ComplexField
wrappers in :mod:`nbodykit_tpu.base.mesh` add attrs/convenience methods.
"""

import functools
import logging
from functools import lru_cache as _lru_cache

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import _global_options
from .diagnostics import counter, current_tracer, fetch, \
    install_compile_telemetry, instrumented_jit, scope, span, \
    trace_state_clean
from .parallel.runtime import AXIS, CurrentMesh, is_eager, mesh_size, \
    shard_leading
from .parallel import dfft
from .parallel.halo import halo_add, halo_fill
from .parallel.exchange import exchange_by_dest
from .ops.window import window_support
from .ops.paint import (paint_local, paint_local_sorted,
                        paint_local_segsum, paint_local_streams,
                        paint_local_mxu, readout_local, tile_geometry,
                        PIECE_ROWS)

# compile telemetry for the paint/FFT entry points below: XLA compiles
# and compilation-cache hits/misses land in the metric registry
install_compile_telemetry()


def _triplet(x, dtype):
    a = np.empty(3, dtype=dtype)
    a[:] = x
    return a


def paint_engine(method, shape, resampler):
    """Which engine ``paint_method=method`` runs on a local block of
    ``shape``: 'mxu', the default, is the tile deposit wherever the
    block admits it (:func:`~nbodykit_tpu.ops.paint.tile_geometry`: a
    rule of the block's shape and the window, not of the particle
    count, so a cell's 64^3 oracle checks the engine its timed call
    runs) and the scatter on the test-sized rest; every other method
    is its own engine.  The ``engine`` attribute of the ``paint``
    span."""
    if method != 'mxu':
        return method
    return 'scatter' if tile_geometry(shape, resampler) is None \
        else 'tile'


def _paint_kernel(method, chunk, order, deposit, streams, storage_dtype):
    """The local paint kernel of one resolved configuration."""
    if method == 'sort':
        return paint_local_sorted
    if method == 'segsum':
        return functools.partial(paint_local_segsum, order_method=order)
    if method == 'streams':
        return functools.partial(paint_local_streams, streams=streams,
                                 chunk=chunk,
                                 storage_dtype=storage_dtype)
    if method == 'mxu':
        return functools.partial(paint_local_mxu, deposit=deposit)
    return functools.partial(paint_local, chunk=chunk)


@functools.partial(instrumented_jit, label='paint.tile',
                   static_argnames=('shape', 'resampler', 'deposit'))
def paint_tile(cpos, massa, shape, resampler, deposit):
    """The one-chip tile deposit of an eager call as one program: op
    by op its stripe scan would be a launch a fold."""
    return paint_local_mxu(cpos, massa, shape, resampler=resampler,
                           deposit=deposit)


@_lru_cache(maxsize=32)
def _slab_paint_programs(mesh, shape_real, resampler, method, chunk,
                         order, deposit, streams, storage_dtype,
                         compute_dtype, mxu):
    """The paint of exchanged particles onto a slab mesh as one
    program: the mask of empty slots, the local kernel into the
    halo-extended slab and ``halo_add``.  Cached per everything the
    body reads: the device mesh, the field's shape, the window, the
    resolved kernel configuration (``mxu``: the backend branch of its
    orderings) and the dtypes; the particle count and so the exchange
    capacity key the jit's own cache.  Returns ``(raw, jit)`` as
    ``dfft._slab_programs`` does: the raw callable for an outer trace,
    the jitted form for the eager call."""
    nproc = mesh_size(mesh)
    N0, N1, N2 = shape_real
    n0 = N0 // nproc
    h = window_support(resampler)
    kernel = _paint_kernel(method, chunk, order, deposit, streams,
                           storage_dtype)

    def local(cpos_l, mass_l):
        d = jax.lax.axis_index(AXIS)
        origin = d * n0 - h
        ext = kernel(cpos_l, mass_l, (n0 + 2 * h, N1, N2),
                     resampler=resampler, period=(N0, N1, N2),
                     origin=origin)
        return halo_add(ext, h, nproc)

    sharded = jax.shard_map(local, mesh=mesh,
                            in_specs=(P(AXIS, None), P(AXIS)),
                            out_specs=P(AXIS, None, None))

    def paint_slab(cpos_r, mass_r, valid):
        mass_r = jnp.where(valid, mass_r, 0.0).astype(compute_dtype)
        return sharded(cpos_r, mass_r)

    return paint_slab, instrumented_jit(paint_slab, label='paint.slab')


def _cell_units(pos, nmesh, boxsize):
    """Positions in units of the mesh's cells."""
    return pos * jnp.asarray(np.divide(nmesh, boxsize), pos.dtype)


def _slab_owner(cpos, N0, nproc):
    """Slab owner per particle (cpos in cell units, shift already
    applied) — THE routing rule, shared by paint/readout and the
    counted-capacity pass so they cannot drift apart."""
    n0 = N0 // nproc
    cell = jnp.mod(jnp.floor(cpos[:, 0]).astype(jnp.int32), N0)
    return cell // n0


def paint_route(pos, mass, shift, nmesh, boxsize, nproc, compute_dtype):
    """Where a paint's particles go: positions in cell units on the
    (shifted) grid, the weights in compute dtype and, on a slab mesh,
    each particle's slab owner.  A pure function of what it is given:
    one device and a traced caller call it as it is, the eager slab
    paint as one program (:data:`_route_jit`)."""
    cpos = _cell_units(pos, nmesh, boxsize) - shift
    # weights are COMPUTE dtype: with bf16 storage the deposit terms
    # stay f32 and only the mesh buffers narrow (the streams kernel's
    # replica meshes, via storage_dtype, plus the final field cast at
    # the exit)
    massa = jnp.broadcast_to(
        jnp.asarray(mass, compute_dtype), (pos.shape[0],))
    dest = None if nproc == 1 else _slab_owner(cpos, nmesh[0], nproc)
    return cpos, massa, dest


_route_jit = instrumented_jit(
    paint_route, label='paint.route',
    static_argnames=('nmesh', 'boxsize', 'nproc', 'compute_dtype'))


def mode_k_list(nmesh, boxsize, dtype, circular=False, full=False):
    """:meth:`ParticleMesh.k_list` of an ``nmesh`` / ``boxsize``
    geometry, in the (resolved) ``dtype``: what a cached program
    builder calls, which has the geometry and no mesh object."""
    N0, N1, N2 = (int(n) for n in nmesh)
    L = boxsize

    def freq(n, L_i, r2c_axis=False):
        if r2c_axis and not full:
            j = jnp.arange(n // 2 + 1, dtype=dtype)
        else:
            j = jnp.fft.fftfreq(n, d=1.0 / n).astype(dtype)
        if circular:
            return j * jnp.asarray(2 * np.pi / n, dtype)
        return j * jnp.asarray(2 * np.pi / L_i, dtype)

    kx = freq(N0, L[0]).reshape(1, N0, 1)
    ky = freq(N1, L[1]).reshape(N1, 1, 1)
    nz = N2 if full else N2 // 2 + 1
    kz = freq(N2, L[2], r2c_axis=True).reshape(1, 1, nz)
    return [kx, ky, kz]


def mode_i_list(nmesh):
    """:meth:`ParticleMesh.i_list_complex` of an ``nmesh`` geometry."""
    N0, N1, N2 = (int(n) for n in nmesh)
    ix = jnp.fft.fftfreq(N0, d=1.0 / N0).astype(jnp.int32).reshape(1, N0, 1)
    iy = jnp.fft.fftfreq(N1, d=1.0 / N1).astype(jnp.int32).reshape(N1, 1, 1)
    iz = jnp.arange(N2 // 2 + 1, dtype=jnp.int32).reshape(1, 1, -1)
    return [ix, iy, iz]


def mode_hermitian_weights(n2, dtype=jnp.float32):
    """:meth:`ParticleMesh.hermitian_weights` of a last axis of ``n2``
    cells."""
    from .utils import working_dtype
    N2 = int(n2)
    nz = N2 // 2 + 1
    iz = jnp.arange(nz)
    w = jnp.where((iz > 0) & ~((N2 % 2 == 0) & (iz == N2 // 2)), 2.0, 1.0)
    return w.astype(working_dtype(dtype)).reshape(1, 1, nz)


class ParticleMesh(object):
    """Geometry + parallel layout descriptor for 3-D particle-mesh fields.

    Parameters
    ----------
    Nmesh : int or 3-vector — cells per side
    BoxSize : float or 3-vector — box side length(s)
    dtype : mesh dtype ('f4' or 'f8')
    comm : jax.sharding.Mesh or None — the device mesh (defaults to the
        ambient :class:`~nbodykit_tpu.parallel.runtime.CurrentMesh`)
    """

    logger = logging.getLogger('ParticleMesh')

    def __init__(self, Nmesh, BoxSize, dtype='f4', comm=None):
        self.Nmesh = _triplet(Nmesh, 'i8')
        self.BoxSize = _triplet(BoxSize, 'f8')
        from .utils import is_narrow_float, mesh_storage_dtype
        # canonicalize up front: an f8 mesh with x64 disabled (the TPU
        # reality) IS an f4 mesh — deciding here keeps every kernel
        # below free of per-callsite truncation warnings.  'bf16' is a
        # STORAGE dtype only: mesh buffers are bfloat16 (half the f4
        # HBM) while everything computed over them — deposit weights,
        # FFT butterflies, readout gathers — runs in ``compute_dtype``
        # (f32) and narrows back at the buffer boundary (docs/PERF.md
        # "Halving the bytes"; accuracy gate in tests/test_precision.py)
        self.dtype = mesh_storage_dtype(dtype)
        self.compute_dtype = np.dtype('f4') \
            if is_narrow_float(self.dtype) else self.dtype
        self.comm = CurrentMesh.resolve(comm)
        self.nproc = mesh_size(self.comm)
        if int(self.Nmesh[0]) % self.nproc or int(self.Nmesh[1]) % self.nproc:
            raise ValueError("Nmesh[0], Nmesh[1] must be divisible by the "
                             "%d-device mesh" % self.nproc)
        self._plan = dfft.dist_fft_plan(self.Nmesh, self.comm)

    # -- shapes -----------------------------------------------------------

    @property
    def shape_real(self):
        return tuple(int(n) for n in self.Nmesh)

    @property
    def shape_complex(self):
        """Transposed, hermitian-compressed layout (ky, kx, kz)."""
        N0, N1, N2 = (int(n) for n in self.Nmesh)
        return (N1, N0, N2 // 2 + 1)

    @property
    def Ntot(self):
        return int(np.prod(self.Nmesh))

    @property
    def cellsize(self):
        return self.BoxSize / self.Nmesh

    def __eq__(self, other):
        return (isinstance(other, ParticleMesh)
                and np.array_equal(self.Nmesh, other.Nmesh)
                and np.array_equal(self.BoxSize, other.BoxSize))

    # -- field creation ---------------------------------------------------

    def sharding(self, ndim=3):
        if self.comm is None:
            return None
        return NamedSharding(self.comm, P(*((AXIS,) + (None,) * (ndim - 1))))

    def create(self, type='real', value=0.):
        """A zero (or constant) field of the requested type."""
        if type == 'real':
            shape, dtype = self.shape_real, self.dtype
        elif type in ('complex', 'transposedcomplex'):
            shape = self.shape_complex
            dtype = jnp.complex64 if self.dtype.itemsize <= 4 \
                else jnp.complex128
        else:
            raise ValueError("field type must be 'real' or 'complex'")
        arr = jnp.full(shape, value, dtype=dtype)
        if self.comm is not None:
            arr = jax.device_put(arr, self.sharding())
        return arr

    # -- FFT --------------------------------------------------------------

    def r2c(self, real):
        """Forward real-to-complex FFT, forward-normalized (pmesh
        convention: divides by Nmesh^3 so the result is 'dimensionless').

        Narrow-storage (bf16) meshes re-widen to f32 at this boundary:
        the FFT stages always compute f32 — storage never reaches a
        butterfly (wire-level compression is the separate
        ``a2a_compress`` knob in parallel/dfft.py)."""
        from .utils import is_narrow_float
        real = jnp.asarray(real)
        if is_narrow_float(real.dtype):
            real = real.astype(jnp.float32)
        return self._plan.r2c(real) * (1.0 / self.Ntot)

    def c2r(self, cplx):
        """Inverse transform of :meth:`r2c` (unnormalized inverse since the
        forward carried the 1/N^3)."""
        return self._plan.c2r(cplx * self.Ntot).astype(self.dtype)

    # -- coordinates ------------------------------------------------------

    def x_list(self, dtype=None):
        """Broadcastable real-space coordinate arrays [x, y, z] for the
        (N0, N1, N2) real layout: x_i = index * cellsize_i, in [0, L)."""
        from .utils import working_dtype
        # coordinates are compute-dtype: a bf16 storage mesh still gets
        # f32 coordinate arrays (8 mantissa bits cannot index a lattice)
        dtype = working_dtype(dtype) if dtype is not None \
            else np.dtype(self.compute_dtype)
        out = []
        for ax, (n, h) in enumerate(zip(self.Nmesh, self.cellsize)):
            shape = [1, 1, 1]
            shape[ax] = int(n)
            out.append((jnp.arange(int(n), dtype=dtype)
                        * jnp.asarray(h, dtype)).reshape(shape))
        return out

    def k_list(self, dtype=None, circular=False, full=False):
        """Broadcastable k-coordinate arrays [kx, ky, kz] for the
        *transposed* complex layout (axis0=ky, axis1=kx, axis2=kz).

        ``circular=True`` gives w_i = k_i * BoxSize_i / Nmesh_i in
        [-pi, pi) (the reference's 'circular' apply kind,
        nbodykit/base/mesh.py:132-145). ``full=True`` gives the
        uncompressed kz axis (c2c layout) instead of the rfft half.
        """
        from .utils import working_dtype
        dtype = working_dtype(dtype) if dtype is not None else (
            jnp.float32 if self.dtype.itemsize <= 4
            else working_dtype('f8'))
        return mode_k_list(self.Nmesh, self.BoxSize, dtype,
                           circular=circular, full=full)

    def i_list_complex(self):
        """Broadcastable integer mode-index arrays [ix, iy, iz] (signed,
        fftfreq convention) for the transposed complex layout."""
        return mode_i_list(self.Nmesh)

    def hermitian_weights(self, dtype=jnp.float32):
        """Double-count weights for the compressed kz half-space: weight 2
        for 0 < kz < Nyquist, weight 1 on the kz=0 and Nyquist planes
        (reference: nbodykit/meshtools.py:188-215)."""
        return mode_hermitian_weights(self.Nmesh[2], dtype)

    # -- paint / readout --------------------------------------------------

    def _to_cell_units(self, pos):
        return _cell_units(pos, self.shape_real,
                           tuple(float(b) for b in self.BoxSize))

    def _check_halo(self, h):
        """Validate halo width against the per-device slab height; the
        single-hop ppermute halo exchange requires support <= N0/P."""
        n0 = int(self.Nmesh[0]) // self.nproc
        if h > n0:
            raise ValueError(
                "resampler support %d exceeds the per-device slab height "
                "%d (= Nmesh[0]=%d / %d devices); use a larger Nmesh, "
                "fewer devices, or a narrower window"
                % (h, n0, int(self.Nmesh[0]), self.nproc))
        return n0

    def _route_dest(self, cpos):
        """Slab owner per particle: :func:`_slab_owner` on this mesh."""
        return _slab_owner(cpos, int(self.Nmesh[0]), self.nproc)

    def exchange_capacity(self, pos, slack=1.05, shift=0.0):
        """Two-pass counted exchange, pass 1 (run EAGERLY): the exact
        per-(src,dst) routing count for these positions, with slack,
        on its rung of the capacity ladder
        (:func:`~nbodykit_tpu.parallel.exchange.ladder_capacity`).

        Pass the result as ``capacity=`` to a *traced* :meth:`paint` /
        :meth:`readout` (with ``return_dropped=True``) so the
        all_to_all buffers are counted-size (~N/P^2) instead of the
        always-sufficient ceil(N/P) — the difference between fitting
        a 2048^3 mesh next to a 1e9-particle exchange and OOM (see
        :func:`memory_plan` and parallel/exchange.py).

        ``shift`` must match the paint's (interlaced painting routes by
        the half-cell-shifted grid; take the max of the capacities at
        shift 0 and 0.5 for an interlaced pair of paints).
        """
        from .parallel.exchange import auto_capacity
        if self.nproc == 1:
            return int(pos.shape[0])
        if slack == 'auto':
            raise ValueError("slack='auto' is not a value: pass a "
                             "number (1.05 by default)")
        dest = self._route_dest(self._to_cell_units(pos) - shift)
        return auto_capacity(dest, self.nproc, slack=slack)

    def paint(self, pos, mass=1.0, resampler=None, out=None, shift=0.0,
              capacity=None, return_dropped=False):
        """Scatter particles onto the mesh; returns a real field.

        Parameters
        ----------
        pos : (N, 3) positions in box units (global array; sharded on axis
            0 when a device mesh is active)
        mass : scalar or (N,) weights; slots with mass 0 are inert
        shift : float, cell units — paint onto a half-cell-shifted grid
            (used by interlacing, reference source/mesh/catalog.py:292)
        capacity : per-(src,dst) exchange capacity; by default the
            counted bound at slack 1.05 on its rung of the ladder
            (:func:`~nbodykit_tpu.parallel.exchange.ladder_capacity`)
            eagerly, the always-sufficient ceil(N/P) under a trace.
        return_dropped : also return the exchange-overflow count so
            traced callers can check it after the step.

        Overflow contract (reference analog: the paint chunk backoff
        loop, nbodykit/source/mesh/catalog.py:275-315): with the default
        capacity, overflow is impossible (the exact bound's rung
        eagerly, ceil bound under trace). An explicit ``capacity`` is
        retried eagerly with doubled capacity until nothing drops;
        under a trace the
        check cannot branch, so ``return_dropped=True`` is REQUIRED —
        silent particle loss is never possible.

        Diagnostics (docs/OBSERVABILITY.md): every call runs under
        ``scope('paint')`` (``nbk.paint`` on the profiler's host line,
        or on the HLO op names under a trace); eager calls with the
        ``diagnostics`` option set also emit a ``paint`` span (attribute
        ``engine``: 'tile' or 'scatter' under the default method,
        :func:`paint_engine`, ``npart`` beside it: the span's wall over
        ``npart`` is the throughput).
        The result is synced (``sc.done``) inside the span so its wall
        is real work, not dispatch — enabled-mode only; the disabled
        path is byte-identical to the undiagnosed one.
        """
        if current_tracer() is None or not trace_state_clean():
            # no JSONL span here, but the layer still gets its name:
            # on the profiler's host line, or on the HLO ops
            with scope('paint'):
                return self._paint_impl(pos, mass, resampler, out,
                                        shift, capacity, return_dropped)
        method = _global_options['paint_method']
        window = resampler or _global_options['resampler']
        with scope('paint', method=method, npart=int(pos.shape[0]),
                   engine=paint_engine(method,
                                       self._local_block(window), window),
                   nproc=self.nproc, resampler=window,
                   nmesh=int(self.Nmesh[0])) as sc:
            return sc.done(self._paint_impl(
                pos, mass, resampler, out, shift, capacity,
                return_dropped))

    def _local_block(self, resampler):
        """Shape of the block the local paint kernel fills: the mesh
        on one device, a slab with the window's halo on either side
        on a slab mesh."""
        N0, N1, N2 = self.shape_real
        if self.nproc == 1:
            return N0, N1, N2
        return (N0 // self.nproc + 2 * window_support(resampler), N1, N2)

    def _paint_impl(self, pos, mass, resampler, out, shift, capacity,
                    return_dropped):
        resampler = resampler or _global_options['resampler']
        h = window_support(resampler)
        N0, N1, N2 = self.shape_real
        npart = pos.shape[0]
        route = _route_jit if self.nproc > 1 and \
            is_eager(pos, mass, shift) else paint_route
        cpos, massa, dest = route(
            pos, mass, shift, nmesh=(N0, N1, N2),
            boxsize=tuple(float(b) for b in self.BoxSize),
            nproc=self.nproc,
            compute_dtype=np.dtype(self.compute_dtype))
        pm_method = _global_options['paint_method']
        chunk = int(_global_options['paint_chunk_size'])
        order = _global_options['paint_order']
        deposit = _global_options['paint_deposit']
        nstreams = int(_global_options['paint_streams'])
        traced = isinstance(cpos, jax.core.Tracer)
        # tier-0 integrity posture + chaos injection resolve here, at
        # dispatch: both are eager-only (a data-dependent raise cannot
        # live under trace) and integrity='off' takes the exact same
        # code path as before — zero added ops, bit-identical fields
        cbits = 0
        chk = False
        if not traced:
            from .resilience.faults import corrupt_spec
            from .resilience.integrity import checks_enabled
            cbits = corrupt_spec('paint.accum')
            chk = checks_enabled()
        kernel = _paint_kernel(pm_method, chunk, order, deposit,
                               nstreams, self.dtype)
        if self.nproc == 1:
            if not traced and paint_engine(
                    pm_method, self.shape_real, resampler) == 'tile':
                block = paint_tile(cpos, massa, self.shape_real,
                                   resampler, deposit)
            else:
                block = kernel(cpos, massa, self.shape_real,
                               resampler=resampler,
                               period=self.shape_real, origin=0)
            # kernels return compute dtype; widen any caller-held
            # accumulator before adding (never mix widths on a
            # mesh-sized operand) and narrow once at the exit
            if cbits:
                block = self._corrupt_accum(block, cbits)
            if out is not None:
                block = block + jnp.asarray(out).astype(block.dtype)
            if chk:
                self._verify_mass(block, massa, out, h, npart)
            out = block.astype(self.dtype)
            if return_dropped:
                return out, jnp.zeros((), jnp.int32)
            return out

        self._check_halo(h)
        self._check_overflow_contract(capacity, traced, return_dropped)

        def attempt(cap):
            recv, valid, dropped = exchange_by_dest(
                dest, [cpos, massa], self.comm, cap)
            from .utils import is_mxu_backend
            raw, jitted = _slab_paint_programs(
                self.comm, (N0, N1, N2), resampler, pm_method, chunk,
                order, deposit, nstreams,
                jnp.dtype(self.dtype), jnp.dtype(self.compute_dtype),
                is_mxu_backend())
            block = (jitted if is_eager(*recv, valid) else raw)(
                *recv, valid)
            return block, dropped

        block, dropped = attempt(capacity)
        # eager: the exchange ends before the paint, so reading its
        # count costs no further wait
        lost = 0 if traced else self._count_dropped(dropped)
        if capacity is not None and lost > 0:
            # eager exchange-capacity backoff (reference:
            # source/mesh/catalog.py:275-315), keeping all three
            # outputs from the final attempt
            cap_max = -(-npart // self.nproc) + 8
            while lost > 0 and capacity < cap_max:
                capacity = min(2 * capacity, cap_max)
                self.logger.info(
                    "exchange overflow (%d dropped); retrying with "
                    "capacity=%d" % (lost, capacity))
                block, dropped = attempt(capacity)
                lost = self._count_dropped(dropped)
            if lost > 0:
                # `dropped` is the globally-summed overflow count:
                # every rank computes the same value and raises
                # together, and no collective program follows
                raise RuntimeError(
                    "particle exchange still overflowing at the "
                    "maximal capacity %d — this should be impossible"
                    % capacity)
        # same merge-then-narrow contract as the single-device exit:
        # the halo_add ran in compute dtype inside the shard_map, the
        # storage cast happens exactly once, here
        if cbits:
            block = self._corrupt_accum(block, cbits)
        if out is not None:
            block = block + jnp.asarray(out).astype(block.dtype)
        if chk:
            self._verify_mass(block, massa, out, h, npart)
        out = block.astype(self.dtype)
        if return_dropped:
            return out, dropped
        return out

    def _corrupt_accum(self, block, bits):
        """Chaos-matrix injection for the ``paint.accum`` point: flip
        the top ``bits`` bits of one accumulated cell (before the
        merge, so the mass guard — not the injector — must catch it).
        Active regardless of the integrity mode: with checks off the
        corruption flows through silently, which IS the documented
        blind spot the tier exists to close."""
        from .resilience.integrity import corrupt_real
        return corrupt_real(block, bits)

    def _verify_mass(self, block, massa, prior, h, npart):
        """Tier-0 mass-conservation guard (resilience/integrity.py):
        the deposit windows sum to one per particle, so the merged
        field's global sum must equal the deposited mass plus any
        caller-held accumulator, within a compute-dtype budget widened
        for narrow (bf16) mesh storage.  The folds double as NaN/Inf
        tripwires on the mesh-sized accumulator."""
        from .resilience import integrity
        f4 = jnp.float32
        expected = jnp.sum(massa.astype(f4))
        scale = jnp.sum(jnp.abs(massa).astype(f4))
        if prior is not None:
            pw = jnp.asarray(prior).astype(f4)
            expected = expected + jnp.sum(pw)
            scale = scale + jnp.sum(jnp.abs(pw))
        total = float(jnp.sum(block.astype(f4)))
        n = max(int(npart), 1) * int(h) ** 3
        integrity.check_mass('paint.mass', total, float(expected),
                             float(scale), n, self.compute_dtype,
                             self.dtype)

    @staticmethod
    def _count_dropped(dropped):
        """An exchange's overflow count as an int, read eagerly, and
        fed to the ``exchange.dropped`` counter: it counts what each
        attempt lost, before a retry heals it."""
        lost = int(fetch(dropped, 'exchange.dropped'))
        counter('exchange.dropped').add(lost)
        return lost

    def _check_overflow_contract(self, capacity, traced, return_dropped):
        if traced and capacity is not None and not return_dropped:
            raise ValueError(
                "paint/readout with an explicit capacity inside jit "
                "cannot retry on exchange overflow; pass "
                "return_dropped=True and check the count after the "
                "step (or use the default capacity, which cannot "
                "overflow)")

    def _retry_grown(self, attempt, block, dropped, capacity, npart):
        """Eager backoff: double the exchange capacity until no
        particle drops (reference: source/mesh/catalog.py:275-315)."""
        cap_max = -(-npart // self.nproc) + 8
        lost = self._count_dropped(dropped)
        while lost > 0 and capacity < cap_max:
            capacity = min(2 * capacity, cap_max)
            self.logger.info(
                "exchange overflow (%d dropped); retrying with "
                "capacity=%d" % (lost, capacity))
            block, dropped = attempt(capacity)
            lost = self._count_dropped(dropped)
        if lost > 0:
            raise RuntimeError(
                "particle exchange still overflowing at the maximal "
                "capacity %d — this should be impossible" % capacity)
        return block, dropped, capacity

    def readout(self, real, pos, resampler=None, capacity=None,
                return_dropped=False, grad_axis=None):
        """Interpolate a real field at particle positions (inverse of
        paint; reference: pmesh Field.readout, used by FFTRecon at
        algorithms/fftrecon.py:217-268).

        ``capacity``/``return_dropped`` follow the same overflow
        contract as :meth:`paint`; eager calls emit a ``readout`` span
        under diagnostics (same sync semantics as :meth:`paint`).

        ``grad_axis`` (0/1/2) reads the window-DERIVATIVE
        interpolation d(readout)/d(pos[grad_axis]) instead, in CELL
        units (multiply by Nmesh/BoxSize for box units) — the position
        cotangent of the paint adjoint (docs/FORWARD.md).
        """
        if current_tracer() is None or not trace_state_clean():
            return self._readout_impl(real, pos, resampler, capacity,
                                      return_dropped, grad_axis)
        with span('readout', npart=int(pos.shape[0]), nproc=self.nproc,
                  nmesh=int(self.Nmesh[0])):
            res = self._readout_impl(real, pos, resampler, capacity,
                                     return_dropped, grad_axis)
            jax.block_until_ready(res)
        return res

    def _readout_impl(self, real, pos, resampler, capacity,
                      return_dropped, grad_axis=None):
        from .utils import is_narrow_float
        real = jnp.asarray(real)
        if is_narrow_float(real.dtype):
            # readout re-widens IMMEDIATELY (the NBK702 contract's
            # read side): interpolation weights and gathers compute
            # f32 — bf16 is a storage format, never an arithmetic one
            real = real.astype(jnp.float32)
        resampler = resampler or _global_options['resampler']
        h = window_support(resampler)
        N0, N1, N2 = self.shape_real
        cpos = self._to_cell_units(pos)
        npart = pos.shape[0]

        if self.nproc == 1:
            out = readout_local(real, cpos, resampler=resampler,
                                period=self.shape_real, origin=0,
                                grad_axis=grad_axis)
            if return_dropped:
                return out, jnp.zeros((), jnp.int32)
            return out

        n0 = self._check_halo(h)
        cell = jnp.mod(jnp.floor(cpos[:, 0]).astype(jnp.int32), N0)
        dest = cell // n0
        gidx = jnp.arange(npart, dtype=jnp.int32)
        traced = isinstance(cpos, jax.core.Tracer)
        self._check_overflow_contract(capacity, traced, return_dropped)
        nproc = self.nproc

        def local(real_l, cpos_l):
            d = jax.lax.axis_index(AXIS)
            origin = d * n0 - h
            ext = halo_fill(real_l, h, nproc)
            return readout_local(ext, cpos_l, resampler=resampler,
                                 period=(N0, N1, N2), origin=origin,
                                 grad_axis=grad_axis)

        def attempt(cap):
            recv, valid, dropped = exchange_by_dest(
                dest, [cpos, gidx], self.comm, cap)
            cpos_r, gidx_r = recv
            vals = jax.shard_map(
                local, mesh=self.comm,
                in_specs=(P(AXIS, None, None), P(AXIS, None)),
                out_specs=P(AXIS))(real, cpos_r)
            # back to original particle order: masked scatter by
            # global index
            vals = jnp.where(valid, vals, 0.0)
            gidx_r = jnp.where(valid, gidx_r, npart)
            out = jnp.zeros((npart + 1,), vals.dtype).at[gidx_r].add(
                vals)
            return out[:npart], dropped

        out, dropped = attempt(capacity)
        if not traced and capacity is not None:
            out, dropped, capacity = self._retry_grown(
                attempt, out, dropped, capacity, npart)
        if return_dropped:
            return out, dropped
        return out

    # -- white noise ------------------------------------------------------

    def generate_whitenoise(self, seed, unitary=False, inverted_phase=False):
        """A hermitian complex field with unit variance per mode, suitable
        for scaling by sqrt(P(k)/V) (reference semantics:
        mockmaker.py:83-134 via pmesh generate_whitenoise).

        Device-count invariant: the draw is a function of (seed, global
        cell index) only.
        """
        key = jax.random.key(seed)
        rdtype = jnp.float32 if self.dtype.itemsize <= 4 else jnp.float64
        g = jax.random.normal(key, self.shape_real, dtype=rdtype)
        if self.comm is not None:
            g = jax.lax.with_sharding_constraint(g, self.sharding())
        eta = self._plan.r2c(g) * (1.0 / np.sqrt(self.Ntot))
        if unitary:
            amp = jnp.abs(eta)
            eta = eta / jnp.where(amp == 0, 1.0, amp)
        if inverted_phase:
            eta = -eta
        return eta

    # -- particle grids ---------------------------------------------------

    def generate_uniform_particle_grid(self, shift=0.5, dtype='f4'):
        """Positions of a uniform lattice of Nmesh^3 particles, offset by
        ``shift`` cells (reference: pm.generate_uniform_particle_grid,
        mockmaker.py:312). Returns (Ntot, 3), x-fastest-varying ordering
        chosen so the particle axis shards along the x slab."""
        N0, N1, N2 = self.shape_real
        H = self.cellsize
        i0 = jnp.arange(N0).reshape(N0, 1, 1)
        i1 = jnp.arange(N1).reshape(1, N1, 1)
        i2 = jnp.arange(N2).reshape(1, 1, N2)
        x = (i0 + shift) * H[0] + 0 * (i1 + i2)
        y = (i1 + shift) * H[1] + 0 * (i0 + i2)
        z = (i2 + shift) * H[2] + 0 * (i0 + i1)
        pos = jnp.stack([x.reshape(-1), y.reshape(-1), z.reshape(-1)],
                        axis=-1).astype(dtype)
        if self.comm is not None:
            pos = shard_leading(self.comm, pos)
        return pos

    def reshape(self, Nmesh):
        """A new ParticleMesh with a different resolution, same box/mesh
        (reference: pm.reshape at base/mesh.py:320, for resampling)."""
        return ParticleMesh(Nmesh, self.BoxSize, self.dtype, self.comm)


#: device memory by ``device_kind`` for backends that report no
#: ``bytes_limit``.  The CPU backend is one: a CPU run rehearses the
#: plans of one TPU v5e chip (published: 16 GB; the attached chip
#: reports 16909336064).  A kind with no figure is an error.
HBM_BYTES = {'cpu': 16e9}


def device_hbm_bytes(device):
    """The memory of ``device`` that plans and admission price
    against: what the device itself reports, else the
    :data:`HBM_BYTES` figure for its ``device_kind``.  Called once by
    an entry point that owns a device (the server,
    ``chip_smoke.py``); everything below takes ``hbm_bytes``."""
    limit = (device.memory_stats() or {}).get('bytes_limit')
    if limit:
        return float(limit)
    if device.device_kind not in HBM_BYTES:
        raise KeyError(
            'device_kind %r reports no memory limit and has no figure '
            'in pmesh.HBM_BYTES; pass hbm_bytes' % device.device_kind)
    return HBM_BYTES[device.device_kind]


def memory_plan(Nmesh, npart, ndevices=1, dtype='f4', resampler='cic',
                paint_method='mxu', paint_chunk=None,
                paint_streams=None, hbm_bytes=None, exchange='counted',
                exchange_imbalance=1.5, fft_decomp='slab',
                fft_pencil=None, ingest_chunk_rows=None,
                catalog_bytes=None, workload='fftpower',
                pm_steps=None, nbins=None, bspec_method='fft',
                pairblock_tile=None):
    """Estimated peak per-device HBM for the FFTPower pipeline
    (paint -> rFFT -> |delta_k|^2 -> chunked binning) — the arithmetic
    behind chunk-size choices and the BASELINE.md scale claims
    (Nmesh=1024/1e8 on one v5e chip; Nmesh=2048/1e9 on v5e-16).

    Returns a dict of per-phase byte estimates and ``peak_bytes``;
    with ``hbm_bytes`` (the caller's device, see
    :func:`device_hbm_bytes` — the plan itself is arithmetic and asks
    no backend) also ``budget_bytes``, ``headroom_bytes`` and ``fits``,
    judged with a 15% allocator margin.  Estimates, not guarantees —
    XLA's actual buffers vary; the model errs high on the FFT workspace (2x the
    complex field for the out-of-place transposed passes).

    ``exchange`` models the multi-device particle routing buffers:
    'counted' assumes the two-pass counted capacity (eager
    :func:`~nbodykit_tpu.parallel.exchange.counted_capacity` feeding a
    static ~npart/P^2 * ``exchange_imbalance`` per-pair buffer —
    pass 1 of the two-pass exchange); 'ceil' is the traced fallback
    bound ceil(N/P) per pair (npart payload slots per device — the
    safe-but-fat bound that cannot sit next to a 2048^3 mesh).

    ``fft_decomp='pencil'`` (multi-device) swaps the slab FFT
    workspace for the pencil path's staging buffers: exactly
    :data:`~nbodykit_tpu.parallel.dfft.PENCIL_BUFFERS` (= 2) padded
    complex pencil units per device — stage 1's output plus stage 2's
    output, stage 2 donating stage 1's intermediate — where the pad
    grows the Hermitian z length Nc = N2//2+1 to the next multiple of
    Py (``fft_pencil`` = (Px, Py); near-square default).  The report
    gains ``fft_pencil_buffers`` / ``fft_pencil`` keys so the smoke
    gate can assert the documented count at the 1024^3 config.

    ``dtype='bf16'`` prices the half-storage mesh pipeline: the real
    field and the streams-paint replica meshes are billed at 2 bytes
    per cell, while everything that computes — FFT workspace, complex
    field, positions, deposit terms, exchange payloads — stays at the
    f32 compute width (the storage/compute split of docs/PERF.md
    "Halving the bytes").  The report's ``mesh_dtype`` /
    ``mesh_itemsize`` keys record what was priced so admission
    rejections can quote it.

    ``workload='forward'`` prices the differentiable LPT/PM pipeline
    (nbodykit_tpu.forward, docs/FORWARD.md) instead of the FFTPower
    one: ``pm_steps`` kick-drift-kick steps, each a paint -> Poisson
    solve -> 3-component force readout, differentiated end to end
    with ``jax.grad``.  The forward pass adds the particle *state*
    (positions + momenta, 6 compute words per particle) and the three
    per-axis force meshes to the usual mesh pipeline; the REVERSE
    pass is the honest part — ``jax.grad`` holds each step's saved
    primals (the particle state plus two live mesh buffers: painted
    density and potential) across the whole backward sweep, so the
    residual term scales LINEARLY with ``pm_steps`` and the backward
    peak roughly doubles the per-step live mesh working set.  The
    report carries ``forward_state_bytes`` / ``grad_residual_bytes``
    / ``workload`` / ``pm_steps`` so an admission rejection can quote
    exactly which term broke the budget.

    ``workload='bispectrum'`` prices the hybrid higher-order estimator
    (nbodykit_tpu.algorithms.bispectrum, docs/BISPECTRUM.md).  The
    FFT path streams per-shell filtered fields through one compiled
    triple-product program, so its peak holds exactly THREE real
    fields next to the complex spectrum and the transform workspace —
    ``nbins`` shifts the triangle count, not the residency.  The
    direct path (``bspec_method='direct'``) holds no mesh at all: its
    peak is the O(tile^2) dense phase blocks of ops/pairblock
    (``pairblock_tile``; phases + cos/sin images + the weight GEMV,
    billed 4 tile^2 compute words erring high on fusion) plus the
    per-mode accumulators of the ~(4 pi / 3)(nbins+1)^3 lattice modes.
    The report carries ``workload`` / ``nbins`` / ``bspec_method`` and
    the dominant term (``shell_fields_bytes`` or ``pairblock_bytes``)
    so a rejection can quote which estimator broke the budget.

    ``ingest_chunk_rows`` prices the streaming-ingestion pipeline of a
    ``data_ref`` request (nbodykit_tpu.ingest): the resident sharded
    catalog replaces the synthetic ``positions`` term (positions PLUS
    the mass column, 4 compute words per row), and the double-buffered
    H2D staging adds two in-flight padded chunks during the paint
    phase.  ``catalog_bytes`` (total per-DEVICE resident catalog-cache
    bytes, this entry included) overrides the single-entry default so
    admission can price an eviction decision: the cache's
    ``fits(resident)`` predicate is exactly this plan re-asked at a
    candidate residency.
    """
    N = _triplet(Nmesh, 'i8')
    ndev = max(int(ndevices), 1)
    from .utils import mesh_storage_dtype
    sdt = mesh_storage_dtype(dtype)
    item = sdt.itemsize          # STORAGE width: mesh buffers
    citem = max(item, 4)         # COMPUTE width: everything else
    ncells = float(np.prod(N))
    s = window_support(resampler or 'cic')

    real = item * ncells / ndev
    cplx = 2 * citem * (N[0] * N[1] * (N[2] // 2 + 1)) / ndev
    fft_ws = 2 * cplx
    pencil_extra = {}
    if fft_decomp == 'pencil' and ndev > 1:
        from .parallel.dfft import PENCIL_BUFFERS
        if fft_pencil is None:
            from .parallel.runtime import default_pencil_factor
            fft_pencil = default_pencil_factor(ndev)
        px, py = int(fft_pencil[0]), int(fft_pencil[1])
        nc = int(N[2]) // 2 + 1
        ncp = nc + (-nc % py)
        # one padded complex pencil unit per device; the eager path
        # holds PENCIL_BUFFERS of them at peak (stage-1 out + stage-2
        # out, stage 2 donating) — same 2x count as the slab model,
        # scaled by the z pad that makes Nc divisible by Py
        stage = 2 * citem * (N[0] * N[1] * ncp) / ndev
        fft_ws = PENCIL_BUFFERS * stage
        pencil_extra = {'fft_pencil': '%dx%d' % (px, py),
                        'fft_pencil_buffers': PENCIL_BUFFERS,
                        'fft_pencil_pad': float(ncp) / float(nc)}
    pos_b = 3 * citem * npart / ndev
    ingest_extra = {}
    ingest_buf = 0.0
    if ingest_chunk_rows is not None:
        # the resident catalog entry (pos + mass, 4 compute words per
        # row, row-sharded) IS this pipeline's particle storage; a
        # caller-supplied total residency (other cache entries
        # included) replaces the single-entry default
        entry_b = 4 * citem * npart / ndev
        pos_b = float(catalog_bytes) / ndev \
            if catalog_bytes is not None else entry_b
        pos_b = max(pos_b, entry_b)
        # two in-flight padded host chunks (double buffer) staged on
        # device during the streaming paint
        ingest_buf = 2 * 4 * citem * float(ingest_chunk_rows) / ndev
        ingest_extra = {'catalog_bytes': pos_b,
                        'ingest_chunk_buffers': ingest_buf}
    if paint_chunk is None:
        chunk = _global_options['paint_chunk_size']
    else:
        chunk = paint_chunk
    live = min(npart / ndev, chunk)
    # the default engine is the tile deposit wherever the local block
    # (a slab with the window's halo either side) admits its tiles
    tiles = tile_geometry(
        (max(int(N[0]) // ndev, 1) + (2 * s if ndev > 1 else 0),
         int(N[1]), int(N[2])), resampler) \
        if paint_method == 'mxu' else None
    if paint_method == 'sort':
        # all s^3 deposit terms live at once: (key i32 + val) pairs,
        # doubled by the sort's out-of-place buffers
        paint_tmp = (s ** 3) * (4 + citem) * (npart / ndev) * 2
    elif paint_method == 'segsum':
        # same one-sort streams as 'sort', plus the segment_sum's
        # (n, s^3) totals and gathered run_tot buffers
        paint_tmp = ((s ** 3) * (4 + citem) * (npart / ndev) * 2
                     + 2 * (s ** 3) * citem * (npart / ndev))
    elif paint_method == 'streams':
        # k replica meshes (full mesh units each — THE cost of
        # breaking the scatter chain) next to the live chunk's
        # deposit terms
        if paint_streams is None:
            paint_streams = _global_options['paint_streams']
        k = max(int(paint_streams), 1)
        # replicas are STORAGE dtype (bf16 halves THE dominant term
        # of this method); the live chunk's deposit terms compute f32
        paint_tmp = k * real + (s ** 3) * (4 + citem) * live
    elif tiles is not None:
        # the tile deposit (ops/paint.py:paint_local_mxu): the mesh
        # with its wrap stripe and halo rows, alive beside the block
        # it is folded into; the four sorted columns the pieces read;
        # and, one after the other in the same bytes, the sort's other
        # buffers (the key and the columns it came from) and one
        # piece's operands (the z one-hots and the weights' three
        # parts in bf16, the f32 x*y expansion) with a stripe's f32
        # accumulator, read and written.  Compiled for a v5e at 512^3
        # / 1e7 the program's temporaries are 0.708 GB for the 0.95
        # this prices
        nl = npart / ndev
        rb, cb, ntx, nty = tiles
        M = (rb + s - 1) * (cb + s - 1)
        N1, N2 = int(N[1]), int(N[2])
        rows = nty * PIECE_ROWS
        piece = rows * (s * (N2 + 3 * M) * 2 + 2 * M * citem) \
            + 2 * nty * 3 * M * N2 * citem
        mesh_pad = ((ntx + 1) * rb + s - 1) * N1 * N2 * citem
        paint_tmp = mesh_pad + 4 * 4 * nl + max(6 * 4 * nl, piece)
    else:
        paint_tmp = (s ** 3) * (4 + citem) * live
    p3 = cplx / 2               # |delta_k|^2 as real of the half-spec
    # multi-device particle routing: send + recv all_to_all buffers,
    # (P, capacity) payload slots each (pos 3*item + mass item + live
    # byte + dest i4). capacity per (src,dst) pair:
    #   counted: ~npart/P^2 * imbalance (two-pass counted exchange)
    #   ceil:    ceil(npart/P)          (traced always-sufficient)
    if ndev > 1:
        payload = 3 * citem + citem + 1 + 4
        if exchange == 'ceil':
            cap = -(-npart // ndev)
        else:
            cap = npart / (ndev * ndev) * exchange_imbalance
        exch = 2 * ndev * cap * payload
    else:
        exch = 0.0
    phases = {
        'real_field': real,
        'complex_field': cplx,
        'fft_workspace': fft_ws,
        'positions': pos_b,
        'paint_temporaries': paint_tmp,
        'exchange_buffers': exch,
        'power3d': p3,
        'mesh_dtype': sdt.name,
        'mesh_itemsize': item,
    }
    phases.update(pencil_extra)
    phases.update(ingest_extra)
    # paint phase: field + positions + temporaries + exchange (+ the
    # in-flight ingest staging chunks on the streaming path);
    # fft phase: real + complex + workspace (positions still resident
    # unless donated); binning adds only O(chunk) slabs
    peak = max(real + pos_b + paint_tmp + exch + ingest_buf,
               real + cplx + fft_ws + pos_b,
               cplx + p3 + pos_b)
    if workload == 'bispectrum':
        nb = max(int(nbins or 4), 1)
        if bspec_method == 'direct':
            # no mesh: dense (tile x tile) phase blocks (phase +
            # cos/sin images + the weight GEMV inputs — 4 tile^2
            # compute words, erring high on what XLA fuses) plus the
            # re/im accumulators over the enumerated lattice modes
            if pairblock_tile is None:
                pairblock_tile = _global_options['pairblock_tile']
            t = max(int(pairblock_tile), 8)
            nk = 4.0 * np.pi / 3.0 * float(nb + 1) ** 3
            pair_b = 4.0 * t * t * citem
            acc_b = 4.0 * nk * citem
            peak = pos_b + pair_b + acc_b + exch
            phases['pairblock_bytes'] = pair_b
            phases['pairblock_tile'] = t
        else:
            # the streaming Scoccimarro triple product: the complex
            # spectrum stays resident while each triangle's three
            # shell-filtered REAL fields are c2r'd next to the
            # transform workspace — 3 real + 1 complex at peak,
            # independent of nbins (the triangle loop reuses one
            # compiled program)
            shell_b = 3 * real
            peak = max(real + pos_b + paint_tmp + exch + ingest_buf,
                       cplx + shell_b + fft_ws + pos_b)
            phases['shell_fields_bytes'] = shell_b
        phases['workload'] = 'bispectrum'
        phases['nbins'] = nb
        phases['bspec_method'] = bspec_method
    if workload == 'forward':
        steps = max(int(pm_steps or 1), 1)
        # KDK particle state: positions + momenta, always live
        part_state = 6 * citem * npart / ndev
        # per-axis force meshes read out at the particle positions
        force_fields = 3 * real
        fwd_peak = max(real + part_state + paint_tmp + exch,
                       real + cplx + fft_ws + part_state,
                       real + cplx + force_fields + part_state)
        # reverse-mode residuals: jax.grad keeps each step's saved
        # primals (particle state + painted density + potential mesh)
        # alive across the whole backward sweep — linear in pm_steps —
        # and the backward step re-runs a paint/readout pair, doubling
        # that step's live mesh working set on top of the pile
        residual = steps * (part_state + 2 * real)
        peak = fwd_peak + residual + real + cplx
        phases['workload'] = 'forward'
        phases['pm_steps'] = steps
        phases['forward_state_bytes'] = part_state + force_fields
        phases['grad_residual_bytes'] = residual
    phases['peak_bytes'] = peak
    # the budget the admission controller (nbodykit_tpu.serve) prices
    # against: the raw HBM less the 15% allocator margin.  Exposed so
    # structured rejections can quote the numbers they were judged by.
    if hbm_bytes is not None:
        phases['budget_bytes'] = 0.85 * hbm_bytes
        phases['headroom_bytes'] = 0.85 * hbm_bytes - peak
        phases['fits'] = bool(peak <= 0.85 * hbm_bytes)
    return phases
