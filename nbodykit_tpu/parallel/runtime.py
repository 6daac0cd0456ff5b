"""Device-mesh runtime: the ambient parallel context.

The reference's ambient context is a stack of MPI communicators
(``CurrentMPIComm``, nbodykit/__init__.py:107-190) injected into every
distributed object. Here the ambient context is a ``jax.sharding.Mesh``
over the available devices — or ``None``, meaning single-device execution
with no collectives.

Conventions
-----------
- The default device mesh is 1-D with axis name ``'dev'``. 3-D fields are
  slab decomposed: a real field of global shape (N0, N1, N2) is sharded
  ``P('dev', None, None)``; catalogs shard their particle axis the same way.
- A *pencil* mesh is 2-D with axes ``('x', 'y')`` (:func:`pencil_mesh`);
  fields are then sharded ``P('x', 'y', None)`` and the distributed FFT
  transposes twice (inner over ``'y'``, outer over ``'x'``) instead of
  once over the whole fleet. On multi-slice hardware the ``'x'`` axis is
  laid out across slices (DCN) and ``'y'`` within a slice (ICI).
- ``CurrentMesh.get()`` returns the ambient mesh (possibly ``None``) and
  accepts either rank. Constructors accept ``comm=`` (kept for
  familiarity with the reference API) holding a ``jax.sharding.Mesh``.
"""

import math
import os
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = 'dev'
# pencil (2-D) mesh axis names: 'x' is the outer/slow axis (DCN on
# multi-slice hardware), 'y' the inner/fast axis (ICI within a slice)
AXIS_X = 'x'
AXIS_Y = 'y'


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None, local_device_ids=None):
    """Multi-host bootstrap: connect this process to the global device
    mesh (the reference's analog is MPI_Init + COMM_WORLD; SURVEY.md
    §2.2.7 / M8 calls for jax.distributed + multi-slice meshes).

    Arguments default to the standard environment variables
    (JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES, JAX_PROCESS_ID), so a
    launcher (SLURM, GKE, a shell loop of processes) can configure the
    job without code changes — the moral equivalent of ``srun -n 16
    python example.py`` in the reference's production jobs
    (reference nersc/example-job.slurm:11).

    After this call ``jax.devices()`` enumerates the devices of ALL
    processes and :func:`world_mesh` spans them; jitted collectives ride
    ICI within a slice and DCN across hosts. No-op when neither
    arguments nor environment variables request a multi-process setup.
    """
    coordinator_address = coordinator_address or \
        os.environ.get('JAX_COORDINATOR_ADDRESS')
    if num_processes is None:
        num_processes = int(os.environ.get('JAX_NUM_PROCESSES', 0)) \
            or None
    if process_id is None:
        pid = os.environ.get('JAX_PROCESS_ID')
        process_id = int(pid) if pid is not None else None
    if coordinator_address is None and num_processes is None:
        return False
    from ..diagnostics import span
    with span('runtime.init_distributed',
              coordinator=str(coordinator_address),
              num_processes=num_processes, process_id=process_id):
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id,
            local_device_ids=local_device_ids)
    return True


def world_mesh():
    """A 1-D mesh over every device of every connected process (the
    COMM_WORLD analog). Identical to :func:`tpu_mesh` on one process;
    after :func:`init_distributed` it spans the whole job."""
    return Mesh(np.array(jax.devices()), (AXIS,))


def process_index():
    """This process's index in the multi-host job (0 on one host) —
    the 'rank' for host-side work like rank-0-only logging."""
    return jax.process_index()


def process_count():
    """Number of processes in the multi-host job (1 on one host) —
    the fleet size coordinated checkpoints shard over."""
    return jax.process_count()


def reform_decomposition(old_nranks, new_nranks, ndev_per_rank=None):
    """The shrink-to-survive mesh plan when a relaunch runs with
    ``new_nranks`` processes instead of ``old_nranks``: the slab
    re-slices (rank r of the new fleet takes its contiguous span of
    the concatenated rows — resilience/fleet.py ``repartition``), and
    the pencil factorization is re-derived from the surviving device
    count via :func:`default_pencil_factor`.  Returns the dict the
    resumed run stamps into its records (``reformed_from`` /
    ``reformed_to`` plus the pencil factors when the per-rank device
    count is known)."""
    out = {'reformed_from': int(old_nranks),
           'reformed_to': int(new_nranks)}
    if ndev_per_rank:
        out['pencil_from'] = list(default_pencil_factor(
            int(old_nranks) * int(ndev_per_rank)))
        out['pencil_to'] = list(default_pencil_factor(
            int(new_nranks) * int(ndev_per_rank)))
    return out


def single_device_mesh(device=None):
    """A 1-device mesh (collectives become no-ops)."""
    if device is None:
        device = jax.devices()[0]
    return Mesh(np.array([device]), (AXIS,))


def cpu_mesh(n=None):
    """A 1-D mesh over n CPU devices (for testing multi-device logic).

    Requires ``JAX_NUM_CPU_DEVICES`` (or the xla_force_host_platform flag)
    to have been set before jax initialization for n > 1.
    """
    devs = jax.devices('cpu')
    if n is not None:
        devs = devs[:n]
    return Mesh(np.array(devs), (AXIS,))


def tpu_mesh(n=None):
    """A 1-D mesh over the available accelerator devices."""
    devs = jax.devices()
    if n is not None:
        devs = devs[:n]
    return Mesh(np.array(devs), (AXIS,))


def default_pencil_factor(n):
    """The default (Px, Py) factorization of ``n`` devices: the most
    nearly square factor pair with Px <= Py, so the outer ('x') axis —
    the one that rides DCN on multi-slice hardware — is the smaller.
    8 -> (2, 4), 16 -> (4, 4), 7 -> (1, 7)."""
    px = int(math.isqrt(n))
    while n % px:
        px -= 1
    return px, n // px


def _slice_groups(devices):
    """Group devices by slice (DCN domain). Devices without a
    slice_index (CPU, single-slice TPU) land in one group."""
    groups = {}
    for d in devices:
        groups.setdefault(getattr(d, 'slice_index', 0), []).append(d)
    return [groups[k] for k in sorted(groups)]


def pencil_mesh(px=None, py=None, devices=None):
    """A 2-D ``Mesh(('x', 'y'))`` over the devices, for the pencil FFT.

    When the job spans multiple slices (DCN present) and the slice count
    divides Px, the mesh is built with
    ``mesh_utils.create_hybrid_device_mesh`` so the ``'x'`` axis is laid
    out across slices — the outer FFT transpose then rides DCN while the
    inner one stays on ICI (SNIPPETS.md [1] idiom). Otherwise the 1-D
    device list is plainly reshaped to (Px, Py), which on a single slice
    (or CPU) makes the flattened (x, y) device order identical to the
    1-D slab mesh — so slab- and pencil-sharded fields coexist without
    data movement.

    ``px``/``py`` default to :func:`default_pencil_factor`; passing one
    of them infers the other. ``py=1`` degenerates to the slab layout.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    if px is None and py is None:
        px, py = default_pencil_factor(n)
    elif px is None:
        px = n // int(py)
    elif py is None:
        py = n // int(px)
    px, py = int(px), int(py)
    if px < 1 or py < 1 or px * py != n:
        raise ValueError(
            "pencil factorization %dx%d does not cover %d devices"
            % (px, py, n))
    groups = _slice_groups(devices)
    nslice = len(groups)
    if nslice > 1 and px % nslice == 0 and \
            all(len(g) == n // nslice for g in groups):
        try:
            from jax.experimental import mesh_utils
            arr = mesh_utils.create_hybrid_device_mesh(
                (px // nslice, py), (nslice, 1), devices=devices)
            return Mesh(arr, (AXIS_X, AXIS_Y))
        except Exception:
            pass  # topology not understood -> plain reshape below
    return Mesh(np.array(devices).reshape(px, py), (AXIS_X, AXIS_Y))


def is_pencil(mesh):
    """True when ``mesh`` is a 2-D pencil mesh with ('x', 'y') axes."""
    return mesh is not None and tuple(mesh.axis_names) == (AXIS_X, AXIS_Y)


def mesh_shape2d(mesh):
    """The (Px, Py) shape of a pencil mesh, or None for slab/None."""
    if not is_pencil(mesh):
        return None
    return (mesh.shape[AXIS_X], mesh.shape[AXIS_Y])


def leading_axes(mesh):
    """The mesh axis name(s) a field's leading dimension shards over:
    ``'dev'`` on the slab mesh, ``('x', 'y')`` flattened on a pencil."""
    if is_pencil(mesh):
        return (AXIS_X, AXIS_Y)
    return AXIS


class CurrentMesh(object):
    """A stack of ambient device meshes, mirroring the reference's
    ``CurrentMPIComm`` stack semantics (nbodykit/__init__.py:107-190).

    The stack is *per-thread* so :class:`...batch.TaskManager` can farm
    tasks to device sub-meshes on worker threads concurrently, each
    with its own ambient mesh (the reference's analog: per-worker
    sub-communicators pushed inside TaskManager.__enter__,
    batch.py:110-151). A thread's stack is seeded with the MAIN
    thread's current mesh at first use, so user-spawned threads inherit
    the ambient context instead of silently falling back to
    single-device.
    """

    _tls = threading.local()
    _main_stack = [None]

    @classmethod
    def _stack(cls):
        if threading.current_thread() is threading.main_thread():
            return cls._main_stack
        st = getattr(cls._tls, 'stack', None)
        if st is None:
            st = [cls._main_stack[-1]]
            cls._tls.stack = st
        return st

    @classmethod
    def get(cls):
        """The current ambient mesh (``None`` → single-device)."""
        return cls._stack()[-1]

    @classmethod
    def push(cls, mesh):
        cls._stack().append(mesh)

    @classmethod
    def pop(cls):
        st = cls._stack()
        if len(st) == 1:
            raise RuntimeError("cannot pop the root mesh")
        return st.pop()

    @classmethod
    def resolve(cls, comm):
        """Resolve a ``comm=`` argument: explicit mesh wins, else ambient."""
        if comm is not None:
            return comm
        return cls.get()


class use_mesh(object):
    """Context manager pushing a device mesh as the ambient context::

        with use_mesh(tpu_mesh()):
            cat = UniformCatalog(nbar, BoxSize, seed=42)
    """

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        CurrentMesh.push(self.mesh)
        return self.mesh

    def __exit__(self, *args):
        CurrentMesh.pop()


def vary_like(x, *like):
    """``x`` cast to vary over every manual (``shard_map``) axis that
    any of ``like`` varies over; the identity outside ``shard_map``.

    A loop carry inside ``shard_map`` that starts from a constant is
    replicated, while the body folds device-local data into it; jax's
    varying-manual-axes check refuses a carry whose type changes, so
    the initial value is cast to the data's type first.  The one
    spelling of that cast in the package."""
    x = jax.numpy.asarray(x)
    want = set()
    for ref in like:
        want |= set(jax.typeof(ref).vma)
    need = tuple(sorted(want - set(jax.typeof(x).vma)))
    return jax.lax.pcast(x, need, to='varying') if need else x


def is_eager(*operands):
    """True where none of ``operands`` is being traced.  The one test
    by which a caller picks between the two forms of a cached program
    pair ``(raw, jitted)``: ``(jitted if is_eager(x) else raw)(x)``,
    the jitted form eagerly, the raw ``shard_map`` under an outer
    trace, where it composes into the caller's program."""
    return not any(isinstance(a, jax.core.Tracer) for a in operands)


def mesh_size(mesh):
    """Total number of devices in the mesh (1 when mesh is None).

    Accepts either rank: the 1-D slab mesh or a 2-D pencil mesh.
    """
    if mesh is None:
        return 1
    return int(math.prod(mesh.shape.values()))


def sharding(mesh, *spec):
    """NamedSharding for the given partition spec on this mesh, or None."""
    if mesh is None:
        return None
    return NamedSharding(mesh, P(*spec))


def shard_leading(mesh, arr):
    """Place a global array so its leading axis is sharded over the mesh.

    Ragged sizes (leading axis not divisible by the mesh) are returned
    unsharded — the catalog-column convention: such arrays get
    distributed by the next exchange, which pads internally
    (base/catalog.py __setitem__, parallel/exchange.py).
    """
    if mesh is None:
        return arr
    n = mesh_size(mesh)
    if arr.shape[0] % n:
        return arr
    spec = (leading_axes(mesh),) + (None,) * (arr.ndim - 1)
    from ..diagnostics import counter, span_if
    eager = not isinstance(arr, jax.core.Tracer)
    nbytes = int(getattr(arr, 'nbytes', 0) or 0)
    if eager:
        counter('runtime.device_put_bytes').add(nbytes)
    with span_if(eager and nbytes > (1 << 20), 'runtime.shard_leading',
                 bytes=nbytes):
        return jax.device_put(arr, NamedSharding(mesh, P(*spec)))


def replicate(mesh, arr):
    """Place an array fully replicated over the mesh."""
    if mesh is None:
        return arr
    from ..diagnostics import counter
    if not isinstance(arr, jax.core.Tracer):
        counter('runtime.device_put_bytes').add(
            int(getattr(arr, 'nbytes', 0) or 0))
    return jax.device_put(arr, NamedSharding(mesh, P()))
