"""Particle exchange: route particles to the device that owns their slab.

The reference's equivalent is ``pmesh.domain.GridND.decompose`` +
``layout.exchange`` — an MPI all-to-allv of a ragged particle partition
(used for painting at nbodykit/source/mesh/catalog.py:271-284, FOF at
algorithms/fof.py:401, pair counting at pair_counters/domain.py:116).

XLA wants static shapes, so the ragged all-to-allv becomes a
*fixed-capacity* exchange (SURVEY.md §7 "hard parts" #2):

1. each device computes dest(p) for its local particles;
2. particles are bucketed into a (P, capacity) send buffer by
   sort-by-destination + masked scatter;
3. one ``lax.all_to_all`` ships the buckets;
4. the receive side is a (P, capacity) buffer with a validity mask.

Capacity policy: when called eagerly (the normal case — paint/readout
size their buffers before tracing), :func:`auto_capacity` counts the
*exact* max per-(src,dst) count and rounds its bound up to a rung of
:func:`ladder_capacity`, so overflow cannot happen and catalogs that
are balanced alike share one set of static shapes. Under a
trace, callers must pass an explicit capacity; the ``dropped`` count is
returned so they can detect overflow outside jit and retry larger — the
same contract as the reference's paint-chunk backoff loop
(source/mesh/catalog.py:275-315).

How the eager call runs: the count is one small program
(``compile.exchange.count``) whose result is read back as a Python
int, because the capacity is a static shape of what follows; the pad,
the bucketing, the all_to_alls and the ``psum`` of ``dropped`` are one
jitted program fetched from :func:`_exchange_programs`, cached on
(device mesh, capacity, fill, payload ranks, backend branch), so
catalogs on one rung share one executable and a call traces nothing
(``compile.exchange.hits``).  An eager ``shard_map`` would run the
same body one primitive a program, each traced, lowered and looked up
in the compile cache on every call.  The ``exchange`` span, gauges and
counters fire in :func:`exchange_by_dest`, once a call.  A traced
caller gets the raw ``shard_map``, which composes into its program.

For LARGE traced pipelines use the two-pass counted exchange: run
:func:`counted_capacity` eagerly (pass 1 — a tiny count program), then
hand its result to the traced exchange as the static capacity (pass 2)
with ``return_dropped=True``. The traced fallback bound ceil(N/P) is
always sufficient but allocates N payload slots per device — at
N=1e9 that is ~16 GB and cannot sit next to a 2048^3 mesh
(pmesh.memory_plan models both).
"""

from functools import lru_cache as _lru_cache, partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .runtime import AXIS, is_eager, mesh_size
from ..diagnostics import counter, fetch, gauge, instrumented_jit, scope


def counted_capacity(pm_or_nproc, pos_or_dest, slack=1.05, n0=None):
    """Two-pass counted exchange, pass 1: the exact per-(src,dst)
    particle count, run EAGERLY so pass 2 (the traced exchange inside
    the main jit) can size its all_to_all buffers statically.

    The always-sufficient traced default is capacity = ceil(N/P): every
    source may ship its whole shard to one destination. That bound
    makes the send buffer per device N slots — ~16 GB of payload at
    N=1e9 — which cannot sit next to a 2048^3 mesh in HBM. The counted
    bound is ~N/P^2 * imbalance instead (~1000x smaller at P=16), the
    same reason the reference's MPI all-to-allv counts first
    (pmesh.domain.GridND.decompose; consumed at
    nbodykit/source/mesh/catalog.py:271-284).

    Parameters
    ----------
    pm_or_nproc : a ParticleMesh-like (with .nproc — routing is then
        delegated to ``pm.exchange_capacity``, which reuses paint's own
        dest computation including the interlacing ``shift``) or an int
        device count (then ``pos_or_dest`` must be dest indices or raw
        x positions in CELL units with ``n0`` given)
    pos_or_dest : (N, 3) positions, or (N,) int32 dest
    slack : headroom on the counted max (particles may move between
        the count and the exchange only within this margin)
    n0 : slab height in cells (required with positions + int nproc)

    Returns a Python int, usable as the static ``capacity`` of
    :func:`exchange_by_dest` / ``ParticleMesh.paint`` inside jit
    (combine with ``return_dropped=True`` to detect any drift past the
    slack after the step).
    """
    if hasattr(pm_or_nproc, 'nproc'):
        return pm_or_nproc.exchange_capacity(pos_or_dest, slack=slack)
    nproc = int(pm_or_nproc)
    if pos_or_dest.ndim == 2:
        if n0 is None:
            raise ValueError("pass n0 (slab height) with raw "
                             "positions and an int device count")
        dest = jnp.floor(jnp.asarray(pos_or_dest)[:, 0]).astype(
            jnp.int32) // n0
    else:
        dest = jnp.asarray(pos_or_dest, jnp.int32)
    if nproc == 1:
        return int(dest.shape[0])
    return auto_capacity(dest, nproc, slack=slack)


#: ratio of the capacity ladder's rungs, as a fraction (17/16 = 1.0625).
#: The capacity is a static shape of the bucketing, the all_to_alls and
#: the paint kernel's particle axis, so a capacity that follows the
#: exact count gives every catalog its own compiles; a rung 1/16 wide
#: holds the scatter of uniform catalogs of one (N, P) (the bound of
#: 1e7 particles on 4 devices is 1.0515-1.0526 x N/P^2) and costs at
#: most 6.25% of buffer over the exact bound.
RUNG = (17, 16)


def pair_count_max(dest, nproc):
    """Largest particle count over the (src, dst) pairs, assuming
    particles are evenly sharded over devices in index order (the
    layout of a freshly created global array, matching the padding in
    :func:`exchange_by_dest`). Eager only: one small program and the
    read of its result."""
    return int(fetch(_pair_count_max(jnp.asarray(dest, jnp.int32), nproc),
                     'exchange.count'))


@partial(instrumented_jit, label='exchange.count', static_argnums=(1,))
def _pair_count_max(dest, nproc):
    n = dest.shape[0]
    per = -(-n // nproc)  # ceil: matches the even sharding of the pad
    src = jnp.arange(n, dtype=jnp.int32) // per
    counts = jnp.bincount(src * nproc + dest, length=nproc * nproc)
    return counts.max()


def ladder_capacity(exact, n, nproc, slack=1.05):
    """The capacity for a counted maximum of ``exact`` particles a
    (src, dst) pair among ``n`` on ``nproc`` devices: the exact bound
    ``ceil(exact * slack) + 8`` rounded UP to the next rung of the
    geometric ladder ``ceil(ceil(n / nproc^2) * RUNG^k)``, k = 0, 1, ...

    Never below the exact bound, so nothing can drop; a function of the
    counted maximum, not of an assumed balance, so a clustered catalog
    climbs the ladder instead of overflowing."""
    bound = int(np.ceil(int(exact) * slack)) + 8
    base = max(-(-int(n) // (nproc * nproc)), 1)
    num, den = RUNG
    k = 0       # in integers: a rung is the same on every host
    while -(-base * num ** k // den ** k) < bound:
        k += 1
    return -(-base * num ** k // den ** k)


def counted_rung(dest, nproc, slack=1.05):
    """``(exact, capacity)`` for these destinations: the counted
    maximum (:func:`pair_count_max`) and its rung of the capacity
    ladder (:func:`ladder_capacity`). Eager only."""
    exact = pair_count_max(dest, nproc)
    return exact, ladder_capacity(exact, int(dest.shape[0]), nproc,
                                  slack=slack)


def auto_capacity(dest, nproc, slack=1.05):
    """Sufficient per-(src,dst)-pair capacity for an exchange: the
    capacity of :func:`counted_rung`. Cheap; call *outside* jit so the
    result can size static buffers.
    """
    return counted_rung(dest, nproc, slack=slack)[1]


def _bucket_local(dest, arrays, nproc, capacity, fill=0.0, live=None):
    """Pack per-particle payloads into a (nproc, capacity, ...) send buffer.

    dest : (n,) int32 destination device per particle
    arrays : list of (n, ...) payloads
    live : optional (n,) bool — entries counted by `dropped` (dead
        padding slots overflowing a bucket are not data loss)
    Returns (buffers, valid, dropped): buffers[i] has shape
    (nproc, capacity, ...); valid is (nproc, capacity) bool.
    """
    n = dest.shape[0]
    from ..utils import is_mxu_backend
    if is_mxu_backend():
        # TPU path: the destination alphabet is tiny (nproc values), so
        # the per-particle rank within its destination bucket comes
        # straight from the radix counting pass (ops/radix.py) — the
        # slot assignment needs NO sort, no searchsorted, and no
        # permutation of the payloads: (dest, rank) pairs are unique by
        # construction, so the buffer scatter is collision-free. Same
        # layout as the argsort path below (both stable).
        from ..ops.radix import _rank_hist
        dest_key = jnp.clip(jnp.asarray(dest, jnp.int32), 0, nproc - 1)
        rank_in_bucket, _ = _rank_hist(dest_key, nproc, 4096)
        live_a = live
        srcs = arrays
    else:
        order = jnp.argsort(dest)
        dest_key = dest[order]
        # rank of each particle within its destination bucket
        idx = jnp.arange(n, dtype=jnp.int32)
        start = jnp.searchsorted(dest_key,
                                 jnp.arange(nproc, dtype=dest_key.dtype),
                                 side='left')
        rank_in_bucket = idx - start[dest_key]
        live_a = None if live is None else live[order]
        srcs = [a[order] for a in arrays]
    # shared capacity/overflow accounting (branch-independent).
    # i32-audited (nbkl NBK302): slot < nproc*capacity + 1 <= the
    # per-device buffer size, which must fit addressable memory —
    # orders of magnitude inside int32 for any realizable exchange
    ok = rank_in_bucket < capacity
    lost = ~ok if live_a is None else (~ok & live_a)
    dropped = jnp.sum(lost)
    slot = jnp.where(ok, dest_key * capacity + rank_in_bucket,
                     nproc * capacity)
    valid = jnp.zeros((nproc * capacity + 1,), dtype=bool).at[slot].set(True)
    valid = valid[:-1].reshape(nproc, capacity)
    out = []
    for a_s, a in zip(srcs, arrays):
        buf_shape = (nproc * capacity + 1,) + a.shape[1:]
        buf = jnp.full(buf_shape, fill, dtype=a.dtype).at[slot].set(a_s)
        out.append(buf[:-1].reshape((nproc, capacity) + a.shape[1:]))
    return out, valid, dropped


def exchange_by_dest(dest, arrays, mesh, capacity=None, fill=0.0):
    """All-to-all exchange of per-particle payloads keyed by destination.

    Parameters
    ----------
    dest : global (N,) int32, sharded on axis 0 — destination device index
        in [0, P)
    arrays : list of global (N, ...) payloads, sharded on axis 0
    mesh : device mesh (may be None / size 1)
    capacity : int or None — max particles shipped per (src, dst) pair;
        None (only valid eagerly) counts the exact maximum and takes
        its rung of the ladder, as :func:`auto_capacity` does.

    Returns
    -------
    recv : list of global (P * P * capacity, ...) arrays sharded on axis 0
        (each device ends with P * capacity slots)
    valid : matching (P*P*capacity,) bool mask (False = empty slot or
        padding)
    dropped : () int32 — particles lost to capacity overflow; zero by
        construction when capacity=None. Check outside jit.

    N need not divide P: inputs are padded to a multiple of P and the
    padding arrives with valid=False.
    """
    nproc = mesh_size(mesh)
    n = dest.shape[0]
    if nproc == 1:
        return list(arrays), jnp.ones(n, dtype=bool), jnp.zeros((), jnp.int32)

    exact = None        # the counted maximum, where this call counted
    if capacity is None:
        if isinstance(dest, jax.core.Tracer):
            # under a trace we cannot inspect the data: use the always-
            # sufficient bound (one source sends its whole shard to one
            # destination). Memory = P*cap = n slots per device; callers
            # wanting tighter buffers pass capacity explicitly.
            capacity = -(-n // nproc)
        else:
            # counted after padding, as the program below buckets
            exact, capacity = counted_rung(
                _pad_rows(dest, (-n) % nproc), nproc)
    capacity = int(capacity)

    arrays = list(arrays)

    # telemetry: the all_to_all buffer volume is shape-derived (static),
    # so the counters are exact even when this runs under a trace —
    # bytes_sent == bytes_received is the global (P, P, capacity)
    # buffer footprint actually shipped (the payloads and the one-byte
    # live mask), the number the counted exchange exists to shrink
    # (~N/P^2 vs the ceil(N/P) bound).  Counters, gauges and the span
    # fire here, once a call; the program is traced once a shape.
    xbytes = nproc * nproc * capacity * (1 + int(sum(
        int(np.prod(a.shape[1:], dtype=np.int64))
        * jnp.dtype(a.dtype).itemsize for a in arrays)))
    counter('exchange.calls').add(1)
    counter('exchange.bytes_sent').add(xbytes)
    gauge('exchange.capacity').set(capacity)
    filled = n / float(nproc * nproc * capacity)
    gauge('exchange.fill').set(filled)

    from ..utils import is_mxu_backend
    raw, jitted = _exchange_programs(
        mesh, capacity, fill, tuple(a.ndim for a in arrays),
        is_mxu_backend())
    eager = is_eager(dest, *arrays)
    with scope('exchange', nproc=nproc, capacity=capacity,
               capacity_exact=exact, fill=filled, bytes=xbytes,
               npart=int(n)):
        return (jitted if eager else raw)(dest, *arrays)


def _pad_rows(a, npad):
    """``a`` with ``npad`` rows of zeros appended."""
    if not npad:
        return a
    return jnp.concatenate([a, jnp.zeros((npad,) + a.shape[1:], a.dtype)])


@_lru_cache(maxsize=64)
def _exchange_programs(mesh, capacity, fill, ndims, mxu):
    """The exchange of payloads of ranks ``ndims`` at one static
    ``capacity`` as one program: the pad to a multiple of P, the
    bucketing, the all_to_alls, the ``psum`` of ``dropped`` and the
    live mask, cached per everything the body reads (``mxu``: the
    bucketing's backend branch; shapes and dtypes key the jit's own
    cache).  Returns ``(raw, jit)`` as ``dfft._slab_programs`` does:
    the raw callable for an outer trace, the jitted form for the eager
    call."""
    nproc = mesh_size(mesh)

    def local(dest_l, *payloads_l):
        # payloads_l[0] is the live mask: pad entries that overflow a
        # bucket are not real losses
        bufs, valid, dropped = _bucket_local(dest_l, payloads_l, nproc,
                                             capacity, fill,
                                             live=payloads_l[0])
        outs = []
        for b in bufs:
            r = jax.lax.all_to_all(b, AXIS, split_axis=0, concat_axis=0,
                                   tiled=True)
            outs.append(r.reshape((nproc * capacity,) + r.shape[2:]))
        v = jax.lax.all_to_all(valid, AXIS, split_axis=0, concat_axis=0,
                               tiled=True)
        dropped = jax.lax.psum(dropped, AXIS)
        return (v.reshape(-1), dropped) + tuple(outs)

    specs = (P(AXIS),) + tuple(
        P(*((AXIS,) + (None,) * (nd - 1))) for nd in ndims)
    sharded = jax.shard_map(local, mesh=mesh, in_specs=(P(AXIS),) + specs,
                            out_specs=(P(AXIS), P()) + specs)

    def exchange(dest, *arrays):
        # pad the particle axis to a multiple of P; padding goes to
        # dest 0 with live=False and is masked out on arrival
        n = dest.shape[0]
        npad = (-n) % nproc
        live = _pad_rows(jnp.ones(n, dtype=bool), npad)
        res = sharded(_pad_rows(dest, npad), live,
                      *[_pad_rows(a, npad) for a in arrays])
        slot_valid, dropped, live_recv = res[0], res[1], res[2]
        return list(res[3:]), slot_valid & live_recv, dropped

    return exchange, instrumented_jit(exchange, label='exchange')
