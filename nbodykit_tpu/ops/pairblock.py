"""Blocked direct-summation Fourier modes — dense pairwise phases on
the MXU.

The direct estimator of a density mode at wavevector ``k_q`` is the
O(Npart x Nk) sum

    delta(k_q) = sum_j w_j exp(-i k_q . x_j)

(the forward sign of ``pmesh.r2c``; PAPERS.md 2005.01739 shows the
direct sum *beating* FFT estimators at high k, where an FFT would need
a prohibitively fine mesh to avoid aliasing).  Unlike every other
workload in the repo — paint (scatter-bound), FFT (all_to_all-bound),
forward (both) — this sum is pure dense FLOPs, and it is shaped for
the MXU on purpose:

- a (tile_p, 3) block of positions against a (3, tile_k) block of
  wavevectors is one dense matmul producing the (tile_p, tile_k)
  phase block ``ph = pos @ kvecs.T``;
- the particle-axis contraction of its cos/sin images against the
  weights, ``w @ cos(ph)`` / ``w @ sin(ph)``, is a second dense
  matmul (a (1, tile_p) x (tile_p, tile_k) GEMV batch).

Both ride the systolic array; only O(tile_p x tile_k) intermediates
are ever live (the ``pairblock_tile`` option bounds them).  The
blocked-accumulate structure — fori_loop over tiles, dynamic_slice
in, dynamic_update_slice out — is the idiom of
``algorithms/threeptcf.py``; the distributed driver shards particles
over the 1-D device mesh and ``psum``s the (small) mode vector, so no
device ever holds the full catalog.

Precision: phases are computed in the position dtype.  Callers needing
mode-exact sums (the bispectrum oracle tests) pass f8 positions under
x64; the accumulators always widen to the phase dtype.
"""

import numpy as np
import jax
import jax.numpy as jnp

from functools import partial

from ..parallel.runtime import vary_like


def _pad_rows(x, n, fill=0):
    """Pad the leading axis of ``x`` up to ``n`` rows with ``fill``."""
    m = int(x.shape[0])
    if m == n:
        return x
    pad = jnp.full((n - m,) + tuple(x.shape[1:]), fill, x.dtype)
    return jnp.concatenate([x, pad])


@partial(jax.jit, static_argnames=('tile_p', 'tile_k'))
def _pairblock_tiles(pos, w, kvecs, tile_p, tile_k):
    """The jit-pure tiled accumulation: ``(re, im)`` with
    ``re[q] = sum_j w_j cos(k_q . x_j)`` and the matching sin sum.

    ``pos`` is (Np, 3) with Np a multiple of ``tile_p`` (zero-weight
    padding rows contribute exactly 0), ``kvecs`` is (Nk, 3) with Nk a
    multiple of ``tile_k`` (padding rows are discarded by the caller).
    """
    Np = int(pos.shape[0])
    Nk = int(kvecs.shape[0])
    npt = Np // tile_p
    nkt = Nk // tile_k
    acc_dtype = jnp.result_type(pos.dtype, w.dtype)

    def kbody(ik, acc):
        re_acc, im_acc = acc
        kt = jax.lax.dynamic_slice(kvecs, (ik * tile_k, 0),
                                   (tile_k, 3))

        def pbody(ip, cs):
            re, im = cs
            pt = jax.lax.dynamic_slice(pos, (ip * tile_p, 0),
                                       (tile_p, 3))
            wt = jax.lax.dynamic_slice(w, (ip * tile_p,), (tile_p,))
            # dense (tile_p, tile_k) phase block — the MXU shape
            ph = pt @ kt.T
            re = re + wt @ jnp.cos(ph)
            im = im + wt @ jnp.sin(ph)
            return re, im

        zero = vary_like(jnp.zeros((tile_k,), acc_dtype), pos, w)
        re_t, im_t = jax.lax.fori_loop(0, npt, pbody, (zero, zero))
        return (jax.lax.dynamic_update_slice(re_acc, re_t,
                                             (ik * tile_k,)),
                jax.lax.dynamic_update_slice(im_acc, im_t,
                                             (ik * tile_k,)))

    zeros = vary_like(jnp.zeros((Nk,), acc_dtype), pos, w)
    return jax.lax.fori_loop(0, nkt, kbody, (zeros, zeros))


def pairblock_sum(pos, w, kvecs, tile=None, comm=None):
    """``sum_j w_j exp(-i k_q . x_j)`` for every row ``k_q`` of
    ``kvecs`` — the blocked direct Fourier sum.

    pos : (Np, 3) positions (any float dtype; phases accumulate in it)
    w : (Np,) weights
    kvecs : (Nk, 3) wavevectors (host numpy or jnp)
    tile : tile edge for both the particle and mode axes; ``None``
        takes the ``pairblock_tile`` option.
    comm : optional 1-D device mesh; when given, particles are sharded
        over it and the mode vector is ``psum``-reduced — each device
        runs the identical tiled program on its slab of the catalog.

    Returns a complex (Nk,) array ``re - 1j * im``.
    """
    from ..parallel.runtime import mesh_size

    pos = jnp.asarray(pos)
    w = jnp.asarray(w, dtype=pos.dtype)
    kvecs = jnp.asarray(kvecs, dtype=pos.dtype)
    Nk = int(kvecs.shape[0])
    if tile is None:
        from .. import _global_options
        tile = _global_options['pairblock_tile']
    tile = max(int(tile), 8)

    nproc = mesh_size(comm)
    tile_k = min(tile, max(8, Nk))
    nk_pad = -(-Nk // tile_k) * tile_k
    kv = _pad_rows(kvecs, nk_pad)

    if comm is None or nproc == 1:
        Np = int(pos.shape[0])
        tile_p = min(tile, max(8, Np))
        np_pad = -(-Np // tile_p) * tile_p
        re, im = _pairblock_tiles(_pad_rows(pos, np_pad),
                                  _pad_rows(w, np_pad),
                                  kv, tile_p, tile_k)
        return (re - 1j * im)[:Nk]

    # distributed: zero-weight-pad the catalog so every device gets an
    # equal, tile-aligned slab; psum the (small) mode vector
    from jax.sharding import PartitionSpec as P
    from ..parallel.runtime import AXIS, shard_leading

    Np = int(pos.shape[0])
    per = -(-Np // nproc)
    tile_p = min(tile, max(8, per))
    per = -(-per // tile_p) * tile_p
    np_pad = per * nproc
    pos_p = shard_leading(comm, _pad_rows(pos, np_pad))
    w_p = shard_leading(comm, _pad_rows(w, np_pad))

    def local(p, wv):
        re, im = _pairblock_tiles(p, wv, kv, tile_p, tile_k)
        return jax.lax.psum(jnp.stack([re, im]), AXIS)

    # one distributed launch sums every tile; the inner
    # _pairblock_tiles jit (keyed on static tile sizes) carries the
    # warm cache across calls
    out = jax.jit(jax.shard_map(  # nbkl: disable=NBK202
        local, mesh=comm,
        in_specs=(P(AXIS, None), P(AXIS)),
        out_specs=P()))(pos_p, w_p)
    return (out[0] - 1j * out[1])[:Nk]


def lattice_kvecs(qvecs, BoxSize):
    """Physical wavevectors ``(2 pi / L) * q`` for integer lattice mode
    triples ``qvecs`` (host numpy, (Nk, 3) int) — the bispectrum's
    direct-path mode list."""
    q = np.asarray(qvecs, dtype='f8')
    L = np.ones(3) * np.asarray(BoxSize, dtype='f8')
    return q * (2.0 * np.pi / L)
