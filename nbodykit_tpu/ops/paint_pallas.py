"""Pallas TPU deposit kernel for the mxu paint.

``paint_local_mxu`` (ops/paint.py) deposits particles as per-tile MXU
matmuls, but its XLA form materializes the one-hot expansions W0Y
(K, M) and Z (K, N2) in HBM — at 512^3/1e7 that is ~100 GB of one-hot
traffic, an order of magnitude more than every other stream combined.
This kernel fuses the one-hot build and the matmul in VMEM: per
(y-tile, piece) grid step it reads only the particle payload
(x, y, z, mass — 16 B/slot), builds W0Y/Z as VMEM temporaries, and
accumulates the (M, N2) tile block with one MXU ``dot_general``. HBM
traffic drops to payload-in + blocks-out.

Semantics are EXACTLY those of the XLA ``piece()`` path (same rloc/
yloc/wrap arithmetic, same trash handling via mass=0 slots); asserted
bitwise against it in tests/test_paint_pallas.py. Reference analog:
pmesh's C CIC paint consumed at nbodykit/source/mesh/catalog.py:287-296.

Layout notes:
- payload components arrive as SEPARATE (nty, npieces, ck) arrays
  (an (..., 3) position block would be lane-padded 3 -> 128 in VMEM);
- the stripe index ``txi`` (a traced scan carry in the caller) rides
  in SMEM;
- grid = (nty, npieces), pieces innermost: the output block (1, M, N2)
  is revisited across pieces and initialized at piece 0.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .window import window_support, window_base, bspline


def _deposit_kernel(tx_ref, x_ref, y_ref, z_ref, m_ref, o_ref, *,
                    resampler, rb, cb, n0l, p0, N1, N2, origin, dtype):
    s = window_support(resampler)
    rbh, cbh = rb + s - 1, cb + s - 1
    M = rbh * cbh
    ty = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros((1, M, N2), dtype)

    tx = tx_ref[0]
    x = x_ref[0, :]
    y = y_ref[0, :]
    z = z_ref[0, :]
    m = m_ref[0, :].astype(dtype)
    ck = x.shape[0]

    b0 = window_base(x, resampler)
    b1 = window_base(y, resampler)
    b2 = window_base(z, resampler)
    r0 = jnp.mod(b0 - origin, p0)
    r0 = jnp.where(r0 >= n0l, r0 - p0, r0)
    rloc = jnp.clip(r0 + rb - tx * rb, 0, rb - 1)
    y0 = jnp.mod(b1, N1)
    yloc = y0 - ty * cb

    col_i = jax.lax.broadcasted_iota(jnp.int32, (ck, M), 1)
    z_i = jax.lax.broadcasted_iota(jnp.int32, (ck, N2), 1)

    w0y = jnp.zeros((ck, M), dtype)
    for a in range(s):
        w0a = bspline(jnp.abs(x - (b0 + a).astype(x.dtype)), s)
        for b in range(s):
            w1b = bspline(jnp.abs(y - (b1 + b).astype(y.dtype)), s)
            # tile-local: rloc < rb, |yloc| < N1, so col stays far
            # inside int32 for any tile  # nbkl: disable=NBK704
            col = (rloc + a) * cbh + (yloc + b)
            w = (w0a * w1b).astype(dtype) * m
            w0y = w0y + jnp.where(col[:, None] == col_i, w[:, None], 0)
    zm = jnp.zeros((ck, N2), dtype)
    for c in range(s):
        w2c = bspline(jnp.abs(z - (b2 + c).astype(z.dtype)), s)
        zc = jnp.mod(b2 + c, N2)
        zm = zm + jnp.where(zc[:, None] == z_i,
                            w2c.astype(dtype)[:, None], 0)

    o_ref[...] += jax.lax.dot_general(
        w0y, zm, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=dtype)[None]


def deposit_blocks_pallas(txi, sx, sy, sz, sm, *, resampler, rb, cb,
                          n0l, p0, N1, N2, origin, dtype,
                          interpret=False):
    """Per-stripe tile deposit: (nty, M, N2) blocks from the padded
    bucket payload of stripe ``txi``.

    txi : () int32 (traced ok) — x-stripe index
    sx, sy, sz, sm : (nty, npieces, ck) — positions (global cell
        units) and masses in the padded bucket layout; empty slots
        must carry mass 0.
    """
    nty, npieces, ck = sx.shape
    s = window_support(resampler)
    M = (rb + s - 1) * (cb + s - 1)
    kern = functools.partial(
        _deposit_kernel, resampler=resampler, rb=rb, cb=cb, n0l=n0l,
        p0=p0, N1=N1, N2=N2, origin=origin, dtype=dtype)
    grid = (nty, npieces)
    # one (1, ck) row per grid step: the TPU lowering wants the last
    # two block dimensions divisible by (8, 128) or equal to the
    # array's, so the payload is viewed (nty, npieces, 1, ck) and the
    # two leading dimensions are squeezed
    row = pl.BlockSpec((None, None, 1, ck), lambda t, j: (t, j, 0, 0))
    blk = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [row] * 4,
        out_specs=pl.BlockSpec((1, M, N2), lambda t, j: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nty, M, N2), dtype),
        interpret=interpret,
    )(jnp.asarray(txi, jnp.int32).reshape(1),
      *(a.reshape(nty, npieces, 1, ck) for a in (sx, sy, sz, sm)))
    return blk
