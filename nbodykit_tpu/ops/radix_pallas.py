"""Pallas TPU kernel for the radix counting pass.

:func:`nbodykit_tpu.ops.radix._pass_rank_hist` is a chunked scan whose
per-chunk working set (the (C, D) one-hot and its cumulative sum) is
materialized in HBM by XLA — ~D columns of traffic per element, the
dominant cost of the counting sort at paint scale. This kernel keeps
the entire per-chunk pipeline in VMEM: the only HBM traffic is the
digit stream in (4 B/elt) and the rank stream out (4 B/elt), plus a
(D,) histogram carried in VMEM scratch across the (sequential) TPU
grid. Same contract as ``_pass_rank_hist``:

    rank[i] = #{j < i : digit[j] == digit[i]},   hist[d] = #{digit==d}

Digits must lie in [0, D); :func:`pass_rank_hist_pallas` pads to a
chunk multiple with digit D-1 and subtracts the padding from hist,
mirroring the XLA version.

Numerically exact: per-chunk counts are f32 integers < chunk <= 2^24,
cross-chunk totals are i32.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rank_kernel(dig_ref, rank_ref, hist_ref, base_ref, *, D, C):
    """One grid step: rank one chunk, accumulate the running histogram.

    dig_ref  : (1, C) i32 VMEM block of digits (row-major element order)
    rank_ref : (1, C) i32 VMEM output block
    hist_ref : (1, D) i32 output (whole array every step; last wins)
    base_ref : (1, D) i32 VMEM scratch — running per-digit totals
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        base_ref[...] = jnp.zeros((1, D), jnp.int32)

    d = dig_ref[0, :]                                    # (C,)
    eq = d[:, None] == jax.lax.broadcasted_iota(jnp.int32, (C, D), 1)
    O = eq.astype(jnp.float32)                           # one-hot
    # inclusive running count down the chunk as a lower-triangular
    # matmul: the Pallas TPU lowering has no cumsum ("Unimplemented
    # primitive in Pallas TPU lowering for KernelType.TC: cumsum"), and
    # this is MXU work.  0/1 operands are exact in bf16 and the counts
    # accumulate in f32, so the result is the exact integer cumsum
    tri = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
           <= jax.lax.broadcasted_iota(jnp.int32, (C, C), 0))
    cumO = jnp.dot(tri.astype(jnp.bfloat16), O.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    # the one-hot picks cumO[r, d_r] / base[d_r] with no gather.
    # Within-chunk counts stay < C <= 2^24, so the f32 cumsum pick is
    # exact; the cross-chunk base can exceed 2^24 and is selected in
    # PURE i32 (an f32 product would round it — corrupted ranks).
    rank_in = (cumO * O).sum(axis=1).astype(jnp.int32) - 1
    base = base_ref[0, :]
    base_pick = jnp.where(eq, base[None, :], 0).sum(axis=1)
    # explicit i32: under x64 the where/sum chain can promote to i64,
    # and a pallas ref swap requires the exact ref dtype
    rank_ref[...] = (rank_in + base_pick).astype(jnp.int32)[None, :]
    base = base + cumO[C - 1].astype(jnp.int32)
    base_ref[...] = base[None, :]
    hist_ref[...] = base[None, :]


def pass_rank_hist_pallas(digit, D, chunk=256, interpret=False):
    """Drop-in for ``radix._pass_rank_hist`` backed by the VMEM kernel.

    digit : (n,) int32 in [0, D).
    chunk : elements per grid step.  The running count costs
        2 * chunk * D MXU flops per element and a (chunk, chunk)
        triangle in VMEM, so it stays small (what a grid step costs
        next to that is not measured).
    Returns (rank (n,) i32, hist (D,) i32).
    """
    from .radix import pad_digits

    n = digit.shape[0]
    C = int(chunk)
    dig_p, npad = pad_digits(digit, D, C)
    nch = dig_p.shape[0]
    Mp = dig_p.size

    kern = functools.partial(_rank_kernel, D=D, C=C)
    # one (1, C) chunk per grid step: the TPU lowering wants the last
    # two block dimensions divisible by (8, 128) or equal to the
    # array's, so the chunks are viewed (nch, 1, C) and the leading
    # dimension is squeezed
    chunk_spec = pl.BlockSpec((None, 1, C), lambda i: (i, 0, 0))
    rank_p, hist = pl.pallas_call(
        kern,
        grid=(nch,),
        in_specs=[chunk_spec],
        out_specs=[chunk_spec,
                   pl.BlockSpec((1, D), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nch, 1, C), jnp.int32),
                   jax.ShapeDtypeStruct((1, D), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, D), jnp.int32)],
        interpret=interpret,
    )(dig_p.reshape(nch, 1, C))
    rank = rank_p.reshape(Mp)[:n]
    hist = hist[0].at[D - 1].add(-npad)
    return rank, hist
