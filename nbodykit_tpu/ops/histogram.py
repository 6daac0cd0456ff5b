"""Fast weighted 2-D histograms — the (k, mu) binning engine.

The reference bins Fourier modes with a per-slab ``numpy.bincount``
(nbodykit/algorithms/fftpower.py:636-672). A straight ``jnp.bincount``
lowers to scatter-add, which TPUs execute at ~10 ns/element — at
Nmesh=1024 (5.4e8 modes x several weight streams) that is tens of
seconds, dominating the whole FFTPower pipeline.

TPU-native redesign: the bin index splits as ``dig = a * NB + b`` with
``a`` (the k bin) taking hundreds of values and ``b`` (the mu bin) a
dozen, so the histogram is a *matrix product* that rides the MXU:

    H_w[a, b] = sum_e w[e] * onehot(a_e)[a] * onehot(b_e)[b]
             => H_w = A^T @ (B * w[:, None]),  A = onehot(a), B = onehot(b)

All weight streams share one dot per chunk (their B-columns are
concatenated), one-hots are exact in bfloat16, each weight is split
into bf16 hi+lo parts (w = hi + lo), the MXU accumulates in f32 and
chunk results are summed in f64. Accuracy (~2e-7 max relative error
vs exact f64 bincount) is asserted by tests/test_histogram.py. On the
chip it takes 0.095 s of an ``FFTPower`` call at 512^3 with 257 x 12
bins and 5 streams (device time under ``nbk.fftpower.binning.hist``,
one v5e; root PERF.md, the builder's traced run of PR 25).

The bin indices it sums by come from ``edge_count_index`` (edges of any
spacing; the lab path) or ``lattice_shell_index`` (unit-width shells;
the serve plane). ``shell_sums`` is the serve plane's sum, the same
matrix product with the shell as both indices (``shell = a * 8 + b``)
and a 3-d field taken a chunk of leading rows at a time.

``hist2d_weighted`` picks the MXU path on TPU and plain bincount
elsewhere (CPU bincount is exact f64 and faster than emulated matmuls).
"""

import numpy as np
import jax
import jax.numpy as jnp


#: cells a one-hot matrix product takes at a time
_CHUNK = 131072


def _pad_to(x, n, fill):
    m = x.shape[0]
    if m == n:
        return x
    return jnp.concatenate([x, jnp.full((n - m,), fill, x.dtype)])


def _bf16_grid(x):
    """f32 ``x`` rounded to bf16's grid, still f32.  Not a convert to
    bf16 and back: inside one fusion the TPU compiler may elide that
    pair as excess precision, which leaves a hi/lo split with
    ``hi = x, lo = 0`` and sums 8 bits wide (the four-chip program
    did)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def hist2d_mxu(abin, bbin, weights, NA, NB, chunk=_CHUNK,
               acc_dtype=jnp.float64):
    """MXU-backed weighted 2-D histograms.

    abin : (M,) int32 in [0, NA)
    bbin : (M,) int32 in [0, NB)
    weights : sequence of (M,) float arrays (any float dtype)
    Returns a list of (NA, NB) ``acc_dtype`` arrays, one per weight.

    Traceable (jit-safe); shapes are static. Elements with bins outside
    the valid range must be pre-clipped by the caller (the fftpower
    binning reserves explicit under/overflow bins, so this holds).

    Precision contract: weights are cast to f32 before the bf16 hi/lo
    split, so per-element fidelity is f32-grade (~1e-7 relative) even
    for f64 inputs; ``acc_dtype`` only sets the cross-chunk
    accumulation width. Callers needing exact f64 sums must use the
    bincount path (``hist2d_weighted`` auto-picks it off-TPU).
    """
    M = int(abin.shape[0])
    nw = len(weights)
    nch = max(1, -(-M // chunk))
    Mp = nch * chunk
    abin = _pad_to(abin.astype(jnp.int32), Mp, 0)
    bbin = _pad_to(bbin.astype(jnp.int32), Mp, 0)
    ws = [_pad_to(w.astype(jnp.float32), Mp, 0.0) for w in weights]

    ncols = 2 * nw * NB

    def body(i, acc):
        a_c = jax.lax.dynamic_slice(abin, (i * chunk,), (chunk,))
        b_c = jax.lax.dynamic_slice(bbin, (i * chunk,), (chunk,))
        A = jax.nn.one_hot(a_c, NA, dtype=jnp.bfloat16)
        Boh = jax.nn.one_hot(b_c, NB, dtype=jnp.bfloat16)
        cols = []
        for w in ws:
            w_c = jax.lax.dynamic_slice(w, (i * chunk,), (chunk,))
            hi = _bf16_grid(w_c)
            lo = w_c - hi
            cols.append(Boh * hi.astype(jnp.bfloat16)[:, None])
            cols.append(Boh * lo.astype(jnp.bfloat16)[:, None])
        B = jnp.concatenate(cols, axis=1)
        H = jax.lax.dot_general(A, B, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return acc + H.astype(acc_dtype)

    # inside shard_map (the multi-device binning) the chunks are
    # device-local, so the accumulator starts with their varying type
    from ..parallel.runtime import vary_like
    H = jax.lax.fori_loop(
        0, nch, body,
        vary_like(jnp.zeros((NA, ncols), acc_dtype), abin, bbin, *ws))
    out = []
    for iw in range(nw):
        hi = H[:, (2 * iw) * NB:(2 * iw + 1) * NB]
        lo = H[:, (2 * iw + 1) * NB:(2 * iw + 2) * NB]
        out.append(hi + lo)
    return out


def lattice_shell_index(isq, nbins):
    """Exact integer-lattice shell index floor(sqrt(isq)), clipped to
    ``nbins - 1``.

    The shared shell-assignment path of the FFTPower-style unit-width
    binnings (serve/scheduler.py, bench.py) and the bispectrum k-bin
    masks: shells are ``[m, m+1)`` in lattice units, so the bin of an
    integer squared norm ``isq = ix^2 + iy^2 + iz^2`` (or the real-space
    ``dsq`` analogue) is exactly ``floor(sqrt(isq))``.  A straight f32
    sqrt rounds modes sitting ON a shell boundary (any perfect-square
    ``isq``) to a rounding-dependent side; the two integer compares
    below correct the rounded root exactly — one rsqrt + two compares
    per element, where ``edge_count_index`` (edges of any spacing)
    spends one compare per element and edge.

    ``isq`` must be int32 with ``(r+1)^2`` inside int32 — true for any
    admissible mesh (3*(Nmesh/2+1)^2 ~ 1.3e7 at Nmesh=4096).
    """
    isq = isq.astype(jnp.int32)
    r = jnp.sqrt(isq.astype(jnp.float32)).astype(jnp.int32)
    # exact floor correction of the f32 sqrt rounding
    # nbkl: disable=NBK704
    r = r - (r * r > isq) + ((r + 1) * (r + 1) <= isq)
    return jnp.minimum(r, nbins - 1)


def shell_sums(shell, value, nbins, weight=None):
    """Per-shell sum of ``value * weight`` and of ``weight``, both
    f32, for a 3-d ``value`` whose cells carry the shell index
    ``shell`` (broadcastable to it; a cell whose index lies outside
    ``[0, nbins)`` counts nowhere) and an integer ``weight``
    (broadcastable; default 1; at most 256, bf16's exact integers).

    The module's matrix product with the shell as its own two indices,
    ``shell = a * 8 + b``: ``(onehot(b) * cols) @ onehot(a)`` a chunk of
    leading rows at a time (``hist2d_mxu``'s 131,072 cells or one row,
    whichever is more; one row of a 512^3 complex field is 131,584),
    contracted over the chunk's three axes as they lie.  ``cols`` are the
    three bf16 parts of ``value * weight`` and the weight itself: the
    parts add back to the f32 product bit for bit and a one-hot is
    exact, so what is rounded is the MXU's f32 sum over a chunk and
    nothing before it.  At 512^3 it takes 0.0096 s on one v5e (0.0063
    inside the served program); on chunks flattened first 0.0217, as
    one ``onehot(shell)`` of 256 columns against the four 0.137, and
    the two scatter-adds it replaced took 1.19 (root PERF.md, PR 31).

    One partial histogram per chunk, the chunks summed at the end: a
    single f32 accumulator per shell stalls once it outgrows its
    addends.  At 512^3 the last shell, which takes every cell past the
    Nyquist sphere, counted 2^25 of its 6.4e7 weight-2 modes and then
    stopped, so the served P(k) there read 74% high, on the CPU as on
    the chip.  A chunk's weights must sum to less than 2^24, so its
    count is an exact integer in f32 (2048^3: 4.2e6 a row); the counts
    are int32 from there and rounded to f32 once, at the end, summed
    over the chunks as two 16-bit halves, so that a mesh of more than
    2^31 cells (2048^3) does not overflow int32 either.
    """
    rows, ny, nz = (int(n) for n in value.shape)
    nch = -(-rows // max(1, min(rows, _CHUNK // (ny * nz))))
    r = -(-rows // nch)                       # rows a chunk
    nb = 8
    na = -(-nbins // nb)

    def by_row(x, dtype):
        # (rows or 1, ...): what varies along the leading axis is sliced
        # a chunk at a time, what does not is never broadcast beyond one
        x = jnp.asarray(x, dtype)
        return x.reshape((1,) * (3 - x.ndim) + x.shape)

    shell = by_row(shell, jnp.int32)
    value = by_row(value, jnp.float32)
    weight = by_row(1 if weight is None else weight, jnp.float32)

    def partial(i):
        # the last chunk starts early where nch * r > rows; the rows it
        # shares with the chunk before it carry weight 0
        start = jnp.minimum(i * r, rows - r)

        def take(x):
            if x.shape[0] != 1:
                x = jax.lax.dynamic_slice_in_dim(x, start, r)
            return jnp.broadcast_to(x, (r, ny, nz))

        w = take(weight)
        if nch * r != rows:
            fresh = start + jnp.arange(r, dtype=jnp.int32) >= i * r
            w = w * fresh[:, None, None].astype(jnp.float32)
        x = take(value) * w
        hi = _bf16_grid(x)
        mid = _bf16_grid(x - hi)
        cols = jnp.stack([hi, mid, x - hi - mid, w])
        s = take(shell)
        B = s % nb == jnp.arange(nb, dtype=jnp.int32).reshape(nb, 1, 1, 1)
        cols = jnp.where(B, cols[:, None], 0.0).astype(jnp.bfloat16)
        A = jax.nn.one_hot(s // nb, na, dtype=jnp.bfloat16)
        # the chunk stays 3-d: flattening it is a relayout of every
        # cell (nz is no multiple of a lane row) and most of the code
        return jax.lax.dot_general(
            cols.reshape(4 * nb, r, ny, nz), A,
            (((1, 2, 3), (0, 1, 2)), ((), ())),
            preferred_element_type=jnp.float32).reshape(4, nb, na)

    H = jax.lax.map(partial, jnp.arange(nch, dtype=jnp.int32))
    # the chunks' f32 sums added by halves, log2(nch) roundings deep: a
    # reduce over the leading axis adds them one after the other on the
    # chip, and read 4.5 times a scatter-add's mean error at 512^3
    S = H[:, :3]
    while S.shape[0] > 1:
        S = jnp.pad(S, ((0, S.shape[0] % 2),) + ((0, 0),) * 3)
        S = S[:S.shape[0] // 2] + S[S.shape[0] // 2:]
    S = S[0]
    N = H[:, 3].astype(jnp.int32)
    hi = (N >> 16).sum(axis=0, dtype=jnp.int32).astype(jnp.float32)
    lo = (N & 0xFFFF).sum(axis=0, dtype=jnp.int32).astype(jnp.float32)
    return tuple(x.T.reshape(-1)[:nbins]             # [b, a] -> shell
                 for x in (S[0] + (S[1] + S[2]), hi * 65536.0 + lo))


def lattice_shell_edges(xedges, unit):
    """Integer squared-norm thresholds for digitizing int32 ``|i|^2``
    against physical bin edges ``xedges`` on a uniform lattice of
    fundamental ``unit``.

    For integer ``v``, ``(e <= v) == (ceil(e) <= v)``, so digitizing
    the exact int32 lattice norms against the ceil'd squared edges is
    FULLY edge-exact — casting the f64 edges to f32 instead would let
    an edge within one ulp of an integer collapse onto the lattice and
    flip that boundary mode (the exact-integer story of
    algorithms/fftpower.py's no-x64 binning path).  Returns an int32
    numpy array of ``len(xedges)`` thresholds.
    """
    qe = np.ceil((np.asarray(xedges, dtype='f8') / float(unit)) ** 2)
    return np.clip(qe, 0, np.iinfo(np.int32).max).astype('i4')


def edge_count_index(v, edges):
    """Bin index of every ``v`` against ascending ``edges``: the number
    of edges at or below it, ``#{j : edges[j] <= v}``, which is
    ``numpy.digitize(v, edges)`` integer for integer (0 below the first
    edge, ``len(edges)`` from the last one up, a value ON an edge in
    the bin that edge opens).

    The index half of the lab binning (``fftpower.project_to_basis``):
    int32 ``|i|^2`` against ``lattice_shell_edges``' int32 thresholds,
    and float ``x^2`` / ``mu`` against float edges.  ``jnp.digitize``
    lowers to a binary search, a ``while`` of log2(len(edges)) rounds
    that each gather one table entry per element, at the TPU's gather
    rate of ~8 ns an element and round: 5.0 s of a 6.6 s ``FFTPower``
    call at 512^3 (root PERF.md, PR 25).  The compare-and-count here
    has no gather; XLA fuses compare and sum into one pass over ``v``
    with the edges held on chip and never builds the
    ``len(edges) x v.size`` comparison.  Plain IEEE ``<=``, as numpy's:
    ``-0.0`` counts an edge at ``0.0`` (the total order of jax's
    searchsorted does not); a NaN counts none.

    ``edges`` must be 1-d and non-decreasing (callers check their host
    edges once); ``v`` has any shape.  Returns int32 of ``v.shape``.
    """
    edges = jnp.asarray(edges)
    v = jnp.asarray(v)
    return (edges.reshape((-1,) + (1,) * v.ndim) <= v[None]).sum(
        axis=0, dtype=jnp.int32)


def hist2d_bincount(abin, bbin, weights, NA, NB):
    """Exact scatter-add path (fast on CPU, exact in the weights'
    dtype)."""
    multi = (abin.astype(jnp.int32) * NB + bbin.astype(jnp.int32))
    return [jnp.bincount(multi, weights=w, length=NA * NB)
            .reshape(NA, NB) for w in weights]


def _default_method():
    # MXU hardware: scatter-add bincount is ~10x slower there
    from ..utils import is_mxu_backend
    return 'mxu' if is_mxu_backend() else 'bincount'


def hist2d_weighted(abin, bbin, weights, NA, NB, method=None,
                    chunk=_CHUNK, acc_dtype=None):
    """Weighted 2-D histograms of flat index streams; see module
    docstring. ``method`` in {'mxu', 'bincount', None=auto}."""
    if method is None:
        method = _default_method()
    if acc_dtype is None:
        acc_dtype = jnp.float64 if jax.config.jax_enable_x64 \
            else jnp.float32
    if method == 'mxu':
        return hist2d_mxu(abin, bbin, weights, NA, NB, chunk=chunk,
                          acc_dtype=acc_dtype)
    return hist2d_bincount(abin, bbin, weights, NA, NB)
