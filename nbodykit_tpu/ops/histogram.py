"""Fast weighted 2-D histograms — the (k, mu) binning engine.

The reference bins Fourier modes with a per-slab ``numpy.bincount``
(nbodykit/algorithms/fftpower.py:636-672). A straight ``jnp.bincount``
lowers to scatter-add, which TPUs execute at ~10 ns/element — at
Nmesh=1024 (5.4e8 modes x several weight streams) that is tens of
seconds, dominating the whole FFTPower pipeline.

TPU-native redesign: the histogram is a *matrix product* that rides the
MXU.  The flat bin index ``f = a * NB + b`` (``a`` the k bin, hundreds
of values; ``b`` the mu bin, a dozen or three) splits as ``f = f_hi * 8
+ f_lo``, and with ``parts`` the streams' bf16 parts

    H[part, f_lo, f_hi] = sum_e part[e] * onehot(f_lo_e)[f_lo]
                                        * onehot(f_hi_e)[f_hi]
                        = (onehot(f_lo) * parts) @ onehot(f_hi)

over a chunk of cells taken as it lies (3-d for the lab binning: a
slab chunk whole, never flattened), the B side's ``8 * parts`` columns
a ``where`` of the parts against an iota on a leading axis, so that
the compiler builds both one-hot operands inside the product's fusion
and stores neither (``hist2d_mxu``, ``mxu_split``).  One-hots are
exact in bfloat16, each weight is split into bf16 hi+lo parts (w = hi
+ lo; a stream handed over AS bfloat16, the count's 1.0 and 2.0, is
its own one part), the MXU accumulates in f32 and chunk results are
summed in ``acc_dtype``.  Accuracy (2e-6 of the largest bin at worst,
two bf16 parts a weight) is asserted by tests/test_histogram.py
against an exact f64 bincount and against the form it replaced, which
concatenated and stored a ``[131072, 2 * nw * NB]`` block of one-hot
columns a chunk of flattened cells: 0.122 s of an ``FFTPower`` call at
512^3 with 257 x 12 bins and 5 streams, 0.551 s of a survey call's
three 128 x 3 binnings (device time under ``nbk.fftpower.binning``,
one v5e; ledger, PR 35); what this form takes is in root PERF.md,
PR 36.

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


#: cells a one-hot matrix product of ``shell_sums`` takes at a time
_CHUNK = 131072
#: and of ``hist2d_mxu``: a slab chunk of the lab binning whole
#: (``fftpower._BIN_CHUNK_ELEMENTS``); a row of it at a time, every
#: stream is sliced and re-tiled a row, a third of the kernel's time
_HIST_CHUNK = 1 << 22
#: the part of the flat bin index that rides on the product's B side
_LO = 8


def _bf16_grid(x):
    """f32 ``x`` rounded to bf16's grid, still f32.  Not a convert to
    bf16 and back: inside one fusion the TPU compiler may elide that
    pair as excess precision, which leaves a hi/lo split with
    ``hi = x, lo = 0`` and sums 8 bits wide (the four-chip program
    did)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def mxu_split(NA, NB, parts):
    """``(rows, columns)`` of ``hist2d_mxu``'s product for ``NA x NB``
    bins and ``parts`` bf16 parts of the streams.

    The flat bin index ``f = a * NB + b`` is split as ``f_hi * 8 +
    f_lo``, whatever ``NA`` and ``NB`` are: ``rows = ceil(NA NB / 8)``
    one-hot rows of ``f_hi`` on the A side, ``columns = 8 parts`` on
    the B side (``f_lo`` against each part).  So a binning with few
    ``b`` bins (the survey's 3, whose 128 x 30 product ran at 2% of
    the bf16 peak) gets as many columns as one with 12, and its rows
    shrink instead.  Why 8: a ``where`` against an iota of 8 on a
    leading axis is what the v5e compiler builds inside the product's
    operand; with the ``b`` bins themselves there (12, 3, 6) or with 16,
    24 or 32 it stores the B side, 19-38 MB a row of 512 x 257 cells,
    and re-lays it (root PERF.md, PR 36: every form as timed).  What it
    gives the cells: lab 257 x 12 x 9 -> 386 x 72, four chips 513 x 12
    x 9 -> 770 x 72, the survey's 128 x 3 x 9 -> 48 x 72."""
    return -(-NA * NB // _LO), _LO * parts


def hist2d_mxu(abin, bbin, weights, NA, NB, chunk=_HIST_CHUNK,
               acc_dtype=jnp.float64):
    """MXU-backed weighted 2-D histograms.

    abin : int32 in [0, NA), of any shape ``(n0, ...)``: the cells
    bbin : int32 in [0, NB), broadcastable to ``abin``
    weights : sequence of float arrays broadcastable to ``abin`` (a
        factor that is constant along an axis stays size 1 there)
    Returns a list of (NA, NB) ``acc_dtype`` arrays, one per weight.

    Traceable (jit-safe); shapes are static. Elements with bins outside
    the valid range must be pre-clipped by the caller (the fftpower
    binning reserves explicit under/overflow bins, so this holds).

    The product is ``shell_sums``'s: a chunk of leading rows at a time
    (``chunk`` cells or one row, whichever is more), contracted over
    the cells' axes as they lie; the B side's columns are a ``where``
    of the parts against an iota on a leading axis, so the compiler
    builds both one-hot operands inside the product and stores neither
    (``mxu_split``).  The last chunk starts early where the chunks do
    not divide the rows, and the cells it shares with the one before
    weigh 0.  A chunk's weights of one bin must sum to less than 2^24
    for a count to come out as an exact integer (``chunk`` cells of
    weight 2 do).

    Precision contract: weights are cast to f32 before the bf16 hi/lo
    split, so per-element fidelity is f32-grade (~1e-7 relative) even
    for f64 inputs; ``acc_dtype`` only sets the cross-chunk
    accumulation width. A weight handed over AS bfloat16 is its own one
    part (the count stream's 1.0 and 2.0: their lo part is identically
    zero). Callers needing exact f64 sums must use the bincount path
    (``hist2d_weighted`` auto-picks it off-TPU).
    """
    shape = tuple(int(n) for n in abin.shape)
    nd = len(shape)
    lead, inner = shape[0], int(np.prod(shape[1:], dtype=np.int64))
    nch = -(-lead // max(1, min(lead, chunk // inner)))
    r = -(-lead // nch)                       # leading rows a chunk
    cells = (r,) + shape[1:]

    def by_row(x, dtype=None):
        x = jnp.asarray(x, dtype)
        return x.reshape((1,) * (nd - x.ndim) + x.shape)

    abin = by_row(abin, jnp.int32)
    bbin = by_row(bbin, jnp.int32)
    exact = [w.dtype == jnp.bfloat16 for w in weights]
    ws = [by_row(w, jnp.float32) for w in weights]
    nparts = sum(1 if e else 2 for e in exact)
    rows, ncols = mxu_split(NA, NB, nparts)

    def partial(i):
        start = jnp.minimum(i * r, lead - r)

        def take(x):
            if x.shape[0] != 1:
                x = jax.lax.dynamic_slice_in_dim(x, start, r)
            return x

        fresh = None
        if nch * r != lead:
            fresh = (start + jnp.arange(r, dtype=jnp.int32) >= i * r
                     ).reshape((r,) + (1,) * (nd - 1)).astype(jnp.float32)
        parts = []
        for w, e in zip(ws, exact):
            w_c = take(w) if fresh is None else take(w) * fresh
            hi = w_c if e else _bf16_grid(w_c)
            parts.append(hi)
            if not e:
                parts.append(w_c - hi)
        cols = jnp.stack([jnp.broadcast_to(p, cells) for p in parts])
        f = jnp.broadcast_to(take(abin) * NB + take(bbin), cells)
        B = f % _LO == jnp.arange(_LO, dtype=jnp.int32).reshape(
            (_LO,) + (1,) * nd)
        cols = jnp.where(B, cols[:, None], 0.0).astype(jnp.bfloat16)
        A = jax.nn.one_hot(f // _LO, rows, dtype=jnp.bfloat16)
        # the chunk stays as it lies: flattening it is a relayout of
        # every cell (shell_sums)
        axes = tuple(range(nd))
        return jax.lax.dot_general(
            cols.reshape((ncols,) + cells), A,
            ((tuple(1 + n for n in axes), axes), ((), ())),
            preferred_element_type=jnp.float32)

    # inside shard_map (the multi-device binning) the chunks are
    # device-local, so the accumulator starts with their varying type
    from ..parallel.runtime import vary_like
    H = jax.lax.fori_loop(
        0, nch, lambda i, acc: acc + partial(i).astype(acc_dtype),
        vary_like(jnp.zeros((ncols, rows), acc_dtype), abin, bbin, *ws))
    # [part, f_lo, f_hi] -> [part, a, b]
    H = H.reshape(nparts, _LO, rows).transpose(0, 2, 1).reshape(
        nparts, rows * _LO)[:, :NA * NB].reshape(nparts, NA, NB)
    out, k = [], 0
    for e in exact:
        out.append(H[k] if e else H[k] + H[k + 1])
        k += 1 if e else 2
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
                    chunk=_HIST_CHUNK, acc_dtype=None):
    """Weighted 2-D histograms of index streams of any one shape (the
    weights broadcastable to it); see module docstring. ``method`` in
    {'mxu', 'bincount', None=auto}."""
    if method is None:
        method = _default_method()
    if acc_dtype is None:
        acc_dtype = jnp.float64 if jax.config.jax_enable_x64 \
            else jnp.float32
    if method == 'mxu':
        return hist2d_mxu(abin, bbin, weights, NA, NB, chunk=chunk,
                          acc_dtype=acc_dtype)

    def flat(x):
        # a bfloat16 weight is exact by its type (hist2d_mxu); summed
        # as bfloat16 it would not be
        if x.dtype == jnp.bfloat16:
            x = x.astype(acc_dtype)
        return jnp.broadcast_to(x, abin.shape).reshape(-1)
    return hist2d_bincount(flat(abin), flat(bbin),
                           [flat(w) for w in weights], NA, NB)
