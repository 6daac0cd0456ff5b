"""Local paint (scatter-add) and readout (gather) kernels.

These are the per-device primitives replacing pmesh's C paint/readout
(consumed by the reference at nbodykit/source/mesh/catalog.py:287-296 and
nbodykit/algorithms/fftrecon.py:217-268). They operate on a *local* mesh
block — the full mesh on a single device, or a halo-extended slab inside
``shard_map`` for the distributed path (see pmesh_tpu.ParticleMesh.paint).

Positions arrive in *cell units*. Indices are wrapped periodically modulo
``period`` (the global mesh size per axis) and then offset into the local
block; the offset+halo bookkeeping is the caller's job.

TPU layout note: all per-particle temporaries are kept 1-D (shape (n,)).
An (n, s, s, s) tensor-product expansion looks natural but is
catastrophic on TPU — trailing dims of 2-4 get padded to the 128-lane
tile, a 32-64x memory blowup. Instead we statically unroll the s^3
window offsets: s^3 scatter-adds (or gathers) of 1-D arrays, which XLA
fuses and tiles cleanly. Particles are chunked with a fori_loop to bound
the live set.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .window import (window_base, window_support, window_weights,
                     window_weights_grad)
# '.trace.' metrics below are bumped once per COMPILATION of the
# enclosing program (these kernels run inside jit/shard_map), not per
# execution — they document which kernel got traced at what size, not
# how often it ran (see diagnostics/metrics.py)
from ..diagnostics import counter, gauge, install_compile_telemetry, \
    instrumented_jit
from ..parallel.runtime import vary_like

# the paint kernels compile inside their enclosing jit: the *.trace.*
# counters below count traces, the xla.compile.* histograms this hook
# feeds time the actual backend compiles
install_compile_telemetry()

# particles a row of the tile paint's sorted payload holds (a TPU
# vector's lanes) and the rows of particles a tile takes per piece
# (paint_local_mxu: ``ck``; pmesh.memory_plan prices it)
LANES = 128
PIECE_ROWS = 256


def _axis_terms(pos_ax, resampler, period, grad=False):
    """Per-axis neighbor indices (wrapped mod period) and weights,
    shapes (n, s).  ``grad=True`` returns the derivative weights
    dW/dx (cell units) instead — the per-axis factor of the analytic
    paint/readout adjoint (forward/adjoint.py)."""
    if grad:
        idx, w = window_weights_grad(pos_ax, resampler)
    else:
        idx, w = window_weights(pos_ax, resampler)
    return jnp.mod(idx, period), w


@functools.partial(
    instrumented_jit, label='paint.window',
    static_argnames=('resampler', 'period', 'grad_axis'))
def _window_terms(pos, resampler, period, grad_axis):
    """:func:`_axis_terms` of the three axes, as one program on the
    eager path (a staged caller inlines it: the served and the
    four-chip paint programs compile to the same instructions with
    and without the jit).  Op by op it is some thirty (n, 3)
    temporaries (160 MB each at 1e7 particles), and how many were
    still alive under the paint's first scatters moved with the
    host's lead and with when the runtime's callbacks released them:
    the survey call's peak allocation read 5.973, 6.142 or 6.302 GB
    from one call to the next (PERF.md section 6, PR 32)."""
    return tuple(_axis_terms(pos[:, ax], resampler, period[ax],
                             grad=grad_axis == ax) for ax in range(3))


def _offset_terms(pos, mass, resampler, period, origin, n0l,
                  grad_axis=None):
    """Yield (flat_rows_valid, lin_index, weight) triples — one per
    static window offset (i, j, k) in s^3 — all 1-D over particles.

    ``grad_axis`` (0/1/2) swaps that axis's window factor for its
    derivative dW/dx, so the weighted gather computes
    d(interpolation)/d(pos[grad_axis]) in cell units — the readout
    side of the paint position-adjoint."""
    s = window_support(resampler)
    N1, N2 = period[1], period[2]
    # trace-time overflow guard: lin below peaks at n0l*N1*N2 - 1 and
    # is int32 (window indices are i32) — a single-device 1291^3+
    # block would wrap silently without this (nbkl NBK704)
    if n0l * N1 * N2 - 1 > np.iinfo(np.int32).max:
        raise ValueError(
            'local block (%d, %d, %d) overflows int32 flat indexing; '
            'shard the mesh over more devices or reduce nmesh'
            % (n0l, N1, N2))
    (i0, w0), (i1, w1), (i2, w2) = _window_terms(
        pos, resampler=resampler,
        period=tuple(int(p) for p in period), grad_axis=grad_axis)
    # local row index relative to block origin
    for a in range(s):
        row = jnp.mod(i0[:, a] - origin, period[0])
        valid = row < n0l
        row_c = jnp.where(valid, row, 0)
        for b in range(s):
            for c in range(s):
                w = w0[:, a] * w1[:, b] * w2[:, c]
                if mass is not None:
                    w = w * mass
                w = jnp.where(valid, w, 0.0)
                lin = (row_c * N1 + i1[:, b]) * N2 + i2[:, c]
                yield lin, w


def paint_local(pos, mass, shape, resampler='cic', period=None, origin=0,
                out=None, chunk=None):
    """Scatter particles onto a local mesh block.

    Parameters
    ----------
    pos : (n, 3) float — positions in global cell units
    mass : (n,) float or scalar — the value to deposit (0 masks a slot)
    shape : (n0l, N1, N2) — local block shape
    period : (3,) int — global mesh size for periodic wrapping; defaults
        to ``shape`` (single-device case)
    origin : int — global row index of the local block's first row
        (halo-extended blocks pass d*n0 - h)
    out : optional existing block to accumulate into (hold=True semantics)
    chunk : particles per scatter pass (default: all at once)

    Returns
    -------
    (n0l, N1, N2) block with sum of mass*window deposited.
    """
    n0l, N1, N2 = (int(x) for x in shape)
    if period is None:
        period = shape
    period = tuple(int(p) for p in period)
    n = pos.shape[0]
    dtype = out.dtype if out is not None else (
        mass.dtype if hasattr(mass, 'dtype') else pos.dtype)
    flat = jnp.zeros(n0l * N1 * N2, dtype=dtype) if out is None \
        else jnp.asarray(out).reshape(-1)

    counter('paint.trace.scatter').add(1)
    counter('paint.trace.scatter_particles').add(int(n))
    # which batch size this program was COMPILED with: the resilience
    # ladder (docs/RESILIENCE.md) degrades paint_chunk_size on OOM, and
    # this gauge is how a post-mortem confirms the smaller batch
    # actually reached the next trace
    gauge('paint.trace.chunk_particles').set(
        int(min(chunk, n)) if chunk else int(n))
    mass = jnp.broadcast_to(jnp.asarray(mass, dtype=dtype), (n,))

    def body(pos_c, mass_c, flat):
        for lin, w in _offset_terms(pos_c, mass_c, resampler, period,
                                    origin, n0l):
            flat = flat.at[lin].add(w.astype(dtype))
        return flat

    if chunk is None or chunk >= n:
        flat = body(pos, mass, flat)
    else:
        nchunks = (n + chunk - 1) // chunk
        npad = nchunks * chunk
        pos_p = jnp.concatenate(
            [pos, jnp.zeros((npad - n, 3), pos.dtype)], axis=0)
        mass_p = jnp.concatenate(
            [mass, jnp.zeros((npad - n,), dtype)], axis=0)
        pos_p = pos_p.reshape(nchunks, chunk, 3)
        mass_p = mass_p.reshape(nchunks, chunk)

        def loop(i, flat):
            return body(pos_p[i], mass_p[i], flat)
        # inside a slab mesh's shard_map the carry starts replicated
        # and takes device-local deposits
        flat = jax.lax.fori_loop(0, nchunks, loop,
                                 vary_like(flat, pos_p, mass_p))

    return flat.reshape(shape)


def readout_local(block, pos, resampler='cic', period=None, origin=0,
                  chunk=None, grad_axis=None):
    """Interpolate a local mesh block at particle positions (gather).

    Parameters mirror :func:`paint_local`; out-of-block rows contribute 0.
    ``grad_axis`` (0/1/2) computes d(interpolation)/d(pos[grad_axis])
    in cell units instead — the position cotangent of the paint
    adjoint (forward/adjoint.py): d/dx of sum_c block[c] W_c(x).

    Returns
    -------
    (n,) values of the window-weighted interpolation.
    """
    shape = block.shape
    n0l, N1, N2 = (int(x) for x in shape)
    if period is None:
        period = shape
    period = tuple(int(p) for p in period)
    n = pos.shape[0]
    flat = block.reshape(-1)
    counter('paint.trace.readout').add(1)
    counter('paint.trace.readout_particles').add(int(n))

    def body(pos_c):
        vals = jnp.zeros(pos_c.shape[0], dtype=block.dtype)
        for lin, w in _offset_terms(pos_c, None, resampler, period,
                                    origin, n0l, grad_axis=grad_axis):
            vals = vals + flat[lin] * w.astype(block.dtype)
        return vals

    if chunk is None or chunk >= n:
        return body(pos)
    nchunks = (n + chunk - 1) // chunk
    npad = nchunks * chunk
    pos_p = jnp.concatenate([pos, jnp.zeros((npad - n, 3), pos.dtype)],
                            axis=0).reshape(nchunks, chunk, 3)
    vals = jax.lax.map(body, pos_p)
    return vals.reshape(-1)[:n]


def _one_sort_streams(pos, mass, shape, resampler, period, origin,
                      dtype, order_method='argsort'):
    """Shared preamble of the one-sort deposit kernels
    (:func:`paint_local_sorted`, :func:`paint_local_segsum`).

    ONE stable ordering of the n base cells (not the s^3*n deposit
    terms): for every window offset (a,b,c) the un-wrapped deposit key
    is the base key plus the constant d=(a*N1+b)*N2+c, so base order
    keeps equal deposit keys contiguous for every offset
    simultaneously, and the segment structure (run boundaries) is
    SHARED — wrap status and cell indices are functions of the base
    cell alone.

    Returns ``(keys, is_start, is_last, idx, offs, W, fbk, fbv,
    sent)``: the sorted base keys, run-start/run-end masks, the slot
    iota, the s^3 constant key offsets, the (s^3, n) un-wrapped weight
    streams in base-sorted order, the concatenated plain-scatter
    fallback stream (keys, values) for wrapped/out-of-block deposits,
    and the dropped-slot sentinel base.

    order_method : stable ordering engine for the one rank
        (:func:`~nbodykit_tpu.ops.radix.order_keys` — 'argsort',
        'radix' over the [0, M) cell alphabet, or the 'auto' hardware
        heuristic). Both engines are stable, so the run structure is
        engine-independent.
    """
    n0l, N1, N2 = (int(x) for x in shape)
    period = tuple(int(p) for p in period)
    n = pos.shape[0]
    M = n0l * N1 * N2
    s = window_support(resampler)
    # the flat deposit keys below are int32 (shapes are static, so this
    # raises at trace time, not silently on device): the largest value
    # formed is the dropped-slot sentinel M + (s-1)*(N1*N2+N2+1) + 1
    if M + (s - 1) * (N1 * N2 + N2 + 1) + 1 > np.iinfo(np.int32).max:
        raise ValueError(
            "one-sort paint: local block %dx%dx%d (+window %d) "
            "overflows the int32 flat index; shard the mesh over more "
            "devices so n0_local*N1*N2 < 2**31" % (n0l, N1, N2, s))

    i0, w0 = _axis_terms(pos[:, 0], resampler, period[0])
    i1, w1 = _axis_terms(pos[:, 1], resampler, period[1])
    i2, w2 = _axis_terms(pos[:, 2], resampler, period[2])
    row0 = jnp.mod(i0[:, 0] - origin, period[0]).astype(jnp.int32)
    valid0 = row0 < n0l
    # i32 is safe here: range proven < 2**31 by the trace-time guard
    # above  # nbkl: disable=NBK302
    lin_base = ((jnp.where(valid0, row0, 0) * N1
                 + i1[:, 0].astype(jnp.int32)) * N2
                + i2[:, 0].astype(jnp.int32))
    from .radix import order_keys
    # lin_base is provably in [0, M) (row clamped, i1/i2 wrapped), so
    # the radix engine's alphabet is the cell count
    order = order_keys(lin_base, M, order_method)
    i0s, i1s, i2s = i0[order], i1[order], i2[order]
    w0s = w0[order].astype(dtype)
    w1s = w1[order].astype(dtype)
    w2s = w2[order].astype(dtype)
    ms = mass[order]
    keys = lin_base[order]
    row0s, valid0s = row0[order], valid0[order]

    idx = jnp.arange(n, dtype=jnp.int32)
    if n:
        neq = keys[1:] != keys[:-1]
        is_last = jnp.concatenate([neq, jnp.ones((1,), bool)])
        is_start = jnp.concatenate([jnp.ones((1,), bool), neq])
    else:
        is_last = is_start = jnp.zeros((0,), bool)
    # dropped-slot sentinel base: strictly above every possible
    # keys + d (d <= (s-1)*(N1*N2+N2+1)), so sentinels can never
    # collide with a wrapped run's out-of-block key + d
    sent = M + (s - 1) * (N1 * N2 + N2 + 1) + 1

    # per-offset deposit values, exact keys, and wrap status — all in
    # base-sorted order. Entries that wrap (periodic boundary) or fall
    # outside the local block break the constant-shift relation and go
    # through a small plain scatter instead.
    offs, wsegs, fb_keys, fb_vals = [], [], [], []
    for a in range(s):
        rowa = jnp.mod(i0s[:, a].astype(jnp.int32) - origin,
                       period[0])
        valida = rowa < n0l
        for b in range(s):
            for c in range(s):
                d = (a * N1 + b) * N2 + c
                w = w0s[:, a] * w1s[:, b] * w2s[:, c] * ms
                # key + d bounded by the sentinel, < 2**31 by the
                # trace-time guard  # nbkl: disable=NBK302
                lin = ((jnp.where(valida, rowa, 0) * N1
                        + i1s[:, b].astype(jnp.int32)) * N2
                       + i2s[:, c].astype(jnp.int32))
                unwrapped = (valida & valid0s
                             & (rowa == row0s + a)
                             & (i1s[:, b] == i1s[:, 0] + b)
                             & (i2s[:, c] == i2s[:, 0] + c))
                offs.append(d)
                wsegs.append(jnp.where(unwrapped, w, 0))
                # fallback stream: wrapped in-block deposits (the
                # periodic boundary strip). The stream is s^3*n wide
                # (XLA cannot elide masked updates) but only the
                # O(n*s^3/N) boundary entries carry weight; masked
                # slots get DISTINCT out-of-bounds indices so they do
                # not pile up on one colliding index — EXCEPT when
                # sent + s^3*n + n would wrap int32 (a masked slot
                # could then alias an in-bounds cell; its zero value
                # makes that silent, not safe): there all masked slots
                # share the single provably-OOB index `sent` instead.
                # Dropped updates never read-modify-write memory, so
                # the shared index costs nothing.
                fb = unwrapped | ~valida
                j = len(offs) - 1
                if sent + (s ** 3) * n + n < 2 ** 31 - 1:
                    fkey = sent + j * n + idx
                else:
                    fkey = sent
                fb_keys.append(jnp.where(fb, fkey, lin))
                fb_vals.append(jnp.where(fb, 0, w))

    W = jnp.stack(wsegs)                      # (s^3, n)
    return (keys, is_start, is_last, idx, offs, W,
            jnp.concatenate(fb_keys), jnp.concatenate(fb_vals), sent)


def paint_local_sorted(pos, mass, shape, resampler='cic', period=None,
                      origin=0, out=None, npasses=None):
    """Collision-free paint: sort + segmented reduction + unique scatter.

    TPU scatter-add serializes on colliding indices. Here all (cell,
    weight) deposit terms are sorted by cell (ONE sort of the n base
    cells — :func:`_one_sort_streams`), each equal-cell run is
    summed with doubling shift-add passes (exact — no global cumsum, so
    f32 precision is preserved), the per-run totals are compacted to one
    entry per distinct cell, and a single scatter with *provably unique*
    indices deposits them (``unique_indices=True`` — XLA needs no
    serialization). Unused compaction slots get distinct out-of-bounds
    indices and are dropped, keeping the uniqueness claim honest.

    The shift loop runs as a lax.while_loop until no run spans the
    current shift, so arbitrarily long collision runs are summed exactly
    (cost: log2(max occupancy) passes).

    Memory is O(n * s^3) beyond the output block — unlike the round-1
    sentinel design there is no O(M) term, so this scales to
    Nmesh=1024 (M=1e9) meshes.

    npasses : optional static cap on the doubling passes (mostly for
        testing); None iterates to completion.
    """
    n0l, N1, N2 = (int(x) for x in shape)
    if period is None:
        period = shape
    n = pos.shape[0]
    M = n0l * N1 * N2
    dtype = out.dtype if out is not None else (
        mass.dtype if hasattr(mass, 'dtype') else pos.dtype)
    counter('paint.trace.sort').add(1)
    counter('paint.trace.sort_particles').add(int(n))
    mass = jnp.broadcast_to(jnp.asarray(mass, dtype=dtype), (n,))

    keys, _, is_last, idx, offs, W, fbk, fbv, sent = _one_sort_streams(
        pos, mass, shape, resampler, period, origin, dtype, 'argsort')

    flat = jnp.zeros(M, dtype=dtype) if out is None else \
        jnp.asarray(out).reshape(-1)
    flat = flat.at[fbk].add(fbv, mode='drop')

    # shared segmented inclusive prefix sum, vectorized over the s^3
    # offsets: doubling shift-add passes; afterwards the last element
    # of each run holds the run total. Exact — no global cumsum, f32
    # precision preserved.
    max_shift = n if npasses is None else min(n, 1 << npasses)

    def cond(state):
        W, shift, active = state
        return active & (shift < max_shift)

    def body(state):
        W, shift, _ = state
        src = jnp.maximum(idx - shift, 0)
        same = (idx >= shift) & (keys == keys[src])
        W = W + jnp.where(same[None, :], W[:, src], 0)
        src2 = jnp.maximum(idx - 2 * shift, 0)
        active = jnp.any((idx >= 2 * shift) & (keys == keys[src2]))
        return W, shift * 2, active

    # data-derived initial 'active' (vma-varying under shard_map; a
    # literal True would type-mismatch the while_loop carry)
    active0 = jnp.any(keys == keys)
    W, _, _ = jax.lax.while_loop(cond, body,
                                 (W, jnp.int32(1), active0))

    # one provably-unique scatter per offset: run-end entries carry
    # their run total to base_key + d; all others get distinct
    # out-of-bounds indices and are dropped
    for j, d in enumerate(offs):
        # run-end keys+d are distinct (distinct run keys, same d) and
        # a wrapped run's key+d stays below `sent`, so the sentinel
        # slots keep the uniqueness claim honest even then
        skeys = jnp.where(is_last, keys + d, sent + idx)
        flat = flat.at[skeys].add(jnp.where(is_last, W[j], 0),
                                  mode='drop', unique_indices=True)
    return flat.reshape(shape)


def paint_local_segsum(pos, mass, shape, resampler='cic', period=None,
                       origin=0, out=None, order_method='argsort'):
    """One-sort paint with ``jax.ops.segment_sum`` run reduction.

    Same single-rank trick as :func:`paint_local_sorted` (ONE stable
    ordering of the n base cells, shared run structure across all s^3
    window offsets — :func:`_one_sort_streams`), but the per-run
    reduction is a single ``segment_sum`` over all s^3 weight streams
    at once (``indices_are_sorted=True`` — one linear pass, no
    data-dependent while_loop) instead of log2(max occupancy) doubling
    shift-add passes. The run totals are gathered back to their run's
    START slot and deposited with one provably-unique scatter per
    offset, exactly mirroring the sorted kernel's run-END compaction.

    order_method : stable ordering engine for the one rank —
        'argsort', 'radix' (:func:`~nbodykit_tpu.ops.radix.
        stable_key_order` over the [0, M) cell alphabet), or 'auto'
        (the hardware heuristic). The ``paint_order`` option.

    Semantics (global cell units, ``origin``/``period``, out-of-block
    masking) match :func:`paint_local` exactly; equivalence is
    asserted per-candidate in tests/test_paint_kernels.py.
    """
    n0l, N1, N2 = (int(x) for x in shape)
    if period is None:
        period = shape
    n = pos.shape[0]
    M = n0l * N1 * N2
    dtype = out.dtype if out is not None else (
        mass.dtype if hasattr(mass, 'dtype') else pos.dtype)
    counter('paint.trace.segsum').add(1)
    counter('paint.trace.segsum_particles').add(int(n))
    mass = jnp.broadcast_to(jnp.asarray(mass, dtype=dtype), (n,))

    keys, is_start, _, idx, offs, W, fbk, fbv, sent = _one_sort_streams(
        pos, mass, shape, resampler, period, origin, dtype,
        order_method)

    flat = jnp.zeros(M, dtype=dtype) if out is None else \
        jnp.asarray(out).reshape(-1)
    flat = flat.at[fbk].add(fbv, mode='drop')

    # run index per sorted slot: 0-based segment ids, monotonically
    # non-decreasing because the slots are key-sorted — so ONE
    # segment_sum reduces every run of every offset stream at once
    seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    totals = jax.ops.segment_sum(W.T, seg, num_segments=max(n, 1),
                                 indices_are_sorted=True)   # (n, s^3)
    run_tot = jnp.take(totals, seg, axis=0)                 # (n, s^3)

    # one provably-unique scatter per offset: run-START entries carry
    # their run total to base_key + d; all others get distinct
    # out-of-bounds indices and are dropped (same uniqueness argument
    # as paint_local_sorted's run-end compaction)
    for j, d in enumerate(offs):
        skeys = jnp.where(is_start, keys + d, sent + idx)
        flat = flat.at[skeys].add(jnp.where(is_start, run_tot[:, j], 0),
                                  mode='drop', unique_indices=True)
    return flat.reshape(shape)


def paint_local_streams(pos, mass, shape, resampler='cic', period=None,
                        origin=0, out=None, streams=4, chunk=None,
                        storage_dtype=None):
    """Offset-stream scatter: k independent scatter chains, one sum.

    XLA lowers scatter-add to a serial per-element loop and the plain
    kernel threads ALL s^3 per-offset deposit streams through ONE mesh
    buffer, so every update serializes behind the last. But the s^3
    window-offset streams are algebraically independent (the CIC/TSC
    decompositions of Jing 2005, astro-ph/0409240, and Cui et al. 2008,
    0804.0070): offset j only ever touches cell ``base + d_j``. Here
    the offsets are dealt round-robin onto ``k = streams`` mesh
    replicas, giving XLA k data-independent scatter chains to overlap,
    and the replicas are pairwise tree-summed once at the end.

    The price is k-1 extra mesh-sized buffers — replicas count as full
    mesh units in the NBK5xx symbolic-peak model, so
    :meth:`~nbodykit_tpu.pmesh.ParticleMesh.memory_plan` grows
    ``paint_tmp`` by k mesh units.

    streams : number of replica meshes (the ``paint_streams``
        option; clamped to [1, s^3] — k=1 degenerates to
        :func:`paint_local`'s chain).
    chunk : particles per scatter pass, as in :func:`paint_local`
        (the replica tuple is the fori_loop carry).
    storage_dtype : when a narrow float (bfloat16), the replica meshes
        are stored at that width — half the HBM of the f32 replicas,
        THE dominant term of this method's memory_plan — while every
        deposit weight is computed f32 and split two-sum style: the
        bf16-representable ``hi`` part and the f32 residual ``lo`` land
        on different replicas, and the merge step re-widens each
        replica to f32 BEFORE the pairwise tree sum (the compensated
        accumulation of the NBK701/702 contracts).  The returned field
        is f32 (compute dtype); callers narrow to storage once, at
        their own exit.  None (default) keeps today's single-width
        behavior.
    """
    from ..utils import is_narrow_float
    n0l, N1, N2 = (int(x) for x in shape)
    if period is None:
        period = shape
    period = tuple(int(p) for p in period)
    n = pos.shape[0]
    s = window_support(resampler)
    k = max(1, min(int(streams), s ** 3))
    dtype = out.dtype if out is not None else (
        mass.dtype if hasattr(mass, 'dtype') else pos.dtype)
    narrow = storage_dtype is not None and is_narrow_float(storage_dtype)
    # rdtype: what the replica meshes STORE; weights always compute
    # at least f32 wide (mdtype) — bf16 is never an arithmetic dtype
    rdtype = np.dtype(storage_dtype) if narrow else dtype
    mdtype = jnp.float32 if narrow else dtype
    counter('paint.trace.streams').add(1)
    counter('paint.trace.streams_particles').add(int(n))
    gauge('paint.trace.stream_count').set(k)
    if narrow:
        counter('paint.trace.streams_narrow').add(1)
    mass = jnp.broadcast_to(jnp.asarray(mass, dtype=mdtype), (n,))

    # data-derived zero: under shard_map the fori_loop carry must have
    # the same varying-manual-axes type as the per-step update
    zinit = jnp.zeros((), rdtype) + (jnp.sum(mass[:1]) * 0).astype(rdtype)
    flats = [jnp.zeros(n0l * N1 * N2, dtype=rdtype) + zinit
             for _ in range(k)]

    def body(pos_c, mass_c, flats):
        flats = list(flats)
        for j, (lin, w) in enumerate(_offset_terms(
                pos_c, mass_c, resampler, period, origin, n0l)):
            # round-robin deal: adjacent offsets land on different
            # replicas, so no chain carries two consecutive streams
            if narrow:
                # two-sum split of the f32 weight: hi is the
                # bf16-representable part, lo the residual it lost —
                # deposited on the NEXT replica so the correction
                # survives until the f32 merge
                w32 = w.astype(jnp.float32)
                hi = w32.astype(jnp.bfloat16)
                lo = w32 - hi.astype(jnp.float32)
                flats[j % k] = flats[j % k].at[lin].add(hi)
                flats[(j + 1) % k] = flats[(j + 1) % k].at[lin].add(
                    lo.astype(jnp.bfloat16))
            else:
                flats[j % k] = flats[j % k].at[lin].add(w.astype(dtype))
        return tuple(flats)

    if chunk is None or chunk >= n:
        flats = body(pos, mass, tuple(flats))
    else:
        nchunks = (n + chunk - 1) // chunk
        npad = nchunks * chunk
        pos_p = jnp.concatenate(
            [pos, jnp.zeros((npad - n, 3), pos.dtype)], axis=0)
        mass_p = jnp.concatenate(
            [mass, jnp.zeros((npad - n,), dtype)], axis=0)
        pos_p = pos_p.reshape(nchunks, chunk, 3)
        mass_p = mass_p.reshape(nchunks, chunk)

        def loop(i, flats):
            return body(pos_p[i], mass_p[i], flats)
        flats = jax.lax.fori_loop(0, nchunks, loop, tuple(flats))

    # pairwise tree sum: log2(k) dependent adds instead of k
    flats = list(flats)
    if narrow:
        # the merge step re-widens FIRST: replicas stored bf16, the
        # accumulation across replicas runs f32 (NBK703: never add
        # mesh-sized operands at mixed widths)
        flats = [f.astype(jnp.float32) for f in flats]
    while len(flats) > 1:
        nxt = [a + b for a, b in zip(flats[::2], flats[1::2])]
        if len(flats) % 2:
            nxt.append(flats[-1])
        flats = nxt
    flat = flats[0]
    if out is not None:
        flat = flat + jnp.asarray(out).reshape(-1).astype(flat.dtype)
    return flat.reshape(shape)


# ---------------------------------------------------------------------------
# tile paint: one payload-carrying sort, contiguous bucket slices and
# per-tile matrix products (the option value and the function keep the
# name 'mxu')

def _bf16_parts(w):
    """f32 ``w`` as three bf16 addends, largest first: 24 mantissa
    bits, so their sum is ``w`` itself.  Split on bf16's grid with
    ``reduce_precision`` (ops/histogram.py:_bf16_grid: a convert pair
    in one fusion is excess precision to the TPU compiler)."""
    from .histogram import _bf16_grid
    hi = _bf16_grid(w)
    rest = w - hi
    mid = _bf16_grid(rest)
    return [p.astype(jnp.bfloat16)
            for p in (hi, mid, _bf16_grid(rest - mid))]


def tile_geometry(shape, resampler, rb=8, cb=8):
    """The tile deposit's geometry on a local block, or None where the
    block does not admit it (a window wider than the block or a tile,
    a wrap strip wider than an axis: test-sized meshes, which the
    scatter kernel paints).  THE rule by which the default paint picks
    its engine: a function of the block's shape and the window alone.

    Returns ``(rb, cb, ntx, nty)``: tile height and width in cells,
    x stripes over [0, n0l) (the deposit adds one leading wrap stripe)
    and y tiles."""
    n0l, N1, N2 = (int(x) for x in shape)
    s = window_support(resampler)
    # the leading tile must fit wrapped-to-valid deposits (rb) and the
    # y-halo fold pads cb - (s-1) columns (cb)
    rb, cb = max(rb, s), max(cb, s)
    rb, cb = min(rb, n0l), min(cb, N1)
    if n0l < max(s, 2) or N1 < s or N2 < s or n0l < rb:
        return None
    for rb, cb in ((rb, cb), (min(rb, max(s, n0l // 2)),
                              min(cb, max(s, N1 // 2)))):
        ntx, nty = -(-n0l // rb), -(-N1 // cb)
        # a wrap strip wider than its axis would double-wrap in the
        # single dense fold below
        if ntx * rb - n0l + s - 1 <= n0l and nty * cb - N1 + s - 1 <= N1:
            return rb, cb, ntx, nty
    return None


def paint_local_mxu(pos, mass, shape, resampler='cic', period=None,
                    origin=0, out=None, rb=8, cb=8, ck=PIECE_ROWS,
                    deposit='xla'):
    """Scatter particles onto a local mesh block with no per-particle
    scatter or gather: one sort that carries the payload, contiguous
    bucket slices, per-tile matrix products.

    On the chip one irregular access costs 8.9 ns an element and a
    two-operand sort 2.2 ns (PERF.md section 6, PR 33), and
    :func:`paint_local` issues one scatter-add of n per window offset.
    Here the window's separability makes the deposit dense: particles
    are bucketed by the (x-row-tile, y-col-tile) of their *base* cell
    and for every tile

        block[(r, y), z] = sum_p W0Y[p, (r, y)] * Z[p, z]

    is one product with M = (rb+s-1)*(cb+s-1) rows.  W0Y carries the
    x*y window product times the mass, Z the z window.  Tiles are
    batched over y and scanned over x with the mesh as carry, then
    halo and wrap strips are folded in with dense shifted adds.
    Periodic wrapping never produces a scatter: base cells near the
    boundary deposit into tile halos and the fold maps them home.

    1. One ``lax.sort`` of ``(key, x, y, z, mass)`` by the bucket key
       (stable): the sort moves the payload itself.  Zero-mass slots
       and rows outside a slab block sort to a trash bucket.
    2. A bucket is a contiguous run ``sorted[lo[b] : hi[b]]``; the
       ``B + 1`` run edges come from one ``searchsorted`` of the
       bucket ids.
    3. The sorted columns are read as rows of LANES particles.  Piece
       ``j`` of a stripe is, for each of its ``nty`` tiles, the ``ck /
       LANES`` whole rows from row ``lo[b] // LANES + j * ck / LANES``
       on (one row gather a column), the particles outside ``[lo[b],
       hi[b])`` masked; the stripe takes as many pieces as its fullest
       tile has rows, a trip count read from the data.  There is no
       capacity: a catalog with every particle in one cell takes more
       pieces and gives the exact field.
    4. In f32 one operand of each product is a pure 0/1 one-hot of the
       z cell (exact in bf16) and every weight sits in the other,
       split into three bf16 parts (:func:`_bf16_parts`), accumulated
       in f32: ``s`` one-hots against ``3 M`` columns a piece.  Wider
       dtypes (f64, the CPU tests) take one product at full precision.

    Semantics (positions in global cell units, ``origin``/``period``/
    valid-row masking) match :func:`paint_local`; both are held to an
    f64 deposit in tests/test_paint_mxu.py. Reference analog: pmesh's C
    CIC paint consumed at nbodykit/source/mesh/catalog.py:287-296.

    Parameters beyond :func:`paint_local`:

    rb, cb : tile height (x rows) and width (y cols).
    ck : rows a tile takes per piece, in whole rows of LANES.  A
        constant: on the chip 256 took 0.134 s of a 512^3 / 1e7 paint
        where 128 took 0.131 and 512 0.166, and 0.135 s of the
        four-chip cell's slab block where 128 took 0.158, 384 0.137
        and 640 0.160 (PERF.md section 6, PR 33): fewer pieces
        re-read the stripe's accumulator less often, larger ones pad
        more.
    deposit : 'xla' (one-hot expansions by XLA) or 'pallas' (fused VMEM
        kernel at the MXU's default precision, ops/paint_pallas.py —
        interpreted off-TPU).
    """
    if deposit not in ('xla', 'pallas'):
        raise ValueError("unknown deposit %r (choose 'xla'/'pallas')"
                         % (deposit,))
    n0l, N1, N2 = (int(x) for x in shape)
    if period is None:
        period = shape
    period = tuple(int(p) for p in period)
    if (period[1], period[2]) != (N1, N2):
        raise ValueError("mxu paint requires full y/z axes "
                         "(period[1:] == shape[1:]); x is the sliced "
                         "axis in this framework")
    p0 = period[0]
    s = window_support(resampler)
    geometry = tile_geometry(shape, resampler, rb, cb)
    if geometry is None:
        return paint_local(pos, mass, shape, resampler=resampler,
                           period=period, origin=origin, out=out)
    rb, cb, ntx, nty = geometry
    n = pos.shape[0]
    dtype = out.dtype if out is not None else (
        mass.dtype if hasattr(mass, 'dtype') else pos.dtype)
    dtype = jnp.dtype(dtype)
    mass = jnp.broadcast_to(jnp.asarray(mass, dtype=dtype), (n,))

    rbh, cbh = rb + s - 1, cb + s - 1
    M = rbh * cbh
    B = (ntx + 1) * nty
    cr = max(-(-int(ck) // LANES), 1)
    ck = cr * LANES
    split = dtype == jnp.float32 and deposit == 'xla'

    counter('paint.trace.tile').add(1)
    counter('paint.trace.tile_particles').add(int(n))
    gauge('paint.tile.buckets').set(int(B))
    gauge('paint.tile.ck').set(int(ck))

    # a slab's origin (d * n0 - h inside shard_map) is an operand, a
    # literal one a constant
    org = (origin,) if isinstance(origin, jax.Array) else ()

    @jax.custom_batching.sequential_vmap
    def tile_block(pos, mass, *org):
        """The block of one catalog.  Under ``vmap`` (the served
        program batches seeds) catalogs take turns: a batched sort
        along the minor axis of (1, n) operands read 0.413 s for the
        0.061 s of the same sort unbatched (PERF.md section 6, PR 33),
        and a batch of meshes would not fit anyway."""
        first_row = org[0] if org else origin

        def tile_row(x):
            """Block row of the base cell, the rows of a slab block's
            absent part shifted negative: rows in [n0l, p0) sit "below"
            the block, so that their wrapped-to-valid offsets (row + a >=
            0) land in the leading stripe and everything else is provably
            dropped."""
            row = jnp.mod(window_base(x, resampler) - first_row, p0)
            return jnp.where(row >= n0l, row - p0, row)

        # ---- 1. bucket key of the base cell, one sort with the payload -----
        row0 = tile_row(pos[:, 0])
        # zero-mass slots deposit nothing: exchange capacity padding
        # (pmesh.paint masks invalid slots to mass 0 with garbage
        # positions) goes to the trash bucket with the rows entirely below
        # a slab block
        keep = (row0 >= -rb) & (mass != 0)
        txf = jnp.clip((row0 + rb) // rb, 0, ntx)
        ty = jnp.mod(window_base(pos[:, 1], resampler), N1) // cb
        key = jnp.where(keep, txf * nty + ty, B).astype(jnp.int32)
        skey, *cols = jax.lax.sort(
            (key, pos[:, 0], pos[:, 1], pos[:, 2], mass),
            num_keys=1, is_stable=True)
        # the sorted columns as rows of LANES: a piece is whole rows,
        # read by one row gather of nty * ck / LANES indices (slices at
        # arbitrary offsets lower to a loop of nty dynamic slices a
        # column: 0.22 s a call on the chip, PERF.md section 6, PR 33)
        nrow = -(-n // LANES)
        sx, sy, sz, sm = (
            jnp.concatenate([c, jnp.zeros((nrow * LANES - n,), c.dtype)]
                            ).reshape(nrow, LANES) for c in cols)

        # ---- 2. buckets as contiguous runs ---------------------------------
        edges = jnp.searchsorted(skey, jnp.arange(B + 1, dtype=jnp.int32),
                                 side='left',
                                 method='scan_unrolled').astype(jnp.int32)
        lo = edges[:-1].reshape(ntx + 1, nty)
        hi = edges[1:].reshape(ntx + 1, nty)
        first = lo // LANES
        rows_b = jnp.where(hi > lo, -(-hi // LANES) - first, 0)
        trips = -(-jnp.max(rows_b, axis=1) // cr)

        # ---- 3./4. per-stripe deposit: batched products over the y tiles ---
        KX = nty * ck
        col_i = jax.lax.broadcasted_iota(jnp.int32, (KX, M), 1)
        z_i = jax.lax.broadcasted_iota(jnp.int32, (KX, N2), 1)
        ty_k = jnp.repeat(jnp.arange(nty, dtype=jnp.int32), ck)
        row_k = jax.lax.broadcasted_iota(jnp.int32, (nty, cr), 1)
        lane_k = jax.lax.broadcasted_iota(jnp.int32, (nty, cr, LANES), 2)
        width = 3 * M if split else M

        P0, P1 = (ntx + 1) * rb + s - 1, nty * cb + s - 1

        def piece(txi, x, y, z, m):
            """(nty, width, N2) deposit of KX rows, ck a tile."""
            ii2, ww2 = window_weights(z, resampler)
            rloc = jnp.clip(tile_row(x) + rb - txi * rb, 0, rb - 1)
            yloc = jnp.mod(window_base(y, resampler), N1) - ty_k * cb
            _, ww0 = window_weights(x, resampler)
            _, ww1 = window_weights(y, resampler)
            w0y = jnp.zeros((KX, M), dtype)
            for a in range(s):
                for b in range(s):
                    # tile-local: rloc < rb, |yloc| < N1, so col <
                    # (rb+s)*cbh + N1 — orders of magnitude inside int32
                    # for any tile geometry  # nbkl: disable=NBK704
                    col = (rloc + a) * cbh + (yloc + b)
                    w = (ww0[:, a] * ww1[:, b]).astype(dtype) * m
                    w0y = w0y + jnp.where(col[:, None] == col_i,
                                          w[:, None], 0)
            hot = [jnp.mod(ii2[:, c], N2)[:, None] == z_i for c in range(s)]
            if not split:
                zm = sum(jnp.where(hot[c], ww2[:, c, None].astype(dtype), 0)
                         for c in range(s))
                return jax.lax.dot_general(
                    w0y.reshape(nty, ck, M), zm.reshape(nty, ck, N2),
                    dimension_numbers=(((1,), (1,)), ((0,), (0,))),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=dtype)
            # the z cell as a 0/1 one-hot per window offset, every weight
            # on the other side in three bf16 parts side by side; the
            # offsets stack along the contraction
            lhs = jnp.concatenate(
                [jnp.concatenate(_bf16_parts(w0y * ww2[:, c, None]),
                                 axis=1).reshape(nty, ck, width)
                 for c in range(s)], axis=1)
            rhs = jnp.concatenate(
                [h.astype(jnp.bfloat16).reshape(nty, ck, N2) for h in hot],
                axis=1)
            return jax.lax.dot_general(
                lhs, rhs, dimension_numbers=(((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)

        def stripe(carry, xs):
            mesh_pad, txi = carry
            lo_t, hi_t, first_t, trips_t = xs

            def body(j, acc):
                at = first_t[:, None] + j * cr + row_k
                at_k = at[:, :, None] * LANES + lane_k
                # the rest of a row is the neighbouring buckets' (or a
                # trash slot's garbage): inert at position 0
                live = (at_k >= lo_t[:, None, None]) \
                    & (at_k < hi_t[:, None, None])

                def rows(c):
                    return jnp.where(
                        live, jnp.take(c, at, axis=0, mode='clip'),
                        0).reshape(nty, ck)

                x, y, z, m = rows(sx), rows(sy), rows(sz), rows(sm)
                if deposit == 'pallas':
                    from .paint_pallas import deposit_blocks_pallas
                    from ..utils import is_mxu_backend
                    return acc + deposit_blocks_pallas(
                        txi, x[:, None], y[:, None], z[:, None], m[:, None],
                        resampler=resampler, rb=rb, cb=cb, n0l=n0l, p0=p0,
                        N1=N1, N2=N2, origin=first_row, dtype=dtype,
                        interpret=not is_mxu_backend())
                return acc + piece(txi, x.reshape(KX), y.reshape(KX),
                                   z.reshape(KX), m.reshape(KX))

            acc = jax.lax.fori_loop(
                0, trips_t, body,
                vary_like(jnp.zeros((nty, width, N2), dtype), pos, mass))
            # the three parts' sums, smallest first
            blocks = acc[:, 2 * M:] + acc[:, M:2 * M] + acc[:, :M] \
                if split else acc
            # fold the y tiles into a (rbh, P1, N2) slab: interior cols by
            # reshape, halo cols by a cb-shifted dense add
            blocks = blocks.reshape(nty, rbh, cbh, N2).transpose(1, 0, 2, 3)
            interior = blocks[:, :, :cb].reshape(rbh, nty * cb, N2)
            halo = jnp.pad(blocks[:, :, cb:],
                           ((0, 0), (0, 0), (0, cb - (s - 1)), (0, 0)))
            halo = halo.reshape(rbh, nty * cb, N2)
            slab = jnp.pad(interior, ((0, 0), (0, s - 1), (0, 0)))
            slab = slab + jnp.pad(halo, ((0, 0), (cb, 0), (0, 0))
                                  )[:, :P1]
            # wrap strip: cols >= N1 are the periodic y images
            slab = slab[:, :N1] + jnp.pad(slab[:, N1:],
                                          ((0, 0), (0, 2 * N1 - P1), (0, 0)))
            row = txi * rb
            zero = jnp.zeros((), row.dtype)
            upd = jax.lax.dynamic_slice(mesh_pad, (row, zero, zero),
                                        (rbh, N1, N2)) + slab
            mesh_pad = jax.lax.dynamic_update_slice(mesh_pad, upd,
                                                    (row, zero, zero))
            return (mesh_pad, txi + 1), None

        # inside a slab mesh's shard_map the carry starts replicated and
        # takes device-local deposits
        (mesh_pad, _), _ = jax.lax.scan(
            stripe,
            (vary_like(jnp.zeros((P0, N1, N2), dtype), pos, mass),
             vary_like(jnp.int32(0), pos, mass)),
            (lo, hi, first, trips))

        # ---- unpad x: rows [rb, rb+n0l) are the block; fold the periodic
        # images (leading wrap tile + trailing halo) into it when the block
        # IS the full mesh, drop them for slab blocks (invalid rows by
        # contract)
        block = mesh_pad[rb:rb + n0l]
        if n0l == p0:
            # true rows [-rb, 0) wrap to + n0l, true rows >= n0l to - n0l
            nt = P0 - rb - n0l
            block = block.at[n0l - rb:].add(mesh_pad[:rb])
            block = block.at[:nt].add(mesh_pad[rb + n0l:])
        return block

    @jax.custom_vjp
    def painted(pos, mass, *org):
        return tile_block(pos, mass, *org)

    def painted_fwd(pos, mass, *org):
        return tile_block(pos, mass, *org), (pos, mass, org)

    def painted_bwd(saved, g):
        """The deposit's adjoint is the readout (forward/adjoint.py):
        reverse mode cannot pass the data-dependent piece loop."""
        pos, mass, org = saved
        at = dict(resampler=resampler, period=period,
                  origin=org[0] if org else origin)
        dpos = jnp.stack([mass * readout_local(g, pos, grad_axis=ax, **at)
                          for ax in range(3)], axis=-1)
        return (dpos.astype(pos.dtype),
                readout_local(g, pos, **at).astype(mass.dtype)) \
            + (None,) * len(org)

    painted.defvjp(painted_fwd, painted_bwd)
    block = painted(pos, mass, *org)
    if out is not None:
        block = jnp.asarray(out) + block
    return block
