"""The TPU pair-counting kernel.

Replaces the Corrfunc C/AVX kernels the reference wraps
(nbodykit/algorithms/pair_counters/corrfunc/*; SURVEY.md §2.3): weighted
pair counts binned in r, (r, mu), (rp, pi), or theta.

One counting body, :func:`_count_tiles`, in the shape the chip runs
(the idiom of ``ops/paint.py``'s tile paint):

1. **grid** (``paircount.grid``): every point's cell id on a grid of
   cells no smaller than rmax, z fastest, and one ``lax.sort`` that
   carries ``(x, y, z[, w])`` with it.  A cell, a pencil of cells
   along z and any z-range of a pencil are contiguous runs of the
   sorted columns; their edges come from one ``searchsorted`` of the
   cell ids.
2. **tiles** (``paircount.tiles``): a block of primaries is one row of
   128 sorted primaries inside one (x, y) pencil; it meets, for each of
   the 9 neighbouring pencils, the run of the cells ``z0 - 1 .. z1 + 1``
   around its own z cells (and, across a periodic z face, the one cell
   on the other side), read as whole rows of the sorted columns, a
   piece of ``_PIECE_ROWS`` rows at a time.  The runs' edges are tables
   made in the grid half for every block at once; blocks are taken
   ``_BATCH`` at a time, and the loop walks the (batch, run) items
   that hold a candidate, each as dense ``(batch, 128, piece)`` tiles
   of squared distances; slots outside a run, a block or the radius
   are masked.  Both trip counts are read from the data (the items
   that hold a candidate; the fullest run of a batch): there is no
   capacity, nothing can drop, and a catalog with every point in one
   cell takes more pieces and gives the exact answer.
3. The radial index is the cumulative compare itself, ``#{pairs: key <
   e_j^2}`` for every edge, differenced on the host; ``(r, mu)``,
   ``(rp, pi)`` and ``theta`` bin the same tile with their own second
   index.  No ``digitize``, no scatter, no per-candidate gather.
4. Counts are exact integers: int32 within a piece (a piece holds at
   most 2^30 slots), carried across pieces as an int32 ``(hi, lo)``
   pair of 2^24, int64 on the host (as Corrfunc's uint64).  Weighted
   sums are per-piece sums in the working dtype, accumulated with
   compensation.

Periodic wrapping costs nothing per slot: where an axis has three
cells or more, a run across a face shifts one side by the box
(whichever side sits near the box's far end, so that the shift is
exact in f4) before the differences are taken; an axis of one or two
cells (test sizes) takes the minimum image.

Two drivers share the body:

- :func:`paircount` — single device: one cached program
  (``compile.paircount.tiles.hits`` / ``.misses``); the catalog stays
  on the device;
- :func:`paircount_dist` — device mesh: primaries routed tight to
  x-slab owners, secondaries routed with both-side ghost copies within
  rmax (:func:`...parallel.domain.slab_route` — the analog of the
  reference's ``decompose_box_data``/``decompose_survey_data``,
  nbodykit/algorithms/pair_counters/domain.py:47-283), then the same
  body per device inside ``shard_map``, counts ``psum``-reduced. No
  device ever holds the full particle set.

``threeptcf.py``, ``fof.py``, ``cgm.py`` and ``kdtree.py`` keep the
per-candidate folds of ``ops/gridhash.py`` / ``ops/devicehash.py``.
"""

from functools import lru_cache

import numpy as np
import jax
import jax.numpy as jnp

from ...diagnostics import counter, fetch, instrumented_jit, scope
from ...ops.gridhash import neighbor_offsets
from ...parallel.runtime import vary_like
from ...utils import working_dtype

#: primaries a block holds and candidates a row holds: one row of the
#: sorted columns
LANES = 128
#: blocks a tile takes at a time, and rows of candidates a piece reads
#: per block.  Constants: at the pair-counting cell's size on the chip
#: (256, 4) read 0.335 s a call where (128, 4) read 0.356, (256, 6)
#: 0.393, (512, 6) 0.387, (64, 4) 0.399 and (64, 12) 0.621 (PERF.md
#: section 6, PR 35): a longer piece pads the shorter runs, and a
#: smaller batch makes more, smaller device operations: 113,475 a
#: call at (128, 4), 56,058 at (256, 4), where a profiler's window of
#: 22 s holds some 6.3e6
_BATCH = 256
_PIECE_ROWS = 4
#: an int32 count is carried as hi * 2^_LO_BITS + lo
_LO_BITS = 24
#: per-axis cap on the cell grid (cell ids stay far inside int32)
_MAX_NCELL = 128
#: mean secondaries a cell holds at least (a run of three fills most
#: of a piece)
_CELL_FILL = 64
#: cells are this much wider than rmax at least, so that a coordinate
#: rounded across a cell's face cannot hide a pair within rmax
_CELL_MARGIN = 1e-5


def rmax_of(mode, edges, pimax=None):
    """Max interaction radius of a mode/edges combination (used by
    callers to decide whether the slab-decomposed driver fits)."""
    edges = np.asarray(edges, dtype='f8')
    if mode == 'angular':
        return float(2 * np.sin(0.5 * np.radians(edges[-1])))
    if mode == 'projected':
        return float(np.sqrt(edges[-1] ** 2 + pimax ** 2))
    return float(edges[-1])


def _mode_setup(box, edges, mode, Nmu, pimax, periodic):
    """Shared mode normalization: working box, radial edges, bin
    counts, max interaction radius (positions are shifted by the
    caller: +2 for the unit sphere, -grid_origin else)."""
    box = np.ones(3) * np.asarray(box, dtype='f8')
    edges = np.asarray(edges, dtype='f8')
    if mode == 'angular':
        # positions are unit vectors; chord distance bins
        redges = 2 * np.sin(0.5 * np.radians(edges))
        work_box = np.ones(3) * 4.0  # unit sphere fits in [-2,2]
        periodic = False
    else:
        redges = edges
        work_box = box

    if mode == '1d':
        rmax, nb2 = redges[-1], 1
    elif mode == '2d':
        rmax, nb2 = redges[-1], int(Nmu)
    elif mode == 'projected':
        rmax, nb2 = np.sqrt(redges[-1] ** 2 + pimax ** 2), int(pimax)
    elif mode == 'angular':
        rmax, nb2 = redges[-1], 1
    else:
        raise ValueError("unknown mode %r" % mode)
    if not np.all(np.diff(redges) > 0):
        # the radial index counts the edges below a value
        raise ValueError("edges must be strictly ascending")
    return work_box, redges, float(rmax), nb2, bool(periodic)


def _grid_cells(work_box, rmax, n2):
    """Cells per axis: no smaller than rmax (with a margin for
    rounding), and no more than one to ``_CELL_FILL`` secondaries: a
    run of three cells should fill a piece, and on cells much emptier
    than that the tiles are mostly masked."""
    cap = int(np.clip(np.floor((max(n2, 1) / _CELL_FILL) ** (1 / 3.0)),
                      1, _MAX_NCELL))
    n = np.floor(work_box / (rmax * (1 + _CELL_MARGIN)))
    return tuple(int(x) for x in np.clip(n, 1, cap))


def _add_hilo(hi, lo, c):
    """``(hi, lo) += c`` for an int32 ``c`` in [0, 2^30)."""
    lo = lo + c
    return hi + (lo >> _LO_BITS), lo & ((1 << _LO_BITS) - 1)


def _from_hilo(hi, lo):
    return (np.asarray(hi).astype('i8') << _LO_BITS) \
        + np.asarray(lo).astype('i8')


def _sorted_rows(pos, w, live, ncell, cellsize):
    """The grid half: cell ids (z fastest; dead slots to a sentinel
    past every cell), one sort carrying the payload, the sorted
    columns as ``(nrow, 3 or 4, LANES)`` rows and the ``ncells + 1``
    run edges."""
    nx, ny, nz = ncell
    ncells = nx * ny * nz
    n = pos.shape[0]
    ci = [jnp.clip(jnp.floor(pos[:, a] / cellsize[a]).astype(jnp.int32),
                   0, ncell[a] - 1) for a in range(3)]
    # flat ids < 128^3  # nbkl: disable=NBK704
    key = (ci[0] * ny + ci[1]) * nz + ci[2]
    if live is not None:
        key = jnp.where(live, key, ncells)
    cols = (pos[:, 0], pos[:, 1], pos[:, 2]) + (() if w is None else (w,))
    skey, *cols = jax.lax.sort((key,) + cols, num_keys=1, is_stable=True)
    # the sorted columns side by side as rows of LANES points: one row
    # gather reads a row of every column (a gather costs by the row,
    # 0.1 us on the chip: PERF.md section 6, PR 35)
    nrow = max(-(-n // LANES), 1)
    rows = jnp.stack([
        jnp.concatenate([c, jnp.zeros((nrow * LANES - n,), c.dtype)]
                        ).reshape(nrow, LANES) for c in cols], axis=1)
    starts = jnp.searchsorted(
        skey, jnp.arange(ncells + 1, dtype=jnp.int32), side='left',
        method='scan_unrolled').astype(jnp.int32)
    return skey, rows, starts


class _Plan(object):
    """What the counting body reads of the grid, the box and the edges,
    as host constants of the working dtype: made once per program,
    outside the trace."""

    def __init__(self, ncell, box, periodic, r2edges, origin, dtype):
        dt = np.dtype(dtype)
        box = np.asarray(box, 'f8')
        self.ncell, self.periodic = ncell, periodic
        self.box = box.astype(dt)
        self.cellsize = (box / np.asarray(ncell)).astype(dt)
        # with ``box`` the box as two addends of the working dtype: a
        # shift by it is exact for a coordinate in the box's far cell
        # (Sterbenz)
        self.box_lo = (box - self.box.astype('f8')).astype(dt)
        self.e2 = np.asarray(r2edges, 'f8').astype(dt)
        self.origin = np.asarray(origin, 'f8').astype(dt)
        # an axis of three cells and more shifts a run across a face
        # by the box; one of fewer takes the minimum image
        self.shift = tuple(periodic and n >= 3 for n in ncell)
        self.image = tuple(periodic and n < 3 for n in ncell)
        # runs: (dx, dy, z segment: main, below 0, past nz)
        oxy = sorted(set((o[0], o[1])
                         for o in neighbor_offsets(ncell, periodic=periodic)))
        segs = (0, 1, 2) if self.shift[2] else (0,)
        self.runs = np.asarray([(dx, dy, s) for dx, dy in oxy
                                for s in segs], 'i4')
        if int(np.prod(ncell)) + 1 > np.iinfo(np.int32).max:
            raise ValueError("too many cells for int32 ids: %r" % (ncell,))


def _count_tiles(p1, w1, live1, p2, w2, live2, plan, *, same, mode, nb2,
                 pimax, los, pair_los, is_auto):
    """Cumulative pair counts of primaries against secondaries: THE
    counting body (see the module docstring).

    ``p1`` / ``p2`` are (n, 3) work coordinates in [0, box) of the
    ``plan``'s dtype; ``w1`` / ``w2`` weights or None (both); ``live1`` /
    ``live2`` bool masks or None; ``same`` says the secondaries are the
    primaries (one sort).  Returns a dict of small arrays: ``hi`` /
    ``lo`` (nedges, nb2) int32 cumulative counts ``#{key < e_j^2}``,
    ``wsum`` (nedges - 1, nb2) per-bin weighted sums (None
    unweighted), ``slots`` the candidate slots met (f32), ``fullest``
    the longest run.
    """
    dt = p1.dtype
    ncell, periodic = plan.ncell, plan.periodic
    nx, ny, nz = ncell
    ncells = nx * ny * nz
    cellsize = jnp.asarray(plan.cellsize)
    weighted = w1 is not None
    nedges = len(plan.e2)
    e2 = jnp.asarray(plan.e2)
    if max(p1.shape[0], p2.shape[0]) + LANES > np.iinfo(np.int32).max:
        raise ValueError("too many points for int32 slot indices")

    with scope('paircount.grid'):
        if periodic:
            # the faces themselves: x = box belongs to cell 0
            p1 = jnp.mod(p1, jnp.asarray(plan.box))
            p2 = p1 if same else jnp.mod(p2, jnp.asarray(plan.box))
        skey1, rows1, starts1 = _sorted_rows(p1, w1, live1, ncell,
                                             cellsize)
        if same:
            rows2, starts2 = rows1, starts1
        else:
            _, rows2, starts2 = _sorted_rows(p2, w2, live2, ncell,
                                             cellsize)
        nrow1 = rows1.shape[0]

        # ---- blocks: the rows each non-empty pencil spans --------------
        npencil = nx * ny
        ps, pe = starts1[0:ncells:nz], starts1[nz::nz]
        prow = ps // LANES
        pnrow = jnp.where(pe > ps, (pe - 1) // LANES - prow + 1, 0)
        cum = jnp.cumsum(pnrow)
        nblocks = cum[-1]
        # a pencil's rows overlap its neighbours' by one at most
        most = nrow1 + min(npencil, nrow1 * LANES)
        G = min(_BATCH, most)
        nbmax = -(-most // G) * G
        k = jnp.arange(nbmax, dtype=jnp.int32)
        pk = jnp.minimum(jnp.searchsorted(
            cum, k, side='right', method='scan_unrolled'
        ).astype(jnp.int32), npencil - 1)
        brow = prow[pk] + k - (cum[pk] - pnrow[pk])
        ba = jnp.maximum(ps[pk], brow * LANES)
        bb = jnp.where(k < nblocks,
                       jnp.minimum(pe[pk], (brow + 1) * LANES), ba)
        brow = jnp.clip(brow, 0, nrow1 - 1)
        # z cells of the block's first and last primary
        nkey = skey1.shape[0]
        bz0 = skey1[jnp.clip(ba, 0, nkey - 1)] % nz
        bz1 = skey1[jnp.clip(bb - 1, 0, nkey - 1)] % nz
        bcx, bcy = pk // ny, pk % ny
        bn1 = bb - ba                   # primaries a block holds

        # ---- runs: for every block and (dx, dy, z segment) the edges
        # in the sorted secondaries and which side shifts by the box,
        # all at once, so that the loops below only read tables -------
        shift, image = plan.shift, plan.image
        R = len(plan.runs)
        rdx, rdy, rseg = (jnp.asarray(plan.runs[:, i]).reshape(R, 1)
                          for i in range(3))
        empty = (bn1 <= 0)[None]
        n, up, down = [bcx[None] + rdx, bcy[None] + rdy], [], []
        for a in range(2):
            over, under = n[a] >= ncell[a], n[a] < 0
            if periodic:
                n[a] = jnp.mod(n[a], ncell[a])
            else:
                empty = empty | over | under
                n[a] = jnp.clip(n[a], 0, ncell[a] - 1)
            up.append(over & shift[a])
            down.append(under & shift[a])
        # flat ids < 128^3  # nbkl: disable=NBK704
        q = (n[0] * ny + n[1]) * nz
        za = jnp.where(rseg == 0, jnp.maximum(bz0 - 1, 0)[None],
                       jnp.where(rseg == 1, nz - 1, 0))
        zb = jnp.where(rseg == 0, jnp.minimum(bz1 + 1, nz - 1)[None],
                       jnp.where(rseg == 1, nz - 1, 0))
        empty = empty | ((rseg == 1) & (bz0 != 0)[None]) \
            | ((rseg == 2) & (bz1 != nz - 1)[None])
        rlo = starts2[q + za]                                # (R, nbmax)
        rhi = jnp.where(empty, rlo, starts2[q + zb + 1])
        # which side steps back by the box: the primaries where the
        # run lies past the far face, the candidates where below 0
        full = jnp.ones((R, nbmax), bool)
        f1 = jnp.stack(up + [full & (rseg == 2)]).astype(dt)  # (3, R, nbmax)
        f2 = jnp.stack(down + [full & (rseg == 1)]).astype(dt)
        cr = min(_PIECE_ROWS, rows2.shape[0])
        K = cr * LANES
        rfirst = rlo // LANES
        nrows = jnp.where(rhi > rlo, -(-rhi // LANES) - rfirst, 0)
        nbatch = nbmax // G
        trips = -(-nrows.reshape(R, nbatch, G).max(axis=2) // cr)
        # work list: the (batch, run) items that hold a candidate, batch
        # by batch; the loop below makes as many trips as there are
        busy = jnp.cumsum((trips.T > 0).reshape(-1).astype(jnp.int32))
        nwork = busy[-1]
        work = jnp.searchsorted(
            busy, jnp.arange(nbatch * R, dtype=jnp.int32), side='right',
            method='scan_unrolled').astype(jnp.int32)
        # candidate slots met and the longest run: sums over the table
        # (f32: a diagnostic, good to 1e-6)
        slots = (bn1[None].astype(jnp.float32)
                 * (rhi - rlo).astype(jnp.float32)).sum()
        fullest = jnp.max(rhi - rlo)

    bfull, bl = (jnp.asarray(x).reshape(3, 1, 1)
                 for x in (plan.box, plan.box_lo))
    from ...utils import is_mxu_backend
    onehot_dt = jnp.bfloat16 if is_mxu_backend() else jnp.float32
    row_k = jnp.arange(cr, dtype=jnp.int32)
    lane = jnp.arange(LANES, dtype=jnp.int32)
    slot_k = jnp.arange(K, dtype=jnp.int32)
    org = jnp.asarray(plan.origin)

    def shifted(x, flag):
        """``x - flag * box`` per axis, the box in two addends: ``x``
        (3, G, n), ``flag`` (3, G) of 0 / 1."""
        f = flag[:, :, None]
        return (x - f * bfull) - f * bl

    def tile(x1, x2, wa, wb, ok):
        """One piece: (G, LANES, K) squared distances and their masks
        summed.  ``x1`` three (G, LANES), ``x2`` three (G, K)."""
        d = []
        for a in range(3):
            da = x1[a][:, :, None] - x2[a][:, None, :]
            if image[a]:
                da = da - bfull[a, 0, 0] * jnp.round(da / bfull[a, 0, 0])
            d.append(da)
        r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2]
        if is_auto:
            # exact self-pairs (and coincident points) are no pairs
            ok = ok & (r2 > 0)
        key, second = r2, None
        if mode in ('2d', 'projected'):
            if pair_los == 'midpoint':
                # observer at the (pre-shift) coordinate origin
                mid = [0.5 * (x1[a][:, :, None] + x2[a][:, None, :])
                       + org[a] for a in range(3)]
                mnorm = jnp.sqrt(sum(m * m for m in mid))
                dlos = jnp.abs(sum(d[a] * mid[a] for a in range(3))) \
                    / jnp.where(mnorm == 0, 1.0, mnorm)
            else:
                dlos = jnp.abs(d[los])
            if mode == '2d':
                rr = jnp.sqrt(jnp.where(r2 == 0, 1.0, r2))
                mu = jnp.where(r2 == 0, 0.0, dlos / rr)
                second = (mu * nb2).astype(jnp.int32)
            else:
                key = r2 - dlos * dlos
                second = dlos.astype(jnp.int32)
                ok = ok & (dlos < pimax)
            second = jnp.clip(second, 0, nb2 - 1)
        # a masked slot sits past every edge
        key = jnp.where(ok, key, jnp.inf)
        ww = wa[:, :, None] * wb[:, None, :] if weighted else None
        if second is None:
            # every edge's count (and every bin's weighted sum) in ONE
            # pass over the tile: a reduce of as many operands as edges
            # shares the squared distances among them and stores
            # nothing of the tile's size, where a sum over a leading
            # edge axis has them stored and read once an edge
            # (0.537 -> 0.399 s a call on the chip: PERF.md section 6,
            # PR 35).  Per-bin weighted sums, not differences of
            # cumulative ones, which would lose the inner bins
            under = [key < e2[j] for j in range(nedges)]
            terms = [u.astype(jnp.int32) for u in under]
            if weighted:
                terms += [jnp.where(under[j + 1] & ~under[j], ww, 0)
                          for j in range(nedges - 1)]
            sums = jax.lax.reduce(
                tuple(terms), tuple(jnp.zeros((), t.dtype) for t in terms),
                lambda x, y: tuple(a + b for a, b in zip(x, y)), (0, 1, 2))
            cnt = jnp.stack(sums[:nedges])[:, None]
            return cnt, (jnp.stack(sums[nedges:])[:, None]
                         if weighted else None)
        # two indices: the sums are products of the cumulative masks
        # with the second index's one-hot (as ops/histogram.py's),
        # E + nb2 compares a slot and not E * nb2, a block at a time:
        # a 0/1 is exact in bf16 and a block's count (at most LANES * K)
        # in the f32 it is summed in; the blocks add up as integers
        S = LANES * K
        key = key.reshape(G, 1, S)
        below = key < e2.reshape(1, -1, 1)                   # (G, E, S)
        hot = second.reshape(G, 1, S) == jnp.arange(
            nb2, dtype=jnp.int32).reshape(1, -1, 1)          # (G, nb2, S)
        by_block = (((2,), (2,)), ((0,), (0,)))

        def summed(lhs, rhs, **kw):
            return jax.lax.dot_general(lhs, rhs, by_block, **kw)

        cnt = summed(below.astype(onehot_dt), hot.astype(onehot_dt),
                     preferred_element_type=jnp.float32
                     ).astype(jnp.int32).sum(axis=0, dtype=jnp.int32)
        if not weighted:
            return cnt, None
        inbin = jnp.where(below[:, 1:] & ~below[:, :-1],
                          ww.reshape(G, 1, S), 0)
        if onehot_dt == jnp.bfloat16 and dt == jnp.float32:
            from ...ops.paint import _bf16_parts
            return cnt, sum(
                summed(part, hot.astype(onehot_dt),
                       preferred_element_type=jnp.float32)
                for part in reversed(_bf16_parts(inbin))).sum(axis=0)
        return cnt, summed(inbin, hot.astype(dt),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=dt).sum(axis=0)

    def item(i, carry):
        """One (batch, run) of the work list: its pieces."""
        flat = work[i]
        bi, r = flat // R, flat % R
        at = bi * G

        def blk(x):
            return jax.lax.dynamic_slice_in_dim(x, at, G)

        def run(x):
            start = (jnp.int32(0),) * (x.ndim - 2) + (r, at)
            size = x.shape[:-2] + (1, G)
            return jax.lax.dynamic_slice(x, start, size).reshape(
                x.shape[:-2] + (G,))

        a1, b1, row1 = blk(ba), blk(bb), blk(brow)
        at1 = row1[:, None] * LANES + lane
        live_1 = (at1 >= a1[:, None]) & (at1 < b1[:, None])     # (G, LANES)
        # (G, columns, LANES) -> columns of (G, LANES)
        prim = jnp.moveaxis(jnp.take(rows1, row1, axis=0), 1, 0)
        x1 = shifted(prim[:3], run(f1))
        lo, hi, first, back = run(rlo), run(rhi), run(rfirst), run(f2)

        def piece(j, carry):
            hi_c, lo_c, ws, wc = carry
            rows_at = first[:, None] + j * cr + row_k           # (G, cr)
            at2 = (first * LANES)[:, None] + j * K + slot_k      # (G, K)
            live_2 = (at2 >= lo[:, None]) & (at2 < hi[:, None])
            cand = jnp.moveaxis(
                jnp.take(rows2, rows_at, axis=0, mode='clip'), 2, 0
            ).reshape(-1, G, K)
            ok = live_1[:, :, None] & live_2[:, None, :]
            cnt, wsum = tile(x1, shifted(cand[:3], back),
                             prim[3] if weighted else None,
                             cand[3] if weighted else None, ok)
            hi_c, lo_c = _add_hilo(hi_c, lo_c, cnt)
            if weighted:
                # compensated (Kahan) across the pieces
                y = wsum - wc
                t = ws + y
                wc = (t - ws) - y
                ws = t
            return hi_c, lo_c, ws, wc

        return jax.lax.fori_loop(0, trips[r, bi], piece, carry)

    with scope('paircount.tiles'):
        zi = jnp.zeros((nedges, nb2), jnp.int32)
        zw = jnp.zeros((nedges - 1, nb2) if weighted else (), dt)
        init = tuple(vary_like(x, p1, p2) for x in (zi, zi, zw, zw))
        hi_c, lo_c, ws, wc = jax.lax.fori_loop(0, nwork, item, init)
    return dict(hi=hi_c, lo=lo_c, wsum=(ws - wc) if weighted else None,
                slots=slots, fullest=fullest)


@lru_cache(maxsize=64)
def _tile_program(mesh, same, weighted, masked, ncell, box, periodic,
                  r2edges, mode, nb2, pimax, los, pair_los, origin,
                  is_auto, dtype):
    """The counting body as one cached program, keyed on everything it
    reads but the operands' shapes (the jit's own key): the grid, the
    box, the edges, the mode, the working dtype.  On a ``mesh`` the body runs
    per device inside ``shard_map`` and the counts are ``psum``-reduced
    (``masked``: the exchange's live masks come with the operands)."""
    plan = _Plan(ncell, box, periodic, r2edges, origin, dtype)
    kw = dict(same=same, mode=mode, nb2=nb2, pimax=pimax, los=los,
              pair_los=pair_los, is_auto=is_auto)

    def unpack(args):
        args = list(args)
        p1 = args.pop(0)
        w1 = args.pop(0) if weighted else None
        l1 = args.pop(0) if masked else None
        if same:
            return p1, w1, l1, p1, w1, l1
        p2 = args.pop(0)
        w2 = args.pop(0) if weighted else None
        l2 = args.pop(0) if masked else None
        return p1, w1, l1, p2, w2, l2

    def count(*args):
        return _count_tiles(*unpack(args), plan, **kw)

    if mesh is None:
        return instrumented_jit(count, label='paircount.tiles')

    from jax.sharding import PartitionSpec as P
    from ...parallel.runtime import AXIS

    def local(*args):
        out = count(*args)
        red = {k: jax.lax.psum(v, AXIS) for k, v in out.items()
               if v is not None and k != 'fullest'}
        red['fullest'] = jax.lax.pmax(out['fullest'], AXIS)
        red.setdefault('wsum', None)
        return red

    side = (P(AXIS, None),) + (P(AXIS),) * (weighted + masked)
    return instrumented_jit(
        jax.shard_map(local, mesh=mesh,
                      in_specs=side * (1 if same else 2), out_specs=P()),
        label='paircount.tiles')


def _run(mesh, pos1, w1, live1, pos2, w2, live2, box, edges, mode, Nmu,
         pimax, los, periodic, is_auto, grid_origin, pair_los, same):
    """Both drivers' common half: the program for these sizes, its one
    launch under the ``paircount`` scopes, the counts as host arrays.
    (``paircount.run`` is the user's call, ``SimulationBoxPairCount``;
    a direct call of a driver is its own root, ``paircount.count``.)"""
    work_box, redges, rmax, nb2, periodic = _mode_setup(
        box, edges, mode, Nmu, pimax, periodic)
    n1, n2 = int(pos1.shape[0]), int(pos2.shape[0])
    nb1 = len(redges) - 1
    weighted = w1 is not None
    origin = tuple(float(x) for x in np.broadcast_to(
        np.asarray(grid_origin, 'f8'), (3,)))
    ncell = _grid_cells(work_box, rmax, n2)
    if n1 == 0 or n2 == 0:
        zero = np.zeros((nb1, nb2)).squeeze()
        return dict(npairs=zero.astype('i8'), wnpairs=zero)
    with scope('paircount.count', mode=mode, n1=n1, n2=n2, nbins=nb1,
               rmax=rmax, is_auto=bool(is_auto)):
        program = _tile_program(
            mesh, bool(same), weighted, live1 is not None, ncell,
            tuple(float(x) for x in work_box), periodic,
            tuple(float(x) for x in redges ** 2), mode, nb2,
            None if pimax is None else float(pimax), int(los), pair_los,
            origin, bool(is_auto), pos1.dtype.name)
        side1 = [x for x in (pos1, w1, live1) if x is not None]
        side2 = [] if same else [x for x in (pos2, w2, live2)
                                 if x is not None]
        with scope('paircount.tiles', tile=LANES,
                   cells=int(np.prod(ncell))) as sc:
            out = fetch(program(*(side1 + side2)), 'paircount.counts')
            cum = _from_hilo(out['hi'], out['lo'])
            slots = int(round(float(out['slots'])))
            pairs = int(cum[-1].sum())
            sc.set(fullest_run=int(out['fullest']), slots=slots,
                   pairs=pairs)
        counter('paircount.slots').add(slots)
        counter('paircount.pairs').add(pairs)
    npairs = np.diff(cum, axis=0)
    wnpairs = npairs.astype('f8') if not weighted \
        else np.asarray(out['wsum'], 'f8')
    return dict(npairs=npairs.reshape(nb1, nb2).squeeze(),
                wnpairs=wnpairs.reshape(nb1, nb2).squeeze())


def _work_coordinates(pos, mode, grid_origin, dt):
    """Positions in the working dtype, shifted into [0, work_box)."""
    pos = jnp.asarray(pos, dt)
    if mode == 'angular':
        return pos + jnp.asarray(2.0, dt)
    origin = np.asarray(grid_origin, 'f8')
    if not np.any(origin):
        return pos
    return pos - jnp.asarray(origin, dt)


def paircount(pos1, w1, pos2, w2, box, edges, mode='1d', Nmu=None,
              pimax=None, los=2, periodic=True, is_auto=False,
              grid_origin=0.0, pair_los='axis'):
    """Weighted pair counts (single-device driver).

    Parameters
    ----------
    pos1, w1 : primaries (N1, 3), (N1,) — host or device arrays; a
        device array stays on the device
    pos2, w2 : secondaries (may be the same arrays; set is_auto)
    box : (3,) periodic box (used for wrapping when ``periodic``)
    edges : radial bin edges — r for '1d'/'2d', rp for 'projected',
        theta degrees for 'angular'
    mode : '1d' | '2d' | 'projected' | 'angular'
    Nmu : number of mu bins in [0, 1] for mode='2d'
    pimax : max line-of-sight separation, with 1 Mpc/h pi bins, for
        mode='projected'
    los : line-of-sight axis index (0, 1, 2)
    is_auto : self-pairs are excluded; every pair counted twice
        (i<j and j>i), matching the reference's Corrfunc conventions
    grid_origin : (3,) offset subtracted before cell hashing (lets
        non-periodic data sit anywhere)
    pair_los : 'axis' (mu against the ``los`` axis; periodic-box
        convention) or 'midpoint' (mu against the pair midpoint
        direction from the observer at the coordinate origin; the
        Corrfunc-mocks convention for survey data)

    Returns
    -------
    dict with 'npairs' (int64, exact: as Corrfunc's uint64) and
    'wnpairs' (f8; with no weights, ``npairs`` as floats) arrays of
    the binned shape.

    Notes
    -----
    Distances are formed in the working dtype (f8 under x64, else
    f4: about 4e-7 of r^2, so a pair within that of a bin edge may
    fall on either side); the counts themselves are integers at every
    step, and weighted sums are compensated sums of the working dtype.
    """
    dt = working_dtype('f8')
    same = pos2 is pos1 and w2 is w1
    weighted = w1 is not None or w2 is not None

    def side(pos, w):
        pos = _work_coordinates(pos, mode, grid_origin, dt)
        if not weighted:
            return pos, None
        return pos, (jnp.ones(pos.shape[0], dt) if w is None
                     else jnp.asarray(w, dt))

    p1, w1 = side(pos1, w1)
    p2, w2 = (p1, w1) if same else side(pos2, w2)
    return _run(None, p1, w1, None, p2, w2, None, box, edges, mode, Nmu,
                pimax, los, periodic, is_auto, grid_origin, pair_los,
                same)


def paircount_dist(pos1, w1, pos2, w2, box, edges, mesh, mode='1d',
                   Nmu=None, pimax=None, los=2, periodic=True,
                   is_auto=False, grid_origin=0.0, pair_los='axis'):
    """Weighted pair counts over the device mesh.

    Same contract as :func:`paircount`, but pos/w arrive as global
    sharded jnp arrays and the counting runs domain-decomposed: no
    device ever gathers the catalogs. Requires rmax <= work_box_x / P
    (single-hop ghosts); callers fall back to :func:`paircount` when
    that fails.
    """
    from ...parallel.domain import slab_route

    dt = working_dtype('f8')
    weighted = w1 is not None or w2 is not None
    p1 = _work_coordinates(pos1, mode, grid_origin, dt)
    p2 = p1 if pos2 is pos1 else _work_coordinates(pos2, mode,
                                                   grid_origin, dt)

    def weights(w, n):
        if not weighted:
            return []
        return [jnp.ones(n, dt) if w is None else jnp.asarray(w, dt)]

    work_box, _, rmax, _, wraps = _mode_setup(box, edges, mode, Nmu,
                                              pimax, periodic)
    # route primaries tight, secondaries with ghosts on both faces;
    # slab boundaries are balanced on the primaries' histogram
    # (reference pair_counters/domain.py:256) and SHARED by both
    # routes so every primary sees its rmax-neighborhood
    route1, f1, live1 = slab_route(p1, work_box, rmax, mesh,
                                   ghosts=None, periodic=wraps,
                                   balance=True)
    route2, f2, live2 = slab_route(p2, work_box, rmax, mesh,
                                   ghosts='both', periodic=wraps,
                                   edges=route1.edges)
    (p1_r, *w1_r), ok1, _ = route1.exchange(
        [p1] + weights(w1, p1.shape[0]))
    (lv2, p2_r, *w2_r), ok2, _ = route2.exchange(
        [live2] + [jnp.concatenate([x] * f2)
                   for x in [p2] + weights(w2, p2.shape[0])])
    w1_r, w2_r = (w[0] if w else None for w in (w1_r, w2_r))
    ok2 = ok2 & lv2
    # the work coordinates are final: no second shift in the program
    return _run(mesh, p1_r, w1_r, ok1, p2_r, w2_r, ok2, box, edges, mode,
                Nmu, pimax, los, periodic, is_auto, grid_origin,
                pair_los, False)
