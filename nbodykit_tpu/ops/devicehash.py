"""In-graph grid hash: the jit-safe sibling of :class:`.gridhash.GridHash`.

:class:`.gridhash.GridHash` prepares its cell table on the host, which
forces callers to gather positions to one process — exactly the
single-device bottleneck the reference avoids with domain decomposition
(``pmesh.domain.GridND`` + ghost exchange, used by FOF at
nbodykit/algorithms/fof.py:367-411 and pair counting at
nbodykit/algorithms/pair_counters/domain.py:47-283).

:class:`DeviceGridHash` builds the cell index with pure jnp ops so it
can be constructed *inside* ``shard_map`` over each device's local
particles. Together with :func:`...parallel.exchange.exchange_by_dest`
(route particles + ghost copies to slab owners) this is the TPU-native
replacement for the reference's decompose/ghost machinery.

Design notes (vs the host version):

- **no dense cell table**: particles are sorted by flat cell id and
  neighbor cells are located by *binary search* into the sorted ids.
  This removes the ``max_ncell`` memory cap, so cells are exactly
  ``rmax``-sized — the occupancy K of a cell is the true local density,
  not density x (capped-cell volume / rmax^3). The reference gets the
  same effect from kd-tree node granularity (kdcount);
- accepts a ``valid`` mask (fixed-capacity exchange buffers have empty
  slots); invalid entries sort to a sentinel cell no search can match;
- the per-cell occupancy bound is a *traced* scalar, per neighbor
  offset (``max(count)``), swept with a ``lax.while_loop`` — compile
  cost is data-independent, and sweep cost adapts to the densest cell
  actually referenced by that offset (the load-balancing concern of
  SURVEY §2.2.3: one crowded cell no longer multiplies the *static*
  cost of every cell).
"""

import numpy as np
import jax
import jax.numpy as jnp

from .gridhash import neighbor_offsets


class DeviceGridHash(object):
    """Cell-hash neighbor sweep, fully in-graph.

    Parameters
    ----------
    pos : (n, 3) positions in [0, box) (device array; may be traced)
    box : (3,) static domain size
    rmax : static interaction radius (cells are >= rmax per side)
    valid : (n,) bool — live entries (None = all live)
    periodic : min-image wrapping at the box boundary
    max_ncell : static per-axis cap on the cell grid (memory-free here,
        but kept to bound flat-id magnitudes; ids use i64 when the cell
        count overflows i32)

    The grid geometry (ncell, cellsize, neighbor offsets) is static —
    computed from ``box``/``rmax`` which must be concrete numbers.
    """

    def __init__(self, pos, box, rmax, valid=None, periodic=True,
                 max_ncell=4096, axis_name=None):
        self.axis_name = axis_name
        box = np.ones(int(pos.shape[-1])) * np.asarray(box, dtype='f8')
        ncell = np.maximum(np.floor(box / float(rmax)), 1).astype('i8')
        ncell = np.minimum(ncell, int(max_ncell))
        cellsize = box / ncell
        self.periodic = bool(periodic)
        self.ncell_np = ncell
        self.ncells_tot = int(np.prod(ncell))
        self.offsets = neighbor_offsets(ncell, periodic=periodic)
        self._offs = jnp.asarray(self.offsets, dtype=jnp.int32)
        self._idt = jnp.int32 if self.ncells_tot < 2 ** 31 - 1 \
            else jnp.int64
        self.ncell = jnp.asarray(ncell, jnp.int32)
        self.cellsize = jnp.asarray(cellsize, pos.dtype)
        self.box = jnp.asarray(box, pos.dtype)

        n = pos.shape[0]
        if valid is None:
            valid = jnp.ones(n, dtype=bool)
        flat = self._flatten(self.cell_of(pos))
        # dead slots go to a sentinel id no query can produce
        flat = jnp.where(valid, flat,
                         jnp.asarray(self.ncells_tot, self._idt))
        if self._idt is jnp.int32:
            # cell-id alphabet is known: the stable counting order
            # replaces the bitonic argsort on TPU (ops/radix.py)
            from .radix import stable_order
            order = stable_order(flat, int(self.ncells_tot) + 1)
        else:
            order = jnp.argsort(flat)
        self.flat_s = flat[order]
        self.order = order
        self.pos_s = pos[order]
        self.valid_s = valid[order]

    def _flatten(self, ci):
        nc1 = jnp.asarray(int(self.ncell_np[1]), self._idt)
        nc2 = jnp.asarray(int(self.ncell_np[2]), self._idt)
        ci = ci.astype(self._idt)
        return (ci[..., 0] * nc1 + ci[..., 1]) * nc2 + ci[..., 2]

    def cell_of(self, p):
        return jnp.clip((p / self.cellsize).astype(jnp.int32), 0,
                        self.ncell - 1)

    def _offset_tables(self, p, ci, oi):
        """(start, count, oob) of the oi-th neighbor cell per query,
        via binary search into the sorted cell ids."""
        nc = ci + self._offs[oi]
        if self.periodic:
            nc = jnp.mod(nc, self.ncell)
            oob = jnp.zeros(p.shape[0], bool)
        else:
            clipped = jnp.clip(nc, 0, self.ncell - 1)
            oob = jnp.any(nc != clipped, axis=-1)
            nc = clipped
        nflat = self._flatten(nc)
        start = jnp.searchsorted(self.flat_s, nflat)
        count = jnp.searchsorted(self.flat_s, nflat,
                                 side='right') - start
        return start.astype(jnp.int32), count.astype(jnp.int32), oob

    def _candidate(self, p, s, c, oob, slot):
        j = s + slot
        valid = (slot < c) & ~oob
        j = jnp.where(valid, j, 0)
        d = self.pos_s[j] - p
        if self.periodic:
            d = d - jnp.round(d / self.box) * self.box
        r2 = jnp.sum(d * d, axis=-1)
        return j, valid, d, r2

    def pvary(self, x):
        """Mark a constant as device-varying (no-op outside shard_map).

        While-loop carries must have matching varying-manual-axes types
        on input and output; constant-initialized carries fed through
        data-dependent bodies need this under shard_map.
        """
        if self.axis_name is None:
            return x
        from ..parallel.runtime import vary_like
        return vary_like(x, self.pos_s)

    def fold(self, p, ci, body, carry):
        """Accumulate ``carry = body(carry, j, valid, d, r2)`` over all
        (offset, slot) candidates. ``j`` indexes the grid's *sorted*
        arrays (``pos_s``/``valid_s``; payloads must be pre-sorted with
        ``order``). Each offset's slot loop is a while_loop bounded by
        that offset's max referenced-cell occupancy."""
        carry = jax.tree.map(self.pvary, carry)
        for oi in range(len(self.offsets)):
            s, c, oob = self._offset_tables(p, ci, oi)
            kmax = jnp.max(jnp.where(oob, 0, c)) if c.shape[0] \
                else jnp.int32(0)

            def w_body(state, s=s, c=c, oob=oob):
                slot, carry = state
                j, valid, d, r2 = self._candidate(p, s, c, oob, slot)
                return slot + 1, body(carry, j, valid, d, r2)

            _, carry = jax.lax.while_loop(
                lambda st, kmax=kmax: st[0] < kmax, w_body,
                (self.pvary(jnp.int32(0)), carry))
        return carry


def local_fof_labels(pos, valid, box, ll, periodic=True,
                     max_ncell=4096, axis_name=None):
    """Connected components under a linking length, on one device's
    particle set, fully in-graph.

    Returns (n,) int32 — for every slot, the *slot index* of its
    component root (min slot index over the component); invalid slots
    are their own root. Mirrors the single-device sweep in
    ``algorithms.fof._fof_labels`` but jit-safe, so it can run inside
    ``shard_map`` (the per-rank role kdcount.cluster.fof plays in the
    reference, nbodykit/algorithms/fof.py:289-309).
    """
    n = pos.shape[0]
    grid = DeviceGridHash(pos, box, ll, valid=valid, periodic=periodic,
                          max_ncell=max_ncell, axis_name=axis_name)
    ci_s = grid.cell_of(grid.pos_s)
    ll2 = jnp.asarray(float(ll) ** 2, pos.dtype)
    vs = grid.valid_s

    def neighbor_min(labels):
        def body(best, j, ok, d, r2):
            ok = ok & vs & (r2 <= ll2)
            return jnp.minimum(best, jnp.where(ok, labels[j], best))
        return grid.fold(grid.pos_s, ci_s, body, labels)

    labels0 = grid.pvary(jnp.arange(n, dtype=jnp.int32))

    def body(state):
        labels, _ = state
        new = neighbor_min(labels)
        new = jnp.minimum(new, new[new])
        new = jnp.minimum(new, new[new])
        return new, jnp.any(new != labels)

    labels, _ = jax.lax.while_loop(
        lambda s: s[1], body, (labels0, grid.pvary(jnp.asarray(True))))

    # back to slot order: root slot = original slot of the root entry
    root_slot = grid.order[labels]
    out = jnp.zeros(n, dtype=jnp.int32).at[grid.order].set(
        root_slot.astype(jnp.int32))
    return out
