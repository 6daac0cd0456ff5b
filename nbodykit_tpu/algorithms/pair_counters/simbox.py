"""SimulationBoxPairCount: pair counts in a periodic box.

Reference: ``nbodykit/algorithms/pair_counters/simbox.py:6`` (wrapping
Corrfunc theory kernels DD/DDsmu/DDrppi). Here the tile kernel of
:mod:`.core` does the counting on device; the catalog stays there.
"""

import numpy as np
import jax.numpy as jnp

from .base import (PairCountBase, catalog_weights, package_result,
                   weight_totals)
from .core import paircount, paircount_dist, rmax_of
from ...diagnostics import scope
from ...parallel.runtime import mesh_size


class SimulationBoxPairCount(PairCountBase):
    """Count weighted pairs in bins of separation.

    Parameters (reference simbox.py): mode in
    {'1d','2d','projected','angular'}, first/second catalogs, edges,
    BoxSize, periodic, weight column, Nmu, pimax, los ('x'|'y'|'z').

    Results in :attr:`pairs`: ``npairs`` is int64 and exact (as
    Corrfunc's uint64: every pair counted twice in an
    autocorrelation), ``wnpairs`` f8 (``npairs`` as floats where the
    catalogs carry no weight column); attrs hold the total weighted
    pair normalizations used by the estimators.
    """

    def __init__(self, mode, first, edges, BoxSize=None, periodic=True,
                 weight='Weight', second=None, los='z', Nmu=None,
                 pimax=None, show_progress=False):
        # the call's root, from its first line (either driver opens
        # ``paircount.count`` around its program)
        with scope('paircount.run', mode=mode):
            if mode not in ('1d', '2d', 'projected', 'angular'):
                raise ValueError("invalid mode %r" % mode)
            if mode == '2d' and Nmu is None:
                raise ValueError("mode='2d' requires Nmu")
            if mode == 'projected' and pimax is None:
                raise ValueError("mode='projected' requires pimax")
            los_i = {'x': 0, 'y': 1, 'z': 2}[los]

            if BoxSize is None:
                BoxSize = first.attrs['BoxSize']
            BoxSize = np.ones(3) * np.asarray(BoxSize, dtype='f8')

            self.first = first
            self.second = second
            self.comm = first.comm
            self.attrs = dict(mode=mode, edges=np.asarray(edges),
                              BoxSize=BoxSize, periodic=periodic, los=los,
                              Nmu=Nmu, pimax=pimax, weight=weight)

            # device-mesh path: catalogs stay sharded, counting is domain-
            # decomposed (reference decompose_box_data, pair_counters/
            # domain.py:47-132); fall back to the single-device driver when
            # rmax exceeds the slab width or there is one device
            nproc = mesh_size(self.comm)
            rmax = rmax_of(mode, edges, pimax)
            workx = 4.0 if mode == 'angular' else BoxSize[0]
            use_dist = nproc > 1 and rmax <= workx / nproc

            pos1 = jnp.asarray(first['Position'])
            w1 = catalog_weights(first, weight)
            if second is None or second is first:
                pos2, w2 = pos1, w1
                is_auto = True
            else:
                pos2 = jnp.asarray(second['Position'])
                w2 = catalog_weights(second, weight)
                is_auto = False

            kw = dict(mode=mode, Nmu=Nmu, pimax=pimax, los=los_i,
                      periodic=periodic, is_auto=is_auto)
            if use_dist:
                counts = paircount_dist(pos1, w1, pos2, w2, BoxSize, edges,
                                        self.comm, **kw)
            else:
                counts = paircount(pos1, w1, pos2, w2, BoxSize, edges, **kw)

            W1, W2, total = weight_totals(w1, len(pos1), w2, len(pos2),
                                          is_auto)
            self.attrs['total_wnpairs'] = total
            self.attrs['W1'] = W1
            self.attrs['W2'] = W2
            self.attrs['N1'] = len(pos1)
            self.attrs['N2'] = len(pos2)
            self.attrs['is_auto'] = is_auto

            self.pairs = package_result(counts, **self.attrs)
