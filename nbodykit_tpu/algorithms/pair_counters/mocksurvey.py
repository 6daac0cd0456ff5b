"""SurveyDataPairCount: pair counts of sky catalogs.

Reference: ``nbodykit/algorithms/pair_counters/mocksurvey.py`` (wrapping
Corrfunc mocks kernels DDsmu_mocks/DDtheta_mocks): positions come as
(ra, dec[, redshift]) converted to Cartesian with a cosmology; counting
is non-periodic in a data-derived bounding box.
"""

import numpy as np

from .base import (PairCountBase, catalog_weights, package_result,
                   weight_totals)
from .core import paircount, paircount_dist, rmax_of
from ...parallel.runtime import mesh_size
from ... import transform


class SurveyDataPairCount(PairCountBase):
    """Count weighted pairs of survey (sky) data.

    Parameters (reference mocksurvey.py): mode in {'1d','2d','angular',
    'projected'}, catalogs with ra/dec(/redshift) columns, edges,
    cosmo (for comoving distances), Nmu, pimax, weight.
    """

    def __init__(self, mode, first, edges, cosmo=None, second=None,
                 Nmu=None, pimax=None, ra='RA', dec='DEC',
                 redshift='Redshift', weight='Weight',
                 show_progress=False):
        if mode not in ('1d', '2d', 'projected', 'angular'):
            raise ValueError("invalid mode %r" % mode)
        if mode == '2d' and Nmu is None:
            raise ValueError("mode='2d' requires Nmu")
        if mode == 'projected' and pimax is None:
            raise ValueError("mode='projected' requires pimax")
        self.comm = first.comm
        self.attrs = dict(mode=mode, edges=np.asarray(edges), Nmu=Nmu,
                          pimax=pimax, weight=weight)

        import jax.numpy as jnp
        nproc = mesh_size(self.comm)
        rmax = rmax_of(mode, edges, pimax)

        def get_pos(cat):
            if mode == 'angular':
                pos = transform.SkyToUnitSphere(cat[ra], cat[dec])
            else:
                if cosmo is None:
                    raise ValueError("need a cosmology to convert "
                                     "redshifts to distances")
                pos = transform.SkyToCartesian(cat[ra], cat[dec],
                                               cat[redshift], cosmo)
            return jnp.asarray(pos)

        pos1 = get_pos(first)
        w1 = catalog_weights(first, weight)
        if second is None or second is first:
            pos2, w2 = pos1, w1
            is_auto = True
        else:
            pos2 = get_pos(second)
            w2 = catalog_weights(second, weight)
            is_auto = False

        if mode == 'angular':
            box = np.ones(3)  # unused by the angular path
            kw = dict(mode=mode, periodic=False, is_auto=is_auto)
            use_dist = nproc > 1 and rmax <= 4.0 / nproc
        else:
            # non-periodic bounding box; mu against the pair midpoint
            # direction from the observer (Corrfunc-mocks convention)
            lo = np.minimum(np.asarray(pos1.min(axis=0)),
                            np.asarray(pos2.min(axis=0)))
            hi = np.maximum(np.asarray(pos1.max(axis=0)),
                            np.asarray(pos2.max(axis=0)))
            box = (hi - lo) * 1.001 + 1e-3
            kw = dict(mode=mode, Nmu=Nmu, pimax=pimax, periodic=False,
                      is_auto=is_auto, grid_origin=lo,
                      pair_los='midpoint')
            use_dist = nproc > 1 and rmax <= box[0] / nproc

        if use_dist:
            counts = paircount_dist(pos1, w1, pos2, w2, box, edges,
                                    self.comm, **kw)
        else:
            counts = paircount(pos1, w1, pos2, w2, box, edges, **kw)

        W1, W2, total = weight_totals(w1, len(pos1), w2, len(pos2),
                                      is_auto)
        self.attrs.update(total_wnpairs=total, W1=W1, W2=W2,
                          N1=len(pos1), N2=len(pos2), is_auto=is_auto)

        self.pairs = package_result(counts, **self.attrs)
