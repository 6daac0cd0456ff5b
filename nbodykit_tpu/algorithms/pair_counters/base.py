"""Shared machinery for the pair-count algorithms.

Reference: ``nbodykit/algorithms/pair_counters/base.py:5`` — result
packaging into BinnedStatistic + persistence.
"""

import json

import numpy as np

from ...binned_statistic import BinnedStatistic
from ...utils import JSONEncoder, JSONDecoder


def package_result(counts, **attrs):
    """Wrap a core.paircount result dict into a BinnedStatistic with
    the reference's dims/variables conventions (mode/edges/Nmu/pimax
    come from the attrs)."""
    mode = attrs['mode']
    edges = np.asarray(attrs['edges'])
    Nmu = attrs.get('Nmu')
    pimax = attrs.get('pimax')
    npairs = np.atleast_1d(counts['npairs'])
    wnpairs = np.atleast_1d(counts['wnpairs'])

    if mode == '1d':
        dims, bin_edges = ['r'], [edges]
    elif mode == '2d':
        dims = ['r', 'mu']
        bin_edges = [edges, np.linspace(0, 1, Nmu + 1)]
    elif mode == 'projected':
        dims = ['rp', 'pi']
        bin_edges = [edges, np.arange(0, int(pimax) + 1)]
    elif mode == 'angular':
        dims, bin_edges = ['theta'], [edges]
    else:
        raise ValueError(mode)

    shape = tuple(len(e) - 1 for e in bin_edges)
    npairs = npairs.reshape(shape)
    wnpairs = wnpairs.reshape(shape)
    data = {'npairs': npairs, 'wnpairs': wnpairs}
    out = BinnedStatistic(dims, bin_edges, data,
                          fields_to_sum=['npairs', 'wnpairs'])
    out.attrs.update(attrs)  # ('edges' collides with the positional)
    return out


def catalog_weights(cat, weight):
    """The catalog's ``weight`` column as a device array, or None
    where it is the default unit ``Weight`` that nobody set (or no
    column at all): the kernel then sums no weights, and ``wnpairs``
    is ``npairs``."""
    import jax.numpy as jnp
    from ...base.catalog import CatalogSource, find_columns
    if weight not in cat:
        return None
    if weight not in getattr(cat, '_columns', {weight: None}) and \
            find_columns(type(cat)).get(weight) is CatalogSource.Weight:
        return None
    return jnp.asarray(cat[weight])


def weight_totals(w1, n1, w2, n2, is_auto):
    """``(W1, W2, total)``: the summed weights of the two catalogs
    (their sizes where a catalog has no weights) and the total weighted
    pair count the estimators normalize by, self-pairs taken out of an
    autocorrelation."""
    from ...diagnostics import fetch

    def on_host(w):
        return None if w is None else np.asarray(
            fetch(w, 'paircount.weights'), 'f8')

    same = w2 is w1
    w1 = on_host(w1)
    w2 = w1 if same else on_host(w2)
    W1 = float(n1) if w1 is None else float(np.sum(w1))
    W2 = float(n2) if w2 is None else float(np.sum(w2))
    if not is_auto:
        return W1, W2, W1 * W2
    sumw2 = float(n1) if w1 is None else float(np.sum(w1 ** 2))
    return W1, W2, W1 * W1 - sumw2


class PairCountBase(object):
    """Base for SimulationBoxPairCount / SurveyDataPairCount; holds
    .pairs and JSON persistence (reference base.py:5)."""

    def save(self, output):
        with open(output, 'w') as ff:
            json.dump(self.__getstate__(), ff, cls=JSONEncoder)

    @classmethod
    def load(cls, output, comm=None):
        with open(output, 'r') as ff:
            state = json.load(ff, cls=JSONDecoder)
        self = object.__new__(cls)
        self.__setstate__(state)
        return self

    def __getstate__(self):
        return dict(pairs=self.pairs.__getstate__(), attrs=self.attrs)

    def __setstate__(self, state):
        self.attrs = state['attrs']
        self.pairs = BinnedStatistic.from_state(state['pairs'])
