"""Traffic kind ``lab_paircount``: one caller, closed loop, through
``nbodykit_tpu.lab``.

Each call is ``SimulationBoxPairCount('1d', cat, edges, BoxSize=...,
periodic=True)`` on a catalog of uniform points built once in set-up
(on the device, from ``--seed``), ending with ``pairs['npairs']`` and
``pairs['wnpairs']`` as host arrays: the DD (= RR) count of a
two-point correlation function, the count the field's codes are
compared on (Corrfunc's ``theory`` DD benchmark).  Everything is a
function of the sizes in the configuration and the traffic file, so
the tests rehearse it on the CPU at 5e3 points.

``correct`` holds the counts to plain numpy twice: in set-up on a
clumped catalog of 2e4 points against every pair counted in f8, and
after the window on the timed call's own points against a kd-tree's
count.  A count is held by a bracket (``perf/reference/
lab_paircount.py:bracket``): the result's separations are f4
arithmetic on f4 coordinates, good to ``delta`` of r, so for every
edge its cumulative count since the first edge must lie between the
reference's at ``e (1 - delta)`` and at ``e (1 + delta)``.  The first
check of all is that ``npairs`` is integer-typed: a float count cannot
hold this deployment's bins (8.8e8 pairs in the widest), and a program
that returns one fails here within seconds."""

import time

import numpy as np

from perf.drivers.lab_fftpower import make_catalog
from perf.lib.checks import check
from perf.reference.lab_paircount import (bracket, bracket_radii,
                                          brute_cumulative,
                                          round_to_bfloat16, shell_means,
                                          tree_cumulative)


def clumped_points(n, boxsize, clumps, sigma, seed):
    """Half of ``n`` points uniform in the box, half in ``clumps``
    Gaussian clumps of width ``sigma`` (wrapped), as f4: pairs at every
    separation, cells of every filling, clumps across the faces."""
    rng = np.random.RandomState(seed)
    uniform = rng.uniform(0, boxsize, (n // 2, 3))
    centres = rng.uniform(0, boxsize, (clumps, 3))
    member = rng.randint(0, clumps, n - n // 2)
    clumped = centres[member] + sigma * rng.standard_normal(
        (n - n // 2, 3))
    return (np.concatenate([uniform, clumped]) % boxsize).astype('f4')


def outside(got, lo, hi):
    """How far a cumulative count lies outside its bracket, in pairs
    (0 inside), the worst edge."""
    return int(np.max(np.maximum(lo - got, 0) + np.maximum(got - hi, 0)))


class Driver(object):
    #: the library's tracer writes spans for this path (window (b))
    library_spans = True

    def __init__(self, config, traffic, chips, seed):
        check(int(chips) == 1, 'the pair-counting cell is a one-chip cell')
        self.config, self.call_args = config, dict(traffic['call'])
        self.oracle_sizes = dict(traffic['oracle'])
        self.timed = dict(traffic['timed'])
        self.seed = int(seed) % (2 ** 32 - 1)   # RandomState's range
        self.cat = self._reference = None

    def count(self, cat, edges, boxsize):
        from nbodykit_tpu.lab import SimulationBoxPairCount
        r = SimulationBoxPairCount(self.call_args['mode'], cat, edges,
                                   BoxSize=boxsize, periodic=True)
        # host arrays: the call ends synchronised
        return {'npairs': np.asarray(r.pairs['npairs']),
                'wnpairs': np.asarray(r.pairs['wnpairs'])}

    def oracle(self):
        """The cell's own call on clumped points against every pair
        counted in f8; the same count with its coordinate differences
        in bfloat16, the format below the cell's, has to miss the
        bracket, or the bracket decides nothing."""
        from nbodykit_tpu.lab import ArrayCatalog
        o = self.oracle_sizes
        edges = np.logspace(np.log10(o['rmin']), np.log10(o['rmax']),
                            o['nbins'] + 1)
        pos = clumped_points(o['N'], o['BoxSize'], o['clumps'],
                             o['clump_sigma'], self.seed)

        def catalog(p):
            return ArrayCatalog({'Position': p}, BoxSize=o['BoxSize'])

        small = self.count(catalog(pos[:o['typed_N']]), edges,
                           o['BoxSize'])['npairs']
        check(np.issubdtype(small.dtype, np.integer),
              'oracle: npairs is %s, not an integer type: a float '
              'cannot hold this deployment\'s counts' % small.dtype)
        got = self.count(catalog(pos), edges, o['BoxSize'])
        check(np.issubdtype(got['npairs'].dtype, np.integer),
              'oracle: npairs is %s' % got['npairs'].dtype)
        cum = np.concatenate([[0], np.cumsum(got['npairs'].astype('i8'))])
        t0 = time.perf_counter()
        radii = np.concatenate([bracket_radii(edges, o['delta']), edges])
        exact, low = brute_cumulative(pos, o['BoxSize'], radii,
                                      quantize=round_to_bfloat16)
        lo, hi = bracket(exact[:2 * len(edges)])
        off = outside(cum, lo, hi)
        low = low[2 * len(edges):]
        off_low = outside(low - low[0], lo, hi)
        rec = {'oracle_n': o['N'], 'oracle_pairs': int(cum[-1]),
               'oracle_bracket_pairs': int((hi - lo).max()),
               'oracle_outside': off, 'oracle_bfloat16_outside': off_low,
               'oracle_reference_s': time.perf_counter() - t0}
        check(off == 0, 'oracle: a cumulative count lies %d pairs outside '
              'the reference\'s bracket (delta %g)' % (off, o['delta']))
        check(off_low > 0, 'oracle: bfloat16 separations pass the bracket')
        check(np.allclose(got['wnpairs'], got['npairs'], rtol=1e-6,
                          atol=0),
              'oracle: wnpairs differs from npairs with unit weights')
        return rec

    def setup(self):
        rec = self.oracle()
        c = self.config
        self.cat = make_catalog(c['N'], c['BoxSize'], self.seed)
        rec['npoints'] = int(self.cat.size)
        self.call(-1)           # warm the cell's one shape
        return rec

    def call(self, i):
        c = self.config
        return self.count(self.cat, np.asarray(c['edges'], 'f8'),
                          c['BoxSize'])

    def reference(self):
        """The bracket of the plain count on the timed call's own
        points (about a minute of one core, after the window)."""
        if self._reference is None:
            c = self.config
            pos = np.asarray(self.cat['Position'], 'f8')
            t0 = time.perf_counter()
            lo, hi = bracket(tree_cumulative(
                pos, c['BoxSize'],
                bracket_radii(c['edges'], self.timed['delta'])))
            self._reference = lo, hi, time.perf_counter() - t0
        return self._reference

    def check_first(self, r):
        """One result against what needs no reference (integer and
        even counts, unit weights, the analytic shells), then against
        the reference."""
        c = self.config
        n = r['npairs']
        check(np.issubdtype(n.dtype, np.integer),
              'npairs is %s, not an integer type' % n.dtype)
        check(n.shape == (c['nbins'],), 'npairs has shape %r' % (n.shape,))
        check(np.all(n % 2 == 0), 'an odd count: every pair counts twice')
        check(np.allclose(r['wnpairs'], n, rtol=1e-6, atol=0),
              'wnpairs differs from npairs with unit weights')
        mean = shell_means(c['N'], c['BoxSize'], c['edges'])
        pull = (n - mean) / np.sqrt(2 * mean)
        worst = float(np.abs(pull).max())
        check(worst < self.timed['nsigma'], 'a bin %.3g sigma off '
              'N (N - 1) V_shell / V' % worst)
        lo, hi, seconds = self.reference()
        cum = np.concatenate([[0], np.cumsum(n.astype('i8'))])
        off = outside(cum, lo, hi)
        check(off == 0, 'a cumulative count lies %d pairs outside the '
              'reference\'s bracket (delta %g)' % (off, self.timed['delta']))
        return {'pairs': int(cum[-1]), 'shell_pull_worst': worst,
                'reference_outside': off,
                'reference_bracket_pairs': int((hi - lo).max()),
                'reference_s': seconds}

    def verify(self, results):
        """How many of the timed results are wrong, and why."""
        failed, why, rec = 0, [], {}
        for i, r in enumerate(results):
            try:
                if i:
                    check(all(r[k].dtype == results[0][k].dtype
                              and r[k].tobytes() == results[0][k].tobytes()
                              for k in ('npairs', 'wnpairs')),
                          'call %d differs from the first' % i)
                    continue
                rec = self.check_first(r)
            except AssertionError as e:
                failed += 1
                why.append(str(e))
        rec['why_failed'] = why[:5]
        return failed, rec

    def close(self):
        self.cat = self._reference = None
