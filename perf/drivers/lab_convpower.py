"""Traffic kind ``lab_convpower``: one caller, closed loop, through
``nbodykit_tpu.lab``.

Each call is upstream's ``Algorithm`` phase as written (bccp/nbodykit
``benchmarks/test_convpower.py``): ``ConvolvedFFTPower(mesh,
poles=[0, 2, 4], dk=0.005)`` on an ``FKPCatalogMesh`` built once in
set-up by ``FKPCatalog(data, randoms).to_mesh(Nmesh, resampler='tsc')``,
ending with ``r.poles`` and ``r.attrs`` as host values.  Everything is
a function of the sizes in the configuration and the traffic file, so
the tests rehearse it on the CPU at 32^3.

``correct`` holds the multipoles to the plain reference twice: in
set-up at 64^3 on a survey with an anisotropic signal (where a wrong
sign or ``m`` is of order one), and after the window on the timed
call's own particles at its own mesh, since the timed programs are
keyed on the mesh and faults have shown only at size."""

import time

import numpy as np

from perf.drivers.lab_fftpower import make_catalog
from perf.lib.checks import check, check_shotnoise, lattice_mode_counts
from perf.reference.lab_convpower import (reference_convpower,
                                          round_to_bfloat16)

ATTRS = ('alpha', 'data.norm', 'randoms.norm', 'shotnoise')


def species_of(mesh):
    """What the plain reference takes of the two species, from what
    the system was given: the device's own f4 values, read as f8."""
    def columns(cat):
        return {'pos': np.asarray(cat['Position'], 'f8'),
                'comp': np.asarray(cat['Weight'], 'f8'),
                'nbar': np.asarray(cat['NZ'], 'f8'),
                'fkp': np.ones(cat.size)}
    return columns(mesh.source['data']), columns(mesh.source['randoms'])


class Driver(object):
    #: the library's tracer writes spans for this path (window (b))
    library_spans = True

    def __init__(self, config, traffic, chips, seed):
        check(int(chips) == 1, 'the survey cell is a one-chip cell')
        self.config, self.call_args = config, dict(traffic['call'])
        self.oracle_sizes = dict(traffic['oracle'])
        self.timed_rtol = float(traffic['timed']['rtol'])
        self.seed = int(seed) % (2 ** 32 - 2)   # RandomState's range
        self.mesh = self._reference = None

    def survey_mesh(self, ndata, boxsize, nmesh, wave=0.0, k0=(0, 0, 0)):
        """``FKPCatalog(data, randoms).to_mesh(nmesh, 'tsc')`` on
        ``ndata`` and ``randoms_per_data`` times as many uniform
        particles from the seed, ``NZ`` = ndata / boxsize^3 on both;
        with ``wave``, the data carry the completeness weight
        ``1 + wave cos(k0 . x)``, k0 in units of 2 pi / boxsize."""
        import jax.numpy as jnp
        from nbodykit_tpu.lab import FKPCatalog
        c = self.config
        nbar = ndata / float(boxsize) ** 3
        data = make_catalog(ndata, boxsize, self.seed)
        randoms = make_catalog(c['randoms_per_data'] * ndata, boxsize,
                               self.seed + 1)
        if wave:
            pos = np.asarray(data['Position'], 'f8')
            data['Weight'] = jnp.asarray((1 + wave * np.cos(pos @ (
                2 * np.pi / boxsize * np.asarray(k0, 'f8')))).astype('f4'))
        for cat in (data, randoms):
            cat['NZ'] = jnp.full(cat.size, nbar, 'f4')
        return FKPCatalog(data, randoms).to_mesh(
            Nmesh=int(nmesh), resampler=c['resampler'])

    def convpower(self, mesh, **over):
        from nbodykit_tpu.lab import ConvolvedFFTPower
        args = dict(self.call_args, **over)
        r = ConvolvedFFTPower(mesh, **args)
        poles = r.poles         # host arrays: the call ends synchronised
        out = {name: np.asarray(poles[name]) for name in
               ['k', 'modes'] + ['power_%d' % l for l in args['poles']]}
        out.update({name: float(r.attrs[name]) for name in ATTRS})
        out['BoxSize'] = np.asarray(r.attrs['BoxSize'], 'f8')
        out['edges'] = np.asarray(r.edges, 'f8')
        return out

    def worst(self, got, ref):
        """The largest difference of any multipole, real or imaginary
        part, in units of the largest monopole (P_2 and P_4 cross
        zero)."""
        scale = float(np.abs(ref['power_0']).max())
        return max(float(np.abs(got['power_%d' % l]
                                - ref['power_%d' % l]).max())
                   for l in self.call_args['poles']) / scale

    def oracle(self):
        """The cell's own call at 64^3 on a survey with an anisotropic
        signal against plain numpy on the same particles: P_2 stands as
        high as P_0 there, so a wrong sign, normalisation or m is of
        order one.  Also how far the same estimator lands with its
        fields kept in bfloat16, the format below the cell's: that has
        to miss the limit, or the limit decides nothing."""
        o = self.oracle_sizes
        poles = self.call_args['poles']
        mesh = self.survey_mesh(
            o['ndata'], o['BoxSize'], o['Nmesh'], o['wave'], o['k0'])
        data, randoms = species_of(mesh)
        got = self.convpower(mesh, dk=o['dk'])
        ref = reference_convpower(data, randoms, o['Nmesh'], poles,
                                  o['dk'])
        low = reference_convpower(data, randoms, o['Nmesh'], poles,
                                  o['dk'], quantize=round_to_bfloat16)
        check(np.array_equal(got['modes'], ref['modes']),
              'oracle: mode counts differ from the reference')
        scale = float(np.abs(ref['power_0']).max())
        err, err_low = self.worst(got, ref), self.worst(low, ref)
        rec = {'oracle_nmesh': o['Nmesh'], 'oracle_ndata': o['ndata'],
               'oracle_max_err': err, 'oracle_bfloat16_err': err_low,
               'oracle_p2_over_p0': float(
                   np.abs(ref['power_2']).max()) / scale,
               'oracle_modes': float(ref['modes'].sum())}
        for name in ATTRS:
            rec['oracle_' + name] = got[name] / float(ref[name]) - 1
            check(abs(rec['oracle_' + name]) < 1e-6,
                  'oracle: %s off by %.3g' % (name, rec['oracle_' + name]))
        # f4 mesh and transforms against f8
        check(err < o['rtol'], 'oracle: a multipole off by %.3g of '
              'max |P_0| (limit %.3g)' % (err, o['rtol']))
        check(err_low > o['rtol'], 'oracle: bfloat16 fields pass '
              'the limit (%.3g of max |P_0|)' % err_low)
        return rec

    def setup(self):
        rec = self.oracle()
        c = self.config
        self.mesh = self.survey_mesh(c['N'], c['BoxSize'], c['Nmesh'])
        rec['ndata'] = int(self.mesh.source['data'].size)
        rec['nrandoms'] = int(self.mesh.source['randoms'].size)
        self.call(-1)           # warm the cell's one shape
        return rec

    def call(self, i):
        return self.convpower(self.mesh)

    def verify(self, results):
        """How many of the timed results are wrong, and why."""
        c = self.config
        poles = self.call_args['poles']
        failed, why, rec = 0, [], {}
        for i, r in enumerate(results):
            try:
                if i:
                    check(all(r[name].tobytes()
                              == results[0][name].tobytes()
                              for name in r if name.startswith('power_'))
                          and np.array_equal(r['modes'],
                                             results[0]['modes']),
                          'call %d differs from the first' % i)
                    continue
                rec = self.check_first(r, c, poles)
            except AssertionError as e:
                failed += 1
                why.append(str(e))
        rec['why_failed'] = why[:5]
        return failed, rec

    def reference(self, quantize=None):
        """The plain estimator on the timed call's own particles at
        its own mesh (42-46 s of the host's cores, after the window);
        with ``quantize`` its fields stored in that format, and not
        kept."""
        if quantize is None and self._reference is not None:
            return self._reference
        t0 = time.perf_counter()
        ref = reference_convpower(
            *species_of(self.mesh), self.config['Nmesh'],
            self.call_args['poles'], self.call_args['dk'],
            quantize=quantize)
        ref['seconds'] = time.perf_counter() - t0
        if quantize is None:
            self._reference = ref
        return ref

    def check_reference(self, r):
        """The timed program's multipoles against the reference's,
        shell by shell."""
        ref = self.reference()
        check(np.array_equal(r['modes'], ref['modes']),
              'mode counts differ from the reference')
        err = self.worst(r, ref)
        check(err < self.timed_rtol, 'a multipole off the reference by '
              '%.3g of max |P_0| (limit %.3g)' % (err, self.timed_rtol))
        for name in ATTRS:
            check(abs(r[name] / float(ref[name]) - 1) < 1e-6,
                  '%s %r, the reference %r' % (name, r[name], ref[name]))
        return {'reference_max_err': err,
                'reference_s': ref['seconds']}

    def check_first(self, r, c, poles):
        """One result against what needs no reference: the lattice,
        the catalog's own sums and a flat shot noise; then against the
        reference."""
        box = r['BoxSize']
        check(np.all(box == box[0]), 'the box is not a cube: %r' % box)
        # the system bins |i|^2 in integers against these thresholds
        # (a cube, no x64): so does the count, no edge tolerance
        unit = 2 * np.pi / box[0]
        q = np.ceil((r['edges'] / unit) ** 2).astype('i8')
        check(np.array_equal(r['modes'],
                             lattice_mode_counts(c['Nmesh'], q)),
              'k mode counts differ from the lattice count')
        modes = r['modes'].astype('f8')
        for l in poles:
            check(np.isfinite(r['power_%d' % l][modes > 0]).all(),
                  'NaN/Inf in P_%d where there are modes' % l)
        alpha = 1.0 / c['randoms_per_data']
        check(abs(r['alpha'] / alpha - 1) < 1e-6,
              'alpha %r, not %r' % (r['alpha'], alpha))
        check(abs(r['data.norm'] / r['randoms.norm'] - 1) < 1e-6,
              'data.norm %r, randoms.norm %r'
              % (r['data.norm'], r['randoms.norm']))
        shot = r['shotnoise']
        want = (1 + alpha) * float(c['BoxSize']) ** 3 / c['N']
        check(abs(shot / want - 1) < 1e-5,
              'shotnoise attr %r, (1 + alpha) V / N %r' % (shot, want))
        mean, worst = check_shotnoise(r['power_0'].real, modes, shot,
                                      c['min_modes'], 'convpower P_0')
        rec = {'p0_over_shot_mean': mean, 'p0_over_shot_worst': worst,
               'modes': float(modes.sum())}
        # a multipole of pure shot noise: zero, with the monopole's
        # variance times 2 ell + 1 (the addition theorem's factor)
        well = modes >= c['min_modes']
        for l in poles:
            if l == 0:
                continue
            p = r['power_%d' % l].real[well] / shot
            sigma = np.sqrt(2 * (2 * l + 1) / modes[well])
            off = float(np.max(np.abs(p) / (0.01 + 5 * sigma)))
            mean = float(np.sum(p * modes[well]) / modes[well].sum())
            rec['p%d_over_limit_worst' % l] = off
            rec['p%d_over_shot_mean' % l] = mean
            check(off < 1, 'convpower P_%d: off zero by %.3g of its '
                  'limit (1%% + 5 sigma of the shot noise)' % (l, off))
            check(abs(mean) < 0.01 + 5 * np.sqrt(
                2 * (2 * l + 1) / modes[well].sum()),
                'convpower P_%d: mode-weighted mean %.4g of the shot '
                'noise' % (l, mean))
        rec.update(self.check_reference(r))
        return rec

    def close(self):
        self.mesh = self._reference = None
