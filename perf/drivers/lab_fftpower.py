"""Traffic kind ``lab_fftpower``: one caller, closed loop, through
``nbodykit_tpu.lab``.

Each call is upstream's ``Algorithm`` phase as written
(bccp/nbodykit ``benchmarks/test_fftpower.py``):
``FFTPower(cat, mode='2d', Nmesh=N, kmin=0.001, Nmu=10)`` on a catalog
built once in set-up, ending with ``r.power`` as host arrays.  The
call path is ``chip_smoke.py:run_fftpower``'s (PR 22) with the catalog
draw moved out of the wall.  Everything is a function of the sizes in
the configuration, so the tests rehearse it on the CPU at 32^3."""

import contextlib

import numpy as np

from perf.lib.checks import check, check_shotnoise, lattice_mode_counts
from perf.reference.lab_fftpower import (reference_fftpower,
                                         shell_thresholds)


def make_catalog(npart, boxsize, seed):
    """``npart`` uniform particles in the box, drawn on the device
    under the ambient mesh: ``UniformCatalog``'s own draw with the
    count fixed.  ``UniformCatalog`` takes its count from
    Poisson(nbar V) of the seed, which would give every seed other
    shapes and so its own compiles; upstream's sample is defined by N
    (``benchmarks/conftest.py``: nbar = N / BoxSize^3)."""
    import jax
    from nbodykit_tpu.lab import RandomCatalog
    from nbodykit_tpu.utils import working_dtype
    cat = RandomCatalog(int(npart), seed=int(seed))
    box = np.full(3, float(boxsize))
    cat.attrs['BoxSize'] = box
    cat.attrs['nbar'] = int(npart) / box.prod()
    wdt = working_dtype('f8')
    cat['Position'] = (cat.rng.uniform(itemshape=(3,), dtype=wdt)
                       * box).astype(wdt)
    jax.block_until_ready(cat['Position'])
    return cat


class Driver(object):
    #: the library's tracer writes spans for this path (window (b))
    library_spans = True

    def __init__(self, config, traffic, chips, seed, mesh=None):
        self.config, self.call_args = config, dict(traffic['call'])
        self.oracle_sizes = dict(traffic['oracle'])
        self.seed = int(seed) % (2 ** 32 - 1)   # RandomState's range
        self.chips = int(chips)
        self._mesh = mesh
        self.cat = None

    def ambient(self):
        """One chip: no ambient mesh (device 0, what a user gets).
        More: the calls sit inside ``use_mesh(tpu_mesh())``."""
        from nbodykit_tpu.lab import tpu_mesh, use_mesh
        if self._mesh is None and self.chips == 1:
            return contextlib.nullcontext()
        return use_mesh(self._mesh if self._mesh is not None
                        else tpu_mesh())

    def fftpower(self, cat, nmesh, **over):
        from nbodykit_tpu.lab import FFTPower
        args = dict(self.call_args, **over)
        r = FFTPower(cat, Nmesh=int(nmesh), **args)
        power = r.power         # host arrays: the call ends synchronised
        return {'power': np.asarray(power['power']),
                'modes': np.asarray(power['modes']),
                'shotnoise': float(r.attrs['shotnoise']),
                'npart': int(r.attrs['N1'])}

    def oracle(self):
        """The cell's own call at 64^3 under the cell's own mesh against
        plain numpy on the same particles.  Nmu = 4 there: no lattice
        mode lies on an interior mu edge (mu = 1/2 would need kx^2 +
        ky^2 = 3 kz^2, which has no integer solution; mu = 0 is the
        exact kz = 0 plane), so the (k, mu) mode counts compare
        exactly."""
        o = self.oracle_sizes
        with self.ambient():
            cat = make_catalog(o['npart'], o['BoxSize'], self.seed)
            got = self.fftpower(cat, o['Nmesh'], Nmu=o['Nmu'])
            pos = np.asarray(cat['Position'])
        ref = reference_fftpower(pos, o['BoxSize'], o['Nmesh'], o['Nmu'],
                                 kmin=self.call_args.get('kmin', 0.0))
        check(np.array_equal(got['modes'], ref['modes']),
              'oracle: (k, mu) mode counts differ from the reference')
        ok = (ref['modes'] > 0) & (ref['power'] > 0)
        err = float(np.max(np.abs(
            got['power'].real[ok] / ref['power'][ok] - 1)))
        # f4 mesh, f4 FFT: the repo's f32 target is 1e-4 on
        # well-populated bins (tests/test_f32_accuracy.py); single-mode
        # bins see the raw f4 FFT error, so the bound is a few times
        # that.  A paint or a transform in bf16 would miss it by 10x.
        check(err < o['rtol'], 'oracle: P(k, mu) off by %.3g' % err)
        return {'oracle_nmesh': o['Nmesh'], 'oracle_npart': o['npart'],
                'oracle_max_rel_err': err,
                'oracle_modes': float(ref['modes'].sum())}

    def setup(self):
        rec = self.oracle()
        c = self.config
        with self.ambient():
            self.cat = make_catalog(c['N'], c['BoxSize'], self.seed)
        rec['npart'] = int(self.cat.size)
        self.call(-1)           # warm the cell's one shape
        return rec

    def call(self, i):
        with self.ambient():
            return self.fftpower(self.cat, self.config['Nmesh'])

    def verify(self, results):
        """How many of the timed results are wrong, and why."""
        c = self.config
        q = shell_thresholds(c['Nmesh'], c['BoxSize'],
                             self.call_args.get('kmin', 0.0))
        want = lattice_mode_counts(c['Nmesh'], q)
        shot = float(c['BoxSize']) ** 3 / int(self.cat.size)
        failed, why, rec = 0, [], {}
        for i, r in enumerate(results):
            try:
                if i:
                    check(r['power'].tobytes()
                          == results[0]['power'].tobytes()
                          and np.array_equal(r['modes'],
                                             results[0]['modes']),
                          'call %d differs from the first' % i)
                    continue
                modes = r['modes'].sum(axis=1)
                check(np.array_equal(modes, want),
                      'k mode counts differ from the lattice count')
                pw = r['power'].real
                check(np.isfinite(pw[r['modes'] > 0]).all(),
                      'NaN/Inf in P(k, mu) where there are modes')
                check(abs(r['shotnoise'] / shot - 1) < 1e-6,
                      'shotnoise attr %r, V/N %r' % (r['shotnoise'], shot))
                p0 = (np.where(r['modes'] > 0, pw, 0.0) * r['modes']
                      ).sum(axis=1) / np.maximum(modes, 1)
                mean, worst = check_shotnoise(
                    p0, modes, shot, c['min_modes'], 'fftpower')
                rec = {'p0_over_shot_mean': mean,
                       'p0_over_shot_worst': worst,
                       'modes': float(modes.sum())}
            except AssertionError as e:
                failed += 1
                why.append(str(e))
        rec['why_failed'] = why[:5]
        return failed, rec

    def close(self):
        self.cat = None
