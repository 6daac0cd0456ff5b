"""Traffic kind ``served_closed_loop``: one client of one
``AnalysisServer``, closed loop.

Each call is one ``AnalysisRequest`` timed from ``submit`` to ``wait``
returning, a new seed each request — a covariance run over mock
realisations.  The call path is ``chip_smoke.py:phase_serve``'s
(PR 22).  The serve plane fixes ``BOX_SIZE`` = 1000 and a 1-d P(k);
a request carries no BoxSize."""

import contextlib

import numpy as np

from perf.lib.checks import (bytes_limit, check, check_shotnoise,
                             counter_value, lattice_mode_counts)
from perf.reference.served_closed_loop import (reference_served,
                                               served_thresholds)

SUMMARY_ZERO = ('lost', 'retried', 'fault_degraded', 'admit_degraded')


class Driver(object):
    #: one fused program: the library's tracer has no span inside it
    library_spans = False

    def __init__(self, config, traffic, chips, seed, mesh=None):
        self.config, self.traffic = config, traffic
        self.oracle_sizes = dict(traffic['oracle'])
        # request seeds stay in int32 however large --seed is: the
        # server turns them into uint32 and the oracle into a key
        self.base = int(seed) % (2 ** 31 - 2 ** 24)
        self._mesh = mesh
        self._stack = contextlib.ExitStack()
        self.server = None
        self.label = None
        self.misses_warm = None
        self.submitted = 0

    def request(self, nmesh, npart, seed):
        from nbodykit_tpu.serve import AnalysisRequest
        return AnalysisRequest(
            algorithm=self.traffic['algorithm'], nmesh=int(nmesh),
            npart=int(npart), seed=int(seed),
            deadline_s=float(self.traffic['deadline_s']))

    def serve(self, req):
        """Submit, wait, and return what the client gets."""
        ticket = self.server.submit(req)
        res = self.server.wait(ticket,
                               timeout=float(self.traffic['deadline_s']))
        self.submitted += 1
        check(res is not None and res.status == 'completed',
              'serve: request ended %r' % (res and res.to_dict(),))
        return {'seed': int(req.seed), 'y': np.asarray(res.y),
                'nmodes': np.asarray(res.nmodes)}

    def oracle(self):
        """One served request at 64^3 against the numpy twin of the
        served estimator, on the positions the program draws
        (``serve/scheduler.py:_uniform_pos``), re-drawn here."""
        import jax
        import jax.numpy as jnp
        from nbodykit_tpu.serve.scheduler import BOX_SIZE
        o = self.oracle_sizes
        check(float(BOX_SIZE) == float(self.config['served_BoxSize']),
              'the serve plane\'s BOX_SIZE is %r, the configuration '
              'says %r' % (BOX_SIZE, self.config['served_BoxSize']))
        got = self.serve(self.request(o['Nmesh'], o['npart'], self.base))
        pos = np.asarray(jax.random.uniform(
            jax.random.key(self.base), (int(o['npart']), 3), jnp.float32,
            0.0, BOX_SIZE))
        power, modes = reference_served(pos, BOX_SIZE, o['Nmesh'])
        check(np.array_equal(got['nmodes'], modes.astype('f4')),
              'oracle: served mode counts differ from the reference')
        ok = modes > 0
        err = float(np.max(np.abs(got['y'][ok] / power[ok] - 1)))
        # one f4 program end to end; the bound and its reason are the
        # lab oracle's
        check(err < o['rtol'], 'oracle: served P(k) off by %.3g' % err)
        return {'oracle_nmesh': o['Nmesh'], 'oracle_npart': o['npart'],
                'oracle_max_rel_err': err}

    def setup(self):
        from nbodykit_tpu.lab import use_mesh
        from nbodykit_tpu.serve import AnalysisServer
        from nbodykit_tpu.serve.scheduler import program_label
        if self._mesh is not None:
            self._stack.enter_context(use_mesh(self._mesh))
        hbm = self.traffic.get('hbm_bytes') or bytes_limit()
        self.server = self._stack.enter_context(AnalysisServer(
            per_task=int(self.traffic['per_task']), hbm_bytes=hbm))
        rec = self.oracle()
        c = self.config
        req = self.request(c['Nmesh'], c['N'], self.base)
        self.label = program_label(req)
        a = self.serve(req)                     # warms the cell's shape
        b = self.serve(self.request(c['Nmesh'], c['N'], self.base))
        check(a['y'].tobytes() == b['y'].tobytes(),
              'serve: the same seed twice gave two spectra')
        self.misses_warm = counter_value(
            'compile.%s.misses' % self.label)
        rec['hbm_bytes'] = hbm
        return rec

    def call(self, i):
        c = self.config
        return self.serve(self.request(c['Nmesh'], c['N'],
                                       self.base + 1 + max(i, 0)))

    def verify(self, results):
        from nbodykit_tpu.serve.scheduler import BOX_SIZE
        c = self.config
        nb = int(c['Nmesh']) // 2
        want = lattice_mode_counts(c['Nmesh'],
                                   served_thresholds(c['Nmesh']))
        want[0] -= 1.0                          # the DC mode
        shot = float(BOX_SIZE) ** 3 / int(c['N'])
        failed, why, rec = 0, [], {}
        seen = {}
        for r in results:
            try:
                check(r['y'].shape == (nb,) and np.isfinite(r['y']).all(),
                      'spectrum not finite of shape (%d,)' % nb)
                check(np.array_equal(r['nmodes'], want.astype('f4')),
                      'shell mode counts differ from the lattice count')
                other = seen.setdefault(r['y'].tobytes(), r['seed'])
                check(other == r['seed'], 'seeds %d and %d gave the '
                      'same spectrum' % (other, r['seed']))
                mean, worst = check_shotnoise(
                    r['y'], want, shot, min(c['min_modes'], want.max() / 4),
                    'serve')
                rec = {'p_over_shot_mean': mean,
                       'p_over_shot_worst': worst}
            except AssertionError as e:
                failed += 1
                why.append(str(e))
        summary = self.server.summary()
        misses = counter_value('compile.%s.misses' % self.label)
        try:
            for key in SUMMARY_ZERO:
                check(summary[key] == 0,
                      'serve: %s = %r' % (key, summary[key]))
            check(summary['completed'] == self.submitted,
                  'serve: completed %r of %d' % (summary['completed'],
                                                 self.submitted))
            check(misses == self.misses_warm,
                  'serve: compile misses went from %r to %r over the '
                  'window' % (self.misses_warm, misses))
        except AssertionError as e:
            failed = max(failed, 1)
            why.append(str(e))
        rec.update(compile_misses=misses, why_failed=why[:5],
                   summary={k: summary[k] for k in
                            SUMMARY_ZERO + ('submitted', 'completed')})
        return failed, rec

    def close(self):
        self._stack.close()
