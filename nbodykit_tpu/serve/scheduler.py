"""Placement + the warm program cache.

Two jobs:

**Programs stay warm.**  Each (program key, worker) pair builds its
compiled analysis program exactly once, wrapped in
:func:`~nbodykit_tpu.diagnostics.instrumented_jit` under a label keyed
by shape class (``serve.fftpower.mesh64-part1e5``), so the
``compile.<label>.misses`` / ``.hits`` counters are the PROOF that the
second identical-shape request compiles nothing.

**Placement is cache-affine.**  A compiled XLA executable is bound to
the devices it was built for, so the scheduler routes a request to the
sub-mesh worker that already holds its warm program: affinity =
``hash(program_key) % n_workers``.  An idle worker may still steal the
globally best-ranked ticket (paying one compile to warm its own copy)
rather than sit out a backlog — classic cache-aware scheduling with
work stealing.  Ranking within a worker's view is priority (desc),
deadline (asc), submission order (asc).

The device programs themselves live here too: self-contained
(seed -> spectrum) pipelines — uniform realization, paint, r2c,
window compensation, integer-lattice shell binning — one per
algorithm, modeled on bench.py's fused pipeline.  On a 1-device
sub-mesh the program is plain jax ops (no shard_map), which is what
makes it vmap-batchable (:mod:`.batching`); on a multi-device
sub-mesh the same builder produces the shard_map form.
"""

import threading

from ..diagnostics import counter, fetch, instrumented_jit, scope
from ..parallel.runtime import mesh_size

BOX_SIZE = 1000.0


def program_label(request):
    """The instrumented-jit label for a request's program: keyed by
    algorithm + shape class, NOT by exact shape — the granularity the
    compile miss/hit counters aggregate at."""
    return 'serve.%s.%s' % (request.algorithm.lower(),
                            request.shape_class)


# ---------------------------------------------------------------------------
# device programs

def _binned_power(pm, c, resampler, npart):
    """Window-compensated, hermitian-weighted |delta_k|^2 binned onto
    integer-lattice k shells (exact shell assignment via the shared
    :func:`~nbodykit_tpu.ops.histogram.lattice_shell_index`, the sums
    as one-hot matrix products,
    :func:`~nbodykit_tpu.ops.histogram.shell_sums`).  Returns
    (k, P(k), nmodes) with nmesh//2 shells."""
    import jax.numpy as jnp
    import numpy as np
    from ..ops.histogram import lattice_shell_index, shell_sums
    from ..ops.window import compensation_transfer

    nmesh = int(pm.Nmesh[0])
    L = float(pm.BoxSize[0])
    nbins = nmesh // 2
    V = L ** 3

    with scope('fftpower.transfer'):
        w = pm.k_list(dtype=jnp.float32, circular=True)
        c = compensation_transfer(resampler, False)(w, c)
        p3 = (jnp.abs(c) ** 2).astype(jnp.float32) * V
        p3 = p3.at[0, 0, 0].set(0.0)

    with scope('fftpower.binning'):
        with scope('fftpower.binning.digitize'):
            ix, iy, iz = pm.i_list_complex()
            shell = lattice_shell_index(ix * ix + iy * iy + iz * iz,
                                        nbins)
        with scope('fftpower.binning.hist'):
            P, Nm = shell_sums(shell, p3, nbins,
                               weight=pm.hermitian_weights(jnp.float32))
    Nm0 = Nm.at[0].set(jnp.maximum(Nm[0] - 1.0, 0.0))  # drop DC mode
    k = jnp.asarray(np.arange(nbins, dtype='f4')) \
        * jnp.float32(2 * np.pi / L)
    return k, P / jnp.maximum(Nm, 1.0), Nm0


def _delta_c(pm, pos, resampler, npart):
    """Painted overdensity in k space (forward-normalized r2c of
    paint/nbar)."""
    field, _ = pm.paint(pos, 1.0, resampler=resampler,
                        return_dropped=True)
    return pm.r2c(field / (float(npart) / pm.Ntot))


def _uniform_pos(seed, npart, L):
    import jax
    import jax.numpy as jnp
    return jax.random.uniform(jax.random.key(seed), (npart, 3),
                              jnp.float32, 0.0, L)


def _rooted(fn):
    """``fn`` under the served program's root scope, so that what a
    program does outside the library's layers (the realization, the
    ``/ nbar``) is named in the device trace too.

    The wrapper's name is the compiled module's (``jit_program``), and
    on purpose not ``fn``'s: jax's persistent cache keys a program by
    its name and computation, not by its metadata, so under the old
    name an executable cached before the scopes existed would be
    loaded again, with no scope in it."""
    def program(arg):
        with scope('serve.program'):
            return fn(arg)
    return program


def _build_data(request, pm):
    """The (painted field -> (k, P, nmodes)) stage of a ``data_ref``
    program.  The paint itself is NOT in here: streaming ingestion is
    eager by construction (chunks arrive over time), so the jitted
    boundary starts at the finished field — one warm executable per
    shape serves every survey."""
    npart = request.npart
    resampler = request.resampler

    def from_field(field):
        c = pm.r2c(field / (float(npart) / pm.Ntot))
        return _binned_power(pm, c, resampler, npart)
    return _rooted(from_field)


def _build_single(request, pm):
    """The single-realization (seed -> (x, y, nmodes)) function for
    one algorithm on one ParticleMesh."""
    import jax.numpy as jnp
    npart = request.npart
    resampler = request.resampler
    L = float(pm.BoxSize[0])

    if request.algorithm == 'FFTPower':
        def single(seed):
            c = _delta_c(pm, _uniform_pos(seed, npart, L), resampler,
                         npart)
            return _binned_power(pm, c, resampler, npart)

    elif request.algorithm == 'ConvolvedFFTPower':
        # FKP-style: data minus an independent synthetic randoms
        # realization (alpha = 1), monopole of the difference field
        def single(seed):
            data = _delta_c(pm, _uniform_pos(seed, npart, L),
                            resampler, npart)
            rand = _delta_c(pm, _uniform_pos(seed + 2 ** 20, npart, L),
                            resampler, npart)
            return _binned_power(pm, data - rand, resampler, npart)

    elif request.algorithm == 'Forward':
        # one field-level-inference sample: realize truth linear modes
        # from the seed, evolve through LPT + KDK PM to an observed
        # density, then take ONE preconditioned gradient step of the
        # Gaussian posterior from the zero initial guess — a full
        # forward+backward pipeline (the reverse-mode pricing branch
        # admission used).  Deliverable: binned P(k) of the recovered
        # linear modes — deterministic in the seed, shadow-verifiable
        # like any seeded request.
        import jax
        from ..forward import ForwardModel, binned_power
        from ..parallel.runtime import use_mesh

        # pin the build context to pm's mesh: on the batchable path pm
        # was built under use_mesh(None) and the model's lattices must
        # stay comm-less (plain ops) for vmap
        with use_mesh(pm.comm):
            model = ForwardModel(request.nmesh, request.npart,
                                 BoxSize=L,
                                 pm_steps=request.pm_steps or 5,
                                 dtype=request.dtype,
                                 resampler=resampler, comm=pm.comm)
        inv_noise = 10.0   # sigma = 0.1 in 1+delta units
        step = 0.05        # one fixed-size gradient step

        def single(seed):
            truth = model.lattice.generate_whitenoise(seed) * model.amp
            obs = model.density(truth)

            def loss(white):
                d = model.density(model.modes_from_white(white))
                r = (d - obs) * inv_noise
                return 0.5 * jnp.sum(r * r) \
                    + 0.5 * jnp.sum(white * white)

            g = jax.grad(loss)(model.white_guess())
            scale = jnp.max(jnp.abs(g))
            white = -step * g / jnp.maximum(scale, 1e-30)
            k, P, nm = binned_power(model.lattice,
                                    model.modes_from_white(white))
            return (k.astype(jnp.float32), P.astype(jnp.float32),
                    nm.astype(jnp.float32))

    elif request.algorithm == 'Bispectrum':
        # equilateral B(k, k, k) per unit-width shell via the
        # streaming Scoccimarro estimator (docs/BISPECTRUM.md): one
        # shell-filtered field resident at a time, so peak residency
        # stays under the memory_plan(workload='bispectrum') price.
        # The triangle-count normalization is seed-independent mesh
        # geometry — enumerated exactly on the host here and baked
        # into the program as constants.
        import numpy as np
        from ..algorithms.bispectrum import (_shell_edges2,
                                             shell_filtered_field)
        nbins = int(request.nbins or 4)
        nmesh = int(pm.Nmesh[0])
        edges2, kedges = _shell_edges2(nbins, pm.BoxSize)
        V = float(np.prod(pm.BoxSize))

        # ordered (q1, q2) pairs in shell b whose mod-N closure
        # q3 = -(q1 + q2) lands back in shell b — the same aliased
        # closure the mesh product sums over
        M = nbins + 1
        r = np.arange(-M, M + 1)
        g = np.stack(np.meshgrid(r, r, r, indexing='ij'),
                     axis=-1).reshape(-1, 3)
        isq = (g ** 2).sum(axis=1)
        T = np.zeros(nbins, dtype='f8')
        for b in range(nbins):
            qs = g[(isq >= edges2[b, 0]) & (isq < edges2[b, 1])]
            tot = 0
            for lo in range(0, qs.shape[0], 2048):
                q3 = (-(qs[lo:lo + 2048, None, :] + qs[None, :, :])
                      + nmesh // 2) % nmesh - nmesh // 2
                s3 = (q3 ** 2).sum(axis=-1)
                tot += int(((s3 >= edges2[b, 0])
                            & (s3 < edges2[b, 1])).sum())
            T[b] = float(tot)
        # B = V^2 * sum_x(d^3) / (Ntot * ntri); empty shells report 0
        # (finite, so shadow verification stays bit-comparable)
        norm = jnp.asarray(
            np.where(T > 0, V * V / np.where(T > 0, T, 1.0)
                     / float(pm.Ntot), 0.0), jnp.float32)
        ntri_c = jnp.asarray(T, jnp.float32)
        kmid = jnp.asarray(0.5 * (kedges[1:] + kedges[:-1]),
                           jnp.float32)
        e2 = [(int(edges2[b, 0]), int(edges2[b, 1]))
              for b in range(nbins)]

        def single(seed):
            c = _delta_c(pm, _uniform_pos(seed, npart, L), resampler,
                         npart)
            Bs = []
            for lo2, hi2 in e2:
                d = shell_filtered_field(pm, c, lo2, hi2)
                Bs.append(jnp.sum(d * d * d))
            B = jnp.stack(Bs).astype(jnp.float32) * norm
            return kmid, B, ntri_c

    else:  # FFTCorr: inverse transform of the 3-d power -> xi(r)
        def single(seed):
            import numpy as np
            c = _delta_c(pm, _uniform_pos(seed, npart, L), resampler,
                         npart)
            from ..ops.window import compensation_transfer
            w = pm.k_list(dtype=jnp.float32, circular=True)
            c = compensation_transfer(resampler, False)(w, c)
            p3c = (c * jnp.conj(c)).at[0, 0, 0].set(0.0)
            xi3 = pm.c2r(p3c.astype(c.dtype))
            # integer-lattice radial shells in real space (periodic
            # signed distance per axis)
            nmesh = int(pm.Nmesh[0])
            nbins = nmesh // 2
            ax = [jnp.asarray(np.minimum(np.arange(n),
                                         n - np.arange(n))
                              .astype('i4')).reshape(
                      [1 if i != j else -1 for j in range(3)])
                  for i, n in enumerate(int(v) for v in pm.Nmesh)]
            from ..ops.histogram import (lattice_shell_index,
                                         shell_sums)
            dsq = ax[0] ** 2 + ax[1] ** 2 + ax[2] ** 2
            S, Nm = shell_sums(lattice_shell_index(dsq, nbins), xi3,
                               nbins)
            x = jnp.asarray(np.arange(nbins, dtype='f4')) \
                * jnp.float32(L / nmesh)
            return x, S / jnp.maximum(Nm, 1.0), Nm

    return _rooted(single)


class Program(object):
    """One warm compiled analysis program, bound to one sub-mesh.

    ``batchable`` programs (1-device sub-meshes: plain jax ops, no
    shard_map) take a ``(B,)`` seed array and vmap over realizations;
    multi-device programs take one seed per launch.
    """

    __slots__ = ('key', 'label', 'mesh', 'batchable', '_fn', '_device',
                 'data', '_pm', '_resampler')

    def __init__(self, request, mesh):
        import jax
        from ..pmesh import ParticleMesh
        self.key = request.program_key(mesh_size(mesh))
        self.label = program_label(request)
        self.mesh = mesh
        self.data = getattr(request, 'data_ref', None) is not None
        self._pm = None
        self._resampler = request.resampler
        if self.data:
            # data programs are never vmap-batched: their input is a
            # streamed catalog, not a seed array.  The pm is kept — the
            # eager ingest paints on it; only field -> spectrum is jit.
            self.batchable = False
            self._device = None
            pm = ParticleMesh(request.nmesh, BOX_SIZE, request.dtype,
                              comm=mesh)
            self._pm = pm
            # memoized-by-ProgramCache lifetime (see below)
            # nbkl: disable=NBK202
            self._fn = instrumented_jit(_build_data(request, pm),
                                        label=self.label)
            return
        self.batchable = mesh_size(mesh) == 1
        if self.batchable:
            # comm-less plain-ops form — the ONLY form vmap can batch
            # (shard_map is not vmappable); placement happens by
            # committing the seed input to the sub-mesh's one device
            self._device = mesh.devices.item() if mesh is not None \
                else None
            from ..parallel.runtime import use_mesh
            with use_mesh(None):
                pm = ParticleMesh(request.nmesh, BOX_SIZE,
                                  request.dtype)
            single = _build_single(request, pm)
            # ProgramCache memoizes Program per (program_key, worker,
            # opts) — __init__ runs once per cache entry, so this jit
            # cache is long-lived, not per-call
            # nbkl: disable=NBK202
            self._fn = instrumented_jit(jax.vmap(single),
                                        label=self.label)
        else:
            self._device = None
            pm = ParticleMesh(request.nmesh, BOX_SIZE, request.dtype,
                              comm=mesh)
            # same memoized-by-ProgramCache lifetime as above
            # nbkl: disable=NBK202
            self._fn = instrumented_jit(_build_single(request, pm),
                                        label=self.label)

    def run(self, seeds):
        """Execute for a list of seeds; returns per-seed
        (x, y, nmodes) numpy triples.  Multi-device programs run the
        seeds sequentially (their parallelism is the mesh); 1-device
        programs run them as one vmapped launch."""
        import jax
        import jax.numpy as jnp
        if self.batchable:
            with scope('serve.launch'):
                arr = jnp.asarray(list(seeds), jnp.uint32)
                if self._device is not None:
                    arr = jax.device_put(arr, self._device)
                out = self._fn(arr)
            x, y, nm = fetch(out, 'serve.result')
            return [(x[i], y[i], nm[i]) for i in range(len(seeds))]
        out = []
        from ..parallel.runtime import use_mesh
        with use_mesh(self.mesh):
            for s in seeds:
                with scope('serve.launch'):
                    res = self._fn(jnp.uint32(s))
                out.append(tuple(fetch(res, 'serve.result')))
        return out

    def run_data(self, ref, cache=None, fits=None, overlap=None):
        """Execute a ``data_ref`` program: stream (or cache-hit) the
        catalog onto this sub-mesh, then run the warm field->spectrum
        executable.  Returns ``([(x, y, nmodes)], ingest_stats)`` —
        the stats carry cache_hit / bytes / seconds so the server can
        expose ingestion throughput per request."""
        from ..ingest.stream import ingest_catalog
        from ..parallel.runtime import use_mesh
        with use_mesh(self.mesh):
            field, _, stats = ingest_catalog(
                ref, self._pm, resampler=self._resampler, cache=cache,
                fits=fits, overlap=overlap)
            with scope('serve.launch'):
                res = self._fn(field)
            out = tuple(fetch(res, 'serve.result'))
        return [out], stats


class ProgramCache(object):
    """(program key, worker) -> warm :class:`Program`.  Its counters
    are exported: ``serve.program.build`` / ``.reuse`` tell the doctor
    how warm the server is running."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs = {}

    def get(self, request, mesh, worker, opts=None):
        """The warm program for (request shape, worker), building it
        on first use.  ``opts`` (request-scoped option overrides) are
        part of the key: jit never sees Python option globals, so a
        degraded run traced under smaller chunks must NOT share an
        executable with the clean-option trace."""
        key = (request.program_key(mesh_size(mesh)), int(worker),
               tuple(sorted((opts or {}).items())))
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                counter('serve.program.reuse').add(1)
                return prog
            # build under the lock: two threads must not race the
            # same (key, worker) into two instrumented wrappers
            prog = Program(request, mesh)
            self._programs[key] = prog
        counter('serve.program.build').add(1)
        return prog

    def __len__(self):
        with self._lock:
            return len(self._programs)


def affinity(request, ndevices, n_workers):
    """The worker whose cache this request's program warms: stable
    across the request stream (hash of the program key), so identical
    shapes land where their executable already lives.  ``data_ref``
    requests salt the hash with the catalog path: repeat requests
    against one survey land on the worker whose CatalogCache already
    holds it (the cache-hit-to-paint route), while distinct surveys of
    the same shape spread."""
    key = request.program_key(ndevices)
    if getattr(request, 'data_ref', None) is not None:
        key = key + (request.data_ref.get('path'),)
    return hash(key) % max(n_workers, 1)


def rank(ticket):
    """Sort key: higher priority first, then earliest deadline, then
    submission order."""
    return (-ticket.request.priority, ticket.deadline_at, ticket.seq)
