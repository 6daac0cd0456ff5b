"""The declarative analysis request.

A request names WHAT to compute — algorithm, mesh resolution, particle
count, dtype, paint options — plus the two scheduling facts the server
needs: a deadline and a priority.  It carries no arrays: synthetic
realizations are generated on-device from ``seed`` (the
device-count-invariant (seed, index) discipline of the mock pipeline),
so a request is a few hundred bytes however large the analysis.
Real-survey requests carry a ``data_ref`` instead — a path + format +
column map the ingestion plane (:mod:`nbodykit_tpu.ingest`) streams
onto the sub-mesh — and are exactly as small.

Two identity keys, deliberately distinct:

- :meth:`AnalysisRequest.shape_class` — the logarithmic bucket
  (``mesh64-part1e5``, :func:`shape_class`): the granularity at which
  serving statistics are aggregated and compile counters labelled.
  Many concrete shapes share one class.
- :meth:`AnalysisRequest.program_key` — the exact compiled-program
  identity (algorithm, nmesh, npart, dtype, resampler, device count):
  two requests with equal program keys can share one warm XLA
  executable (and, on a single-device sub-mesh, one vmap-batched
  launch — :mod:`.batching`).
"""

import itertools
import math

ALGORITHMS = ('FFTPower', 'ConvolvedFFTPower', 'FFTCorr', 'Forward',
              'Bispectrum')

# 'bf16' requests a half-width storage mesh (NBK503 admission prices
# it at 2 bytes/cell through pmesh.memory_plan; compute stays f32)
_DTYPES = ('f4', 'f8', 'bf16')

_ids = itertools.count(1)


def shape_class(nmesh=None, npart=None):
    """The logarithmic shape bucket for (nmesh, npart):
    ``mesh512-part1e7`` / ``mesh512`` / ``part1e7``.  Nmesh buckets to
    the nearest power of two, Npart to the nearest decade."""
    parts = []
    if nmesh:
        parts.append('mesh%d' % (1 << max(0, int(round(
            math.log2(float(nmesh)))))))
    if npart:
        parts.append('part1e%d' % max(0, int(round(
            math.log10(float(npart))))))
    if not parts:
        raise ValueError('shape_class needs nmesh and/or npart')
    return '-'.join(parts)


class AnalysisRequest(object):
    """One unit of multi-tenant work.

    Parameters
    ----------
    algorithm : 'FFTPower', 'ConvolvedFFTPower', 'FFTCorr',
        'Forward' (a differentiable LPT+PM forward-model sample — one
        full forward+backward pipeline, docs/FORWARD.md) or
        'Bispectrum' (equilateral B(k,k,k) shells via the streaming
        Scoccimarro estimator, docs/BISPECTRUM.md)
    nmesh : int — cells per side of the analysis mesh
    npart : int — synthetic particles to realize from ``seed``
        (Forward: must be a cube ng^3; the particle lattice is a mesh)
    pm_steps : int or None — Forward only: KDK steps from a_start to
        a_end (defaults to 5).  Part of :meth:`program_key` — the step
        schedule is unrolled into the compiled program.
    nbins : int or None — Bispectrum only: unit-width k shells
        (defaults to 4; admission prices the 3-field streaming peak
        through ``memory_plan(workload='bispectrum', nbins=...)``).
        Part of :meth:`program_key` — the shell count sets the result
        shape baked into the executable.
    dtype : 'f4', 'f8' or 'bf16' — mesh storage dtype ('bf16' halves
        the admission-priced mesh bytes; compute stays f32)
    resampler : paint window ('nnb'/'cic'/'tsc'/'pcs')
    paint_method : the paint kernel admission prices, or None for
        the ``paint_method`` option as it stands
    seed : int — realization seed (device-count invariant)
    deadline_s : float — seconds after submission by which the result
        must be DELIVERED; the queue evicts (with a structured
        verdict) rather than run a request it can no longer honor
    priority : int — larger is more urgent; ties broken by earliest
        deadline, then submission order
    request_id : stable external id; autogenerated ``req-NNNNNN``
        when omitted
    data_ref : dict or None — ``{'path', 'format', 'columns',
        'options'}`` pointer to an on-disk catalog
        (:class:`nbodykit_tpu.ingest.DataRef`).  When given, the
        catalog replaces the seeded realization: admission fills
        ``npart`` from the file's row count and prices
        ingestion+paint+FFT jointly, the scheduler streams (or
        cache-hits) the catalog onto the sub-mesh, and ``seed`` is
        ignored.  FFTPower only; never batched.
    verify : bool — request tier-1 shadow verification
        (docs/INTEGRITY.md): the completed result is re-executed on a
        different sub-mesh worker and compared (bit-identical when no
        lossy compression is in play, margin-gated otherwise) before
        delivery.  Runtime behavior only — NOT part of
        :meth:`program_key`, so a verified request shares the warm
        executable with its unverified twins.  Seeded requests only;
        never batched.
    """

    __slots__ = ('request_id', 'algorithm', 'nmesh', 'npart', 'dtype',
                 'resampler', 'paint_method', 'seed', 'deadline_s',
                 'priority', 'data_ref', 'verify', 'pm_steps', 'nbins')

    def __init__(self, algorithm='FFTPower', nmesh=64, npart=100000,
                 dtype='f4', resampler='cic', paint_method=None,
                 seed=0, deadline_s=60.0, priority=0, request_id=None,
                 data_ref=None, verify=False, pm_steps=None,
                 nbins=None):
        if algorithm not in ALGORITHMS:
            raise ValueError('algorithm must be one of %s (got %r)'
                             % (', '.join(ALGORITHMS), algorithm))
        if str(dtype) not in _DTYPES:
            raise ValueError('dtype must be f4, f8 or bf16 (got %r)'
                             % dtype)
        nmesh, npart = int(nmesh), int(npart)
        if nmesh < 4:
            raise ValueError('nmesh must be >= 4 (got %d)' % nmesh)
        if npart < 1:
            raise ValueError('npart must be >= 1 (got %d)' % npart)
        if float(deadline_s) <= 0:
            raise ValueError('deadline_s must be positive (got %r)'
                             % deadline_s)
        self.request_id = str(request_id) if request_id \
            else 'req-%06d' % next(_ids)
        self.algorithm = algorithm
        self.nmesh = nmesh
        self.npart = npart
        self.dtype = str(dtype)
        self.resampler = str(resampler)
        self.paint_method = paint_method
        self.seed = int(seed)
        self.deadline_s = float(deadline_s)
        self.priority = int(priority)
        if data_ref is not None:
            # normalize (and validate format/columns) through DataRef;
            # stored as the plain dict so to_dict stays JSON-clean
            from ..ingest.stream import DataRef
            data_ref = DataRef.from_dict(data_ref).to_dict()
            if algorithm != 'FFTPower':
                raise ValueError('data_ref requests support FFTPower '
                                 'only (got %r)' % algorithm)
            if verify:
                raise ValueError('verify=True requires a seeded '
                                 'request (data_ref shadow '
                                 're-ingestion is not supported)')
        self.data_ref = data_ref
        self.verify = bool(verify)
        if algorithm == 'Forward':
            if data_ref is not None:
                raise ValueError('Forward requests are seeded only '
                                 '(no data_ref)')
            pm_steps = 5 if pm_steps is None else int(pm_steps)
            if pm_steps < 1:
                raise ValueError('pm_steps must be >= 1 (got %d)'
                                 % pm_steps)
            ng = int(round(float(npart) ** (1.0 / 3.0)))
            if ng ** 3 != npart:
                raise ValueError('Forward npart must be a cube ng^3 '
                                 '(got %d); the particle lattice is a '
                                 'mesh' % npart)
        elif pm_steps is not None:
            raise ValueError('pm_steps applies to Forward requests '
                             'only (got algorithm=%r)' % algorithm)
        self.pm_steps = pm_steps
        if algorithm == 'Bispectrum':
            if data_ref is not None:
                raise ValueError('Bispectrum requests are seeded only '
                                 '(no data_ref)')
            nbins = 4 if nbins is None else int(nbins)
            if nbins < 1:
                raise ValueError('nbins must be >= 1 (got %d)' % nbins)
            if nbins + 1 > nmesh // 2:
                raise ValueError('nbins=%d needs lattice modes up to '
                                 '|q| < %d, beyond the nmesh=%d '
                                 'Nyquist (%d)' % (nbins, nbins + 1,
                                                   nmesh, nmesh // 2))
        elif nbins is not None:
            raise ValueError('nbins applies to Bispectrum requests '
                             'only (got algorithm=%r)' % algorithm)
        self.nbins = nbins

    @property
    def shape_class(self):
        """The bucket this request's shape falls in."""
        return shape_class(nmesh=self.nmesh, npart=self.npart)

    def program_key(self, ndevices=1):
        """The exact compiled-program identity on an ``ndevices``
        sub-mesh.  Requests with equal program keys share one warm
        executable; ``seed``/deadline/priority are runtime inputs,
        never part of the key."""
        key = (self.algorithm, self.nmesh, self.npart, self.dtype,
               self.resampler, self.paint_method, int(ndevices))
        if self.pm_steps is not None:
            # the KDK step count is unrolled into the jaxpr — a
            # different step schedule IS a different executable
            key += (self.pm_steps,)
        if self.nbins is not None:
            # the shell count sets the result shape baked into the
            # compiled bispectrum program
            key += (self.nbins,)
        if self.data_ref is not None:
            # a data program's jitted stage (field -> spectrum) is a
            # different executable from the seeded realize+paint+fft
            # pipeline; the path is NOT part of the key — one program
            # serves every survey of this shape
            key += ('data',)
        return key

    def to_dict(self):
        return {k: getattr(self, k) for k in self.__slots__}

    @classmethod
    def from_dict(cls, d):
        return cls(**dict(d))

    def __repr__(self):
        return ('AnalysisRequest(%s %s mesh%d/part%d %s prio=%d '
                'deadline=%.1fs)'
                % (self.request_id, self.algorithm, self.nmesh,
                   self.npart, self.dtype, self.priority,
                   self.deadline_s))
