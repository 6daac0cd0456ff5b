"""Admission control: price first, schedule second.

Every request is priced through :func:`nbodykit_tpu.pmesh.memory_plan`
against its target sub-mesh's HBM budget (0.85 x ``hbm_bytes`` — the
same 15% allocator margin the plan itself applies) BEFORE it can touch
the queue.  Three outcomes:

``admit``
    the plan fits as requested — no configuration changes.
``degrade``
    the plan fits only after stepping the request down the resilience
    degradation ladder (:func:`nbodykit_tpu.resilience.scoped_ladder`
    — the per-request form that writes into a private options dict,
    never the process-wide options).  The accumulated option overrides
    ride on the decision and are applied with
    :func:`nbodykit_tpu.option_scope` around just this request's
    execution.
``reject``
    no rung makes it fit (or the geometry is impossible on the
    sub-mesh).  The decision carries a STRUCTURED reason — machine
    shape, never a bare string — quoting the peak and budget it was
    judged by, so a 2048^3 request can never OOM a chip that a
    thousand small tenants are sharing, and the caller learns exactly
    why and by how much.
"""

from .. import _global_options
from ..pmesh import memory_plan

# decision states
ADMIT = 'admit'
DEGRADE = 'degrade'
REJECT = 'reject'


class AdmissionDecision(object):
    """The priced verdict for one request on one sub-mesh."""

    __slots__ = ('status', 'request_id', 'plan', 'reason', 'options',
                 'rungs')

    def __init__(self, status, request_id, plan=None, reason=None,
                 options=None, rungs=None):
        self.status = status
        self.request_id = request_id
        self.plan = plan
        self.reason = reason
        self.options = dict(options or {})
        self.rungs = list(rungs or [])

    @property
    def admitted(self):
        return self.status != REJECT

    def to_dict(self):
        out = {'status': self.status, 'request_id': self.request_id,
               'options': dict(self.options),
               'rungs': [r[0] for r in self.rungs]}
        if self.reason is not None:
            out['reason'] = dict(self.reason)
        if self.plan is not None:
            out['peak_bytes'] = self.plan.get('peak_bytes')
            out['budget_bytes'] = self.plan.get('budget_bytes')
        return out

    def __repr__(self):
        return 'AdmissionDecision(%s %s%s)' % (
            self.status, self.request_id,
            ' %s' % self.reason.get('code') if self.reason else '')


def _plan(request, ndevices, hbm_bytes, paint_chunk=None,
          catalog_bytes=None):
    method = request.paint_method
    if method is None:
        # price what executes: the option as it stands, and for a
        # Forward request the kernel reverse mode takes in its place
        method = _global_options['paint_method']
        if request.algorithm == 'Forward':
            from ..forward.adjoint import grad_paint_method
            method = grad_paint_method(method)
    chunk_rows = None
    if getattr(request, 'data_ref', None) is not None:
        # a data_ref request streams+paints+transforms jointly: price
        # the resident catalog and the double-buffered staging chunks
        # alongside the mesh pipeline
        from ..ingest.stream import resolve_chunk_rows
        chunk_rows = resolve_chunk_rows()
    # a Forward request is a forward+BACKWARD pipeline: price it with
    # the reverse-mode branch (per-step residuals held live) instead
    # of the one-shot fftpower peak; a Bispectrum request is priced by
    # its streaming 3-field shell peak (the serve path always runs the
    # FFT estimator — the direct path is a library concern)
    workload = {'Forward': 'forward',
                'Bispectrum': 'bispectrum'}.get(request.algorithm,
                                                'fftpower')
    return memory_plan(request.nmesh, request.npart,
                       ndevices=ndevices, dtype=request.dtype,
                       resampler=request.resampler,
                       paint_method=method, paint_chunk=paint_chunk,
                       hbm_bytes=hbm_bytes,
                       ingest_chunk_rows=chunk_rows,
                       catalog_bytes=catalog_bytes,
                       workload=workload,
                       pm_steps=getattr(request, 'pm_steps', None),
                       nbins=getattr(request, 'nbins', None))


def catalog_fits_fn(request, ndevices, hbm_bytes):
    """The catalog-cache eviction predicate for one admitted data_ref
    request: ``fits(total_resident_bytes)`` is this request's
    admission plan re-priced at a candidate cache residency — the
    scheduler hands it to :meth:`CatalogCache.ensure_room` so LRU
    entries fall out exactly when memory_plan says the joint
    ingestion+paint+FFT peak would not fit beside them."""
    def fits(resident_bytes):
        return bool(_plan(request, ndevices, hbm_bytes,
                          catalog_bytes=resident_bytes)['fits'])
    return fits


def admit(request, ndevices, hbm_bytes):
    """Price ``request`` for an ``ndevices`` sub-mesh and decide.

    Geometry that cannot run at all (Nmesh not divisible by the
    sub-mesh, resampler support wider than a slab) rejects with
    ``code='indivisible'``; an over-budget plan walks the scoped
    degradation ladder and either admits degraded or rejects with
    ``code='over_budget'`` quoting every rung it tried.
    """
    ndevices = max(int(ndevices), 1)
    if getattr(request, 'data_ref', None) is not None:
        # open the ref NOW: an unreadable path must reject with a
        # structured verdict at admission, never fail a worker later —
        # and the file's row count becomes the npart everything else
        # (pricing, shape class, program key) is judged by
        from ..ingest.stream import IngestError, probe_ref
        try:
            info = probe_ref(request.data_ref)
        except IngestError as e:
            return AdmissionDecision(REJECT, request.request_id,
                                     reason=e.to_reason())
        if info['nrows'] < 1:
            return AdmissionDecision(REJECT, request.request_id,
                                     reason={
                'code': 'unreadable_data_ref',
                'path': request.data_ref.get('path'),
                'detail': 'catalog has zero rows'})
        request.npart = int(info['nrows'])
    if request.nmesh % ndevices:
        return AdmissionDecision(REJECT, request.request_id, reason={
            'code': 'indivisible', 'nmesh': request.nmesh,
            'ndevices': ndevices,
            'detail': 'Nmesh must be divisible by the sub-mesh size'})
    from ..ops.window import window_support
    if window_support(request.resampler) > request.nmesh // ndevices:
        return AdmissionDecision(REJECT, request.request_id, reason={
            'code': 'indivisible', 'nmesh': request.nmesh,
            'ndevices': ndevices, 'resampler': request.resampler,
            'detail': 'resampler support exceeds the per-device slab'})
    if request.algorithm == 'Forward':
        # the particle lattice is a second mesh (ng^3 = npart) and
        # must shard over the same sub-mesh
        ng = int(round(float(request.npart) ** (1.0 / 3.0)))
        if ng % ndevices:
            return AdmissionDecision(REJECT, request.request_id,
                                     reason={
                'code': 'indivisible', 'npart': request.npart,
                'ndevices': ndevices,
                'detail': 'Forward particle lattice ng=%d must be '
                          'divisible by the sub-mesh size' % ng})

    plan = _plan(request, ndevices, hbm_bytes)
    if plan['fits']:
        return AdmissionDecision(ADMIT, request.request_id, plan=plan)

    # over budget as requested: step the request-scoped ladder until
    # the re-priced plan fits or the rungs run out
    from ..resilience import scoped_ladder
    opts = {}
    ladder = scoped_ladder(opts)
    rungs = []
    while True:
        rung = ladder.step()
        if rung is None:
            break
        rungs.append(rung)
        plan2 = _plan(request, ndevices, hbm_bytes,
                      paint_chunk=opts.get('paint_chunk_size'))
        if plan2['fits']:
            return AdmissionDecision(DEGRADE, request.request_id,
                                     plan=plan2, options=opts,
                                     rungs=rungs)
    return AdmissionDecision(REJECT, request.request_id, plan=plan,
                             reason={
        'code': 'over_budget',
        'peak_bytes': int(plan['peak_bytes']),
        'budget_bytes': int(plan['budget_bytes']),
        'hbm_bytes': int(hbm_bytes),
        'nmesh': request.nmesh, 'npart': request.npart,
        'ndevices': ndevices,
        'rungs_tried': [r[0] for r in rungs],
        'detail': 'peak exceeds 0.85*HBM on every degradation rung'})
